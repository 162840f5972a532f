#!/usr/bin/env python3
"""Chip benchmark of the signSGD majority-vote trainer: one cell, one run.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs from the root of a checkout, in one process that holds the cell's
chips. Without a TPU, or with fewer chips than the cell asks for, it
exits with code 2 before any phase and prints no result. The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
with the reference beside its limit (also the last lines of standard
error). JAX's compilation cache is kept in ``<checkout>/.jax_cache``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    from chipbench import harness
    harness.use_compile_cache(ROOT)
    cell = harness.load_cell(ROOT, args.workload)
    if len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    out = harness.run(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START, devices)
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
