#!/usr/bin/env python3
"""The readings that limits are set from, many seeds in one process.

    python chipbench/readings.py --workload <cell> --seeds 1 2 3 \
        [--control 3] [--reference-wire allgather_1bit] [--fault half_batch]

Per seed: the program's check steps (the same three steps, through the
same call, as a benchmark run's set-up) and the float32 reference, and
every number ``yardstick/compare.py`` computes. With ``--control N`` the
first N seeds also read the control: the reference at ``fp8`` (or at
``--control-prec``) in the program's place. With ``--reference-wire W``
those N seeds also read the program against the float32 reference voting
by wire W's rule in place of the cell's (another tie rule). With
``--fault NAME`` the program runs with that fault of
``chipbench/tests/faults.py`` planted. No window is timed. The last line
is one JSON object of all readings. Needs the cell's chips.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import sys
from pathlib import Path
from typing import Dict

ROOT = Path(__file__).resolve().parents[1]


def control_readings(root: Path, workload: str, seed: int, devices,
                     prec: str = "fp8"):
    """(numbers, limits) of the control: the reference at `prec` in the
    program's place, against the float32 reference."""
    from chipbench import harness
    from chipbench.reference import common
    from chipbench.yardstick import compare

    cell = harness.load_cell(root, workload)
    devices = list(devices)[:cell.chips]
    c = harness.reference_config(cell)
    family = importlib.import_module(f"chipbench.reference.{c['reference']}")
    gen = harness.tokens(cell, c, seed)
    batches = [gen.batch(s) for s in range(harness.CHECK_STEPS)]
    low = common.run(family, c, prec, devices, seed, batches, 1)
    ref = common.run(family, c, "f32", devices, seed, batches, 1)
    return compare.numbers(low, ref), cell.limits


def wire_readings(prog: Dict, family, c: Dict, wire: str, devices,
                  seed: int, batches) -> Dict[str, float]:
    """The numbers of the program's readings `prog` against the float32
    reference voting by `wire`'s rule in place of the cell's."""
    from chipbench.reference import common
    from chipbench.yardstick import compare
    ref = common.run(family, {**c, "vote_strategy": wire}, "f32", devices,
                     seed, batches, 1)
    return compare.numbers(prog, ref)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--control-prec", default="fp8",
                    help="the control's precision (reference/common.mm)")
    ap.add_argument("--reference-wire", default=None,
                    help="also read the program against the reference "
                         "voting by this wire (reference/common.WIRES)")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return 2
    from chipbench import harness
    harness.use_compile_cache(ROOT)
    from chipbench.reference import common
    from chipbench.tests import faults
    from chipbench.yardstick import compare

    cell = harness.load_cell(ROOT, args.workload)
    plant = (faults.FAULTS[args.fault] if args.fault
             else contextlib.nullcontext)
    with plant():
        prog = harness.Program(cell, jax.devices())
    out = {"program": {}, "control": {}, "reference_wire": {}}
    for i, seed in enumerate(args.seeds):
        gen = prog.tokens(seed)
        with plant():
            params, opt_state = prog.start(seed)
            params, opt_state, mine = prog.check_steps(params, opt_state,
                                                       gen, seed)
        del params, opt_state
        batches = [gen.batch(s) for s in range(harness.CHECK_STEPS)]
        ref = common.run(prog.family, prog.c, "f32", prog.devices, seed,
                         batches, 1)
        nums = compare.numbers(mine, ref)
        out["program"][seed] = nums
        print(f"program seed={seed} " + " ".join(
            f"{k}={v!r}" for k, v in nums.items()), flush=True)
        harness.log_readings(mine, ref, nums)
        if i < args.control:
            low = common.run(prog.family, prog.c, args.control_prec,
                             prog.devices, seed, batches, 1)
            nums = compare.numbers(low, ref)
            out["control"][seed] = nums
            print(f"control seed={seed} " + " ".join(
                f"{k}={v!r}" for k, v in nums.items()), flush=True)
            if args.reference_wire:
                nums = wire_readings(mine, prog.family, prog.c,
                                     args.reference_wire, prog.devices, seed,
                                     batches)
                out["reference_wire"][seed] = nums
                print(f"reference_wire={args.reference_wire} seed={seed} "
                      + " ".join(f"{k}={v!r}" for k, v in nums.items()),
                      flush=True)
    print(json.dumps({"workload": args.workload, "fault": args.fault,
                      "wire": args.reference_wire, **out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
