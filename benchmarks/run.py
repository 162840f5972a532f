"""Benchmark driver — one module per paper table/figure.

Prints ``name,value,derived`` CSV rows:
  fig1  toy-quadratic convergence incl. adversaries   (bench_convergence)
  fig2  gradient-noise unimodality/symmetry on an LM  (bench_noise)
  fig3  SNR vs the critical line                      (bench_noise)
  fig4  Byzantine training robustness sweep           (bench_robustness)
  attacks  adaptive-attack breaking points vs the
           Theorem 2 bound, defense-aware degradation (bench_attacks)
  fig5  communication volume/time vs dense all-reduce (bench_comm)
  fig6  end-to-end step-time speedup model            (bench_speedup)
  codecs  codec frontier: convergence vs bits/param   (bench_codecs)
  federated  streamed population engine: sampling,
             churn, weighted votes, 100k-client bound  (bench_federated)
  serving  continuous vs static batching, hot swap,
           one-compile + bit-identity gates            (bench_serving)
  roofline  per-cell terms from the dry-run artifacts (roofline)

``--emit-json FILE`` additionally writes every produced row as JSON —
the machine-readable bench baseline (e.g. ``--only codecs --emit-json
BENCH_codecs.json`` seeds the codec trajectory; the CI codec-smoke stage
writes the same file via ``bench_codecs --smoke``).

Run:  PYTHONPATH=src python -m benchmarks.run [--only fig1,fig5]
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache
from repro.obs import recorder as obs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module keys "
                         "(fig1..fig6,attacks,codecs,vote_plan,federated,"
                         "serving,roofline)")
    ap.add_argument("--list", action="store_true",
                    help="enumerate the registered suites (key, module, "
                         "one-line description) and exit")
    ap.add_argument("--emit-json", dest="json_out", default=None,
                    help="also write the produced rows to this JSON file")
    obs.add_trace_arg(ap)
    args = ap.parse_args()
    enable_compile_cache()
    rec = obs.activate_trace(args)

    from benchmarks import (bench_attacks, bench_codecs, bench_comm,
                            bench_convergence, bench_federated, bench_noise,
                            bench_robustness, bench_serving, bench_speedup,
                            bench_vote_plan, roofline)
    suites = {
        "fig1": bench_convergence, "fig2": bench_noise, "fig3": bench_noise,
        "fig4": bench_robustness, "fig5": bench_comm, "fig6": bench_speedup,
        "attacks": bench_attacks, "codecs": bench_codecs,
        "vote_plan": bench_vote_plan,
        "federated": bench_federated, "serving": bench_serving,
        "roofline": roofline,
    }
    if args.list:
        for key, mod in suites.items():
            desc = (mod.__doc__ or "").strip().splitlines()[0]
            print(f"{key:<10s} {mod.__name__:<28s} {desc}")
        return
    only = set(args.only.split(",")) if args.only else None
    seen_mods = set()
    print("name,value,derived")
    failures = 0
    collected = []
    for key, mod in suites.items():
        if only and key not in only:
            continue
        if id(mod) in seen_mods:
            continue
        seen_mods.add(id(mod))
        try:
            with obs.get_recorder().span("bench.suite", key=key):
                suite_rows = mod.rows()
            for name, value, derived in suite_rows:
                print(f"{name},{value:.6g},{derived}", flush=True)
                collected.append({"name": name, "value": value,
                                  "derived": derived})
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{key}/ERROR,-1,see stderr", flush=True)
            # the JSON must carry the failure too — a partially failed
            # sweep must not emit a healthy-looking baseline
            collected.append({"name": f"{key}/ERROR", "value": -1.0,
                              "derived": "see stderr"})
    if args.json_out:
        # the ONE shared bench-JSON writer (same schema every bench
        # emits; gated by scripts/perf_gate.py)
        obs.emit_bench_json(collected, args.json_out)
        print(f"# wrote {args.json_out}", flush=True)
    obs.finish_trace(rec)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
