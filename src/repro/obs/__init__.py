"""Unified telemetry: spans, counters and step records (DESIGN.md §13).

One subsystem behind every quantitative claim in the repo — the exact
wire/launch/chunk accounting the benches assert against, host-side span
timing for the schedule walks, and per-step structured records unifying
`WireReport` + `StepTrace`, all emitted to a versioned JSONL sink that
`scripts/trace_report.py` aggregates. Import as ``from repro import
obs`` (or ``from repro.obs import recorder as obs`` inside hot modules).
"""
from repro.obs.recorder import (COUNTERS, CounterRegistry, Recorder,
                                SCHEMA_VERSION, TraceRecorder,
                                activate_trace, add_trace_arg,
                                emit_bench_json, finish_trace,
                                get_recorder, install_compile_watch,
                                jit_counters_at, read_trace, recording,
                                set_recorder, warn_deprecated)

__all__ = [
    "COUNTERS", "CounterRegistry", "Recorder", "SCHEMA_VERSION",
    "TraceRecorder", "activate_trace", "add_trace_arg",
    "emit_bench_json", "finish_trace", "get_recorder",
    "install_compile_watch", "jit_counters_at", "read_trace",
    "recording", "set_recorder",
    "warn_deprecated",
]
