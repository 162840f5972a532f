#!/usr/bin/env python3
"""Record the small trace that ``test_trace.py`` reads, on one chip.

    python chipbench/tests/record_trace.py [OUT_DIR]

A two-layer MLP's training step (value_and_grad, then a sign update
outside the gradient), traced over three steps inside the benchmark's
host spans. Writes ``small.xplane.pb`` and the step's optimized HLO
``small.hlo.txt`` to OUT_DIR (default ``chipbench/tests/data/trace``,
where the test reads them), and prints each plane and line
of the trace with its first events, for reading by hand.
"""
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
OUT = HERE / "data" / "trace"


def main() -> int:
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else OUT
    sys.path.insert(0, str(HERE.parents[1]))
    import jax
    import jax.numpy as jnp

    from chipbench.yardstick import trace as ytrace
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2

    def loss(p, x):
        h = jnp.tanh(x @ p["w1"])
        return jnp.mean(jnp.square(h @ p["w2"]))

    def step(p, x):
        l, g = jax.value_and_grad(loss)(p, x)
        return {k: p[k] - 1e-3 * jnp.sign(g[k]) for k in p}, l

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    p = {"w1": jax.random.normal(k1, (1024, 2048), jnp.bfloat16),
         "w2": jax.random.normal(k2, (2048, 1024), jnp.bfloat16)}
    x = jax.random.normal(k3, (4096, 1024), jnp.bfloat16)
    fn = jax.jit(step, donate_argnums=(0,))
    hlo = fn.lower(p, x).compile().as_text()
    p, l = fn(p, x)
    jax.block_until_ready(l)
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.dispatch"):
                p, l = fn(p, x)
            with jax.profiler.TraceAnnotation("chipbench.sync"):
                l.block_until_ready()
    jax.profiler.stop_trace()
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(ytrace.find_xplane(tmp), out / "small.xplane.pb")
    (out / "small.hlo.txt").write_text(hlo)

    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(out / "small.xplane.pb"))
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r} events={len(evs)}")
            for ev in evs[:3]:
                print(f"    {ev.name[:200]!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={dict(ev.stats)}")
    red = ytrace.reduce_trace(str(out / "small.xplane.pb"), hlo, n_devices=1)
    print("window_s", red.window_s, "busy_s", red.busy_s(),
          {c: red.class_s(c) for c in ("model", "optimizer", "collective",
                                        "other")})
    print("top", red.top_ops(5))
    print("gaps", red.idle_gaps(5))
    return 0


if __name__ == "__main__":
    sys.exit(main())
