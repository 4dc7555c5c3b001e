"""Record the small trace the reductions are tested on (run on the chip).

    chiprun -- python3 -m benchmark.record_fixture

Four runs of one small jitted program under a host annotation, traced
with the harness's own profiler options and written in the plain form
`benchmark.trace_reduce` works on, to
chiprun_out/trace_v5e.json (copied to tests/benchmark/fixtures by hand).
"""

from __future__ import annotations

import json
import os
import shutil
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce

    def fixture_step(x, w):
        for _ in range(3):
            x = jnp.tanh(x @ w)
        return x

    step = jax.jit(fixture_step)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16) * 1e-3
    step(x, w).block_until_ready()
    directory = os.path.join(ROOT, ".benchmark_trace", "fixture")
    shutil.rmtree(directory, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=opts)
    with jax.profiler.TraceAnnotation("benchmark.fixture"):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("benchmark.fixture.wait"):
                time.sleep(0.002)
            x = step(x, w)
        x.block_until_ready()
    jax.profiler.stop_trace()
    trace = trace_reduce.load(directory)
    print(json.dumps([[p["name"], [[ln["name"], len(ln["events"])]
                                   for ln in p["lines"]]]
                      for p in trace["planes"]]))
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "trace_v5e.json"), "w") as f:
        json.dump(trace, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
