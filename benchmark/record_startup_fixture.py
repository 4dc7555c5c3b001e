"""Record the fixture the start-up readers are tested on (run on the chip).

    chiprun -- python3 -m benchmark.record_startup_fixture

One pretrain cell and one serve cell at their rehearsal sizes, each run
twice as `benchmark.run --rehearse --trace 1` runs it, in a process of
its own, on one new compile cache: the first run compiles, the second
loads. Of each run it keeps the start-up collector's records
(`proteinbert_tpu.obs.tracing.startup_spans()`), the
`tracing.program_scopes` spans of the recorder, and the run's own result
line. Written to chiprun_out/startup_v5e.json (copied to
tests/benchmark/fixtures by hand). This parent never imports jax: a
process that has touched it holds the chip.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = ("pretrain-base-dense", "serve-base-sat")
SEED = 2147483659
MARK = "startup fixture: "


def child(cell: str) -> int:
    """One traced rehearsal of `cell`, then what the spine kept of it."""
    import contextlib
    import io

    from benchmark import run as harness
    from benchmark import startup_readers
    from proteinbert_tpu.obs import tracing

    said = io.StringIO()
    with contextlib.redirect_stdout(said):
        harness.main(["--workload", cell, "--seed", str(SEED), "--seconds", "1",
                      "--trace", "1", "--rehearse"])
    line = json.loads(said.getvalue().strip().splitlines()[-1])
    print(MARK + json.dumps({
        "device": line["device"]["kind"],
        "metrics": {k: v["value"] for k, v in line["metrics"].items()},
        "startup_spans": tracing.startup_spans(),
        "spans": [s for s in tracing.recorder().spans()
                  if s["name"] == startup_readers.SCOPE_MAP],
    }))
    return 0


def main() -> int:
    if len(sys.argv) == 3 and sys.argv[1] == "--child":
        return child(sys.argv[2])
    cache = os.path.join(ROOT, ".benchmark_trace", "startup_fixture_cache")
    shutil.rmtree(cache, ignore_errors=True)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    fixture = {}
    for cell in CELLS:
        for run in ("first", "second"):
            done = subprocess.run(
                [sys.executable, "-m", "benchmark.record_startup_fixture",
                 "--child", cell], cwd=ROOT, env=env, capture_output=True, text=True)
            if done.returncode:
                print(done.stderr[-3000:], file=sys.stderr)
                return done.returncode
            got = json.loads(next(ln for ln in done.stdout.splitlines()
                                  if ln.startswith(MARK))[len(MARK):])
            fixture.setdefault(cell, {})[run] = got
            print(cell, run, got["device"], len(got["startup_spans"]), "start-up records,",
                  len(got["spans"]), "scope maps,",
                  {k: round(v, 3) for k, v in got["metrics"].items()
                   if k.startswith(("startup_", "scope_map_s"))})
    shutil.rmtree(cache, ignore_errors=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "startup_v5e.json"), "w") as f:
        json.dump(fixture, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
