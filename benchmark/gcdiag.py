"""The interpreter's garbage collections inside a cell's window: a diagnostic.

    chiprun -- python3 -m benchmark.gcdiag --workload serve-base-steady \\
        --seed 2730000001 --seconds 10 [--collector off]

One ordinary run of the cell through `benchmark.run` (same result line),
with an observer on `gc.callbacks` that changes nothing: after the line,
on standard error, every FULL collection (generation 2) that began
inside the window, when and how long it held the process. A full
collection walks every tracked object of a process that has traced and
compiled JAX programs, 60-130 ms in the serve cells, and every thread
waits for it: three a 10 s window at 2,250 requests/s are what lifts
`embed_latency_p95_ms` from 107 ms to 140-275 (PERF.md sections 5, 7).
`--collector off` switches the collector off for the window: what the
cell would read without them. Neither belongs to a judged run: the
observer is not in the benchmark's timed path.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--collector", choices=("on", "off"), default="on")
    args, rest = ap.parse_known_args(argv)

    from benchmark import run as bench_run

    full, began, window = [], [None], []

    def observe(phase, info):
        if info["generation"] < 2:
            return
        if phase == "start":
            began[0] = time.perf_counter()
        elif began[0] is not None:
            full.append((began[0], time.perf_counter() - began[0],
                         info["collected"]))

    real_window = bench_run.Run.window

    @contextlib.contextmanager
    def watched(self):
        with real_window(self):
            t0 = time.perf_counter()
            if args.collector == "off":
                gc.disable()
            try:
                yield
            finally:
                gc.enable()
                window.append((t0, time.perf_counter()))

    bench_run.Run.window = watched
    gc.callbacks.append(observe)
    try:
        rc = bench_run.main(["--workload", args.workload, "--seed", str(args.seed),
                             "--seconds", str(args.seconds), "--trace", "0", *rest])
    finally:
        gc.callbacks.remove(observe)
        bench_run.Run.window = real_window
    for t0, t1 in window:
        inside = [(t - t0, d, n) for t, d, n in full if t0 <= t <= t1]
        print(f"gc (collector {args.collector}): {len(inside)} full collections "
              f"in the window of {t1 - t0:.2f} s" + "".join(
                  f"; at {t:.2f} s {1e3 * d:.0f} ms, {n} freed"
                  for t, d, n in inside), file=sys.stderr)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
