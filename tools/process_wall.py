"""Time whole processes of one benchmark cell: the wall of each run and
the phases its own lines tell (PERF.md section 5's table of four shapes).
Run on the chip; never imports jax itself: each run is a child process.

    chiprun --timeout 3300 -- python3 tools/process_wall.py --tag parent \
        --tree .parent --workload pretrain-glm47flash-packed8k --shapes 0c,0w,1c,1w

A shape is <trace><c|w|m>: c starts on a new empty cache directory, w
goes on with the directory the shape before it filled, m leaves the
machine's own JAX_COMPILATION_CACHE_DIR as it comes. `--tree` is a
checkout (this one, or the parent unpacked into a git-ignored directory).
Each run is `benchmark.run` of that tree with the tracing layer's log
line shown and the window's edges printed (the harness's `Run.window`
wrapped, in the child alone: it times nothing the harness does not).
Every line of a run goes, stamped with the seconds since the process
started, to chiprun_out/wall_<tag>_<cell>.log; one JSON row a run to
standard output and chiprun_out/wall_<tag>_<cell>.json: wall_s, backend_up_s,
setup_s, window_s, stop_and_reduce_s, reference_s, program_scopes (program, what it
was, seconds), fused_path (the process's
`fused_kernel_path_total` as the window closes: the executables traced by
then, by path and reason), the result line's metrics.
"""

import argparse
import json
import os
import re
import subprocess
import sys
import time


def one(tree, workload, seed, trace, cache, seconds, log, extra=()):
    env = dict(os.environ, PYTHONUNBUFFERED="1")
    if cache is not None:       # None: the machine's own, as it comes
        env["JAX_COMPILATION_CACHE_DIR"] = cache
    env.pop("BENCH_RUN", None)
    t0 = time.perf_counter()
    child = subprocess.Popen(
        [sys.executable, "-u", os.path.abspath(__file__), "--child",
         "--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), *extra],
        cwd=tree, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, errors="replace")
    lines = []
    for line in child.stdout:
        at = time.perf_counter() - t0
        lines.append((at, line.rstrip("\n")))
        log.write(f"[{at:8.2f}] {line}")
    rc = child.wait()
    wall = time.perf_counter() - t0
    log.write(f"[{wall:8.2f}] exit {rc}\n")
    log.flush()
    return rc, wall, lines


def phases(wall, lines):
    out = {"wall_s": wall}
    result = None
    for at, line in lines:
        m = re.search(r"reference: \d+ steps in ([\d.]+) s", line)
        if m:
            out["reference_s"] = float(m.group(1))
            out["reference_end_at_s"] = at
        m = re.search(r"wall: fused_kernel_path_total (\{.*\})", line)
        if m:
            out["fused_path"] = json.loads(m.group(1))
        m = re.search(r"program_scopes\(([^)]*)\): (.*?) in ([\d.]+) s", line)
        if m:
            out.setdefault("program_scopes", []).append(
                [m.group(1), m.group(2), float(m.group(3))])
        m = re.search(r"wall: window opens, setup_s ([\d.]+), start_trace ([\d.\-]+) s", line)
        if m:
            out["setup_s"], out["start_trace_s"] = float(m.group(1)), float(m.group(2))
        m = re.search(r"wall: backend up ([\d.]+) s", line)
        if m:
            out["backend_up_s"] = float(m.group(1))
        m = re.search(r"wall: window closed, window_s ([\d.]+), stop_and_reduce ([\d.]+) s", line)
        if m:
            out["window_s"], out["stop_and_reduce_s"] = float(m.group(1)), float(m.group(2))
        if line.startswith("{") and '"metrics"' in line:
            result = json.loads(line)
    if result is not None:
        metrics = result["metrics"]
        out["correct"] = result["correct"]
        out["metrics"] = {k: v["value"] for k, v in metrics.items()}
        out["device"] = result["device"]
    return out


def child(argv) -> int:
    """`benchmark.run` of the tree in the working directory."""
    import contextlib
    import logging

    sys.path.insert(0, os.getcwd())
    logging.basicConfig(format="%(message)s")
    logging.getLogger("proteinbert_tpu.obs.tracing").setLevel(logging.INFO)
    from benchmark import run

    inner = run.Run.window

    @contextlib.contextmanager
    def window(self):
        t0 = time.perf_counter()
        with inner(self):
            print(f"wall: window opens, setup_s {self.setup_s:.2f}, start_trace "
                  f"{self.setup_s - (t0 - run._PROCESS_START):.2f} s", flush=True)
            try:
                yield
            finally:
                t1 = time.perf_counter()
        print(f"wall: window closed, window_s {self.window_s:.2f}, stop_and_reduce "
              f"{time.perf_counter() - t1:.2f} s", flush=True)
        from proteinbert_tpu.kernels.fused_block import PATH_TOTAL

        # Before a traced run lowers each row class again for its scope map.
        print("wall: fused_kernel_path_total " + json.dumps(
            {f"{p}/{r}": n for (p, r), n in sorted(PATH_TOTAL.items())}),
            flush=True)

    devices = run._devices

    def _devices(a_run):
        found = devices(a_run)
        print(f"wall: backend up {time.perf_counter() - run._PROCESS_START:.2f} s "
              "after the process's start", flush=True)
        return found

    run.Run.window, run._devices = window, _devices
    return run.main(argv)


def main():
    if sys.argv[1:2] == ["--child"]:
        return child(sys.argv[2:])
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tag", required=True)
    ap.add_argument("--tree", default=".")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--shapes", default="0c,0w,1c,1w")
    ap.add_argument("--seed", type=int, default=3000000101)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    root = os.path.abspath(".")
    os.makedirs("chiprun_out", exist_ok=True)
    stem = f"chiprun_out/wall_{args.tag}_{args.workload}"
    rows, cache = [], None
    if os.path.exists(stem + ".json"):      # a later call under one tag goes on
        with open(stem + ".json") as f:
            rows = json.load(f)
    with open(stem + ".log", "a") as log:
        for i, shape in enumerate(args.shapes.split(",")):
            trace, kind = int(shape[0]), shape[1]
            if kind == "c":
                cache = os.path.join(root, ".wall_cache", f"{args.tag}_{args.workload}_{i}")
                subprocess.run(["rm", "-rf", cache])
            if kind == "m":
                cache = None
            else:
                os.makedirs(cache, exist_ok=True)
            log.write(f"==== {args.tag} {args.workload} shape {shape} cache {cache}\n")
            rc, wall, lines = one(os.path.join(root, args.tree), args.workload,
                                  args.seed + 17 * i, trace, cache, args.seconds, log,
                                  ["--rehearse"] if args.rehearse else [])
            row = dict(phases(wall, lines), tag=args.tag, workload=args.workload,
                       shape=shape, rc=rc, seed=args.seed + 17 * i)
            rows.append(row)
            print(json.dumps({k: v for k, v in row.items() if k != "device"}),
                  flush=True)
            with open(stem + ".json", "w") as f:
                json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
