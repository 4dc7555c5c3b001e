"""Read, on the chip, the numbers a cell's limits are set from.

    chiprun -- python3 -m benchmark.read_limits --workload <cell> \\
        --seeds 12 --control-seeds 4 [--seconds 3]

For every seed, the cell's own driver runs with a short window and its
comparison's numbers are kept (the sound runs). For the first
`--control-seeds` seeds the control is read too: for a training cell the
plain reference in the program's place, computed one precision down
("int8": int8 matrix products; on the first seed also "bf16_params":
parameters in bfloat16); for a serving cell the program itself with its
own int8 weights switched on. One JSON line per reading, and a summary: the sound runs'
largest and the control's smallest of every number. PERF.md section 2
holds the readings the limits were set from.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import compare, run as bench_run
    from proteinbert_tpu.utils.compat import configure_compile_cache

    configure_compile_cache()
    readings = []

    def make_run(seed):
        return bench_run.tool_run(args.workload, seed, args.seconds,
                                  args.rehearse)

    probe = make_run(args.first_seed)
    devices = bench_run._devices(probe)
    driver = importlib.import_module(
        "benchmark.drivers." + probe.workload["driver"])
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        run = make_run(seed)
        out = driver.run(run, devices)
        numbers = {n: v for n, v, _ in out["checks"]}
        if "grad_dir_by_leaf" in out["obs"]:
            numbers["grad_dir_by_leaf"] = out["obs"]["grad_dir_by_leaf"]
        note("sound", seed, numbers, readings)
        if i >= args.control_seeds:
            continue
        if probe.workload["driver"] == "serve":
            run = make_run(seed)
            run.workload["server"] = dict(run.workload["server"], quant="int8")
            out = driver.run(run, devices)
            note("control:int8_weights", seed,
                 {n: v for n, v, _ in out["checks"]}, readings)
        else:
            from benchmark.drivers import pretrain
            from benchmark.program import model_sizes
            from benchmark.reference import proteinbert_f32 as ref

            run = make_run(seed)
            m = model_sizes(run.config)
            feed, _ = pretrain.make_feed(run, m)
            batches = [next(feed) for _ in range(pretrain.CHECKED_STEPS)]
            follow = lambda precision: ref.follow_steps(  # noqa: E731
                seed, batches, m, run.config["corruption"],
                pretrain.optimizer_sizes(run.config), precision=precision,
                rows=run.workload["reference_rows"],
                operands=pretrain.product_operands(run.config))
            sound = follow("f32")
            # bfloat16 parameters read 0.99-1 whatever the seed: once is enough
            controls = ("int8", "bf16_params") if i == 0 else ("int8",)
            for precision in controls:
                control = follow(precision)
                numbers = compare.training_checks(control, sound)
                numbers["grad_dir_by_leaf"] = compare.leaf_dir_spread(
                    control["first_grad"], sound["first_grad"])
                note("control:" + precision, seed, numbers, readings)
                del control

    summary = {}
    for r in readings:
        for name, value in r["numbers"].items():
            if isinstance(value, list):
                continue
            lo, hi = summary.setdefault(r["kind"], {}).get(name, (value, value))
            summary[r["kind"]][name] = (min(lo, value), max(hi, value))
    print(json.dumps({"workload": args.workload, "summary_min_max": summary}))
    out_dir = os.path.join(bench_run.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"limits_{args.workload}.jsonl"), "a") as f:
        for r in readings:
            f.write(json.dumps(r) + "\n")
    return 0


def note(kind, seed, numbers, readings):
    r = {"kind": kind, "seed": seed, "numbers": numbers}
    readings.append(r)
    print(json.dumps(r), flush=True)
    sys.stdout.flush()


if __name__ == "__main__":
    raise SystemExit(main())
