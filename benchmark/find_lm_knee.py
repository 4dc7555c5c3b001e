"""`benchmark.find_knee` for a cell whose driver is not ProteinBERT's.

    chiprun -- python3 -m benchmark.find_lm_knee --workload serve-ling3flash-sat \\
        --rates 12:17:1 --seeds 2500000003 --seconds 10

`find_knee.py` calls `drivers/serve.measure` by name; this opens the windows
of the cell's OWN driver (`workloads/<cell>.json`: `driver`, which has
`serving`, `offer` and `account`) and is otherwise that tool: only the
mix's `rate_per_s` replaced, no comparison with the reference, one JSON
line a rate, `find_knee`'s own rules for SUSTAINED, NOT OFFERED and the
knee. A seed's rates share ONE booted server, lowest first, each window
opened once every request of the one before is answered (making 9.4 GiB
of weights and warming the server is most of a rate's minute on the
chip). The last line names the knee and `--factor` times it; the cell's
rate goes into its traffic file by hand. The rule holds the backlog's
growth to 2 % of the rate, which at 12-18 requests/s is under what two
windows at one rate differ by: read the lines, not only the last
(PERF.md sections 6 and 7).
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json

import numpy as np

from benchmark import find_knee


def one_rate(args, seed, rate, driver, devices, boot) -> dict:
    """One window at `rate` on the booted server, every request waited
    for; the sweep's line, printed."""
    from benchmark import readers
    from benchmark import run as bench_run

    run = bench_run.tool_run(args.workload, seed, args.seconds, args.rehearse)
    run.mix["arrivals"] = {"rate_per_s": rate}
    run.workload["judged"] = "latency"      # wait for every request
    out, _ = driver.account(run, boot, driver.offer(run, devices, boot))
    obs = out["obs"]
    line = {
        "seed": seed,
        "offered_per_s": rate,
        "completed_per_s": obs["requests_in_window"] / run.window_s,
        "residues_per_s": obs["residues_in_window"] / run.window_s,
        "latency_p50_ms": 1e3 * float(np.percentile(obs["latency_s"], 50)),
        "latency_p95_ms": out["e2e"]["embed_latency_p95_ms"],
        "left_at_close": out["attempted"] - obs["requests_in_window"],
        "backlog_growth_per_s": readers.backlog_growth_per_s(
            obs["latency_s"], obs["due_s"], run.seconds),
        "failed": out["failed"],
        "generator_late_p95_ms": 1e3 * float(np.percentile(obs["late_s"], 95)),
        "batches": obs["batches"],
        "batch_class_counts": obs["batch_class_counts"],
        "fill_pct": (100.0 * obs["residues_in_batches"]
                     / max(1, obs["batched_positions"])),
    }
    line["sustained"] = find_knee.sustained(line) and find_knee.offered(line)
    print(json.dumps(line), flush=True)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", default="2500000003")
    ap.add_argument("--factor", type=float, default=1.5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import run as bench_run
    from proteinbert_tpu.utils.compat import configure_compile_cache

    configure_compile_cache()
    devices = None
    knees = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        run = bench_run.tool_run(args.workload, seed, args.seconds, args.rehearse)
        devices = devices or bench_run._devices(run)
        driver = importlib.import_module(
            "benchmark.drivers." + run.workload["driver"])
        with driver.serving(run) as boot:
            lines = [one_rate(args, seed, rate, driver, devices, boot)
                     for rate in sorted(find_knee.parse_rates(args.rates))]
        del boot
        gc.collect()    # the closed server's weights, before the next seed's are made
        knees[seed] = find_knee.knee_of(lines)
    if None in knees.values():
        print(json.dumps({"knee_per_s": None, "by_seed": knees,
                          "why": "the lowest rate swept is past the knee"}))
        return 0
    knee = min(knees.values())
    print(json.dumps({"knee_per_s": knee, "by_seed": knees,
                      "saturated_rate_per_s": round(args.factor * knee, 1)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
