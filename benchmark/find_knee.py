"""Sweep a serving cell's arrival rate on the chip, once, to find the knee.

    chiprun -- python3 -m benchmark.find_knee --workload serve-base-steady \\
        --rates 1200,1400,1600,1800 --seconds 10

One process, one server per rate, the cell's own driver with only the
mix's `rate_per_s` replaced. For each rate one JSON line: requests and
residues completed per second inside the window, the 95th percentile of
latency from the due time, how many requests were still unanswered when
the window closed (a backlog that grows with the window is past the
knee), and how late the generator ran. The knee is the highest rate at
which the completed rate still equals the offered one and nothing is
left over; the cells then fix 0.8 x and 1.25 x that rate in their mix
files. A later `benchmark` PR runs this again after the knee has moved.
"""

from __future__ import annotations

import argparse
import json

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=2_500_000_003)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import run as bench_run
    from benchmark.drivers import serve
    from proteinbert_tpu.utils.compat import configure_compile_cache

    configure_compile_cache()
    devices = None
    for rate in (float(r) for r in args.rates.split(",")):
        run = bench_run.tool_run(args.workload, args.seed, args.seconds,
                                 args.rehearse)
        run.mix["arrivals"] = {"rate_per_s": rate}
        run.workload["judged"] = "latency"      # wait for every request
        devices = devices or bench_run._devices(run)
        out = serve.run(run, devices)
        obs = out["obs"]
        print(json.dumps({
            "offered_per_s": rate,
            "completed_per_s": obs["requests_in_window"] / run.window_s,
            "residues_per_s": obs["residues_in_window"] / run.window_s,
            "latency_p50_ms": 1e3 * float(np.percentile(obs["latency_s"], 50)),
            "latency_p95_ms": 1e3 * float(np.percentile(obs["latency_s"], 95)),
            "left_at_close": out["attempted"] - obs["requests_in_window"],
            "failed": out["failed"],
            "generator_late_p95_ms": 1e3 * float(np.percentile(obs["late_s"], 95)),
            "batches": obs["batches"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
