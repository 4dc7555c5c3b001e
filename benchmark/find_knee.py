"""Sweep a serving cell's arrival rate on the chip to find the knee.

    chiprun -- python3 -m benchmark.find_knee --workload serve-base-steady \\
        --rates 1800:4200:200 --seeds 2500000003,2600000011 --seconds 10

One process, one server per rate and seed, the cell's own driver and
server options with only the mix's `rate_per_s` replaced (and no
comparison with the reference: `correct` is the cells' to decide). For
each rate one JSON line: requests and residues completed per second
inside the window, the 95th percentile of latency from the due time, how
many requests were still unanswered when the window closed (a backlog
that grows with the window is past the knee), how late the generator
ran, the batches by row class and the real fill.

A rate is SUSTAINED when nothing failed and the backlog (requests due
and not yet answered) does not grow through the window's second half:
`readers.backlog_growth_per_s`, the median backlog of the last quarter
less that of the third, over a quarter's length, is at most GROWTH (2 %)
of the offered rate. Medians over 2.5 s, so a 0.1 s stall on the close
does not decide it, as it decides what is left at close: at a rate the
server carries the growth reads within half a per cent of the rate of 0
(ten windows at 2,250/s), and one step of 200/s past the knee it reads
that step, 7 %. A rate was NOT OFFERED when the generator itself ran
over a second late at its 95th percentile: the process stalled for
seconds (about one run in ten does, PERF.md section 7) and the server
was never asked; such a rate says nothing either way. The knee of one
sweep is the highest sustained rate under which every rate that was
offered is sustained too; the knee is the lowest over the seeds. The
last line names it and the two rates it implies, 0.8 x and 1.25 x to the
nearest 50/s, which go into `traffic/ragged-steady.json` and
`traffic/ragged-sat.json` by hand, and with the date into each cell's
`why`, in a `benchmark` PR.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

GROWTH = 0.02
NOT_OFFERED_MS = 1000.0


def parse_rates(text: str) -> list:
    """'1800:4200:200' (both ends in) or '1800,2000,2200'."""
    if ":" in text:
        lo, hi, step = (float(x) for x in text.split(":"))
        return [float(r) for r in np.arange(lo, hi + step / 2, step)]
    return [float(r) for r in text.split(",")]


def sustained(line: dict) -> bool:
    return (line["failed"] == 0 and line["backlog_growth_per_s"]
            <= GROWTH * line["offered_per_s"])


def offered(line: dict) -> bool:
    return line.get("generator_late_p95_ms", 0.0) <= NOT_OFFERED_MS


def knee_of(lines: list):
    """The highest sustained rate of one sweep with every lower rate that
    was offered sustained too; None where the lowest is already past it."""
    knee = None
    for line in sorted(lines, key=lambda ln: ln["offered_per_s"]):
        if not offered(line):
            continue
        if not sustained(line):
            break
        knee = line["offered_per_s"]
    return knee


def implied(knee: float) -> dict:
    to_50 = lambda rate: 50.0 * round(rate / 50.0)  # noqa: E731
    return {"knee_per_s": knee, "steady_rate_per_s": to_50(0.8 * knee),
            "saturated_rate_per_s": to_50(1.25 * knee)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", default="2500000003")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import readers
    from benchmark import run as bench_run
    from benchmark.drivers import serve
    from proteinbert_tpu.utils.compat import configure_compile_cache

    configure_compile_cache()
    devices = None
    knees = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        lines = []
        for rate in parse_rates(args.rates):
            run = bench_run.tool_run(args.workload, seed, args.seconds,
                                     args.rehearse)
            run.mix["arrivals"] = {"rate_per_s": rate}
            run.workload["judged"] = "latency"      # wait for every request
            devices = devices or bench_run._devices(run)
            out, _ = serve.measure(run, devices)
            obs = out["obs"]
            lines.append({
                "seed": seed,
                "offered_per_s": rate,
                "completed_per_s": obs["requests_in_window"] / run.window_s,
                "residues_per_s": obs["residues_in_window"] / run.window_s,
                "latency_p50_ms": 1e3 * float(np.percentile(obs["latency_s"], 50)),
                "latency_p95_ms": out["e2e"]["embed_latency_p95_ms"],
                "typical_p95_ms": readers.typical_p95_ms(obs),
                "left_at_close": out["attempted"] - obs["requests_in_window"],
                "backlog_growth_per_s": readers.backlog_growth_per_s(
                    obs["latency_s"], obs["due_s"], run.seconds),
                "failed": out["failed"],
                "generator_late_p95_ms": 1e3 * float(np.percentile(obs["late_s"], 95)),
                "batches": obs["batches"],
                "batch_class_counts": obs["batch_class_counts"],
                "fill_pct": (100.0 * obs["residues_in_batches"]
                             / max(1, obs["batched_positions"])),
            })
            lines[-1]["sustained"] = sustained(lines[-1]) and offered(lines[-1])
            print(json.dumps(lines[-1]), flush=True)
        knees[seed] = knee_of(lines)
    if None in knees.values():
        print(json.dumps({"knee_per_s": None, "by_seed": knees,
                          "why": "the lowest rate swept is past the knee"}))
        return 0
    print(json.dumps(dict(implied(min(knees.values())), by_seed=knees)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
