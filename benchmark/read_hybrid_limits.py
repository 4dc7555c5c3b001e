"""Read, on the chip, what the served decoder cell's limits are set from:
a seed's sound run and its controls one precision down.

    chiprun -- python3 -m benchmark.read_hybrid_limits \\
        --workload serve-ling3flash-sat --seed 2950000001 --controls int8,bf16_state

The sound run is the cell as the driver runs it (the served answers
against the plain reference). A control is the reference
(`reference/bailing_hybrid_f32.py`) over the same sampled documents once
more, one precision down, IN THE PROGRAM'S PLACE: "int8" (int8 products
with every weight matrix; the router stays float32, as in the program) or
"bf16_state" (the KDA state rounded to bfloat16 after every token). Its
answers go through the cell's own comparison and limits: a control has to
come out NOT correct, and the line says which numbers caught it. One JSON
line per reading, appended to chiprun_out/limits_<cell>.jsonl; PERF.md
section 2 holds the readings the limits were set from.
"""

from __future__ import annotations

import argparse
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", default="int8,bf16_state")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import run as bench_run
    from benchmark.drivers import lm_serve
    from benchmark.reference import bailing_hybrid_f32 as ref
    from proteinbert_tpu.utils.compat import configure_compile_cache

    configure_compile_cache()
    run = bench_run.tool_run(args.workload, args.seed, args.seconds, args.rehearse)
    devices = bench_run._devices(run)
    out, sample = lm_serve.measure(run, devices)
    sound = ref.embed_documents(args.seed, sample["docs"], sample["c"])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", f"limits_{args.workload}.jsonl")

    def reading(kind, answers):     # written as soon as it is read
        gaps = lm_serve.gaps(answers, sound)
        caught = [name for name, value, limit in lm_serve.limit_checks(
            gaps, run.workload) if not value <= limit]
        line = {"kind": kind, "seed": args.seed, "numbers": gaps,
                "by_document": {
                    key: [float(f"{e:.4g}") for e in
                          lm_serve.document_errors(answers, sound, key)]
                    for key in ("global", "local_mean")},
                "tokens": [len(d) for d in sample["docs"]],
                "correct": not caught and (kind != "sound" or out["failed"] == 0),
                "caught_by": caught,
                "documents": len(sample["docs"]),
                "residues_per_s": out["e2e"].get("embed_residues_per_s"),
                "setup_s": run.setup_s,
                "device": devices[0].device_kind}
        with open(path, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)

    reading("sound", sample["served"])
    for precision in (p for p in args.controls.split(",") if p):
        reading("control:" + precision, ref.embed_documents(
            args.seed, sample["docs"], sample["c"], precision=precision))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
