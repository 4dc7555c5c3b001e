"""`benchmark.read_hybrid_limits` by the cell's OWN driver and reference
(`workloads/<cell>.json`: `driver`, which has `measure`, `gaps`,
`limit_checks`, `document_errors` and its reference as `ref`): read, on
the chip, what the served ZAYA1 cell's limits are set from: a seed's
sound run and its control one precision down.

    chiprun -- python3 -m benchmark.read_cca_limits \\
        --workload serve-zaya1-8b-sat --seed 3500000011 --controls int8

The sound run is the cell as the driver runs it (the served answers
against the plain reference). A control is the reference
(`reference/zaya_f32.py`) over the same sampled documents once more, one
precision down, IN THE PROGRAM'S PLACE: "int8" (int8 products with every
weight matrix, the grouped convolution's too; the router stays float32,
as in the program), or "flip" (no precision: the float32 reference with
the balance bias of ONE layer, the middle one held, left at zero, so that
many tokens of that layer take another expert: the documents whose LAST
token is among them read what one flipped top-1 choice costs an answer,
10-20 x the limits on a sample's rms and maximum, which do NOT allow
one: PERF.md section 2). Its
answers go through the cell's own comparison and limits: a control has to
come out NOT correct, and the line says which numbers caught it. One JSON
line per reading, appended to chiprun_out/limits_<cell>.jsonl; PERF.md
section 2 holds the readings the limits were set from.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", default="int8")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import run as bench_run
    from proteinbert_tpu.utils.compat import configure_compile_cache

    configure_compile_cache()
    run = bench_run.tool_run(args.workload, args.seed, args.seconds, args.rehearse)
    devices = bench_run._devices(run)
    driver = importlib.import_module("benchmark.drivers." + run.workload["driver"])
    ref = driver.ref
    out, sample = driver.measure(run, devices)
    sound = ref.embed_documents(args.seed, sample["docs"], sample["c"])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", f"limits_{args.workload}.jsonl")

    def reading(kind, answers):     # written as soon as it is read
        gaps = driver.gaps(answers, sound)
        caught = [name for name, value, limit in driver.limit_checks(
            gaps, run.workload) if not value <= limit]
        line = {"kind": kind, "seed": args.seed, "numbers": gaps,
                "by_document": {
                    key: [float(f"{e:.4g}") for e in
                          driver.document_errors(answers, sound, key)]
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

    c = sample["c"]
    middle = c["first_layer_index"] + c["num_hidden_layers"] // 2

    def flip(index, tree):
        if index == middle:
            tree["moe"]["router_bias"] = 0.0 * tree["moe"]["router_bias"]
        return tree

    reading("sound", sample["served"])
    for control in (p for p in args.controls.split(",") if p):
        how = dict(edit=flip) if control == "flip" else dict(precision=control)
        reading("control:" + control, ref.embed_documents(
            args.seed, sample["docs"], c, **how))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
