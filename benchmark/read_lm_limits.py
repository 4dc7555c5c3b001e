"""Read, on the chip, what a decoder cell's limits are set from: one seed's
sound run and its controls.

    chiprun -- python3 -m benchmark.read_lm_limits \\
        --workload pretrain-glm47flash-packed8k --seed 2950000001 --trace 0

`benchmark/read_limits.py` runs the SOUND seeds of any driver; its
control branch is ProteinBERT's. This is the decoder's, in phases that
are each a process of their own (the reference's three steps take most
of a one-chip machine's 40 GiB of host memory, once):

- `sound`: the cell as the driver runs it (`benchmark.run.main`: the
  program against the plain reference, its result line printed as ever);
  what the reference returned and the batches it followed are left in
  files under the temporary directory. `reference` leaves the same files
  without the program's run (`--no-sound`).
- `control`, once for each of `--controls`: the reference
  (`reference/glm4_moe_lite_f32.py`) follows the same checked steps once
  more, one precision down, IN THE PROGRAM'S PLACE: "int8" (int8 products
  with every weight matrix; the router stays float32, as in the program)
  or "bf16_params" (parameters and their updates in bfloat16). Its
  numbers go through the cell's own comparison (`lm_pretrain.gaps_against`,
  the limits of the workload file): a control has to come out NOT
  correct, and the line says which numbers caught it.

One JSON line per reading, appended to chiprun_out/limits_<cell>.jsonl;
PERF.md section 2 holds the readings the limits were set from.
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import shutil
import subprocess
import sys
import tempfile


def _kept_dir(args) -> str:
    return os.path.join(tempfile.gettempdir(),
                        f"lm_limits_{args.workload}_{args.seed}")


def _keep(args, batches, c, sound):
    """What a control is compared with, for a later process: the first
    gradient (2.8 GB at the published sizes) a leaf a file, read back
    mapped and not loaded."""
    import jax
    import numpy as np

    os.makedirs(_kept_dir(args), exist_ok=True)
    for i, leaf in enumerate(jax.tree.leaves(sound["first_grad"])):
        np.save(os.path.join(_kept_dir(args), f"grad_{i}.npy"), leaf)
    rest = {k: v for k, v in sound.items() if k != "first_grad"}
    with open(os.path.join(_kept_dir(args), "rest.pkl"), "wb") as f:
        pickle.dump({"batches": batches, "c": c, "sound": rest}, f)


def _kept(args) -> dict:
    import jax
    import numpy as np

    with open(os.path.join(_kept_dir(args), "rest.pkl"), "rb") as f:
        kept = pickle.load(f)
    tree = jax.tree.structure(kept["sound"]["first_grad_norms"])
    kept["sound"]["first_grad"] = jax.tree.unflatten(tree, [
        np.load(os.path.join(_kept_dir(args), f"grad_{i}.npy"), mmap_mode="r")
        for i in range(tree.num_leaves)])
    return kept


def sound(args) -> int:
    from benchmark import run as bench_run
    from benchmark.drivers import lm_pretrain

    follow = lm_pretrain.follow_reference

    def follow_and_keep(run, batches, c, precision="f32"):
        out = follow(run, batches, c, precision)
        _keep(args, batches, c, out)
        return out

    lm_pretrain.follow_reference = follow_and_keep
    try:
        return bench_run.main(
            ["--workload", args.workload, "--seed", str(args.seed), "--seconds",
             str(args.seconds), "--trace", str(args.trace)]
            + ["--rehearse"] * args.rehearse)
    finally:
        lm_pretrain.follow_reference = follow


def _tool_run(args):
    from benchmark import run as bench_run
    from proteinbert_tpu.utils.compat import configure_compile_cache

    configure_compile_cache()
    run = bench_run.tool_run(args.workload, args.seed, args.seconds, args.rehearse)
    bench_run._devices(run)
    return run


def reference(args) -> int:
    from benchmark.drivers import lm_pretrain
    from benchmark.drivers.pretrain import CHECKED_STEPS

    run = _tool_run(args)
    cfg = lm_pretrain.cell_config(run.workload, run.config)
    c = lm_pretrain.reference_sizes(run.config, cfg)
    feed = lm_pretrain.make_feed(run, cfg)
    batches = [next(feed) for _ in range(CHECKED_STEPS)]
    _keep(args, batches, c, lm_pretrain.follow_reference(run, batches, c))
    return 0


def control(args, precision: str) -> int:
    from benchmark import run as bench_run
    from benchmark.drivers import lm_pretrain

    kept = _kept(args)
    run = _tool_run(args)
    readings = lm_pretrain.follow_reference(run, kept["batches"], kept["c"], precision)
    gaps = lm_pretrain.gaps_against(readings, kept["sound"],
                                    kept["batches"][0]["segment_ids"])
    caught = [name for name, value, limit in lm_pretrain.limit_checks(
        gaps, run.workload) if not value <= limit]
    reading = {"kind": "control:" + precision, "seed": args.seed,
               "correct": not caught, "caught_by": caught, "numbers": gaps}
    print(json.dumps(reading), flush=True)
    out_dir = os.path.join(bench_run.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"limits_{args.workload}.jsonl"), "a") as f:
        f.write(json.dumps(reading) + "\n")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--controls", default="int8",
                    help="comma-separated: int8, bf16_params")
    ap.add_argument("--no-sound", action="store_true",
                    help="follow the reference without the program's run")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--phase", default="all", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.phase != "all":
        kind, _, precision = args.phase.partition(":")
        return (control(args, precision) if kind == "control"
                else {"sound": sound, "reference": reference}[kind](args))
    # This process stays off JAX: a chip belongs to one process at a time.
    argv = list(sys.argv[1:] if argv is None else argv)
    phases = ["reference" if args.no_sound else "sound"] + [
        "control:" + p for p in filter(None, args.controls.split(","))]
    try:
        for phase in phases:
            done = subprocess.run([sys.executable, "-m", "benchmark.read_lm_limits",
                                   *argv, "--phase", phase])
            if done.returncode:
                return done.returncode
    finally:
        shutil.rmtree(_kept_dir(args), ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
