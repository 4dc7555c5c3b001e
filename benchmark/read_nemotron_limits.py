"""Read, on the chip, what the served Nemotron 3 cell's limits are set
from (`benchmark.read_cca_limits` with this reference's two controls, and
`benchmark.read_cca_flips` by another trunk).

    chiprun -- python3 -m benchmark.read_nemotron_limits \\
        --workload serve-nemotron3super-sat --seed 3543000011 \\
        --controls int8,state_bf16

The sound run is the cell as the driver runs it (the served answers
against the plain reference). A control is the reference
(`reference/nemotron_h_f32.py`) over the same sampled documents once more,
one precision down, IN THE PROGRAM'S PLACE: "int8" (int8 products with
every weight matrix; the router stays float32, as in the program) or
"state_bf16" (the recurrence's state rounded to bfloat16 after every
token). Its answers go through the cell's own comparison and limits: a
control has to come out NOT correct, and the line says which numbers
caught it. One JSON line per reading, appended to
chiprun_out/limits_<cell>.jsonl; PERF.md section 2 holds the readings the
limits were set from.

With `--flips` instead: the program's trunk over one packed batch of the
cell's own documents against the reference on each document alone, EVERY
token (a causal decoder's final-norm state at token t is what `embed`
answers as `global` for the document cut after t): with 22 of 512 chosen
the 22nd and 23rd scores lie close far more often than a top-8's, and the
line's quantiles and counts over thresholds say how many of a batch's
tokens a flipped choice moved, and by how much.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

THRESHOLDS = (0.002, 0.004, 0.006, 0.01, 0.02)


def read_limits(args) -> int:
    """`read_cca_limits` finds the driver, its reference and its
    comparison by the workload's file, and a control is any `precision`
    the reference takes: this cell's are its own two."""
    from benchmark import read_cca_limits

    argv = ["--workload", args.workload, "--seed", str(args.seed), "--seconds",
            str(args.seconds), "--controls", args.controls]
    return read_cca_limits.main(argv + ["--rehearse"] * args.rehearse)


def read_flips(args) -> int:
    import jax
    import jax.numpy as jnp

    from benchmark import run as bench_run
    from benchmark.drivers import nemotron_serve
    from benchmark.read_cca_flips import packed_batch
    from benchmark.reference import nemotron_h_f32 as ref
    from proteinbert_tpu.models import glm_moe
    from proteinbert_tpu.ops.layers import rms_norm_apply

    run = bench_run.tool_run(args.workload, args.seed, 10.0, args.rehearse)
    cfg = nemotron_serve.cell_config(run.workload, run.config)
    m, server = cfg.model, run.workload["server"]
    docs, _ = nemotron_serve.documents(run.mix, 1, args.seed, stream=7)
    tokens, seg, taken = packed_batch(
        docs, server["max_batch"], cfg.data.seq_len, server["pack_max_segments"])

    @jax.jit
    def every_token(params, tokens, seg):
        real = (seg > 0) & (tokens >= 0)
        h, *_ = glm_moe.served_trunk(params, tokens, seg, real, m)
        return rms_norm_apply(params["final_norm"], h, m.rms_norm_eps).astype(
            jnp.float32)

    params = glm_moe.init_served(ref.seed_key(args.seed), m)
    got = np.asarray(every_token(params, jnp.asarray(tokens), jnp.asarray(seg)))
    del params
    got = [got[b][seg[b] == s] for b, row in enumerate(taken)
           for s in range(1, len(row) + 1)]
    packed = [d for row in taken for d in row]
    want = ref.embed_documents(args.seed, packed, nemotron_serve.reference_sizes(
        run.config, cfg), every_token=True)
    err = np.concatenate([
        np.linalg.norm(g - w, axis=-1) / np.linalg.norm(w, axis=-1)
        for g, w in zip(got, want)])
    print(json.dumps({
        "kind": "flips", "seed": args.seed, "tokens": int(err.size),
        "documents": len(packed),
        "err_quantiles": {str(q): float(np.quantile(err, q))
                          for q in (0.25, 0.5, 0.75, 0.9, 0.99, 0.999, 1.0)},
        "tokens_over": {str(t): int((err > t).sum()) for t in THRESHOLDS},
        "largest": [float(e) for e in np.sort(err)[::-1][:12]],
        "device": jax.devices()[0].device_kind}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", default="int8,state_bf16")
    ap.add_argument("--flips", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from proteinbert_tpu.utils.compat import configure_compile_cache

    configure_compile_cache()
    return read_flips(args) if args.flips else read_limits(args)


if __name__ == "__main__":
    raise SystemExit(main())
