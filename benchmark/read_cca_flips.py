"""How often the served CCA decoder's top-1 choice differs from the plain
reference's, read over EVERY token of one packed batch a seed.

    chiprun -- python3 -m benchmark.read_cca_flips \\
        --workload serve-zaya1-8b-sat --seeds 3570000011,3570000022

A causal decoder's final-norm state at token t is what `embed` answers as
`global` for the document cut after t, so one batch of the cell's own
documents gives ~15,000 last-token vectors where a run of the cell samples
12. The program's trunk (`glm_moe.cca_trunk`, what the served executable
runs under `encode`, on the chip its kernels) over the batch, against the
reference (`reference/zaya_f32.embed_documents(every_token=True)`) on each
document ALONE. Where two experts' p + b lie within the program's rounding
of each other the choice flips, and that token reads 0.0045-0.010 where the
others read ~0.0001: the line counts the tokens over a few thresholds. It
is what the cell's `global_rel_err_max` limit and its `*_but_one` numbers
stand on (PERF.md section 2). One JSON line a seed.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

THRESHOLDS = (0.0004, 0.0006, 0.001, 0.002, 0.004)


def packed_batch(docs: list, rows: int, length: int, segments: int):
    """The documents first-fit into `rows` rows in the order drawn ->
    (tokens, segment ids, the documents taken, row by row)."""
    taken = [[] for _ in range(rows)]
    for d in docs:
        for row in taken:
            if len(row) < segments and sum(map(len, row)) + len(d) <= length:
                row.append(d)
                break
    tokens = -np.ones((rows, length), np.int32)
    seg = np.zeros((rows, length), np.int32)
    for b, row in enumerate(taken):
        at = 0
        for s, d in enumerate(row, 1):
            tokens[b, at:at + len(d)], seg[b, at:at + len(d)] = d, s
            at += len(d)
    return tokens, seg, taken


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark import run as bench_run
    from benchmark.drivers import cca_serve
    from benchmark.reference import zaya_f32 as ref
    from proteinbert_tpu.models import glm_moe
    from proteinbert_tpu.ops.layers import rms_norm_apply
    from proteinbert_tpu.utils.compat import configure_compile_cache

    configure_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        run = bench_run.tool_run(args.workload, seed, 10.0, args.rehearse)
        cfg = cca_serve.cell_config(run.workload, run.config)
        m, server = cfg.model, run.workload["server"]
        docs, _ = cca_serve.documents(run.mix, 1, seed, stream=7)
        tokens, seg, taken = packed_batch(
            docs, server["max_batch"], cfg.data.seq_len, server["pack_max_segments"])

        @jax.jit
        def every_token(params, tokens, seg):
            real = (seg > 0) & (tokens >= 0)
            h, *_ = glm_moe.cca_trunk(params, tokens, seg, real, m)
            return rms_norm_apply(params["final_norm"], h, m.rms_norm_eps).astype(
                jnp.float32)

        params = glm_moe.init_served(ref.seed_key(seed), m)
        got = np.asarray(every_token(params, jnp.asarray(tokens), jnp.asarray(seg)))
        del params
        got = [got[b][seg[b] == s] for b, row in enumerate(taken)
               for s in range(1, len(row) + 1)]
        packed = [d for row in taken for d in row]
        want = ref.embed_documents(seed, packed, cca_serve.reference_sizes(
            run.config, cfg), every_token=True)
        err = np.concatenate([
            np.linalg.norm(g - w, axis=-1) / np.linalg.norm(w, axis=-1)
            for g, w in zip(got, want)])
        print(json.dumps({
            "seed": seed, "tokens": int(err.size), "documents": len(packed),
            "err_quantiles": {str(q): float(np.quantile(err, q))
                              for q in (0.25, 0.5, 0.75, 0.99, 0.999, 1.0)},
            "tokens_over": {str(t): int((err > t).sum()) for t in THRESHOLDS},
            "largest": [float(e) for e in np.sort(err)[::-1][:12]],
            "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
