#!/usr/bin/env python
"""Ragged Pallas attention + tiled-segment fused-block smoke (ISSUE 13,
tier-1 stage).

Tiny shapes through the real dispatch entries (interpret mode on CPU —
the same kernels Mosaic compiles on TPU), gates:

  1. PACKED ATTENTION PARITY — the segment-layout Pallas attention
     kernel vs `packed_global_attention_apply` on a training-style
     layout AND a serving-style layout (bucket-quantized spans with
     <pad> tails via real_mask), per-output deviation <= 1e-5, with the
     dispatch counted on `attention_kernel_path_total{path=pallas,
     reason=packed}` and ZERO reason=segments fallbacks.
  2. DENSE ATTENTION PARITY — the S=1 entry vs `global_attention_apply`
     including a fully-padded batch-class row (uniform-softmax
     semantics preserved), counted as path=pallas/reason=dense.
  3. VJP — gradient parity of the custom-VJP backward vs autodiff
     through the masked-XLA reference, <= 1e-4.
  4. FORCED OVERRIDE — PBT_FORCE_REFERENCE_KERNEL routes a fresh
     attention trace onto the reference path (reason=forced),
     bit-identical to the reference.
  5. TILED SEGMENT FUSED BLOCK — one C=1024 packed row through
     `fused_local_track_segments` runs the channel-tiled SEGMENT
     variant (pallas/packed, zero reason=segments) and matches the
     boundary-masked reference at bf16 tolerance.

Exit nonzero on any violation — this stage GATES (run_tier1.sh).
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

PARITY_BOUND = 1e-5   # documented jitted tolerance
GRAD_BOUND = 1e-4
TILED_BOUND = 0.05    # bf16 tiled tolerance (tests/test_kernels.py)


def main() -> int:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from proteinbert_tpu.kernels import attention as ka
    from proteinbert_tpu.kernels import fused_block as fb
    from proteinbert_tpu.ops.attention import (
        global_attention_apply,
        global_attention_init,
        packed_global_attention_apply,
    )

    failures = []

    def gate(ok: bool, msg: str) -> None:
        print(("PASS " if ok else "FAIL ") + msg)
        if not ok:
            failures.append(msg)

    B, L, C, S = 2, 128, 128, 4
    G, KD, H = 64, 16, 4
    params = global_attention_init(jax.random.PRNGKey(0), C, G, KD, H)
    local = jax.random.normal(jax.random.PRNGKey(1), (B, L, C),
                              jnp.float32)
    gseg = jax.random.normal(jax.random.PRNGKey(2), (B, S, G),
                             jnp.float32)
    seg = np.zeros((B, L), np.int32)
    seg[0, :60] = 1
    seg[0, 60:110] = 2
    seg[1, :L] = 1
    seg = jnp.asarray(seg)

    gate(ka.pallas_attention_supported(C, G, L, S, KD, H, "float32"),
         "guard: (128, 64, 128, 4) fp32 shape is supported")

    # ---- gate 1: packed parity + counter coverage --------------------
    before = dict(ka.ATTN_PATH_TOTAL)
    got = jax.jit(lambda p, x, g, s: ka.fused_packed_attention(
        p, x, g, s, interpret=True))(params, local, gseg, seg)
    delta_p = (ka.ATTN_PATH_TOTAL.get(("pallas", "packed"), 0)
               - before.get(("pallas", "packed"), 0))
    delta_s = (ka.ATTN_PATH_TOTAL.get(("reference", "segments"), 0)
               - before.get(("reference", "segments"), 0))
    want = jax.jit(lambda p, x, g, s: packed_global_attention_apply(
        p, x, g, s))(params, local, gseg, seg)
    diff = float(np.abs(np.asarray(got) - np.asarray(want)).max())
    gate(diff <= PARITY_BOUND,
         f"packed attention parity {diff:.2e} <= {PARITY_BOUND}")
    gate(delta_p >= 1 and delta_s == 0,
         f"packed dispatch on the Pallas path (pallas/packed +{delta_p},"
         f" reference/segments +{delta_s})")

    # Serving layout: spans bucket-quantized, tails are <pad>.
    real = np.zeros((B, L), bool)
    real[0, :41] = True
    real[0, 60:60 + 30] = True
    real[1, :100] = True
    real = jnp.asarray(real)
    got_m = ka.fused_packed_attention(params, local, gseg, seg,
                                      real_mask=real, interpret=True)
    want_m = packed_global_attention_apply(params, local, gseg, seg,
                                           real_mask=real)
    diff_m = float(np.abs(np.asarray(got_m) - np.asarray(want_m)).max())
    gate(diff_m <= PARITY_BOUND,
         f"serving real_mask parity {diff_m:.2e} <= {PARITY_BOUND}")

    # ---- gate 2: dense parity (incl. an all-pad row) -----------------
    g2 = jax.random.normal(jax.random.PRNGKey(3), (B, G), jnp.float32)
    pad = np.ones((B, L), bool)
    pad[1, :] = False
    pad = jnp.asarray(pad)
    before = dict(ka.ATTN_PATH_TOTAL)
    got_d = ka.fused_global_attention(params, local, g2, pad,
                                      interpret=True)
    delta_d = (ka.ATTN_PATH_TOTAL.get(("pallas", "dense"), 0)
               - before.get(("pallas", "dense"), 0))
    want_d = global_attention_apply(params, local, g2, pad)
    diff_d = float(np.abs(np.asarray(got_d) - np.asarray(want_d)).max())
    gate(diff_d <= PARITY_BOUND and delta_d >= 1,
         f"dense attention parity {diff_d:.2e} <= {PARITY_BOUND} on "
         "the Pallas path (all-pad row keeps uniform softmax)")

    # ---- gate 3: VJP gradient parity ---------------------------------
    def loss_f(p, x, g):
        return jnp.sum(ka.fused_packed_attention(
            p, x, g, seg, interpret=True) ** 2)

    def loss_r(p, x, g):
        return jnp.sum(packed_global_attention_apply(p, x, g, seg) ** 2)

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(params, local, gseg)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(params, local, gseg)
    gdiff = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
                for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gr)))
    gate(gdiff <= GRAD_BOUND,
         f"custom-VJP gradient parity {gdiff:.2e} <= {GRAD_BOUND}")

    # ---- gate 4: forced-reference override ---------------------------
    os.environ[fb.FORCE_REFERENCE_ENV] = "1"
    try:
        before = dict(ka.ATTN_PATH_TOTAL)
        got_fo = jax.jit(lambda p, x, g, s: ka.fused_packed_attention(
            p, x, g, s, interpret=True))(params, local, gseg, seg)
        bumps = (ka.ATTN_PATH_TOTAL.get(("reference", "forced"), 0)
                 - before.get(("reference", "forced"), 0))
        bit = np.array_equal(np.asarray(got_fo), np.asarray(want))
        gate(bumps >= 1 and bit,
             "PBT_FORCE_REFERENCE_KERNEL routes attention onto the "
             f"reference path (forced +{bumps}, bit_identical={bit})")
    finally:
        del os.environ[fb.FORCE_REFERENCE_ENV]

    # ---- gate 5: tiled segment fused block at C=1024 -----------------
    from proteinbert_tpu.configs import ModelConfig
    from proteinbert_tpu.models import proteinbert

    Ct = 1024
    cfg = ModelConfig(local_dim=Ct, global_dim=64, key_dim=16,
                      num_heads=4, num_blocks=1, num_annotations=32,
                      dtype="bfloat16")
    block = proteinbert.block_init(jax.random.PRNGKey(4), cfg)
    tparams = {k: block[k] for k in ("narrow_conv", "wide_conv",
                                     "local_ln1", "local_dense",
                                     "local_ln2")}
    xt = jax.random.normal(jax.random.PRNGKey(5), (1, 128, Ct),
                           jnp.bfloat16)
    bct = jax.random.normal(jax.random.PRNGKey(6), (1, 2, Ct),
                            jnp.bfloat16)
    segt = jnp.asarray(np.array([[1] * 70 + [2] * 50 + [0] * 8],
                                np.int32))
    gate(fb.pallas_segments_supported(Ct, 128, 2),
         "guard: C=1024 packed shape has a tiled segment plan")
    before = dict(fb.PATH_TOTAL)
    got_t = fb.fused_local_track_segments(tparams, xt, bct, segt, 1, 5,
                                          True).astype(jnp.float32)
    dp = (fb.PATH_TOTAL.get(("pallas", "packed"), 0)
          - before.get(("pallas", "packed"), 0))
    dsg = (fb.PATH_TOTAL.get(("reference", "segments"), 0)
           - before.get(("reference", "segments"), 0))
    want_t = fb.local_track_segment_reference(
        tparams, xt, fb.gather_segment_broadcast(bct, segt), segt, 1, 5
    ).astype(jnp.float32)
    diff_t = float(np.abs(np.asarray(got_t) - np.asarray(want_t)).max())
    scale_t = float(np.abs(np.asarray(want_t)).max())
    gate(diff_t <= TILED_BOUND * max(scale_t, 1.0) and dp >= 1
         and dsg == 0,
         f"tiled segment C=1024 parity {diff_t:.3f} (bf16) on the "
         f"Pallas path (pallas/packed +{dp}, reference/segments +{dsg})")

    print(f"\n{len(failures)} failure(s)")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
