#!/usr/bin/env python
"""One-pass trunk kernel smoke (ISSUE 16, tier-1 stage).

Tiny shapes through the real dispatch entries (interpret mode on CPU —
the same kernel Mosaic compiles on TPU), gates:

  1. PACKED BIT-IDENTITY — the one-pass kernel (local track + ragged
     attention in ONE grid program) vs the two-kernel Pallas
     composition on a training-style layout AND a serving-style layout
     (bucket-quantized spans with <pad> tails via real_mask),
     bit-identical on BOTH outputs, counted on
     `onepass_kernel_path_total{path=pallas,reason=packed}` with ZERO
     reason=segments fallbacks.
  2. SINGLE KERNEL BOUNDARY — the one-pass trace contains exactly ONE
     pallas_call (the composition two): the inter-track activation
     never leaves VMEM, so there is no HBM round-trip to spill.
  3. DENSE BIT-IDENTITY — the S=1 entry vs the dense composition,
     including a fully-padded batch-class row (uniform-softmax
     semantics preserved), counted as path=pallas/reason=dense.
  4. VJP — gradient parity of the custom-VJP backward vs autodiff
     through the one-hot reference, <= 1e-4.
  5. FORCED OVERRIDE — PBT_FORCE_REFERENCE_KERNEL routes a fresh
     one-pass trace onto the reference composition (reason=forced),
     bit-identical to it.
  6. INT8 IN-KERNEL DEQUANT — `quantize_params` int8 weights + scales
     dequantized inside the kernel bit-match HLO-dequantizing the same
     tree first (both entries).

Exit nonzero on any violation — this stage GATES (run_tier1.sh).
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

GRAD_BOUND = 1e-4


def main() -> int:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from proteinbert_tpu.configs import ModelConfig
    from proteinbert_tpu.kernels import attention as ka
    from proteinbert_tpu.kernels import fused_block as fb
    from proteinbert_tpu.kernels import one_pass as op
    from proteinbert_tpu.models import proteinbert
    from proteinbert_tpu.parallel.quant import quantize_params

    failures = []

    def gate(ok: bool, msg: str) -> None:
        print(("PASS " if ok else "FAIL ") + msg)
        if not ok:
            failures.append(msg)

    B, L, C, S = 2, 128, 128, 4
    G, KD, H = 64, 16, 4
    cfg = ModelConfig(local_dim=C, global_dim=G, key_dim=KD, num_heads=H,
                      num_blocks=1, num_annotations=16, dtype="float32")
    block = proteinbert.block_init(jax.random.PRNGKey(0), cfg)
    track = {k: block[k] for k in ("narrow_conv", "wide_conv",
                                   "local_ln1", "local_dense",
                                   "local_ln2")}
    attn = block["attention"]
    x = jax.random.normal(jax.random.PRNGKey(1), (B, L, C), jnp.float32)
    bc = jax.random.normal(jax.random.PRNGKey(2), (B, S, C), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(3), (B, S, G), jnp.float32)
    seg = np.zeros((B, L), np.int32)
    seg[0, :60] = 1
    seg[0, 60:110] = 2
    seg[1, :L] = 1
    seg = jnp.asarray(seg)

    gate(op.pallas_onepass_supported(C, G, L, S, KD, H, "float32"),
         "guard: (128, 64, 128, 4) fp32 shape has a one-pass plan")

    def one(tp, ap, xx, bb, gg, ss):
        return op.fused_onepass_segments(tp, ap, xx, bb, gg, ss,
                                         interpret=True)

    def two(tp, ap, xx, bb, gg, ss):
        loc = fb.fused_local_track_segments(tp, xx, bb, ss, 1, 5, True)
        return loc, ka.fused_packed_attention(ap, loc, gg, ss,
                                              interpret=True)

    # ---- gate 1: packed bit-identity + counter coverage --------------
    before = dict(op.ONEPASS_PATH_TOTAL)
    got = jax.jit(one)(track, attn, x, bc, g, seg)
    delta_p = (op.ONEPASS_PATH_TOTAL.get(("pallas", "packed"), 0)
               - before.get(("pallas", "packed"), 0))
    delta_s = (op.ONEPASS_PATH_TOTAL.get(("reference", "segments"), 0)
               - before.get(("reference", "segments"), 0))
    want = jax.jit(two)(track, attn, x, bc, g, seg)
    bit = all(np.array_equal(np.asarray(a), np.asarray(b))
              for a, b in zip(got, want))
    gate(bit, "packed one-pass bit-matches the two-kernel composition")
    gate(delta_p >= 1 and delta_s == 0,
         f"packed dispatch on the one-pass path (pallas/packed "
         f"+{delta_p}, reference/segments +{delta_s})")

    # Serving layout: spans bucket-quantized, tails are <pad>.
    real = np.zeros((B, L), bool)
    real[0, :41] = True
    real[0, 60:60 + 30] = True
    real[1, :100] = True
    real = jnp.asarray(real)
    got_m = op.fused_onepass_segments(track, attn, x, bc, g, seg,
                                      real_mask=real, interpret=True)
    loc_m = fb.fused_local_track_segments(track, x, bc, seg, 1, 5, True)
    want_m = (loc_m, ka.fused_packed_attention(attn, loc_m, g, seg,
                                               real_mask=real,
                                               interpret=True))
    bit_m = all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(got_m, want_m))
    gate(bit_m, "serving real_mask layout bit-matches the composition")

    # ---- gate 2: one kernel boundary (the HBM round-trip claim) ------
    calls_one = str(jax.make_jaxpr(one)(
        track, attn, x, bc, g, seg)).count("pallas_call")
    calls_two = str(jax.make_jaxpr(two)(
        track, attn, x, bc, g, seg)).count("pallas_call")
    gate(calls_one == 1 and calls_two == 2,
         f"one-pass trace has exactly 1 pallas_call boundary "
         f"(composition {calls_two}) — inter-track activation stays "
         "in VMEM")

    # ---- gate 3: dense bit-identity (incl. an all-pad row) -----------
    bc_d, g_d = bc[:, 0, :], g[:, 0, :]
    pad = np.ones((B, L), bool)
    pad[1, :] = False
    pad = jnp.asarray(pad)
    before = dict(op.ONEPASS_PATH_TOTAL)
    got_d = op.fused_onepass_dense(track, attn, x, bc_d, g_d,
                                   pad_mask=pad, interpret=True)
    delta_d = (op.ONEPASS_PATH_TOTAL.get(("pallas", "dense"), 0)
               - before.get(("pallas", "dense"), 0))
    loc_d = fb.fused_local_track(track, x, bc_d, 1, 5, True)
    want_d = (loc_d, ka.fused_global_attention(attn, loc_d, g_d, pad,
                                               interpret=True))
    bit_d = all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(got_d, want_d))
    gate(bit_d and delta_d >= 1,
         f"dense one-pass bit-matches the dense composition on the "
         f"Pallas path (pallas/dense +{delta_d}, all-pad row keeps "
         "uniform softmax)")

    # ---- gate 4: VJP gradient parity ---------------------------------
    seg_oh = jnp.asarray(
        (np.asarray(seg)[:, :, None] == np.arange(1, S + 1)),
        jnp.float32)
    ones_real = jnp.ones((B, L, 1), jnp.float32)

    def loss_f(tp, ap, xx, bb, gg):
        lo, at = op.fused_onepass_segments(tp, ap, xx, bb, gg, seg,
                                           interpret=True)
        return jnp.sum(lo ** 2) + jnp.sum(at ** 2)

    def loss_r(tp, ap, xx, bb, gg):
        lo, at = op.onepass_oh_reference(tp, ap, xx, bb, gg, seg_oh,
                                         ones_real)
        return jnp.sum(lo ** 2) + jnp.sum(at ** 2)

    gf = jax.grad(loss_f, argnums=(0, 1, 2, 3, 4))(track, attn, x, bc, g)
    gr = jax.grad(loss_r, argnums=(0, 1, 2, 3, 4))(track, attn, x, bc, g)
    gdiff = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
                for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gr)))
    gate(gdiff <= GRAD_BOUND,
         f"custom-VJP gradient parity {gdiff:.2e} <= {GRAD_BOUND}")

    # ---- gate 5: forced-reference override ---------------------------
    os.environ[fb.FORCE_REFERENCE_ENV] = "1"
    try:
        before = dict(op.ONEPASS_PATH_TOTAL)
        # Fresh lambdas: re-jitting a cached function object would hit
        # the trace cache and skip the trace-time env read.
        got_fo = jax.jit(lambda tp, ap, xx, bb, gg: (
            op.fused_onepass_segments(tp, ap, xx, bb, gg, seg,
                                      interpret=True)))(
            track, attn, x, bc, g)
        want_fo = jax.jit(lambda tp, ap, xx, bb, gg: (
            lambda loc: (loc, ka.fused_packed_attention(
                ap, loc, gg, seg, interpret=True)))(
            fb.fused_local_track_segments(tp, xx, bb, seg, 1, 5, True)))(
            track, attn, x, bc, g)
        bumps = (op.ONEPASS_PATH_TOTAL.get(("reference", "forced"), 0)
                 - before.get(("reference", "forced"), 0))
        bit_fo = all(np.array_equal(np.asarray(a), np.asarray(b))
                     for a, b in zip(got_fo, want_fo))
        gate(bumps >= 1 and bit_fo,
             "PBT_FORCE_REFERENCE_KERNEL routes one-pass onto the "
             f"reference path (forced +{bumps}, bit_identical={bit_fo})")
    finally:
        del os.environ[fb.FORCE_REFERENCE_ENV]

    # ---- gate 6: int8 in-kernel dequant bit-identity -----------------
    qtrack, qattn = quantize_params(track), quantize_params(attn)
    dtrack, dattn = fb.dequant_params(qtrack), fb.dequant_params(qattn)
    got_q = op.fused_onepass_segments(qtrack, qattn, x, bc, g, seg,
                                      interpret=True)
    want_q = op.fused_onepass_segments(dtrack, dattn, x, bc, g, seg,
                                       interpret=True)
    bit_q = all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(got_q, want_q))
    got_qd = op.fused_onepass_dense(qtrack, qattn, x, bc_d, g_d,
                                    interpret=True)
    want_qd = op.fused_onepass_dense(dtrack, dattn, x, bc_d, g_d,
                                     interpret=True)
    bit_qd = all(np.array_equal(np.asarray(a), np.asarray(b))
                 for a, b in zip(got_qd, want_qd))
    gate(bit_q and bit_qd,
         "int8 in-kernel dequant bit-matches HLO dequant (both entries)")

    print(f"\n{len(failures)} failure(s)")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
