"""Ragged Pallas global-attention kernel (ISSUE 13 tentpole) against
the masked-XLA references in ops/attention.py. Runs in interpret mode
on the CPU test mesh; the same kernel compiles via Mosaic on TPU.

Cost discipline: ONE kernel shape (B, L, C, S) = (2, 256, 128, 4) —
L=256 so segment boundaries sit mid-row — with module-scoped params
and TWO module-level jitted entries shared by every layout, mirroring
tests/test_packing.py's fused-block suite.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from proteinbert_tpu.configs import ModelConfig
from proteinbert_tpu.kernels import attention as ka
from proteinbert_tpu.models import proteinbert
from proteinbert_tpu.ops.attention import (
    global_attention_apply,
    global_attention_init,
    packed_global_attention_apply,
)

B, L, C, S = 2, 256, 128, 4
G, KD, H = 64, 16, 4


@pytest.fixture(scope="module")
def attn_inputs():
    kp, kx, kg = jax.random.split(jax.random.PRNGKey(7), 3)
    params = global_attention_init(kp, C, G, KD, H)
    local = jax.random.normal(kx, (B, L, C), jnp.float32)
    gseg = jax.random.normal(kg, (B, S, G), jnp.float32)
    return params, local, gseg


def _seg_rows(*rows):
    """(n_rows, L) segment ids from [(segment_id, span), ...] specs —
    remaining positions stay 0 (pad)."""
    seg = np.zeros((len(rows), L), np.int32)
    for i, spans in enumerate(rows):
        pos = 0
        for sid, ln in spans:
            seg[i, pos:pos + ln] = sid
            pos += ln
    return jnp.asarray(seg)


@jax.jit
def _fused(params, x, g, seg):
    return ka.fused_packed_attention(params, x, g, seg, interpret=True)


@jax.jit
def _ref(params, x, g, seg):
    return packed_global_attention_apply(params, x, g, seg)


@jax.jit
def _fused_masked(params, x, g, seg, real):
    return ka.fused_packed_attention(params, x, g, seg, real_mask=real,
                                     interpret=True)


@jax.jit
def _ref_masked(params, x, g, seg, real):
    return packed_global_attention_apply(params, x, g, seg,
                                         real_mask=real)


LAYOUTS = {
    "single_segment_full_row": [[(1, L)], [(1, L)]],
    "max_segments": [[(1, 64), (2, 64), (3, 64), (4, 50)],
                     [(1, 30), (2, 30), (3, 30), (4, 30)]],
    "empty_tail_rows": [[(1, 100), (2, 60)], []],  # row 1 ALL pad
    "boundary_at_tile_edge": [[(1, 128), (2, 100)],
                              [(1, 128), (2, 128)]],
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_packed_parity_across_layouts(attn_inputs, layout):
    """ISSUE 13 acceptance: fused-vs-reference parity at the documented
    jitted ≤1e-5 tolerance across segment layouts, with ZERO
    reason=segments fallbacks on this supported shape."""
    params, x, g = attn_inputs
    assert ka.pallas_attention_supported(C, G, L, S, KD, H, "float32")
    seg = _seg_rows(*LAYOUTS[layout])
    before = ka.ATTN_PATH_TOTAL.get(("reference", "segments"), 0)
    got = _fused(params, x, g, seg)
    want = _ref(params, x, g, seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert ka.ATTN_PATH_TOTAL.get(("reference", "segments"), 0) == before


def test_serving_real_mask_parity(attn_inputs):
    """The ragged-serving layout: bucket-quantized spans whose tails
    hold <pad> tokens — `real_mask` must keep them out of the softmax
    exactly as the reference does (serve/dispatch.RaggedDispatcher's
    span rule)."""
    params, x, g = attn_inputs
    # Spans quantized to 64/128 buckets; the real lengths are shorter.
    seg = _seg_rows([(1, 64), (2, 128)], [(1, 128), (2, 64)])
    real = np.zeros((B, L), bool)
    real[0, :41] = True          # segment 1 real length 41 of span 64
    real[0, 64:64 + 99] = True   # segment 2 real length 99 of span 128
    real[1, :120] = True
    real[1, 128:128 + 30] = True
    real = jnp.asarray(real)
    got = _fused_masked(params, x, g, seg, real)
    want = _ref_masked(params, x, g, seg, real)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_dense_parity_and_all_pad_row(attn_inputs):
    """The dense (S=1) entry vs `global_attention_apply`, including a
    fully-padded row (a bucketed batch-class padding row): the kernel
    must keep the reference's uniform softmax there, not zero it."""
    params, x, _ = attn_inputs
    g2 = jax.random.normal(jax.random.PRNGKey(9), (B, G), jnp.float32)
    pad = np.ones((B, L), bool)
    pad[0, 200:] = False
    pad[1, :] = False  # all-pad row
    pad = jnp.asarray(pad)
    before = dict(ka.ATTN_PATH_TOTAL)
    got = jax.jit(lambda p, xx, gg, m: ka.fused_global_attention(
        p, xx, gg, m, interpret=True))(params, x, g2, pad)
    assert (ka.ATTN_PATH_TOTAL.get(("pallas", "dense"), 0)
            > before.get(("pallas", "dense"), 0))
    want = jax.jit(lambda p, xx, gg, m: global_attention_apply(
        p, xx, gg, m))(params, x, g2, pad)
    assert got.shape == (B, G)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_gradient_parity(attn_inputs):
    """The custom VJP (rematerialised oh-reference backward) against
    autodiff through the masked-XLA reference."""
    params, x, g = attn_inputs
    seg = _seg_rows([(1, 100), (2, 80)], [(1, L)])

    def loss_fused(p, xx, gg):
        return jnp.sum(ka.fused_packed_attention(
            p, xx, gg, seg, interpret=True) ** 2)

    def loss_ref(p, xx, gg):
        return jnp.sum(
            packed_global_attention_apply(p, xx, gg, seg) ** 2)

    g_fused = jax.grad(loss_fused, argnums=(0, 1, 2))(params, x, g)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(params, x, g)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4),
        g_fused, g_ref)


def test_cross_segment_leakage_bit_identical(attn_inputs):
    """Scrambling one segment's residues AND its global vector must not
    move the other segment's attention output by a single bit: masked
    scores' exp underflows to exact +0.0 and 0·V terms add exactly
    nothing (the same proof obligation as the fused block's
    `_segment_conv`)."""
    params, x, g = attn_inputs
    seg = _seg_rows([(1, 120), (2, 100)], [(1, 120), (2, 100)])
    out1 = np.asarray(_fused(params, x, g, seg))
    # Scramble segment 2's local rows and global vector.
    x2 = np.asarray(x).copy()
    x2[:, 120:220, :] = np.random.default_rng(0).normal(
        size=(B, 100, C)).astype(np.float32)
    g2 = np.asarray(g).copy()
    g2[:, 1, :] = 123.0
    out2 = np.asarray(_fused(params, jnp.asarray(x2), jnp.asarray(g2),
                             seg))
    np.testing.assert_array_equal(out1[:, 0], out2[:, 0])
    assert not np.array_equal(out1[:, 1], out2[:, 1])  # probe is live


def test_bf16_parity(attn_inputs):
    params, x, g = attn_inputs
    seg = _seg_rows([(1, 200)], [(1, 64), (2, 190)])
    got = ka.fused_packed_attention(
        params, x.astype(jnp.bfloat16), g.astype(jnp.bfloat16), seg,
        interpret=True,
    ).astype(jnp.float32)
    want = packed_global_attention_apply(
        params, x.astype(jnp.bfloat16), g.astype(jnp.bfloat16), seg
    ).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.05, atol=0.05)


def test_force_reference_env_override(attn_inputs, monkeypatch):
    """PBT_FORCE_REFERENCE_KERNEL (the kernel-family-wide debug
    override, ISSUE 13 satellite) routes the attention dispatch onto
    the reference path — bit-identical to calling the reference
    directly, counted as reason=forced."""
    from proteinbert_tpu.kernels import fused_block as fb

    params, x, g = attn_inputs
    seg = _seg_rows([(1, 200)], [(1, L)])
    monkeypatch.setenv(fb.FORCE_REFERENCE_ENV, "0")
    before = dict(ka.ATTN_PATH_TOTAL)
    _ = ka.fused_packed_attention(params, x, g, seg, interpret=True)
    assert (ka.ATTN_PATH_TOTAL.get(("reference", "forced"), 0)
            == before.get(("reference", "forced"), 0))
    # Unforced, a supported shape takes the kernel.
    assert (ka.ATTN_PATH_TOTAL.get(("pallas", "packed"), 0)
            == before.get(("pallas", "packed"), 0) + 1)
    monkeypatch.setenv(fb.FORCE_REFERENCE_ENV, "1")
    before = ka.ATTN_PATH_TOTAL.get(("reference", "forced"), 0)
    got = ka.fused_packed_attention(params, x, g, seg, interpret=True)
    assert ka.ATTN_PATH_TOTAL.get(("reference", "forced"), 0) == before + 1
    want = packed_global_attention_apply(params, x, g, seg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # The dense entry honors it too.
    g2 = jnp.zeros((B, G), jnp.float32)
    got_d = ka.fused_global_attention(params, x, g2, interpret=True)
    assert ka.ATTN_PATH_TOTAL.get(("reference", "forced"), 0) == before + 2
    np.testing.assert_array_equal(
        np.asarray(got_d),
        np.asarray(global_attention_apply(params, x, g2)))


def test_supported_gating():
    sup = ka.pallas_attention_supported
    assert sup(128, 64, 256, 4, 16, 4, "float32")
    assert sup(512, 512, 512, 8, 64, 4)          # base config, bf16
    # Attention weights are tiny — Large C=1024 prices in (the whole
    # point: no supported shape leaves the fast path).
    assert sup(1024, 512, 512, 8, 64, 4)
    assert not sup(96, 64, 256, 4, 16, 4)        # non-lane-aligned C
    assert not sup(4096, 512, 512, 8, 64, 4)     # beyond MAX_TILED_DIM
    assert not sup(128, 64, 4, 4, 16, 4)         # seq too short
    assert not sup(128, 64, 256, 0, 16, 4)       # no segments
    assert not sup(128, 63, 256, 4, 16, 4)       # G % heads != 0
    # A very long row at fp32 blows the VMEM price.
    assert not sup(512, 512, 16384, 64, 64, 4, "float32")


def test_model_level_wiring_packed_and_dense(attn_inputs):
    """block_apply routes BOTH forms through the ONE-PASS trunk
    dispatch under use_pallas (ISSUE 16): a packed forward and a dense
    forward each bump the onepass (path=pallas) counters — NOT the
    per-kernel families, which only count when the one-pass plan
    doesn't fit — and match the reference config ≤1e-5."""
    from proteinbert_tpu.kernels import one_pass as op

    cfg = ModelConfig(local_dim=C, global_dim=G, key_dim=KD, num_heads=H,
                      num_blocks=1, num_annotations=16, dtype="float32",
                      use_pallas=True)
    rcfg = ModelConfig(**{**cfg.__dict__, "use_pallas": False})
    params = proteinbert.init(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(4, 26, size=(B, L)).astype(np.int32))
    seg = _seg_rows([(1, 100), (2, 80)], [(1, L)])
    tokens = jnp.where(seg > 0, tokens, 0)
    ann = jnp.asarray((rng.random((B, S, 16)) < 0.1).astype(np.float32))
    assert op.pallas_onepass_supported(C, G, L, S, KD, H, "float32")
    before = dict(op.ONEPASS_PATH_TOTAL)
    out_f = proteinbert.apply(params, tokens, ann, cfg, segment_ids=seg)
    assert (op.ONEPASS_PATH_TOTAL.get(("pallas", "packed"), 0)
            > before.get(("pallas", "packed"), 0))
    out_r = proteinbert.apply(params, tokens, ann, rcfg, segment_ids=seg)
    for a, b in zip(out_f, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)
    # Dense (unpacked) form — the bucketed-serving executable shape.
    ann_d = jnp.asarray((rng.random((B, 16)) < 0.1).astype(np.float32))
    before = dict(op.ONEPASS_PATH_TOTAL)
    out_fd = proteinbert.apply(params, tokens, ann_d, cfg)
    assert (op.ONEPASS_PATH_TOTAL.get(("pallas", "dense"), 0)
            > before.get(("pallas", "dense"), 0))
    out_rd = proteinbert.apply(params, tokens, ann_d, rcfg)
    for a, b in zip(out_fd, out_rd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)
