"""One-pass trunk kernel (ISSUE 16 tentpole): the whole block pass —
tap-decomposed local conv track AND ragged global attention — as ONE
VMEM-resident Pallas grid program, against the TWO-KERNEL composition
it replaces (`fused_local_track_segments` → `fused_packed_attention`).
Runs in interpret mode on the CPU test mesh; the same kernel compiles
via Mosaic on TPU.

The acceptance contract is BIT-identity in interpret mode: both sides
execute the same tap matmuls / `_finish_row` / `_attention_body` in
the same fp32 order, so the fusion may not change a single ulp — any
drift means the one-pass kernel reordered the math.

Cost discipline: ONE kernel shape (B, L, C, S) = (2, 256, 128, 4) —
L=256 so segment boundaries sit mid-row — with module-scoped params
and module-level jitted entries shared by every layout, mirroring
tests/test_attention_kernel.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from proteinbert_tpu.configs import ModelConfig
from proteinbert_tpu.kernels import attention as ka
from proteinbert_tpu.kernels import fused_block as fb
from proteinbert_tpu.kernels import one_pass as op
from proteinbert_tpu.models import proteinbert
from proteinbert_tpu.parallel.quant import quantize_params

B, L, C, S = 2, 256, 128, 4
G, KD, H = 64, 16, 4

TRACK_KEYS = ("narrow_conv", "wide_conv", "local_ln1", "local_dense",
              "local_ln2")


@pytest.fixture(scope="module")
def onepass_inputs():
    cfg = ModelConfig(local_dim=C, global_dim=G, key_dim=KD, num_heads=H,
                      num_blocks=1, num_annotations=16, dtype="float32")
    block = proteinbert.block_init(jax.random.PRNGKey(7), cfg)
    track = {k: block[k] for k in TRACK_KEYS}
    attn = block["attention"]
    kx, kb, kg = jax.random.split(jax.random.PRNGKey(8), 3)
    x = jax.random.normal(kx, (B, L, C), jnp.float32)
    bcast = jax.random.normal(kb, (B, S, C), jnp.float32)
    gseg = jax.random.normal(kg, (B, S, G), jnp.float32)
    return track, attn, x, bcast, gseg


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
def _one(track, attn, x, bc, g, seg):
    return op.fused_onepass_segments(track, attn, x, bc, g, seg,
                                     interpret=True)


@jax.jit
def _two(track, attn, x, bc, g, seg):
    local = fb.fused_local_track_segments(track, x, bc, seg, 1, 5, True)
    return local, ka.fused_packed_attention(attn, local, g, seg,
                                            interpret=True)


@jax.jit
def _one_masked(track, attn, x, bc, g, seg, real):
    return op.fused_onepass_segments(track, attn, x, bc, g, seg,
                                     real_mask=real, interpret=True)


@jax.jit
def _two_masked(track, attn, x, bc, g, seg, real):
    local = fb.fused_local_track_segments(track, x, bc, seg, 1, 5, True)
    return local, ka.fused_packed_attention(attn, local, g, seg,
                                            real_mask=real,
                                            interpret=True)


@jax.jit
def _one_dense(track, attn, x, bc, g, pad):
    return op.fused_onepass_dense(track, attn, x, bc, g, pad_mask=pad,
                                  interpret=True)


@jax.jit
def _two_dense(track, attn, x, bc, g, pad):
    local = fb.fused_local_track(track, x, bc, 1, 5, True)
    return local, ka.fused_global_attention(attn, local, g, pad,
                                            interpret=True)


# The packed layout grid: the empty tail row (scheduler under-fill),
# a segment boundary AT the 128-lane tile edge, and the max-segments
# row all exercise distinct mask/one-hot corners of the shared (L, S)
# selector.
LAYOUTS = {
    "single_segment_full_row": [[(1, L)], [(1, L)]],
    "max_segments": [[(1, 64), (2, 64), (3, 64), (4, 50)],
                     [(1, 30), (2, 30), (3, 30), (4, 30)]],
    "empty_tail_rows": [[(1, 100), (2, 60)], []],  # row 1 ALL pad
    "boundary_at_tile_edge": [[(1, 128), (2, 100)],
                              [(1, 128), (2, 128)]],
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_onepass_bit_identity_across_layouts(onepass_inputs, layout):
    """ISSUE 16 acceptance: the one-pass kernel bit-matches the
    two-kernel composition on BOTH outputs across packed layouts, with
    ZERO fallbacks on this supported shape."""
    track, attn, x, bc, g = onepass_inputs
    assert op.pallas_onepass_supported(C, G, L, S, KD, H, "float32")
    seg = _seg_rows(*LAYOUTS[layout])
    before = op.ONEPASS_PATH_TOTAL.get(("reference", "segments"), 0)
    got_l, got_a = _one(track, attn, x, bc, g, seg)
    want_l, want_a = _two(track, attn, x, bc, g, seg)
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want_l))
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a))
    assert op.ONEPASS_PATH_TOTAL.get(("reference", "segments"),
                                     0) == before


@pytest.mark.parametrize("entry", ["packed", "dense"])
def test_onepass_trace_has_one_kernel_boundary(onepass_inputs, entry):
    """What the one-pass kernel is for: its trace holds exactly ONE
    `pallas_call` where the composition holds two, so the activation
    between the tracks never leaves VMEM for HBM."""
    track, attn, x, bc, g = onepass_inputs
    if entry == "packed":
        args = (track, attn, x, bc, g, _seg_rows([(1, 100), (2, 80)],
                                                 [(1, L)]))
        one, two = _one, _two
    else:
        args = (track, attn, x, bc[:, 0, :], g[:, 0, :],
                jnp.ones((B, L), bool))
        one, two = _one_dense, _two_dense
    assert str(jax.make_jaxpr(one)(*args)).count("pallas_call") == 1
    assert str(jax.make_jaxpr(two)(*args)).count("pallas_call") == 2


def test_cross_segment_leakage_is_plus_zero(onepass_inputs):
    """The exact +0.0 cross-segment contract: perturbing every token of
    segment 2 must leave segment 1's local-track rows and attention
    vector BIT-unchanged (not just close) — same discipline as the
    constituent kernels' leakage tests."""
    track, attn, x, bc, g = onepass_inputs
    seg = _seg_rows([(1, 100), (2, 120)], [(1, L)])
    l0, a0 = _one(track, attn, x, bc, g, seg)
    bump = jnp.where((np.asarray(seg[0]) == 2)[None, :, None],
                     jnp.float32(17.0), 0.0)
    x2 = x.at[0].add(bump[0])
    l1, a1 = _one(track, attn, x2, bc, g, seg)
    # Segment 1 spans positions [0, 100); the wide-conv halo reaches
    # 20 positions, so rows [0, 80) see NO perturbed input at all.
    np.testing.assert_array_equal(np.asarray(l0[0, :80]),
                                  np.asarray(l1[0, :80]))
    np.testing.assert_array_equal(np.asarray(a0[0, 0]),
                                  np.asarray(a1[0, 0]))
    np.testing.assert_array_equal(np.asarray(l0[1]), np.asarray(l1[1]))


def test_serving_real_mask_bit_identity(onepass_inputs):
    """The ragged-serving layout: bucket-quantized spans whose tails
    hold <pad> tokens. `real_mask` narrows the ATTENTION mask exactly
    as the two-kernel path does, while the conv track still sees the
    full span (the dispatcher's span rule) — bit-identical on both
    outputs."""
    track, attn, x, bc, g = onepass_inputs
    seg = _seg_rows([(1, 64), (2, 128)], [(1, 128), (2, 64)])
    real = np.zeros((B, L), bool)
    real[0, :41] = True          # segment 1 real length 41 of span 64
    real[0, 64:64 + 99] = True   # segment 2 real length 99 of span 128
    real[1, :120] = True
    real[1, 128:128 + 30] = True
    real = jnp.asarray(real)
    got_l, got_a = _one_masked(track, attn, x, bc, g, seg, real)
    want_l, want_a = _two_masked(track, attn, x, bc, g, seg, real)
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want_l))
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a))


def test_dense_entry_bit_identity_with_all_pad_row(onepass_inputs):
    """The dense (S=1) entry vs the dense two-kernel composition,
    including a fully-padded row (a bucketed batch-class padding row):
    the kernel must keep the reference's uniform softmax there."""
    track, attn, x, bc, g = onepass_inputs
    bc_d, g_d = bc[:, 0, :], g[:, 0, :]
    pad = np.ones((B, L), bool)
    pad[0, 200:] = False
    pad[1, :] = False  # all-pad row
    pad = jnp.asarray(pad)
    before = dict(op.ONEPASS_PATH_TOTAL)
    got_l, got_a = _one_dense(track, attn, x, bc_d, g_d, pad)
    assert (op.ONEPASS_PATH_TOTAL.get(("pallas", "dense"), 0)
            >= before.get(("pallas", "dense"), 0))
    want_l, want_a = _two_dense(track, attn, x, bc_d, g_d, pad)
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want_l))
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a))
    assert got_a.shape == (B, G)


def test_gradient_parity(onepass_inputs):
    """The custom VJP (rematerialised oh-reference backward, matching
    the fused-block remat policy) against autodiff through the plain
    one-hot reference — the same 1e-4 tolerance as the constituent
    kernels' gradient tests (the two backwards run the same math in
    different XLA fusion contexts)."""
    track, attn, x, bc, g = onepass_inputs
    seg = _seg_rows([(1, 100), (2, 80)], [(1, L)])
    seg_oh = jnp.asarray(
        (np.asarray(seg)[:, :, None] == np.arange(1, S + 1)),
        jnp.float32)
    real = jnp.ones((B, L, 1), jnp.float32)

    def loss_one(tp, ap, xx, bb, gg):
        local, a = op.fused_onepass_segments(tp, ap, xx, bb, gg, seg,
                                             interpret=True)
        return jnp.sum(local ** 2) + jnp.sum(a ** 2)

    def loss_ref(tp, ap, xx, bb, gg):
        local, a = op.onepass_oh_reference(tp, ap, xx, bb, gg, seg_oh,
                                           real)
        return jnp.sum(local ** 2) + jnp.sum(a ** 2)

    g_one = jax.grad(loss_one, argnums=(0, 1, 2, 3, 4))(
        track, attn, x, bc, g)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(
        track, attn, x, bc, g)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4),
        g_one, g_ref)


def test_force_reference_env_override_both_entries(onepass_inputs,
                                                   monkeypatch):
    """PBT_FORCE_REFERENCE_KERNEL routes BOTH one-pass entries onto the
    reference composition — counted reason=forced on the onepass family
    and bit-identical to the forced composition (both land on the same
    XLA reference code). Fresh jits per probe: a re-jit of a cached
    function would skip the trace-time env read."""
    track, attn, x, bc, g = onepass_inputs
    seg = _seg_rows([(1, 200)], [(1, L)])
    monkeypatch.setenv(fb.FORCE_REFERENCE_ENV, "1")
    assert fb.force_reference_requested()

    before = op.ONEPASS_PATH_TOTAL.get(("reference", "forced"), 0)
    got = jax.jit(lambda tp, ap, xx, bb, gg: op.fused_onepass_segments(
        tp, ap, xx, bb, gg, seg, interpret=True))(track, attn, x, bc, g)
    assert op.ONEPASS_PATH_TOTAL.get(("reference", "forced"),
                                     0) == before + 1
    want = jax.jit(lambda tp, ap, xx, bb, gg: (
        lambda local: (local, ka.fused_packed_attention(
            ap, local, gg, seg, interpret=True)))(
        fb.fused_local_track_segments(tp, xx, bb, seg, 1, 5, True)))(
        track, attn, x, bc, g)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    bc_d, g_d = bc[:, 0, :], g[:, 0, :]
    before = op.ONEPASS_PATH_TOTAL.get(("reference", "forced"), 0)
    got_d = jax.jit(lambda tp, ap, xx, bb, gg: op.fused_onepass_dense(
        tp, ap, xx, bb, gg, interpret=True))(track, attn, x, bc_d, g_d)
    assert op.ONEPASS_PATH_TOTAL.get(("reference", "forced"),
                                     0) == before + 1
    # `fused_local_track` is the raw kernel (no force check of its
    # own — the dispatch above it owns that), so the forced dense
    # composition is the XLA reference directly.
    want_d = jax.jit(lambda tp, ap, xx, bb, gg: (
        lambda local: (local, ka.fused_global_attention(
            ap, local, gg, interpret=True)))(
        fb.local_track_reference(tp, xx, bb, 1, 5)))(
        track, attn, x, bc_d, g_d)
    for a, b in zip(got_d, want_d):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_int8_inkernel_dequant_bit_matches_hlo_dequant(onepass_inputs):
    """The int8 leg (ISSUE 16 second leg): the one-pass kernel loading
    `quantize_params`' int8 weights + per-channel scales into VMEM and
    dequantizing IN-KERNEL must produce bit-identical outputs to
    HLO-dequantizing the same quant tree first (`dequant_params`) and
    running the fp32 kernel — the dequant expression is the same
    `(q.astype(f32) * scale)` either way, so moving it inside the grid
    program may not change a single bit. Covers BOTH entries."""
    track, attn, x, bc, g = onepass_inputs
    qtrack, qattn = quantize_params(track), quantize_params(attn)
    assert fb.is_quant_leaf(qtrack["narrow_conv"]["kernel"])
    assert fb.is_quant_leaf(qattn["wq"])
    dtrack, dattn = fb.dequant_params(qtrack), fb.dequant_params(qattn)

    seg = _seg_rows([(1, 64), (2, 128)], [(1, 128), (2, 64)])
    before = dict(op.ONEPASS_PATH_TOTAL)
    got = _one(qtrack, qattn, x, bc, g, seg)
    assert (op.ONEPASS_PATH_TOTAL.get(("pallas", "packed"), 0)
            > before.get(("pallas", "packed"), 0))
    want = _one(dtrack, dattn, x, bc, g, seg)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    bc_d, g_d = bc[:, 0, :], g[:, 0, :]
    got_d = _one_dense(qtrack, qattn, x, bc_d, g_d, None)
    want_d = _one_dense(dtrack, dattn, x, bc_d, g_d, None)
    for a, b in zip(got_d, want_d):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
