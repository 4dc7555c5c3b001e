"""Numerical parity of the Pallas fused local-track kernel vs the plain
jax.nn composition (SURVEY §4: "numerical parity tests of the Pallas fused
block against the plain jax.nn composition"). Runs in interpret mode on the
CPU test mesh; the same kernel compiles via Mosaic on TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proteinbert_tpu.configs import ModelConfig
from proteinbert_tpu.kernels import (
    fused_local_track,
    local_track_reference,
    pallas_supported,
)
from proteinbert_tpu.models import proteinbert


def _make_inputs(key, B=2, L=128, C=128, G=64, dtype=jnp.float32):
    cfg = ModelConfig(local_dim=C, global_dim=G, key_dim=16, num_heads=4,
                      num_blocks=1, num_annotations=32, dtype=str(dtype.dtype.name)
                      if hasattr(dtype, "dtype") else "float32")
    kp, kx, kb = jax.random.split(key, 3)
    block = proteinbert.block_init(kp, cfg)
    params = {k: block[k] for k in ("narrow_conv", "wide_conv", "local_ln1",
                                    "local_dense", "local_ln2")}
    x = jax.random.normal(kx, (B, L, C), dtype)
    bcast = jax.random.normal(kb, (B, C), dtype)
    return params, x, bcast


def test_forward_parity_fp32(key):
    params, x, bcast = _make_inputs(key)
    got = fused_local_track(params, x, bcast, 1, 5, True)
    want = local_track_reference(params, x, bcast, 1, 5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_forward_parity_tiled(key):
    # L=256 with tile 128 exercises the multi-tile grid + halo windows.
    params, x, bcast = _make_inputs(key, B=1, L=256, C=128)
    got = fused_local_track(params, x, bcast, 1, 5, True)
    want = local_track_reference(params, x, bcast, 1, 5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_forward_parity_bf16(key):
    params, x, bcast = _make_inputs(key, dtype=jnp.bfloat16)
    got = fused_local_track(params, x, bcast, 1, 5, True).astype(jnp.float32)
    want = local_track_reference(params, x, bcast, 1, 5).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.05, atol=0.05)


def test_gradient_parity(key):
    params, x, bcast = _make_inputs(key, B=1, L=64, C=128)

    def loss_fused(p, xx, bb):
        return jnp.sum(fused_local_track(p, xx, bb, 1, 5, True) ** 2)

    def loss_ref(p, xx, bb):
        return jnp.sum(local_track_reference(p, xx, bb, 1, 5) ** 2)

    g_fused = jax.grad(loss_fused, argnums=(0, 1, 2))(params, x, bcast)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(params, x, bcast)
    # Backward recomputes the reference composition; the only forward-path
    # difference is the kernel's fp32 residual accumulation feeding the
    # output cotangent, so tolerances stay tight in fp32.
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
        ),
        g_fused, g_ref,
    )


def test_model_level_parity(key):
    cfg = ModelConfig(local_dim=128, global_dim=64, key_dim=16, num_heads=4,
                      num_blocks=2, num_annotations=32, dtype="float32")
    params = proteinbert.init(key, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 4, 26)
    ann = (jax.random.uniform(jax.random.PRNGKey(2), (2, 32)) < 0.1
           ).astype(jnp.float32)

    plain_l, plain_g = proteinbert.apply(params, tokens, ann, cfg)
    pcfg = ModelConfig(**{**cfg.__dict__, "use_pallas": True})
    fused_l, fused_g = proteinbert.apply(params, tokens, ann, pcfg)
    np.testing.assert_allclose(np.asarray(fused_l), np.asarray(plain_l),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(fused_g), np.asarray(plain_g),
                               rtol=1e-4, atol=1e-4)


def test_pallas_supported_gating():
    assert pallas_supported(128, 256)
    assert pallas_supported(512, 512)               # base config, bf16
    assert pallas_supported(1024, 512)              # Large → channel-tiled
    assert not pallas_supported(1024, 512, "float32")  # fp32 tiled plan: no
    assert not pallas_supported(4096, 512)          # beyond MAX_TILED_DIM
    assert not pallas_supported(96, 256)            # non-lane-aligned C
    assert not pallas_supported(512, 512, "float32")  # fp32 weights blow VMEM
    assert pallas_supported(128, 64, "float32")     # small fp32 is fine
    # Unsharded long rows keep the whole padded row in VMEM — too big at
    # C=512; the seq-sharded per-shard length (2048/4=512) is what the
    # kernel sees under the long preset, and that fits.
    assert not pallas_supported(512, 2048)
    assert pallas_supported(512, 2048 // 4)


# (local_dim, seq_len, max_segments[, dtype], {taps}) -> supported. One
# case a decision, so each counts; the last three are ISSUE 42's: the
# serving row (1024 x 512, bfloat16) has a plan at a tile of 256.
SEGMENT_GATING = [
    ((128, 256, 8, "float32"), {}, True),
    ((512, 512, 8), {}, True),            # base config, bf16
    ((96, 256, 8), {}, False),            # non-lane-aligned C
    # Channel-tiled SEGMENT variant (ISSUE 13): Large C=1024 packed
    # rows run the fast path instead of falling back with
    # reason="segments"…
    ((1024, 512, 8), {}, True),
    # …but the fp32 tiled plan still has no room, like the dense one,
    # and nothing exceeds MAX_TILED_DIM.
    ((1024, 512, 8, "float32"), {}, False),
    ((4096, 512, 8), {}, False),
    ((512, 512, 8, "float32"), {}, False),  # VMEM
    ((128, 4, 2), {}, False),             # seq too short
    ((128, 256, 0), {}, False),           # no segments
    # Even tap counts break the symmetric-halo tap layout.
    ((128, 256, 8, "float32"), {"narrow_taps": 8}, False),
    # The one-hot row block is priced in: the dense kernel fits this
    # long-row bf16 shape, the segment kernel must still fit too (the
    # oh block is lane-padded but small next to the weights).
    ((256, 1024, 16), {}, True),
    ((512, 1024, 1), {}, True),
    ((512, 1024, 8), {}, True),
    ((512, 1024, 16), {}, True),
]


@pytest.mark.parametrize(
    "shape,taps,want", SEGMENT_GATING,
    ids=["-".join(map(str, sh)) + ("-even_taps" if kw else "")
         for sh, kw, _ in SEGMENT_GATING])
def test_pallas_segments_supported_gating(shape, taps, want):
    from proteinbert_tpu.kernels import pallas_segments_supported

    assert pallas_segments_supported(*shape, **taps) is want


def test_train_step_with_pallas(key):
    """One jitted train step with the fused kernel end to end."""
    from proteinbert_tpu.configs import (
        DataConfig, OptimizerConfig, PretrainConfig, TrainConfig,
    )
    from proteinbert_tpu.train import create_train_state, train_step

    cfg = PretrainConfig(
        model=ModelConfig(local_dim=128, global_dim=64, key_dim=16,
                          num_heads=4, num_blocks=2, num_annotations=32,
                          dtype="float32", use_pallas=True),
        data=DataConfig(seq_len=64, batch_size=2),
        optimizer=OptimizerConfig(warmup_steps=10),
        train=TrainConfig(max_steps=1),
    )
    state = create_train_state(key, cfg)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": rng.integers(4, 26, size=(2, 64)).astype(np.int32),
        "annotations": (rng.random((2, 32)) < 0.1).astype(np.float32),
    }
    new_state, metrics = train_step(state, batch, cfg)
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_state.step) == 1


# ------------------------------------------- channel-tiled variant (C>512)

def test_tiled_forward_parity_c1024(key):
    """Large-config C=1024 runs the channel-tiled kernel (scratch
    accumulation over the c grid axis). fp32 has no tiled VMEM plan, so
    parity runs in bf16 — the config the Large preset actually trains —
    with bf16-appropriate tolerances against the reference composition."""
    params, x, bcast = _make_inputs(key, B=1, L=128, C=1024,
                                    dtype=jnp.bfloat16)
    assert pallas_supported(1024, 128)
    got = fused_local_track(params, x, bcast, 1, 5, True).astype(jnp.float32)
    want = local_track_reference(params, x, bcast, 1, 5).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.05, atol=0.05)


def test_tiled_multi_l_tiles_and_batch(key):
    """Multiple L tiles AND batch entries: the fp32 scratch row must be
    fully overwritten per (b, l) step — stale columns from the previous
    grid step would show up as cross-tile leakage."""
    params, x, bcast = _make_inputs(key, B=2, L=256, C=1024,
                                    dtype=jnp.bfloat16)
    got = fused_local_track(params, x, bcast, 1, 5, True).astype(jnp.float32)
    want = local_track_reference(params, x, bcast, 1, 5).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.05, atol=0.05)


def test_tiled_gradient_parity(key):
    params, x, bcast = _make_inputs(key, B=1, L=64, C=1024,
                                    dtype=jnp.bfloat16)

    def f_fused(p, xx, bb):
        return (fused_local_track(p, xx, bb, 1, 5, True)
                .astype(jnp.float32).sum())

    def f_ref(p, xx, bb):
        return (local_track_reference(p, xx, bb, 1, 5)
                .astype(jnp.float32).sum())

    g_fused = jax.grad(f_fused, argnums=(0, 1, 2))(params, x, bcast)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(params, x, bcast)
    for a, b in zip(jax.tree.leaves(g_fused), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=0.05, atol=0.1)


def test_tiled_plan_details():
    from proteinbert_tpu.kernels.fused_block import _plan_tiled

    # Large preset, unsharded L=512: fits via the narrower L tile.
    tc, tile = _plan_tiled(1024, 512, "bfloat16")
    assert tc == 128 and tile == 128
    # Unequal tap counts can't use the stacked phase layout → no plan.
    assert _plan_tiled(1024, 512, "bfloat16", narrow_taps=9,
                       wide_taps=5)[0] == 0
    # The weights-resident order (full-row fp32 scratch) fits at Large
    # L=512 — the order the kernel actually runs there...
    assert _plan_tiled(1024, 512, "bfloat16", resident=True) == (128, 128)
    # ...but not at long L, where only the per-row order has a plan.
    assert _plan_tiled(640, 2048, "bfloat16", resident=True)[0] == 0
    assert _plan_tiled(640, 2048, "bfloat16") == (128, 128)


def test_tiled_per_row_order_parity(key):
    """C=640/L=2048 has no weights-resident plan (full-row scratch blows
    VMEM), so this shape exercises the per-row fallback grid order."""
    from proteinbert_tpu.kernels.fused_block import _plan_tiled

    assert _plan_tiled(640, 2048, "bfloat16", resident=True)[0] == 0
    assert pallas_supported(640, 2048)
    params, x, bcast = _make_inputs(key, B=1, L=2048, C=640,
                                    dtype=jnp.bfloat16)
    got = fused_local_track(params, x, bcast, 1, 5, True).astype(jnp.float32)
    want = local_track_reference(params, x, bcast, 1, 5).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.05, atol=0.05)


def test_tiled_unequal_taps_falls_back_to_xla(key):
    """pallas_supported must refuse the stacked layout when the convs
    have different tap counts (the model then runs the XLA path)."""
    assert not pallas_supported(1024, 128, narrow_taps=9, wide_taps=5)


def test_tiled_prehaloed_parity(key):
    """The seq-parallel pre-haloed variant also routes through the tiled
    kernel at C=1024 (real halo rows, VALID output center)."""
    from proteinbert_tpu.kernels import (
        fused_local_track_valid, local_track_valid_reference, track_halo,
    )

    params, _, bcast = _make_inputs(key, B=1, L=64, C=1024,
                                    dtype=jnp.bfloat16)
    H = track_halo(params, 1, 5)
    xh = jax.random.normal(jax.random.PRNGKey(3), (1, 64 + 2 * H, 1024),
                           jnp.bfloat16)
    got = fused_local_track_valid(params, xh, bcast, 1, 5, True
                                  ).astype(jnp.float32)
    want = local_track_valid_reference(params, xh, bcast, 1, 5
                                       ).astype(jnp.float32)
    assert got.shape == (1, 64, 1024)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.05, atol=0.05)


# ------------------------------- channel-tiled SEGMENT variant (ISSUE 13)
# The C>512 packed fast path: same grid orders as the dense tiled
# kernel, segment one-hot operands folded in. Shapes here mirror the
# dense tiled tier so interpret cost stays bounded.

def _make_segment_inputs(key, B=1, L=128, C=1024, S=4,
                         dtype=jnp.bfloat16):
    params, x, _ = _make_inputs(key, B=B, L=L, C=C, dtype=dtype)
    bc = jax.random.normal(jax.random.PRNGKey(11), (B, S, C), dtype)
    rng = np.random.default_rng(5)
    seg = np.zeros((B, L), np.int32)
    for b in range(B):
        pos = 0
        for sid in range(1, S + 1):
            ln = int(rng.integers(8, max(9, L // S)))
            if pos + ln > L:
                break
            seg[b, pos:pos + ln] = sid
            pos += ln
    return params, x, bc, jnp.asarray(seg)


def test_tiled_segment_forward_parity_c1024(key):
    """Large-config C=1024 PACKED rows run the channel-tiled segment
    kernel instead of falling back with reason=segments (ISSUE 13
    acceptance). bf16 like the dense tiled tier (fp32 has no plan)."""
    from proteinbert_tpu.kernels import (
        fused_local_track_segments, gather_segment_broadcast,
        local_track_segment_reference, pallas_segments_supported,
    )
    from proteinbert_tpu.kernels import fused_block as fb

    params, x, bc, seg = _make_segment_inputs(key)
    assert pallas_segments_supported(1024, 128, 4)
    before = dict(fb.PATH_TOTAL)
    got = fused_local_track_segments(params, x, bc, seg, 1, 5, True
                                     ).astype(jnp.float32)
    assert (fb.PATH_TOTAL.get(("pallas", "packed"), 0)
            > before.get(("pallas", "packed"), 0))
    assert (fb.PATH_TOTAL.get(("reference", "segments"), 0)
            == before.get(("reference", "segments"), 0))
    want = local_track_segment_reference(
        params, x, gather_segment_broadcast(bc, seg), seg, 1, 5
    ).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.05, atol=0.05)


def test_tiled_segment_multi_l_tiles_and_batch(key):
    """Multiple L tiles AND batch entries with a segment boundary at
    the tile edge: the fp32 scratch row must be fully overwritten per
    step and the one-hot masks must track the (b, j) window."""
    from proteinbert_tpu.kernels import (
        fused_local_track_segments, gather_segment_broadcast,
        local_track_segment_reference,
    )

    params, x, _, _ = _make_segment_inputs(key, B=2, L=256)
    bc = jax.random.normal(jax.random.PRNGKey(12), (2, 3, 1024),
                           jnp.bfloat16)
    seg = np.zeros((2, 256), np.int32)
    seg[0, :128] = 1
    seg[0, 128:220] = 2   # boundary exactly at the 128 tile edge
    seg[1, :100] = 1
    seg[1, 100:256] = 3
    seg = jnp.asarray(seg)
    got = fused_local_track_segments(params, x, bc, seg, 1, 5, True
                                     ).astype(jnp.float32)
    want = local_track_segment_reference(
        params, x, gather_segment_broadcast(bc, seg), seg, 1, 5
    ).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.05, atol=0.05)


def test_tiled_segment_gradient_parity(key):
    """The existing custom VJP wraps whichever forward variant runs —
    the tiled segment path must keep the rematerialised oh-reference
    backward contract."""
    from proteinbert_tpu.kernels import fused_block as fb

    params, x, bc, seg = _make_segment_inputs(key, L=64)

    def f_fused(p, xx, bb):
        return (fb.fused_local_track_segments(p, xx, bb, seg, 1, 5, True)
                .astype(jnp.float32).sum())

    def f_ref(p, xx, bb):
        return (fb.local_track_segment_reference(
            p, xx, fb.gather_segment_broadcast(bb, seg), seg, 1, 5)
            .astype(jnp.float32).sum())

    g_fused = jax.grad(f_fused, argnums=(0, 1, 2))(params, x, bc)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(params, x, bc)
    for a, b in zip(jax.tree.leaves(g_fused), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=0.05, atol=0.1)


def test_tiled_segment_plan_details():
    from proteinbert_tpu.kernels.fused_block import _plan_tiled

    # Large preset packed: the per-row order has a plan at L=512; the
    # weights-resident order's full-row fp32 scratch only fits once
    # the one-hot/bcast extras shrink with L (the forward prefers
    # resident when it fits, per-row otherwise — both orders run).
    assert _plan_tiled(1024, 512, "bfloat16",
                       max_segments=8) == (128, 128)
    assert _plan_tiled(1024, 512, "bfloat16", resident=True,
                       max_segments=8)[0] == 0
    assert _plan_tiled(1024, 128, "bfloat16", resident=True,
                       max_segments=4) == (128, 128)
    # Long rows: only the per-row order fits (same shape family as the
    # dense tier's per-row case).
    assert _plan_tiled(640, 2048, "bfloat16", resident=True,
                       max_segments=16)[0] == 0
    assert _plan_tiled(640, 2048, "bfloat16",
                       max_segments=16) == (128, 128)
    # The one-hot/bcast price is real: a plan that fits dense can still
    # refuse segments when S is enormous.
    assert _plan_tiled(1024, 512, "bfloat16")[0] > 0
    assert _plan_tiled(1024, 512, "bfloat16", max_segments=4096)[0] == 0


def test_tiled_segment_per_row_order_parity(key):
    """C=640/L=2048 has no weights-resident segment plan (full-row
    scratch blows VMEM) — exercises the per-row fallback grid order of
    the SEGMENT kernel."""
    from proteinbert_tpu.kernels import (
        fused_local_track_segments, gather_segment_broadcast,
        local_track_segment_reference, pallas_segments_supported,
    )
    from proteinbert_tpu.kernels.fused_block import _plan_tiled

    assert _plan_tiled(640, 2048, "bfloat16", resident=True,
                       max_segments=2)[0] == 0
    assert pallas_segments_supported(640, 2048, 2)
    params, x, _ = _make_inputs(key, B=1, L=2048, C=640,
                                dtype=jnp.bfloat16)
    bc = jax.random.normal(jax.random.PRNGKey(13), (1, 2, 640),
                           jnp.bfloat16)
    seg = np.zeros((1, 2048), np.int32)
    seg[0, :1200] = 1
    seg[0, 1200:2000] = 2
    seg = jnp.asarray(seg)
    got = fused_local_track_segments(params, x, bc, seg, 1, 5, True
                                     ).astype(jnp.float32)
    want = local_track_segment_reference(
        params, x, gather_segment_broadcast(bc, seg), seg, 1, 5
    ).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.05, atol=0.05)
