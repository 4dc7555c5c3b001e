"""Explicit sequence-parallel path vs the unsharded model (SURVEY §7
stage 10): shard_map forward/gradients, distributed softmax, pre-haloed
fused-track variants — all on the 8-device CPU mesh."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proteinbert_tpu.configs import (
    DataConfig, MeshConfig, ModelConfig, OptimizerConfig, PretrainConfig,
    TrainConfig,
)
from proteinbert_tpu.kernels import (
    fused_local_track_valid, local_track_reference,
    local_track_valid_reference, track_halo,
)
from proteinbert_tpu.models import proteinbert
from proteinbert_tpu.parallel import make_mesh
from proteinbert_tpu.parallel.seq_parallel import (
    make_seq_parallel_train_step, seq_parallel_apply,
)

requires_8 = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 (virtual) devices"
)

MODEL = ModelConfig(local_dim=16, global_dim=32, key_dim=8, num_heads=4,
                    num_blocks=2, num_annotations=64, dtype="float32")


def _inputs(key, B=4, L=128, A=64):
    kt, ka = jax.random.split(key)
    tokens = np.array(jax.random.randint(kt, (B, L), 4, 26))
    # Real padding tails so the distributed softmax's masking is exercised.
    tokens[:, L - 16:] = 0
    ann = np.asarray(
        (jax.random.uniform(ka, (B, A)) < 0.1).astype(np.float32))
    return jnp.asarray(tokens), jnp.asarray(ann)


def test_valid_reference_matches_same_padding(key):
    """Center rows of the pre-haloed VALID track == zero-padded track when
    the halo rows really are zeros."""
    kp, kx, kb = jax.random.split(key, 3)
    block = proteinbert.block_init(kp, MODEL)
    track = {k: block[k] for k in ("narrow_conv", "wide_conv", "local_ln1",
                                   "local_dense", "local_ln2")}
    x = jax.random.normal(kx, (2, 64, MODEL.local_dim))
    b = jax.random.normal(kb, (2, MODEL.local_dim))
    H = track_halo(track, 1, MODEL.wide_dilation)
    xh = jnp.pad(x, ((0, 0), (H, H), (0, 0)))
    got = local_track_valid_reference(track, xh, b, 1, MODEL.wide_dilation)
    want = local_track_reference(track, x, b, 1, MODEL.wide_dilation)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fused_valid_kernel_parity(key):
    """Pallas pre-haloed kernel == VALID reference (with REAL halo rows)."""
    C = 128
    cfg = dataclasses.replace(MODEL, local_dim=C)
    kp, kx, kb = jax.random.split(key, 3)
    block = proteinbert.block_init(kp, cfg)
    track = {k: block[k] for k in ("narrow_conv", "wide_conv", "local_ln1",
                                   "local_dense", "local_ln2")}
    H = track_halo(track, 1, cfg.wide_dilation)
    xh = jax.random.normal(kx, (2, 64 + 2 * H, C))  # halos are real data
    b = jax.random.normal(kb, (2, C))
    got = fused_local_track_valid(track, xh, b, 1, cfg.wide_dilation, True)
    want = local_track_valid_reference(track, xh, b, 1, cfg.wide_dilation)
    assert got.shape == (2, 64, C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@requires_8
@pytest.mark.parametrize("unroll", [1, 2], ids=["u1", "u2"])
@pytest.mark.parametrize("mesh_cfg", [
    MeshConfig(data=2, seq=4),
    MeshConfig(data=2, fsdp=2, seq=2),
], ids=["dp-sp4", "dp-fsdp-sp2"])
def test_seq_parallel_forward_parity(key, mesh_cfg, unroll):
    # unroll=2 covers scan_unroll coexisting with the per-block halo
    # exchange + distributed-softmax collectives inside shard_map.
    model = dataclasses.replace(MODEL, scan_unroll=unroll)
    mesh = make_mesh(mesh_cfg)
    params = proteinbert.init(key, model)
    tokens, ann = _inputs(jax.random.fold_in(key, 1))
    want_l, want_g = proteinbert.apply(params, tokens, ann, MODEL)
    got_l, got_g = seq_parallel_apply(mesh, params, tokens, ann, model)
    np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g),
                               rtol=2e-5, atol=2e-5)


@requires_8
@pytest.mark.parametrize("variant", ["u1", "u2", "st"])
def test_seq_parallel_gradient_parity(key, variant):
    # u2 and st run under remat-convs (an unrolled scan body / a
    # _split_transpose'd scan under shard_map); a grad regression there
    # is invisible to the forward-parity test.
    model = dataclasses.replace(
        MODEL,
        scan_unroll=2 if variant == "u2" else 1,
        scan_split_transpose=variant == "st",
        remat=variant != "u1",
        remat_policy="full" if variant == "u1" else "convs")
    mesh = make_mesh(MeshConfig(data=2, seq=4))
    params = proteinbert.init(key, model)
    tokens, ann = _inputs(jax.random.fold_in(key, 1))

    def loss_sharded(p):
        l, g = seq_parallel_apply(mesh, p, tokens, ann, model)
        return jnp.sum(l ** 2) + jnp.sum(g ** 2)

    def loss_plain(p):
        l, g = proteinbert.apply(p, tokens, ann, model)
        return jnp.sum(l ** 2) + jnp.sum(g ** 2)

    g_sharded = jax.grad(loss_sharded)(params)
    g_plain = jax.grad(loss_plain)(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4),
        g_sharded, g_plain,
    )


@requires_8
def test_seq_parallel_train_step(key):
    """Full seq-parallel train step (with the fused Pallas local track in
    interpret mode) matches the default train step's loss."""
    from proteinbert_tpu.parallel import batch_sharding, shard_train_state
    from proteinbert_tpu.train import create_train_state, train_step

    model = dataclasses.replace(MODEL, local_dim=128, use_pallas=True)
    mesh_cfg = MeshConfig(data=2, seq=4)
    cfg = PretrainConfig(
        model=model,
        data=DataConfig(seq_len=128, batch_size=4),
        optimizer=OptimizerConfig(warmup_steps=10),
        mesh=mesh_cfg,
        train=TrainConfig(max_steps=1),
    )
    tokens, ann = _inputs(jax.random.fold_in(key, 2), B=4, L=128,
                          A=model.num_annotations)
    batch = {"tokens": np.asarray(tokens), "annotations": np.asarray(ann)}

    ref_state, ref_metrics = train_step(
        create_train_state(jax.random.PRNGKey(0), cfg), dict(batch), cfg)

    mesh = make_mesh(mesh_cfg)
    state = shard_train_state(
        create_train_state(jax.random.PRNGKey(0), cfg), mesh)
    bsh = batch_sharding(mesh)
    dbatch = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
    step = make_seq_parallel_train_step(mesh, cfg)
    new_state, metrics = step(state, dbatch)

    assert float(metrics["loss"]) == pytest.approx(
        float(ref_metrics["loss"]), rel=1e-4)
    assert int(jax.device_get(new_state.step)) == 1
    for r, g in zip(jax.tree.leaves(ref_state.params),
                    jax.tree.leaves(new_state.params)):
        np.testing.assert_allclose(np.asarray(r),
                                   np.asarray(jax.device_get(g)), atol=1e-4)


@requires_8
def test_long_preset_miniature_h5_bucketed_seq_parallel(key, tmp_path):
    """The `long` preset's machinery end to end, miniaturized: an HDF5
    corpus with mixed lengths → counter-based crops → length-bucketed
    per-host batches → the EXPLICIT seq-parallel train step on a
    {data:2, seq:4} mesh — each emitted bucket shape must produce the
    same loss as the default (implicit-SPMD) step on the identical
    batch. Binds together the pieces the long config uses that are
    otherwise only tested separately."""
    import h5py

    from proteinbert_tpu.data.dataset import (
        HDF5PretrainingDataset, make_bucketed_iterator,
    )
    from proteinbert_tpu.train import create_train_state, train_step

    rng = np.random.default_rng(0)
    N, A = 64, MODEL.num_annotations
    seqs = []
    for i in range(N):
        n = int(rng.integers(5, 28)) if i % 2 else int(rng.integers(80, 200))
        seqs.append("".join(rng.choice(list("ACDEFGHIKLMNPQRSTVWY"), size=n)))
    path = tmp_path / "mini.h5"
    with h5py.File(path, "w") as f:
        sd = h5py.string_dtype()
        f.create_dataset("seqs", data=np.array(seqs, dtype=object), dtype=sd)
        f.create_dataset("uniprot_ids",
                         data=np.array([f"P{i}" for i in range(N)],
                                       dtype=object), dtype=sd)
        f.create_dataset("seq_lengths",
                         data=np.array([len(s) for s in seqs], np.int32))
        f.create_dataset("annotation_masks",
                         data=rng.random((N, A)) < 0.1)
        f.create_dataset("included_annotations",
                         data=np.array([f"GO:{i:07d}" for i in range(A)],
                                       dtype=object), dtype=sd)

    mesh_cfg = MeshConfig(data=2, seq=4)
    cfg = PretrainConfig(
        model=MODEL,
        data=DataConfig(seq_len=128, batch_size=4, buckets=(32, 128)),
        optimizer=OptimizerConfig(warmup_steps=10),
        mesh=mesh_cfg,
        train=TrainConfig(max_steps=4),
    )
    mesh = make_mesh(mesh_cfg)
    sstep = make_seq_parallel_train_step(mesh, cfg)

    ds = HDF5PretrainingDataset(str(path), cfg.data.seq_len, crop_seed=5)
    it = make_bucketed_iterator(ds, cfg.data.batch_size, cfg.data.buckets,
                                seed=3, num_epochs=1)
    widths_seen = set()
    for batch, _ in zip(it, range(4)):
        L = batch["tokens"].shape[1]
        widths_seen.add(L)
        ref_state = create_train_state(jax.random.PRNGKey(0), cfg)
        _, ref_m = train_step(ref_state, dict(batch), cfg)
        sp_state = create_train_state(jax.random.PRNGKey(0), cfg)
        sp_state, sp_m = sstep(sp_state, dict(batch))
        assert np.isfinite(float(sp_m["loss"]))
        np.testing.assert_allclose(float(sp_m["loss"]),
                                   float(ref_m["loss"]),
                                   rtol=1e-4, atol=1e-4)
    ds.close()
    assert widths_seen == {32, 128}, widths_seen
