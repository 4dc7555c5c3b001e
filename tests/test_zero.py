"""ZeRO-1 sharded weight update (parallel/zero.py) on the virtual
8-device mesh: numerical parity with the replicated step, the per-chip
optimizer-state memory claim, the bf16 compressed-reduction error
bound, and byte-identical checkpoint resume (including the PR-1 staged
overlapped save path)."""

import dataclasses
import functools

import numpy as np
import pytest

import jax

from proteinbert_tpu.configs import (
    CheckpointConfig, DataConfig, MeshConfig, ModelConfig, OptimizerConfig,
    ParallelConfig, PretrainConfig, TrainConfig,
)
from proteinbert_tpu.data import (
    InMemoryPretrainingDataset, make_pretrain_iterator,
)
from proteinbert_tpu.parallel import (
    batch_sharding, make_mesh, make_zero_train_step, shard_train_state,
    zero_extent,
)
from proteinbert_tpu.parallel.sharding import state_sharding
from proteinbert_tpu.parallel.quant import make_quant_zero_train_step
from proteinbert_tpu.parallel.zero import (
    collective_bytes_from_hlo, collective_wire_bytes_from_hlo,
    grad_reduce_wire_bytes, per_chip_state_bytes, zero_gradient_update,
)
from proteinbert_tpu.train import Checkpointer, create_train_state, pretrain, train_step
from tests.conftest import make_random_proteins

requires_8 = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 (virtual) devices"
)


def cfg_for(mesh_cfg, parallel=None, **kw):
    model = dict(
        local_dim=16, global_dim=32, key_dim=8, num_heads=4, num_blocks=2,
        num_annotations=64, dtype="float32",
    )
    return PretrainConfig(
        model=ModelConfig(**model),
        data=DataConfig(seq_len=32, batch_size=16),
        optimizer=OptimizerConfig(learning_rate=1e-3, warmup_steps=10,
                                  **kw.pop("opt_kw", {})),
        mesh=mesh_cfg,
        parallel=parallel or ParallelConfig(zero_update=True),
        train=TrainConfig(max_steps=4, **kw.pop("train_kw", {})),
    )


# ONE canonical config for every single-device REFERENCE run in this
# module: cfg is a static jit arg, so giving each test its own
# mesh/parallel variant would recompile the identical reference
# train_step per test — with a shared config the module pays one
# reference compile (and the zero-vs-ref math never depends on the
# mesh/parallel fields the variants differ in).
REF_CFG = cfg_for(MeshConfig(), parallel=ParallelConfig())


def _ref_two_steps(batch):
    state = create_train_state(jax.random.PRNGKey(0), REF_CFG)
    state, m1 = train_step(state, dict(batch), REF_CFG)
    state, m2 = train_step(state, dict(batch), REF_CFG)
    return state, m1, m2


def make_batch(cfg, seed=0):
    rng = np.random.default_rng(seed)
    seqs, ann = make_random_proteins(
        cfg.data.batch_size, rng, num_annotations=cfg.model.num_annotations,
        max_len=40,
    )
    ds = InMemoryPretrainingDataset(seqs, ann, cfg.data.seq_len)
    return next(make_pretrain_iterator(ds, cfg.data.batch_size, seed=seed))


def _run_two_steps_zero(cfg, batch):
    mesh = make_mesh(cfg.mesh)
    state = shard_train_state(
        create_train_state(jax.random.PRNGKey(0), cfg), mesh,
        zero_update=True)
    zstep = make_zero_train_step(mesh, cfg)
    bsh = batch_sharding(mesh)
    dbatch = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
    state, m1 = zstep(state, dbatch)
    state, m2 = zstep(state, dbatch)
    return state, m1, m2


def _max_param_err(ref_state, state):
    err = 0.0
    for r, g in zip(jax.tree.leaves(ref_state.params),
                    jax.tree.leaves(state.params)):
        err = max(err, float(np.max(np.abs(
            np.asarray(r, np.float64)
            - np.asarray(jax.device_get(g), np.float64)))))
    return err


@requires_8
@pytest.mark.parametrize(
    "mesh_cfg",
    [
        MeshConfig(data=8),                      # pure DP — the motivating case
        MeshConfig(data=4, fsdp=2),              # joint replica axis
        MeshConfig(data=2, fsdp=2, model=2),     # + tensor parallelism
    ],
    ids=["dp8", "dp4-fsdp2", "dp2-fsdp2-tp2"],
)
def test_zero_update_matches_replicated(mesh_cfg):
    """Reduce-scatter → sharded apply → all-gather must be numerically
    the replicated clip→Adam update: loss, grad_norm and every param
    leaf agree with the single-device step over two steps (fp32,
    tight tolerance — the acceptance criterion's parity gate)."""
    cfg = cfg_for(mesh_cfg)
    batch = make_batch(cfg)

    ref_state, ref_m1, ref_m2 = _ref_two_steps(batch)

    state, m1, m2 = _run_two_steps_zero(cfg, batch)
    assert int(jax.device_get(state.step)) == 2

    for ref_m, m in ((ref_m1, m1), (ref_m2, m2)):
        for key in ("loss", "grad_norm", "lr"):
            a, b = float(ref_m[key]), float(m[key])
            assert abs(a - b) <= 2e-5 * max(1.0, abs(a)), (key, a, b)
    assert _max_param_err(ref_state, state) < 2e-6


@requires_8
def test_zero_opt_state_sharded_and_smaller():
    """The memory claim, from the sharding rules themselves: Adam mu/nu
    carry the joint ('data','fsdp') axis, per-chip opt-state bytes drop
    by ~data_extent vs the fsdp-only layout, and params keep their
    storage layout (shapes and specs unchanged between modes)."""
    mesh_cfg = MeshConfig(data=4, fsdp=2)
    cfg = cfg_for(mesh_cfg)
    mesh = make_mesh(mesh_cfg)
    abstract = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), cfg))

    rep = per_chip_state_bytes(mesh, abstract, zero_update=False)
    zer = per_chip_state_bytes(mesh, abstract, zero_update=True)
    assert zer["params"] == rep["params"]
    # ~(1 - 1/data_extent) of the (already fsdp-sharded) Adam state goes
    # away; small/indivisible leaves keep a bounded replicated remainder.
    assert zer["opt_state"] <= rep["opt_state"] / 3.0, (rep, zer)

    sh = state_sharding(mesh, abstract, zero_update=True)
    mu_specs = [s.spec for s in jax.tree.leaves(sh.opt_state[1][0].mu)]
    assert any(("data", "fsdp") in tuple(s) for s in mu_specs), mu_specs
    # params specs identical to the replicated rule
    sh_rep = state_sharding(mesh, abstract, zero_update=False)
    assert ([s.spec for s in jax.tree.leaves(sh.params)]
            == [s.spec for s in jax.tree.leaves(sh_rep.params)])


@requires_8
def test_bf16_grad_reduction_error_bounded():
    """parallel.grad_reduce_dtype='bf16' now routes to the QUANTIZED
    reduce-scatter (parallel/quant.py, ISSUE 12): per-replica partial
    gradients are stochastically rounded to bf16 and exchanged at 2
    bytes/element on the wire. Measured bound (documented in
    docs/distributed.md): after two steps at lr 1e-3 the max param
    deviation from the exact fp32 path stays under 5e-4 — the
    stochastic per-PARTIAL rounding of n=8 replicas accumulates
    ~sqrt(n) of the old post-reduction cast's error, which is the
    price of the wire actually moving bf16 — while the fp32 zero path
    stays under 2e-6 (the parity test). The loss at step 1 is computed
    BEFORE any update and must match exactly (same corruption ops on
    the same key; tests/test_quant.py holds the full payload grid)."""
    mesh_cfg = MeshConfig(data=4, fsdp=2)
    batch = make_batch(cfg_for(mesh_cfg))

    ref_state, ref_m1, _ = _ref_two_steps(batch)

    cfg16 = cfg_for(mesh_cfg, parallel=ParallelConfig(
        zero_update=True, grad_reduce_dtype="bf16"))
    state, m1, m2 = _run_two_steps_zero(cfg16, batch)

    assert abs(float(m1["loss"]) - float(ref_m1["loss"])) <= 2e-5
    err = _max_param_err(ref_state, state)
    assert 0.0 < err < 5e-4, err  # rounded (not exact), and bounded


def test_grad_reduce_dtype_rejected():
    mesh_cfg = MeshConfig(data=jax.device_count())
    cfg = cfg_for(mesh_cfg, parallel=ParallelConfig(
        zero_update=True, grad_reduce_dtype="fp8"))
    mesh = make_mesh(mesh_cfg)
    state = create_train_state(jax.random.PRNGKey(0), cfg)
    grads = jax.tree.map(np.zeros_like, state.params)
    with pytest.raises(ValueError, match="grad_reduce_dtype"):
        zero_gradient_update(mesh, cfg.optimizer, state.params, grads,
                             state.opt_state, grad_reduce_dtype="fp8")


@requires_8
def test_zero_seq_parallel_step_parity():
    """The explicit shard_map sequence-parallel step with zero_update on
    (its gradient_update routed through zero_gradient_update) matches
    the replicated implicit step on the same batch."""
    from proteinbert_tpu.parallel.seq_parallel import (
        make_seq_parallel_train_step,
    )

    mesh_cfg = MeshConfig(data=2, fsdp=2, seq=2)
    cfg = cfg_for(mesh_cfg)
    batch = make_batch(cfg)

    _, ref_m = train_step(
        create_train_state(jax.random.PRNGKey(0), REF_CFG), dict(batch),
        REF_CFG)

    mesh = make_mesh(mesh_cfg)
    assert zero_extent(mesh) == 4
    state = shard_train_state(
        create_train_state(jax.random.PRNGKey(0), cfg), mesh,
        zero_update=True)
    sstep = make_seq_parallel_train_step(mesh, cfg)
    _, m = sstep(state, dict(batch))
    ref_loss, got = float(ref_m["loss"]), float(m["loss"])
    assert abs(got - ref_loss) <= 1e-4 * max(1.0, abs(ref_loss))


@requires_8
def test_zero_trainer_resume_byte_identical(tmp_path):
    """Resume across a checkpoint boundary under zero_update — with the
    OVERLAPPED (staged-snapshot) save path on — must reproduce the
    uninterrupted run bit-for-bit: params, resharded Adam moments, RNG
    key, step, and the post-resume loss stream (the acceptance
    criterion's resume gate, riding the PR-1 staged-save machinery)."""
    mesh_cfg = MeshConfig(data=4, fsdp=2)

    def build_cfg():
        cfg = cfg_for(mesh_cfg, train_kw=dict(log_every=1))
        return cfg.replace(
            train=dataclasses.replace(cfg.train, max_steps=12, log_every=1),
            checkpoint=CheckpointConfig(every_steps=4, async_save=True,
                                        overlap=True))

    cfg = build_cfg()
    mesh = make_mesh(mesh_cfg)

    def make_iter(seed=0):
        rng = np.random.default_rng(seed)
        seqs, ann = make_random_proteins(
            64, rng, num_annotations=cfg.model.num_annotations, max_len=40)
        ds = InMemoryPretrainingDataset(seqs, ann, cfg.data.seq_len)
        return lambda skip: make_pretrain_iterator(
            ds, cfg.data.batch_size, seed=0, skip_batches=skip)

    full = pretrain(cfg, make_iter(), mesh=mesh)
    assert int(full["state"].step) == 12

    # Interrupted twin: stop at 6 (checkpoint landed at 4), resume to 12.
    half_cfg = cfg.replace(
        train=dataclasses.replace(cfg.train, max_steps=6))
    ck = Checkpointer(str(tmp_path / "ck"), async_save=True)
    pretrain(half_cfg, make_iter(), checkpointer=ck, mesh=mesh)
    assert 6 in ck.all_steps()
    ck.close()

    ck2 = Checkpointer(str(tmp_path / "ck"), async_save=True)
    resumed = pretrain(cfg, make_iter(), checkpointer=ck2, mesh=mesh)
    ck2.close()
    assert int(resumed["state"].step) == 12

    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        resumed["state"], full["state"])
    full_tail = {h["step"]: h["loss"] for h in full["history"]
                 if h["step"] > 6}
    res_tail = {h["step"]: h["loss"] for h in resumed["history"]
                if h["step"] > 6}
    assert res_tail == full_tail

    # The restored mu really came back SHARDED (not replicated): its
    # per-device shard must be 1/8 of the leaf.
    mu_leaf = jax.tree.leaves(resumed["state"].opt_state[1][0].mu)[0]
    nshards = len({d.id for d in mu_leaf.sharding.device_set})
    assert nshards == 8
    shard = mu_leaf.sharding.shard_shape(mu_leaf.shape)
    assert np.prod(shard) * 8 == np.prod(mu_leaf.shape), (
        shard, mu_leaf.shape)


@requires_8
def test_zero_checkpoint_interchangeable_with_replicated(tmp_path):
    """Leaf SHAPES are mode-independent, so a replicated-mode checkpoint
    restores into a zero-sharded template (and the values match)."""
    mesh_cfg = MeshConfig(data=4, fsdp=2)
    cfg = cfg_for(mesh_cfg)
    mesh = make_mesh(mesh_cfg)
    state = create_train_state(jax.random.PRNGKey(0), cfg)

    ck = Checkpointer(str(tmp_path / "ck"), async_save=False)
    ck.save(1, jax.device_get(state))
    template = shard_train_state(state, mesh, zero_update=True)
    restored, _ = ck.restore(template)
    ck.close()
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b))),
        restored, state)


@requires_8
def test_zero_with_eval_keyed_plateau(tmp_path):
    """The zero step carries the plateau_value contract natively: an
    eval-keyed plateau run under zero_update matches the replicated
    eval-keyed run loss-for-loss (schedule semantics untouched)."""
    mesh_cfg = MeshConfig(data=4, fsdp=2)

    def build(parallel):
        cfg = cfg_for(
            mesh_cfg, parallel=parallel,
            opt_kw=dict(schedule="warmup_plateau", plateau_metric="eval_loss",
                        plateau_window=2))
        return cfg.replace(train=dataclasses.replace(
            cfg.train, max_steps=6, log_every=1, eval_every=2))

    rng = np.random.default_rng(7)
    seqs, ann = make_random_proteins(32, rng, num_annotations=64, max_len=40)
    ds = InMemoryPretrainingDataset(seqs, ann, 32)
    train_it = lambda: make_pretrain_iterator(ds, 16, seed=0)  # noqa: E731
    evb = lambda: make_pretrain_iterator(  # noqa: E731
        ds, 16, shuffle=False, num_epochs=1)

    mesh = make_mesh(mesh_cfg)
    runs = {}
    for name, parallel in (("rep", ParallelConfig()),
                           ("zero", ParallelConfig(zero_update=True))):
        out = pretrain(build(parallel), train_it(), mesh=mesh,
                       eval_batches=evb)
        runs[name] = {h["step"]: h["loss"] for h in out["history"]
                      if "loss" in h}
    assert runs["rep"].keys() == runs["zero"].keys() and runs["rep"]
    for step, loss in runs["rep"].items():
        assert abs(runs["zero"][step] - loss) <= 2e-5 * max(1.0, abs(loss)), (
            step, loss, runs["zero"][step])


# The compiled step's bytes, one case a reduction mode: `replicated`
# (no zero), `zero` (the partitioner's own fp32 reduce-scatter),
# `zero_rs_fp32` (the EXPLICIT reduce-scatter at an fp32 payload: the
# same program as the quantized ones, only the payload differs),
# `zero_bf16` / `zero_int8` (quantized payloads).
COMM_MODES = {
    "replicated": (False, "fp32"), "zero": (True, "fp32"),
    "zero_rs_fp32": (True, "fp32"), "zero_bf16": (True, "bf16"),
    "zero_int8": (True, "int8"),
}
COMM_MESH = MeshConfig(data=4, fsdp=2)


@functools.lru_cache(maxsize=None)
def _comm_row(mode):
    """Counts of one mode's step compiled for data=4 x fsdp=2 (from
    abstract arguments: nothing runs), kept for the modes after it."""
    zero, grd = COMM_MODES[mode]
    cfg = cfg_for(COMM_MESH, parallel=ParallelConfig(
        zero_update=zero, grad_reduce_dtype=grd))
    mesh = make_mesh(COMM_MESH)
    abstract = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), cfg))
    sh = state_sharding(mesh, abstract, zero_update=zero)
    st = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, sh)
    bsh = batch_sharding(mesh)
    batch = {
        "tokens": jax.ShapeDtypeStruct(
            (cfg.data.batch_size, cfg.data.seq_len), np.int32,
            sharding=bsh["tokens"]),
        "annotations": jax.ShapeDtypeStruct(
            (cfg.data.batch_size, cfg.model.num_annotations), np.float32,
            sharding=bsh["annotations"]),
    }
    if mode == "zero_rs_fp32":
        lowered = make_quant_zero_train_step(
            mesh, cfg, payload="fp32").lower(st, batch)
    elif zero:
        lowered = make_zero_train_step(mesh, cfg).lower(st, batch)
    else:
        lowered = train_step.lower(st, batch, cfg)
    hlo = lowered.compile().as_text()
    wire = collective_wire_bytes_from_hlo(hlo, mesh.size)
    return {"collective": collective_bytes_from_hlo(hlo), "wire": wire,
            "grad_wire": grad_reduce_wire_bytes(wire),
            "state": per_chip_state_bytes(mesh, abstract, zero_update=zero)}


@requires_8
@pytest.mark.parametrize("mode", sorted(COMM_MODES))
def test_compiled_step_bytes_by_reduction_mode(mode):
    """What ZeRO-1 and the quantized wire claim, read from the COMPILED
    per-chip program and the sharding rules on data=4 x fsdp=2: Adam
    state a chip shrinks ~4x and parameters a chip do not move;
    reduce-scatter + all-gather stay within 1.5x the replicated
    all-reduce's bytes; an int8 payload moves <= 0.30x and a bf16 one
    <= 0.60x the gradient-reduction wire bytes of the SAME explicit
    reduce-scatter at fp32."""
    row, rep = _comm_row(mode), _comm_row("replicated")
    assert row["collective"]["total"] > 0 and row["wire"]["total"] > 0
    assert row["state"]["params"] == rep["state"]["params"]
    if mode == "replicated":
        return
    assert rep["state"]["opt_state"] / row["state"]["opt_state"] >= 3.0
    if mode == "zero":
        ratio = row["collective"]["total"] / rep["collective"]["total"]
        assert 0 < ratio <= 1.5, ratio
    bound = {"zero_int8": 0.30, "zero_bf16": 0.60}.get(mode)
    if bound is not None:
        ratio = row["grad_wire"] / _comm_row("zero_rs_fp32")["grad_wire"]
        assert 0 < ratio <= bound, ratio


def test_collective_bytes_from_hlo_parses_ops():
    hlo = """
  %g = f32[128,64]{1,0} all-gather(f32[16,64]{1,0} %p), dimensions={0}
  %ags = (f32[16,8]{1,0}, f32[128,8]{1,0}) all-gather-start(f32[16,8]{1,0} %q), dimensions={0}
  %agd = f32[128,8]{1,0} all-gather-done((f32[16,8]{1,0}, f32[128,8]{1,0}) %ags)
  %ar = bf16[1024]{0} all-reduce-start(bf16[1024]{0} %x), to_apply=%sum
  %ard = bf16[1024]{0} all-reduce-done(bf16[1024]{0} %ar)
  %rs = f32[32]{0} reduce-scatter(f32[256]{0} %y), dimensions={0}
  %cp = f32[4,4]{1,0} collective-permute(f32[4,4]{1,0} %z)
  %not_a_collective = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)
"""
    got = collective_bytes_from_hlo(hlo)
    # tuple-shaped async start: the leading operand alias is NOT counted
    assert got["all-gather"] == 128 * 64 * 4 + 128 * 8 * 4
    assert got["all-reduce"] == 1024 * 2  # -start counted, -done not
    assert got["reduce-scatter"] == 32 * 4
    assert got["collective-permute"] == 16 * 4
    assert got["total"] == sum(
        got[k] for k in ("all-reduce", "all-gather", "reduce-scatter",
                         "all-to-all", "collective-permute"))
