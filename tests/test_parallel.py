"""Distribution tests on the virtual 8-device CPU mesh (SURVEY §4 plan).

Covers: mesh construction, sharding rules (DP/FSDP/TP/SP), numerical
parity of the sharded train step vs single-device, and the explicit
halo-exchange sequence-parallel conv vs the unsharded conv.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from proteinbert_tpu.configs import (
    DataConfig, MeshConfig, ModelConfig, OptimizerConfig, PretrainConfig,
    TrainConfig,
)
from proteinbert_tpu.data import make_pretrain_iterator, InMemoryPretrainingDataset
from proteinbert_tpu.ops.layers import conv1d_init, conv1d_apply
from proteinbert_tpu.parallel import (
    batch_sharding, conv1d_halo, make_mesh, seq_parallel_conv1d,
    shard_train_state, state_sharding,
)
from proteinbert_tpu.train import create_train_state, train_step
from tests.conftest import make_random_proteins

requires_8 = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 (virtual) devices"
)


def cfg_for(mesh_cfg, **model_kw):
    model = dict(
        local_dim=16, global_dim=32, key_dim=8, num_heads=4, num_blocks=2,
        num_annotations=64, dtype="float32",
    )
    model.update(model_kw)
    return PretrainConfig(
        model=ModelConfig(**model),
        data=DataConfig(seq_len=32, batch_size=16),
        optimizer=OptimizerConfig(learning_rate=1e-3, warmup_steps=10),
        mesh=mesh_cfg,
        train=TrainConfig(max_steps=4),
    )


def make_batch(cfg, seed=0):
    rng = np.random.default_rng(seed)
    seqs, ann = make_random_proteins(
        cfg.data.batch_size, rng, num_annotations=cfg.model.num_annotations,
        max_len=40,
    )
    ds = InMemoryPretrainingDataset(seqs, ann, cfg.data.seq_len)
    return next(make_pretrain_iterator(ds, cfg.data.batch_size, seed=seed))


@requires_8
def test_mesh_construction():
    mesh = make_mesh(MeshConfig(data=2, fsdp=2, model=2, seq=1))
    assert mesh.shape == {"data": 2, "fsdp": 2, "model": 2, "seq": 1}
    with pytest.raises(ValueError, match="devices"):
        make_mesh(MeshConfig(data=3))


@requires_8
def test_sharding_rules_tp_and_fsdp():
    mesh = make_mesh(MeshConfig(data=2, fsdp=2, model=2, seq=1))
    cfg = cfg_for(MeshConfig(data=2, fsdp=2, model=2, seq=1))
    abstract = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), cfg)
    )
    sh = state_sharding(mesh, abstract)
    # TP: global head column-sharded over 'model'
    assert sh.params["global_head"]["kernel"].spec == P(None, "model")
    assert sh.params["global_in"]["kernel"].spec == P("model", None)
    # scalars replicated
    assert sh.step.spec == P()
    # FSDP: some block tensor carries the fsdp axis, never on axis 0
    block_specs = jax.tree.leaves(
        jax.tree.map(lambda s: s.spec, sh.params["blocks"],
                     is_leaf=lambda x: hasattr(x, "spec"))
    )
    fsdp_specs = [s for s in block_specs if "fsdp" in tuple(s)]
    assert fsdp_specs, "no block param is fsdp-sharded"
    for s in fsdp_specs:
        assert s[0] is None


@requires_8
@pytest.mark.parametrize(
    "mesh_cfg",
    [
        MeshConfig(data=8),                      # pure DP
        MeshConfig(data=2, fsdp=2, model=2),     # DP+FSDP+TP
        MeshConfig(data=2, seq=4),               # DP+SP
        MeshConfig(data=2, fsdp=2, seq=2),       # DP+FSDP+SP
    ],
    ids=["dp", "dp-fsdp-tp", "dp-sp", "dp-fsdp-sp"],
)
@pytest.mark.parametrize("pinned", [False, True], ids=["bare", "pinned"])
def test_sharded_train_step_matches_single_device(mesh_cfg, pinned):
    """The compiled distributed step must be numerically equivalent to the
    single-device step (XLA inserts psum/all-gather/halo automatically),
    bare and as the trainer runs it: pinned to the state's layout and
    traced under the mesh, its activations held to the batch's layout
    and its block weights gathered (PR 29)."""
    _assert_sharded_step_matches(cfg_for(mesh_cfg), pinned)


def _assert_sharded_step_matches(cfg, pinned=False):
    from proteinbert_tpu.parallel.sharding import pin_state_sharding

    mesh_cfg = cfg.mesh
    batch = make_batch(cfg)

    state0 = create_train_state(jax.random.PRNGKey(0), cfg)
    ref_state, ref_metrics = train_step(state0, batch, cfg)

    mesh = make_mesh(mesh_cfg)
    state = create_train_state(jax.random.PRNGKey(0), cfg)
    state = shard_train_state(state, mesh)
    bsh = batch_sharding(mesh)
    dbatch = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
    step = (pin_state_sharding(train_step, state, static_argnums=2)
            if pinned else train_step)
    new_state, metrics = step(state, dbatch, cfg)

    assert float(metrics["loss"]) == pytest.approx(
        float(ref_metrics["loss"]), rel=2e-5
    )
    ref_leaves = jax.tree.leaves(ref_state.params)
    got_leaves = jax.tree.leaves(new_state.params)
    for r, g in zip(ref_leaves, got_leaves):
        np.testing.assert_allclose(
            np.asarray(r), np.asarray(jax.device_get(g)), atol=2e-5,
            err_msg=str(mesh_cfg),
        )


@requires_8
def test_pinning_the_same_step_again_compiles_nothing():
    """A caller that drives the first steps itself (the benchmark's mesh
    driver) and the trainer each wrap `train_step` in
    `pin_state_sharding`: two jitted wrappers, one executable."""
    from proteinbert_tpu.parallel.sharding import pin_state_sharding

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if "backend_compile" in name else None)
    cfg = cfg_for(MeshConfig(data=2, fsdp=4))
    mesh = make_mesh(cfg.mesh)
    state = shard_train_state(create_train_state(jax.random.PRNGKey(0), cfg), mesh)
    bsh = batch_sharding(mesh)
    batch = {k: jax.device_put(v, bsh[k]) for k, v in make_batch(cfg).items()}
    first = pin_state_sharding(train_step, state, static_argnums=2)
    state, metrics = first(state, batch, cfg)
    float(metrics["loss"])
    assert compiles
    before = len(compiles)
    again = pin_state_sharding(train_step, state, static_argnums=2)
    assert again is not first
    state, metrics = again(state, batch, cfg)
    float(metrics["loss"])
    assert len(compiles) == before


@requires_8
@pytest.mark.parametrize("devices, loaded", [(1, True), (2, False)],
                         ids=["one-device-loads", "two-devices-compile-afresh"])
def test_multi_device_cpu_executable_is_never_loaded_from_the_cache(
        devices, loaded):
    """jaxlib 0.9.0 runs a LOADED multi-device CPU executable with its
    collectives in no fixed order, and a pinned fsdp step then stops at a
    rendezvous (utils/compat.configure_compile_cache, which the harness
    calls): such a program compiles again, a one-device one still loads.
    Fails if jax moves the function the helper wraps."""
    hits = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: hits.append(name)
        if name.endswith("compilation_cache/cache_hits") else None)
    mesh = jax.make_mesh((devices,), ("data",), devices=jax.devices()[:devices])
    x = jax.device_put(jnp.arange(8.0), NamedSharding(mesh, P("data")))
    program = lambda x: jnp.sum(jnp.tanh(x) * 3.25)            # noqa: E731
    least = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        jax.jit(program)(x)
        jax.clear_caches()          # so the second call asks the disk
        before = len(hits)
        jax.jit(program)(x)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", least)
    assert (len(hits) > before) == loaded


@requires_8
@pytest.mark.parametrize("model_kw", [
    # num_blocks=5 with unroll=2 keeps a REAL loop (2 iterations of 2
    # bodies + remainder) — at the default num_blocks=2 the scan would
    # fully unroll to straight-line code and never compile the mixed
    # loop-plus-unroll pattern this test exists to cover.
    dict(scan_unroll=2, num_blocks=5, remat=True, remat_policy="convs"),
    dict(scan_split_transpose=True, remat=True, remat_policy="convs"),
    # Both levers together.
    dict(scan_unroll=2, num_blocks=5, scan_split_transpose=True,
         remat=True, remat_policy="convs"),
], ids=["u2-remat-convs", "st-remat-convs", "u2st-remat-convs"])
def test_scan_knobs_match_single_device_under_fsdp(model_kw):
    """The scan scheduling knobs (partial unroll / split transpose) on
    the implicit-SPMD path must stay numerically equivalent to the
    single-device step when the stacked-block params are fsdp-sharded —
    with unroll the scan body consumes k fsdp-sharded block slices per
    iteration, a different all-gather pattern than the u1 scan the other
    parity tests compile."""
    mesh_cfg = MeshConfig(data=2, fsdp=2, seq=2)
    _assert_sharded_step_matches(cfg_for(mesh_cfg, **model_kw))


@requires_8
@pytest.mark.parametrize("dilation", [1, 5])
def test_halo_conv_matches_dense(dilation):
    """Explicit shard_map halo conv == unsharded 'SAME' conv."""
    mesh = make_mesh(MeshConfig(data=2, seq=4))
    key = jax.random.PRNGKey(0)
    C = 8
    params = conv1d_init(key, 9, C, C)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 64, C))
    ref = conv1d_apply(params, x, dilation=dilation)
    got = seq_parallel_conv1d(mesh, params, x, dilation=dilation)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(jax.device_get(got)), atol=1e-5
    )


@requires_8
def test_halo_conv_single_shard_degenerates():
    mesh = make_mesh(MeshConfig(data=8, seq=1))
    key = jax.random.PRNGKey(0)
    params = conv1d_init(key, 9, 4, 4)
    x = jax.random.normal(key, (8, 16, 4))
    ref = conv1d_apply(params, x, dilation=2)
    got = seq_parallel_conv1d(mesh, params, x, dilation=2)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=1e-5)


# ------------------------------------------------------ multi-slice mesh

class _FakeTpuDev:
    """Stub with the attributes mesh_utils consults (id, process_index,
    slice_index, coords, core_on_chip, device_kind, platform)."""

    def __init__(self, i, slice_index):
        self.id = i
        self.process_index = slice_index
        self.slice_index = slice_index
        self.platform = "tpu"
        self.device_kind = "faketpu"
        j = i % 4
        self.coords = (j % 2, j // 2, 0)
        self.core_on_chip = 0

    def __repr__(self):
        return f"fake{self.id}@slice{self.slice_index}"


def test_multislice_mesh_puts_data_axis_on_dcn():
    """2 slices x 4 chips: the data axis must span slices (outer DCN hop)
    while fsdp/model stay within a slice's ICI."""
    from proteinbert_tpu.configs import MeshConfig
    from proteinbert_tpu.parallel.mesh import make_mesh

    devs = [_FakeTpuDev(i, i // 4) for i in range(8)]
    mesh = make_mesh(MeshConfig(data=2, fsdp=2, model=2, seq=1), devs)
    assert dict(mesh.shape) == {"data": 2, "fsdp": 2, "model": 2, "seq": 1}
    arr = mesh.devices
    # Each data-axis row is one slice; every other axis stays intra-slice.
    for d in range(2):
        slices = {dev.slice_index for dev in arr[d].flatten()}
        assert slices == {d}, f"data row {d} spans slices {slices}"


def test_multislice_mesh_rejects_indivisible_data_axis():
    from proteinbert_tpu.configs import MeshConfig
    from proteinbert_tpu.parallel.mesh import make_mesh

    devs = [_FakeTpuDev(i, i // 4) for i in range(8)]
    with pytest.raises(ValueError, match="multiple of the 2 slices"):
        make_mesh(MeshConfig(data=1, fsdp=2, model=2, seq=2), devs)


def test_fsdp_compile_has_no_involuntary_remat_warning():
    """The fsdp-bearing mesh must compile the train step without the SPMD
    partitioner's "Involuntary full rematerialization" fallback (VERDICT
    r2 Weak #3: the scan-boundary stash of per-block bf16 param casts
    used to trigger it; fixed by hoisting the cast out of the scan and
    FSDP-sharding stacked-block leaves on their LAST divisible axis).
    XLA emits the warning from C++ on stderr, so compile in a subprocess
    and grep — an in-process warnings filter cannot see it. As a
    POSITIVE control against silent rot (XLA rewording the message, or a
    log-level knob suppressing C++ warnings would otherwise keep this
    green forever), the same compile under the classic GSPMD partitioner
    (shardy off) is known to emit the warning and must still match the
    grep."""
    import os
    import subprocess
    import sys

    if not jax.config.jax_use_shardy_partitioner:
        pytest.skip("default partitioner is GSPMD (jax 0.4.x: shardy not "
                    "yet the default) — the warning-free property under "
                    "test belongs to the shardy partitioner")

    code = """
import jax
from proteinbert_tpu.utils.compat import request_cpu_devices
request_cpu_devices(8)
# A persistent-cache hit loads an AOT result and SKIPS partitioning, so
# neither arm would emit the warning (observed: the positive control
# went silent once the suite's cache warmed) — force fresh compiles.
jax.config.update("jax_enable_compilation_cache", False)
import os as _os
if _os.environ.get("PBT_TEST_FORCE_GSPMD"):
    jax.config.update("jax_use_shardy_partitioner", False)
import numpy as np
from proteinbert_tpu.configs import (DataConfig, MeshConfig, ModelConfig,
    OptimizerConfig, PretrainConfig, TrainConfig)
from proteinbert_tpu.parallel import batch_sharding, make_mesh
from proteinbert_tpu.parallel.sharding import state_sharding
from proteinbert_tpu.train import create_train_state
import proteinbert_tpu.train.train_state as TS

mesh_cfg = MeshConfig(data=2, fsdp=2, model=2, seq=1)
cfg = PretrainConfig(
    model=ModelConfig(local_dim=32, global_dim=64, key_dim=16, num_heads=4,
                      num_blocks=2, num_annotations=128, dtype="bfloat16",
                      remat=True, remat_policy="convs"),
    data=DataConfig(seq_len=64, batch_size=8),
    optimizer=OptimizerConfig(warmup_steps=10),
    mesh=mesh_cfg, train=TrainConfig(max_steps=1))
mesh = make_mesh(mesh_cfg, jax.devices()[:8])
abstract = jax.eval_shape(lambda: create_train_state(jax.random.PRNGKey(0), cfg))
sh = state_sharding(mesh, abstract)
bsh = batch_sharding(mesh)
bat = {"tokens": jax.ShapeDtypeStruct((8, 64), np.int32, sharding=bsh["tokens"]),
       "annotations": jax.ShapeDtypeStruct((8, 128), np.float32,
                                           sharding=bsh["annotations"])}
st = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                  abstract, sh)
TS.train_step.lower(st, bat, cfg).compile()
print("COMPILED-OK")
"""
    def compile_once(force_gspmd):
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        if force_gspmd:
            env["PBT_TEST_FORCE_GSPMD"] = "1"
        return subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=420,
                              env=env)

    marker = "Involuntary full rematerialization"
    out = compile_once(force_gspmd=False)
    assert "COMPILED-OK" in out.stdout, out.stderr[-2000:]
    assert marker not in out.stderr, out.stderr[-3000:]

    control = compile_once(force_gspmd=True)
    assert "COMPILED-OK" in control.stdout, control.stderr[-2000:]
    assert marker in control.stderr, (
        "positive control failed: the GSPMD compile no longer emits the "
        "warning text this test greps for — update the marker (XLA may "
        "have reworded it) before trusting the negative assertion above")


# ------------------------------------ fsdp moves the weights, not the batch

def _pinned_step_and_args(mesh_cfg, rows=24):
    """`train_step` pinned as the trainer pins it, with a state and a
    batch on the mesh. 24 rows: no width of the tiny model is 24, so a
    result with 24 rows is the batch and nothing else."""
    import dataclasses

    from proteinbert_tpu.parallel.sharding import pin_state_sharding

    cfg = cfg_for(mesh_cfg)
    cfg = dataclasses.replace(
        cfg, data=dataclasses.replace(cfg.data, batch_size=rows))
    mesh = make_mesh(mesh_cfg, jax.devices()[:mesh_cfg.num_devices])
    state = shard_train_state(
        create_train_state(jax.random.PRNGKey(0), cfg), mesh)
    bsh = batch_sharding(mesh)
    batch = {k: jax.device_put(v, bsh[k]) for k, v in make_batch(cfg).items()}
    step = pin_state_sharding(train_step, state, static_argnums=2)
    return step, state, batch, cfg, mesh


def _census_of(step, state, batch, cfg):
    from proteinbert_tpu.obs.tracing import collective_census

    return collective_census(
        step.lower(state, batch, cfg).compile().as_text(),
        batch["tokens"].shape[0],
        [leaf.shape for leaf in jax.tree.leaves(state.params)])


def _assert_moves_weights_not_batch(census, state, mesh):
    assert census["activation"] == 0, census
    specs = jax.tree.leaves(
        state_sharding(mesh, jax.eval_shape(lambda: state)).params["blocks"])
    split = sum("fsdp" in jax.tree.leaves(tuple(s.spec)) for s in specs)
    assert split and census["parameter_gathers"] >= split, (split, census)


FSDP_MESHES = pytest.mark.parametrize(
    "mesh_cfg", [MeshConfig(fsdp=4), MeshConfig(data=2, fsdp=2)],
    ids=["fsdp4", "data2-fsdp2"])


@requires_8
@FSDP_MESHES
def test_fsdp_step_gathers_weights_and_no_activation(mesh_cfg):
    """The compiled step of an fsdp mesh brings no activation together
    over the chips (no collective whose result has the global row count)
    and all-gathers every sharded block weight (PR 29)."""
    step, state, batch, cfg, mesh = _pinned_step_and_args(mesh_cfg)
    _assert_moves_weights_not_batch(
        _census_of(step, state, batch, cfg), state, mesh)


@requires_8
@FSDP_MESHES
def test_fsdp_census_catches_a_step_that_gathers_the_batch(mesh_cfg, monkeypatch):
    """The positive control: with the activations left unpinned and the
    weights left where they are stored, the partitioner gathers the
    batch (what the tree did before PR 29), and the assertion of the
    test above must FAIL on that step."""
    from proteinbert_tpu.parallel import sharding

    monkeypatch.setattr(sharding, "pin_to_batch_layout",
                        lambda x, positions=None: x)
    monkeypatch.setattr(sharding, "gathered_over_fsdp", lambda tree: tree)
    jax.clear_caches()      # the pinned trace of the same step is cached
    try:
        step, state, batch, cfg, mesh = _pinned_step_and_args(mesh_cfg)
        census = _census_of(step, state, batch, cfg)
    finally:
        jax.clear_caches()
    assert census["activation"] > 0, census
    with pytest.raises(AssertionError):
        _assert_moves_weights_not_batch(census, state, mesh)


def _lowered_text(fn, *args):
    """The lowering of a FRESH jit of `fn` (nothing of an earlier trace
    is found again), as text."""
    return jax.jit(lambda *a: fn(*a)).lower(*args).as_text()


@pytest.mark.parametrize("program", ["train_step", "_packed_encode_batch"])
def test_no_mesh_program_is_untouched_by_the_pin(program, monkeypatch):
    """With no mesh the helper adds nothing: the one-chip cells' programs
    lower to the same text, byte for byte, as with the helper patched to
    identity."""
    from proteinbert_tpu import inference
    from proteinbert_tpu.parallel import sharding

    cfg = cfg_for(MeshConfig())
    state = create_train_state(jax.random.PRNGKey(0), cfg)
    if program == "train_step":
        batch = make_batch(cfg)
        fn = lambda s, b: train_step.__wrapped__(s, b, cfg)      # noqa: E731
        args = (state, batch)
    else:
        rows, S = 4, 3
        seg = np.repeat(np.arange(1, S + 1), 10)[None].repeat(rows, 0)
        seg = np.pad(seg, ((0, 0), (0, 2))).astype(np.int32)
        tokens = np.where(seg > 0, 5, 0).astype(np.int32)
        ann = np.zeros((rows, S, cfg.model.num_annotations), np.float32)
        fn = lambda p, t, s, a: inference._packed_encode_batch.__wrapped__(  # noqa: E731
            p, t, s, a, cfg.model)
        args = (state.params, tokens, seg, ann)
    with_pin = _lowered_text(fn, *args)
    monkeypatch.setattr(sharding, "pin_to_batch_layout",
                        lambda x, positions=None: x)
    monkeypatch.setattr(sharding, "gathered_over_fsdp", lambda tree: tree)
    assert _lowered_text(fn, *args) == with_pin
    assert "sharding_constraint" not in with_pin and "Sharding" not in with_pin
