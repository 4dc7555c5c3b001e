"""Distribution tests on the virtual 8-device CPU mesh (SURVEY §4 plan).

Covers: mesh construction, sharding rules (DP/FSDP/TP/SP), numerical
parity of the sharded train step vs single-device, and the explicit
halo-exchange sequence-parallel conv vs the unsharded conv.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

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
def test_sharded_train_step_matches_single_device(mesh_cfg):
    """The compiled distributed step must be numerically equivalent to the
    single-device step (XLA inserts psum/all-gather/halo automatically)."""
    _assert_sharded_step_matches(cfg_for(mesh_cfg))


def _assert_sharded_step_matches(cfg):
    mesh_cfg = cfg.mesh
    batch = make_batch(cfg)

    state0 = create_train_state(jax.random.PRNGKey(0), cfg)
    ref_state, ref_metrics = train_step(state0, batch, cfg)

    mesh = make_mesh(mesh_cfg)
    state = create_train_state(jax.random.PRNGKey(0), cfg)
    state = shard_train_state(state, mesh)
    bsh = batch_sharding(mesh)
    dbatch = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
    new_state, metrics = train_step(state, dbatch, cfg)

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
@pytest.mark.parametrize("model_kw", [
    # num_blocks=5 with unroll=2 keeps a REAL loop (2 iterations of 2
    # bodies + remainder) — at the default num_blocks=2 the scan would
    # fully unroll to straight-line code and never compile the mixed
    # loop-plus-unroll pattern this test exists to cover.
    dict(scan_unroll=2, num_blocks=5, remat=True, remat_policy="convs"),
    dict(scan_split_transpose=True, remat=True, remat_policy="convs"),
    # Both levers together — the bench's remat-convs-u2st variant.
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
