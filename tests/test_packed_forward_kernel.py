"""The forward-only packed local track on the segment kernel (ISSUE 42).

Every forward-only packed ProteinBERT program (the serving and mapping
entries of inference.py and heads/apply.py) runs its local track through
`kernels/fused_block.packed_local_track_forward`: on a TPU and at
C <= MAX_PALLAS_DIM `fused_local_track_segments` (the segment kernel where
its guard has a plan, else its counted reference), everywhere else
`local_track_segment_reference` as a differentiated program runs it.
Here: the plan at the serving shape, the kernel at a tile the BUDGET chose
(interpreter, small float32 shapes) against the reference on the layouts a
served row has, bit-identity of a segment on the kernel path, the dispatch
rule, and that a differentiated packed program never reaches the kernel.
Nothing here runs a served batch through the interpreter on the CPU's own
dispatch: a test that wants the kernel says so by patching the module.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proteinbert_tpu import inference
from proteinbert_tpu.configs import ModelConfig
from proteinbert_tpu.heads import apply as heads_apply
from proteinbert_tpu.kernels import fused_block as fb
from proteinbert_tpu.kernels import vmem_budget as vb
from proteinbert_tpu.models import proteinbert

C, L, S = 128, 512, 4
HALO = 20  # the wide conv's: (9 - 1) // 2 * 5


# ------------------------------------------------------------- the plan

@pytest.mark.parametrize("segments", [1, 8, 16])
def test_guard_has_a_plan_at_the_serving_shape(segments):
    """1024 x 512 in bfloat16: the whole weight set, the padded row and
    its one-hot leave room for the temporaries of 256 rows, not of 512."""
    assert fb.pallas_segments_supported(512, 1024, segments)
    tile = fb._segment_tile(512, 1024, segments, "bfloat16", 9, 9, HALO)
    assert tile == 256 and 1024 % tile == 0
    assert fb._pick_tile(1024) == 512  # what the guard asked for alone


@pytest.mark.parametrize("shape,tile", [
    ((512, 512, 8, "bfloat16"), 256),    # base training rows: as before
    ((128, 256, 8, "float32"), 128),
    ((256, 1024, 16, "bfloat16"), 512),
    ((512, 2048, 8, "bfloat16"), 128),   # a longer row: a smaller tile
    ((512, 1536, 8, "bfloat16"), 128),   # 256 divides it and does not fit
    ((512, 4096, 8, "bfloat16"), 0),     # the row itself passes the budget
    ((512, 1024, 8, "float32"), 0),      # float32 weights alone do
])
def test_the_tile_is_the_largest_the_budget_takes(shape, tile):
    c, l, s, dt = shape
    assert fb._segment_tile(c, l, s, dt, 9, 9, HALO) == tile
    assert fb.pallas_segments_supported(c, l, s, dt) is (tile > 0)


# ------------------------------------- the kernel at a budget-chosen tile

@pytest.fixture(scope="module")
def track():
    cfg = ModelConfig(local_dim=C, global_dim=64, key_dim=16, num_heads=4,
                      num_blocks=1, num_annotations=32, dtype="float32")
    kp, kx, kb = jax.random.split(jax.random.PRNGKey(42), 3)
    block = proteinbert.block_init(kp, cfg)
    params = {k: block[k] for k in ("narrow_conv", "wide_conv", "local_ln1",
                                    "local_dense", "local_ln2")}
    x = jax.random.normal(kx, (2, L, C), jnp.float32)
    bc = jax.random.normal(kb, (2, S, C), jnp.float32)
    return params, x, bc


@pytest.fixture()
def tight_budget(monkeypatch):
    """A budget under which `_pick_tile(512)` = 256 no longer fits at
    C=128 float32 and the plan falls to 128 rows: four tiles a row, so the
    tile the kernel runs at is the budget's choice as at the serving
    shape, at a size the interpreter runs in seconds."""
    def need(tile):
        lp = L + 2 * HALO
        return ((19 * C * C + lp * C + lp * vb.lanes(S) + S * C) * 4
                + vb.track_temp_bytes(tile, C) + tile * vb.lanes(S) * 4)
    monkeypatch.setattr(vb, "VMEM_BUDGET", (need(128) + need(256)) // 2)
    assert fb._pick_tile(L) == 256
    assert fb._segment_tile(C, L, S, "float32", 9, 9, HALO) == 128
    return 128


def _segments(*rows):
    """(rows, L) segment ids from [(id, span), ...]; the rest is pad."""
    seg = np.zeros((len(rows), L), np.int32)
    for i, spans in enumerate(rows):
        pos = 0
        for sid, n in spans:
            seg[i, pos:pos + n] = sid
            pos += n
    return jnp.asarray(seg)


# Tile edges at 128, 256, 384. A served span is quantized to its bucket, so
# its tail is <pad> TOKENS under the span's own segment id: to the local
# track they are positions of the span like any other (they take part in
# the convs); `pad_tails` draws them as one constant vector, as an
# embedding row would be.
LAYOUTS = {
    "boundary_on_a_tile_edge": [[(1, 128), (2, 128), (3, 200)],
                                [(1, 256), (2, 256)]],
    "boundary_inside_the_wide_halo_of_an_edge": [
        [(1, 128 + 7), (2, 256 - 13 - 135), (3, 150)],
        [(1, 384 - 19), (2, 19 + 20), (3, 60)]],
    "pad_tails_inside_a_span": [[(1, 96), (2, 160), (3, 64)],
                                [(1, 480)]],
    "a_whole_pad_row": [[(1, 300), (2, 100)], []],
}


def _kernel(params, x, bc, seg):
    return jax.jit(lambda p, xx, bb, ss: fb.fused_local_track_segments(
        p, xx, bb, ss, 1, 5, True))(params, x, bc, seg)


def _reference(params, x, bc, seg):
    return jax.jit(lambda p, xx, bb, ss: fb.local_track_segment_reference(
        p, xx, fb.gather_segment_broadcast(bb, ss), ss, 1, 5))(
            params, x, bc, seg)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_kernel_at_the_chosen_tile_matches_the_reference(
        track, tight_budget, layout):
    params, x, bc = track
    seg = _segments(*LAYOUTS[layout])
    if layout == "pad_tails_inside_a_span":
        tail = np.zeros((2, L), bool)
        tail[0, 64:96] = tail[0, 200:256] = tail[1, 300:480] = True
        x = jnp.where(jnp.asarray(tail)[..., None], x[0, 0][None, None], x)
    before = dict(fb.PATH_TOTAL)
    got = _kernel(params, x, bc, seg)
    want = _reference(params, x, bc, seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert (fb.PATH_TOTAL.get(("pallas", "packed"), 0)
            == before.get(("pallas", "packed"), 0) + 1)
    assert (fb.PATH_TOTAL.get(("reference", "segments"), 0)
            == before.get(("reference", "segments"), 0))


def test_a_segment_is_bit_identical_when_its_neighbours_change(
        track, tight_budget):
    """The kernel masks by multiplication with an exact 0.0, as
    `_segment_conv` does: the middle segment (its ends inside the wide
    halo of two tile edges) reads the same to the last bit whatever the
    segments either side of it hold, and so does the other row."""
    params, x, bc = track
    seg = _segments([(1, 128 + 7), (2, 256 - 13 - 135), (3, 150)],
                    [(1, 384 - 19), (2, 19 + 20), (3, 60)])
    mine = np.asarray(seg) == 2
    other = jax.random.normal(jax.random.PRNGKey(7), x.shape, x.dtype)
    x2 = jnp.where(jnp.asarray(mine)[..., None], x, other)
    bc2 = bc.at[:, 0].set(-bc[:, 0]).at[:, 2].set(3.0 * bc[:, 2])
    a = np.asarray(_kernel(params, x, bc, seg))
    b = np.asarray(_kernel(params, x2, bc2, seg))
    np.testing.assert_array_equal(a[mine], b[mine])
    assert not np.array_equal(a[~mine], b[~mine])


# ------------------------------------------------------------ the dispatch

ENTRIES = {
    "embed": inference._packed_encode_batch,
    "predict_go": inference._packed_go_probs_batch,
    "predict_residues": inference._packed_residue_probs_batch,
    "predict_task_trunk": heads_apply.packed_trunk_batch,
}


def _model(local_dim, narrow_kernel=9, dtype="bfloat16"):
    return ModelConfig(local_dim=local_dim, global_dim=64, key_dim=16,
                       num_heads=4, num_blocks=1, num_annotations=32,
                       narrow_kernel=narrow_kernel, dtype=dtype)


def _abstract_batch(cfg, rows=1, seq_len=64, segments=4):
    params = jax.eval_shape(lambda k: proteinbert.init(k, cfg),
                            jax.random.PRNGKey(0))
    sds = jax.ShapeDtypeStruct
    return (params, sds((rows, seq_len), jnp.int32),
            sds((rows, seq_len), jnp.int32),
            sds((rows, segments, cfg.num_annotations), jnp.float32))


@pytest.mark.parametrize("guard_holds", [True, False],
                         ids=["guard", "no_plan"])
@pytest.mark.parametrize("local_dim", [512, 1024])
@pytest.mark.parametrize("on_tpu", [False, True], ids=["cpu", "chip"])
@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_forward_only_entries_take_the_kernel_by_the_rule(
        entry, on_tpu, local_dim, guard_holds, monkeypatch):
    """On a TPU at C <= 512: the kernel where the guard holds, and where
    it has no plan the reference, COUNTED (`reference/segments`: a served
    shape that misses the kernel shows in `fused_path`). Everywhere else
    the reference, uncounted as before. Traced only (`eval_shape`):
    nothing is lowered or run. A guard without a plan is an even tap
    count (the symmetric-halo layout)."""
    monkeypatch.setattr(fb, "pallas_compiles", lambda: on_tpu)
    cfg = _model(local_dim, narrow_kernel=9 if guard_holds else 8)
    assert fb.pallas_segments_supported(
        local_dim, 64, 4, "bfloat16", cfg.narrow_kernel) is guard_holds
    before = dict(fb.PATH_TOTAL)
    jax.eval_shape(functools.partial(ENTRIES[entry].__wrapped__, cfg=cfg),
                   *_abstract_batch(cfg))
    moved = {k: v - before.get(k, 0) for k, v in fb.PATH_TOTAL.items()
             if v != before.get(k, 0)}
    if not (on_tpu and local_dim <= 512):
        assert moved == {}
    elif guard_holds:
        assert moved == {("pallas", "packed"): 1}
    else:
        assert moved == {("reference", "segments"): 1}


def test_only_a_tpu_compiles_the_kernel(monkeypatch):
    """The backend predicate names the TPU, not "anything but the CPU": a
    backend with no Mosaic compiler keeps XLA's composition."""
    for backend, takes in (("tpu", True), ("cpu", False), ("gpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert fb.pallas_compiles() is takes


@pytest.mark.parametrize("program", ["grad_of_apply", "train_step",
                                     "eval_step"])
def test_a_differentiated_packed_program_stays_on_xla(program, monkeypatch):
    """`jax.grad` through the packed `apply`, and the packed train and
    eval steps, trace `local_track_segment_reference` whatever the backend
    says: no kernel in the jaxpr, `PATH_TOTAL` unchanged."""
    from proteinbert_tpu.configs import (
        DataConfig, OptimizerConfig, PretrainConfig, TrainConfig,
    )
    from proteinbert_tpu.train import create_train_state, train_state

    monkeypatch.setattr(fb, "pallas_compiles", lambda: True)
    cfg = PretrainConfig(
        model=_model(128, dtype="float32"),
        data=DataConfig(seq_len=64, batch_size=2, packing=True,
                        pack_max_segments=4),
        optimizer=OptimizerConfig(warmup_steps=10),
        train=TrainConfig(max_steps=1))
    assert fb.pallas_segments_supported(128, 64, 4, "float32")
    rng = np.random.default_rng(0)
    seg = np.zeros((2, 64), np.int32)
    seg[:, :30], seg[:, 30:55] = 1, 2
    batch = {
        "tokens": rng.integers(4, 26, size=(2, 64)).astype(np.int32),
        "annotations": (rng.random((2, 4, 32)) < 0.1).astype(np.float32),
        "segment_ids": seg,
    }
    before = dict(fb.PATH_TOTAL)
    if program == "grad_of_apply":
        params = proteinbert.init(jax.random.PRNGKey(0), cfg.model)

        def loss(p):
            ll, gl = proteinbert.apply(
                p, batch["tokens"], batch["annotations"], cfg.model,
                segment_ids=batch["segment_ids"])
            return jnp.sum(ll ** 2) + jnp.sum(gl ** 2)

        jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    else:
        state = create_train_state(jax.random.PRNGKey(0), cfg)
        if program == "train_step":
            def step(s, b):
                return train_state.train_step.__wrapped__(s, b, cfg)
        else:
            def step(s, b):
                return train_state.eval_step.__wrapped__(
                    s, b, jax.random.PRNGKey(3), cfg)
        jaxpr = jax.make_jaxpr(step)(state, batch)
    assert "pallas_call" not in str(jaxpr)
    assert dict(fb.PATH_TOTAL) == before


# ------------------------------- a served batch through the kernel's path

@pytest.fixture()
def kernel_in_the_interpreter(monkeypatch):
    """The chip's dispatch on the CPU: the rule sees a backend that
    compiles the kernel, and the one `pallas_call` it reaches runs in the
    interpreter."""
    real = fb._pallas_segments_forward

    def interpreted(params, x, bc, oh, nd, wd, interpret):
        return real(params, x, bc, oh, nd, wd, True)

    monkeypatch.setattr(fb, "pallas_compiles", lambda: True)
    monkeypatch.setattr(fb, "_pallas_segments_forward", interpreted)


def _packed_batch(cfg, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(4, 26, size=(2, 64)).astype(np.int32)
    seg = np.zeros((2, 64), np.int32)
    seg[0, :20], seg[0, 20:44], seg[0, 44:60] = 1, 2, 3
    seg[1, :33] = 1
    tokens[0, 16:20] = 0      # a span's <pad> tail under its segment id
    tokens[seg == 0] = 0
    ann = (rng.random((2, 4, cfg.num_annotations)) < 0.1
           ).astype(np.float32)
    return jnp.asarray(tokens), jnp.asarray(seg), jnp.asarray(ann)


@pytest.mark.parametrize("entry", sorted(ENTRIES) + ["embed_int8"])
def test_a_served_batch_reads_the_same_on_the_kernel(
        entry, kernel_in_the_interpreter, monkeypatch):
    """Each forward-only entry (and the int8 arm's, whose weights reach
    the kernel dequantized in HLO) on the kernel against the same entry on
    XLA, float32: one answer to the jitted tolerance, and one
    `pallas/packed` a traced executable."""
    from proteinbert_tpu.parallel import quant

    cfg = _model(128, dtype="float32")
    params = proteinbert.init(jax.random.PRNGKey(1), cfg)
    batch = _packed_batch(cfg)
    if entry == "embed_int8":
        fn, params = quant._q_packed_encode_batch, quant.quantize_params(
            params)
    else:
        fn = ENTRIES[entry]
    before = dict(fb.PATH_TOTAL)
    got = jax.jit(functools.partial(fn.__wrapped__, cfg=cfg))(params, *batch)
    assert (fb.PATH_TOTAL.get(("pallas", "packed"), 0)
            == before.get(("pallas", "packed"), 0) + 1)
    assert (fb.PATH_TOTAL.get(("reference", "segments"), 0)
            == before.get(("reference", "segments"), 0))
    monkeypatch.setattr(fb, "pallas_compiles", lambda: False)
    want = jax.jit(functools.partial(fn.__wrapped__, cfg=cfg))(params, *batch)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        atol=2e-4, rtol=2e-4), got, want)


# ------------------------------------------------- the same over a mesh

@pytest.mark.parametrize("kind", ["embed", "predict_go", "predict_task"])
def test_a_mesh_runs_the_entry_on_each_replica(
        kind, kernel_in_the_interpreter, monkeypatch, request):
    """`--serve-mode ragged --mesh`: the partitioner cannot split a Mosaic
    kernel, so the dispatcher calls every packed entry (the shared trunk of
    `predict_task` too) through `parallel/sharding.on_each_replica`: each
    replica runs the entry itself, kernel and all, on its own rows. One
    answer with the one-device dispatcher on XLA, and `pallas/packed`
    counted once for the one program traced."""
    from proteinbert_tpu.configs import DataConfig, PretrainConfig
    from proteinbert_tpu.parallel import mesh_for_devices
    from proteinbert_tpu.parallel.sharding import on_each_replica
    from proteinbert_tpu.serve import RaggedDispatcher

    cfg = PretrainConfig(model=_model(128, dtype="float32"),
                         data=DataConfig(seq_len=64))
    params = proteinbert.init(jax.random.PRNGKey(1), cfg.model)
    mesh = mesh_for_devices(2)
    tokens, seg, ann = (np.asarray(a) for a in _packed_batch(cfg.model))
    sharded = RaggedDispatcher(params, cfg, buckets=(16, 32, 64),
                               rows_per_batch=2, max_segments=4, mesh=mesh)
    entry = (heads_apply.packed_trunk_batch if kind == "predict_task"
             else ENTRIES[kind])
    fn = (sharded._packed_trunk_fn() if kind == "predict_task"
          else sharded._packed_fn(kind))
    assert fn is on_each_replica(entry, mesh) and fn is not entry
    # One program an (entry, mesh) for the whole process: traced here with
    # the kernel steered in, it must not be found by a later test (nor an
    # earlier one's found here, where the counter would not move).
    fn.clear_cache()
    request.addfinalizer(fn.clear_cache)
    before = dict(fb.PATH_TOTAL)
    got = fn(sharded.params, *sharded._place_packed(tokens, seg, ann),
             cfg.model)
    assert (fb.PATH_TOTAL.get(("pallas", "packed"), 0)
            == before.get(("pallas", "packed"), 0) + 1)
    for leaf in jax.tree.leaves(got):
        assert len(leaf.sharding.device_set) == 2
    monkeypatch.setattr(fb, "pallas_compiles", lambda: False)
    single = RaggedDispatcher(params, cfg, buckets=(16, 32, 64),
                              rows_per_batch=2, max_segments=4)
    plain = (single._packed_trunk_fn() if kind == "predict_task"
             else single._packed_fn(kind))
    assert plain is entry
    want = jax.jit(functools.partial(entry.__wrapped__, cfg=cfg.model))(
        params, tokens, seg, ann)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        atol=2e-4, rtol=2e-4), got, want)
