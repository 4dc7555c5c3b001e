"""The experts' loop's row movers (kernels/moe_rows.py, ops/moe.py): the
Pallas kernels in the interpreter against the plain path, the loop with
the kernel path forced against the plain loop, and the counters that say
which path a traced loop took and how many block rows it did not move."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import glm4_moe_lite_f32 as ref
from proteinbert_tpu.configs import get_preset
from proteinbert_tpu.kernels import moe_rows
from proteinbert_tpu.ops import moe

T, BLOCK = 96, 16
WIDTHS = [256, 640]     # 640 = 5 x 128: whole lanes, not whole tiles (as 2,560)


def _rows(width, dtype, seed=0):
    rng = np.random.default_rng(seed)
    src = rng.standard_normal((T + BLOCK, width)).astype(np.float32)
    src[T:] = 0.0
    tok = np.sort(rng.choice(T, BLOCK, replace=False)).astype(np.int32)
    upd = rng.standard_normal((BLOCK, width)).astype(np.float32)
    return jnp.asarray(src, dtype), tok, jnp.asarray(upd)


@pytest.mark.parametrize("n", [0, 1, 11, BLOCK])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("width", WIDTHS)
def test_gather_rows_takes_the_real_rows_and_zeros_the_rest(width, dtype, n):
    src, tok, _ = _rows(width, dtype)
    # the rows past n point anywhere: what lies there must not come back
    got = moe_rows.gather_rows(moe_rows.pack(src), jnp.asarray(tok),
                               jnp.int32(n), interpret=True)
    assert got.dtype == src.dtype
    got = np.asarray(moe_rows.unpack(got, width), np.float32)
    want = np.asarray(src, np.float32)[tok]
    np.testing.assert_array_equal(got[:n], want[:n])
    assert not got[n:].any()
    # the plain path reads the same through tok's spare rows
    spare = np.where(np.arange(BLOCK) < n, tok, T + np.arange(BLOCK)).astype(np.int32)
    plain = moe._gather_rows(src, jnp.asarray(spare), jnp.int32(n), width)
    np.testing.assert_array_equal(np.asarray(plain, np.float32), got)


@pytest.mark.parametrize("n", [0, 1, 11, BLOCK])
@pytest.mark.parametrize("width", WIDTHS)
def test_scatter_add_rows_adds_at_the_real_rows_and_touches_no_other(width, n):
    dst, tok, upd = _rows(width, "float32", seed=1)
    dst = dst.at[T:].set(7.0)       # a spare row the kernel must leave alone
    got = moe_rows.scatter_add_rows(moe_rows.pack(dst), moe_rows.pack(upd),
                                    jnp.asarray(tok), jnp.int32(n), interpret=True)
    got = np.asarray(moe_rows.unpack(got, width))
    want = np.asarray(dst).copy()
    want[tok[:n]] += np.asarray(upd)[:n]
    np.testing.assert_array_equal(got, want)
    # the lanes past the width stay zeros
    assert not np.asarray(moe_rows.pack(jnp.asarray(got)))[..., width:].any()


def test_a_token_in_consecutive_blocks_is_summed_once_each():
    """A token recurs in the blocks of the other experts it chose: each
    call's rows are back in HBM before the next call reads them."""
    dst, tok, upd = _rows(256, "float32", seed=2)
    acc = moe_rows.pack(jnp.zeros_like(dst))
    for k in range(3):
        acc = moe_rows.scatter_add_rows(acc, moe_rows.pack(upd * (k + 1)),
                                        jnp.asarray(tok), jnp.int32(BLOCK - k),
                                        interpret=True)
    want = np.zeros(dst.shape, np.float32)
    for k in range(3):
        want[tok[:BLOCK - k]] += np.asarray(upd)[:BLOCK - k] * (k + 1)
    np.testing.assert_allclose(np.asarray(moe_rows.unpack(acc, 256)), want, rtol=1e-6)


@pytest.fixture
def kernel_path(monkeypatch):
    """Every `lax.platform_dependent` on its TPU branch and every
    `pallas_call` in the interpreter; yields the kernels' names as called."""
    from jax import lax
    from jax.experimental import pallas as pl

    called, pallas_call = [], pl.pallas_call

    def interpreted(*args, **kwargs):
        called.append(kwargs["name"])
        return pallas_call(*args, **{**kwargs, "interpret": True})

    monkeypatch.setattr(pl, "pallas_call", interpreted)
    monkeypatch.setattr(lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    return called


def _layer(width, tokens=80, experts=8, top_k=2, hidden=32, seed=0):
    rng = np.random.default_rng(seed)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)  # noqa: E731
    x = draw(tokens, width) * 10
    ids = jnp.asarray(np.stack([rng.permutation(experts + 2)[:top_k]
                                for _ in range(tokens)]), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (tokens, top_k)), jnp.float32)
    return (x, weights, draw(experts, width, hidden), draw(experts, width, hidden),
            draw(experts, hidden, width)), ids


@pytest.mark.parametrize("width", WIDTHS)
def test_the_loop_on_the_kernel_path_equals_the_plain_loop(width, kernel_path,
                                                           monkeypatch):
    """`expert_ffn` forward and gradient: slabs moved by the kernels (part
    -full blocks, experts with nothing, assignments to experts not held)
    against the same loop over a width that takes no slabs' plain path."""
    operands, ids = _layer(width)
    block, top_k = 16, 2
    plan = moe.plan_dispatch(ids, 8, 0, block)

    def loss(x, w, g, u, d):
        return (moe.expert_ffn(x, w, g, u, d, plan, top_k, block) ** 2).sum()

    got = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(*operands)
    assert sorted(set(kernel_path)) == ["moe_gather_rows", "moe_scatter_add_rows"]
    assert kernel_path.count("moe_gather_rows") == 3       # forward 1, backward 2
    monkeypatch.setattr(moe_rows, "slabs_fit", lambda width: False)
    want = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(*operands)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-6, atol=1e-6)


@pytest.mark.parametrize("path", ["kernels", "plain_slabs"])
def test_no_assignment_is_dropped_under_total_imbalance_on_slabs(path, request):
    """`test_no_assignment_is_dropped_when_every_token_takes_one_expert`
    at a width that is held as slabs: every token on experts 0 and 1, all
    taken, the dense sum and its gradient."""
    if path == "kernels":
        request.getfixturevalue("kernel_path")
    cfg = dataclasses.replace(get_preset("glm_tiny").model, hidden_size=128)
    c = dataclasses.asdict(cfg)
    params, _ = ref.init_params(jax.random.PRNGKey(5), c)
    layer = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
    bias = jnp.zeros((8,)).at[0].set(100.0).at[1].set(50.0)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((128, 128)), jnp.float32)
    real = jnp.ones((128,), bool)
    y, stats = moe.moe_apply(layer, bias, x, real, cfg)
    assert stats["held_counts"].tolist() == [128, 128, 0, 0, 0, 0, 0, 0]
    assert int(stats["dropped"]) == 0
    assert int(stats["block_rows"]) == 256
    want, _, _ = ref._routed(layer, bias, x, real, c, "f32")
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=4e-6)

    def dense(p):
        return (ref._routed(p, bias, x, real, c, "f32")[0] ** 2).sum()

    def grouped(p):
        return (moe.moe_apply(p, bias, x, real, cfg)[0] ** 2).sum()

    for a, b in zip(jax.tree.leaves(jax.grad(grouped)(layer)),
                    jax.tree.leaves(jax.grad(dense)(layer))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("width,backend,noted", [
    (256, "tpu", ("pallas", "slabs")),
    (256, "cpu", ("reference", "not_tpu")),
    (64, "tpu", ("reference", "row_not_lanes")),
])
def test_a_traced_loop_notes_its_path_and_the_reason(width, backend, noted,
                                                     monkeypatch):
    operands, ids = _layer(width, tokens=32)
    plan = moe.plan_dispatch(ids, 8, 0, 16)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    seen = []
    observer = lambda p, r: seen.append((p, r))  # noqa: E731
    moe_rows.register_moe_rows_path_observer(observer)
    try:
        before = moe_rows.MOE_ROWS_PATH_TOTAL.get(noted, 0)
        jax.eval_shape(lambda *o: moe.expert_ffn(*o, plan, 2, 16), *operands)
    finally:
        moe_rows.unregister_moe_rows_path_observer(observer)
    assert seen == [noted]
    assert moe_rows.MOE_ROWS_PATH_TOTAL[noted] == before + 1


def test_block_rows_counts_what_the_old_form_moved():
    """`block_rows` = blocks filled x block: with `held_counts` it gives
    the share of the block rows that are real, which is all that moves."""
    _, ids = _layer(256, tokens=80)
    plan = moe.plan_dispatch(ids, 8, 0, 16)
    counts = np.asarray(plan.held_counts)
    assert int(plan.n_blocks) * 16 == int((-(-counts // 16) * 16).sum())
    assert int(plan.block_rows.sum()) == int(counts.sum())


def test_a_server_hands_out_block_rows_and_the_movers_path():
    """`Server.stats()`: `routing.block_rows` beside the assignments
    held (held <= block rows < held + a block for every expert and
    layer), and which movers its executables were traced with."""
    from proteinbert_tpu.models import glm_moe
    from proteinbert_tpu.serve import Server

    cfg = get_preset("ling_tiny")
    m = cfg.model
    params = glm_moe.init_served(jax.random.PRNGKey(0), m)
    rng = np.random.default_rng(3)
    docs = [rng.integers(0, m.vocab_size, n) for n in (20, 30, 7, 41)]
    before = dict(moe_rows.MOE_ROWS_PATH_TOTAL)
    with Server(params, cfg, serve_mode="ragged", max_batch=2,
                pack_max_segments=4, cache_size=0) as server:
        for f in [server.submit("embed", d) for d in docs]:
            f.result(timeout=300)
        stats = server.stats()
    routing = stats["routing"]
    held = routing["assignments_held"]
    assert held == sum(len(d) for d in docs) * m.num_experts_per_tok * m.num_moe_layers
    spare = routing["batches"] * m.num_moe_layers * m.experts_held * m.expert_block
    assert held <= routing["block_rows"] < held + spare
    assert routing["block_rows"] % m.expert_block == 0
    # ling_tiny's rows are 64 wide: no slabs, XLA's movers, and said so
    key = ("reference", "row_not_lanes")
    assert stats["moe_rows_path"]["reference/row_not_lanes"] > before.get(key, 0)


def test_a_train_step_reports_block_rows_among_its_scalars():
    from proteinbert_tpu.train import train_state as ts

    cfg = get_preset("glm_tiny")
    m = cfg.model
    rng = np.random.default_rng(0)
    seg = np.stack([np.repeat([1, 2, 0], [30, 30, 4]), np.repeat([1, 2], [40, 24])])
    batch = {"tokens": rng.integers(0, m.vocab_size, (2, 64)).astype(np.int32),
             "segment_ids": seg.astype(np.int32)}
    _, metrics = ts.train_step(ts.create_train_state(jax.random.PRNGKey(0), cfg),
                               batch, cfg)
    rows, held = float(metrics["block_rows"]), float(metrics["assignments_held"])
    assert metrics["block_rows"].ndim == 0
    loops = m.num_moe_layers + m.num_nextn_predict_layers
    assert held <= rows < held + loops * m.experts_held * m.expert_block
    assert rows % m.expert_block == 0
