"""Nemotron 3 (`nemotron_h`: Mamba-2 state-space layers, attention over
grouped keys with no position term, LatentMoE with squared-ReLU experts,
one sublayer a layer by a published pattern) on the serving path at
`nemotron_tiny` widths, against the plain reference
(`benchmark/reference/nemotron_h_f32.py`): the whole model through
`Server.submit`, the chunked recurrence against the token recurrence
across document boundaries inside a chunk, document isolation of the
state and of the convolution, the four shares' sum against the uncut
layer, the expert loop's two kinds, the table that carries the three
served stacks, and what is refused by name."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import nemotron_flops
from benchmark.drivers import nemotron_serve
from benchmark.reference import nemotron_h_f32 as ref
from proteinbert_tpu import inference
from proteinbert_tpu.configs import get_preset
from proteinbert_tpu.models import glm_moe
from proteinbert_tpu.ops import moe, ssd
from proteinbert_tpu.serve.server import Server
from tests.test_zaya_decoder import _alone_and_packed, _err, _packed

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3000000043
TOP = 2 ** 20


def _file(name):
    with open(os.path.join(ROOT, "benchmark/configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("nemotron_tiny")
    return cfg, nemotron_serve.reference_sizes(_file("nemotron-tiny"), cfg)


@pytest.fixture(scope="module")
def served(tiny):
    cfg, _ = tiny
    return glm_moe.init_served(ref.seed_key(SEED), cfg.model)


def _wake(index, tree):
    """Every leaf the recipe leaves at 0 or 1 (the convolution's bias, D,
    every norm's scale) moved, the same way in both trees: a mechanism
    that is never applied would else go unnoticed."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = []
    for j, (path, leaf) in enumerate(flat):
        name = str(path[-1].key)
        if name in ("conv_bias", "ssm_D") or "norm" in name:
            rng = np.random.default_rng([index, j])
            leaf = leaf + (0.3 * rng.normal(size=leaf.shape)).astype(np.float32)
        leaves.append(leaf)
    return jax.tree.unflatten(treedef, leaves)


def _woken(params, m):
    """`_wake` over the program's tree: the top, then stack by stack."""
    top = _wake(TOP, {k: params[k] for k in ("embed", "final_norm")})
    out = dict(params, **top)
    for kind, indices in glm_moe._kind_indices(m).items():
        layers = [_wake(index, jax.tree.map(lambda a: a[n], params[kind]))
                  for n, index in enumerate(indices)]
        out[kind] = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    return out


# ------------------------------------------------------------ whole model

def test_the_whole_model_through_submit_equals_the_reference(tiny, served):
    """Documents of token ids through `Server.submit("embed", ids)`: the
    queue, the online packer, the span ladder, the row classes and the
    packed executable; each answer against the reference on that
    document ALONE, weights from the same seed by the same recipe."""
    cfg, c = tiny
    rng = np.random.default_rng(3)
    docs = [rng.integers(0, cfg.model.vocab_size, n)
            for n in (20, 30, 7, 41, 15, 64, 3, 33, 8, 1)]
    inference._packed_decoder_embed_batch.clear_cache()
    with Server(served, cfg, serve_mode="ragged", max_batch=2,
                pack_max_segments=4, cache_size=0) as server:
        got = [f.result(timeout=300)
               for f in [server.submit("embed", d) for d in docs]]
        stats = server.stats()
    tokens = sum(len(d) for d in docs)
    assert stats["routing"]["dropped_assignments"] == 0
    assert stats["routing"]["real_tokens"] == tokens
    # every expert held: top 4 in each of the 6 expert layers of the 13
    assert stats["routing"]["assignments_held"] == tokens * 4 * 6
    assert set(stats["batch_class_counts"]) <= {1, 2}
    want = ref.embed_documents(SEED, docs, c)
    for g, w in zip(got, want):
        assert g["global"].dtype == np.float32 and g["global"].shape == (64,)
        for key in ("global", "local_mean"):
            assert _err(g[key], w[key]) < 1e-5, key


def test_every_learned_vector_is_applied(tiny, served):
    """The recipe starts the convolution's bias, D and the norms' scales
    at 0 or 1, where leaving one out changes nothing: with all of them
    moved, in the program's tree and in the reference's alike, a packed
    batch still equals the reference, and moving them mattered."""
    cfg, c = tiny
    m = cfg.model
    tokens, seg, docs = _packed([[20, 30, 7], [41, 15]], 64, m.vocab_size)
    run = jax.jit(lambda p: glm_moe.served_embed(p, tokens, seg, 4, m))
    got, plain = run(_woken(served, m)), run(served)
    want = ref.embed_documents(SEED, [d for _, _, d in docs], c, edit=_wake)
    for (r, s, _), w in zip(docs, want):
        for key in ("global", "local_mean"):
            assert _err(got[key][r, s], w[key]) < 1e-5, (key, r, s)
            assert _err(plain[key][r, s], w[key]) > 1e-2


def test_each_kind_of_layer_moves_the_answer(tiny, served):
    """A layer kind whose result is lost (a dead squared ReLU, a decay
    that forgets everything, 22 equal weights on a zero latent) would
    pass every comparison with a reference that loses it too: with the
    stack of one kind zeroed the answer has to move."""
    cfg, _ = tiny
    m = cfg.model
    tokens, seg, _ = _packed([[20, 30, 7], [41, 15]], 64, m.vocab_size)
    run = jax.jit(lambda p: glm_moe.served_embed(p, tokens, seg, 4, m)["global"])
    whole = np.asarray(run(served))
    for kind, leaf in (("mamba", ("mixer", "o")), ("gqa", ("mixer", "o")),
                       ("latent_moe", ("moe", "from_latent")),
                       ("latent_moe", ("shared", "down"))):
        stack = jax.tree.map(lambda a: a, served[kind])
        stack[leaf[0]] = dict(stack[leaf[0]], **{leaf[1]: 0.0 * stack[leaf[0]][leaf[1]]})
        moved = _err(run(dict(served, **{kind: stack}))[0, 1], whole[0, 1])
        assert moved > 1e-3, (kind, leaf, moved)


# ------------------------------------------------- the state-space recurrence

def _ssd_operands(seed=0, B=2, L=64, H=8, P=4, G=2, N=6):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, L, H)) - 2.0)
    a = -jnp.exp(jax.random.normal(k[2], (H,)))
    b = jax.random.normal(k[3], (B, L, G, N))
    c = jax.random.normal(k[4], (B, L, G, N))
    # row 0: a boundary INSIDE the first chunk (7), a document over three
    # chunks (38), one that ends inside a chunk, pad; row 1: one document
    # over all four chunks
    seg = jnp.asarray(np.stack([np.r_[[1] * 7, [2] * 38, [3] * 10, [0] * 9],
                                np.ones(64)]).astype(np.int32))
    return x, dt, a, b, c, seg


def test_the_chunked_recurrence_is_the_token_recurrence():
    x, dt, a, b, c, seg = _ssd_operands()
    want = ssd.ssd_recurrent(x, dt, a, b, c, seg)
    got = ssd.ssd_chunked(x, dt, a, b, c, seg, 16)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and each document of the row is what it is ALONE, from a zero state
    alone = ssd.ssd_recurrent(x[:1, 7:45], dt[:1, 7:45], a, b[:1, 7:45],
                              c[:1, 7:45], jnp.ones((1, 38), jnp.int32))
    np.testing.assert_allclose(got[0, 7:45], alone[0], atol=2e-5)


def test_the_chunked_recurrence_differentiates_to_the_token_recurrences():
    x, dt, a, b, c, seg = _ssd_operands(seed=1)
    real = (seg > 0)[..., None, None]

    def loss(fn, x, dt, a, b, c):
        return jnp.sum(jnp.where(real, fn(x, dt, a, b, c), 0.0) ** 2)

    chunked = lambda *o: ssd.ssd_chunked(*o, seg, 16)  # noqa: E731
    token = lambda *o: ssd.ssd_recurrent(*o, seg)  # noqa: E731
    got = jax.grad(loss, argnums=(1, 2, 3, 4, 5))(chunked, x, dt, a, b, c)
    want = jax.grad(loss, argnums=(1, 2, 3, 4, 5))(token, x, dt, a, b, c)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-4 * float(jnp.abs(w).max()))


def test_bfloat16_products_keep_the_state_and_the_decays_in_float32():
    x, dt, a, b, c, seg = _ssd_operands(seed=2)
    want = ssd.ssd_recurrent(x, dt, a, b, c, seg)
    got = ssd.ssd_chunked(x, dt, a, b, c, seg, 16, jnp.bfloat16)
    assert got.dtype == jnp.float32
    assert 1e-4 < _err(got, np.asarray(want)) < 2e-2


def test_the_gated_group_norm_gates_then_norms_each_group_apart():
    rng = np.random.default_rng(0)
    y, z = rng.normal(size=(5, 12)), rng.normal(size=(5, 12))
    scale = rng.normal(size=12)
    g = (y * (z / (1 + np.exp(-z)))).reshape(5, 3, 4)
    want = (g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)).reshape(5, 12) * scale
    got = ssd.gated_group_norm(jnp.asarray(scale, jnp.float32), jnp.asarray(y, jnp.float32),
                               jnp.asarray(z, jnp.float32), 3, 1e-5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


# ----------------------------------------------------------------- isolation

def test_a_document_packed_after_others_answers_as_it_does_alone(tiny, served):
    cfg, _ = tiny
    assert _alone_and_packed(_woken(served, cfg.model), cfg.model,
                             [[9, 16, 21, 8], [33, 24]]) < 1e-5


@pytest.mark.parametrize("reads_across", ["state", "convolution", "keys"])
def test_the_isolation_test_fails_if_anything_reads_across_a_boundary(
        tiny, served, reads_across, monkeypatch):
    """The recurrence's state, the convolution and attention read
    backwards along the packed row; each made blind to the documents'
    bounds in turn, the test above has to fail."""
    cfg, _ = tiny
    blind = lambda ids: jnp.zeros_like(ids)  # noqa: E731
    if reads_across == "state":
        real = glm_moe.ssd_chunked
        monkeypatch.setattr(glm_moe, "ssd_chunked", lambda x, dt, a, b, c, ids, *rest:
                            real(x, dt, a, b, c, blind(ids), *rest))
    elif reads_across == "convolution":
        real = glm_moe.segment_conv
        monkeypatch.setattr(glm_moe, "segment_conv",
                            lambda x, k, ids: real(x, k, blind(ids)))
    else:
        real = glm_moe._grouped_key_core
        monkeypatch.setattr(glm_moe, "_grouped_key_core", lambda q, k, v, ids, cfg:
                            real(q, k, v, blind(ids), cfg))
    assert _alone_and_packed(_woken(served, cfg.model), cfg.model,
                             [[9, 16, 21, 8], [33, 24]]) > 1e-3


# ------------------------------------------------------------- the expert layer

def test_the_routers_choice_of_22_is_the_references(tiny):
    cfg, c = tiny
    m = dataclasses.replace(cfg.model, n_routed_experts=64, num_experts_per_tok=22)
    rng = np.random.default_rng(0)
    h = rng.normal(size=(96, m.hidden_size)).astype(np.float32)
    router = (0.3 * rng.normal(size=(m.hidden_size, 64))).astype(np.float32)
    bias = (0.05 * rng.normal(size=(64,))).astype(np.float32)
    ids, w = moe.route(h, router, bias, 22, m.routed_scaling_factor, m.norm_topk_prob)
    want_ids, want_w = ref.route(jnp.asarray(h), router, bias,
                                 dict(c, num_experts_per_tok=22))
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want_ids, -1))
    np.testing.assert_allclose(np.sort(w, -1), np.sort(want_w, -1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 5.0, rtol=1e-5)


def _latent_layer(m, rng):
    D, U, F, R, W = (m.hidden_size, m.moe_latent_size, m.moe_intermediate_size,
                     m.n_routed_experts, m.moe_shared_expert_intermediate_size)
    w = lambda *s: (0.2 * rng.normal(size=s)).astype(np.float32)  # noqa: E731
    return {"moe": {"router": w(D, R), "router_bias": np.zeros(R, np.float32),
                    "to_latent": w(D, U), "from_latent": w(U, D),
                    "experts": {"up": w(R, U, F), "down": w(R, F, U)}},
            "shared": {"up": w(D, W), "down": w(W, D)}}


def test_the_four_shares_add_up_to_the_uncut_layer(tiny):
    """Each share's routed part (its experts between the ONE product down
    and the one product up, which every chip holds whole), plus the
    shared expert ONCE, is what the uncut reference layer gives: the cut
    of the configuration (this chip holds a quarter of the experts,
    routes over all 22 of a token) loses nothing but the absent experts'
    part, and W_up is linear, so the parts add up."""
    cfg, c = tiny
    m = cfg.model
    rng = np.random.default_rng(1)
    full = _latent_layer(m, rng)
    h = rng.normal(size=(80, m.hidden_size)).astype(np.float32)
    real = np.ones(80, bool)
    routed, shared, _ = ref.latent_moe(
        jax.tree.map(jnp.asarray, full), jnp.asarray(h), jnp.asarray(real),
        dict(c, n_routed_experts=m.n_routed_experts, expert_offset=0), "f32")
    want = np.asarray(routed + shared)

    total, assignments = np.asarray(shared), 0
    held = m.n_routed_experts // 4
    for share in range(4):
        cut = dataclasses.replace(m, experts_held=held, expert_offset=share * held)
        part = dict(full["moe"], experts={
            k: jnp.asarray(v[share * held:(share + 1) * held])
            for k, v in full["moe"]["experts"].items()})
        y, stats = moe.moe_apply(part, full["moe"]["router_bias"], jnp.asarray(h),
                                 jnp.asarray(real), cut)
        assert int(stats["dropped"]) == 0
        assignments += int(stats["held_counts"].sum())
        total = total + np.asarray(y)
    assert assignments == 80 * m.num_experts_per_tok
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=2e-5)


def _dense_experts(x, weights, ids, experts, kind):
    """Every expert over every token, masked by the choice."""
    y = jnp.zeros_like(x)
    for e in range(experts["up"].shape[0]):
        mine = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        if kind == "swiglu":
            h = jax.nn.silu(x @ experts["gate"][e]) * (x @ experts["up"][e])
        else:
            h = jnp.square(jax.nn.relu(x @ experts["up"][e]))
        y = y + mine[:, None] * (h @ experts["down"][e])
    return y


@pytest.mark.parametrize("kind", moe.KINDS)
def test_the_expert_loop_of_either_kind_equals_every_expert_over_every_token(kind):
    """Values and all five gradients of `expert_ffn`, SwiGLU (three
    matrices) and squared ReLU (two, no gate) alike, at an input width
    that is not the stream's."""
    rng = np.random.default_rng(5)
    T, U, F, E, K, block = 40, 24, 20, 6, 3, 8
    w = lambda *s: jnp.asarray((0.3 * rng.normal(size=s)).astype(np.float32))  # noqa: E731
    x, weights = w(T, U), jnp.abs(w(T, K))
    ids = jnp.asarray(np.stack([rng.permutation(E)[:K] for _ in range(T)]).astype(np.int32))
    experts = {"up": w(E, U, F), "down": w(E, F, U)}
    if kind == "swiglu":
        experts["gate"] = w(E, U, F)
    plan = moe.plan_dispatch(ids, E, 0, block)

    def loop(x, weights, experts):
        return jnp.sum(moe.expert_ffn(x, weights, experts.get("gate"), experts["up"],
                                      experts["down"], plan, K, block, kind) ** 2)

    def dense(x, weights, experts):
        return jnp.sum(_dense_experts(x, weights, ids, experts, kind) ** 2)

    got = jax.value_and_grad(loop, argnums=(0, 1, 2))(x, weights, experts)
    want = jax.value_and_grad(dense, argnums=(0, 1, 2))(x, weights, experts)
    for g, w_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w_, rtol=2e-4, atol=2e-5)
    with pytest.raises(ValueError, match="expert kind"):
        moe.expert_ffn(x, weights, None, experts["up"], experts["down"], plan, K,
                       block, "swiglu")


# What `ops/moe.expert_ffn` gave on the three presets that had it before
# the expert's kind became its parameter (PR 42's tree, this container):
# sha256 over the bytes of y and of the gradients of x, the weights and
# the three matrices. The SwiGLU path computes what it computed.
SWIGLU_BEFORE = {
    "glm_tiny": "48db14f18fb46ece313b92d80a3b3822cc97a9caba3cf738f16bd916a50cc735",
    "ling_tiny": "6181522cda11563b1e4a1ee7c6af08b81df75f55f71492e7849c1bfa688fbb44",
    "zaya_tiny": "f630fc70fa48d161f2b22bcd5020c7e860783944a7c2bc19e5b9680d1506c252",
}


@pytest.mark.parametrize("preset", sorted(SWIGLU_BEFORE))
def test_the_swiglu_loop_is_bit_for_bit_what_it_was(preset):
    m = get_preset(preset).model
    rng = np.random.default_rng(11)
    T, D, F, E, R = (96, m.hidden_size, m.moe_intermediate_size, m.experts_held,
                     m.n_routed_experts)
    w = lambda *s: jnp.asarray((0.2 * rng.normal(size=s)).astype(np.float32))  # noqa: E731
    x, router = w(T, D) * 5, w(D, R)
    experts = {"gate": w(E, D, F), "up": w(E, D, F), "down": w(E, F, D)}
    ids, weights = moe.route(x, router, jnp.zeros(R), m.num_experts_per_tok,
                             m.routed_scaling_factor, m.norm_topk_prob, m.n_group,
                             m.topk_group)
    plan = moe.plan_dispatch(ids, E, 0, m.expert_block)

    def loss(x, weights, gate, up, down):
        y = moe.expert_ffn(x, weights, gate, up, down, plan,
                           m.num_experts_per_tok, m.expert_block)
        return (y ** 2).sum(), y

    (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(
        x, weights, experts["gate"], experts["up"], experts["down"])
    digest = hashlib.sha256()
    for a in (y, *grads):
        digest.update(np.asarray(a).tobytes())
    assert digest.hexdigest() == SWIGLU_BEFORE[preset]


# ------------------------------------------------- the table and the trees

def test_one_table_carries_the_three_served_stacks():
    table = lambda preset: [k for _, k in glm_moe.layer_table(  # noqa: E731
        get_preset(preset).model)]
    M, A, E = "mamba", "gqa", "latent_moe"
    assert table("nemotron_tiny") == [M, E, M, E, M, E, M, A, E, M, E, M, E]
    assert table("nemotron3super_ep4") == table("nemotron_tiny")
    assert table("ling_tiny") == (["kda_dense"] + ["kda_moe"] * 3 + ["mla_moe"]
                                  + ["kda_moe"] * 2)
    assert table("zaya_tiny") == ["cca"] * 4
    assert [i for i, _ in glm_moe.layer_table(get_preset("ling_tiny").model)] == list(
        range(1, 8))
    # a repeating unit of kinds is one scan: what is traced is 7 layers of 13
    assert glm_moe._compress(tuple(table("nemotron_tiny"))) == [
        ((M, E), 3), ((M,), 1), ((A,), 1), ((E, M), 2), ((E,), 1)]
    assert glm_moe._compress(("a",) * 5 + ("b",) + ("a",) * 5 + ("b",)) == [
        (("a",) * 5 + ("b",), 2)]
    assert glm_moe._compress(("a", "b", "c")) == [(("a",), 1), (("b",), 1), (("c",), 1)]
    whole = get_preset("nemotron3super_ep4").model
    uncut = dataclasses.replace(whole, num_hidden_layers=88)
    kinds = [k for _, k in glm_moe.layer_table(uncut)]
    assert (kinds.count(M), kinds.count(E), kinds.count(A)) == (40, 40, 8)
    stage2 = dataclasses.replace(whole, first_layer_index=13)
    assert "".join({M: "M", A: "*", E: "E"}[k] for _, k in glm_moe.layer_table(
        stage2)) == whole.hybrid_override_pattern[13:26]


def test_the_trees_count_is_the_files(tiny):
    cfg, c = tiny
    assert (glm_moe.served_param_count(cfg.model) == ref.param_count(c)
            == nemotron_flops.param_count(c) == _file("nemotron-tiny")["parameters"])
    big, file = get_preset("nemotron3super_ep4"), _file("nemotron-3-super-ep4")
    sizes = nemotron_serve.reference_sizes(file, big)
    assert (glm_moe.served_param_count(big.model) == ref.param_count(sizes)
            == nemotron_flops.param_count(sizes) == file["parameters"]
            == 5_382_756_608)
    # the same shapes at 88 layers, 512 experts, the whole vocabulary and
    # the untied head: the card's "120B"
    whole = nemotron_flops.published_param_count(file["published"])
    assert whole == 120_668_687_360 and round(whole / 1e9, 2) == 120.67
    uncut = dict(sizes, num_hidden_layers=88, n_routed_experts=512, vocab_size=131_072)
    assert ref.param_count(uncut) + 131_072 * 4096 == whole
    # active a token: everything but the 490 experts a layer it does not choose
    active = whole - 40 * (512 - 22) * nemotron_flops.expert_params(sizes)
    assert round(active / 1e9, 2) == 12.77
    m = dataclasses.replace(cfg.model, param_dtype="bfloat16")
    params = glm_moe.init_served(ref.seed_key(5), m)
    assert {leaf.dtype for leaf in jax.tree.leaves(params)} == {jnp.dtype("bfloat16")}
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == (
        glm_moe.served_param_count(m) + 6 * m.n_routed_experts)
    abstract = glm_moe.served_abstract(m)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), abstract) == jax.tree.map(
        lambda a: (a.shape, a.dtype), params)


def test_the_mamba_recipe_is_the_reference_initialisation(served, tiny):
    cfg, _ = tiny
    mix = served["mamba"]["mixer"]
    a, dt = np.exp(np.asarray(mix["ssm_A_log"])), np.asarray(
        jax.nn.softplus(mix["ssm_dt_bias"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 2.0
    assert 0.9e-3 <= dt.min() and dt.max() <= 0.11 and dt.max() / dt.min() > 5
    np.testing.assert_array_equal(np.asarray(mix["ssm_D"]), 1.0)
    np.testing.assert_array_equal(np.asarray(mix["conv_bias"]), 0.0)
    assert abs(float(np.asarray(mix["conv"]).std()) - 0.5) < 0.05
    # a latent expert's second matrix writes the latent, not the stream
    moe_ = served["latent_moe"]["moe"]
    assert abs(float(np.asarray(moe_["experts"]["down"]).std()) - cfg.model.init_std) < 0.01
    assert abs(float(np.asarray(moe_["from_latent"]).std()) - cfg.model.out_init_std) < 0.005
    # a squared ReLU's second matrix is centred: its input's mean writes nothing
    for down in (moe_["experts"]["down"], served["latent_moe"]["shared"]["down"]):
        assert float(np.abs(np.asarray(down).mean(axis=-2)).max()) < 2e-4   # bfloat16's rounding
    assert float(np.abs(np.asarray(moe_["experts"]["up"]).mean(axis=-2)).max()) > 5e-3


def test_the_configuration_file_states_every_published_width():
    file = _file("nemotron-3-super-ep4")
    pub = file["published"]
    assert file["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    for key, value in pub.items():
        if key not in file["reduced"]:
            assert file[key] == value, key
    assert (pub["num_hidden_layers"], pub["n_routed_experts"], pub["vocab_size"]) == (
        88, 512, 131_072)
    assert (file["num_hidden_layers"], file["n_routed_experts"], file["vocab_size"],
            file["router_width"]) == (13, 128, 32_768, 512)
    assert (file["hidden_size"], file["mamba_num_heads"], file["mamba_head_dim"],
            file["ssm_state_size"], file["n_groups"], file["conv_kernel"],
            file["chunk_size"]) == (4096, 128, 64, 128, 8, 4, 128)
    assert (file["num_attention_heads"], file["num_key_value_heads"], file["head_dim"],
            file["num_experts_per_tok"], file["routed_scaling_factor"],
            file["moe_latent_size"], file["moe_intermediate_size"],
            file["moe_shared_expert_intermediate_size"]) == (32, 2, 128, 22, 5, 1024,
                                                             2688, 5376)
    assert file["pattern_held"] == pub["hybrid_override_pattern"][:13] == "MEMEMEM*EMEME"
    assert "28 chips" in file["deployment"] and "seven pipeline stages" in file["deployment"]
    for key in ("layer", "mamba_dt", "attention", "latent", "weights", "stream"):
        assert key in file["assumed"]
    assert set(file["not_on_this_path"]) >= {"lm_head", "mtp", "cache"}
    # and the program runs exactly that
    nemotron_serve.cell_config({"overrides": {}}, file)
    with pytest.raises(SystemExit, match="moe_latent_size"):
        nemotron_serve.cell_config({"overrides": {}}, dict(file, moe_latent_size=512))
    with pytest.raises(SystemExit, match="use_conv_bias"):
        nemotron_serve.cell_config({"overrides": {}}, dict(file, use_conv_bias=False))


# ------------------------------------------------------------ refused by name

def test_what_the_stack_does_not_carry_is_refused_by_name(tiny):
    cfg, _ = tiny
    m = cfg.model
    with pytest.raises(NotImplementedError, match="pattern stack.*serving path only"):
        glm_moe.param_shapes(m)
    for wrong in (dict(hybrid_override_pattern="MEX" + m.hybrid_override_pattern[3:]),
                  dict(mixer="cca"), dict(layer_group_size=6),
                  dict(first_k_dense_replace=1), dict(first_layer_index=80)):
        with pytest.raises(ValueError, match="pattern's layers held"):
            glm_moe.served_param_count(dataclasses.replace(m, **wrong))
    with pytest.raises(ValueError, match="training path"):
        glm_moe.layer_table(get_preset("glm_tiny").model)
    with pytest.raises(KeyError):
        dataclasses.replace(m, mlp_hidden_act="gelu").expert_kind
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        ssd.ssd_chunked(*_ssd_operands()[:6], 24)


@pytest.mark.parametrize("asked, named", [
    (dict(serve_mode="bucketed", cache_size=0), "bucketed serving"),
    (dict(serve_mode="ragged", cache_size=8), "result cache"),
    (dict(serve_mode="ragged", cache_size=0, quant="int8"), "int8"),
    (dict(serve_mode="ragged", cache_size=0, registry="/nowhere"), "heads"),
])
def test_what_is_not_built_for_the_decoder_is_refused_by_name(tiny, served,
                                                              asked, named):
    cfg, _ = tiny
    with pytest.raises(ValueError, match=named):
        Server(served, cfg, **asked)


def test_pbt_serve_picks_the_decoder_by_the_presets_model():
    """`pbt serve --preset nemotron_tiny`: the same loader as Ling's and
    ZAYA1's makes the weights from the seed and pins the only way the
    decoder is served."""
    from proteinbert_tpu.cli.main import _load_serving_model, build_parser

    args = build_parser().parse_args(
        ["serve", "--preset", "nemotron_tiny", "--serve-mode", "bucketed",
         "--cache-size", "64", "--pretrained-set", "train.seed=7"])
    params, cfg = _load_serving_model(args)
    assert (args.serve_mode, args.cache_size) == ("ragged", 0)
    assert (args.max_batch, args.pack_max_segments) == (2, 4)
    want = glm_moe.init_served(jax.random.PRNGKey(7), cfg.model)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(SystemExit, match="checkpoints are not built"):
        _load_serving_model(build_parser().parse_args(
            ["serve", "--preset", "nemotron_tiny", "--pretrained", "/nowhere"]))
    assert build_parser().parse_args(
        ["serve", "--preset", "nemotron3super_ep4"]).preset == "nemotron3super_ep4"


# ----------------------------------------------------------- the TPU's branches

def test_the_tpu_branches_of_the_whole_model_equal_the_reference(tiny, monkeypatch):
    """What a TPU runs and the CPU never picks: `served_embed` with every
    `lax.platform_dependent` on its TPU branch and the Pallas kernels in
    the interpreter: the flash kernel at a group of 2 with heads of the
    published 128, the experts' row movers over slabs of a 128-wide
    latent, the recurrence's kernel over 2 groups of 2 heads of the
    published 64 on the published state of 128 in chunks of 128 (two
    heads share a lane tile; boundaries fall inside chunks); packed rows
    with a pad tail, against the reference on each document ALONE."""
    from jax import lax
    from jax.experimental import pallas as pl

    cfg, c = tiny
    mamba = dict(mamba_num_heads=4, mamba_head_dim=64, ssm_state_size=128,
                 chunk_size=128)
    m = dataclasses.replace(cfg.model, cca_head_dim=128, attention_block=128,
                            moe_latent_size=128, num_hidden_layers=9, **mamba)
    c = dict(c, head_dim=128, moe_latent_size=128, num_hidden_layers=9, **mamba)
    params = glm_moe.init_served(ref.seed_key(SEED), m)
    tokens, seg, docs = _packed([[100, 37, 90], [200, 56]], 256, m.vocab_size)
    called, pallas_call = [], pl.pallas_call

    def interpreted(*args, **kwargs):
        called.append(kwargs["name"])
        return pallas_call(*args, **{**kwargs, "interpret": True})

    monkeypatch.setattr(pl, "pallas_call", interpreted)
    monkeypatch.setattr(lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    got = jax.jit(lambda p: glm_moe.served_embed(p, tokens, seg, 4, m))(params)
    # MEMEMEM*E: the unit (M, E) traced once, then M, *, E
    assert sorted(set(called)) == ["moe_gather_rows", "moe_scatter_add_rows",
                                   "segment_flash_fwd", "ssd_chunks"]
    assert called.count("segment_flash_fwd") == 1
    assert called.count("ssd_chunks") == 2
    assert called.count("moe_gather_rows") == 2
    assert int(got["routing"]["dropped"]) == 0
    want = ref.embed_documents(SEED, [d for _, _, d in docs], c)
    for (r, s, _), w in zip(docs, want):
        for key in ("global", "local_mean"):
            assert _err(got[key][r, s], w[key]) < 1e-5, (key, r, s)
