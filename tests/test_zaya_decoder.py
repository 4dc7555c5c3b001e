"""ZAYA1 (`zaya`: compressed convolutional attention, a top-1 MLP router
that carries its state from layer to layer, residual scaling) on the
serving path at `zaya_tiny` widths, against the plain reference
(`benchmark/reference/zaya_f32.py`): the whole model through
`Server.submit`, document isolation of the value shift and both
convolutions, the grouped-key flash kernel in the interpreter, the
carried router state, top 1 under an uneven load, and what is refused by
name. No expert and no vocabulary row of this model is cut, so the
guide's shares-add-up test (tests/test_hybrid_decoder.py has Ling's) has
nothing to add up here."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import cca_serve
from benchmark.reference import zaya_f32 as ref
from proteinbert_tpu import inference
from proteinbert_tpu.configs import get_preset
from proteinbert_tpu.kernels.segment_flash import CCA_CORE_PATH_TOTAL
from proteinbert_tpu.models import glm_moe
from proteinbert_tpu.ops import cca, kda, moe
from proteinbert_tpu.ops.attention import (
    causal_segment_attention, flash_segment_attention,
)
from proteinbert_tpu.ops.layers import rotary_apply
from proteinbert_tpu.serve.server import Server

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3000000019
TOP = 2 ** 20
INERT = ("bias", "_scale", "tau", "carry", "b1", "b2")


def _file(name):
    with open(os.path.join(ROOT, "benchmark/configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("zaya_tiny")
    return cfg, cca_serve.reference_sizes(_file("zaya-tiny"), cfg)


@pytest.fixture(scope="module")
def served(tiny):
    cfg, _ = tiny
    return glm_moe.init_served(ref.seed_key(SEED), cfg.model)


def _err(got, want):
    return float(np.linalg.norm(np.asarray(got) - want) / np.linalg.norm(want))


def _packed(docs_by_row, width, vocab, step=8, seed=1):
    """Rows of documents in spans of the ladder (a span's tail holds no
    token) -> tokens, segment ids, [(row, slot, ids)]."""
    rng = np.random.default_rng(seed)
    rows = len(docs_by_row)
    tokens = np.full((rows, width), -1, np.int32)
    seg, docs = np.zeros((rows, width), np.int32), []
    for r, row in enumerate(docs_by_row):
        at = 0
        for s, n in enumerate(row, 1):
            d = rng.integers(0, vocab, n).astype(np.int32)
            docs.append((r, s - 1, d))
            span = -(-n // step) * step
            tokens[r, at:at + n], seg[r, at:at + span] = d, s
            at += span
    return tokens, seg, docs


def _wake(index, tree):
    """Every leaf the recipe leaves at 0 or 1 (biases, residual scales,
    tau, the router's carry) moved, the same way in both trees: a
    mechanism that is never applied would else go unnoticed."""
    if index == TOP:
        return tree
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = []
    for j, (path, leaf) in enumerate(flat):
        name = str(path[-1].key)
        if name.endswith(INERT) and name != "router_bias":
            rng = np.random.default_rng([index, j])
            leaf = leaf + (0.3 * rng.normal(size=leaf.shape)).astype(np.float32)
        leaves.append(leaf)
    return jax.tree.unflatten(treedef, leaves)


def _woken(params, cfg):
    """`_wake` over the program's stacked tree, layer by layer."""
    stack = params["cca"]
    layers = [_wake(cfg.first_layer_index + l, jax.tree.map(lambda a: a[l], stack))
              for l in range(cfg.num_hidden_layers)]
    return dict(params, cca=jax.tree.map(lambda *a: jnp.stack(a), *layers))


def test_the_whole_model_through_submit_equals_the_reference(tiny, served):
    """Documents of token ids through `Server.submit("embed", ids)`: the
    queue, the online packer, the span ladder, the row classes and the
    packed executable; each answer against the reference on that
    document ALONE, weights from the same seed by the same recipe."""
    cfg, c = tiny
    rng = np.random.default_rng(3)
    docs = [rng.integers(0, cfg.model.vocab_size, n)
            for n in (20, 30, 7, 41, 15, 64, 3, 33, 8, 1)]
    # A file that ran earlier on this worker may have served these widths
    # (tests/benchmark/test_zaya_cell.py's rehearsals): its traces would
    # be this server's, and the counter below would not move.
    inference._packed_decoder_embed_batch.clear_cache()
    cores_before = {f"{p}/{r}": n for (p, r), n in CCA_CORE_PATH_TOTAL.items()}
    with Server(served, cfg, serve_mode="ragged", max_batch=2,
                pack_max_segments=4, cache_size=0) as server:
        got = [f.result(timeout=300)
               for f in [server.submit("embed", d) for d in docs]]
        stats = server.stats()
    tokens = sum(len(d) for d in docs)
    assert stats["routing"]["dropped_assignments"] == 0
    assert stats["routing"]["real_tokens"] == tokens
    # top 1 and every expert held: one assignment a token and layer
    assert stats["routing"]["assignments_held"] == tokens * cfg.model.num_hidden_layers
    assert set(stats["batch_class_counts"]) <= {1, 2}
    # the counter is the process's: what THIS server's two classes traced
    traced = {k: n - cores_before.get(k, 0)
              for k, n in stats["cca_core_path"].items()}
    assert {k: n for k, n in traced.items() if n} == {
        "reference/tiles_do_not_fit": 2}
    want = ref.embed_documents(SEED, docs, c)
    for g, w in zip(got, want):
        assert g["global"].dtype == np.float32 and g["global"].shape == (64,)
        for key in ("global", "local_mean"):
            assert _err(g[key], w[key]) < 1e-5, key


def test_every_learned_vector_is_applied(tiny, served):
    """The recipe starts the biases, tau, the carry and the residual
    scales at 0 or 1, where leaving one out changes nothing: with all of
    them moved, in the program's tree and in the reference's alike, a
    packed batch still equals the reference, and moving them mattered."""
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


def _alone_and_packed(params, m, rows):
    tokens, seg, docs = _packed(rows, 64, m.vocab_size, seed=4)
    run = jax.jit(lambda p, t, s: glm_moe.served_embed(p, t, s, 4, m))
    packed = run(params, tokens, seg)
    worst = 0.0
    for r, s, d in docs:
        t1, s1, _ = _packed([[len(d)]], 64, m.vocab_size)
        t1[0, :len(d)] = d
        alone = run(params, np.repeat(t1, 2, 0), np.repeat(s1, 2, 0))
        for key in ("global", "local_mean"):
            worst = max(worst, _err(packed[key][r, s], np.asarray(alone[key][0, 0])))
    return worst


def test_a_document_packed_after_others_answers_as_it_does_alone(tiny, served):
    cfg, _ = tiny
    assert _alone_and_packed(_woken(served, cfg.model), cfg.model,
                             [[9, 16, 21, 8], [33, 24]]) < 1e-5


@pytest.mark.parametrize("reads_across", ["value_shift", "conv0", "conv1"])
def test_the_isolation_test_fails_if_anything_reads_across_a_boundary(
        tiny, served, reads_across, monkeypatch):
    """The value shift and both convolutions read backwards along the
    packed row; each made blind to the documents' bounds in turn, the
    test above has to fail (it holds a document's answer to 1e-5)."""
    cfg, _ = tiny
    blind = lambda ids: jnp.zeros_like(ids)  # noqa: E731
    if reads_across == "conv0":
        real = kda.segment_conv
        monkeypatch.setattr(cca, "segment_conv",
                            lambda x, k, ids: real(x, k, blind(ids)))
    else:       # the shift moves (B, L, C); the grouped convolution (B, L, G, d)
        real, hit = cca.segment_back, 3 if reads_across == "value_shift" else 4
        monkeypatch.setattr(
            cca, "segment_back", lambda x, ids, j=1: real(
                x, blind(ids) if x.ndim == hit else ids, j))
    assert _alone_and_packed(_woken(served, cfg.model), cfg.model,
                             [[9, 16, 21, 8], [33, 24]]) > 1e-3


def test_the_grouped_key_kernel_in_the_interpreter_equals_plain_attention(monkeypatch):
    """`kernels/segment_flash.py`'s forward kernel with 8 query heads on
    2 key heads, the key tile chosen by the block index map, against
    `causal_segment_attention` on keys and values REPEATED to 8 heads:
    packed rows, a pad tail, several tiles a row."""
    from jax.experimental import pallas as pl

    rng = np.random.default_rng(0)
    B, L, H, G, d = 2, 512, 8, 2, 128
    q = rng.normal(size=(B, L, H, d)).astype(np.float32)
    k = rng.normal(size=(B, L, G, d)).astype(np.float32)
    v = rng.normal(size=(B, L, G, d)).astype(np.float32)
    seg = np.zeros((B, L), np.int32)
    seg[0, :200], seg[0, 200:470] = 1, 2
    seg[1, :130], seg[1, 130:140], seg[1, 140:512] = 1, 2, 3
    want = causal_segment_attention(
        q, np.repeat(k, H // G, 2), np.repeat(v, H // G, 2), seg, d ** -0.5, 128)
    np.testing.assert_allclose(        # the plain path groups by itself
        causal_segment_attention(q, k, v, seg, d ** -0.5, 128), want, atol=1e-6)
    seen, pallas_call = [], pl.pallas_call

    def interpreted(*args, **kwargs):
        seen.append(kwargs["name"])
        return pallas_call(*args, **{**kwargs, "interpret": True})

    monkeypatch.setattr(pl, "pallas_call", interpreted)
    got = flash_segment_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                  jnp.asarray(seg), d ** -0.5, 128)
    assert seen == ["segment_flash_fwd"]
    real = seg > 0
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real],
                               atol=2e-5)
    # heads of one group do differ, and a group reads ITS key head
    assert np.abs(np.asarray(got)[:, :, 0] - np.asarray(got)[:, :, 1]).max() > 0.1
    swapped = flash_segment_attention(
        jnp.asarray(q), jnp.asarray(k[:, :, ::-1]), jnp.asarray(v[:, :, ::-1]),
        jnp.asarray(seg), d ** -0.5, 128)
    first_on_second = causal_segment_attention(
        q[:, :, :4], k[:, :, 1:], v[:, :, 1:], seg, d ** -0.5, 128)
    np.testing.assert_allclose(np.asarray(swapped)[:, :, :4][real],
                               np.asarray(first_on_second)[real], atol=2e-5)
    with pytest.raises(NotImplementedError, match="grouped keys.*no backward"):
        jax.grad(lambda q: flash_segment_attention(
            q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(seg), d ** -0.5, 128
        ).sum())(jnp.asarray(q))
    with pytest.raises(ValueError, match="do not divide"):
        flash_segment_attention(jnp.asarray(q), jnp.asarray(k[:, :, :1].repeat(3, 2)),
                                jnp.asarray(v[:, :, :1].repeat(3, 2)),
                                jnp.asarray(seg), d ** -0.5, 128)


def test_the_tpu_branches_of_the_whole_model_equal_the_reference(tiny, monkeypatch):
    """What a TPU runs and the CPU never picks: `served_embed` with every
    `lax.platform_dependent` on its TPU branch and the flash kernel in
    the interpreter, heads of the published 128 (8 on 2), packed rows
    with a pad tail, against the reference on each document ALONE."""
    from jax import lax
    from jax.experimental import pallas as pl

    cfg, c = tiny
    m = dataclasses.replace(cfg.model, cca_head_dim=128, attention_block=128,
                            num_hidden_layers=2)
    c = dict(c, head_dim=128, num_hidden_layers=2)
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
    assert called == ["segment_flash_fwd"]      # one scan, one traced layer
    assert int(got["routing"]["dropped"]) == 0
    want = ref.embed_documents(SEED, [d for _, _, d in docs], c)
    for (r, s, _), w in zip(docs, want):
        for key in ("global", "local_mean"):
            assert _err(got[key][r, s], w[key]) < 1e-5, (key, r, s)


def test_the_routers_state_is_really_carried(tiny, served):
    """The router of layer l adds `carry_l` times layer l - 1's state:
    the program equals the reference (first test), and the reference
    with the carry cut (every layer starting from zero) is another
    model: the same test would fail on a scan that dropped the state."""
    cfg, c = tiny
    m = cfg.model
    tokens, seg, docs = _packed([[40, 20], [60]], 64, m.vocab_size)
    got = jax.jit(lambda p: glm_moe.served_embed(p, tokens, seg, 4, m))(served)

    def cut(index, tree):
        if index != TOP:
            tree["moe"]["router"]["carry"] = jnp.zeros_like(
                tree["moe"]["router"]["carry"])
        return tree

    whole = ref.embed_documents(SEED, [d for _, _, d in docs], c)
    without = ref.embed_documents(SEED, [d for _, _, d in docs], c, edit=cut)
    for (r, s, _), w, z in zip(docs, whole, without):
        assert _err(got["global"][r, s], w["global"]) < 1e-5
        assert _err(got["global"][r, s], z["global"]) > 1e-3
    # and layer by layer: r_l = x W + b + carry * r_(l-1)
    rng = np.random.default_rng(5)
    p = jax.tree.map(lambda a: np.asarray(a[1], np.float32),
                     served["cca"]["moe"]["router"])
    x = rng.normal(size=(12, m.hidden_size)).astype(np.float32)
    state = rng.normal(size=(12, m.router_hidden_size)).astype(np.float32)
    bias = np.zeros(m.n_routed_experts, np.float32)
    ids, w, r = moe.route_mlp(x, p, state, bias, 1, m.rms_norm_eps)
    want_ids, want_w, want_r = ref.route(jnp.asarray(x), p, jnp.asarray(state), bias, c)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(w, want_w, rtol=1e-5)
    np.testing.assert_allclose(r, want_r, atol=1e-5)
    _, _, r0 = moe.route_mlp(x, p, 0 * state, bias, 1, m.rms_norm_eps)
    np.testing.assert_allclose(np.asarray(r) - np.asarray(r0), p["carry"] * state,
                               atol=1e-5)
    # the weight is the chosen probability itself; the bias moves the choice only
    assert (np.asarray(w) <= 1).all() and (np.asarray(w) > 1 / 16).all()
    tilted = bias.copy()
    tilted[3] = 10.0
    ids3, w3, _ = moe.route_mlp(x, p, state, tilted, 1, m.rms_norm_eps)
    assert (np.asarray(ids3) == 3).all() and (np.asarray(w3) < 1).all()


def test_the_balance_bias_is_the_recipes_and_evens_the_load(tiny, served):
    """b_e = 1 / E - the mean of p_e over seeded probe states: the
    program's leaf is the reference's, and over other states the fullest
    expert is less full with it than without."""
    cfg, c = tiny
    m = cfg.model
    rng = np.random.default_rng(8)
    states = rng.normal(size=(2048, m.router_hidden_size)).astype(np.float32)
    fullest = {True: [], False: []}
    for l in range(m.num_hidden_layers):
        want = ref.make_tree(ref.seed_key(SEED), l, ref.layer_shapes(c), c)["moe"]
        got = np.asarray(served["cca"]["moe"]["router_bias"][l])
        np.testing.assert_array_equal(got, want["router_bias"])
        assert np.abs(got).max() > 1e-3 and abs(got.sum()) < 1e-2
        probs = np.asarray(moe.router_probs(want["router"], states, m.rms_norm_eps))
        for with_bias in (True, False):
            load = np.bincount((probs + got * with_bias).argmax(-1),
                               minlength=m.n_routed_experts)
            fullest[with_bias].append(load.max() / load.mean())
    assert np.mean(fullest[True]) < 0.7 * np.mean(fullest[False]), fullest
    assert max(fullest[True]) < 0.5 * max(fullest[False]), fullest


def test_top_one_under_a_load_on_one_expert_drops_nothing(tiny):
    """A balance bias that sends most tokens to ONE expert (the most
    uneven load the experts' loop can see: top 1 of few): every
    assignment is taken, and the layer's result is the reference's."""
    cfg, c = tiny
    m = cfg.model
    rng = np.random.default_rng(6)
    D, F, E, R = (m.hidden_size, m.moe_intermediate_size, m.n_routed_experts,
                  m.router_hidden_size)
    w = lambda *s: (0.2 * rng.normal(size=s)).astype(np.float32)  # noqa: E731
    router = {"proj": w(D, R), "proj_bias": w(R), "carry": 1 + w(R),
              "norm": np.ones(R, np.float32), "w1": w(R, R), "b1": w(R),
              "w2": w(R, R), "b2": w(R), "w3": 3 * w(R, E)}
    bias = np.zeros(E, np.float32)
    bias[5] = 0.4           # most tokens, not all: the rest stay spread
    p = jax.tree.map(jnp.asarray, {
        "router": router, "router_bias": bias,
        "experts": {"gate": w(E, D, F), "up": w(E, D, F), "down": w(E, F, D)}})
    T = 200
    h = rng.normal(size=(T, D)).astype(np.float32)
    state = rng.normal(size=(T, R)).astype(np.float32)
    real = np.ones(T, bool)
    real[-7:] = False
    y, stats = moe.moe_apply(p, bias, jnp.asarray(h), jnp.asarray(real), m,
                             router_state=jnp.asarray(state))
    counts = np.asarray(stats["held_counts"])
    assert int(stats["dropped"]) == 0 and counts.sum() == T - 7
    assert counts[5] > 0.5 * (T - 7) and (counts > 0).sum() >= 4
    want, want_r, want_ids = ref.routed_experts(
        p, jnp.asarray(h), jnp.asarray(state), jnp.asarray(real), c, "f32")
    np.testing.assert_allclose(y, want, atol=2e-5)
    np.testing.assert_allclose(stats["router_state"], want_r, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(stats["ids"])[real, 0],
                                  np.asarray(want_ids)[real, 0])
    assert (np.asarray(y)[~real] == 0).all()      # a pad is routed nowhere


def test_rotary_over_half_a_head_is_the_references():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(2, 24, 3, 16)).astype(np.float32)
    pos = np.tile(np.arange(24), (2, 1))
    got = rotary_apply(jnp.asarray(x), jnp.asarray(pos), 5e6, rotary_dim=8)
    want = ref.rotary_partial(jnp.asarray(x[0]), jnp.arange(24), 5e6, 8)
    np.testing.assert_allclose(got[0], want, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got)[..., 8:], x[..., 8:])
    whole = rotary_apply(jnp.asarray(x), jnp.asarray(pos), 5e6)
    assert np.abs(np.asarray(got) - np.asarray(whole)).max() > 1e-2
    np.testing.assert_array_equal(
        rotary_apply(jnp.asarray(x), jnp.asarray(pos), 5e6, rotary_dim=16), whole)


def test_the_trees_count_is_the_files(tiny):
    from benchmark import cca_flops

    cfg, c = tiny
    assert (glm_moe.served_param_count(cfg.model) == ref.param_count(c)
            == cca_flops.param_count(c) == _file("zaya-tiny")["parameters"])
    big, file = get_preset("zaya1_8b_pp2"), _file("zaya1-8b-pp2")
    sizes = cca_serve.reference_sizes(file, big)
    assert (glm_moe.served_param_count(big.model) == ref.param_count(sizes)
            == cca_flops.param_count(sizes) == file["parameters"] == 5_519_138_864)
    m = dataclasses.replace(cfg.model, param_dtype="bfloat16")
    params = glm_moe.init_served(ref.seed_key(5), m)
    assert {leaf.dtype for leaf in jax.tree.leaves(params)} == {jnp.dtype("bfloat16")}
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == (
        glm_moe.served_param_count(m) + m.num_hidden_layers * m.n_routed_experts)
    abstract = glm_moe.served_abstract(m)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), abstract) == jax.tree.map(
        lambda a: (a.shape, a.dtype), params)


def test_what_the_stack_does_not_carry_is_refused_by_name(tiny):
    cfg, _ = tiny
    m = cfg.model
    with pytest.raises(NotImplementedError, match="CCA mixer.*serving path only"):
        glm_moe.param_shapes(m)
    for wrong in (dict(n_shared_experts=1), dict(first_k_dense_replace=1),
                  dict(layer_group_size=6)):
        with pytest.raises(ValueError, match="ZAYA1's layer alone"):
            glm_moe.served_param_count(dataclasses.replace(m, **wrong))
    # the router's kind and the residual scaling come with the mixer: no
    # field asks for one of them on another stack, or without them on this
    for derived in ("router", "residual_scaling"):
        with pytest.raises(TypeError, match=derived):
            dataclasses.replace(m, **{derived: getattr(m, derived)})
    ling = get_preset("ling_tiny").model
    assert (m.router, m.residual_scaling) == ("mlp", True)
    assert (ling.router, ling.residual_scaling) == ("sigmoid", False)


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
    """`pbt serve --preset zaya_tiny`: the same loader as Ling's makes the
    weights from the seed and pins the only way the decoder is served."""
    from proteinbert_tpu.cli.main import _load_serving_model, build_parser

    args = build_parser().parse_args(
        ["serve", "--preset", "zaya_tiny", "--serve-mode", "bucketed",
         "--cache-size", "64", "--pretrained-set", "train.seed=7"])
    params, cfg = _load_serving_model(args)
    assert (args.serve_mode, args.cache_size) == ("ragged", 0)
    assert (args.max_batch, args.pack_max_segments) == (2, 4)
    want = glm_moe.init_served(jax.random.PRNGKey(7), cfg.model)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(SystemExit, match="checkpoints are not built"):
        _load_serving_model(build_parser().parse_args(
            ["serve", "--preset", "zaya_tiny", "--pretrained", "/nowhere"]))
    assert build_parser().parse_args(
        ["serve", "--preset", "zaya1_8b_pp2"]).preset == "zaya1_8b_pp2"
