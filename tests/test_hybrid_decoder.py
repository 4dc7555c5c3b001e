"""The hybrid decoder (Ling-3.0-flash, `bailing_hybrid`) on the serving
path at `ling_tiny` widths, against the plain reference
(`benchmark/reference/bailing_hybrid_f32.py`): the router's group-limited
choice, the share test, the whole model through `Server.submit`, and what
is refused by name."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import lm_serve
from benchmark.reference import bailing_hybrid_f32 as ref
from proteinbert_tpu.configs import get_preset
from proteinbert_tpu.models import glm_moe
from proteinbert_tpu.ops import moe
from proteinbert_tpu.ops.layers import rotary_apply
from proteinbert_tpu.serve.server import Server

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3000000019


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("ling_tiny")
    with open(os.path.join(ROOT, "benchmark/configs/ling-tiny.json")) as f:
        c = lm_serve.reference_sizes(json.load(f), cfg)
    return cfg, c


@pytest.fixture(scope="module")
def served(tiny):
    cfg, _ = tiny
    return glm_moe.init_served(ref.seed_key(SEED), cfg.model)


def test_group_limited_choice_is_the_references(tiny):
    cfg, c = tiny
    m = cfg.model
    rng = np.random.default_rng(0)
    h = rng.normal(size=(96, m.hidden_size)).astype(np.float32)
    router = (0.3 * rng.normal(size=(m.hidden_size, m.n_routed_experts))).astype(np.float32)
    bias = (0.05 * rng.normal(size=(m.n_routed_experts,))).astype(np.float32)
    ids, w = moe.route(h, router, bias, m.num_experts_per_tok,
                       m.routed_scaling_factor, m.norm_topk_prob, m.n_group,
                       m.topk_group)
    want_ids, want_w = ref.route(jnp.asarray(h), router, bias, c)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want_ids, -1))
    np.testing.assert_allclose(np.sort(w, -1), np.sort(want_w, -1), rtol=1e-5)
    # the choice really is limited: a token's experts lie in topk_group groups
    groups = np.asarray(ids) // (m.n_routed_experts // m.n_group)
    assert max(len(set(row)) for row in groups) <= m.topk_group
    free, _ = moe.route(h, router, bias, m.num_experts_per_tok,
                        m.routed_scaling_factor, m.norm_topk_prob)
    assert (np.sort(free, -1) != np.sort(ids, -1)).any()


def test_the_four_shares_add_up_to_the_uncut_layer(tiny):
    """Each share's routed part, plus the shared expert ONCE, is what the
    uncut reference layer gives: the cut of the configuration (this chip
    holds a quarter of the experts, routes over all) loses nothing but
    the absent experts' part."""
    cfg, c = tiny
    m = cfg.model
    rng = np.random.default_rng(1)
    D, F, R = m.hidden_size, m.moe_intermediate_size, m.n_routed_experts
    w = lambda *s: (0.2 * rng.normal(size=s)).astype(np.float32)  # noqa: E731
    full = {"router": w(D, R), "router_bias": np.zeros(R, np.float32),
            "experts": {"gate": w(R, D, F), "up": w(R, D, F), "down": w(R, F, D)}}
    shared = {"gate": w(D, F), "up": w(D, F), "down": w(F, D)}
    h = rng.normal(size=(80, D)).astype(np.float32)
    real = np.ones(80, bool)
    uncut = dict(c, experts_held=R, expert_offset=0)
    routed, _ = ref.routed_experts(full, jnp.asarray(h), jnp.asarray(real), uncut, "f32")
    want = np.asarray(routed + ref._ffn(shared, jnp.asarray(h), "f32"))

    total = np.asarray(ref._ffn(shared, jnp.asarray(h), "f32"))
    held = R // 4
    for share in range(4):
        cut = dataclasses.replace(m, experts_held=held, expert_offset=share * held)
        part = {"router": full["router"], "experts": {
            k: jnp.asarray(v[share * held:(share + 1) * held])
            for k, v in full["experts"].items()}}
        y, stats = moe.moe_apply(part, full["router_bias"], jnp.asarray(h),
                                 jnp.asarray(real), cut)
        assert int(stats["dropped"]) == 0
        total = total + np.asarray(y)
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_interleaved_rotary_is_the_references():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(2, 24, 3, 8)).astype(np.float32)
    pos = np.tile(np.arange(24), (2, 1))
    got = rotary_apply(jnp.asarray(x), jnp.asarray(pos), 6e6, interleave=True)
    want = ref.rotary_interleaved(jnp.asarray(x[0]), jnp.arange(24), 6e6)
    np.testing.assert_allclose(got[0], want, atol=1e-6)
    assert np.abs(np.asarray(got) - np.asarray(
        rotary_apply(jnp.asarray(x), jnp.asarray(pos), 6e6))).max() > 1e-2


def test_the_whole_model_through_submit_equals_the_reference(tiny, served):
    """Documents of token ids through `Server.submit("embed", ids)`: the
    queue, the online packer, the span ladder, the row classes and the
    packed executable; each answer against the reference on that
    document ALONE, weights from the same seed by the same recipe."""
    cfg, c = tiny
    rng = np.random.default_rng(3)
    docs = [rng.integers(0, cfg.model.vocab_size, n)
            for n in (20, 30, 7, 41, 15, 64, 3, 33, 8)]
    with Server(served, cfg, serve_mode="ragged", max_batch=2,
                pack_max_segments=4, cache_size=0) as server:
        got = [f.result(timeout=300)
               for f in [server.submit("embed", d) for d in docs]]
        stats = server.stats()
    assert stats["routing"]["dropped_assignments"] == 0
    assert stats["routing"]["real_tokens"] == sum(len(d) for d in docs)
    # every expert is held at these widths: every assignment falls here
    assert stats["routing"]["assignments_held"] == (
        sum(len(d) for d in docs) * cfg.model.num_experts_per_tok
        * cfg.model.num_moe_layers)
    assert set(stats["batch_class_counts"]) <= {1, 2}
    want = ref.embed_documents(SEED, docs, c)
    for g, w in zip(got, want):
        assert g["global"].dtype == np.float32 and g["global"].shape == (64,)
        for key in ("global", "local_mean"):
            err = np.linalg.norm(g[key] - w[key]) / np.linalg.norm(w[key])
            assert err < 1e-5, (key, err)


def test_the_tpu_branches_of_the_whole_model_equal_the_reference(tiny, monkeypatch):
    """What a TPU runs and the CPU never picks: `served_embed` with every
    `lax.platform_dependent` taking its TPU branch (the KDA core's two
    kernels in all three KDA scans, the flash kernel behind the padding
    of a 192-wide head to its lane tile) and every `pallas_call` in the
    interpreter, on packed rows with a pad tail, against the reference on
    each document ALONE. Heads of the published sizes (128; 128 + 64 /
    128), which the kernels' tiles take; everything else `ling_tiny`'s."""
    from jax import lax
    from jax.experimental import pallas as pl

    cfg, c = tiny
    sizes = dict(num_attention_heads=2, kda_head_dim=128, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=32)
    m = dataclasses.replace(cfg.model, kda_chunk=64, attention_block=128, **sizes)
    c = dict(c, **sizes)
    params = glm_moe.init_served(ref.seed_key(SEED), m)
    rng = np.random.default_rng(1)
    tokens, seg, docs = np.zeros((2, 256), np.int32), np.zeros((2, 256), np.int32), []
    for r, row in enumerate([[100, 37, 90], [200, 56]]):
        at = 0
        for s, n in enumerate(row, 1):
            docs.append((r, s - 1, rng.integers(0, m.vocab_size, n).astype(np.int32)))
            tokens[r, at:at + n], seg[r, at:at + n] = docs[-1][2], s
            at += n
    called, pallas_call = [], pl.pallas_call

    def interpreted(*args, **kwargs):
        called.append(kwargs["name"])
        return pallas_call(*args, **{**kwargs, "interpret": True})

    monkeypatch.setattr(pl, "pallas_call", interpreted)
    monkeypatch.setattr(lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    got = jax.jit(lambda p: glm_moe.served_embed(p, tokens, seg, 4, m))(params)
    assert sorted(called) == (["kda_pairs"] * 3 + ["kda_walk"] * 3
                              + ["segment_flash_fwd"])
    assert int(got["routing"]["dropped"]) == 0
    want = ref.embed_documents(SEED, [d for _, _, d in docs], c)
    for (r, s, _), w in zip(docs, want):
        for key in ("global", "local_mean"):
            err = np.linalg.norm(got[key][r, s] - w[key]) / np.linalg.norm(w[key])
            assert err < 1e-5, (key, r, s, err)


def test_the_served_tree_is_made_in_the_parameter_dtype(tiny):
    cfg, _ = tiny
    m = dataclasses.replace(cfg.model, param_dtype="bfloat16")
    params = glm_moe.init_served(ref.seed_key(5), m)
    assert {leaf.dtype for leaf in jax.tree.leaves(params)} == {jnp.dtype("bfloat16")}
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == (
        glm_moe.served_param_count(m) + m.num_moe_layers * m.n_routed_experts)
    # the values are the float32 tree's, which were rounded as they were made
    f32 = glm_moe.init_served(ref.seed_key(5), cfg.model)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(f32)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b))
    abstract = glm_moe.served_abstract(m)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), abstract) == jax.tree.map(
        lambda a: (a.shape, a.dtype), params)


def test_the_period_is_carried_by_the_stack(tiny):
    cfg, _ = tiny
    m = cfg.model
    assert glm_moe.hybrid_schedule(m) == (1, 3, 2)       # layers 2-4, 5, 6-7
    whole = dataclasses.replace(m, first_layer_index=0, first_k_dense_replace=0,
                                num_hidden_layers=12)
    assert glm_moe.hybrid_schedule(whole) == (2, 5, 0)   # five KDA, then latent
    with pytest.raises(ValueError, match="whole periods"):
        glm_moe.hybrid_schedule(dataclasses.replace(m, num_hidden_layers=6))
    with pytest.raises(ValueError, match="latent mixer"):
        glm_moe.hybrid_schedule(dataclasses.replace(
            m, first_layer_index=5, num_hidden_layers=7))
    with pytest.raises(NotImplementedError, match="serving path only"):
        glm_moe.param_shapes(m)


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


@pytest.mark.parametrize("bad, named", [
    ([600], "outside the 512 rows"), ([-1, 3], "outside the 512 rows"),
    ("ACDE", "token ids"), ([[1, 2]], "token ids"), ([1.5, 2.0], "token ids"),
])
def test_a_request_that_is_no_document_of_held_ids_is_its_own_error(tiny, served,
                                                                   bad, named):
    cfg, _ = tiny
    server = Server(served, cfg, serve_mode="ragged", max_batch=2,
                    pack_max_segments=4, cache_size=0)
    try:
        with pytest.raises(ValueError, match=named):
            server.submit("embed", bad)
        with pytest.raises(ValueError, match="not built for the decoder"):
            server.submit("predict_go", [1, 2, 3])
    finally:
        server.abort()


def test_a_document_over_http_is_a_list_of_ids(tiny, served):
    """POST /v1/embed {"seq": [ids]} rides the same handler: the answer
    is the in-process one, a string is the request's error (400)."""
    import threading
    import urllib.error
    import urllib.request

    from proteinbert_tpu.serve.http import make_http_server

    cfg, _ = tiny
    srv = Server(served, cfg, serve_mode="ragged", max_batch=2,
                 pack_max_segments=4, cache_size=0)
    srv.start()
    httpd = make_http_server(srv, port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}/v1/embed"

    def post(payload):
        req = urllib.request.Request(url, data=json.dumps(payload).encode(),
                                     headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=120) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    try:
        ids = [5, 17, 300, 4, 4, 211, 9]
        status, body = post({"seq": ids})
        assert status == 200
        local = srv.submit("embed", ids).result(timeout=120)
        np.testing.assert_allclose(body["global"], local["global"], rtol=1e-6,
                                   atol=1e-7)
        for bad in ("ACDE", [1, 600], []):
            status, body = post({"seq": bad})
            assert status == 400 and body["type"] == "bad_request", (bad, body)
    finally:
        httpd.shutdown()
        httpd.server_close()
        srv.close(drain=True, timeout=60)


def test_pbt_serve_picks_the_decoder_by_the_presets_model():
    """`pbt serve --preset ling_tiny`: the loader makes the weights from
    the seed, pins the only way the decoder is served, and refuses a
    checkpoint and a fleet replica by name; ProteinBERT's presets still
    ask for --pretrained."""
    from proteinbert_tpu.cli.main import _load_serving_model, build_parser

    args = build_parser().parse_args(
        ["serve", "--preset", "ling_tiny", "--serve-mode", "bucketed",
         "--cache-size", "64", "--pretrained-set", "train.seed=7"])
    params, cfg = _load_serving_model(args)
    assert (args.serve_mode, args.cache_size) == ("ragged", 0)
    assert (args.max_batch, args.pack_max_segments) == (2, 4)
    want = glm_moe.init_served(jax.random.PRNGKey(7), cfg.model)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(SystemExit, match="checkpoints are not built"):
        _load_serving_model(build_parser().parse_args(
            ["serve", "--preset", "ling_tiny", "--pretrained", "/nowhere"]))
    with pytest.raises(SystemExit, match="--pretrained is required"):
        _load_serving_model(build_parser().parse_args(
            ["serve", "--preset", "tiny"]))
