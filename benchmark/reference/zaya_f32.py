"""ZAYA1-8B (`zaya`) on the serving path in plain `jax.numpy`, float32,
products at "highest": the first of two pipeline stages, one document at
a time.

The plain reference of the `zaya1-8b-pp2` configuration: weights from a
seed and the forward pass that `embed` needs (the tied output head is not
on this path), written from the published config.json
(`benchmark/configs/zaya1-8b-pp2.json`, key `published`), Compressed
Convolutional Attention (Zyphra, arXiv:2510.04476), the ZAYA1 technical
report (arXiv:2511.17127) and the layer equations of ISSUE 35 / PERF.md
section 4. It imports nothing of `proteinbert_tpu`. No kernels, no
packing: the convolutions are shifted adds, the value shift a shifted
array, attention sees all keys a block of queries at a time with the two
key heads repeated to the eight query heads, and every one of the 16
experts runs over every token, masked by the choice.

As `bailing_hybrid_f32.py`: every document of a sample is padded to ONE
length (nothing real reads the padding: everything here is causal), the
mixer runs over a whole padded document, the expert sublayer (which sees
one token at a time, and its router's state with it) over the blocks of
`BLOCK` tokens that hold a real one, and a weight is drawn by one
function a shape. A layer's weights are made, used for every document and
dropped before the next layer's: the stage is 22 GB in float32 and never
whole.

Equations (c: the configuration as a dict, published key names; H query
heads, G key heads, d = head_dim, N = RMSNorm with eps c.rms_norm_eps):
  layer l        x <- (a1 x + c1) + (a1' CCA(N1(x)) + c1')
                 x <- (a2 x + c2) + (a2' MoE(N2(x)) + c2')   four learned
                 D-vectors a sublayer (`res1`, `res2`)
  CCA            q~ = u W_q (H heads), k~ = u W_k (G heads)
                 v = [u_t W_v1 ; u_(t-1) W_v2]: the first half of the
                 value heads of the current token, the second half of the
                 PREVIOUS one (zero at the first token)
                 z = conv1(conv0([q~ ; k~])): conv0 depthwise, cca_time0
                 taps, with bias; conv1 grouped, one group a head, d -> d,
                 cca_time1 taps, with bias; both causal (tap j weighs the
                 token j positions back; nothing before the first token)
                 m_q[h] = (q~[h] + k~[h // (H / G)]) / 2
                 m_k[g] = mean of m_q over the query heads of group g
                 q = z_q + m_q; k = z_k + m_k
                 q = sqrt(d) q / sqrt(|q|^2 + 1e-6)
                 k = exp(tau_g) sqrt(d) k / sqrt(|k|^2 + 1e-6)
                 rotary over the first partial_rotary_factor d of a head,
                 half-split pairs (j with j + rd / 2), angle
                 pos * theta^(-2j / rd)
                 o[h] = softmax(q[h] . k[h // (H / G)] / sqrt(d), causal)
                        v[h // (H / G)];  y = concat_h(o) W_o
  MoE            r_l = u W_proj + b_proj + carry_l * r_(l-1); r before the
                 first layer is 0; r_l is what layer l + 1 reads
                 s = W_3 gelu(W_2 gelu(W_1 N(r_l) + b_1) + b_2), gelu exact
                 p = softmax(s); chosen = top-k of p + b (k = 1); w = p[chosen]
                 y = sum over chosen e of w * down_e(silu(gate_e u) * up_e u)
                 (the router float32 whatever the precision)
  embed          {"global": N_final(x) at the last token,
                  "local_mean": the mean of N_final(x) over the tokens}

Weights: the layer with the published index i draws leaf number j of its
own tree (keys sorted) as std * normal(fold_in(fold_in(key, i), j)),
ROUNDED TO BFLOAT16 and held here as float32; std is `init_std`, but
`embed_init_std` for the embedding's rows, `out_init_std` for the
products that write into the residual stream (`o`, `down`), and
fan_in^-1/2 for the two convolutions (cca_time0; cca_time1 d) and the
router's three MLP layers (router_hidden_size). Norm scales, `carry` and
the four residual scales are 1; every bias (the convolutions', the
router's, the residual ones) and `tau` are 0; the balance bias is
b_e = 1 / E - mean p_e of the layer's own router MLP over 4,096 probe
states normal(fold_in(fold_in(key, i), 2**16)), rounded to bfloat16: what
the rule that no gradient reaches would leave, every expert's p + b of
one mean. The
embedding and the final norm are the tree of the index 2**20. `edit`
(tests only) is handed every tree as it is made, (index, tree) -> tree.

`precision`: "f32" is the reference; "int8" the control one step below
bfloat16 products (every product with a weight matrix, the grouped
convolution's too, takes int8 weights, one scale per output channel, and
int8 activations, one scale per row; the router stays float32, as in the
program).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
BLOCK = 256      # queries a step of attention; tokens a call of the experts
TOP_INDEX = 2 ** 20


# ------------------------------------------------------------------ weights

def layer_shapes(c: dict) -> dict:
    D, H, G, d = (c["hidden_size"], c["num_attention_heads"],
                  c["num_key_value_heads"], c["head_dim"])
    C, R = (H + G) * d, c["router_hidden_size"]
    E, F = c["num_experts"], c["moe_intermediate_size"]
    vectors = {"res_scale": (D,), "res_bias": (D,), "out_scale": (D,),
               "out_bias": (D,)}
    return {
        "mixer": {"q": (D, H * d), "k": (D, G * d), "v1": (D, G * d // 2),
                  "v2": (D, G * d // 2), "o": (H * d, D),
                  "conv0": (c["cca_time0"], C), "conv0_bias": (C,),
                  "conv1": (c["cca_time1"], H + G, d, d), "conv1_bias": (C,),
                  "tau": (G,)},
        "norm1": (D,), "norm2": (D,), "res1": vectors, "res2": dict(vectors),
        "moe": {"router": {"proj": (D, R), "proj_bias": (R,), "carry": (R,),
                           "norm": (R,), "w1": (R, R), "b1": (R,),
                           "w2": (R, R), "b2": (R,), "w3": (R, E)},
                "router_bias": (E,),
                "experts": {"gate": (E, D, F), "up": (E, D, F), "down": (E, F, D)}},
    }


def top_shapes(c: dict) -> dict:
    return {"embed": (c["vocab_size"], c["hidden_size"]),
            "final_norm": (c["hidden_size"],)}


def param_count(c: dict) -> int:
    """Parameters of the stage (norms and vectors included; the balance
    bias and the tied head not)."""
    def count(tree):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_shape)
        return sum(math.prod(shape) for path, shape in flat
                   if path[-1].key != "router_bias")

    return count(top_shapes(c)) + c["num_hidden_layers"] * count(layer_shapes(c))


def _is_shape(s):
    return isinstance(s, tuple)


def seed_key(seed: int):
    """One PRNG key from any whole-number seed (they pass 2**31)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _bf16(x):
    """Round to bfloat16 and back (`reduce_precision`: the TPU's compiler
    may drop a pair of casts as excess precision)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@partial(jax.jit, static_argnames=("std", "shape"))
def _draw(key, std: float, shape: tuple):
    return _bf16(std * jax.random.normal(key, shape, jnp.float32))


def leaf_std(name: str, c: dict) -> float:
    by_fan_in = {"conv0": c["cca_time0"], "conv1": c["cca_time1"] * c["head_dim"],
                 "w1": c["router_hidden_size"], "w2": c["router_hidden_size"],
                 "w3": c["router_hidden_size"]}
    if name in by_fan_in:
        return by_fan_in[name] ** -0.5
    return {"embed": c["embed_init_std"], "o": c["out_init_std"],
            "down": c["out_init_std"]}.get(name, c["init_std"])


BIAS_PROBES, BIAS_INDEX = 4096, 2 ** 16


@partial(jax.jit, static_argnames="eps")
def balanced_bias(key, router: dict, eps: float):
    probe = jax.random.normal(key, (BIAS_PROBES, router["norm"].shape[0]),
                              jnp.float32)
    mean = router_probs(router, probe, eps).mean(0)
    return _bf16(1.0 / mean.shape[0] - mean)


def make_tree(key, index: int, shapes: dict, c: dict) -> dict:
    """The weights of the tree with the published index `index`."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)
    base = jax.random.fold_in(key, index)
    leaves = []
    for j, (path, shape) in enumerate(flat):
        name = str(path[-1].key)
        if "norm" in name or name == "carry" or name.endswith("_scale"):
            leaf = jnp.ones(shape, jnp.float32)
        elif name.endswith("_bias") or name in ("b1", "b2", "tau"):
            leaf = jnp.zeros(shape, jnp.float32)
        else:
            leaf = _draw(jax.random.fold_in(base, j), float(leaf_std(name, c)), shape)
        leaves.append(leaf)
    tree = jax.tree.unflatten(treedef, leaves)
    if "moe" in tree:
        tree["moe"]["router_bias"] = balanced_bias(
            jax.random.fold_in(base, BIAS_INDEX), tree["moe"]["router"],
            c["rms_norm_eps"])
    return tree


# --------------------------------------------------------------- arithmetic

def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w, precision):
    if precision == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    return jnp.matmul(x, w, precision=_HI)


def _rms(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _ffn(p, x, precision):
    return _mm(_silu(_mm(x, p["gate"], precision)) * _mm(x, p["up"], precision),
               p["down"], precision)


def shifted(x, j: int):
    """x[t - j] at t along the first axis of ONE document; before the
    first token there is nothing."""
    return x if j == 0 else jnp.pad(x[:-j], [(j, 0)] + [(0, 0)] * (x.ndim - 1))


def conv_depthwise(x, taps, bias):
    """x: (L, C); taps: (K, C), tap j weighs the token j positions back."""
    return sum(shifted(x, j) * taps[j] for j in range(taps.shape[0])) + bias


def conv_grouped(x, taps, bias, precision="f32"):
    """x: (L, G, d); taps: (K, G, d, d): group g maps its own d channels
    to d, tap j weighs the token j positions back; bias: (G, d)."""
    def tap(a, w):
        if precision == "int8":
            a, w = _fake_int8(a, -1), _fake_int8(w, 1)
        return jnp.einsum("lgd,gde->lge", a, w, precision=_HI)

    return sum(tap(shifted(x, j), taps[j]) for j in range(taps.shape[0])) + bias


def rotary_partial(x, pos, theta, rotary_dim: int):
    """x: (L, heads, d): of the first `rotary_dim`, dimension j turns with
    dimension j + rotary_dim / 2 by the angle pos * theta ** (-2j /
    rotary_dim); the rest pass unchanged."""
    half = rotary_dim // 2
    freq = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    ang = (pos.astype(jnp.float32)[:, None] * freq)[:, None, :]
    a, b = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang),
                            x[..., rotary_dim:]], axis=-1)


def cca_operands(p, u, c, precision):
    """(q (L, H, d), k, v (L, G, d)) of the core from the normed input u."""
    L = u.shape[0]
    H, G, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    group = H // G
    q_, k_ = _mm(u, p["q"], precision), _mm(u, p["k"], precision)
    z = conv_depthwise(jnp.concatenate([q_, k_], axis=-1), p["conv0"],
                       p["conv0_bias"])
    z = conv_grouped(z.reshape(L, H + G, d), p["conv1"],
                     p["conv1_bias"].reshape(H + G, d), precision)
    m_q = 0.5 * (q_.reshape(L, G, group, d) + k_.reshape(L, G, 1, d))
    unit = lambda a: math.sqrt(d) * a / jnp.sqrt(  # noqa: E731
        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q = unit(z[:, :H] + m_q.reshape(L, H, d))
    k = unit(z[:, H:] + m_q.mean(axis=2)) * jnp.exp(p["tau"])[:, None]
    pos = jnp.arange(L)
    rd = int(d * c["partial_rotary_factor"])
    v = jnp.concatenate([_mm(u, p["v1"], precision),
                         shifted(_mm(u, p["v2"], precision), 1)], axis=-1)
    return (rotary_partial(q, pos, c["rope_theta"], rd),
            rotary_partial(k, pos, c["rope_theta"], rd), v.reshape(L, G, d))


def _cca(p, u, c, precision):
    L = u.shape[0]
    H, G, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    q, k, v = cca_operands(p, u, c, precision)
    k, v = jnp.repeat(k, H // G, axis=1), jnp.repeat(v, H // G, axis=1)
    pos = jnp.arange(L)

    def block(start):
        """A block of queries against ALL the keys, the later ones masked."""
        s = jnp.einsum("qhd,khd->hqk", jax.lax.dynamic_slice_in_dim(q, start, BLOCK),
                       k, precision=_HI) * d ** -0.5
        causal = pos[None, :] <= start + jnp.arange(BLOCK)[:, None]
        s = jnp.where(causal[None], s, -1e30)
        w = jnp.exp(s - s.max(-1, keepdims=True))
        return jnp.einsum("hqk,khd->qhd", w / w.sum(-1, keepdims=True), v,
                          precision=_HI)

    if L % BLOCK:
        raise ValueError(f"a document of {L} positions is no multiple of {BLOCK}")
    out = jax.lax.map(block, jnp.arange(0, L, BLOCK)).reshape(L, H * d)
    return _mm(out, p["o"], precision)


def _scaled_residual(p, x, y):
    return (p["res_scale"] * x + p["res_bias"]) + (p["out_scale"] * y + p["out_bias"])


def router_probs(p, r, eps):
    """The router from its state on: r (L, R) -> p (L, experts)."""
    mm = partial(jnp.matmul, precision=_HI)
    h = _rms(p["norm"], r, eps)
    h = _gelu(mm(h, p["w1"]) + p["b1"])
    h = _gelu(mm(h, p["w2"]) + p["b2"])
    s = mm(h, p["w3"])
    e = jnp.exp(s - s.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def route(u, p, state, bias, c):
    """u: (L, D), state: (L, R) -> (ids (L, k), weights (L, k), r (L, R))."""
    r = (jnp.matmul(u, p["proj"], precision=_HI) + p["proj_bias"]
         + p["carry"] * state)
    probs = router_probs(p, r, c["rms_norm_eps"])
    ids = jax.lax.top_k(probs + bias, c["num_experts_per_tok"])[1]
    return ids, jnp.take_along_axis(probs, ids, axis=-1), r


def routed_experts(p, u, state, real, c, precision):
    """Every expert over every token, masked by the choice. u: (L, D);
    state: (L, R); real: (L,) bool -> (y, r, ids)."""
    ids, w, r = route(u, p["router"], state, p["router_bias"], c)
    ids = jnp.where(real[:, None], ids, -1)

    def one(y, xs):
        e, gate, up, down = xs
        mine = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
        out = _ffn({"gate": gate, "up": up, "down": down}, u, precision)
        return y + mine[:, None] * out, None

    ex = p["experts"]
    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (jnp.arange(c["num_experts"]), ex["gate"], ex["up"], ex["down"]))
    return y, r, ids


def mixer_step(p, x, c: dict, precision="f32"):
    """The attention sublayer over ONE document. p: the layer's `norm1`,
    `mixer`, `res1`; x: (L, D), real tokens first."""
    u = _rms(p["norm1"], x, c["rms_norm_eps"])
    return _scaled_residual(p["res1"], x, _cca(p["mixer"], u, c, precision))


def ffn_step(p, x, r, start, n, c: dict, precision="f32"):
    """(x, r) with the expert sublayer's result and the router's new
    state in the rows start .. start + BLOCK, of which those before n are
    real. p: the layer's `norm2`, `moe`, `res2`; r: (L, R), the previous
    layer's state."""
    rows = jax.lax.dynamic_slice_in_dim(x, start, BLOCK)
    u = _rms(p["norm2"], rows, c["rms_norm_eps"])
    real = start + jnp.arange(BLOCK) < n
    y, r_rows, _ = routed_experts(
        p["moe"], u, jax.lax.dynamic_slice_in_dim(r, start, BLOCK), real, c,
        precision)
    rows = _scaled_residual(p["res2"], rows, y)
    return (jax.lax.dynamic_update_slice_in_dim(x, rows, start, 0),
            jax.lax.dynamic_update_slice_in_dim(r, r_rows, start, 0))


def pooled(final_norm, x, n, c):
    h = _rms(final_norm, x, c["rms_norm_eps"])
    real = (jnp.arange(x.shape[0]) < n)[:, None]
    return {"global": h[n - 1],
            "local_mean": jnp.sum(jnp.where(real, h, 0.0), axis=0) / n}


def embed_documents(seed: int, documents: list, c: dict, precision="f32",
                    edit=None, every_token=False) -> list:
    """The reference's answer to each document (a 1-D array of token ids),
    each ALONE: [{"global": (D,), "local_mean": (D,)}] float32. Weights a
    layer at a time; every document padded to the power of two that holds
    the longest (nothing real reads the padding). With `every_token` the
    final-norm state of each token instead, [(n, D)]: row t is what
    `global` would be for the document cut after token t."""
    key = seed_key(seed)
    edit = edit or (lambda index, tree: tree)
    lengths = [len(d) for d in documents]
    width = max(BLOCK, 2 ** math.ceil(math.log2(max(lengths))))
    with jax.default_matmul_precision("highest"):
        top = edit(TOP_INDEX, make_tree(key, TOP_INDEX, top_shapes(c), c))
        xs = []
        for d, n in zip(documents, lengths):
            ids = np.zeros(width, np.int32)
            ids[:n] = d
            xs.append(jnp.take(top["embed"], jnp.asarray(ids), axis=0))
        final_norm = top["final_norm"]
        del top
        rs = [jnp.zeros((width, c["router_hidden_size"]), jnp.float32) for _ in xs]
        mix = jax.jit(partial(mixer_step, c=c, precision=precision))
        ffn = jax.jit(partial(ffn_step, c=c, precision=precision))
        shapes = layer_shapes(c)
        for j in range(c["num_hidden_layers"]):
            index = c["first_layer_index"] + j
            p = edit(index, make_tree(key, index, shapes, c))
            first = {k: p.pop(k) for k in ("norm1", "mixer", "res1")}
            xs = [mix(first, x) for x in xs]
            del first
            for i, n in enumerate(lengths):
                for start in range(0, n, BLOCK):
                    xs[i], rs[i] = ffn(p, xs[i], rs[i], start, n)
            jax.block_until_ready(xs)
            del p
        if every_token:
            return [jax.device_get(_rms(final_norm, x, c["rms_norm_eps"]))[:n]
                    for x, n in zip(xs, lengths)]
        pool = jax.jit(partial(pooled, c=c))
        return [jax.device_get(pool(final_norm, x, n)) for x, n in zip(xs, lengths)]
