"""Nemotron-3-Super-120B-A12B (`nemotron_h`) on the serving path in plain
`jax.numpy`, float32, products at "highest": one chip's share of the first
pipeline stage, one document at a time.

The plain reference of the `nemotron-3-super-ep4` configuration: weights
from a seed and the forward pass that `embed` needs (the output head and
the prediction module are not on this path), written from the published
config.json (`benchmark/configs/nemotron-3-super-ep4.json`, key
`published`), Mamba-2 (Dao and Gu, arXiv:2405.21060), the Nemotron-H
report (arXiv:2504.03624) and the layer equations of ISSUE 43 / PERF.md
section 4. It imports nothing of `proteinbert_tpu`. No kernels, no
packing, no chunks: the recurrence runs TOKEN BY TOKEN (`lax.scan` over
the tokens, the state heads x head_dim x state), the convolution is four
shifted adds, attention sees all keys a block of queries at a time with
the two key heads repeated to the 32 query heads, every held expert runs
over every token masked by the choice, and the way down to the latent and
up from it are plain products.

As `zaya_f32.py`: every document of a sample is padded to ONE length
(nothing real reads the padding: everything here is causal), a mixer runs
over a whole padded document, an expert layer (which sees one token at a
time) over the blocks of `BLOCK` tokens that hold a real one, and a weight
is drawn by one function a shape. A layer's weights are made, used for
every document and dropped before the next layer's: the share is 21.5 GB
in float32 and never whole.

Equations (c: the configuration as a dict, published key names; N =
RMSNorm with eps c.layer_norm_epsilon). Layer l, of the kind
c.hybrid_override_pattern[l], is ONE sublayer: x <- x + Mixer(N(x)).
  M  Mamba-2    [z | xBC | dt] = u W_in;  xBC <- silu(conv(xBC) + b_conv),
                causal, depthwise, conv_kernel taps (tap j weighs the
                token j positions back; nothing before the first token)
                xBC -> x (H heads x P), B (G x N), C (G x N); head h reads
                group h // (H / G)
                dt_h = softplus(dt_h + dt_bias_h), not clamped;
                a_h = -exp(A_log_h)
                S_t = exp(dt_t a) S_(t-1) + dt_t x_t B_t^T, S before the
                first token 0, per head (P x N);  y_t = S_t C_t + D_h x_t
                y <- N_group(y * silu(z)): the gate THEN the norm, over
                each of the G groups of H P / G channels apart, one scale
                of H P;  out = y W_out
  *  attention  q = u W_q (H heads of d), k = u W_k, v = u W_v (G heads);
                NO rotary, no other position term;
                o[h] = softmax(q[h] . k[h // (H / G)] / sqrt(d), causal)
                v[h // (H / G)];  out = concat_h(o) W_o
  E  LatentMoE  s = sigmoid(u W_r) over the router's width, float32;
                chosen = top-k of s + b (b moves the choice only);
                w = scaling * s[chosen] / (sum + 1e-20)
                lat = u W_dn;  Expert_e(lat) = W2_e relu(W1_e lat)^2
                r = sum over the chosen e HELD HERE of w_e Expert_e(lat)
                out = r W_up + W2_s relu(W1_s u)^2   (the shared expert
                and the router read u, not lat)
  embed         {"global": N_final(x) at the last token,
                 "local_mean": the mean of N_final(x) over the tokens}

Weights: the layer with the published index i draws leaf number j of its
own tree (keys sorted) from fold_in(fold_in(key, i), j), ROUNDED TO
BFLOAT16 and held here as float32: std * normal with std `init_std`, but
`embed_init_std` for the embedding's rows, `out_init_std` for the
products that write into the residual stream (`o`, the shared expert's
`down`, `from_latent`; a routed expert's `down` writes the latent and
keeps `init_std`) and conv_kernel^-1/2 for the convolution's taps; every
`down` (a squared ReLU's second matrix: relu^2 >= 0 has a mean in every
hidden channel) CENTRED before the rounding, each column's mean over its
input rows subtracted, so that the mean writes nothing; norm
scales and `ssm_D` 1; the convolution's and the router's bias 0;
`ssm_A_log` = log(uniform(1, 16)); `ssm_dt_bias` the inverse softplus of
exp(uniform(0, 1) (log time_step_max - log time_step_min) + log
time_step_min) floored at time_step_floor. The embedding and the final
norm are the tree of the index 2**20. `edit` (tests only) is handed every
tree as it is made, (index, tree) -> tree.

`precision`: "f32" is the reference; "int8" the control one step below
bfloat16 products (every product with a weight matrix takes int8 weights,
one scale per output channel, and int8 activations, one scale per row;
the router stays float32, as in the program); "state_bf16" the control
that rounds the recurrence's state to bfloat16 after every token.
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
KINDS = {"M": "mamba", "*": "gqa", "E": "latent_moe"}
PRECISIONS = ("f32", "int8", "state_bf16")


# ------------------------------------------------------------------ weights

def layer_shapes(c: dict, kind: str) -> dict:
    D = c["hidden_size"]
    if kind == "mamba":
        H, inner = c["mamba_num_heads"], c["mamba_num_heads"] * c["mamba_head_dim"]
        C = inner + 2 * c["n_groups"] * c["ssm_state_size"]
        return {"norm": (D,), "mixer": {
            "in_proj": (D, inner + C + H), "conv": (c["conv_kernel"], C),
            "conv_bias": (C,), "ssm_A_log": (H,), "ssm_dt_bias": (H,),
            "ssm_D": (H,), "norm": (inner,), "o": (inner, D)}}
    if kind == "gqa":
        H, G, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
        return {"norm": (D,), "mixer": {"q": (D, H * d), "k": (D, G * d),
                                        "v": (D, G * d), "o": (H * d, D)}}
    E, U, F = c["n_routed_experts"], c["moe_latent_size"], c["moe_intermediate_size"]
    W = c["moe_shared_expert_intermediate_size"]
    return {"norm": (D,),
            "moe": {"router": (D, c["router_width"]),
                    "router_bias": (c["router_width"],),
                    "to_latent": (D, U), "from_latent": (U, D),
                    "experts": {"up": (E, U, F), "down": (E, F, U)}},
            "shared": {"up": (D, W), "down": (W, D)}}


def top_shapes(c: dict) -> dict:
    return {"embed": (c["vocab_size"], c["hidden_size"]),
            "final_norm": (c["hidden_size"],)}


def held_kinds(c: dict) -> list:
    """[(published index, kind)] of the layers held."""
    first = c["first_layer_index"]
    held = c["hybrid_override_pattern"][first:first + c["num_hidden_layers"]]
    if len(held) != c["num_hidden_layers"]:
        raise ValueError("the pattern is shorter than the layers held")
    return [(first + j, KINDS[ch]) for j, ch in enumerate(held)]


def param_count(c: dict) -> int:
    """Parameters of the share (norms included; the balance bias, the
    output head and the prediction module not)."""
    def count(tree):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_shape)
        return sum(math.prod(shape) for path, shape in flat
                   if path[-1].key != "router_bias")

    return count(top_shapes(c)) + sum(count(layer_shapes(c, kind))
                                      for _, kind in held_kinds(c))


def _is_shape(s):
    return isinstance(s, tuple)


def seed_key(seed: int):
    """One PRNG key from any whole-number seed (they pass 2**31)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _bf16(x):
    """Round to bfloat16 and back (`reduce_precision`: the TPU's compiler
    may drop a pair of casts as excess precision)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@partial(jax.jit, static_argnames=("recipe", "shape"))
def _draw(key, recipe: tuple, shape: tuple):
    what = recipe[0]
    if what in ("normal", "normal_centred"):
        leaf = recipe[1] * jax.random.normal(key, shape, jnp.float32)
        if what == "normal_centred":
            leaf = leaf - leaf.mean(axis=-2, keepdims=True)
    elif what == "log_uniform_1_16":
        leaf = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    else:       # "dt_bias": the inverse softplus of a floored log-uniform step
        low, high, floor = recipe[1:]
        dt = jnp.maximum(floor, jnp.exp(
            jax.random.uniform(key, shape, jnp.float32)
            * (np.log(high) - np.log(low)) + np.log(low)))
        leaf = dt + jnp.log(-jnp.expm1(-dt))
    return _bf16(leaf)


def leaf_std(path: tuple, c: dict) -> float:
    name = path[-1]
    if name == "conv":
        return c["conv_kernel"] ** -0.5
    if name == "embed":
        return c["embed_init_std"]
    writes_stream = (name in ("o", "from_latent")
                     or (name == "down" and "experts" not in path))
    return c["out_init_std"] if writes_stream else c["init_std"]


def make_tree(key, index: int, shapes: dict, c: dict) -> dict:
    """The weights of the tree with the published index `index`."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)
    base = jax.random.fold_in(key, index)
    leaves = []
    for j, (path, shape) in enumerate(flat):
        names = tuple(str(k.key) for k in path)
        name = names[-1]
        if "norm" in name or name == "ssm_D":
            leaves.append(jnp.ones(shape, jnp.float32))
            continue
        if name in ("conv_bias", "router_bias"):
            leaves.append(jnp.zeros(shape, jnp.float32))
            continue
        if name == "ssm_A_log":
            recipe = ("log_uniform_1_16",)
        elif name == "ssm_dt_bias":
            recipe = ("dt_bias", float(c["time_step_min"]), float(c["time_step_max"]),
                      float(c["time_step_floor"]))
        else:       # a squared ReLU's second matrix: columns summing to zero
            recipe = ("normal_centred" if name == "down" else "normal",
                      float(leaf_std(names, c)))
        leaves.append(_draw(jax.random.fold_in(base, j), recipe, shape))
    return jax.tree.unflatten(treedef, leaves)


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


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def shifted(x, j: int):
    """x[t - j] at t along the first axis of ONE document; before the
    first token there is nothing."""
    return x if j == 0 else jnp.pad(x[:-j], [(j, 0)] + [(0, 0)] * (x.ndim - 1))


def mamba_operands(p, u, c, precision):
    """(z (L, H P), x (L, H, P), B, C (L, G, N), dt (L, H) after its
    softplus) from the normed input u."""
    L = u.shape[0]
    H, P, G, N = (c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"],
                  c["ssm_state_size"])
    inner = H * P
    zxbcdt = _mm(u, p["in_proj"], precision)
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * G * N],
                  zxbcdt[:, 2 * inner + 2 * G * N:])
    xbc = _silu(sum(shifted(xbc, j) * p["conv"][j]
                    for j in range(c["conv_kernel"])) + p["conv_bias"])
    x, b, cm = (xbc[:, :inner], xbc[:, inner:inner + G * N], xbc[:, inner + G * N:])
    dt = jnp.logaddexp(dt + p["ssm_dt_bias"], 0.0)
    return (z, x.reshape(L, H, P), b.reshape(L, G, N), cm.reshape(L, G, N), dt)


def recurrence(x, dt, a, b, cm, precision="f32"):
    """The state-space recurrence of ONE document, token by token.
    x: (L, H, P); dt: (L, H); a: (H,); b, cm: (L, G, N) -> y (L, H, P)."""
    H, G = x.shape[1], b.shape[1]
    b, cm = jnp.repeat(b, H // G, axis=1), jnp.repeat(cm, H // G, axis=1)

    def step(S, t):
        x_t, dt_t, b_t, c_t = t
        S = (jnp.exp(dt_t * a)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if precision == "state_bf16":
            S = _bf16(S)
        return S, jnp.einsum("hpn,hn->hp", S, c_t, precision=_HI)

    S0 = jnp.zeros((H, x.shape[2], b.shape[2]), jnp.float32)
    return jax.lax.scan(step, S0, (x, dt, b, cm))[1]


def _mamba(p, u, c, precision):
    L = u.shape[0]
    G = c["n_groups"]
    z, x, b, cm, dt = mamba_operands(p, u, c, precision)
    y = recurrence(x, dt, -jnp.exp(p["ssm_A_log"]), b, cm, precision)
    y = (y + p["ssm_D"][:, None] * x).reshape(L, -1) * _silu(z)
    groups = y.reshape(L, G, -1)
    groups = groups / jnp.sqrt(jnp.mean(groups * groups, axis=-1, keepdims=True)
                               + c["layer_norm_epsilon"])
    return _mm(groups.reshape(L, -1) * p["norm"], p["o"], precision)


def _gqa(p, u, c, precision):
    L = u.shape[0]
    H, G, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    q = _mm(u, p["q"], precision).reshape(L, H, d)
    k = jnp.repeat(_mm(u, p["k"], precision).reshape(L, G, d), H // G, axis=1)
    v = jnp.repeat(_mm(u, p["v"], precision).reshape(L, G, d), H // G, axis=1)
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


def route(u, router, bias, c):
    """u: (L, D) -> (ids (L, k), weights (L, k)), float32 at "highest"."""
    s = 1.0 / (1.0 + jnp.exp(-jnp.matmul(u, router, precision=_HI)))
    ids = jax.lax.top_k(s + bias, c["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(s, ids, axis=-1)
    if c["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return ids, w * c["routed_scaling_factor"]


def latent_moe(p, u, real, c, precision):
    """(routed (L, D), shared (L, D), ids (L, k)): the held experts'
    part of the layer over u (L, D), and the shared expert's."""
    ids, w = route(u, p["moe"]["router"], p["moe"]["router_bias"], c)
    ids = jnp.where(real[:, None], ids, -1)
    lat = _mm(u, p["moe"]["to_latent"], precision)

    def one(y, xs):
        e, up, down = xs
        mine = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
        return y + mine[:, None] * _mm(_relu2(_mm(lat, up, precision)), down,
                                       precision), None

    ex = p["moe"]["experts"]
    held = c["expert_offset"] + jnp.arange(c["n_routed_experts"])
    r, _ = jax.lax.scan(one, jnp.zeros_like(lat), (held, ex["up"], ex["down"]))
    routed = _mm(r, p["moe"]["from_latent"], precision)
    shared = _mm(_relu2(_mm(u, p["shared"]["up"], precision)), p["shared"]["down"],
                 precision)
    return routed, shared, ids


def mixer_step(p, x, kind: str, c: dict, precision="f32"):
    """A Mamba or attention layer over ONE document. x: (L, D), real
    tokens first."""
    u = _rms(p["norm"], x, c["layer_norm_epsilon"])
    mixer = _mamba if kind == "mamba" else _gqa
    return x + mixer(p["mixer"], u, c, precision)


def ffn_step(p, x, start, n, c: dict, precision="f32"):
    """x with the expert layer's result in the rows start .. start +
    BLOCK, of which those before n are real."""
    rows = jax.lax.dynamic_slice_in_dim(x, start, BLOCK)
    u = _rms(p["norm"], rows, c["layer_norm_epsilon"])
    routed, shared, _ = latent_moe(p, u, start + jnp.arange(BLOCK) < n, c, precision)
    return jax.lax.dynamic_update_slice_in_dim(x, rows + routed + shared, start, 0)


def pooled(final_norm, x, n, c):
    h = _rms(final_norm, x, c["layer_norm_epsilon"])
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
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} is none of {PRECISIONS}")
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
        steps = {kind: jax.jit(partial(mixer_step, kind=kind, c=c, precision=precision))
                 for kind in ("mamba", "gqa")}
        ffn = jax.jit(partial(ffn_step, c=c, precision=precision))
        for index, kind in held_kinds(c):
            p = edit(index, make_tree(key, index, layer_shapes(c, kind), c))
            if kind == "latent_moe":
                for i, n in enumerate(lengths):
                    for start in range(0, n, BLOCK):
                        xs[i] = ffn(p, xs[i], start, n)
            else:
                xs = [steps[kind](p, x) for x in xs]
            jax.block_until_ready(xs)
            del p
        if every_token:
            return [jax.device_get(_rms(final_norm, x, c["layer_norm_epsilon"]))[:n]
                    for x, n in zip(xs, lengths)]
        pool = jax.jit(partial(pooled, c=c))
        return [jax.device_get(pool(final_norm, x, n)) for x, n in zip(xs, lengths)]
