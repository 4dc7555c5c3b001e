"""Ling-3.0-flash (`bailing_hybrid`) on the serving path in plain
`jax.numpy`, float32, products at "highest": one chip's share of a
four-chip expert-parallel layer group, one document at a time.

The plain reference of the `ling-3.0-flash-ep4` configuration: weights
from a seed and the forward pass that `embed` needs (no output head, no
prediction module: neither is on this path), written from the published
config.json (`benchmark/configs/ling-3.0-flash-ep4.json`, key
`published`) and the layer equations of ISSUE 33 / PERF.md section 4. It
imports nothing of `proteinbert_tpu`. No kernels, no chunks, no packing:
the linear-attention state walks token by token, the convolution is four
shifted adds, attention sees all keys a block of queries at a time, and
every held expert runs over every token, masked by the routing.

Shapes are chosen so that a cold run compiles little (the first form
padded each document to its own power of two and compiled a whole layer
for every padded length, and the weights of every layer anew: 332 s on the
chip's host, PERF.md section 6): every document of a sample is padded to
ONE length, the mixers run over a whole padded document, the FFNs (which
see one token at a time) over its blocks of `BLOCK` tokens that hold a
real one, and a weight is drawn by one function a shape.

Equations (c: the configuration as a dict, published key names; H heads):
  layer i        x + Mixer(N(x)); x + FFN(N(x)); N = RMSNorm, eps
                 c.rms_norm_eps. Mixer: latent where (i + 1) %
                 layer_group_size == 0, else KDA. FFN: dense SwiGLU for the
                 first `first_k_dense_replace` layers held, else experts.
  KDA            q~, k~, v~ = x W_q, x W_k, x W_v; q, k, v = silu(conv(.)),
                 conv: causal depthwise, `short_conv_kernel_size` taps;
                 q, k / sqrt(sum of squares + 1e-6) per head, q * d_k^-1/2;
                 g = kda_lower_bound * sigmoid(exp(A_log_h) (x W_f + dt_bias));
                 beta = sigmoid(x W_beta);
                 S_t = (I - beta k k^T) Diag(exp(g)) S_{t-1} + beta k v^T,
                 S = 0 before the first token; o_t = S_t^T q_t;
                 y = W_o [N_head(o) * sigmoid(x W_g)], N_head one RMSNorm
                 scale of d_v shared by the heads, the gate one scalar a head
  latent         q = x W_q -> per head [q_nope, q_rope];
                 [c_kv, k_rope] = x W_kva; c_kv = N(c_kv);
                 [k_nope, v] = c_kv W_kvb per head; rotary on q_rope and the
                 one shared k_rope in INTERLEAVED pairs (2j, 2j + 1), angle
                 pos * theta^(-2j / rope); scores / sqrt(nope + rope), causal;
                 y = W_o [concat_heads(P v) * sigmoid(x W_g)]
  experts        s = sigmoid(h W_r) (float32 always); choice on s + b: the
                 experts in n_group groups, a group's score the sum of its two
                 best, the best topk_group groups kept, top k among their
                 experts; w = s[chosen] / (sum + 1e-20) * routed_scaling_factor;
                 sum over chosen experts HELD HERE of w * Expert_e(h) + Shared(h)
  embed          {"global": N_final(x) at the last token,
                  "local_mean": the mean of N_final(x) over the tokens}

Weights: the layer with the published index i draws leaf number j of its
own tree (keys sorted) as std * normal(fold_in(fold_in(key, i), j)),
ROUNDED TO BFLOAT16 (the published weights are bfloat16 values) and held
here as float32 (the conv taps too); std is `init_std`, but
`embed_init_std` for the embedding's rows and `out_init_std` for the
products that write into the residual stream (a mixer's `o`, an FFN's
`down`); a norm's scale is 1, the router's
bias 0, A_log_h = log(1 + 3 h / (H - 1)), dt_bias = -4 (the configuration
file lists these under `assumed`). The embedding
and the final norm are the tree of the index 2**20. A layer's weights are
made, used for every document, and dropped before the next layer's, so
that the share (18.9 GiB in float32) never stands whole.

`precision`: "f32" is the reference; "int8" the control one step below
bfloat16 products (every product with a weight matrix takes int8 weights,
one scale per output channel, and int8 activations, one scale per row;
the router stays float32, as in the program); "bf16_state" the control
one step below the float32 KDA state (rounded to bfloat16 after every
token).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
BLOCK = 256      # queries a step of attention; tokens a call of an FFN
TOP_INDEX = 2 ** 20


# ------------------------------------------------------------------ weights

def layer_kinds(c: dict) -> list:
    """[(published index, "kda" | "mla", "dense" | "moe")] of the layers held."""
    out = []
    for j in range(c["num_hidden_layers"]):
        i = c["first_layer_index"] + j
        out.append((i, "mla" if (i + 1) % c["layer_group_size"] == 0 else "kda",
                    "dense" if j < c["first_k_dense_replace"] else "moe"))
    return out


def layer_shapes(c: dict, mixer: str, ffn: str) -> dict:
    D, H = c["hidden_size"], c["num_attention_heads"]
    if mixer == "kda":
        dk, K = c["kda_head_dim"], c["short_conv_kernel_size"]
        mix = {"q": (D, H * dk), "k": (D, H * dk), "v": (D, H * dk),
               "f": (D, H * dk), "o": (H * dk, D), "beta": (D, H), "g": (D, H),
               "conv_q": (K, H * dk), "conv_k": (K, H * dk), "conv_v": (K, H * dk),
               "A_log": (H,), "dt_bias": (H * dk,), "o_norm": (dk,)}
    else:
        nope, rope, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
        mix = {"q": (D, H * (nope + rope)), "kv_a": (D, c["kv_lora_rank"] + rope),
               "kv_norm": (c["kv_lora_rank"],),
               "kv_b": (c["kv_lora_rank"], H * (nope + dv)),
               "o": (H * dv, D), "g": (D, H)}
    swiglu = lambda width: {"gate": (D, width), "up": (D, width),  # noqa: E731
                            "down": (width, D)}
    tree = {"mixer": mix, "norm1": (D,), "norm2": (D,)}
    if ffn == "dense":
        tree["mlp"] = swiglu(c["intermediate_size"])
    else:
        E, F = c["experts_held"], c["moe_intermediate_size"]
        tree["moe"] = {"router": (D, c["n_routed_experts"]),
                       "router_bias": (c["n_routed_experts"],),
                       "experts": {"gate": (E, D, F), "up": (E, D, F),
                                   "down": (E, F, D)}}
        tree["shared"] = swiglu(c["n_shared_experts"] * F)
    return tree


def top_shapes(c: dict) -> dict:
    return {"embed": (c["vocab_size"], c["hidden_size"]),
            "final_norm": (c["hidden_size"],)}


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


def make_tree(key, index: int, shapes: dict, c: dict) -> dict:
    """The weights of the tree with the published index `index`."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)
    base = jax.random.fold_in(key, index)
    H = c["num_attention_heads"]
    leaves = []
    for j, (path, shape) in enumerate(flat):
        name = str(path[-1].key)
        if "norm" in name:
            leaf = jnp.ones(shape, jnp.float32)
        elif name == "router_bias":
            leaf = jnp.zeros(shape, jnp.float32)
        elif name == "A_log":
            leaf = _bf16(jnp.log(
                1.0 + 3.0 * jnp.arange(H, dtype=jnp.float32) / max(H - 1, 1)))
        elif name == "dt_bias":
            leaf = jnp.full(shape, -4.0, jnp.float32)
        else:
            std = {"embed": c["embed_init_std"], "o": c["out_init_std"],
                   "down": c["out_init_std"]}.get(name, c["init_std"])
            leaf = _draw(jax.random.fold_in(base, j), float(std), shape)
        leaves.append(leaf)
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


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _silu(x):
    return x * _sigmoid(x)


def _ffn(p, x, precision):
    return _mm(_silu(_mm(x, p["gate"], precision)) * _mm(x, p["up"], precision),
               p["down"], precision)


def conv_taps(x, taps):
    """x: (L, C) of ONE document; taps: (K, C), tap j weighs the token j
    positions back; before the first token there is nothing."""
    out = x * taps[0]
    for j in range(1, taps.shape[0]):
        out = out + jnp.pad(x[:-j], ((j, 0), (0, 0))) * taps[j]
    return out


def kda_recurrence(q, k, v, g, beta, state_dtype="f32"):
    """Token by token. q, k, g: (L, H, d_k); v: (L, H, d_v); beta: (L, H).
    -> o (L, H, d_v)."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, :, None] * S
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, S, precision=_HI))
        S = S + k_t[:, :, None] * u[:, None, :]
        if state_dtype == "bf16":
            S = _bf16(S)
        return S, jnp.einsum("hk,hkv->hv", q_t, S, precision=_HI)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    return o


def kda_inputs(p, x, c, precision):
    """(q, k, v, g, beta) of the recurrence from the normed input x (L, D)."""
    L, H, dk = x.shape[0], c["num_attention_heads"], c["kda_head_dim"]
    heads = lambda a: a.reshape(L, H, dk)  # noqa: E731
    proj = lambda name: _silu(conv_taps(  # noqa: E731
        _mm(x, p[name], precision), p["conv_" + name]))
    unit = lambda a: a / jnp.sqrt(  # noqa: E731
        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q = unit(heads(proj("q"))) * dk ** -0.5
    k = unit(heads(proj("k")))
    v = heads(proj("v"))
    f = heads(_mm(x, p["f"], precision) + p["dt_bias"])
    g = c["kda_lower_bound"] * _sigmoid(jnp.exp(p["A_log"])[None, :, None] * f)
    beta = _sigmoid(_mm(x, p["beta"], precision))
    return q, k, v, g, beta


def _kda(p, x, c, precision):
    L, H, dk = x.shape[0], c["num_attention_heads"], c["kda_head_dim"]
    q, k, v, g, beta = kda_inputs(p, x, c, precision)
    o = kda_recurrence(q, k, v, g, beta,
                       "bf16" if precision == "bf16_state" else "f32")
    o = _rms(p["o_norm"], o, c["rms_norm_eps"])
    gate = _sigmoid(_mm(x, p["g"], precision))
    return _mm((o * gate[:, :, None]).reshape(L, H * dk), p["o"], precision)


def rotary_interleaved(x, pos, theta):
    """x: (L, ..., d): dimension 2j turns with dimension 2j + 1 by the
    angle pos * theta ** (-2j / d)."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * freq
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1).reshape(x.shape)


def _latent(p, x, c, precision):
    L, H = x.shape[0], c["num_attention_heads"]
    nope, rope, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    pos = jnp.arange(L)
    q = _mm(x, p["q"], precision).reshape(L, H, nope + rope)
    kv = _mm(x, p["kv_a"], precision)
    c_kv = _rms(p["kv_norm"], kv[:, :c["kv_lora_rank"]], c["rms_norm_eps"])
    k_rope = rotary_interleaved(kv[:, c["kv_lora_rank"]:], pos, c["rope_theta"])
    kv_up = _mm(c_kv, p["kv_b"], precision).reshape(L, H, nope + dv)
    q = jnp.concatenate(
        [q[..., :nope], rotary_interleaved(q[..., nope:], pos, c["rope_theta"])], -1)
    k = jnp.concatenate(
        [kv_up[..., :nope], jnp.broadcast_to(k_rope[:, None, :], (L, H, rope))], -1)
    v = kv_up[..., nope:]

    def block(start):
        """A block of queries against ALL the keys, the later ones masked."""
        s = jnp.einsum("qhd,khd->hqk", jax.lax.dynamic_slice_in_dim(q, start, BLOCK),
                       k, precision=_HI) * (nope + rope) ** -0.5
        causal = pos[None, :] <= start + jnp.arange(BLOCK)[:, None]
        s = jnp.where(causal[None], s, -1e30)
        w = jnp.exp(s - s.max(-1, keepdims=True))
        return jnp.einsum("hqk,khd->qhd", w / w.sum(-1, keepdims=True), v,
                          precision=_HI)

    if L % BLOCK:
        raise ValueError(f"a document of {L} positions is no multiple of {BLOCK}")
    out = jax.lax.map(block, jnp.arange(0, L, BLOCK)).reshape(L, H, dv)
    gate = _sigmoid(_mm(x, p["g"], precision))
    return _mm((out * gate[:, :, None]).reshape(L, H * dv), p["o"], precision)


def route(h, router, bias, c):
    """h: (L, D) -> (ids (L, k), weights (L, k)), the choice group-limited."""
    s = _sigmoid(jnp.matmul(h, router, precision=_HI))
    choice = s + bias
    n_group, R = c["n_group"], c["n_routed_experts"]
    if n_group > 1:
        grouped = choice.reshape(-1, n_group, R // n_group)
        group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)
        kept = jax.lax.top_k(group_score, c["topk_group"])[1]
        open_ = jnp.zeros(group_score.shape, bool).at[
            jnp.arange(kept.shape[0])[:, None], kept].set(True)
        choice = jnp.where(jnp.repeat(open_, R // n_group, axis=1), choice, -jnp.inf)
    ids = jax.lax.top_k(choice, c["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(s, ids, axis=-1)
    if c["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return ids, w * c["routed_scaling_factor"]


def routed_experts(p, h, real, c, precision):
    """The held experts' part of the layer's result, every held expert
    over every token, masked by the routing. h: (L, D); real: (L,) bool."""
    ids, w = route(h, p["router"], p["router_bias"], c)
    ids = jnp.where(real[:, None], ids, -1)

    def one(y, xs):
        e, gate, up, down = xs
        mine = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
        out = _ffn({"gate": gate, "up": up, "down": down}, h, precision)
        return y + mine[:, None] * out, None

    held = c["expert_offset"] + jnp.arange(c["experts_held"])
    ex = p["experts"]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (held, ex["gate"], ex["up"], ex["down"]))
    return y, ids


def mixer_step(p, x, mixer: str, c: dict, precision="f32"):
    """x + Mixer(N(x)) over ONE document. p: the layer's `norm1` and
    `mixer`; x: (L, D), real tokens first (what follows them is padding
    that nothing real reads: both mixers are causal)."""
    h = _rms(p["norm1"], x, c["rms_norm_eps"])
    return x + (_kda if mixer == "kda" else _latent)(p["mixer"], h, c, precision)


def ffn_step(p, x, start, n, c: dict, precision="f32"):
    """x with x + FFN(N(x)) in the rows start .. start + BLOCK, of which
    those before n are real. p: the layer's `norm2` and its FFN."""
    rows = jax.lax.dynamic_slice_in_dim(x, start, BLOCK)
    h = _rms(p["norm2"], rows, c["rms_norm_eps"])
    if "mlp" in p:
        y = _ffn(p["mlp"], h, precision)
    else:
        real = start + jnp.arange(BLOCK) < n
        y = (routed_experts(p["moe"], h, real, c, precision)[0]
             + _ffn(p["shared"], h, precision))
    return jax.lax.dynamic_update_slice_in_dim(x, rows + y, start, 0)


def pooled(final_norm, x, n, c):
    h = _rms(final_norm, x, c["rms_norm_eps"])
    real = (jnp.arange(x.shape[0]) < n)[:, None]
    return {"global": h[n - 1],
            "local_mean": jnp.sum(jnp.where(real, h, 0.0), axis=0) / n}


def embed_documents(seed: int, documents: list, c: dict, precision="f32") -> list:
    """The reference's answer to each document (a 1-D array of token ids),
    each ALONE: [{"global": (D,), "local_mean": (D,)}] float32. Weights a
    layer at a time; every document padded to the power of two that holds
    the longest (nothing real reads the padding)."""
    key = seed_key(seed)
    lengths = [len(d) for d in documents]
    width = max(BLOCK, 2 ** math.ceil(math.log2(max(lengths))))
    with jax.default_matmul_precision("highest"):
        top = make_tree(key, TOP_INDEX, top_shapes(c), c)
        xs = []
        for d, n in zip(documents, lengths):
            ids = np.zeros(width, np.int32)
            ids[:n] = d
            xs.append(jnp.take(top["embed"], jnp.asarray(ids), axis=0))
        final_norm = top["final_norm"]
        del top
        mix = {mixer: jax.jit(partial(mixer_step, mixer=mixer, c=c, precision=precision))
               for mixer in ("kda", "mla")}
        ffn = jax.jit(partial(ffn_step, c=c, precision=precision))
        for index, mixer, kind in layer_kinds(c):
            p = make_tree(key, index, layer_shapes(c, mixer, kind), c)
            first = {"norm1": p.pop("norm1"), "mixer": p.pop("mixer")}
            xs = [mix[mixer](first, x) for x in xs]
            del first
            for i, n in enumerate(lengths):
                for start in range(0, n, BLOCK):
                    xs[i] = ffn(p, xs[i], start, n)
            jax.block_until_ready(xs)
            del p
        pool = jax.jit(partial(pooled, c=c))
        return [jax.device_get(pool(final_norm, x, n)) for x, n in zip(xs, lengths)]
