"""ProteinBERT in plain `jax.numpy`, float32, matmuls at "highest".

The plain reference of the benchmark: parameter initialisation from a
seed, the denoising corruption, the dual-track forward pass, the
pretraining loss with its gradients, and the clip + Adam update, written
from the paper (Brandes et al. 2022, Bioinformatics 38:2102) and from the
published description of this repository's model (PERF.md section 4). It
imports nothing from `proteinbert_tpu` and takes nothing the program has
made: weights, corrupted batches and optimizer state are all its own,
drawn from the same seed by the same recipe.

Departures from the paper, each because the system under test makes the
same choice and the comparison is of arithmetic, not of design:
GELU is the tanh approximation; LayerNorm is per position over features;
global attention has one query per head and a softmax over the sequence
with padding masked; both loss terms are means weighted by the loss masks.

`precision` selects the arithmetic. "f32" is the reference. "int8" is the
control of the comparison: every matrix product takes int8 weights (one
scale per output channel) and int8 activations (one scale per row), the
step below bfloat16 that the v5e's MXU offers. "bf16_params" is the
second control: parameters and their updates held in bfloat16, the step
below the float32 the configuration states for them. `operands="bf16"`
is not a precision of the reference's arithmetic but of what it is
given: where the configuration states bfloat16 products, the weights
enter them rounded to bfloat16, and all else stays float32.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

PAD_ID, SOS_ID, EOS_ID, N_SPECIAL, VOCAB = 0, 1, 2, 4, 26
ALPHABET = "ACDEFGHIKLMNPQRSTUVWXY"
_HI = jax.lax.Precision.HIGHEST
_LN_EPS = 1e-5


# ------------------------------------------------------------------ weights

def _lecun(key, shape, fan_in):
    # Truncated normal at +-2 sigma rescaled to variance 1/fan_in: the
    # LeCun-normal initialiser, as jax.nn.initializers defines it.
    std = math.sqrt(1.0 / fan_in) / 0.87962566103423978
    return jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std


def _dense(key, n_in, n_out):
    return {"kernel": _lecun(key, (n_in, n_out), n_in),
            "bias": jnp.zeros((n_out,), jnp.float32)}


def _conv(key, taps, n_in, n_out):
    return {"kernel": _lecun(key, (taps, n_in, n_out), taps * n_in),
            "bias": jnp.zeros((n_out,), jnp.float32)}


def _ln(n):
    return {"scale": jnp.ones((n,), jnp.float32),
            "bias": jnp.zeros((n,), jnp.float32)}


def _block(key, m):
    C, G, H, k = m["local_dim"], m["global_dim"], m["num_heads"], m["key_dim"]
    ks = jax.random.split(key, 7)
    kq, kk, kv = jax.random.split(ks[5], 3)
    return {
        "narrow_conv": _conv(ks[0], m["narrow_kernel"], C, C),
        "wide_conv": _conv(ks[1], m["wide_kernel"], C, C),
        "global_to_local": _dense(ks[2], G, C),
        "local_ln1": _ln(C),
        "local_dense": _dense(ks[3], C, C),
        "local_ln2": _ln(C),
        "global_dense1": _dense(ks[4], G, G),
        # Fan-in counts all heads: the heads' projections are drawn as
        # one (H, in, out) array whose variance is 1 / (H * in).
        "attention": {"wq": _lecun(kq, (H, G, k), H * G),
                      "wk": _lecun(kk, (H, C, k), H * C),
                      "wv": _lecun(kv, (H, C, G // H), H * C)},
        "global_ln1": _ln(G),
        "global_dense2": _dense(ks[6], G, G),
        "global_ln2": _ln(G),
    }


def init_params(key, m):
    """The model's parameters from a PRNG key; blocks stacked on axis 0."""
    k_embed, k_gin, k_blocks, k_lh, k_gh = jax.random.split(key, 5)
    blocks = [_block(k, m) for k in jax.random.split(k_blocks, m["num_blocks"])]
    return {
        "embedding": {"embedding": jax.random.normal(
            k_embed, (m["vocab_size"], m["local_dim"]), jnp.float32)},
        "global_in": _dense(k_gin, m["num_annotations"], m["global_dim"]),
        "blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *blocks),
        "local_head": _dense(k_lh, m["local_dim"], m["vocab_size"]),
        "global_head": _dense(k_gh, m["global_dim"], m["num_annotations"]),
    }


def seed_key(seed: int):
    """One PRNG key from any whole-number seed (they pass 2**31)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def train_keys(seed: int):
    """(key of the weights, key the corruption stream starts from)."""
    k_init, k_state = jax.random.split(seed_key(seed))
    return k_init, k_state


def _bf16(x):
    """Round to bfloat16 and back. `reduce_precision`, not a pair of
    casts: the TPU's compiler may drop those as excess precision."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


_PRODUCT_WEIGHTS = ("kernel", "wq", "wk", "wv", "embedding")


@jax.jit
def round_product_weights(params):
    """The weights as the matrix products of the configuration take
    them: it states bfloat16 operands, so kernels, attention projections
    and the embedding table are rounded to bfloat16. Norms, biases, every
    activation and every accumulation stay float32."""
    def one(path, x):
        if getattr(path[-1], "key", None) in _PRODUCT_WEIGHTS:
            return _bf16(x)
        return x

    return jax.tree_util.tree_map_with_path(one, params)


# --------------------------------------------------------------- arithmetic

def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    # Straight-through: the rounding has no gradient of its own.
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, precision):
    """x (..., n_in) @ w (n_in, n_out)."""
    if precision == "int8":
        x = _fake_int8(x, -1)
        w = _fake_int8(w, 0)
    return jnp.matmul(x, w, precision=_HI)


def _layer_norm(p, x):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + _LN_EPS) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _conv_same(p, x, dilation, precision):
    """Zero-padded 'same' 1-D convolution as one product per tap."""
    taps = p["kernel"].shape[0]
    total = (taps - 1) * dilation
    lo = total // 2
    xp = jnp.pad(x, ((0, 0), (lo, total - lo), (0, 0)))
    L = x.shape[1]
    out = p["bias"]
    for t in range(taps):
        out = out + _mm(xp[:, t * dilation:t * dilation + L], p["kernel"][t],
                        precision)
    return out


def _block_apply(p, local, global_, pad_mask, m, precision):
    mm = partial(_mm, precision=precision)
    broadcast = _gelu(mm(global_, p["global_to_local"]["kernel"])
                      + p["global_to_local"]["bias"])
    narrow = _gelu(_conv_same(p["narrow_conv"], local, 1, precision))
    wide = _gelu(_conv_same(p["wide_conv"], local, m["wide_dilation"],
                            precision))
    h = _layer_norm(p["local_ln1"],
                    local + narrow + wide + broadcast[:, None, :])
    local = _layer_norm(
        p["local_ln2"],
        h + _gelu(mm(h, p["local_dense"]["kernel"]) + p["local_dense"]["bias"]))

    # Global attention over the NEW local track with the OLD global track.
    a = p["attention"]
    H, k = a["wq"].shape[0], a["wq"].shape[2]
    heads = []
    for i in range(H):
        q = jnp.tanh(mm(global_, a["wq"][i]))                  # (B, k)
        K = jnp.tanh(mm(local, a["wk"][i]))                    # (B, L, k)
        V = _gelu(mm(local, a["wv"][i]))                       # (B, L, v)
        scores = jnp.einsum("bk,blk->bl", q, K, precision=_HI) / math.sqrt(k)
        scores = jnp.where(pad_mask, scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        heads.append(jnp.einsum("bl,blv->bv", w, V, precision=_HI))
    attn = jnp.concatenate(heads, axis=-1)

    d1 = _gelu(mm(global_, p["global_dense1"]["kernel"])
               + p["global_dense1"]["bias"])
    global_ = _layer_norm(p["global_ln1"], global_ + d1 + attn)
    d2 = _gelu(mm(global_, p["global_dense2"]["kernel"])
               + p["global_dense2"]["bias"])
    global_ = _layer_norm(p["global_ln2"], global_ + d2)
    return local, global_


def encode(params, tokens, annotations, m, precision="f32"):
    """Trunk: (local (B, L, C), global (B, G)) representations."""
    pad_mask = tokens != PAD_ID
    local = params["embedding"]["embedding"][tokens]
    global_ = _gelu(_mm(annotations, params["global_in"]["kernel"], precision)
                    + params["global_in"]["bias"])
    # One block at a time, recomputed in the backward pass: the same
    # numbers as keeping everything, in a fraction of the memory.
    body = jax.checkpoint(partial(_block_apply, m=m, precision=precision))
    for i in range(m["num_blocks"]):
        blk = jax.tree.map(lambda x: x[i], params["blocks"])
        local, global_ = body(blk, local, global_, pad_mask)
    return local, global_


def logits(params, tokens, annotations, m, precision="f32"):
    local, global_ = encode(params, tokens, annotations, m, precision)
    ll = _mm(local, params["local_head"]["kernel"], precision) \
        + params["local_head"]["bias"]
    gl = _mm(global_, params["global_head"]["kernel"], precision) \
        + params["global_head"]["bias"]
    return ll, gl


@partial(jax.jit, static_argnames=("m_items", "precision"))
def _embed_rows(params, tokens, m_items, precision):
    m = dict(m_items)
    ann = jnp.zeros((tokens.shape[0], m["num_annotations"]), jnp.float32)
    local, global_ = encode(params, tokens, ann, m, precision)
    real = (tokens != PAD_ID).astype(jnp.float32)
    local_mean = (jnp.einsum("bl,blc->bc", real, local, precision=_HI)
                  / jnp.maximum(real.sum(-1, keepdims=True), 1.0))
    return {"global": global_, "local_mean": local_mean}


def embed_rows(params, tokens, m, precision="f32"):
    """What an `embed` request answers, for rows of one padded length,
    each sequence alone in its row with no annotations known."""
    return _embed_rows(params, tokens, tuple(sorted(m.items())), precision)


# ------------------------------------------------------------------ training

def corrupt(key, tokens, annotations, d):
    """The denoising corruption of one clean batch: (X, Y, W)."""
    k_tok, k_ann = jax.random.split(key)
    k_mask, k_draw = jax.random.split(k_tok)
    replace = jax.random.bernoulli(k_mask, d["token_randomize_prob"],
                                   tokens.shape) & (tokens >= N_SPECIAL)
    random_aa = jax.random.randint(k_draw, tokens.shape, N_SPECIAL, VOCAB,
                                   dtype=tokens.dtype)
    x_local = jnp.where(replace, random_aa, tokens)

    k_keep, k_drop, k_add = jax.random.split(k_ann, 3)
    keep = jax.random.bernoulli(k_keep, d["annotation_corrupt_prob"],
                                annotations.shape[:-1])[..., None]
    x = jnp.where(jax.random.bernoulli(k_drop, d["annotation_drop_prob"],
                                       annotations.shape), 0.0, annotations)
    x = jnp.where(jax.random.bernoulli(k_add, d["annotation_add_prob"],
                                       annotations.shape), 1.0, x)
    x_global = jnp.where(keep, x, 0.0)

    w_local = (tokens != PAD_ID).astype(jnp.float32)
    has_any = (annotations.sum(-1, keepdims=True) > 0).astype(jnp.float32)
    w_global = jnp.broadcast_to(has_any, annotations.shape)
    return ({"local": x_local, "global": x_global},
            {"local": tokens, "global": annotations},
            {"local": w_local, "global": w_global})


def _loss_sums(params, X, Y, W, m, precision):
    """Weighted sums of both loss terms over some rows of a batch."""
    ll, gl = logits(params, X["local"], X["global"], m, precision)
    logp = jax.nn.log_softmax(ll, axis=-1)
    ce = -jnp.take_along_axis(logp, Y["local"][..., None], axis=-1)[..., 0]
    y = Y["global"]
    bce = jnp.maximum(gl, 0) - gl * y + jnp.log1p(jnp.exp(-jnp.abs(gl)))
    return (ce * W["local"]).sum(), (bce * W["global"]).sum()


@partial(jax.jit, static_argnames=("m_items", "precision"))
def _rows_value_and_grad(params, X, Y, W, inv_local, inv_global, m_items,
                         precision):
    m = dict(m_items)

    def f(p):
        s_local, s_global = _loss_sums(p, X, Y, W, m, precision)
        return s_local * inv_local + s_global * inv_global

    return jax.value_and_grad(f)(params)


@jax.jit
def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def loss_and_grads(params, key, batch, m, d, precision="f32", rows=16,
                   operands="f32"):
    """Loss and gradients of one step on a clean batch, corrupted under
    `key`, computed `rows` rows at a time so that the float32 activations
    of a whole batch never have to exist. With `operands="bf16"` (the
    configuration states bfloat16 for the products) the weights enter
    the products rounded to bfloat16 and the gradient is taken there:
    the float32 parameters stay the master copy."""
    tokens = jnp.asarray(batch["tokens"])
    ann = jnp.asarray(batch["annotations"], jnp.float32)
    X, Y, W = jax.jit(partial(corrupt, d=d))(key, tokens, ann)
    inv_local = 1.0 / jnp.maximum(W["local"].sum(), 1.0)
    inv_global = 1.0 / jnp.maximum(W["global"].sum(), 1.0)
    run = params
    if precision == "bf16_params":
        run = jax.tree.map(_bf16, params)
    elif operands == "bf16":
        run = round_product_weights(params)
    arith = "int8" if precision == "int8" else "f32"
    loss, grads = 0.0, None
    for lo in range(0, tokens.shape[0], rows):
        sl = jax.tree.map(lambda a: a[lo:lo + rows], (X, Y, W))
        v, g = _rows_value_and_grad(run, *sl, inv_local, inv_global,
                                    tuple(sorted(m.items())), arith)
        loss = loss + v
        grads = g if grads is None else _tree_add(grads, g)
    return loss, grads


def learning_rate(count, o):
    """Linear warm-up from 0, then flat: the rate of update `count`."""
    return o["learning_rate"] * jnp.minimum(count / o["warmup_steps"], 1.0)


@partial(jax.jit, static_argnames=("o_items", "precision"))
def _adam_step(params, grads, mu, nu, count, o_items, precision):
    o = dict(o_items)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.where(gnorm < o["grad_clip_norm"], 1.0,
                     o["grad_clip_norm"] / gnorm)
    grads = jax.tree.map(lambda g: g * clip, grads)
    b1, b2 = o["b1"], o["b2"]
    mu = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, nu, grads)
    t = count + 1
    lr = learning_rate(count, o)

    def upd(p, a, b):
        step = lr * (a / (1 - b1 ** t)) / (jnp.sqrt(b / (1 - b2 ** t)) + 1e-8)
        new = p - step
        if precision == "bf16_params":
            new = _bf16(new)
        return new

    return jax.tree.map(upd, params, mu, nu), grads, mu, nu


@jax.jit
def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))), tree)


def follow_steps(seed, batches, m, d, o, precision="f32", rows=16,
                 operands="f32"):
    """Follow the first len(batches) optimizer steps from the seed.

    Returns what the comparison reads: each step's loss, the first
    gradient as Adam is handed it (after the clip) with the norm of
    every leaf, and the norm of every leaf's change after the last step.
    """
    k_init, key = train_keys(seed)
    params = jax.jit(partial(init_params, m=m))(k_init)
    if precision == "bf16_params":
        params = jax.tree.map(_bf16, params)
    start = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad, first_norms = [], None, None
    o_items = tuple(sorted(o.items()))
    for count, batch in enumerate(batches):
        key, step_key = jax.random.split(key)
        loss, grads = loss_and_grads(params, step_key, batch, m, d,
                                     precision, rows, operands)
        params, clipped, mu, nu = _adam_step(
            params, grads, mu, nu, jnp.float32(count), o_items, precision)
        losses.append(float(loss))
        if first_grad is None:
            first_norms = jax.device_get(leaf_norms(clipped))
            first_grad = jax.device_get(clipped)
    change = jax.device_get(leaf_norms(
        jax.tree.map(jnp.subtract, params, start)))
    return {"losses": losses, "first_grad": first_grad,
            "first_grad_norms": first_norms,
            "change_norms": change}
