"""GLM-4.7-Flash (`glm4_moe_lite`) on the training path in plain
`jax.numpy`, float32, products at "highest": one chip's share of an
expert-parallel layer group.

The plain reference of the `glm-4.7-flash-ep8` configuration: weights
from a seed, the forward pass (latent attention, dense and expert layers,
the multi-token-prediction module), the loss, its gradients, clip + Adam
and the balance-bias update, written from the published config.json
(`benchmark/configs/glm-4.7-flash-ep8.json`, key `published`) and the
layer equations of ISSUE 28 / PERF.md section 4. It imports nothing of
`proteinbert_tpu`. No kernels, no grouped products: every held expert
runs over every token and its result is masked by the routing.

Equations (c: the configuration as a dict, published key names):
  norms          RMSNorm, eps c.rms_norm_eps; x + Attn(N(x)); x + FFN(N(x))
  attention      c_q = N(x W_qa); q = c_q W_qb -> per head [q_nope, q_rope]
                 [c_kv, k_rope] = x W_kva; c_kv = N(c_kv);
                 [k_nope, v] = c_kv W_kvb per head; rotary (theta, all rope
                 dims, half-split pairs) on q_rope and the one shared k_rope,
                 positions restarting at each segment;
                 scores / sqrt(nope + rope), causal AND same segment
  dense layer    W_down(silu(W_gate x) * W_up x)
  expert layer   s = sigmoid(x W_r) (float32 always); top-k of s + b;
                 w = s[chosen] / (sum + 1e-20) * routed_scaling_factor;
                 sum over chosen experts HELD HERE of w * Expert_e(x)
                 + Shared(x). A pad token is routed nowhere.
  after a step   b += gamma * sign(mean load - load_e), loads over all experts
  module         h' = W_eh [N(Emb(t_{i+1})) ; N(h_i)], one expert layer,
                 the shared final norm and head, target t_{i+2}
  loss           CE(main, t_{i+1}) + lambda * CE(module, t_{i+2}), means over
                 the targets inside the same segment

`precision`: "f32" is the reference; "int8" the control one step below
bfloat16 products (every product with a weight matrix takes int8 weights,
one scale per output channel, and int8 activations, one scale per row;
the router stays float32, as in the program); "bf16_params" the control
one step below float32 parameters. `operands="bf16"`: the weights enter
the products rounded to bfloat16 (the router's not), all else float32.

The master parameters and the optimizer's moments live on the host
(updated in place, block by block: `benchmark/blocked.py`), so that the
device holds one rounded copy of the weights and the gradient summed
over the rows so far (5.7 GB at 706.5 M parameters) beside one row's
activations and what of its gradient is not yet added; rows go one at a time, queries in blocks,
the head's logits in chunks, each layer, each held expert and each block
recomputed in the backward pass; the summed gradient comes back to the
host once a step.
"""

from __future__ import annotations

import resource
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import blocked

_HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
HEAD_CHUNK = 2048


class Laps:
    """A line a phase: its seconds, the process's peak on the host, the
    device's memory in use (`tools/process_wall.py` stamps every line of a
    run with the process's clock; PERF.md section 5 has the table)."""

    def __init__(self):
        self.at = time.perf_counter()

    def __call__(self, what):
        now = time.perf_counter()
        held = jax.local_devices()[0].memory_stats() or {}
        print(f"reference: {what} {now - self.at:.2f} s (host peak "
              f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20:.2f} GiB, "
              f"on the device {held.get('bytes_in_use', 0) / 2 ** 30:.2f} GiB, its peak "
              f"{held.get('peak_bytes_in_use', 0) / 2 ** 30:.2f})", flush=True)
        self.at = time.perf_counter()


# ------------------------------------------------------------------ weights

def shapes(c: dict) -> dict:
    D, H = c["hidden_size"], c["num_attention_heads"]
    E, F, R = c["experts_held"], c["moe_intermediate_size"], c["n_routed_experts"]
    nope, rope, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense

    def attn(n):
        return {"q_a": n + (D, c["q_lora_rank"]), "q_norm": n + (c["q_lora_rank"],),
                "q_b": n + (c["q_lora_rank"], H * (nope + rope)),
                "kv_a": n + (D, c["kv_lora_rank"] + rope),
                "kv_norm": n + (c["kv_lora_rank"],),
                "kv_b": n + (c["kv_lora_rank"], H * (nope + dv)),
                "o": n + (H * dv, D)}

    def ffn(n, width):
        return {"gate": n + (D, width), "up": n + (D, width),
                "down": n + (width, D)}

    def expert_layer(n):
        return {"attn": attn(n), "norm1": n + (D,), "norm2": n + (D,),
                "moe": {"router": n + (D, R),
                        "experts": {"gate": n + (E, D, F), "up": n + (E, D, F),
                                    "down": n + (E, F, D)}},
                "shared": ffn(n, c["n_shared_experts"] * F)}

    tree = {
        "embed": (c["vocab_size"], D), "head": (D, c["vocab_size"]),
        "final_norm": (D,),
        "dense": {"attn": attn((n_dense,)), "norm1": (n_dense, D),
                  "norm2": (n_dense, D),
                  "mlp": ffn((n_dense,), c["intermediate_size"])},
        "layers": expert_layer((n_moe,)),
        "balance_bias": {"layers": (n_moe, R)},
    }
    if c["num_nextn_predict_layers"]:
        tree["mtp"] = {"enorm": (D,), "hnorm": (D,), "eh_proj": (2 * D, D),
                       "layer": expert_layer(())}
        tree["balance_bias"]["mtp"] = (R,)
    return tree


def _is_shape(s):
    return isinstance(s, tuple)


def init_params(key, c: dict):
    """(parameters, balance bias). Leaf number i of the whole tree, in
    the order of its sorted keys, is init_std * normal(fold_in(key, i));
    a norm's scale is 1 and the balance bias 0."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes(c), is_leaf=_is_shape)
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = "/".join(str(k.key) for k in path)
        if "balance_bias" in name:
            leaves.append(jnp.zeros(shape, jnp.float32))
        elif "norm" in name:
            leaves.append(jnp.ones(shape, jnp.float32))
        else:
            leaves.append(c["init_std"] * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32))
    params = jax.tree.unflatten(treedef, leaves)
    bias = params.pop("balance_bias")
    return params, bias


def seed_key(seed: int):
    """One PRNG key from any whole-number seed (they pass 2**31)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _bf16(x):
    """Round to bfloat16 and back (`reduce_precision`: the TPU's compiler
    may drop a pair of casts as excess precision)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@partial(jax.jit, donate_argnums=0)
def round_product_weights(params):
    """The weights as the configuration's bfloat16 products take them:
    every matrix but the router's; norms stay float32."""
    def one(path, x):
        name = "/".join(str(k.key) for k in path)
        return x if ("norm" in name or "router" in name) else _bf16(x)

    return jax.tree_util.tree_map_with_path(one, params)


# --------------------------------------------------------------- arithmetic

def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, precision):
    if precision == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    return jnp.matmul(x, w, precision=_HI)


def _rms(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _ffn(p, x, precision):
    return _mm(_silu(_mm(x, p["gate"], precision)) * _mm(x, p["up"], precision),
               p["down"], precision)


def positions_in_segment(seg):
    """(L,) position of each token in its own segment."""
    idx = jnp.arange(seg.shape[0])
    start = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    return idx - jax.lax.cummax(jnp.where(start, idx, 0), axis=0)


def _rotary(x, pos, theta):
    """x: (L, ..., d): dimension j turns with dimension j + d/2 by the
    angle pos * theta ** (-2j / d)."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * freq
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _attention(p, x, seg, pos, c, precision):
    """x: (L, D) of ONE row."""
    L = x.shape[0]
    H = c["num_attention_heads"]
    nope, rope, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    rank, eps = c["kv_lora_rank"], c["rms_norm_eps"]
    c_q = _rms(p["q_norm"], _mm(x, p["q_a"], precision), eps)
    q = _mm(c_q, p["q_b"], precision).reshape(L, H, nope + rope)
    kv = _mm(x, p["kv_a"], precision)
    c_kv = _rms(p["kv_norm"], kv[:, :rank], eps)
    k_rope = _rotary(kv[:, rank:], pos, c["rope_theta"])            # (L, rope)
    up = _mm(c_kv, p["kv_b"], precision).reshape(L, H, nope + dv)
    q_nope, q_rope = q[..., :nope], _rotary(q[..., nope:], pos, c["rope_theta"])
    k_nope, v = up[..., :nope], up[..., nope:]

    @jax.checkpoint
    def block(args):
        qn, qr, seg_q, at = args
        scores = (jnp.einsum("qhd,khd->hqk", qn, k_nope, precision=_HI)
                  + jnp.einsum("qhd,kd->hqk", qr, k_rope, precision=_HI))
        scores = scores / jnp.sqrt(jnp.float32(nope + rope))
        allowed = ((jnp.arange(L)[None, :] <= at[:, None])
                   & (seg_q[:, None] == seg[None, :]))
        probs = jax.nn.softmax(jnp.where(allowed[None], scores, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=_HI)

    # Blocks of queries one after another (a loop, not an unrolled
    # program: the compiler would hold many blocks' scores at once), each
    # against ALL the keys, the mask doing what causality asks.
    n = min(QUERY_BLOCK, L)
    assert L % n == 0, (L, n)
    blocks = lambda a: a.reshape((L // n, n) + a.shape[1:])  # noqa: E731
    out = jax.lax.map(block, (blocks(q_nope), blocks(q_rope), blocks(seg),
                              blocks(jnp.arange(L))))
    out = out.reshape(L, H * dv)
    return _mm(out, p["o"], precision)


def _routed(p, bias, x, real, c, precision):
    """-> (sum over held chosen experts of weight * Expert_e(x), the
    loads of all experts over the real tokens, the chosen ids)."""
    k, R = c["num_experts_per_tok"], c["n_routed_experts"]
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision=_HI))
    ids = jnp.argsort(-(scores + bias), axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if c["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * c["routed_scaling_factor"]
    ids = jnp.where(real[:, None], ids, R)
    y = jnp.zeros_like(x)
    one_expert = jax.checkpoint(partial(_ffn, precision=precision))
    for e in range(c["experts_held"]):
        mine = (ids == c["expert_offset"] + e)                      # (L, k)
        expert = jax.tree.map(lambda a: a[e], p["experts"])
        y = y + (w * mine).sum(-1, keepdims=True) * one_expert(expert, x)
    load = (ids[..., None] == jnp.arange(R)).sum((0, 1))
    return y, load, ids


def _expert_layer(p, bias, x, seg, pos, c, precision):
    x = x + _attention(p["attn"], _rms(p["norm1"], x, c["rms_norm_eps"]),
                       seg, pos, c, precision)
    h = _rms(p["norm2"], x, c["rms_norm_eps"])
    routed, load, ids = _routed(p["moe"], bias, h, seg > 0, c, precision)
    return x + routed + _ffn(p["shared"], h, precision), load, ids


def _dense_layer(p, x, seg, pos, c, precision):
    x = x + _attention(p["attn"], _rms(p["norm1"], x, c["rms_norm_eps"]),
                       seg, pos, c, precision)
    return x + _ffn(p["mlp"], _rms(p["norm2"], x, c["rms_norm_eps"]), precision)


def _head_sum(params, h, targets, valid, c, precision):
    """Sum of the cross-entropy over the valid positions."""
    @jax.checkpoint
    def chunk(args):
        hc, tc, vc = args
        logits = _mm(_rms(params["final_norm"], hc, c["rms_norm_eps"]),
                     params["head"], precision)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, tc[:, None], axis=-1)[:, 0]
        return jnp.where(vc, ce, 0.0).sum()

    L = h.shape[0]
    n = min(HEAD_CHUNK, L)
    assert L % n == 0, (L, n)
    chunks = lambda a: a.reshape((L // n, n) + a.shape[1:])  # noqa: E731
    return jax.lax.map(chunk, (chunks(h), chunks(targets), chunks(valid))).sum()


def _shift(a, k):
    return jnp.concatenate([a[k:], jnp.zeros((k,), a.dtype)])


def row_sums(params, bias, tokens, seg, c, precision="f32"):
    """One row; `params["dense"]` and `params["layers"]` are LISTS of
    layers here (`unstacked`). -> (sum of the main head's cross-entropy,
    of the module's, the number of targets of each, loads (layers +
    module, R), ids (layers + module, L, k))."""
    pos = positions_in_segment(seg)
    real = seg > 0
    x = params["embed"][tokens]
    for p in params["dense"]:
        x = jax.checkpoint(partial(_dense_layer, c=c, precision=precision))(
            p, x, seg, pos)
    loads, ids = [], []
    layer = jax.checkpoint(partial(_expert_layer, c=c, precision=precision))
    for i, p in enumerate(params["layers"]):
        x, load, chosen = layer(p, bias["layers"][i], x, seg, pos)
        loads.append(load)
        ids.append(chosen)
    next_tok, next_ok = _shift(tokens, 1), real & (_shift(seg, 1) == seg)
    main = _head_sum(params, x, next_tok, next_ok, c, precision)
    module, after_ok = 0.0, jnp.zeros_like(real)
    if c["num_nextn_predict_layers"]:
        m, eps = params["mtp"], c["rms_norm_eps"]
        joined = jnp.concatenate([_rms(m["enorm"], params["embed"][next_tok], eps),
                                  _rms(m["hnorm"], x, eps)], axis=-1)
        h2, load, chosen = layer(m["layer"], bias["mtp"],
                                 _mm(joined, m["eh_proj"], precision), seg, pos)
        loads.append(load)
        ids.append(chosen)
        after_ok = real & (_shift(seg, 2) == seg)
        module = _head_sum(params, h2, _shift(tokens, 2), after_ok, c, precision)
    return main, module, next_ok.sum(), after_ok.sum(), jnp.stack(loads), jnp.stack(ids)


# ------------------------------------------------------------------ training

def _hashable(c: dict):
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, bool, str))))


def unstacked(params):
    """The two stacks of layers as lists of layers. The gradient is taken
    with respect to the layers one by one: through a slice of the stack,
    every layer's gradient would be a whole zero-padded stack."""
    def layers(stack):
        n = jax.tree.leaves(stack)[0].shape[0]
        return [jax.tree.map(lambda a: a[i], stack) for i in range(n)]

    return dict(params, dense=layers(params["dense"]), layers=layers(params["layers"]))


def restacked(params):
    stack = lambda layers: jax.tree.map(lambda *xs: jnp.stack(xs), *layers)  # noqa: E731
    return dict(params, dense=stack(params["dense"]), layers=stack(params["layers"]))


@partial(jax.jit, static_argnames=("c_items", "precision"), donate_argnames="acc")
def _row_value_and_grad(params, bias, tokens, seg, inv_main, inv_module, acc,
                        c_items, precision):
    """One row's share of the loss, and `acc` (donated) with the row's
    gradient added to it: the sum over the rows stays on the device."""
    c = dict(c_items)

    def f(p):
        main, module, _, _, loads, ids = row_sums(p, bias, tokens, seg, c, precision)
        return (main * inv_main + c["mtp_loss_weight"] * module * inv_module,
                (loads, ids))

    value, grads = jax.value_and_grad(f, has_aux=True)(unstacked(params))
    return value, jax.tree.map(jnp.add, acc, restacked(grads))


def target_counts(tokens, seg):
    """How many main and module targets a batch has (host, numpy)."""
    seg = np.asarray(seg)
    real = seg > 0
    pad1 = np.pad(seg[:, 1:], ((0, 0), (0, 1)))
    pad2 = np.pad(seg[:, 2:], ((0, 0), (0, 2)))
    return int((real & (pad1 == seg)).sum()), int((real & (pad2 == seg)).sum())


def fetch(tree):
    """Writable numpy copies, in C order, of a tree on the device, whose
    leaves are freed one by one as they arrive (a few transfers ahead of
    the copy). `order="C"`: the TPU hands some leaves back with their
    dimensions in another order in memory (the stacked experts'), and a
    flat view of such a leaf, as `blocked` takes, would be a copy."""
    leaves, treedef = jax.tree.flatten(tree)
    out = []
    for i, x in enumerate(leaves):
        for ahead in leaves[i:i + 3]:
            ahead.copy_to_host_async()
        out.append(np.array(x, order="C"))
        x.delete()
    return jax.tree.unflatten(treedef, out)


def loss_and_grads(params, bias, batch, c, precision="f32", operands="f32",
                   lap=lambda what: None):
    """Loss and gradients of one step on a packed batch, a row at a
    time; `params` and `bias` come from the host and the gradients go
    back to it, summed over the rows on the device and fetched once.
    -> (loss, gradients, loads, ids (rows, layers, L, k))."""
    tokens, seg = np.asarray(batch["tokens"]), np.asarray(batch["segment_ids"])
    n_main, n_module = target_counts(tokens, seg)
    run, bias = jax.device_put(params), jax.device_put(bias)
    if precision == "bf16_params":
        run = jax.jit(lambda t: jax.tree.map(_bf16, t), donate_argnums=0)(run)
    elif operands == "bf16":
        run = round_product_weights(run)    # the unrounded copy is dropped
    arith = "int8" if precision == "int8" else "f32"
    jax.block_until_ready(run)
    lap("weights to the device and rounded")
    # Every row is dispatched before any of its results is waited for; a
    # row's program adds its gradient into the sum it is handed (12.1 GiB
    # at the peak at 706.5 M parameters and 8,192 tokens, by the v5e
    # compiler's count: the sum apart from the program would take 13.6).
    acc, rows = jax.tree.map(jnp.zeros_like, run), []
    for r in range(tokens.shape[0]):
        (v, (load, chosen)), acc = _row_value_and_grad(
            run, bias, jnp.asarray(tokens[r]), jnp.asarray(seg[r]),
            1.0 / max(n_main, 1), 1.0 / max(n_module, 1), acc,
            c_items=_hashable(c), precision=arith)
        rows.append((v, load, chosen))
    del run
    jax.block_until_ready(acc)
    lap(f"{len(rows)} rows forward and backward, summed on the device")
    grads = fetch(acc)
    loss = 0.0
    for v, _, _ in rows:
        loss = loss + float(v)
    loads = sum(np.asarray(load) for _, load, _ in rows)
    ids = np.stack([np.asarray(chosen) for _, _, chosen in rows])
    lap("gradient fetched")
    return loss, grads, loads, ids


def learning_rate(count, o):
    return o["learning_rate"] * min(count / o["warmup_steps"], 1.0)


def adam_step(params, grads, mu, nu, count, o, precision="f32"):
    """Clip by the global norm, then Adam: numpy, on the host, IN PLACE
    (706.5 M parameters: a second copy of the four trees would not fit
    beside the first), block by block through a thread's own scratch,
    the pieces of all leaves over `blocked`'s pool. `grads` becomes the
    clipped gradient, `params`, `mu`, `nu` their next values. Every
    element sees float32 operations in this order:
        g *= clip; a = a * b1 + (1 - b1) * g; b = b * b2 + ((1 - b2) * g) * g;
        p -= (lr * (a / c1)) / (sqrt(b / c2) + 1e-8)"""
    gnorm = float(np.sqrt(sum(blocked.sq_sums(grads))))
    clip = np.float32(1.0 if gnorm < o["grad_clip_norm"]
                      else o["grad_clip_norm"] / gnorm)
    b1, b2 = np.float32(o["b1"]), np.float32(o["b2"])
    t = count + 1
    lr = np.float32(learning_rate(count, o))
    c1, c2 = np.float32(1 - o["b1"] ** t), np.float32(1 - o["b2"] ** t)
    eps, rest1, rest2 = np.float32(1e-8), 1 - b1, 1 - b2

    def piece(p, g, a, b, lo, hi):
        s, u = blocked.scratch(np.float32), blocked.scratch(np.float32, 1)
        for at in range(lo, hi, blocked.BLOCK):
            n = min(blocked.BLOCK, hi - at)
            p_, g_, a_, b_ = (x[at:at + n] for x in (p, g, a, b))
            s_, u_ = s[:n], u[:n]
            np.multiply(g_, clip, out=g_)
            np.multiply(a_, b1, out=a_)
            np.multiply(g_, rest1, out=s_)
            np.add(a_, s_, out=a_)
            np.multiply(b_, b2, out=b_)
            np.multiply(g_, rest2, out=s_)
            np.multiply(s_, g_, out=s_)
            np.add(b_, s_, out=b_)
            np.divide(a_, c1, out=s_)
            np.multiply(s_, lr, out=s_)
            np.divide(b_, c2, out=u_)
            np.sqrt(u_, out=u_)
            np.add(u_, eps, out=u_)
            np.divide(s_, u_, out=s_)
            np.subtract(p_, s_, out=p_)

    blocked.over_pieces(piece, params, grads, mu, nu, in_place=True)
    if precision == "bf16_params":
        for p in jax.tree.leaves(params):
            p[...] = np.asarray(_bf16(jnp.asarray(p)))


def update_bias(bias, loads, c):
    """loads: (layers + module, R) over the whole batch."""
    loads = np.asarray(loads, np.float32)
    moved = lambda b, l: b + np.float32(c["bias_update_speed"]) * np.sign(  # noqa: E731
        l.mean(-1, keepdims=True) - l)
    n = bias["layers"].shape[0]
    new = {"layers": moved(bias["layers"], loads[:n])}
    if "mtp" in bias:
        new["mtp"] = moved(bias["mtp"], loads[n])
    return new


host_norms = blocked.norms


def follow_steps(seed, batches, c, o, precision="f32", operands="f32"):
    """Follow the first len(batches) optimizer steps from the seed.
    Returns what the comparison reads: each step's loss, the first
    gradient as Adam is handed it (after the clip) with every leaf's
    norm, every leaf's change after the last step, the first step's
    chosen experts (rows, layers + module, L, k) and the balance bias
    after the last step."""
    k_init, _ = jax.random.split(seed_key(seed))

    def first_weights():
        params, bias = jax.jit(partial(init_params, c=c))(k_init)
        if precision == "bf16_params":
            params = jax.tree.map(_bf16, params)
        return fetch(params), jax.device_get(bias)

    lap = Laps()
    params, bias = first_weights()
    lap("first weights made and fetched")
    mu = jax.tree.map(np.zeros_like, params)
    nu = jax.tree.map(np.zeros_like, params)
    losses, first_grad, first_ids = [], None, None
    for count, batch in enumerate(batches):
        loss, grads, loads, ids = loss_and_grads(
            params, bias, batch, c, precision, operands, lap)
        adam_step(params, grads, mu, nu, count, o, precision)
        lap(f"step {count} clip and Adam")
        bias = update_bias(bias, loads, c)
        losses.append(loss)
        if first_grad is None:
            first_grad, first_ids = grads, ids      # the clipped gradient
        del grads
    del mu, nu
    # the change against the first weights, made again from the seed rather
    # than kept through the steps: 2.83 GB less at the host's peak for ~4 s
    change = blocked.norms(params, minus=first_weights()[0])
    lap("first weights made again, change norms")
    norms = host_norms(first_grad)
    lap("first gradient's norms")
    return {"losses": losses, "first_grad": first_grad,
            "first_grad_norms": norms,
            "change_norms": change, "first_ids": first_ids, "bias": bias}
