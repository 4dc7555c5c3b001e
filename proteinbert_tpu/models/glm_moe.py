"""Causal expert decoder (GLM-4.7-Flash, `glm4_moe_lite`) on the training
path: latent attention, one leading dense layer then expert layers with
a shared expert and a bias-balanced sigmoid router, and one
multi-token-prediction module. The second model beside
`models/proteinbert.py`; `train/train_state.py` picks between them by
the type of `cfg.model` (`configs.DecoderConfig`).

Every layer is `x + Attn(RMSNorm(x))` then `x + FFN(RMSNorm(x))`.

Latent attention, training form (no absorbed products, no cache):
    c_q = RMSNorm(x W_qa);  q = c_q W_qb -> per head [q_nope, q_rope]
    [c_kv, k_rope] = x W_kva;  c_kv = RMSNorm(c_kv)
    [k_nope, v] = c_kv W_kvb per head;  rotary on q_rope and on k_rope
    (one k_rope, shared by all heads), positions restarting at each
    segment of a packed row
    scores = (q_nope . k_nope + q_rope . k_rope) / sqrt(qk_head_dim),
    causal AND inside the segment;  out = concat_heads(P v) W_o
FFN: layer 0 is SwiGLU of width `intermediate_size`; an expert layer is
`ops/moe.py`'s routed part (the experts this chip holds) + one shared
SwiGLU expert that every token takes.

Prediction module (depth 1): h' = W_eh [RMSNorm(Emb(t_{i+1})) ;
RMSNorm(h_i)], one expert layer, then the main model's final norm and
head; it predicts t_{i+2}. Loss = CE(main, t_{i+1}) + lambda CE(module,
t_{i+2}), each a mean over the targets that lie in the same segment.

The expert layers are stacked on a leading axis and driven by `lax.scan`
with each layer recomputed in the backward pass (only its input is
kept). The head's loss walks chunks of positions so that the logits
(positions x vocabulary slice, float32) never exist whole.

The router's balance bias is in the parameter tree
(`params["balance_bias"]`) so that it is sharded, saved and restored
with everything else, but no gradient reaches it (`stop_gradient`, so
Adam's update of it is exactly zero): `train_step` moves it after the
optimizer, by `update_balance_bias` from the step's expert loads.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from proteinbert_tpu.configs import DecoderConfig
from proteinbert_tpu.ops.attention import (
    causal_segment_attention, flash_segment_attention, flash_tiles_fit,
    tiles_walked_share,
)
from proteinbert_tpu.ops.layers import (
    rms_norm_apply, rotary_apply, segment_positions, swiglu_apply,
)
from proteinbert_tpu.ops.moe import moe_apply

Params = Dict[str, Any]


# ----------------------------------------------------------------- init

def param_shapes(cfg: DecoderConfig) -> Dict[str, Any]:
    """The parameter tree as shapes. A leaf named `*norm*` starts at 1,
    `balance_bias` at 0, every other leaf is normal(0, init_std)."""
    D, H = cfg.hidden_size, cfg.num_attention_heads
    E, F = cfg.experts_held, cfg.moe_intermediate_size
    attn = {
        "q_a": (D, cfg.q_lora_rank), "q_norm": (cfg.q_lora_rank,),
        "q_b": (cfg.q_lora_rank, H * cfg.qk_head_dim),
        "kv_a": (D, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "kv_norm": (cfg.kv_lora_rank,),
        "kv_b": (cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "o": (H * cfg.v_head_dim, D),
    }
    swiglu = lambda width: {"gate": (D, width), "up": (D, width),  # noqa: E731
                            "down": (width, D)}
    expert_layer = {
        "attn": attn, "norm1": (D,), "norm2": (D,),
        "moe": {"router": (D, cfg.n_routed_experts),
                "experts": {"gate": (E, D, F), "up": (E, D, F), "down": (E, F, D)}},
        "shared": swiglu(cfg.n_shared_experts * F),
    }
    stack = lambda tree, n: jax.tree.map(  # noqa: E731
        lambda s: (n,) + s, tree, is_leaf=lambda s: isinstance(s, tuple))
    shapes = {
        "embed": (cfg.vocab_size, D), "head": (D, cfg.vocab_size),
        "final_norm": (D,),
        "dense": stack({"attn": attn, "norm1": (D,), "norm2": (D,),
                        "mlp": swiglu(cfg.intermediate_size)},
                       cfg.first_k_dense_replace),
        "layers": stack(expert_layer, cfg.num_moe_layers),
        "balance_bias": {"layers": (cfg.num_moe_layers, cfg.n_routed_experts)},
    }
    if cfg.num_nextn_predict_layers:
        shapes["mtp"] = {"enorm": (D,), "hnorm": (D,), "eh_proj": (2 * D, D),
                         "layer": expert_layer}
        shapes["balance_bias"]["mtp"] = (cfg.n_routed_experts,)
    return shapes


def _leaf_paths(shapes) -> list:
    flat, _ = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    return [("/".join(str(k.key) for k in path), shape) for path, shape in flat]


def init(key: jax.Array, cfg: DecoderConfig) -> Params:
    """Leaf number i of the tree (keys sorted, the order `jax.tree` walks)
    is drawn from `fold_in(key, i)`: a recipe a reference can follow
    without this module."""
    shapes = param_shapes(cfg)
    leaves = []
    for i, (name, shape) in enumerate(_leaf_paths(shapes)):
        if "balance_bias" in name:
            leaves.append(jnp.zeros(shape, jnp.float32))
        elif "norm" in name:
            leaves.append(jnp.ones(shape, jnp.float32))
        else:
            leaves.append(cfg.init_std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32))
    treedef = jax.tree.structure(shapes, is_leaf=lambda s: isinstance(s, tuple))
    return jax.tree.unflatten(treedef, leaves)


def param_count(cfg: DecoderConfig) -> int:
    """Trained parameters (the balance bias is not one)."""
    total = 0
    for name, shape in _leaf_paths(param_shapes(cfg)):
        if "balance_bias" not in name:
            n = 1
            for s in shape:
                n *= s
            total += n
    return total


# -------------------------------------------------------------- forward

def latent_attention(p: Params, x, segment_ids, positions, cfg: DecoderConfig):
    with jax.named_scope("mla"):
        B, L, _ = x.shape
        H, dt = cfg.num_attention_heads, x.dtype
        nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        c_q = rms_norm_apply(p["q_norm"], x @ p["q_a"].astype(dt), cfg.rms_norm_eps)
        q = (c_q @ p["q_b"].astype(dt)).reshape(B, L, H, nope + rope)
        kv = x @ p["kv_a"].astype(dt)
        c_kv = rms_norm_apply(p["kv_norm"], kv[..., :cfg.kv_lora_rank],
                              cfg.rms_norm_eps)
        k_rope = rotary_apply(kv[..., cfg.kv_lora_rank:], positions, cfg.rope_theta)
        kv_up = (c_kv @ p["kv_b"].astype(dt)).reshape(B, L, H, nope + dv)
        q = jnp.concatenate(
            [q[..., :nope], rotary_apply(q[..., nope:], positions, cfg.rope_theta)],
            axis=-1)
        k = jnp.concatenate(
            [kv_up[..., :nope],
             jnp.broadcast_to(k_rope[:, :, None, :], (B, L, H, rope))], axis=-1)
        # The core by the platform the program is LOWERED for: the Pallas
        # flash kernel on a TPU (plain-jax attention does not fit the chip
        # at the published sizes), blocks of queries in plain jax elsewhere.
        # Sizes the kernel's tiles do not take (the CPU tests' widths) run
        # in plain jax where there is no TPU; on one they are an error
        # that names them, not a path that is slow or does not fit.
        sizes = dict(scale=float(nope + rope) ** -0.5, block=cfg.attention_block)
        plain = partial(causal_segment_attention, **sizes)
        with jax.named_scope("mla_core"):
            if (flash_tiles_fit(L, cfg.attention_block, nope + rope, dv)
                    or jax.default_backend() == "tpu"):
                out = lax.platform_dependent(
                    q, k, kv_up[..., nope:], segment_ids,
                    tpu=partial(flash_segment_attention, **sizes), default=plain)
            else:
                out = plain(q, k, kv_up[..., nope:], segment_ids)
        return out.reshape(B, L, H * dv) @ p["o"].astype(dt)


def dense_layer(p: Params, x, segment_ids, positions, cfg: DecoderConfig):
    x = x + latent_attention(
        p["attn"], rms_norm_apply(p["norm1"], x, cfg.rms_norm_eps),
        segment_ids, positions, cfg)
    with jax.named_scope("dense_mlp"):
        return x + swiglu_apply(
            p["mlp"], rms_norm_apply(p["norm2"], x, cfg.rms_norm_eps))


def expert_layer(p: Params, bias, x, segment_ids, positions, cfg: DecoderConfig):
    """-> (x, stats of `ops/moe.moe_apply`)."""
    x = x + latent_attention(
        p["attn"], rms_norm_apply(p["norm1"], x, cfg.rms_norm_eps),
        segment_ids, positions, cfg)
    h = rms_norm_apply(p["norm2"], x, cfg.rms_norm_eps)
    B, L, D = h.shape
    routed, stats = moe_apply(p["moe"], bias, h.reshape(B * L, D),
                              segment_ids.reshape(B * L) > 0, cfg)
    with jax.named_scope("shared_expert"):
        shared = swiglu_apply(p["shared"], h)
    return x + routed.reshape(B, L, D) + shared, stats


def trunk(params: Params, tokens, segment_ids, positions, cfg: DecoderConfig):
    """-> (h (B, L, D) before the final norm, the expert layers' stats
    stacked on a leading axis)."""
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(dt)

    def dense_body(x, p):
        return dense_layer(p, x, segment_ids, positions, cfg), None

    def expert_body(x, layer):
        p, bias = layer
        x, stats = expert_layer(p, bias, x, segment_ids, positions, cfg)
        return x, stats

    x, _ = lax.scan(jax.checkpoint(dense_body), x, params["dense"])
    return lax.scan(jax.checkpoint(expert_body), x,
                    (params["layers"], params["balance_bias"]["layers"]))


def head_loss(params: Params, h, targets, valid, cfg: DecoderConfig):
    """Sum over the valid positions of the cross-entropy of
    `head(final_norm(h))` against `targets`, and how many were right;
    positions go through the head `loss_chunk` at a time, each chunk's
    logits recomputed in the backward pass."""
    with jax.named_scope("lm_head"):
        D = h.shape[-1]
        n = h.shape[0] * h.shape[1]
        chunk = min(cfg.loss_chunk, n)
        pad = -n % chunk
        flat = lambda a: jnp.pad(  # noqa: E731
            a.reshape((n,) + a.shape[2:]), [(0, pad)] + [(0, 0)] * (a.ndim - 2)
        ).reshape((-1, chunk) + a.shape[2:])
        norm, head = params["final_norm"], params["head"]

        @jax.checkpoint
        def body(carry, xs):
            hc, tc, vc = xs
            hc = rms_norm_apply(norm, hc, cfg.rms_norm_eps)
            logits = jnp.dot(hc, head.astype(hc.dtype),
                             preferred_element_type=jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
            right = (jnp.argmax(logits, axis=-1) == tc) & vc
            return (carry[0] + jnp.sum(jnp.where(vc, lse - picked, 0.0)),
                    carry[1] + right.sum()), None

        (total, right), _ = lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
            (flat(h.reshape(h.shape[0], h.shape[1], D)), flat(targets), flat(valid)))
        return total, right


def _shift(a, k: int):
    """a[:, i + k] at column i, zeros past the row's end."""
    return jnp.pad(a[:, k:], [(0, 0), (0, k)] + [(0, 0)] * (a.ndim - 2))


def loss_and_stats(params: Params, tokens, segment_ids, cfg: DecoderConfig):
    """The training loss of one packed batch and what the step reports.
    tokens, segment_ids: (B, L) int32; segment 0 is padding."""
    real = segment_ids > 0
    positions = segment_positions(segment_ids)
    h, stats = trunk(params, tokens, segment_ids, positions, cfg)
    next_tok, next_valid = _shift(tokens, 1), real & (_shift(segment_ids, 1) == segment_ids)
    main_sum, main_right = head_loss(params, h, next_tok, next_valid, cfg)
    n_main = jnp.maximum(next_valid.sum(), 1)
    main = main_sum / n_main
    out = {"loss": main, "main_loss": main, "main_acc": main_right / n_main}
    counters = {"load": stats["load"], "held_counts": stats["held_counts"],
                "dropped": stats["dropped"].sum(), "ids": stats["ids"]}
    if cfg.num_nextn_predict_layers:
        with jax.named_scope("mtp"):
            m, dt = params["mtp"], h.dtype
            emb = jnp.take(params["embed"], next_tok, axis=0).astype(dt)
            joined = jnp.concatenate(
                [rms_norm_apply(m["enorm"], emb, cfg.rms_norm_eps),
                 rms_norm_apply(m["hnorm"], h, cfg.rms_norm_eps)], axis=-1)
            body = jax.checkpoint(lambda x, p, b: expert_layer(
                p, b, x, segment_ids, positions, cfg))
            h2, mtp_stats = body(joined @ m["eh_proj"].astype(dt), m["layer"],
                                 params["balance_bias"]["mtp"])
            after_valid = real & (_shift(segment_ids, 2) == segment_ids)
            mtp_sum, mtp_right = head_loss(
                params, h2, _shift(tokens, 2), after_valid, cfg)
        n_mtp = jnp.maximum(after_valid.sum(), 1)
        out.update(mtp_loss=mtp_sum / n_mtp, mtp_acc=mtp_right / n_mtp,
                   loss=main + cfg.mtp_loss_weight * mtp_sum / n_mtp)
        counters["mtp_load"] = mtp_stats["load"]
        counters["held_counts"] = jnp.concatenate(
            [counters["held_counts"], mtp_stats["held_counts"][None]])
        counters["dropped"] = counters["dropped"] + mtp_stats["dropped"]
        counters["ids"] = jnp.concatenate([counters["ids"], mtp_stats["ids"][None]])
    return out["loss"], (out, counters)


def update_balance_bias(bias: Params, counters, cfg: DecoderConfig) -> Params:
    """b += gamma * sign(mean load - load_e), per expert layer, from the
    step's loads over ALL experts: an overloaded expert's bias falls."""
    def moved(b, load):
        load = load.astype(jnp.float32)
        mean = load.mean(axis=-1, keepdims=True)
        return b + cfg.bias_update_speed * jnp.sign(mean - load)

    new = {"layers": moved(bias["layers"], counters["load"])}
    if "mtp" in bias:
        new["mtp"] = moved(bias["mtp"], counters["mtp_load"])
    return new


def step_metrics(out, counters, segment_ids, cfg: DecoderConfig
                 ) -> Dict[str, jax.Array]:
    """The scalars a step reports, fetched at the log cadence like the
    loss: both losses under their own names, this chip's share of the
    routing, and the share of the causal tiles that the attention core
    walks (the flash kernels' own bounds; 1.0 for one document a row)."""
    held = counters["held_counts"].astype(jnp.float32)
    real = (segment_ids > 0).sum().astype(jnp.float32)
    assigned = jnp.maximum(real * cfg.num_experts_per_tok * held.shape[0], 1.0)
    zero = jnp.zeros((), jnp.float32)
    return {
        "loss": out["loss"], "main_loss": out["main_loss"],
        "mtp_loss": out.get("mtp_loss", zero),
        "main_acc": out["main_acc"], "mtp_acc": out.get("mtp_acc", zero),
        "real_tokens": real,
        "expert_load_max_over_mean": held.max() / jnp.maximum(held.mean(), 1.0),
        "assignments_held": held.sum(),
        "routed_here_share": held.sum() / assigned,
        "dropped_assignments": counters["dropped"].astype(jnp.float32),
        "attn_tiles_walked_share": tiles_walked_share(
            segment_ids, cfg.attention_block),
    }
