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

The same decoder carries a HYBRID stack (Ling-3.0-flash,
`bailing_hybrid`; `cfg.layer_group_size` > 0), built on the serving path:
the layer with the published index i has the latent mixer where
(i + 1) % layer_group_size == 0 and the KDA mixer (`kda_mixer`, the
delta-rule linear attention of `ops/kda.py` behind a short causal
convolution) elsewhere; both end in a head-wise sigmoid gate; the latent
mixer has no low-rank query path and turns its rotary pairs interleaved;
the router is group-limited. `served_embed` is what the server runs: the
final-norm hidden state at each document's last token and its mean over
the document, with the step's routing counters.

And a third stack (ZAYA1, `zaya`; `cfg.mixer` "cca"), on the serving
path too: every layer is compressed convolutional attention (`cca_mixer`:
8 query heads read 2 key / value heads through the flash forward kernel's
grouped keys; q and k mixed by two short causal convolutions, half the
value heads the previous token's: `ops/cca.py`) and routed experts alone,
top 1 of a softmax by an MLP router (`ops/moe.route_mlp`) whose narrow
state the scan over layers carries beside the stream, `(x, r)`; both
sublayers write `(a x + c) + (a' f(N(x)) + c')` with learned vectors
(`cfg.residual_scaling`). Its period is one layer: one stack, one scan.

And a fourth (Nemotron-H / Nemotron 3, `nemotron_h`;
`cfg.hybrid_override_pattern`), served too: a layer is ONE sublayer,
`x + Mixer(RMSNorm(x))`, of the kind the published pattern STRING gives
its index: `M` a Mamba-2 mixer (`mamba_mixer`: the selective state-space
recurrence of `ops/ssd.py` behind a causal convolution, a gated group
norm after it), `*` attention over grouped keys with no position term
(`gqa_mixer`), `E` LatentMoE (routed squared-ReLU experts of two
matrices in a latent narrower than the stream, `ops/moe.py`, beside a
shared expert on the stream).

ONE table carries the three served stacks (`layer_table`: published
index and kind of every layer held): the layers of a kind lie stacked
under the kind's name, and `served_trunk` walks the table, a repeating
unit of kinds as a `lax.scan` over its repeats (`_compress`).

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
import numpy as np
from jax import lax

from proteinbert_tpu.configs import DecoderConfig
from proteinbert_tpu.ops.attention import (
    causal_segment_attention, flash_segment_attention, flash_tiles_fit,
    tiles_walked_share,
)
from proteinbert_tpu.ops.cca import cca_mix
from proteinbert_tpu.ops.kda import kda_chunked, segment_conv
from proteinbert_tpu.ops.layers import (
    ffn_apply, rms_norm_apply, rotary_apply, segment_positions, swiglu_apply,
)
from proteinbert_tpu.ops.moe import moe_apply, router_probs
from proteinbert_tpu.ops.ssd import gated_group_norm, ssd_chunked
from proteinbert_tpu.ops.ssd import kernel_takes as ssd_kernel_takes

Params = Dict[str, Any]


# ----------------------------------------------------------------- init

def param_shapes(cfg: DecoderConfig) -> Dict[str, Any]:
    """The parameter tree as shapes. A leaf named `*norm*` starts at 1,
    `balance_bias` at 0, every other leaf is normal(0, init_std)."""
    if cfg.hybrid:
        raise NotImplementedError(
            "the hybrid stack (layer_group_size > 0) is built on the serving "
            "path only (`init_served`, `served_embed`): its output head, its "
            "prediction module and the KDA kernel's backward pass are not")
    if cfg.hybrid_override_pattern:
        raise NotImplementedError(
            "the pattern stack (hybrid_override_pattern) is built on the "
            "serving path only (`init_served`, `served_embed`): its output "
            "head and its prediction module are not built, and the flash "
            "kernel's backward pass takes no grouped keys")
    if cfg.mixer == "cca":
        raise NotImplementedError(
            "the CCA mixer (mixer='cca') is built on the serving path only "
            "(`init_served`, `served_embed`): the flash kernel's backward "
            "pass takes no grouped keys, and the tied head is not built")
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
        rotary = partial(rotary_apply, positions=positions, theta=cfg.rope_theta,
                         interleave=cfg.rope_interleave)
        if cfg.q_lora_rank is None:
            q = x @ p["q"].astype(dt)
        else:
            c_q = rms_norm_apply(p["q_norm"], x @ p["q_a"].astype(dt),
                                 cfg.rms_norm_eps)
            q = c_q @ p["q_b"].astype(dt)
        q = q.reshape(B, L, H, nope + rope)
        kv = x @ p["kv_a"].astype(dt)
        c_kv = rms_norm_apply(p["kv_norm"], kv[..., :cfg.kv_lora_rank],
                              cfg.rms_norm_eps)
        k_rope = rotary(kv[..., cfg.kv_lora_rank:])
        kv_up = (c_kv @ p["kv_b"].astype(dt)).reshape(B, L, H, nope + dv)
        q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:])], axis=-1)
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
        # A head of 192 is a lane tile and a half: the flash kernel takes
        # it padded with zeros to the next whole tile (the scores are the
        # same; a third of the score products multiply zeros).
        from proteinbert_tpu.kernels.segment_flash import LANES

        lanes = -(nope + rope) % LANES

        def flash(q, k, v, segment_ids):
            widen = lambda a: jnp.pad(a, [(0, 0)] * 3 + [(0, lanes)])  # noqa: E731
            return flash_segment_attention(widen(q), widen(k), v, segment_ids,
                                           **sizes)

        with jax.named_scope("mla_core"):
            if (flash_tiles_fit(L, cfg.attention_block, nope + rope + lanes, dv)
                    or jax.default_backend() == "tpu"):
                out = lax.platform_dependent(
                    q, k, kv_up[..., nope:], segment_ids,
                    tpu=flash if lanes else partial(flash_segment_attention, **sizes),
                    default=plain)
            else:
                out = plain(q, k, kv_up[..., nope:], segment_ids)
        if cfg.mixer_output_gate:
            out = _head_gate(out, x, p["g"])
        return out.reshape(B, L, H * dv) @ p["o"].astype(dt)


def _head_gate(heads, x, gate_kernel):
    """heads (B, L, H, d) * sigmoid(x W_g), one scalar a head, float32."""
    gate = jax.nn.sigmoid(jnp.dot(x, gate_kernel.astype(x.dtype),
                                  preferred_element_type=jnp.float32))
    return (heads.astype(jnp.float32) * gate[..., None]).astype(x.dtype)


def kda_mixer(p: Params, x, segment_ids, cfg: DecoderConfig):
    """The delta-rule linear-attention mixer (KDA): projections in the
    activation dtype accumulated in float32; convolution, norms, gates
    and the recurrence's state in float32."""
    with jax.named_scope("kda"):
        B, L, _ = x.shape
        H, dk, dt = cfg.num_attention_heads, cfg.kda_head_dim, x.dtype
        f32 = jnp.float32
        wide = lambda name: jnp.dot(  # noqa: E731
            x, p[name].astype(dt), preferred_element_type=f32)
        heads = lambda a: a.reshape(B, L, H, dk)  # noqa: E731
        proj = lambda name: heads(jax.nn.silu(segment_conv(  # noqa: E731
            wide(name), p["conv_" + name].astype(f32), segment_ids)))
        unit = lambda a: a * lax.rsqrt(  # noqa: E731
            jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
        q, k, v = unit(proj("q")) * dk ** -0.5, unit(proj("k")), proj("v")
        rate = jnp.exp(p["A_log"].astype(f32))[:, None]
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            rate * heads(wide("f") + p["dt_bias"].astype(f32)))
        beta = jax.nn.sigmoid(wide("beta"))
        with jax.named_scope("kda_core"):
            o = kda_chunked(q, k, v, g, beta, segment_ids, cfg.kda_chunk)
        o = rms_norm_apply(p["o_norm"].astype(f32), o, cfg.rms_norm_eps)
        o = _head_gate(o, x, p["g"])
        return o.reshape(B, L, H * dk) @ p["o"].astype(dt)


def _note_cca_core(fits: bool, shape) -> None:
    """Which core this traced mixer gets (trace time, once a mixer)."""
    from proteinbert_tpu.kernels.segment_flash import note_cca_core_path

    if jax.default_backend() == "tpu":
        note_cca_core_path("pallas", "grouped_keys")
    else:
        note_cca_core_path(
            "reference", "not_tpu" if fits else "tiles_do_not_fit", shape)


def cca_mixer(p: Params, x, segment_ids, positions, cfg: DecoderConfig):
    """Compressed convolutional attention (`ops/cca.py` has the
    equations): projections in the activation dtype accumulated in
    float32; convolutions, means, norms and rotary in float32 (the
    grouped convolution's products in the activation dtype); the core
    over `num_key_value_heads` key and value heads, each read by its
    group of query heads and never repeated in HBM on a TPU."""
    with jax.named_scope("cca"):
        B, L, _ = x.shape
        H, G, d, dt = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.cca_head_dim, x.dtype)
        wide = lambda name: jnp.dot(  # noqa: E731
            x, p[name].astype(dt), preferred_element_type=jnp.float32)
        projected = [wide(name) for name in ("q", "k", "v1", "v2")]
        with jax.named_scope("cca_mix"):
            q, k, v = cca_mix(
                p, *projected, segment_ids, positions, H, G,
                int(d * cfg.partial_rotary_factor), cfg.rope_theta, dt)
        _note_cca_core(flash_tiles_fit(L, cfg.attention_block, d, d),
                       (B, L, H, G, d))
        with jax.named_scope("cca_core"):
            out = _grouped_key_core(q, k, v, segment_ids, cfg)
        return out.reshape(B, L, H * d) @ p["o"].astype(dt)


def _grouped_key_core(q, k, v, segment_ids, cfg: DecoderConfig):
    """softmax(q k^T / sqrt d) v, causal inside a document, over grouped
    keys (q: (B, L, H, d); k, v: (B, L, G, d)). As `latent_attention`:
    the flash kernel where the program is lowered for a TPU, plain jax
    (keys repeated) elsewhere; sizes the tiles do not take are an error
    on a TPU."""
    L, d = q.shape[1], q.shape[-1]
    sizes = dict(scale=float(d) ** -0.5, block=cfg.attention_block)
    plain = partial(causal_segment_attention, **sizes)
    if (flash_tiles_fit(L, cfg.attention_block, d, d)
            or jax.default_backend() == "tpu"):
        return lax.platform_dependent(
            q, k, v, segment_ids,
            tpu=partial(flash_segment_attention, **sizes), default=plain)
    return plain(q, k, v, segment_ids)


def gqa_mixer(p: Params, x, segment_ids, cfg: DecoderConfig):
    """Nemotron's attention layer: `num_attention_heads` query heads on
    `num_key_value_heads` key and value heads of `cca_head_dim`, no bias,
    NO rotary and no other position term; the core as CCA's."""
    with jax.named_scope("gqa"):
        B, L, _ = x.shape
        H, G, d, dt = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.cca_head_dim, x.dtype)
        q = (x @ p["q"].astype(dt)).reshape(B, L, H, d)
        k = (x @ p["k"].astype(dt)).reshape(B, L, G, d)
        v = (x @ p["v"].astype(dt)).reshape(B, L, G, d)
        with jax.named_scope("gqa_core"):
            out = _grouped_key_core(q, k, v, segment_ids, cfg)
        return out.reshape(B, L, H * d) @ p["o"].astype(dt)


def _note_ssd_core(fits: bool, shape) -> None:
    """Which recurrence this traced mixer gets (trace time, once a mixer)."""
    from proteinbert_tpu.kernels.ssd import note_ssd_core_path

    if fits and jax.default_backend() == "tpu":
        note_ssd_core_path("pallas", "chunked")
    else:
        note_ssd_core_path(
            "reference", "not_tpu" if fits else "tiles_do_not_fit", shape)


def mamba_mixer(p: Params, x, segment_ids, cfg: DecoderConfig):
    """The Mamba-2 mixer (`ops/ssd.py` has the recurrence): one product
    in, [z | xBC | dt]; a causal depthwise convolution with bias and SiLU
    over xBC that reads zero across a document's boundary; the selective
    state-space recurrence over x with B, C (a group's heads share them)
    and dt = softplus(dt + dt_bias), a = -exp(A_log): on a TPU the Pallas
    kernel `kernels/ssd.ssd_chunks` (a group's state in VMEM, x, B, C and y
    where they lie) at sizes its tiles take, the plain scan over chunks
    otherwise, counted either way; the skip D x; the gate THEN the norm
    over each group's channels apart; one product out. Products in the
    activation dtype accumulated in float32; convolution, dt, decays, the
    state, gate and norm in float32."""
    with jax.named_scope("mamba"):
        B, L, _ = x.shape
        H, P, G, N = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                      cfg.ssm_state_size)
        inner, dt, f32 = H * P, x.dtype, jnp.float32
        z, xbc, step = jnp.split(x @ p["in_proj"].astype(dt),
                                 [inner, 2 * inner + 2 * G * N], axis=-1)
        xbc = jax.nn.silu(
            segment_conv(xbc.astype(f32), p["conv"].astype(f32), segment_ids)
            + p["conv_bias"].astype(f32)).astype(dt)
        u, b, c = jnp.split(xbc, [inner, inner + G * N], axis=-1)
        u = u.reshape(B, L, H, P)
        step = jax.nn.softplus(step.astype(f32) + p["ssm_dt_bias"].astype(f32))
        b, c = b.reshape(B, L, G, N), c.reshape(B, L, G, N)
        _note_ssd_core(ssd_kernel_takes(u, b, cfg.chunk_size, dt), (B, L, H, P, G, N))
        with jax.named_scope("ssd_core"):
            y = ssd_chunked(u, step, -jnp.exp(p["ssm_A_log"].astype(f32)),
                            b, c, segment_ids, cfg.chunk_size, dt)
        y = y + p["ssm_D"].astype(f32)[:, None] * u.astype(f32)
        y = gated_group_norm(p["norm"], y.reshape(B, L, inner), z, G,
                             cfg.rms_norm_eps).astype(dt)
        return y @ p["o"].astype(dt)


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


# ------------------------------------------- the served stacks, by one table

TOP_INDEX = 2 ** 20     # the "layer index" of the embedding and the final norm

# kind of a layer -> (its mixer or None, its feed-forward part or None).
# A kind with both is a two-sublayer layer (`_hybrid_layer`, `_cca_layer`);
# a kind with one is Nemotron's single-sublayer layer (`_single_layer`).
# The kind is also the name of its stack in the served tree.
LAYER_KINDS = {
    "kda_dense": ("kda", "dense"), "kda_moe": ("kda", "moe"),
    "mla_moe": ("mla", "moe"), "cca": ("cca", "routed"),
    "mamba": ("mamba", None), "gqa": ("gqa", None), "latent_moe": (None, "moe"),
}
PATTERN_KINDS = {"M": "mamba", "*": "gqa", "E": "latent_moe"}


def hybrid_schedule(cfg: DecoderConfig):
    """(periods, pre, post): the expert layers held are `periods` whole
    periods of `layer_group_size`, each `pre` KDA layers, the latent
    layer, `post` KDA layers; `pre` follows from the published index of
    the first of them. The table carries any depth; a cut that is not
    whole periods is refused all the same, so that every kind of layer
    is held in its published ratio."""
    G, n_dense = cfg.layer_group_size, cfg.first_k_dense_replace
    first = cfg.first_layer_index + n_dense
    if any((cfg.first_layer_index + j + 1) % G == 0 for j in range(n_dense)):
        raise ValueError("a leading dense layer with the latent mixer is not "
                         "carried: the dense layers held have the KDA mixer")
    periods, rest = divmod(cfg.num_moe_layers, G)
    if rest or not periods:
        raise ValueError(
            f"the hybrid stack carries whole periods of {G} expert layers; "
            f"{cfg.num_moe_layers} held from the published index {first} are not")
    pre = (G - 1 - first % G) % G
    return periods, pre, G - 1 - pre


def layer_table(cfg: DecoderConfig) -> list:
    """[(published index, kind)] of the layers held, in order: what the
    served tree's stacks, the weights' recipe and the trunk all follow."""
    held = range(cfg.first_layer_index, cfg.first_layer_index + cfg.num_hidden_layers)
    if cfg.hybrid_override_pattern:
        if (cfg.mixer == "cca" or cfg.hybrid or cfg.first_k_dense_replace
                or len(cfg.pattern_held) != cfg.num_hidden_layers
                or set(cfg.pattern_held) - set(PATTERN_KINDS)):
            raise ValueError(
                f"the pattern's layers held ({cfg.pattern_held!r}, "
                f"{cfg.num_hidden_layers} from the published index "
                f"{cfg.first_layer_index}) have to be characters of "
                f"{sorted(PATTERN_KINDS)}, with no other stack asked for beside them")
        return [(i, PATTERN_KINDS[ch]) for i, ch in zip(held, cfg.pattern_held)]
    if cfg.mixer == "cca":
        if cfg.first_k_dense_replace or cfg.n_shared_experts or cfg.hybrid:
            raise ValueError(
                "the CCA stack carries ZAYA1's layer alone: routed experts "
                "chosen by the MLP router, residual scaling, no leading "
                "dense layer, no shared expert, no layer period")
        return [(i, "cca") for i in held]
    if not cfg.hybrid:
        raise ValueError("the plain latent-attention stack is built on the "
                         "training path (`init`, `loss_and_stats`), not served")
    hybrid_schedule(cfg)
    G = cfg.layer_group_size
    return [(i, ("mla" if (i + 1) % G == 0 else "kda")
             + ("_dense" if j < cfg.first_k_dense_replace else "_moe"))
            for j, i in enumerate(held)]


def _kind_indices(cfg: DecoderConfig) -> Dict[str, list]:
    """kind -> the published indices of its layers, in the stack's order."""
    stacks: Dict[str, list] = {}
    for index, kind in layer_table(cfg):
        stacks.setdefault(kind, []).append(index)
    return stacks


def _ffn_shapes(cfg: DecoderConfig, width: int) -> Dict[str, Any]:
    D = cfg.hidden_size
    tree = {"up": (D, width), "down": (width, D)}
    if cfg.expert_kind == "swiglu":
        tree["gate"] = (D, width)
    return tree


def layer_shapes(cfg: DecoderConfig, kind: str) -> Dict[str, Any]:
    """One layer's tree as shapes; the names and their sorted order are
    part of the weights' recipe (`init_served`)."""
    mixer, ffn = LAYER_KINDS[kind]
    D, H = cfg.hidden_size, cfg.num_attention_heads
    if mixer == "cca":
        G, d = cfg.num_key_value_heads, cfg.cca_head_dim
        C = (H + G) * d
        mix = {"q": (D, H * d), "k": (D, G * d), "v1": (D, G * d // 2),
               "v2": (D, G * d // 2), "o": (H * d, D),
               "conv0": (cfg.cca_time0, C), "conv0_bias": (C,),
               "conv1": (cfg.cca_time1, H + G, d, d), "conv1_bias": (C,),
               "tau": (G,)}
    elif mixer == "kda":
        W, K = H * cfg.kda_head_dim, cfg.short_conv_kernel_size
        mix = {"q": (D, W), "k": (D, W), "v": (D, W), "f": (D, W), "o": (W, D),
               "beta": (D, H), "g": (D, H), "conv_q": (K, W), "conv_k": (K, W),
               "conv_v": (K, W), "A_log": (H,), "dt_bias": (W,),
               "o_norm": (cfg.kda_head_dim,)}
    elif mixer == "mla":
        mix = {"q": (D, H * cfg.qk_head_dim),
               "kv_a": (D, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
               "kv_norm": (cfg.kv_lora_rank,),
               "kv_b": (cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
               "o": (H * cfg.v_head_dim, D), "g": (D, H)}
    elif mixer == "mamba":
        Hm, inner = cfg.mamba_num_heads, cfg.mamba_inner
        C = inner + 2 * cfg.n_groups * cfg.ssm_state_size
        mix = {"in_proj": (D, inner + C + Hm), "conv": (cfg.conv_kernel, C),
               "conv_bias": (C,), "ssm_A_log": (Hm,), "ssm_dt_bias": (Hm,),
               "ssm_D": (Hm,), "norm": (inner,), "o": (inner, D)}
    elif mixer == "gqa":
        G, d = cfg.num_key_value_heads, cfg.cca_head_dim
        mix = {"q": (D, H * d), "k": (D, G * d), "v": (D, G * d), "o": (H * d, D)}
    if ffn is None or mixer is None:
        tree = {"norm": (D,)}
    else:
        tree = {"norm1": (D,), "norm2": (D,)}
    if mixer is not None:
        tree["mixer"] = mix
    if cfg.residual_scaling:
        vectors = {"res_scale": (D,), "res_bias": (D,), "out_scale": (D,),
                   "out_bias": (D,)}
        tree.update(res1=vectors, res2=dict(vectors))
    E, F = cfg.experts_held, cfg.moe_intermediate_size
    if ffn == "dense":
        tree["mlp"] = _ffn_shapes(cfg, cfg.intermediate_size)
    elif ffn == "routed":
        R = cfg.router_hidden_size
        tree["moe"] = {"router": {"proj": (D, R), "proj_bias": (R,), "carry": (R,),
                                  "norm": (R,), "w1": (R, R), "b1": (R,),
                                  "w2": (R, R), "b2": (R,),
                                  "w3": (R, cfg.n_routed_experts)},
                       "router_bias": (cfg.n_routed_experts,),
                       "experts": {"gate": (E, D, F), "up": (E, D, F),
                                   "down": (E, F, D)}}
    elif ffn == "moe":
        U = cfg.moe_latent_size or D
        experts = {"up": (E, U, F), "down": (E, F, U)}
        if cfg.expert_kind == "swiglu":
            experts["gate"] = (E, U, F)
        tree["moe"] = {"router": (D, cfg.n_routed_experts),
                       "router_bias": (cfg.n_routed_experts,), "experts": experts}
        if cfg.moe_latent_size is not None:
            tree["moe"].update(to_latent=(D, U), from_latent=(U, D))
        tree["shared"] = _ffn_shapes(cfg, cfg.shared_expert_width)
    return tree


def served_param_count(cfg: DecoderConfig) -> int:
    """Parameters the served tree holds (the router's bias is not one)."""
    def count(tree):
        flat, _ = jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda s: isinstance(s, tuple))
        return sum(int(np.prod(shape)) for path, shape in flat
                   if path[-1].key != "router_bias")

    return (cfg.vocab_size * cfg.hidden_size + cfg.hidden_size
            + sum(len(indices) * count(layer_shapes(cfg, kind))
                  for kind, indices in _kind_indices(cfg).items()))


def served_abstract(cfg: DecoderConfig) -> Params:
    """The served tree as `jax.ShapeDtypeStruct`s: what it takes to lower
    `served_embed` without making a weight."""
    dt = jnp.dtype(cfg.param_dtype)
    is_shape = lambda s: isinstance(s, tuple)  # noqa: E731
    leaf = lambda s: jax.ShapeDtypeStruct(s, dt)  # noqa: E731
    tree = {"embed": leaf((cfg.vocab_size, cfg.hidden_size)),
            "final_norm": leaf((cfg.hidden_size,))}
    for kind, indices in _kind_indices(cfg).items():
        tree[kind] = jax.tree.map(lambda s: leaf((len(indices),) + s),
                                  layer_shapes(cfg, kind), is_leaf=is_shape)
    return tree


@partial(jax.jit, static_argnames=("name", "shape", "heads", "std", "dtype",
                                   "dt_limits", "centred"))
def _draw(key, index, j, name, shape, heads, std, dtype, dt_limits=None,
          centred=False):
    own = jax.random.fold_in(jax.random.fold_in(key, index), j)
    if name == "ssm_A_log":
        leaf = jnp.log(jax.random.uniform(own, shape, jnp.float32, 1.0, 16.0))
    elif name == "ssm_dt_bias":
        low, high, floor = dt_limits
        dt = jnp.maximum(floor, jnp.exp(
            jax.random.uniform(own, shape, jnp.float32)
            * (np.log(high) - np.log(low)) + np.log(low)))
        leaf = dt + jnp.log(-jnp.expm1(-dt))       # softplus(leaf) = dt
    elif "norm" in name or name == "ssm_D":
        leaf = jnp.ones(shape, jnp.float32)
    elif name == "A_log":
        leaf = jnp.log(1.0 + 3.0 * jnp.arange(heads, dtype=jnp.float32)
                       / max(heads - 1, 1))
    elif name == "dt_bias":
        leaf = jnp.full(shape, -4.0, jnp.float32)
    elif name == "carry" or name.endswith("_scale"):
        leaf = jnp.ones(shape, jnp.float32)
    elif name.endswith("_bias") or name in ("b1", "b2", "tau"):
        leaf = jnp.zeros(shape, jnp.float32)
    else:
        leaf = std * jax.random.normal(own, shape, jnp.float32)
        if centred:     # every column sums to zero over its input rows
            leaf = leaf - leaf.mean(axis=-2, keepdims=True)
    return lax.reduce_precision(leaf, exponent_bits=8, mantissa_bits=7).astype(dtype)


@partial(jax.jit, donate_argnums=0)
def _put(stack, leaf, at):
    return lax.dynamic_update_slice(
        stack, leaf[None], (at,) + (jnp.zeros((), at.dtype),) * leaf.ndim)


def _leaf_std(path: tuple, cfg: DecoderConfig) -> float:
    """The embedding's rows and the products that write into the
    residual stream (`o`, `down`, the latent's way up) have a deviation
    of their own; `path` is the leaf's names from its tree's root."""
    name = path[-1]
    own = {"embed": cfg.embed_init_std, "o": cfg.out_init_std,
           "down": cfg.out_init_std, "from_latent": cfg.out_init_std}.get(name)
    if name == "down" and "experts" in path and cfg.moe_latent_size is not None:
        own = None      # a latent expert writes the latent, not the stream
    if name in ("conv0", "conv1", "w1", "w2", "w3", "conv"):
        # CCA's and Mamba's convolutions and the MLP router's layers by
        # their fan-in: at `init_std` the convolved part of q and k would
        # be a hundredth of the mean part and the router's logits all but
        # equal, and neither mechanism would move an answer.
        own = {"conv0": cfg.cca_time0, "conv": cfg.conv_kernel,
               "conv1": cfg.cca_time1 * cfg.cca_head_dim}.get(
                   name, cfg.router_hidden_size) ** -0.5
    return cfg.init_std if own is None else own


BIAS_PROBES, BIAS_INDEX = 4096, 2 ** 16


@partial(jax.jit, static_argnames=("eps", "dtype"))
def _balanced_bias(key, router: Params, eps: float, dtype):
    """The MLP router's balance bias as the training rule that no
    gradient reaches would leave it: b_e = 1 / E - mean p_e over
    `BIAS_PROBES` seeded normal states, so that every expert's p + b has
    one mean. (A seeded MLP's logits have a part that no token moves,
    the GELUs' mean through W_2 and W_3; with b = 0 the fullest expert of
    a layer took 9 x the mean load on the chip, which no trained router
    does: PERF.md section 6, PR 35.)"""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    probe = jax.random.normal(
        key, (BIAS_PROBES, router["norm"].shape[0]), jnp.float32)
    mean = router_probs(jax.tree.map(f32, router), probe, eps).mean(0)
    return lax.reduce_precision(1.0 / mean.shape[0] - mean, exponent_bits=8,
                                mantissa_bits=7).astype(dtype)


def _tree_of(key, index: int, shapes, cfg: DecoderConfig):
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    names = lambda path: tuple(str(k.key) for k in path)  # noqa: E731
    tree = jax.tree.unflatten(treedef, [
        _draw(key, index, j, names(path)[-1], shape, cfg.num_attention_heads,
              _leaf_std(names(path), cfg), cfg.param_dtype,
              (cfg.time_step_min, cfg.time_step_max, cfg.time_step_floor),
              centred=cfg.expert_kind == "relu2" and names(path)[-1] == "down")
        for j, (path, shape) in enumerate(flat)])
    if cfg.router == "mlp" and "moe" in tree:
        tree["moe"]["router_bias"] = _balanced_bias(
            jax.random.fold_in(jax.random.fold_in(key, index), BIAS_INDEX),
            tree["moe"]["router"], cfg.rms_norm_eps, cfg.param_dtype)
    return tree


def init_served(key: jax.Array, cfg: DecoderConfig) -> Params:
    """The served tree, made on the device LEAF BY LEAF in
    `cfg.param_dtype`: the layer with the published index i draws leaf
    number j of its own tree (keys sorted) as init_std * normal(
    fold_in(fold_in(key, i), j)) in float32, rounded to bfloat16 as it
    is made (the published weights are bfloat16 values) and written into
    its place in its stack, so the share never stands whole in float32.
    Norm scales 1, the router's bias 0, A_log_h = log(1 + 3 h / (H - 1)),
    dt_bias -4, every other leaf (the conv taps too) init_std * normal,
    but the embedding's rows (`embed_init_std`) and the products that
    write into the residual stream (`out_init_std`: a mixer's `o`, an
    FFN's `down`, the latent's `from_latent`; a LATENT expert's `down`
    writes the latent and stays at `init_std`); in the CCA stack besides:
    the router's `carry` and the residual scales 1, every bias and `tau`
    0, the convolutions and the MLP router's three layers normal at
    fan_in^-1/2 (`_leaf_std`), the balance bias from the layer's own
    router over seeded probes (`_balanced_bias`); in a Mamba layer (the
    Mamba-2 reference initialisation): `ssm_A_log` = log(uniform(1, 16)),
    `ssm_dt_bias` the inverse softplus of a log-uniform draw in
    [`time_step_min`, `time_step_max`] floored at `time_step_floor`,
    `ssm_D` 1, the convolution normal at fan_in^-1/2 with a zero bias;
    the second matrix of a squared-ReLU feed-forward part (`down`, the
    shared expert's and every routed expert's) CENTRED, each column's mean
    over its input rows subtracted before the rounding (relu^2 has a mean
    of half its input's variance in EVERY hidden channel; through an
    uncentred W2 that mean is one vector no token moves, 41 % of the
    shared expert's output at the published widths and 22 % of the stream
    after six layers, the router's scores carry its part, and the chip
    read the fullest held expert at 2.59 x the mean load: PERF.md section
    6, PR 43): a recipe a reference can follow without this module.
    (With every leaf at 0.02 a layer's result is as large as the stream
    it is added to, and the seeded network passed a rounding on with a
    gain of ~20 over seven layers: no comparison could tell bfloat16
    products from int8. Rows of unit size and results a tenth of the
    stream, as a depth-scaled init gives a model of the published 42
    layers, keep the gain near 1. Taps of the size
    of K^-1/2 were tried first for KDA: SiLU of a unit-variance input has
    a mean of a quarter of its rms, linear attention sums that mean
    coherently over a document, every token's hidden state collapses onto
    one vector and every token picks the same experts. With taps of 0.02
    the SiLU is all but linear at its input's size and the mean is gone;
    q, k and v are rescaled after it by their norms.) Tree: `embed`,
    `final_norm`, and one stack a KIND of layer (`layer_table`), named by
    the kind, (layers of that kind,) + the layer's tree, in the order of
    their published indices."""
    top = {"embed": (cfg.vocab_size, cfg.hidden_size),
           "final_norm": (cfg.hidden_size,)}
    params = _tree_of(key, TOP_INDEX, top, cfg)
    dt = jnp.dtype(cfg.param_dtype)
    for kind, indices in _kind_indices(cfg).items():
        shapes = layer_shapes(cfg, kind)
        stack = jax.tree.map(lambda s: jnp.zeros((len(indices),) + s, dt), shapes,
                             is_leaf=lambda s: isinstance(s, tuple))
        for n, index in enumerate(indices):
            at = jnp.asarray(n, jnp.int32)
            stack = jax.tree.map(lambda big, leaf: _put(big, leaf, at), stack,
                                 _tree_of(key, index, shapes, cfg))
        params[kind] = stack
    return params


def _layer_at(stack: Params, at):
    """(layer `at` (leading indices) of a stack but for its experts, the
    held experts' matrices as the stack they lie in, or None): the
    grouped products are handed the stack and the layer's place in it."""
    experts = stack.get("moe", {}).get("experts")
    return jax.tree.map(lambda a: a[at], {
        k: ({m: w for m, w in v.items() if m != "experts"} if k == "moe" else v)
        for k, v in stack.items()}), experts


def _counters(stats, cfg: DecoderConfig):
    """(held_counts (expert layers: 0 or 1, experts_held), dropped (),
    block_rows ()) of one layer: what every kind hands the trunk."""
    if stats is None:
        zero = jnp.zeros((), jnp.int32)
        return jnp.zeros((0, cfg.experts_held), jnp.int32), zero, zero
    return (stats["held_counts"][None], stats["dropped"].astype(jnp.int32),
            stats["block_rows"].astype(jnp.int32))


def _hybrid_layer(stack: Params, at, x, segment_ids, positions, real,
                  cfg: DecoderConfig, mixer: str):
    """Layer `at` (leading indices) of a stack."""
    p, experts = _layer_at(stack, at)
    dt = jnp.dtype(cfg.dtype)
    h = rms_norm_apply(p["norm1"], x, cfg.rms_norm_eps).astype(dt)
    if mixer == "kda":
        x = x + kda_mixer(p["mixer"], h, segment_ids, cfg)
    else:
        x = x + latent_attention(p["mixer"], h, segment_ids, positions, cfg)
    h = rms_norm_apply(p["norm2"], x, cfg.rms_norm_eps).astype(dt)
    if experts is None:
        with jax.named_scope("dense_mlp"):
            return x + swiglu_apply(p["mlp"], h), None
    B, L, D = h.shape
    routed, stats = moe_apply(
        dict(p["moe"], experts=experts), p["moe"]["router_bias"].astype(jnp.float32),
        h.reshape(B * L, D), real.reshape(B * L), cfg, at=at)
    with jax.named_scope("shared_expert"):
        shared = swiglu_apply(p["shared"], h)
    return x + routed.reshape(B, L, D) + shared, stats


def _single_layer(stack: Params, at, x, segment_ids, real, cfg: DecoderConfig,
                  kind: str):
    """Layer `at` of a stack of single-sublayer layers (Nemotron's): one
    norm, one mixer OR one feed-forward part, one add. The router reads
    the normed stream in float32 (with 22 of 512 chosen the 22nd and 23rd
    scores lie close: it is not rounded to the activation dtype first)."""
    p, experts = _layer_at(stack, at)
    dt = jnp.dtype(cfg.dtype)
    h = rms_norm_apply(p["norm"], x, cfg.rms_norm_eps)
    if kind == "mamba":
        return x + mamba_mixer(p["mixer"], h.astype(dt), segment_ids, cfg), None
    if kind == "gqa":
        return x + gqa_mixer(p["mixer"], h.astype(dt), segment_ids, cfg), None
    B, L, D = h.shape
    rounded = h.astype(dt)
    routed, stats = moe_apply(
        dict(p["moe"], experts=experts), p["moe"]["router_bias"].astype(jnp.float32),
        rounded.reshape(B * L, D), real.reshape(B * L), cfg, at=at,
        router_x=h.reshape(B * L, D))
    with jax.named_scope("shared_expert"):
        shared = ffn_apply(p["shared"], rounded, cfg.expert_kind)
    return x + routed.reshape(B, L, D) + shared, stats


def _scaled_residual(p: Params, x, y):
    """(a x + c) + (a' y + c'), float32: ZAYA1's residual scaling."""
    f32 = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    return ((f32("res_scale") * x + f32("res_bias"))
            + (f32("out_scale") * y.astype(jnp.float32) + f32("out_bias")))


def _cca_layer(stack: Params, at, x, r, segment_ids, positions, real,
               cfg: DecoderConfig):
    """Layer `at` of the CCA stack over the stream x (B, L, D) float32
    and the router's carried state r (B L, R) float32 -> (x, r, the
    layer's stats). The router reads the normed stream in float32
    (a top-1 choice moves a token's whole expert: it is not rounded to
    the activation dtype first)."""
    p, experts = _layer_at(stack, at)
    dt = jnp.dtype(cfg.dtype)
    # `residual`: what the float32 stream costs outside the two sublayers,
    # its two norms and its two scaled adds
    with jax.named_scope("residual"):
        h = rms_norm_apply(p["norm1"], x, cfg.rms_norm_eps).astype(dt)
    mixed = cca_mixer(p["mixer"], h, segment_ids, positions, cfg)
    with jax.named_scope("residual"):
        x = _scaled_residual(p["res1"], x, mixed)
        h = rms_norm_apply(p["norm2"], x, cfg.rms_norm_eps)
    B, L, D = h.shape
    routed, stats = moe_apply(
        dict(p["moe"], experts=experts), p["moe"]["router_bias"].astype(jnp.float32),
        h.astype(dt).reshape(B * L, D), real.reshape(B * L), cfg, at=at,
        router_x=h.reshape(B * L, D), router_state=r)
    with jax.named_scope("residual"):
        x = _scaled_residual(p["res2"], x, routed.reshape(B, L, D))
    return x, stats["router_state"], stats


def _compress(kinds: tuple) -> list:
    """[(unit, repeats)] covering `kinds` in order: at each place the
    unit (a run of kinds) whose immediate repeats cover the most layers,
    the shortest such unit first; a unit of several kinds stands only
    where it repeats. `MEMEMEM*EMEME` -> (ME) x 3, M, *, (EM) x 2, E."""
    out, i, n = [], 0, len(kinds)
    while i < n:
        best = ((kinds[i],), 1)
        for u in range(1, (n - i) // 2 + 1):
            unit, r = kinds[i:i + u], 1
            while kinds[i + r * u:i + (r + 1) * u] == unit:
                r += 1
            if r > 1 and u * r > len(best[0]) * best[1]:
                best = (unit, r)
        out.append(best)
        i += len(best[0]) * best[1]
    return out


def served_trunk(params: Params, tokens, segment_ids, real, cfg: DecoderConfig):
    """-> (h (B, L, D) before the final norm, held_counts (expert layers,
    experts_held), dropped (), block_rows ()). `real` (B, L) marks the
    positions that hold a token: a span's tail past its document is
    routed nowhere.

    ONE walk of `layer_table` carries every served stack: the layers of a
    kind lie stacked on a leading axis (`init_served`), a unit of kinds
    that repeats (`_compress`) is one `lax.scan` whose body is the unit
    (walked the same way, so a run of one kind inside it is a scan too)
    and whose counter gives each kind's place in its stack; what does not
    repeat is traced where it stands. The carry is the stream and, for
    the MLP router alone, its narrow state (zeros before the first layer
    held: the stack starts at the published layer 0, or at a pipeline
    stage's first layer with the state it would be handed)."""
    positions = segment_positions(segment_ids)
    # The residual stream is float32 (a layer's result, in the activation
    # dtype, is added to it): rounding it to bfloat16 at every add would
    # cost as much accuracy as int8 products do.
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], jnp.maximum(tokens, 0),
                     axis=0).astype(jnp.float32)
    r = jnp.zeros((x.shape[0] * x.shape[1],
                   cfg.router_hidden_size if cfg.router == "mlp" else 0), jnp.float32)

    def layer(kind, at, carry):
        x, r = carry
        mixer, ffn = LAYER_KINDS[kind]
        if kind == "cca":
            x, r, stats = _cca_layer(params[kind], at, x, r, segment_ids,
                                     positions, real, cfg)
        elif mixer is None or ffn is None:
            x, stats = _single_layer(params[kind], at, x, segment_ids, real,
                                     cfg, kind)
        else:
            x, stats = _hybrid_layer(params[kind], at, x, segment_ids,
                                     positions, real, cfg, mixer)
        return (x, r), _counters(stats, cfg)

    def walk(carry, kinds, base):
        """The layers `kinds` in order, each kind's first at `base[kind]`
        of its stack."""
        base, counters = dict(base), []
        for unit, repeats in _compress(kinds):
            per = {k: unit.count(k) for k in unit}
            if repeats == 1:
                carry, c = layer(unit[0], (base[unit[0]],), carry)
            else:
                def body(carry, i, unit=unit, per=per, base=dict(base)):
                    at = {k: base[k] + i * per[k] for k in per}
                    if len(unit) == 1:
                        return layer(unit[0], (at[unit[0]],), carry)
                    return walk(carry, unit, at)
                carry, (counts, dropped, rows) = lax.scan(
                    body, carry, jnp.arange(repeats))
                c = (counts.reshape(-1, cfg.experts_held), dropped.sum(), rows.sum())
            counters.append(c)
            for k in per:
                base[k] = base[k] + repeats * per[k]
        counts, dropped, rows = zip(*counters)
        return carry, (jnp.concatenate(counts), sum(dropped), sum(rows))

    kinds = tuple(kind for _, kind in layer_table(cfg))
    (x, _), (counts, dropped, block_rows) = walk(
        (x, r), kinds, {k: 0 for k in set(kinds)})
    return x, counts, dropped, block_rows


# `benchmark/read_cca_flips.py` calls the CCA stack's trunk by this name.
cca_trunk = served_trunk


def served_embed(params: Params, tokens, segment_ids, num_segments: int,
                 cfg: DecoderConfig):
    """What `embed` answers for every document of a packed batch.
    tokens: (B, L) int32, a NEGATIVE id where a span holds no token (a
    request's span is the smallest of the ladder that holds it);
    segment_ids: (B, L), 0 outside every span, 1..num_segments inside.
    -> {"global": (B, S, D) the final-norm hidden state at each
    document's last token, "local_mean": (B, S, D) its mean over the
    document's tokens, "routing": the batch's counters}, float32."""
    real = (segment_ids > 0) & (tokens >= 0)
    with jax.named_scope("encode"):
        h, counts, dropped, block_rows = served_trunk(
            params, tokens, segment_ids, real, cfg)
        h = rms_norm_apply(params["final_norm"], h, cfg.rms_norm_eps)
    with jax.named_scope("pool"):
        h = h.astype(jnp.float32)
        m = ((segment_ids[:, None, :]
              == jnp.arange(1, num_segments + 1, dtype=segment_ids.dtype)[None, :, None])
             & real[:, None, :])                                    # (B, S, L)
        n = m.sum(-1)
        last = jnp.argmax(jnp.where(m, jnp.arange(m.shape[-1]), -1), axis=-1)
        mean = (jnp.einsum("bsl,bld->bsd", m.astype(jnp.float32), h,
                           precision=lax.Precision.HIGHEST)
                / jnp.maximum(n, 1)[..., None])
        at_last = jnp.take_along_axis(h, last[..., None], axis=1)
        return {"global": jnp.where((n > 0)[..., None], at_last, 0.0),
                "local_mean": mean,
                "routing": {"held_counts": counts, "dropped": dropped,
                            "block_rows": block_rows,
                            "real_tokens": real.sum()}}


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
                "dropped": stats["dropped"].sum(), "ids": stats["ids"],
                "block_rows": stats["block_rows"].sum()}
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
        counters["block_rows"] = counters["block_rows"] + mtp_stats["block_rows"]
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
        "block_rows": counters["block_rows"].astype(jnp.float32),
        "attn_tiles_walked_share": tiles_walked_share(
            segment_ids, cfg.attention_block),
    }
