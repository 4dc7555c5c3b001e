"""ProteinBERT dual-track model (reference C11/C12, TPU-native).

Functional pytree implementation of the dual-track (local sequence /
global annotation) ProteinBERT trunk (Brandes et al. 2022; reference
ProteinBERT/modules.py:95-304), with the paper-correct semantics the
reference gets wrong (SURVEY ledger #1-#4):

- every parameter is a pytree leaf (optimizer sees the attention heads);
- attention softmax is over the sequence axis, padding masked out;
- LayerNorm is per-position over features only → the model is
  shape-parametric in L (one set of weights serves any sequence length);
- output heads emit LOGITS; probabilities never enter the loss (the
  reference applies Softmax/Sigmoid in the model and then feeds
  CrossEntropyLoss, reference modules.py:277-293 + utils.py:293).

TPU mapping:
- activations run in bfloat16 (cfg.dtype), parameters in float32;
- the N identical blocks are stacked on a leading axis and driven by
  `lax.scan` (cfg.scan_blocks) → one compiled block body instead of N
  unrolled copies, cutting compile time and enabling `jax.checkpoint`
  rematerialisation per scan step (cfg.remat) for long-context configs;
- layout is feature-last (B, L, C) throughout so the L axis can carry a
  `seq` mesh axis (sequence parallelism) and convs lower to MXU implicit
  GEMMs (see ops/layers.py).

Block dataflow (reference modules.py:201-231, shapes in SURVEY §3.4):
  local:  x = LN(x + narrow_conv(x)·gelu + wide_conv(x)·gelu
                 + broadcast(gelu(dense(g))))
          x = LN(x + gelu(dense(x)))
  global: g = LN(g + gelu(dense(g)) + attention(x, g))
          g = LN(g + gelu(dense(g)))
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from proteinbert_tpu.configs import ModelConfig
from proteinbert_tpu.data.vocab import PAD_ID
from proteinbert_tpu.ops.attention import (
    global_attention_apply,
    global_attention_init,
    packed_global_attention_apply,
)
from proteinbert_tpu.ops.layers import (
    conv1d_init,
    dense_apply,
    dense_init,
    embedding_apply,
    embedding_init,
    layer_norm_apply,
    layer_norm_init,
)

Params = Dict[str, Any]


def remat_wrap(body, cfg: ModelConfig):
    """Apply cfg's rematerialisation choice to a block body — the single
    policy-dispatch point shared by the jit path here and the explicit
    sequence-parallel path (parallel/seq_parallel.py).

    "full" recomputes the whole block in backward; "convs" keeps the two
    conv outputs (the FLOPs-heavy ~85% of a block, tagged "conv_out" in
    ops/layers.conv1d_apply and the seq-parallel valid-conv variant) and
    recomputes only the cheap dense/LN/attention tail: ~3.15× forward
    FLOPs per step instead of full remat's 4×, for 2·(B,L,C) bf16 extra
    residency per block (measured +8% throughput, BASELINE.md). Under
    use_pallas the kernel's custom VJP hides its internals either way, so
    both policies degenerate to recompute-everything there.
    """
    if cfg.remat_policy not in ("full", "convs"):
        raise ValueError(
            f"unknown remat_policy {cfg.remat_policy!r}; have 'full', 'convs'"
        )
    if not cfg.remat:
        return body
    if cfg.remat_policy == "convs":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names("conv_out"),
        )
    return jax.checkpoint(body)


def block_init(key: jax.Array, cfg: ModelConfig) -> Params:
    """One dual-track block's parameters (reference modules.py:95-199)."""
    C, G = cfg.local_dim, cfg.global_dim
    ks = jax.random.split(key, 7)
    return {
        "narrow_conv": conv1d_init(ks[0], cfg.narrow_kernel, C, C),
        "wide_conv": conv1d_init(ks[1], cfg.wide_kernel, C, C),
        "global_to_local": dense_init(ks[2], G, C),
        "local_ln1": layer_norm_init(C),
        "local_dense": dense_init(ks[3], C, C),
        "local_ln2": layer_norm_init(C),
        "global_dense1": dense_init(ks[4], G, G),
        "attention": global_attention_init(ks[5], C, G, cfg.key_dim, cfg.num_heads),
        "global_ln1": layer_norm_init(G),
        "global_dense2": dense_init(ks[6], G, G),
        "global_ln2": layer_norm_init(G),
    }


def block_apply(
    params: Params,
    local: jax.Array,
    global_: jax.Array,
    pad_mask: Optional[jax.Array],
    cfg: ModelConfig,
    segment_ids: Optional[jax.Array] = None,
    forward_only: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Apply one block. local (B,L,C), global (B,G), pad_mask (B,L) bool.

    PACKED rows (data/packing.py): pass `segment_ids` (B,L) and a
    per-SEGMENT global track (B,S,G). The local convs are boundary-
    masked, the global→local broadcast is gathered per position from the
    position's own segment, and attention/annotation state run per
    segment — a packed row is numerically a batch of independent
    proteins (tests/test_packing.py asserts bit-level isolation).

    `forward_only` is the caller's word that the program will not be
    differentiated (the packed serving and mapping entries say it): a
    packed row's local track then runs as one VMEM-resident kernel
    where the backend and the shape allow
    (kernels/fused_block.packed_local_track_forward). It changes
    nothing dense, and nothing a training or evaluation step traces."""
    packed = segment_ids is not None
    from proteinbert_tpu.kernels import (
        gather_segment_broadcast, local_track_reference,
        local_track_segment_reference, packed_local_track_forward,
    )

    # Local track (reference modules.py:201-217). The scopes
    # (`local_track`, `attention`, `global_track`) name the compiled
    # instructions for obs/tracing.program_scopes; the one-pass kernel
    # runs both tracks as one program and is `onepass`.
    with jax.named_scope("local_track"):
        broadcast = jax.nn.gelu(
            dense_apply(params["global_to_local"], global_))

    track_params = {k: params[k] for k in ("narrow_conv", "wide_conv",
                                           "local_ln1", "local_dense",
                                           "local_ln2")}
    # Under use_pallas BOTH tracks route through the one-pass trunk
    # dispatch (kernels/one_pass.py, ISSUE 16): on supported shapes the
    # local conv track and the global attention run as ONE VMEM-resident
    # grid program (the inter-track activations never round-trip through
    # HBM, and the segment one-hot is built once for both masks);
    # otherwise the dispatch falls back to the existing two-kernel
    # composition, each leg with its own guard + counter family. Every
    # decision is counted in onepass_kernel_path_total{path=,reason=}.
    # `attn` comes back alongside `local`; it attends over the NEW local
    # track with the OLD global track, exactly like the split path.
    if cfg.use_pallas:
        from proteinbert_tpu.kernels import (
            fused_onepass_dense, fused_onepass_segments, pallas_interpret,
        )

        interp = pallas_interpret()
        with jax.named_scope("onepass"):
            if packed:
                # pad_mask is the REAL-token mask: for training packs it
                # equals segment_ids > 0 (segments hold no pad); the
                # ragged serving path packs bucket-quantized spans with
                # <pad> tails, which are excluded from the attention
                # softmax but DO participate in the convs (two-kernel
                # semantics).
                local, attn = fused_onepass_segments(
                    track_params, params["attention"], local, broadcast,
                    global_, segment_ids, real_mask=pad_mask,
                    narrow_dilation=1, wide_dilation=cfg.wide_dilation,
                    interpret=interp,
                )
            else:
                local, attn = fused_onepass_dense(
                    track_params, params["attention"], local, broadcast,
                    global_, pad_mask=pad_mask,
                    narrow_dilation=1, wide_dilation=cfg.wide_dilation,
                    interpret=interp,
                )
    elif packed:
        # Gather each position's own segment's broadcast vector:
        # (B, S, C) → (B, L, C), zero at pad so nothing row-wide
        # leaks into the masked conv taps.
        with jax.named_scope("local_track"):
            if forward_only:
                local = packed_local_track_forward(
                    track_params, local, broadcast, segment_ids,
                    1, cfg.wide_dilation)
            else:
                local = local_track_segment_reference(
                    track_params, local,
                    gather_segment_broadcast(broadcast, segment_ids),
                    segment_ids, 1, cfg.wide_dilation,
                )
        with jax.named_scope("attention"):
            attn = packed_global_attention_apply(
                params["attention"], local, global_, segment_ids,
                real_mask=pad_mask)
    else:
        with jax.named_scope("local_track"):
            local = local_track_reference(
                track_params, local, broadcast, 1, cfg.wide_dilation
            )
        with jax.named_scope("attention"):
            attn = global_attention_apply(
                params["attention"], local, global_, pad_mask)

    # Global track (reference modules.py:219-229) — per segment when
    # packed: every dense/LN is feature-last and shape-agnostic over the
    # leading (B, S) axes; `attn` was computed above against the OLD
    # global track.
    with jax.named_scope("global_track"):
        dense1 = jax.nn.gelu(dense_apply(params["global_dense1"], global_))
        global_ = layer_norm_apply(params["global_ln1"],
                                   global_ + dense1 + attn)
        global_ = layer_norm_apply(
            params["global_ln2"],
            global_ + jax.nn.gelu(
                dense_apply(params["global_dense2"], global_)),
        )
    return local, global_


def init(key: jax.Array, cfg: ModelConfig) -> Params:
    """Full-model parameter pytree (reference modules.py:234-293)."""
    k_embed, k_gin, k_blocks, k_lh, k_gh = jax.random.split(key, 5)
    block_keys = jax.random.split(k_blocks, cfg.num_blocks)
    blocks = [block_init(k, cfg) for k in block_keys]
    if cfg.scan_blocks:
        blocks = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
    return {
        "embedding": embedding_init(k_embed, cfg.vocab_size, cfg.local_dim),
        "global_in": dense_init(k_gin, cfg.num_annotations, cfg.global_dim),
        "blocks": blocks,
        "local_head": dense_init(k_lh, cfg.local_dim, cfg.vocab_size),
        "global_head": dense_init(k_gh, cfg.global_dim, cfg.num_annotations),
    }


_LN_NAMES = ("local_ln1", "local_ln2", "global_ln1", "global_ln2")


def _cast_blocks(blocks: Params, dtype) -> Params:
    """Cast the scanned block stack to the compute dtype ONCE, outside the
    scan. Every non-LN leaf is consumed at activation dtype anyway
    (`.astype(x.dtype)` in ops/layers.py), but casting per-use INSIDE the
    scan makes autodiff stash the per-block bf16 copies into a stacked
    loop-carried buffer whose forward/backward shardings the SPMD
    partitioner cannot reconcile on fsdp-bearing meshes ("Involuntary
    full rematerialization", VERDICT r2 Weak #3). Hoisting the cast means
    the scan xs ARE the bf16 tensors — nothing new is saved per step, the
    warning disappears, and the f32→bf16 convert runs once per step
    instead of once per block. LN leaves stay f32: layer_norm_apply
    consumes them in f32 statistics space. int8 quant leaves
    ({"q", "scale"} from parallel/quant.partial_dequantize_params, the
    in-kernel-dequant serving arm) pass through untouched — the kernels
    consume the int8 weights + fp32 scales directly."""
    def cast(path, leaf):
        if any(getattr(p, "key", None) in _LN_NAMES + ("q", "scale")
               for p in path):
            return leaf
        return leaf.astype(dtype)

    return jax.tree_util.tree_map_with_path(cast, blocks)


def encode(
    params: Params,
    tokens: jax.Array,
    annotations: jax.Array,
    cfg: ModelConfig,
    pad_mask: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    forward_only: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Trunk forward: embeddings + N dual-track blocks, no output heads.

    Returns (local (B, L, C), global (B, G)) representations — the input
    to the pretraining heads here and to fine-tuning task heads
    (models/finetune.py), which the reference only sketched in
    commented-out code (reference utils.py:348-493, SURVEY C14).

    PACKED rows: pass `segment_ids` (B, L) with annotations shaped
    (B, S, A) per segment; the global representation comes back
    per-segment as (B, S, G) and every cross-position op is segment-
    masked (see block_apply). `forward_only`: see block_apply.
    """
    from proteinbert_tpu.parallel.sharding import (
        gathered_over_fsdp, pin_to_batch_layout,
    )

    dtype = jnp.dtype(cfg.dtype)
    if pad_mask is None:
        pad_mask = (segment_ids > 0 if segment_ids is not None
                    else tokens != PAD_ID)

    def pinned(l, g):
        # Under a mesh the two tracks keep the layout their batch came in
        # with, from block to block, so that it is the WEIGHTS a sharded
        # step moves (parallel/sharding.py); with no mesh, nothing.
        return pin_to_batch_layout(l, positions=1), pin_to_batch_layout(g)

    with jax.named_scope("embed"):
        local = embedding_apply(params["embedding"], tokens, dtype)
        global_ = jax.nn.gelu(
            dense_apply(params["global_in"], annotations.astype(dtype))
        )
        local, global_ = pinned(local, global_)

    body = remat_wrap(
        partial(block_apply, cfg=cfg, segment_ids=segment_ids,
                forward_only=forward_only), cfg)

    if cfg.scan_blocks:
        def scan_body(carry, blk):
            # One all-gather a leaf a block, outside the remat: the
            # backward reads the forward's gathered copy.
            blk = gathered_over_fsdp(blk)
            l, g = pinned(*carry)
            l, g = body(blk, l, g, pad_mask)
            return pinned(l, g), None

        (local, global_), _ = lax.scan(
            scan_body, (local, global_), _cast_blocks(params["blocks"], dtype),
            unroll=cfg.scan_unroll,
            _split_transpose=cfg.scan_split_transpose,
        )
    else:
        for blk in params["blocks"]:
            local, global_ = pinned(
                *body(gathered_over_fsdp(blk), local, global_, pad_mask))
    return pinned(local, global_)


def encode_trunk(
    params: Params,
    tokens: jax.Array,
    cfg: ModelConfig,
    annotations: Optional[jax.Array] = None,
    pad_mask: Optional[jax.Array] = None,
) -> Dict[str, jax.Array]:
    """The SHARED representation every task head consumes (ISSUE 8).

    One forward through the trunk, packaged for split-apply serving:
    `{"local": (B, L, C), "global": (B, G), "pad_mask": (B, L) bool}`.
    Any registered head (heads/apply.py) — and the monolithic
    models/finetune.apply — runs off exactly this dict, so the
    expensive computation is executed once per micro-batch and the
    cheap per-head tails are appended (the operator-fusion-for-
    inference batching shape, PAPERS.md).

    `annotations` defaults to the all-zero "no annotations known"
    input (the same convention as models/finetune.apply — it is the
    trained hide-all-annotations branch, so a zero global input is
    in-distribution for the trunk). Extra keys in `params` (a pretrain
    checkpoint's `local_head`/`global_head`) are ignored: pretrain
    params and a stripped finetune trunk encode identically.
    """
    if pad_mask is None:
        pad_mask = tokens != PAD_ID
    if annotations is None:
        annotations = jnp.zeros(
            (tokens.shape[0], cfg.num_annotations), jnp.float32)
    local, global_ = encode(params, tokens, annotations, cfg, pad_mask)
    return {"local": local, "global": global_, "pad_mask": pad_mask}


def apply(
    params: Params,
    tokens: jax.Array,
    annotations: jax.Array,
    cfg: ModelConfig,
    pad_mask: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    forward_only: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Forward pass.

    Args:
      tokens: (B, L) int token ids (the corrupted "local" input).
      annotations: (B, A) float annotation vector (the corrupted "global"
        input; reference input contract at modules.py:295-304) — or
        (B, S, A) per-segment vectors when `segment_ids` is passed.
      pad_mask: (B, L) bool, True at real positions; derived from tokens
        (or segment_ids) if omitted.
      segment_ids: optional (B, L) int segment map for PACKED rows
        (data/packing.py); 0 = pad, 1..S = packed protein index.
      forward_only: the program will not be differentiated (see
        block_apply); a training or evaluation step never passes it.
    Returns:
      (local_logits (B, L, V), global_logits (B, A)) — LOGITS, in
      float32; global_logits is (B, S, A) when packed.
    """
    local, global_ = encode(params, tokens, annotations, cfg, pad_mask,
                            segment_ids, forward_only)
    with jax.named_scope("heads"):
        local_logits = dense_apply(
            params["local_head"], local).astype(jnp.float32)
        global_logits = dense_apply(
            params["global_head"], global_).astype(jnp.float32)
    return local_logits, global_logits


def param_count(params: Params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))
