"""Pallas TPU kernel: ragged global attention (ISSUE 13 tentpole).

The global track attends over the local track with one query set per
protein (ops/attention.py). On PACKED rows the masked-XLA form
(`packed_global_attention_apply`) materialises a (B, S, H, L) float32
score tensor and (B, S, L) boolean segment masks in HBM — per layer.
Following Ragged Paged Attention (PAPERS.md), this kernel consumes the
packed segment layout natively instead: per batch row, the whole
attention chain — Q/K/V projections, per-segment q·K scores, masked
softmax, weighted-V reduction — runs in one VMEM-resident pass, with
segment membership carried as the same (L, S) one-hot block the fused
local-track kernel rides (`_seg_tap_matmuls`' trick): the one-hot IS
the mask, applied in (L, S) score layout with no transposes and no
materialised (B, S, L)/(B, S, H, L) tensors.

Per head h (static loop — H is small), one grid step per batch row:

  K_h = tanh(local · wk[h])        (L, C) @ (C, k) -> (L, k)
  V_h = gelu(local · wv[h])        (L, C) @ (C, v) -> (L, v)
  q_h = tanh(global · wq[h])       (S, G) @ (G, k) -> (S, k)
  scores = K_h · q_hᵀ / sqrt(k)    MXU A·Bᵀ       -> (L, S) fp32
  masked softmax over L            one-hot mask, exact-0 cross-segment
  out_h = weightsᵀ · V_h           MXU Aᵀ·B       -> (S, v)

Heads concatenate to (S, G); empty segment slots are zeroed exactly as
the reference (`zero_empty`) so the (B, S, G) state stays leak-proof.
Cross-segment contributions are exact 0.0 (the -1e30 mask's exp
underflows to +0.0 in float32 and 0·v terms add exactly nothing), so
the leakage test asserts BIT-identity (tests/test_attention_kernel.py).

The DENSE (S=1) entry phrases plain pad-masked attention as the same
kernel with the pad mask as a one-column one-hot and `zero_empty=False`
(an all-pad row keeps the reference's uniform softmax), so the bucketed
serve path and unpacked training share the kernel with packed training
and ragged serving — no supported shape leaves the fast path.

Backward mirrors the fused block's remat contract: a custom VJP whose
backward recomputes the plain-JAX one-hot composition
(`attention_oh_reference`) and differentiates it, saving only
(params, local, global, one-hot).

Dispatch is guarded by `pallas_attention_supported` (VMEM-priced) with
the masked-XLA reference as fallback; every decision feeds the
two-sided `ATTN_PATH_TOTAL` / `attention_kernel_path_total{path=,
reason=}` counter (kernels/path_counter.py — same machinery as the
fused block's `fused_kernel_path_total`), and the shared
PBT_FORCE_REFERENCE_KERNEL debug override forces the reference path
for this kernel family too (reason=forced, read at trace time).
"""

from __future__ import annotations

import functools
import logging
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from proteinbert_tpu.kernels import vmem_budget as _vb
from proteinbert_tpu.kernels.fused_block import (
    dequant_params,
    force_reference_requested,
    is_quant_leaf,
    weight_leaf,
)
from proteinbert_tpu.kernels.path_counter import KernelPathCounter
from proteinbert_tpu.kernels.vmem_budget import lanes as _lanes

Params = Dict[str, jax.Array]

# Two-sided fast-path accounting for the attention family (ISSUE 13):
# same trace-time granularity and reason vocabulary as the fused
# block's PATH_TOTAL —
#   pallas/packed     — the segment-aware kernel (packed rows)
#   pallas/dense      — the S=1 entry (bucketed serving / unpacked)
#   reference/segments          — packed shape with no VMEM plan
#   reference/unsupported_shape — dense shape with no VMEM plan
#   reference/forced            — PBT_FORCE_REFERENCE_KERNEL override
logger = logging.getLogger(__name__)

_COUNTER = KernelPathCounter("global-attention kernel",
                             "attention_kernel_path_total", log=logger)
ATTN_PATH_TOTAL: Dict[Tuple[str, str], int] = _COUNTER.total
# Shape-keyed one-time-warning latch (same contract as
# fused_block._FALLBACK_WARNED).
_FALLBACK_WARNED: set = _COUNTER._warned


def register_attention_path_observer(cb) -> None:
    """`cb(path, reason)` on every attention dispatch bump (trace
    time) — the coverage feed for `attention_kernel_path_total`."""
    _COUNTER.register(cb)


def unregister_attention_path_observer(cb) -> None:
    _COUNTER.unregister(cb)


def note_attention_path(path: str, reason: str,
                        shape: Optional[tuple] = None) -> None:
    _COUNTER.note(path, reason, shape)


def pallas_attention_supported(
    local_dim: int, global_dim: int, seq_len: int, max_segments: int,
    key_dim: int, num_heads: int, dtype: str = "bfloat16",
) -> bool:
    """Whether the attention kernel handles this shape+dtype within the
    VMEM budget (else the dispatch falls back to the masked-XLA
    reference). Unlike the fused local track, the weights here are tiny
    (H·(G+2C)·k-ish), so the whole ProteinBERT range — including the
    Large C=1024 — prices in; the budget is dominated by the (L, C)
    activation row and the per-head fp32 temporaries. `max_segments` is
    1 for the dense entry."""
    if not _vb.shape_prechecks(local_dim, seq_len, max_segments):
        return False
    if global_dim < 1 or global_dim % num_heads:
        return False
    item = _vb.itemsize(dtype)
    C, G, L, S, H, k = (local_dim, global_dim, seq_len, max_segments,
                        num_heads, key_dim)
    # Blocks whose index map varies with b are double-buffered by the
    # pipeline; weight blocks are whole (single buffer).
    row = 2 * L * C * item
    oh = 2 * L * _lanes(S) * item
    gseg = 2 * S * _lanes(G) * item
    out = 2 * S * _lanes(G) * item
    weights = _vb.attention_weight_bytes(C, G, k, H, item)
    temps = _vb.attention_temp_bytes(L, S, G, k, H)
    return _vb.fits(row, oh, gseg, out, weights, temps)


def attention_oh_reference(
    params: Params, local: jax.Array, global_seg: jax.Array,
    seg_oh: jax.Array, zero_empty: bool = True,
) -> jax.Array:
    """Plain-JAX ground truth of the attention kernel, phrased in the
    one-hot form the kernel consumes: `seg_oh` (B, L, S) is 1.0 where
    position l belongs to segment s AND is a real token (0.0 at pad,
    halo, and masked-out serving <pad> spans). Bit-compatible with
    `packed_global_attention_apply(params, local, global_, segment_ids,
    real_mask)` when seg_oh = onehot(segment_ids)·real_mask (the
    boolean mask `seg_oh > 0` reproduces its `seg_mask` exactly). The
    kernel's custom VJP rematerialises and differentiates THIS
    composition. `zero_empty=False` is the dense (S=1) entry's
    semantics: an all-masked row keeps the uniform softmax of
    `global_attention_apply` instead of a zero output."""
    dtype = local.dtype
    wq = params["wq"].astype(dtype)
    wk = params["wk"].astype(dtype)
    wv = params["wv"].astype(dtype)
    key_dim = wq.shape[-1]

    q = jnp.tanh(jnp.einsum("bsg,hgk->bshk", global_seg.astype(dtype), wq))
    k = jnp.tanh(jnp.einsum("blc,hck->bhlk", local, wk))
    v = jax.nn.gelu(jnp.einsum("blc,hcv->bhlv", local, wv))

    scores = jnp.einsum("bshk,bhlk->bshl", q, k) / jnp.sqrt(
        jnp.asarray(key_dim, dtype)
    )
    scores = scores.astype(jnp.float32)
    mask = jnp.transpose(seg_oh, (0, 2, 1)) > 0  # (B, S, L)
    scores = jnp.where(mask[:, :, None, :], scores, jnp.float32(-1e30))
    weights = jax.nn.softmax(scores, axis=-1).astype(dtype)

    out = jnp.einsum("bshl,bhlv->bshv", weights, v)
    if zero_empty:
        seg_exists = mask.any(axis=-1)  # (B, S)
        out = jnp.where(seg_exists[:, :, None, None], out,
                        jnp.zeros((), dtype))
    b, s, h, vd = out.shape
    return out.reshape(b, s, h * vd)


def _attention_body(
    x, oh, g, wq, wk, wv,
    *, key_dim, num_heads, zero_empty,
):
    """The whole VMEM-resident attention chain on VALUES: `x` (L, C)
    activations, `oh` (L, S) one-hot mask, `g` (S, G) global rows,
    `wq`/`wk`/`wv` the (H, ·, ·) projections (refs or arrays — only
    indexed). Factored out of `_attention_kernel` so the one-pass trunk
    kernel (kernels/one_pass.py, ISSUE 16) can feed it the local-track
    output it just computed WITHOUT an HBM round-trip. Returns the
    (S, G) output in x's dtype."""
    dtype = x.dtype
    inv_scale = 1.0 / jnp.sqrt(jnp.asarray(key_dim, jnp.float32))
    S = g.shape[0]
    # The mask stays fp32 until its compare: the v5e VPU has no bf16
    # comparison.
    oh32 = oh.astype(jnp.float32)
    live = oh32 > 0
    if zero_empty:
        # Empty segment slots are zeroed through their softmax WEIGHTS,
        # in the (L, S) layout the scores already have: a (1, S) row
        # broadcasts down the sublanes, where zeroing the (S, G) output
        # instead would need the row turned into an (S, 1) column — a
        # relayout Mosaic refuses for a mask vector.
        seg_exists = (jnp.sum(oh32, axis=0, keepdims=True)
                      > 0).astype(jnp.float32)

    heads = []
    for h in range(num_heads):
        q_h = jnp.tanh(lax.dot_general(
            g, wq[h], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dtype))  # (S, k)
        k_h = jnp.tanh(lax.dot_general(
            x, wk[h], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dtype))  # (L, k)
        v_h = jax.nn.gelu(lax.dot_general(
            x, wv[h], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dtype))  # (L, v)

        # (L, S) scores: position l's score against segment s's query —
        # A·Bᵀ on the MXU; the one-hot applies as-is, no transposes.
        if S == 1:
            # One query row is a matrix-VECTOR product; written out as
            # the fp32 multiply + lane reduction it is, because Mosaic's
            # own rewrite of that case refuses bf16 operands.
            scores = jnp.sum(
                k_h.astype(jnp.float32) * q_h.astype(jnp.float32),
                axis=1, keepdims=True)
        else:
            scores = lax.dot_general(
                k_h, q_h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        scores = jnp.where(live, scores * inv_scale, jnp.float32(-1e30))
        # Masked softmax over L (axis 0): -1e30 entries underflow to
        # exact +0.0 after the max shift, so cross-segment V rows
        # contribute exact zeros to the weighted sum (bit-identity,
        # tests/test_attention_kernel.py). An all-masked column yields
        # the uniform 1/L weights of the XLA reference; the packed
        # entry zeroes those segments below.
        m = jnp.max(scores, axis=0, keepdims=True)
        e = jnp.exp(scores - m)
        w = e / jnp.sum(e, axis=0, keepdims=True)
        if zero_empty:
            w = w * seg_exists
        w = w.astype(dtype)
        # (S, v) = weightsᵀ · V — Aᵀ·B on the MXU.
        heads.append(lax.dot_general(
            w, v_h, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ))
    # Head-major assembly as Σ_h out_h @ E_h with E_h the static
    # (v, G) slot selector — a contraction, NOT a concatenate: the
    # SPMD partitioner handles sharded-operand contractions inside the
    # interpreted grid loop exactly (partial sums + all-reduce), while
    # a concatenate whose pieces ride an fsdp-sharded value-dim (the
    # ZeRO/fsdp state shards every param's last axis) was observed to
    # produce silently wrong lanes on jax 0.4.x CPU — the
    # tests/multidevice_packed_child.py zero_pallas parity gate pins
    # this. The selector matmuls are (S, v) @ (v, G) — negligible.
    v_dim = heads[0].shape[1]
    G = num_heads * v_dim
    eye = jnp.eye(v_dim, dtype=jnp.float32)
    out = None
    for h, out_h in enumerate(heads):
        sel = jnp.pad(eye, ((0, 0), (h * v_dim, G - (h + 1) * v_dim)))
        part = lax.dot_general(out_h, sel, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
        out = part if out is None else out + part  # (S, G) fp32
    return out.astype(dtype)


def _attention_kernel(
    x_ref, oh_ref, g_ref, wq_ref, wk_ref, wv_ref,
    *rest,
    key_dim, num_heads, zero_empty, quantized=False,
):
    out_ref = rest[-1]
    dtype = x_ref.dtype
    if quantized:
        # int8 projections + per-channel scales are VMEM-resident; the
        # q·scale dequant (fp32 multiply, cast to the activation dtype)
        # runs per grid step inside the kernel — bit-identical numerics
        # to the HLO dequant, int8 bytes on the HBM wire (ISSUE 16).
        wqs_ref, wks_ref, wvs_ref = rest[0], rest[1], rest[2]
        wq = (wq_ref[:].astype(jnp.float32) * wqs_ref[:]).astype(dtype)
        wk = (wk_ref[:].astype(jnp.float32) * wks_ref[:]).astype(dtype)
        wv = (wv_ref[:].astype(jnp.float32) * wvs_ref[:]).astype(dtype)
    else:
        wq, wk, wv = wq_ref, wk_ref, wv_ref
    out_ref[0] = _attention_body(
        x_ref[0], oh_ref[0], g_ref[0], wq, wk, wv,
        key_dim=key_dim, num_heads=num_heads, zero_empty=zero_empty)


def _pallas_attention_forward(
    params: Params, local: jax.Array, global_seg: jax.Array,
    seg_oh: jax.Array, zero_empty: bool, interpret: bool,
) -> jax.Array:
    B, L, C = local.shape
    S, G = global_seg.shape[1], global_seg.shape[2]
    dtype = local.dtype
    quantized = is_quant_leaf(params["wq"])
    if quantized:
        wq, wk, wv = (params[n]["q"] for n in ("wq", "wk", "wv"))
        # (H, k)/(H, v) scales reshaped to (H, 1, ·) so the in-kernel
        # q·scale multiply broadcasts per output channel exactly like
        # dequantize_params' scale[..., None, :].
        scales = tuple(
            params[n]["scale"][:, None, :].astype(jnp.float32)
            for n in ("wq", "wk", "wv"))
    else:
        wq = params["wq"].astype(dtype)  # (H, G, k)
        wk = params["wk"].astype(dtype)  # (H, C, k)
        wv = params["wv"].astype(dtype)  # (H, C, v)
        scales = ()
    H, _, key_dim = wq.shape

    def whole(a):
        return pl.BlockSpec(a.shape, lambda b: (0,) * a.ndim,
                            memory_space=pltpu.VMEM)

    # Projections dominate: 2·L·C·(k+v) + 2·S·G·k MACs per head, plus
    # the O(L·S·(k+v)) score/reduce matmuls.
    v_dim = G // H
    flops = 2 * B * H * (L * C * (key_dim + v_dim) + S * G * key_dim
                         + L * S * (key_dim + v_dim))
    cost = pl.CostEstimate(
        flops=flops,
        bytes_accessed=local.size * local.dtype.itemsize * 2,
        transcendentals=B * H * L * (key_dim + v_dim + S),
    )
    kernel = functools.partial(
        _attention_kernel, key_dim=key_dim, num_heads=H,
        zero_empty=zero_empty, quantized=quantized,
    )
    return pl.pallas_call(
        kernel,
        name="pbt_global_attention",
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, L, C), lambda b: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, L, S), lambda b: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S, G), lambda b: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            whole(wq), whole(wk), whole(wv),
            *[whole(s) for s in scales],
        ],
        out_specs=pl.BlockSpec((1, S, G), lambda b: (b, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, S, G), dtype),
        cost_estimate=cost,
        interpret=interpret,
    )(local, seg_oh.astype(dtype), global_seg.astype(dtype), wq, wk, wv,
      *scales)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_attention(
    params: Params, local: jax.Array, global_seg: jax.Array,
    seg_oh: jax.Array, zero_empty: bool = True, interpret: bool = False,
) -> jax.Array:
    """Attention kernel under the fused block's memory contract:
    Pallas forward, rematerialised backward (the VJP recomputes
    `attention_oh_reference` and differentiates it, saving only
    params, local, global_seg, seg_oh)."""
    return _pallas_attention_forward(params, local, global_seg, seg_oh,
                                     zero_empty, interpret)


def _fwd_attention(params, local, global_seg, seg_oh,
                   zero_empty, interpret):
    y = _pallas_attention_forward(params, local, global_seg, seg_oh,
                                  zero_empty, interpret)
    return y, (params, local, global_seg, seg_oh)


def _bwd_attention(zero_empty, interpret, res, g):
    params, local, global_seg, seg_oh = res
    _, vjp = jax.vjp(
        lambda p, xx, gg, oo: attention_oh_reference(
            p, xx, gg, oo, zero_empty
        ),
        params, local, global_seg, seg_oh,
    )
    return vjp(g)


_fused_attention.defvjp(_fwd_attention, _bwd_attention)


def _segment_one_hot(segment_ids: jax.Array, S: int, dtype,
                     real_mask: Optional[jax.Array] = None) -> jax.Array:
    """(B, L) segment ids (+ optional real-token mask) → the (B, L, S)
    one-hot block the kernel consumes. Ids outside 1..S and masked-out
    positions get all-zero rows (= fully masked)."""
    oh = (segment_ids[..., None]
          == jnp.arange(1, S + 1, dtype=segment_ids.dtype)
          ).astype(dtype)
    if real_mask is not None:
        oh = oh * real_mask[..., None].astype(dtype)
    return oh


def fused_packed_attention(
    params: Params,
    local: jax.Array,
    global_: jax.Array,
    segment_ids: jax.Array,
    real_mask: Optional[jax.Array] = None,
    *,
    interpret: bool,
) -> jax.Array:
    """Per-segment global attention over a packed row — the dispatch
    that closes the attention leg of ROADMAP item 3: on supported
    shapes (`pallas_attention_supported`) the Pallas kernel consumes
    the segment layout natively; unsupported shapes (and the
    PBT_FORCE_REFERENCE_KERNEL debug override) take the masked-XLA
    reference `packed_global_attention_apply` — semantically
    identical. Same signature/semantics as the reference: `global_`
    is the per-segment (B, S, G) track, `real_mask` the ragged-serving
    real-token mask (None = every in-segment position is real).

    Every dispatch counts in `ATTN_PATH_TOTAL[(path, reason)]` at
    trace time: ("pallas", "packed") on the fast path, ("reference",
    "segments"|"forced") otherwise, with a one-time warning per
    (reason, shape)."""
    from proteinbert_tpu.ops.attention import packed_global_attention_apply

    B, L, C = local.shape
    S, G = global_.shape[1], global_.shape[2]
    H, _, key_dim = weight_leaf(params["wq"]).shape
    quantized = is_quant_leaf(params["wq"])
    shape_key = (B, L, C, S, G, str(jnp.dtype(local.dtype)))
    if force_reference_requested():
        reason = "forced"
    elif pallas_attention_supported(C, G, L, S, key_dim, H,
                                    local.dtype):
        reason = None
    else:
        reason = "segments"
    if reason is None:
        note_attention_path("pallas", "packed", shape_key)
        oh = _segment_one_hot(segment_ids, S, local.dtype, real_mask)
        if quantized:
            # Inference-only int8 path: in-kernel dequant, no VJP
            # (quantized params carry no gradient contract).
            return _pallas_attention_forward(params, local, global_, oh,
                                             True, interpret)
        return _fused_attention(params, local, global_, oh, True,
                                interpret)
    note_attention_path("reference", reason, shape_key)
    if quantized:
        params = dequant_params(params)
    return packed_global_attention_apply(params, local, global_,
                                         segment_ids, real_mask)


def fused_global_attention(
    params: Params,
    local: jax.Array,
    global_: jax.Array,
    pad_mask: Optional[jax.Array] = None,
    *,
    interpret: bool,
) -> jax.Array:
    """DENSE (unpacked) global attention through the same kernel: the
    (B, G) global track is an S=1 segment set and the pad mask a
    one-column one-hot, so bucketed serving and unpacked training
    share the packed kernel's executable shape family. All-pad rows
    keep the reference's uniform softmax (`zero_empty=False`) — a
    batch-class padding row must stay bit-compatible with
    `global_attention_apply`. Fallback reasons: "unsupported_shape"
    (no VMEM plan), "forced" (debug override)."""
    from proteinbert_tpu.ops.attention import global_attention_apply

    B, L, C = local.shape
    G = global_.shape[-1]
    H, _, key_dim = weight_leaf(params["wq"]).shape
    quantized = is_quant_leaf(params["wq"])
    shape_key = (B, L, C, 1, G, str(jnp.dtype(local.dtype)))
    if force_reference_requested():
        reason = "forced"
    elif pallas_attention_supported(C, G, L, 1, key_dim, H,
                                    local.dtype):
        reason = None
    else:
        reason = "unsupported_shape"
    if reason is None:
        note_attention_path("pallas", "dense", shape_key)
        if pad_mask is None:
            oh = jnp.ones((B, L, 1), local.dtype)
        else:
            oh = pad_mask[..., None].astype(local.dtype)
        if quantized:
            out = _pallas_attention_forward(params, local,
                                            global_[:, None, :], oh,
                                            False, interpret)
        else:
            out = _fused_attention(params, local, global_[:, None, :],
                                   oh, False, interpret)
        return out.reshape(B, G)
    note_attention_path("reference", reason, shape_key)
    if quantized:
        params = dequant_params(params)
    return global_attention_apply(params, local, global_, pad_mask)
