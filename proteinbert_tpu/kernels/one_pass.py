"""Pallas TPU kernel: one-pass trunk (ISSUE 16 tentpole).

PR 12 put every supported shape on a Pallas fast path, but a
ProteinBERT layer still ran as TWO kernels — the fused local track
(kernels/fused_block.py) and the ragged global attention
(kernels/attention.py) — with the (B, L, C) local activations
round-tripping through HBM between them, and the (B, L, S) segment
one-hot materialised once per kernel. Following the
operator-fusion-for-inference direction (PAPERS.md) this kernel runs
BOTH tracks in one VMEM-resident grid program per batch row:

  window  = x row + conv halo                      (Lp, C)   VMEM
  oh      = segment one-hot + halo                 (Lp, S)   VMEM, ONCE
  local   = conv track (tap matmuls, masked by oh) + LN/dense/LN tail
  attn    = _attention_body(local, oh·real, g)     per-head chain

The inter-track activation (`local`) never leaves VMEM on its way into
the attention projections — it is written to HBM once, as the OUTPUT —
and the one-hot block is shared between the cross-segment conv masks
(`_seg_tap_matmuls`) and the attention mask (oh·real), instead of
being built twice. Cross-segment contributions stay exact +0.0 in both
tracks (multiplication by a zero mask / exp-underflow after the max
shift — the same bit-identity the two constituent kernels prove in
tests/test_packing.py and tests/test_attention_kernel.py).

The DENSE (S=1) entry (`fused_onepass_dense`) phrases unpacked rows as
the same program: unmasked taps, a (B, 1, C) broadcast row, the pad
mask as a one-column one-hot feeding ONLY the attention mask, and
`zero_empty=False` so an all-pad row keeps the reference's uniform
softmax — bucketed serving and unpacked training share the executable
shape family with packed training and ragged serving.

Backward matches the fused-block remat contract: a custom VJP whose
backward recomputes the plain-JAX composition (`onepass_oh_reference`
— the segment/dense track reference followed by
`attention_oh_reference`) and differentiates it, saving only
(params, x, broadcast, global, one-hot, real).

int8 leg: when the params carry `quantize_params` leaves
({"q": int8, "scale": fp32}), the kernel loads the int8 weights and
per-channel scales into VMEM and dequantizes per-tile INSIDE the
program (`q·scale` in fp32, cast to the activation dtype — numerics
bit-identical to the HLO dequant, int8 bytes on the HBM wire). The
quantized path is inference-only and skips the custom-VJP wrapper.

Dispatch is guarded by `pallas_onepass_supported` — the UNION working
set priced with the shared kernels/vmem_budget.py primitives. There is
deliberately NO channel-tiled one-pass variant: the attention chain
needs the full (L, C) local row resident, so beyond MAX_PALLAS_DIM the
dispatch falls back to the existing two-kernel composition (each leg
keeping its own guard, counter family and int8 handling) with a typed
reason. Every decision feeds the third KernelPathCounter family,
`ONEPASS_PATH_TOTAL` / `onepass_kernel_path_total{path=,reason=}`,
mirrored into Server.stats()["onepass_path"] and
`pbt diagnose --serve` exactly like the fused/attention families.
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
from proteinbert_tpu.kernels.attention import (
    _attention_body,
    _segment_one_hot,
    attention_oh_reference,
    fused_global_attention,
    fused_packed_attention,
)
from proteinbert_tpu.kernels.fused_block import (
    MAX_PALLAS_DIM,
    _finish_row,
    _gelu,
    _seg_tap_matmuls,
    _tap_matmuls,
    dequant_params,
    force_reference_requested,
    fused_local_track,
    fused_local_track_segments,
    is_quant_leaf,
    local_track_reference,
    local_track_segment_oh_reference,
    note_kernel_path,
    pallas_supported,
    weight_leaf,
)
from proteinbert_tpu.kernels.path_counter import KernelPathCounter

Params = Dict[str, jax.Array]

# Third two-sided fast-path family (ISSUE 16): same trace-time
# granularity and reason vocabulary as the fused block's PATH_TOTAL and
# the attention family's ATTN_PATH_TOTAL —
#   pallas/packed     — the one-pass program ran on a packed row
#   pallas/dense      — the S=1 entry (bucketed serving / unpacked)
#   reference/segments          — packed shape with no one-pass plan
#                                 (falls back to the TWO-KERNEL
#                                 composition, which counts its own
#                                 families as usual)
#   reference/unsupported_shape — dense shape with no one-pass plan
#   reference/forced            — PBT_FORCE_REFERENCE_KERNEL override
logger = logging.getLogger(__name__)

_COUNTER = KernelPathCounter("one-pass trunk kernel",
                             "onepass_kernel_path_total", log=logger)
ONEPASS_PATH_TOTAL: Dict[Tuple[str, str], int] = _COUNTER.total
# Shape-keyed one-time-warning latch (same contract as
# fused_block._FALLBACK_WARNED / attention._FALLBACK_WARNED).
_FALLBACK_WARNED: set = _COUNTER._warned


def register_onepass_path_observer(cb) -> None:
    """`cb(path, reason)` on every one-pass dispatch bump (trace time)
    — the coverage feed for `onepass_kernel_path_total`."""
    _COUNTER.register(cb)


def unregister_onepass_path_observer(cb) -> None:
    _COUNTER.unregister(cb)


def note_onepass_path(path: str, reason: str,
                      shape: Optional[tuple] = None) -> None:
    _COUNTER.note(path, reason, shape)


def pallas_onepass_supported(
    local_dim: int, global_dim: int, seq_len: int, max_segments: int,
    key_dim: int, num_heads: int, dtype: str = "bfloat16",
    narrow_taps: int = 9, wide_taps: int = 9,
    wide_dilation: int = 5, narrow_dilation: int = 1,
) -> bool:
    """Whether the one-pass program handles this shape+dtype within the
    VMEM budget. The working set is the UNION of the two constituent
    kernels' (both weight sets, the haloed row + one-hot, the full-L
    conv temporaries AND the attention temporaries, plus the resident
    local output feeding the attention chain), priced with the shared
    kernels/vmem_budget.py primitives — so shapes whose two halves
    individually fit can honestly fail here and fall back to the
    two-kernel composition.

    Structural preconditions beyond the shared `shape_prechecks`: odd
    tap counts (the symmetric-halo layout), head-divisible G, and
    sublane-aligned (multiple-of-8) key/value head widths — the fused
    program keeps the per-head fp32 partials resident next to the conv
    scratch, and a ragged head width would force a layout repack
    between the two tracks (no preset shape has one; those shapes stay
    on the two-kernel path). There is NO channel-tiled one-pass
    variant: the attention chain needs the full (L, C) local row
    resident, so C > MAX_PALLAS_DIM always defers."""
    if not _vb.shape_prechecks(local_dim, seq_len, max_segments):
        return False
    if global_dim < 1 or global_dim % num_heads:
        return False
    if narrow_taps % 2 == 0 or wide_taps % 2 == 0:
        return False
    if key_dim % 8 or (global_dim // num_heads) % 8:
        return False
    if local_dim > MAX_PALLAS_DIM:
        return False
    item = _vb.itemsize(dtype)
    C, G, L, S = local_dim, global_dim, seq_len, max_segments
    H, k = num_heads, key_dim
    halo = max((narrow_taps - 1) // 2 * narrow_dilation,
               (wide_taps - 1) // 2 * wide_dilation)
    Lp = L + 2 * halo
    # Blocks whose index map varies with b are double-buffered by the
    # pipeline; weight blocks are whole (single buffer).
    row = 2 * Lp * C * item
    oh_row = 2 * Lp * _vb.lanes(S) * item
    real_col = 2 * L * _vb.lanes(1) * item
    bcast = 2 * S * C * item
    gseg = 2 * S * _vb.lanes(G) * item
    out_local = 2 * L * C * item
    out_attn = 2 * S * _vb.lanes(G) * item
    weights = (_vb.track_weight_bytes(C, narrow_taps, wide_taps, item)
               + _vb.attention_weight_bytes(C, G, k, H, item))
    # The conv track runs untiled (tile = L: attention needs the full
    # row anyway), its output stays live into the attention chain, and
    # the tap masks add one (L, S) fp32 temporary.
    temps = (_vb.track_temp_bytes(L, C)
             + L * _vb.lanes(S) * 4
             + L * C * item
             + _vb.attention_temp_bytes(L, S, G, k, H))
    return _vb.fits(row, oh_row, real_col, bcast, gseg, out_local,
                    out_attn, weights, temps)


def onepass_oh_reference(
    track_params: Params, attn_params: Params, x: jax.Array,
    broadcast_seg: jax.Array, global_seg: jax.Array, seg_oh: jax.Array,
    real: jax.Array,
    narrow_dilation: int = 1, wide_dilation: int = 5,
    seg_masked: bool = True, zero_empty: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Plain-JAX ground truth of the one-pass program, phrased in the
    one-hot form the kernel consumes: the constituent kernels' own
    references composed — segment (or dense) local track, then
    attention over `seg_oh · real` (the conv masks deliberately ignore
    `real`: serving `<pad>` spans inside a segment DO participate in
    convs, exactly like the two-kernel path). The custom VJP
    rematerialises and differentiates THIS composition."""
    if seg_masked:
        local = local_track_segment_oh_reference(
            track_params, x, broadcast_seg, seg_oh,
            narrow_dilation, wide_dilation)
    else:
        local = local_track_reference(
            track_params, x, broadcast_seg[:, 0, :],
            narrow_dilation, wide_dilation)
    attn = attention_oh_reference(
        attn_params, local, global_seg,
        seg_oh * real.astype(seg_oh.dtype), zero_empty)
    return local, attn


def _onepass_kernel(
    x_ref, oh_ref, real_ref, bcast_ref, g_ref,
    nk_ref, nb_ref, wk_ref, wb_ref,
    s1_ref, b1_ref, dk_ref, db_ref, s2_ref, b2_ref,
    wq_ref, wak_ref, wav_ref,
    *rest,
    L, halo, narrow_taps, wide_taps, narrow_dilation, wide_dilation,
    key_dim, num_heads, seg_masked, zero_empty, quantized=False,
):
    local_ref, attn_ref = rest[-2], rest[-1]
    dtype = x_ref.dtype
    if quantized:
        # int8 weights + per-channel scales are VMEM-resident; the
        # per-tile dequant (q·scale in fp32, cast to the activation
        # dtype) reproduces the HLO dequant's numerics bit-for-bit
        # (ISSUE 16 second leg), but HBM ships int8 bytes.
        nks, wks, dks, wqs, waks, wavs = rest[0:6]
        nk = (nk_ref[:].astype(jnp.float32) * nks[:]).astype(dtype)
        wk = (wk_ref[:].astype(jnp.float32) * wks[:]).astype(dtype)
        dk = (dk_ref[:].astype(jnp.float32) * dks[:]).astype(dtype)
        wq = (wq_ref[:].astype(jnp.float32) * wqs[:]).astype(dtype)
        wak = (wak_ref[:].astype(jnp.float32) * waks[:]).astype(dtype)
        wav = (wav_ref[:].astype(jnp.float32) * wavs[:]).astype(dtype)
    else:
        nk, wk, dk = nk_ref, wk_ref, dk_ref
        wq, wak, wav = wq_ref, wak_ref, wav_ref

    window = x_ref[0]          # (Lp, C)
    oh_window = oh_ref[0]      # (Lp, S) — shared by BOTH tracks
    x_center = window[halo:halo + L].astype(jnp.float32)
    oh_center = oh_window[halo:halo + L]

    if seg_masked:
        narrow = _seg_tap_matmuls(window, oh_window, nk[:], narrow_taps,
                                  narrow_dilation, halo, L)
        wide = _seg_tap_matmuls(window, oh_window, wk[:], wide_taps,
                                wide_dilation, halo, L)
        # Own-segment broadcast gather as a one-hot matmul: a pad
        # position's all-zero one-hot row receives exact 0.0.
        bcast_pos = lax.dot_general(
            oh_center, bcast_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    else:
        narrow = _tap_matmuls(window, nk[:], narrow_taps,
                              narrow_dilation, halo, L)
        wide = _tap_matmuls(window, wk[:], wide_taps,
                            wide_dilation, halo, L)
        bcast_pos = bcast_ref[0, 0].astype(jnp.float32)[None, :]
    narrow = _gelu(narrow + nb_ref[0].astype(jnp.float32))
    wide = _gelu(wide + wb_ref[0].astype(jnp.float32))

    h = x_center + narrow + wide + bcast_pos
    local_val = _finish_row(h, s1_ref, b1_ref, dk, db_ref,
                            s2_ref, b2_ref, dtype)
    local_ref[0] = local_val
    # The local activations feed the attention chain STRAIGHT from
    # VMEM — the HBM round-trip between the two kernels is the traffic
    # this program exists to eliminate. The attention mask is the same
    # one-hot block the conv masks rode, narrowed to real tokens.
    attn_oh = (oh_center * real_ref[0]).astype(dtype)
    attn_ref[0] = _attention_body(
        local_val, attn_oh, g_ref[0], wq, wak, wav,
        key_dim=key_dim, num_heads=num_heads, zero_empty=zero_empty)


def _pallas_onepass_forward(
    track_params: Params, attn_params: Params, x: jax.Array,
    broadcast_seg: jax.Array, global_seg: jax.Array, seg_oh: jax.Array,
    real: jax.Array,
    narrow_dilation: int, wide_dilation: int,
    seg_masked: bool, zero_empty: bool, interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    nk = track_params["narrow_conv"]["kernel"]
    wk = track_params["wide_conv"]["kernel"]
    quantized = is_quant_leaf(nk)
    narrow_taps = weight_leaf(nk).shape[0]
    wide_taps = weight_leaf(wk).shape[0]
    halo = max((narrow_taps - 1) // 2 * narrow_dilation,
               (wide_taps - 1) // 2 * wide_dilation)
    B, L, C = x.shape
    S, G = global_seg.shape[1], global_seg.shape[2]
    dtype = x.dtype
    x_padded = jnp.pad(x, ((0, 0), (halo, halo), (0, 0)))
    oh_padded = jnp.pad(seg_oh.astype(dtype),
                        ((0, 0), (halo, halo), (0, 0)))
    Lp = L + 2 * halo

    def vec(p):  # (C,) fp32 vector → (1, C) VMEM block
        return p.reshape(1, -1)

    ln1 = track_params["local_ln1"]
    ln2 = track_params["local_ln2"]
    dn = track_params["local_dense"]
    if quantized:
        # int8 weight operands ride as-is; scales are reshaped so the
        # in-kernel q·scale multiply broadcasts per output channel
        # exactly like dequantize_params' scale[..., None, :].
        nk_w, wk_w, dk_w = nk["q"], wk["q"], dn["kernel"]["q"]
        wq_w = attn_params["wq"]["q"]
        wak_w = attn_params["wk"]["q"]
        wav_w = attn_params["wv"]["q"]
        scales = (
            nk["scale"][:, None, :].astype(jnp.float32),
            wk["scale"][:, None, :].astype(jnp.float32),
            dn["kernel"]["scale"].reshape(1, C).astype(jnp.float32),
            attn_params["wq"]["scale"][:, None, :].astype(jnp.float32),
            attn_params["wk"]["scale"][:, None, :].astype(jnp.float32),
            attn_params["wv"]["scale"][:, None, :].astype(jnp.float32),
        )
    else:
        nk_w, wk_w = nk.astype(dtype), wk.astype(dtype)
        dk_w = dn["kernel"].astype(dtype)
        wq_w = attn_params["wq"].astype(dtype)
        wak_w = attn_params["wk"].astype(dtype)
        wav_w = attn_params["wv"].astype(dtype)
        scales = ()
    H, _, key_dim = wq_w.shape
    inputs = (
        x_padded, oh_padded, real.astype(dtype),
        broadcast_seg.astype(dtype), global_seg.astype(dtype),
        nk_w, vec(track_params["narrow_conv"]["bias"]),
        wk_w, vec(track_params["wide_conv"]["bias"]),
        vec(ln1["scale"]), vec(ln1["bias"]),
        dk_w, vec(dn["bias"]),
        vec(ln2["scale"]), vec(ln2["bias"]),
        wq_w, wak_w, wav_w,
    )

    def whole(a):
        return pl.BlockSpec(a.shape, lambda b: (0,) * a.ndim,
                            memory_space=pltpu.VMEM)

    def bmap(shape):
        return pl.BlockSpec(shape, lambda b: (b,) + (0,) * (len(shape) - 1),
                            memory_space=pltpu.VMEM)

    v_dim = G // H
    flops = (2 * B * L * C * C * (narrow_taps + wide_taps + 1)
             + 2 * B * H * (L * C * (key_dim + v_dim)
                            + S * G * key_dim
                            + L * S * (key_dim + v_dim)))
    cost = pl.CostEstimate(
        flops=flops,
        bytes_accessed=x.size * x.dtype.itemsize * 2,
        transcendentals=3 * B * L * C + B * H * L * (key_dim + v_dim + S),
    )
    kernel = functools.partial(
        _onepass_kernel, L=L, halo=halo,
        narrow_taps=narrow_taps, wide_taps=wide_taps,
        narrow_dilation=narrow_dilation, wide_dilation=wide_dilation,
        key_dim=key_dim, num_heads=H,
        seg_masked=seg_masked, zero_empty=zero_empty,
        quantized=quantized,
    )
    return pl.pallas_call(
        kernel,
        name="pbt_onepass",
        grid=(B,),
        in_specs=[
            bmap((1, Lp, C)), bmap((1, Lp, S)), bmap((1, L, 1)),
            bmap((1, S, C)), bmap((1, S, G)),
        ] + [whole(a) for a in inputs[5:]] + [whole(s) for s in scales],
        out_specs=[
            bmap((1, L, C)),
            bmap((1, S, G)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, L, C), dtype),
            jax.ShapeDtypeStruct((B, S, G), dtype),
        ],
        cost_estimate=cost,
        interpret=interpret,
    )(*inputs, *scales)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _fused_onepass(
    track_params: Params, attn_params: Params, x: jax.Array,
    broadcast_seg: jax.Array, global_seg: jax.Array, seg_oh: jax.Array,
    real: jax.Array,
    narrow_dilation: int = 1, wide_dilation: int = 5,
    seg_masked: bool = True, zero_empty: bool = True,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """One-pass program under the fused-block memory contract: Pallas
    forward, rematerialised backward (the VJP recomputes
    `onepass_oh_reference` — conv_out remat tag intact — and
    differentiates it, saving only params/x/broadcast/global/one-hot/
    real)."""
    return _pallas_onepass_forward(
        track_params, attn_params, x, broadcast_seg, global_seg, seg_oh,
        real, narrow_dilation, wide_dilation, seg_masked, zero_empty,
        interpret)


def _fwd_onepass(track_params, attn_params, x, broadcast_seg, global_seg,
                 seg_oh, real, narrow_dilation, wide_dilation, seg_masked,
                 zero_empty, interpret):
    y = _pallas_onepass_forward(
        track_params, attn_params, x, broadcast_seg, global_seg, seg_oh,
        real, narrow_dilation, wide_dilation, seg_masked, zero_empty,
        interpret)
    return y, (track_params, attn_params, x, broadcast_seg, global_seg,
               seg_oh, real)


def _bwd_onepass(narrow_dilation, wide_dilation, seg_masked, zero_empty,
                 interpret, res, g):
    track_params, attn_params, x, broadcast_seg, global_seg, seg_oh, real = res
    _, vjp = jax.vjp(
        lambda tp, ap, xx, bb, gg, oo, rr: onepass_oh_reference(
            tp, ap, xx, bb, gg, oo, rr, narrow_dilation, wide_dilation,
            seg_masked, zero_empty,
        ),
        track_params, attn_params, x, broadcast_seg, global_seg, seg_oh,
        real,
    )
    return vjp(g)


_fused_onepass.defvjp(_fwd_onepass, _bwd_onepass)


def fused_onepass_segments(
    track_params: Params, attn_params: Params, x: jax.Array,
    broadcast_seg: jax.Array, global_seg: jax.Array,
    segment_ids: jax.Array,
    real_mask: Optional[jax.Array] = None,
    narrow_dilation: int = 1, wide_dilation: int = 5,
    *,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    """Whole packed trunk layer — local track AND per-segment global
    attention — as one dispatch (the ISSUE 16 tentpole). On supported
    shapes (`pallas_onepass_supported`) the one-pass program runs;
    otherwise (and under PBT_FORCE_REFERENCE_KERNEL) the existing
    TWO-KERNEL composition runs — `fused_local_track_segments` then
    `fused_packed_attention`, each with its own guard, counter family
    and int8 handling — so no supported shape regresses off the Pallas
    fast path when the fused plan doesn't fit.

    Args match the constituent dispatches: `broadcast_seg` (B, S, C)
    per-segment projected global vectors, `global_seg` (B, S, G),
    `segment_ids` (B, L) with 0 = pad, `real_mask` the ragged-serving
    real-token mask (None = every in-segment position is real; it
    narrows the ATTENTION mask only — `<pad>` spans inside a serving
    segment still participate in convs, both paths).

    Returns (local, attn): the (B, L, C) local track output and the
    (B, S, G) attention output. Every dispatch counts in
    `ONEPASS_PATH_TOTAL[(path, reason)]` at trace time."""
    B, L, C = x.shape
    S, G = global_seg.shape[1], global_seg.shape[2]
    H, _, key_dim = weight_leaf(attn_params["wq"]).shape
    nt = weight_leaf(track_params["narrow_conv"]["kernel"]).shape[0]
    wt = weight_leaf(track_params["wide_conv"]["kernel"]).shape[0]
    quantized = is_quant_leaf(track_params["narrow_conv"]["kernel"])
    shape_key = (B, L, C, S, G, str(jnp.dtype(x.dtype)))
    if force_reference_requested():
        reason = "forced"
    elif pallas_onepass_supported(C, G, L, S, key_dim, H, x.dtype,
                                  nt, wt, wide_dilation, narrow_dilation):
        reason = None
    else:
        reason = "segments"
    if reason is None:
        note_onepass_path("pallas", "packed", shape_key)
        # The conv one-hot must NOT fold in real_mask (serving <pad>
        # spans inside a segment participate in convs); the kernel
        # narrows the attention mask with `real` itself.
        seg_oh = _segment_one_hot(segment_ids, S, x.dtype)
        real = (jnp.ones((B, L, 1), x.dtype) if real_mask is None
                else real_mask[..., None].astype(x.dtype))
        if quantized:
            # Inference-only int8 path: in-kernel dequant, no VJP.
            return _pallas_onepass_forward(
                track_params, attn_params, x, broadcast_seg, global_seg,
                seg_oh, real, narrow_dilation, wide_dilation, True, True,
                interpret)
        return _fused_onepass(
            track_params, attn_params, x, broadcast_seg, global_seg,
            seg_oh, real, narrow_dilation, wide_dilation, True, True,
            interpret)
    note_onepass_path("reference", reason, shape_key)
    local = fused_local_track_segments(
        track_params, x, broadcast_seg, segment_ids,
        narrow_dilation, wide_dilation, interpret)
    attn = fused_packed_attention(
        attn_params, local, global_seg, segment_ids,
        real_mask=real_mask, interpret=interpret)
    return local, attn


def fused_onepass_dense(
    track_params: Params, attn_params: Params, x: jax.Array,
    broadcast: jax.Array, global_: jax.Array,
    pad_mask: Optional[jax.Array] = None,
    narrow_dilation: int = 1, wide_dilation: int = 5,
    *,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    """DENSE (unpacked) trunk layer through the same one-pass program:
    the (B, G) global track is an S=1 segment set, `broadcast` (B, C)
    a one-segment broadcast row, and the pad mask a one-column one-hot
    feeding ONLY the attention mask (the convs stay unmasked, exactly
    like `local_track_reference`). All-pad rows keep the reference's
    uniform softmax (`zero_empty=False`). Fallback is the existing
    two-kernel dense composition — `fused_local_track` (or the XLA
    reference, under its own `fused_kernel_path_total` accounting,
    matching the model's pre-one-pass dispatch) then
    `fused_global_attention`.

    Returns (local, attn): (B, L, C) and (B, G)."""
    B, L, C = x.shape
    G = global_.shape[-1]
    H, _, key_dim = weight_leaf(attn_params["wq"]).shape
    nt = weight_leaf(track_params["narrow_conv"]["kernel"]).shape[0]
    wt = weight_leaf(track_params["wide_conv"]["kernel"]).shape[0]
    quantized = is_quant_leaf(track_params["narrow_conv"]["kernel"])
    shape_key = (B, L, C, 1, G, str(jnp.dtype(x.dtype)))
    forced = force_reference_requested()
    if forced:
        reason = "forced"
    elif pallas_onepass_supported(C, G, L, 1, key_dim, H, x.dtype,
                                  nt, wt, wide_dilation, narrow_dilation):
        reason = None
    else:
        reason = "unsupported_shape"
    if reason is None:
        note_onepass_path("pallas", "dense", shape_key)
        if pad_mask is None:
            oh = jnp.ones((B, L, 1), x.dtype)
        else:
            oh = pad_mask[..., None].astype(x.dtype)
        real = jnp.ones((B, L, 1), x.dtype)
        if quantized:
            local, attn = _pallas_onepass_forward(
                track_params, attn_params, x, broadcast[:, None, :],
                global_[:, None, :], oh, real, narrow_dilation,
                wide_dilation, False, False, interpret)
        else:
            local, attn = _fused_onepass(
                track_params, attn_params, x, broadcast[:, None, :],
                global_[:, None, :], oh, real, narrow_dilation,
                wide_dilation, False, False, interpret)
        return local, attn.reshape(B, G)
    note_onepass_path("reference", reason, shape_key)
    # Two-kernel dense composition — the model's pre-one-pass dispatch,
    # fused_kernel_path_total accounting included.
    tp = dequant_params(track_params) if quantized else track_params
    track_key = (B, L, C, str(jnp.dtype(x.dtype)))
    if forced:
        note_kernel_path("reference", "forced", track_key)
        local = local_track_reference(tp, x, broadcast,
                                      narrow_dilation, wide_dilation)
    elif pallas_supported(C, L, x.dtype, nt, wt, wide_dilation):
        note_kernel_path("pallas", "dense", track_key)
        local = fused_local_track(tp, x, broadcast,
                                  narrow_dilation, wide_dilation, interpret)
    else:
        note_kernel_path("reference", "unsupported_shape", track_key)
        local = local_track_reference(tp, x, broadcast,
                                      narrow_dilation, wide_dilation)
    attn = fused_global_attention(attn_params, local, global_, pad_mask,
                                  interpret=interpret)
    return local, attn
