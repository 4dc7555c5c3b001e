"""Pallas TPU kernel: fused local-track block (SURVEY §7 stage 8).

The local (per-residue) track of a ProteinBERT block is the FLOPs and
bandwidth hot spot (SURVEY §3.4; reference modules.py:201-217):

    h  = x + gelu(narrow_conv(x)) + gelu(wide_conv(x)) + broadcast
    x1 = LN(h)
    y  = LN(x1 + gelu(dense(x1)))

Composed from jax.nn ops, XLA materialises several (B, L, C) intermediates
in HBM. This kernel computes the whole chain in one VMEM-resident pass:

- each 'SAME' dilated conv is lowered to K shifted (TL, C) @ (C, C)
  matmuls on the MXU (an implicit GEMM: tap t of a kernel-size-K,
  dilation-d conv contributes x[l + (t-(K-1)/2)·d] @ W[t]);
- the input is zero-padded by the widest halo (20 rows for k=9, d=5) on
  the host side so every tap is a static in-VMEM slice;
- conv accumulation and LayerNorm statistics are float32; matmul inputs
  stay in the activation dtype (bfloat16 on TPU) so the MXU runs native;
- grid is (B, L/TL); the full padded row sits in VMEM and is re-fetched
  only when the batch index changes (the L-tile axis iterates fastest).

Backward: `fused_local_track` is a jax.custom_vjp whose backward pass
recomputes the plain-JAX composition (`local_track_reference`) and
differentiates it — i.e. the kernel behaves like a rematerialised
(jax.checkpoint) block, saving only (params, x, broadcast).

PACKED rows (data/packing.py) run a SEGMENT-AWARE variant of the same
kernel (`fused_local_track_segments`, ISSUE 10): each tap's shifted
matmul operand is masked by segment-id equality inside the block (a
one-hot lane reduction — exact 0.0 across boundaries, the
`_segment_conv` semantics), and the per-position global→local
broadcast is gathered from each position's own segment IN the kernel
as a (TL, S) @ (S, C) one-hot matmul, so the packed fast path never
materialises the (B, L, C) broadcast tensor. Beyond C = MAX_PALLAS_DIM
a channel-tiled SEGMENT variant runs (`_fused_segment_kernel_tiled`,
ISSUE 13) — ProteinBERT-Large packed rows stay on the fast path.
Shapes neither plan fits fall back to the XLA reference path, counted
in `PATH_TOTAL` / `fused_kernel_path_total{path=,reason=}`.

VMEM budget: weights dominate at 2·K·C² + C² activation-dtype bytes
(~10 MB at C=512 bf16). Up to C = 512 the whole weight set resides in
VMEM and the grid is (B, L/TL). Beyond that (ProteinBERT-Large C=1024)
a CHANNEL-TILED variant runs instead: the grid grows a third, fastest
axis over output-channel tiles of width TC — each step loads only one
conv's (K, C, TC) weight slice and accumulates its (TL, TC) slice of
    gelu(narrow) + gelu(wide)
into a persistent (TL, C) fp32 VMEM scratch (TPU grid steps run
sequentially, so scratch carries across the c-axis); the final c step
adds x + broadcast over the FULL row (static slices only — Mosaic
cannot lower lax.dynamic_slice on materialized values, so nothing may
column-slice x/broadcast by the dynamic grid index) and then computes
LN → dense (+GELU, residual) → LN. The grid order adapts to VMEM:
when an fp32 scratch covering the full (L, C) row set fits, the L-tile
axis runs FASTEST so each conv weight slice stays resident across the
whole L sweep (weight HBM traffic O(weights), not O(B·L/TL·weights));
otherwise the per-row order runs with phase fastest. Shapes the tiled
plan cannot fit either way fall back to the XLA path automatically.

OFFICIAL SCOPE. Two verdicts, one a kind of program, and the ENTRY
POINT tells the kinds apart (no shape does):

- A program that will be DIFFERENTIATED (rounds 2-3, measured on v5e —
  BASELINE.md "Kernel same-batch verdict"): a tiled plan exists only at
  C <= 512 (the full weight set VMEM-resident), but even there the full
  train step LOSES to the remat_policy="convs" XLA path at every
  measured batch (0.478 vs 0.547 MFU at B=256/L=512, round 3) — its
  only full-step win was over NON-remat XLA, a configuration no preset
  uses: the custom VJP recomputes the whole track where XLA keeps its
  saved `conv_out`. At C = 1024 every schedule is weight-bandwidth-
  bound (38 MB of conv weights vs 16 MB VMEM) and the measured kernel
  is 0.88-1.03x XLA. Every preset therefore TRAINS (and evaluates) on
  the XLA path with remat_policy="convs"; there the kernel remains an
  opt-in (`model.use_pallas`) validated for correctness — including
  the Mosaic-only resident-order semantics — by chip_smoke.py (phase
  `kernels`) on real hardware, and is the reference implementation for
  fused-local-track schedules at sharded (seq-parallel) shapes. That
  verdict stands, for training only.
- A FORWARD-ONLY PACKED program (ISSUE 42: the serving and mapping
  entries, `inference._packed_encode_batch`, `_packed_go_probs_batch`,
  `_packed_residue_probs_batch`, `heads/apply.packed_trunk_batch`, and
  through them serve/dispatch.py, mapper/engine.py and the int8 arm of
  parallel/quant.py) runs the SEGMENT kernel with or without
  `model.use_pallas`, on a TPU (`pallas_compiles`) at
  C <= MAX_PALLAS_DIM (`packed_local_track_forward`), wherever the
  guard has a whole-weights-resident plan (`_segment_tile`: the L tile
  comes from the budget, 256 rows at the serving shape 1024 x 512 x 8
  in bfloat16); a shape without one runs XLA's composition and is
  counted `reference/segments`. There XLA's lowering of `_segment_conv` is eighteen
  mask-multiply-product taps a block with the running sum written to
  and read from HBM between taps, bound by memory; the kernel keeps
  the row in VMEM and is bound by the MXU (PERF.md sections 5 and 6
  have the chip's numbers per row class). C = 1024 (`large` served)
  stays on XLA until a cell serves it; everything dense, `train_step`'s
  packed path and `eval_step` are untouched.
"""

from __future__ import annotations

import functools
import logging
import os
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from proteinbert_tpu.kernels.path_counter import KernelPathCounter
from proteinbert_tpu.kernels import vmem_budget as _vb
from proteinbert_tpu.kernels.vmem_budget import (  # noqa: F401
    LANE as _LANE,
    MAX_PALLAS_DIM,
    MAX_TILED_DIM,
    VMEM_BUDGET as _VMEM_BUDGET,
)

logger = logging.getLogger(__name__)

Params = Dict[str, jax.Array]

# Two-sided fast-path accounting (ISSUE 10 satellite): process-wide
# count of kernel dispatch decisions keyed by (path, reason), bumped at
# TRACE time — once per traced BLOCK BODY. Under cfg.scan_blocks (every
# preset) the N blocks share one traced body, so that is once per
# EXECUTABLE — exactly the granularity the MFU question needs ("how
# many of my compiled shapes run the fast path"), not once per step;
# with scan_blocks=False an executable contributes num_blocks bumps
# (all on the same path — the ratio, and the zero-miss gates, are
# unaffected). Paths are
# "pallas" (the fused kernel ran) and "reference" (the XLA composition
# ran); reasons label WHY/WHAT:
#   pallas/dense      — the unpacked fused kernel
#   pallas/packed     — the segment-aware fused kernel (packed rows)
#   reference/segments          — packed shape the segment kernel has
#                                 no VMEM plan for (C > MAX_PALLAS_DIM,
#                                 non-lane-aligned C, ...)
#   reference/unsupported_shape — dense shape outside pallas_supported
#   reference/forced            — PBT_FORCE_REFERENCE_KERNEL debug
#                                 override (read at trace time)
# `register_path_observer` lets a telemetry owner (serve/server.Server,
# or any trainer holding a registry) mirror bumps into a registry
# counter (`fused_kernel_path_total{path=,reason=}`) so fast-path
# COVERAGE — not just misses — is visible in /metrics, Server.stats()
# and `pbt diagnose --serve`. The mechanics (dict, observers, one-time
# shape-keyed reference warning) live in the shared KernelPathCounter
# (kernels/path_counter.py) so the attention kernel's counter cannot
# drift from this one; the module-level API here is kept verbatim.
_COUNTER = KernelPathCounter("fused local-track kernel",
                             "fused_kernel_path_total", log=logger)
PATH_TOTAL: Dict[Tuple[str, str], int] = _COUNTER.total
# The shape-keyed one-time-warning latch, exposed for tests that reset
# specific (reason, shape) keys to make warning counts deterministic.
_FALLBACK_WARNED: set = _COUNTER._warned

# Debug override: force every fused_local_track_segments dispatch onto
# the XLA reference path. Read at TRACE time — set it before the first
# call of a given (shape, config), or the cached fused executable wins.
FORCE_REFERENCE_ENV = "PBT_FORCE_REFERENCE_KERNEL"


def force_reference_requested() -> bool:
    """Whether the debug override is ON. Parsed like the other PBT_*
    flags: "0"/"false"/empty mean off — a `=0` export must not
    silently force the slow path."""
    return os.environ.get(FORCE_REFERENCE_ENV, "").strip().lower() not in (
        "", "0", "false")


def pallas_interpret() -> bool:
    """Whether a Pallas request runs under the interpreter in this
    process — with `pallas_compiles` below, the ONE place that reads
    the backend. True only on the CPU backend, where the interpreter is
    the only way a TPU kernel can run (the tests and the CPU
    rehearsals); on any other backend it is False, so the request
    compiles for the device or raises. The
    kernels' callers (models/proteinbert.block_apply,
    parallel/seq_parallel.seq_parallel_apply) ask once per trace and
    pass the answer down; no kernel entry looks at the backend."""
    return jax.default_backend() == "cpu"


def pallas_compiles() -> bool:
    """Whether this process's backend compiles a Mosaic kernel: a TPU,
    and nothing else (the kernels hold `pltpu.VMEM` blocks, which no
    other backend lowers). The second and last place that reads the
    backend, for the one caller that takes a kernel nobody asked for by
    name (`packed_local_track_forward`): anything that is not a TPU
    keeps XLA's composition there, where `pallas_interpret` would send
    an explicit `use_pallas` request to the compiler and let it
    raise."""
    return jax.default_backend() == "tpu"


def register_path_observer(cb: Callable[[str, str], None]) -> None:
    """`cb(path, reason)` is invoked on every dispatch bump (trace
    time), both fast-path and reference — the coverage feed."""
    _COUNTER.register(cb)


def unregister_path_observer(cb: Callable[[str, str], None]) -> None:
    _COUNTER.unregister(cb)


def note_kernel_path(path: str, reason: str,
                     shape: Optional[tuple] = None) -> None:
    """Record one kernel dispatch decision (trace time = once per
    executable). `shape` keys the one-time reference warning per
    (reason, call-site shape)."""
    _COUNTER.note(path, reason, shape)

# The VMEM constants (MAX_PALLAS_DIM, MAX_TILED_DIM, _LANE,
# _VMEM_BUDGET) are owned by kernels/vmem_budget.py since ISSUE 16 and
# re-exported above under their historical names.


def _gelu(x):
    return jax.nn.gelu(x)


# ------------------------------------------- int8 weight leaves (ISSUE 16)
# parallel/quant.quantize_params turns every >= 2-D float leaf into
# {"q": int8, "scale": fp32} (symmetric per-output-channel, scale
# reduced over axis -2). The kernel dispatches accept those leaves
# directly so the quantized serving arm loads int8 weights into VMEM
# and dequantizes per-tile INSIDE the kernel. The predicates are
# duplicated from parallel/quant (they must match bit-for-bit) because
# kernels/ cannot import parallel/ without a cycle.


def is_quant_leaf(x) -> bool:
    """Whether `x` is a quantize_params leaf ({"q": int8, "scale":
    fp32}) rather than a plain weight array."""
    return isinstance(x, dict) and set(x) == {"q", "scale"}


def weight_leaf(x):
    """The array carrying a (possibly quantized) weight's SHAPE."""
    return x["q"] if is_quant_leaf(x) else x


def dequant_leaf(x):
    """HLO dequant of one quant leaf — the exact
    parallel/quant.dequantize_params formula, used on kernel paths
    that do not dequantize in-kernel (XLA reference fallbacks and the
    channel-tiled variants)."""
    if is_quant_leaf(x):
        return x["q"].astype(jnp.float32) * x["scale"][..., None, :]
    return x


def dequant_params(params):
    """Dequantize every quant leaf of a param subtree in HLO."""
    return jax.tree.map(dequant_leaf, params, is_leaf=is_quant_leaf)


def local_track_reference(
    params: Params, x: jax.Array, broadcast: jax.Array,
    narrow_dilation: int = 1, wide_dilation: int = 5,
) -> jax.Array:
    """Plain-JAX local track, the kernel's semantic ground truth (and its
    recompute path in the backward pass). Mirrors models/proteinbert.py
    block_apply's local half (reference modules.py:201-217)."""
    from proteinbert_tpu.ops.layers import conv1d_apply, dense_apply, layer_norm_apply

    narrow = _gelu(conv1d_apply(params["narrow_conv"], x, dilation=narrow_dilation))
    wide = _gelu(conv1d_apply(params["wide_conv"], x, dilation=wide_dilation))
    h = layer_norm_apply(
        params["local_ln1"], x + narrow + wide + broadcast[:, None, :]
    )
    return layer_norm_apply(
        params["local_ln2"],
        h + _gelu(dense_apply(params["local_dense"], h)),
    )


def _segment_conv(
    p: Params, x: jax.Array, segment_ids: jax.Array, dilation: int
) -> jax.Array:
    """'SAME' dilated conv whose taps NEVER cross a segment boundary.

    Lowered as K shifted (B, L, C) @ (C, C) matmuls (the same implicit-
    GEMM decomposition the Pallas kernel uses, _tap_matmuls): tap t of a
    kernel-size-K, dilation-d conv reads x[l + (t-(K-1)/2)·d]; here that
    shifted operand is ZEROED wherever its segment id differs from the
    center position's (or the center is pad), so a contribution from
    another packed protein is an exact 0.0 — multiplication by a zero
    mask, not a subtraction — which is what lets the leakage test assert
    BIT-identity across segments (tests/test_packing.py). FLOPs equal
    the plain conv (K·C² MACs/position either way).
    """
    kernel = p["kernel"].astype(x.dtype)
    taps = kernel.shape[0]
    L = x.shape[1]
    # 'SAME' halo, asymmetric for even kernels exactly like
    # conv1d_apply's padding="SAME" (lo = total//2, extra on the right).
    total = (taps - 1) * dilation
    lo = total // 2
    xp = jnp.pad(x, ((0, 0), (lo, total - lo), (0, 0)))
    sp = jnp.pad(segment_ids, ((0, 0), (lo, total - lo)))
    real = segment_ids > 0
    acc = None
    for t in range(taps):
        off = t * dilation
        xs = lax.slice_in_dim(xp, off, off + L, axis=1)
        ss = lax.slice_in_dim(sp, off, off + L, axis=1)
        mask = ((ss == segment_ids) & real).astype(x.dtype)[..., None]
        part = (xs * mask) @ kernel[t]
        acc = part if acc is None else acc + part
    # Same remat tag as conv1d_apply so model.remat_policy="convs" also
    # bites on the packed path; inert without remat.
    return checkpoint_name(acc + p["bias"].astype(x.dtype), "conv_out")


def local_track_segment_reference(
    params: Params, x: jax.Array, broadcast_pos: jax.Array,
    segment_ids: jax.Array,
    narrow_dilation: int = 1, wide_dilation: int = 5,
) -> jax.Array:
    """Segment-aware local track for PACKED rows (data/packing.py).

    Same dataflow as local_track_reference with two changes: the convs
    are boundary-masked (`_segment_conv`), and `broadcast_pos` is
    already per-POSITION (B, L, C) — each position receives its own
    segment's global→local projection (gathered by the model), not one
    row-wide vector.
    """
    from proteinbert_tpu.ops.layers import dense_apply, layer_norm_apply

    narrow = _gelu(_segment_conv(params["narrow_conv"], x, segment_ids,
                                 narrow_dilation))
    wide = _gelu(_segment_conv(params["wide_conv"], x, segment_ids,
                               wide_dilation))
    h = layer_norm_apply(
        params["local_ln1"], x + narrow + wide + broadcast_pos
    )
    return layer_norm_apply(
        params["local_ln2"],
        h + _gelu(dense_apply(params["local_dense"], h)),
    )


def local_track_segment_oh_reference(
    params: Params, x: jax.Array, broadcast_seg: jax.Array,
    seg_oh: jax.Array,
    narrow_dilation: int = 1, wide_dilation: int = 5,
) -> jax.Array:
    """Plain-JAX ground truth of the SEGMENT kernel, phrased in terms
    of the one-hot segment matrix `seg_oh` (B, L, S) — the form the
    kernel consumes — instead of integer segment ids. Tap masks are
    one-hot dot products (Σ_s oh[l]·oh[l+off], exact 0.0/1.0, so a
    cross-segment contribution is an exact zero like `_segment_conv`'s)
    and the own-segment global→local gather is the matmul
    `seg_oh @ broadcast_seg` (a pad position's all-zero one-hot row
    receives exact 0.0). Bit-compatible with gathering (B, L, C)
    broadcast rows and calling `local_track_segment_reference` for
    segment ids in 0..S (the packer contract). The fused kernel's
    backward differentiates THIS composition (rematerialised, like the
    dense kernel's backward differentiates local_track_reference)."""
    from proteinbert_tpu.ops.layers import dense_apply, layer_norm_apply

    oh = seg_oh.astype(x.dtype)
    L = x.shape[1]

    def conv(p, dilation):
        kernel = p["kernel"].astype(x.dtype)
        taps = kernel.shape[0]
        total = (taps - 1) * dilation
        lo = total // 2
        xp = jnp.pad(x, ((0, 0), (lo, total - lo), (0, 0)))
        ohp = jnp.pad(oh, ((0, 0), (lo, total - lo), (0, 0)))
        acc = None
        for t in range(taps):
            off = t * dilation
            xs = lax.slice_in_dim(xp, off, off + L, axis=1)
            ohs = lax.slice_in_dim(ohp, off, off + L, axis=1)
            mask = jnp.sum(oh * ohs, axis=-1, keepdims=True)
            part = (xs * mask.astype(x.dtype)) @ kernel[t]
            acc = part if acc is None else acc + part
        # Same remat tag as _segment_conv/conv1d_apply; inert w/o remat.
        return checkpoint_name(acc + p["bias"].astype(x.dtype), "conv_out")

    narrow = _gelu(conv(params["narrow_conv"], narrow_dilation))
    wide = _gelu(conv(params["wide_conv"], wide_dilation))
    broadcast_pos = jnp.einsum("bls,bsc->blc", oh,
                               broadcast_seg.astype(x.dtype))
    h = layer_norm_apply(
        params["local_ln1"], x + narrow + wide + broadcast_pos
    )
    return layer_norm_apply(
        params["local_ln2"],
        h + _gelu(dense_apply(params["local_dense"], h)),
    )


def gather_segment_broadcast(broadcast_seg: jax.Array,
                             segment_ids: jax.Array) -> jax.Array:
    """(B, S, C) per-segment broadcast + (B, L) segment ids → (B, L, C)
    per-position broadcast, exact 0.0 at pad — the materialised gather
    the fused segment kernel folds into its block (shared by the
    model's non-pallas packed path and the reference fallback here)."""
    idx = jnp.clip(segment_ids - 1, 0)[..., None]
    broadcast_pos = jnp.take_along_axis(broadcast_seg, idx, axis=1)
    return jnp.where((segment_ids > 0)[..., None], broadcast_pos,
                     jnp.zeros((), broadcast_pos.dtype))


def fused_local_track_segments(
    params: Params, x: jax.Array, broadcast_seg: jax.Array,
    segment_ids: jax.Array,
    narrow_dilation: int = 1, wide_dilation: int = 5,
    interpret: bool = False,
) -> jax.Array:
    """Segment-aware fused local track for PACKED rows — the dispatch
    point that closes ROADMAP item 2: on supported shapes
    (`pallas_segments_supported`) the Pallas kernel runs with
    cross-segment boundary masks folded into its tap matmuls AND the
    per-position global→local broadcast gathered from each position's
    own segment INSIDE the block (a one-hot matmul on the MXU), so the
    model never materialises the (B, L, C) broadcast tensor on the
    fast path. Unsupported shapes (and the PBT_FORCE_REFERENCE_KERNEL
    debug override) take the XLA reference path — semantically
    identical, boundary-masked.

    Args:
      broadcast_seg: (B, S, C) PER-SEGMENT projected global vectors
        (gelu(dense(global)) per segment) — NOT the per-position
        (B, L, C) gather.
      segment_ids: (B, L) int, 0 = pad, 1..S = packed protein index
        (ids above S are treated as pad — the packer never emits them).

    Every dispatch counts in `PATH_TOTAL[(path, reason)]` at trace time
    (once per executable): ("pallas", "packed") on the fast path,
    ("reference", "segments"|"forced") otherwise, with a one-time
    warning per (reason, shape). Backward matches the unpacked fused
    path's memory behavior: a custom VJP that recomputes the reference
    composition (saving only params/x/broadcast/one-hot), with the
    conv_out remat tag intact inside the recompute."""
    B, L, C = x.shape
    S = broadcast_seg.shape[1]
    quantized = is_quant_leaf(params["narrow_conv"]["kernel"])
    nk = weight_leaf(params["narrow_conv"]["kernel"])
    wk = weight_leaf(params["wide_conv"]["kernel"])
    shape_key = (B, L, C, S, str(jnp.dtype(x.dtype)))
    if force_reference_requested():
        reason = "forced"
    elif pallas_segments_supported(
            C, L, S, x.dtype, nk.shape[0], wk.shape[0],
            wide_dilation, narrow_dilation):
        reason = None
    else:
        reason = "segments"
    if reason is None:
        note_kernel_path("pallas", "packed", shape_key)
        seg_oh = (segment_ids[..., None]
                  == jnp.arange(1, S + 1, dtype=segment_ids.dtype)
                  ).astype(x.dtype)
        if quantized:
            if C <= MAX_PALLAS_DIM:
                # int8 weights dequantize per-tile IN the kernel
                # (inference-only: the quantized arm never
                # differentiates, so the custom-VJP wrapper is skipped).
                return _pallas_segments_forward(
                    params, x, broadcast_seg, seg_oh,
                    narrow_dilation, wide_dilation, interpret)
            # Channel-tiled range: HLO dequant, still the Pallas path.
            params = dequant_params(params)
        return _fused_segments(params, x, broadcast_seg, seg_oh,
                               narrow_dilation, wide_dilation, interpret)
    note_kernel_path("reference", reason, shape_key)
    if quantized:
        params = dequant_params(params)
    broadcast_pos = gather_segment_broadcast(broadcast_seg, segment_ids)
    return local_track_segment_reference(
        params, x, broadcast_pos, segment_ids, narrow_dilation,
        wide_dilation
    )


def packed_local_track_forward(
    params: Params, x: jax.Array, broadcast_seg: jax.Array,
    segment_ids: jax.Array,
    narrow_dilation: int = 1, wide_dilation: int = 5,
) -> jax.Array:
    """Local track of a packed row in a program that will NOT be
    differentiated (the serving and mapping entries of inference.py and
    heads/apply.py). On a TPU and at C <= MAX_PALLAS_DIM (the whole
    weight set stays in VMEM; the channel-tiled variant is bound by its
    weights' bandwidth and keeps waiting for a cell that serves
    `large`) it is `fused_local_track_segments`, which counts what it
    did: `pallas/packed` where its guard has a plan for the shape,
    `reference/segments` (and a warning, once a shape) where it has
    none. Everywhere else (the CPU, where a served batch never runs the
    interpreter; a backend with no Mosaic compiler; C = 1024) it is
    `local_track_segment_reference` exactly as a differentiated program
    runs it, uncounted as before. A program that will be differentiated
    never comes here: it wants XLA's saved `conv_out`, the kernel's VJP
    recomputes the whole track (module docstring), and no shape tells
    the two apart, so the entry point does."""
    if pallas_compiles() and x.shape[-1] <= MAX_PALLAS_DIM:
        return fused_local_track_segments(
            params, x, broadcast_seg, segment_ids, narrow_dilation,
            wide_dilation, interpret=False)
    return local_track_segment_reference(
        params, x, gather_segment_broadcast(broadcast_seg, segment_ids),
        segment_ids, narrow_dilation, wide_dilation)


def track_halo(params: Params, narrow_dilation: int = 1,
               wide_dilation: int = 5) -> int:
    """Context rows each side a shard needs for exact conv results (20 for
    the reference k=9/d=5 geometry)."""
    nt = params["narrow_conv"]["kernel"].shape[0]
    wt = params["wide_conv"]["kernel"].shape[0]
    return max((nt - 1) // 2 * narrow_dilation, (wt - 1) // 2 * wide_dilation)


def local_track_valid_reference(
    params: Params, xh: jax.Array, broadcast: jax.Array,
    narrow_dilation: int = 1, wide_dilation: int = 5,
) -> jax.Array:
    """Local track on a PRE-HALOED shard: `xh` is (B, L + 2·halo, C) whose
    first/last `halo` rows are real neighbor context (sequence
    parallelism, parallel/halo.py) rather than zeros; output is the (B, L,
    C) center. Semantically equals slicing rows [halo, halo+L) out of
    local_track_reference applied to the neighbor-stitched sequence."""
    from proteinbert_tpu.ops.layers import dense_apply, layer_norm_apply

    H = track_halo(params, narrow_dilation, wide_dilation)
    L = xh.shape[1] - 2 * H

    def valid_conv(p, dilation):
        y = lax.conv_general_dilated(
            xh, p["kernel"].astype(xh.dtype), window_strides=(1,),
            padding="VALID", rhs_dilation=(dilation,),
            dimension_numbers=("NWC", "WIO", "NWC"),
        )
        # Same remat tag as conv1d_apply so the "convs" policy also
        # bites on the sequence-parallel XLA path (parallel/seq_parallel
        # wraps this body in jax.checkpoint); inert everywhere else.
        return checkpoint_name(y + p["bias"].astype(xh.dtype), "conv_out")

    # VALID output row m covers input rows starting at m; center row l of
    # a 'SAME' conv corresponds to window start l + H - ((k-1)/2)·d.
    n_off = H - (params["narrow_conv"]["kernel"].shape[0] - 1) // 2 * narrow_dilation
    w_off = H - (params["wide_conv"]["kernel"].shape[0] - 1) // 2 * wide_dilation
    narrow = _gelu(valid_conv(params["narrow_conv"], narrow_dilation)
                   [:, n_off:n_off + L])
    wide = _gelu(valid_conv(params["wide_conv"], wide_dilation)
                 [:, w_off:w_off + L])
    h = layer_norm_apply(
        params["local_ln1"],
        xh[:, H:H + L] + narrow + wide + broadcast[:, None, :],
    )
    return layer_norm_apply(
        params["local_ln2"],
        h + _gelu(dense_apply(params["local_dense"], h)),
    )


def _tap_matmuls(window, kernel, taps, dilation, halo, tile):
    """Σ_t window[halo + (t-(K-1)/2)·d : …+tile] @ kernel[t]  (fp32 acc).

    `window` is (tile + 2·halo, C) in activation dtype; every slice is
    static so XLA/Mosaic sees `taps` plain MXU matmuls.
    """
    center = (taps - 1) // 2
    acc = None
    for t in range(taps):
        off = halo + (t - center) * dilation
        part = lax.dot_general(
            window[off:off + tile],
            kernel[t],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc = part if acc is None else acc + part
    return acc


def _layer_norm_f32(x32, scale, bias, eps=1e-5):
    mean = x32.mean(axis=-1, keepdims=True)
    var = x32.var(axis=-1, keepdims=True)
    return (x32 - mean) * lax.rsqrt(var + eps) * scale + bias


def _finish_row(h32, s1_ref, b1_ref, dk_ref, db_ref, s2_ref, b2_ref, dtype):
    """LN → dense(+GELU, residual) → LN tail shared by both kernel
    variants (they must never diverge numerically)."""
    x1 = _layer_norm_f32(h32, s1_ref[0], b1_ref[0]).astype(dtype)
    d = lax.dot_general(
        x1, dk_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + db_ref[0].astype(jnp.float32)
    h2 = x1.astype(jnp.float32) + _gelu(d)
    return _layer_norm_f32(h2, s2_ref[0], b2_ref[0]).astype(dtype)


def _fused_kernel(
    x_ref, bcast_ref,
    nk_ref, nb_ref, wk_ref, wb_ref,
    s1_ref, b1_ref, dk_ref, db_ref, s2_ref, b2_ref,
    out_ref,
    *, tile, halo, narrow_taps, wide_taps, narrow_dilation, wide_dilation,
):
    j = pl.program_id(1)
    dtype = x_ref.dtype
    # Window of padded rows covering this tile plus both halos.
    window = x_ref[0, pl.ds(j * tile, tile + 2 * halo), :]
    x_center = window[halo:halo + tile].astype(jnp.float32)

    narrow = _tap_matmuls(window, nk_ref[:], narrow_taps, narrow_dilation, halo, tile)
    narrow = _gelu(narrow + nb_ref[0].astype(jnp.float32))
    wide = _tap_matmuls(window, wk_ref[:], wide_taps, wide_dilation, halo, tile)
    wide = _gelu(wide + wb_ref[0].astype(jnp.float32))

    # bcast is shaped (B, 1, C) outside so this program's (1, 1, C) block
    # satisfies Mosaic's last-two-dims tiling rule (a (1, C) slice of a
    # (B, C) array does not, nor does a dynamic row-select).
    h = x_center + narrow + wide + bcast_ref[0, 0].astype(jnp.float32)[None, :]
    out_ref[0] = _finish_row(h, s1_ref, b1_ref, dk_ref, db_ref,
                             s2_ref, b2_ref, dtype)


def _fused_kernel_tiled(
    x_ref, bcast_ref,
    cw_ref, cb_ref,
    s1_ref, b1_ref, dk_ref, db_ref, s2_ref, b2_ref,
    out_ref,
    h_scratch,
    *, tile, halo, taps, narrow_dilation, wide_dilation, c_tiles,
    resident,
):
    """Channel-tiled body, one of two grid orders (see _plan_tiled):

    - resident=False: grid (B, L/tile, c_tiles, 2), phase fastest,
      scratch covers ONE (tile, C) row. Conv weight slices are refetched
      for every L tile and batch row.
    - resident=True: grid (B, c_tiles, 2, L/tile), L-tile fastest,
      scratch covers the FULL (L, C) row set of one batch entry. The
      conv weight slice's block index varies only with the slow (c,
      phase) axes, so Mosaic's pipeline keeps each slice resident across
      the whole L sweep — weight HBM traffic drops from
      O(B · L/tile · weights) to O(weights) per call. Preferred
      whenever the full-row scratch fits the VMEM budget.

    The two convs are stacked on a leading axis of `cw_ref`/`cb_ref` and
    visited as grid phases so only ONE conv's (taps, C, TC) weight slice
    is resident per step (the conv weights dominate VMEM at C=1024; see
    _plan_tiled). Phase 0 seeds this c tile's columns of the fp32
    scratch row with gelu(narrow); phase 1 adds gelu(wide); the final
    (c, phase) step adds x + broadcast over the FULL row — static
    slices only; Mosaic cannot lower lax.dynamic_slice on materialized
    values, so nothing may column-slice `window`/`bcast` by the dynamic
    grid index `c` — then finishes (LN → dense residual → LN) and
    writes the output block.
    """
    if resident:
        c = pl.program_id(1)
        phase = pl.program_id(2)
        j = pl.program_id(3)
        rsel = pl.ds(j * tile, tile)
    else:
        j = pl.program_id(1)
        c = pl.program_id(2)
        phase = pl.program_id(3)
        rsel = slice(None)
    dtype = x_ref.dtype
    window = x_ref[0, pl.ds(j * tile, tile + 2 * halo), :]

    tc = cw_ref.shape[-1]

    @pl.when(phase == 0)
    def _narrow():
        conv = _tap_matmuls(window, cw_ref[0], taps, narrow_dilation,
                            halo, tile)
        h_scratch[rsel, pl.ds(c * tc, tc)] = _gelu(
            conv + cb_ref[0, 0].astype(jnp.float32))

    @pl.when(phase == 1)
    def _wide():
        conv = _tap_matmuls(window, cw_ref[0], taps, wide_dilation,
                            halo, tile)
        h_scratch[rsel, pl.ds(c * tc, tc)] += _gelu(
            conv + cb_ref[0, 0].astype(jnp.float32))

    @pl.when((c == c_tiles - 1) & (phase == 1))
    def _finish():
        h32 = (h_scratch[rsel, :]
               + window[halo:halo + tile].astype(jnp.float32)
               + bcast_ref[0, 0].astype(jnp.float32)[None, :])
        out_ref[0] = _finish_row(h32, s1_ref, b1_ref,
                                 dk_ref, db_ref, s2_ref, b2_ref, dtype)


def _fused_segment_kernel_tiled(
    x_ref, oh_ref, bcast_ref,
    cw_ref, cb_ref,
    s1_ref, b1_ref, dk_ref, db_ref, s2_ref, b2_ref,
    out_ref,
    h_scratch,
    *, tile, halo, taps, narrow_dilation, wide_dilation, c_tiles,
    resident,
):
    """Channel-tiled SEGMENT body (ISSUE 13 second leg): the same two
    grid orders and phase layout as `_fused_kernel_tiled`, with the
    segment one-hot folded in exactly like the weights-resident segment
    kernel — every tap's shifted operand is masked by the one-hot lane
    reduction (`_seg_tap_matmuls`), and the finish step's broadcast is
    the own-segment (TL, S) @ (S, C) one-hot gather instead of the
    row-wide vector. The one-hot row block and per-segment broadcast
    ride the b-varying specs (priced in `_plan_tiled(max_segments=)`);
    nothing column-slices them by the dynamic grid index, so the
    static-slice rule the dense tiled kernel obeys holds here too."""
    if resident:
        c = pl.program_id(1)
        phase = pl.program_id(2)
        j = pl.program_id(3)
        rsel = pl.ds(j * tile, tile)
    else:
        j = pl.program_id(1)
        c = pl.program_id(2)
        phase = pl.program_id(3)
        rsel = slice(None)
    dtype = x_ref.dtype
    window = x_ref[0, pl.ds(j * tile, tile + 2 * halo), :]
    oh_window = oh_ref[0, pl.ds(j * tile, tile + 2 * halo), :]

    tc = cw_ref.shape[-1]

    @pl.when(phase == 0)
    def _narrow():
        conv = _seg_tap_matmuls(window, oh_window, cw_ref[0], taps,
                                narrow_dilation, halo, tile)
        h_scratch[rsel, pl.ds(c * tc, tc)] = _gelu(
            conv + cb_ref[0, 0].astype(jnp.float32))

    @pl.when(phase == 1)
    def _wide():
        conv = _seg_tap_matmuls(window, oh_window, cw_ref[0], taps,
                                wide_dilation, halo, tile)
        h_scratch[rsel, pl.ds(c * tc, tc)] += _gelu(
            conv + cb_ref[0, 0].astype(jnp.float32))

    @pl.when((c == c_tiles - 1) & (phase == 1))
    def _finish():
        bcast_pos = lax.dot_general(
            oh_window[halo:halo + tile], bcast_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        h32 = (h_scratch[rsel, :]
               + window[halo:halo + tile].astype(jnp.float32)
               + bcast_pos)
        out_ref[0] = _finish_row(h32, s1_ref, b1_ref,
                                 dk_ref, db_ref, s2_ref, b2_ref, dtype)


def _plan_tiled(C: int, seq_len: int, dtype,
                narrow_taps: int = 9, wide_taps: int = 9,
                wide_dilation: int = 5, resident: bool = False,
                max_segments: int = 0):
    """(c_tile, l_tile) of the widest-channel plan that fits the VMEM
    budget, or (0, 0).

    The model counts what Mosaic actually keeps resident: blocks whose
    index map varies over the grid are DOUBLE-buffered (conv weight/bias
    slices vary with (phase, c); the input row, broadcast, and output
    blocks vary with b/j), plus the fp32 scratch and the finish step's
    (tile, C) temporaries. The phase split exists exactly so the
    double-buffered conv residency is one conv, not two. A narrower L
    tile is tried before a narrower channel tile — it shrinks the
    scratch/out/finish terms without adding weight refetches.

    `resident=True` prices the weights-resident grid order (L-tile axis
    fastest, see _fused_kernel_tiled): the only difference is the fp32
    scratch covering the full (seq_len, C) row set instead of one
    (tile, C) row, so a resident plan always fits wherever it exists —
    the per-row plan is the superset and remains the support gate.

    `max_segments > 0` prices the SEGMENT variant (ISSUE 13): the
    (Lp, S) one-hot row block (lane-padded, varies with b → double-
    buffered), the (S, C) per-segment broadcast block replacing the
    (1, C) row vector, and the per-tap mask temporaries."""
    if narrow_taps != wide_taps:
        return 0, 0  # the stacked phase layout needs equal tap counts
    itemsize = jnp.dtype(dtype).itemsize
    halo = max((narrow_taps - 1) // 2, (wide_taps - 1) // 2 * wide_dilation)
    # Mosaic pads the lane dim UP to the next multiple of 128.
    lanes = -(-max_segments // _LANE) * _LANE if max_segments else 0
    for tc in (512, 256, 128):
        if C % tc:
            continue
        for tile in (_pick_tile(seq_len), 128):
            if seq_len % tile:
                continue
            conv_w = 2 * narrow_taps * C * tc * itemsize  # one conv, 2 bufs
            dense = C * C * itemsize                      # whole, 1 buffer
            row = 2 * (seq_len + 2 * halo) * C * itemsize  # varies with b
            out = 2 * tile * C * itemsize                 # varies with (b, j)
            scratch = (seq_len if resident else tile) * C * 4  # fp32 h
            finish = tile * C * (4 + 4 + 4 + itemsize)    # h32, d, h2 f32 + x1
            seg = 0
            if max_segments:
                seg = (2 * (seq_len + 2 * halo) * lanes * itemsize  # one-hot
                       + 2 * max_segments * C * itemsize            # bcast
                       + tile * lanes * 4)                          # masks
            if (conv_w + dense + row + out + scratch + finish + seg
                    <= _VMEM_BUDGET):
                return tc, tile
    return 0, 0


def _pallas_forward(
    params: Params, x: jax.Array, broadcast: jax.Array,
    narrow_dilation: int, wide_dilation: int, interpret: bool,
    prehaloed: bool = False,
) -> jax.Array:
    nk = params["narrow_conv"]["kernel"]
    wk = params["wide_conv"]["kernel"]
    narrow_taps, wide_taps = nk.shape[0], wk.shape[0]
    halo = max((narrow_taps - 1) // 2 * narrow_dilation,
               (wide_taps - 1) // 2 * wide_dilation)

    dtype = x.dtype
    if prehaloed:
        # x rows already carry `halo` rows of real neighbor context on
        # each side (sequence parallelism); output is the center.
        B, Lp, C = x.shape
        L = Lp - 2 * halo
        x_padded = x
    else:
        B, L, C = x.shape
        x_padded = jnp.pad(x, ((0, 0), (halo, halo), (0, 0)))
        Lp = L + 2 * halo

    tile = _pick_tile(L)

    def vec(p):  # (C,) fp32 vector → (1, C) activation-dtype VMEM block
        return p.reshape(1, C)

    ln1, ln2, dn = params["local_ln1"], params["local_ln2"], params["local_dense"]
    inputs = (
        x_padded,
        broadcast.astype(dtype).reshape(B, 1, C),
        nk.astype(dtype), vec(params["narrow_conv"]["bias"]),
        wk.astype(dtype), vec(params["wide_conv"]["bias"]),
        vec(ln1["scale"]), vec(ln1["bias"]),
        dn["kernel"].astype(dtype), vec(dn["bias"]),
        vec(ln2["scale"]), vec(ln2["bias"]),
    )
    flops_conv = 2 * B * L * C * C * (narrow_taps + wide_taps + 1)
    cost = pl.CostEstimate(
        flops=flops_conv,
        bytes_accessed=x.size * x.dtype.itemsize * 2,
        transcendentals=3 * B * L * C,
    )

    if C <= MAX_PALLAS_DIM:
        grid = (B, L // tile)

        row_spec = pl.BlockSpec((1, Lp, C), lambda b, j: (b, 0, 0),
                                memory_space=pltpu.VMEM)

        def whole(a):
            return pl.BlockSpec(a.shape, lambda b, j: (0,) * a.ndim,
                                memory_space=pltpu.VMEM)

        bcast_spec = pl.BlockSpec((1, 1, C), lambda b, j: (b, 0, 0),
                                  memory_space=pltpu.VMEM)

        kernel = functools.partial(
            _fused_kernel, tile=tile, halo=halo,
            narrow_taps=narrow_taps, wide_taps=wide_taps,
            narrow_dilation=narrow_dilation, wide_dilation=wide_dilation,
        )
        return pl.pallas_call(
            kernel,
            name="pbt_local_track",
            grid=grid,
            in_specs=[row_spec, bcast_spec] + [whole(a) for a in inputs[2:]],
            out_specs=pl.BlockSpec((1, tile, C), lambda b, j: (b, j, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((B, L, C), dtype),
            cost_estimate=cost,
            interpret=interpret,
        )(*inputs)

    # Channel-tiled variant for C > MAX_PALLAS_DIM (module docstring).
    # Prefer the weights-resident grid order; fall back to the per-row
    # scratch order when the full-row scratch doesn't fit (long L).
    resident = True
    tc, tile = _plan_tiled(C, L, dtype, narrow_taps, wide_taps,
                           wide_dilation, resident=True)
    if tc == 0:
        resident = False
        tc, tile = _plan_tiled(C, L, dtype, narrow_taps, wide_taps,
                               wide_dilation)
    if tc == 0:  # callers gate via pallas_supported; belt and braces
        raise ValueError(f"no VMEM plan for C={C}, L={L}")
    c_tiles = C // tc
    if resident:
        grid = (B, c_tiles, 2, L // tile)  # L tiles fastest

        def imap(f):  # block index from (c, phase, j)
            return lambda b, c, p, j: f(b, c, p, j)
    else:
        grid = (B, L // tile, c_tiles, 2)  # phase (narrow/wide) fastest

        def imap(f):
            return lambda b, j, c, p: f(b, c, p, j)

    # Both convs stacked on a leading phase axis so each grid step loads
    # ONE conv's weight slice (see _plan_tiled).
    conv_w = jnp.stack([inputs[2], inputs[4]])          # (2, taps, C, C)
    conv_b = jnp.stack([inputs[3], inputs[5]])          # (2, 1, C)

    row_spec = pl.BlockSpec((1, Lp, C), imap(lambda b, c, p, j: (b, 0, 0)),
                            memory_space=pltpu.VMEM)
    bcast_spec = pl.BlockSpec((1, 1, C), imap(lambda b, c, p, j: (b, 0, 0)),
                              memory_space=pltpu.VMEM)

    def whole4(a):
        return pl.BlockSpec(a.shape, lambda *_: (0,) * a.ndim,
                            memory_space=pltpu.VMEM)

    conv_w_spec = pl.BlockSpec((1, narrow_taps, C, tc),
                               imap(lambda b, c, p, j: (p, 0, 0, c)),
                               memory_space=pltpu.VMEM)
    conv_b_spec = pl.BlockSpec((1, 1, tc),
                               imap(lambda b, c, p, j: (p, 0, c)),
                               memory_space=pltpu.VMEM)

    in_specs = [
        row_spec, bcast_spec, conv_w_spec, conv_b_spec,
        *[whole4(a) for a in inputs[6:]],
    ]
    kernel = functools.partial(
        _fused_kernel_tiled, tile=tile, halo=halo, taps=narrow_taps,
        narrow_dilation=narrow_dilation, wide_dilation=wide_dilation,
        c_tiles=c_tiles, resident=resident,
    )
    if resident:
        # The kernel only writes output on the final (c, phase) sweep, but
        # Mosaic copies an output block to HBM on every block-index
        # CHANGE — with j fastest a plain (b, j, 0) map would stream the
        # (uninitialized) block 2·c_tiles times per row. Pinning the index
        # to (b, 0, 0) during non-finish sweeps makes it change only
        # across the finish sweep's j steps, so exactly the finished
        # blocks are written, once each.
        def out_map(b, c, p, j):
            return (b, jnp.where((c == c_tiles - 1) & (p == 1), j, 0), 0)
    else:
        out_map = imap(lambda b, c, p, j: (b, j, 0))
    return pl.pallas_call(
        kernel,
        name="pbt_local_track_tiled",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tile, C), out_map,
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, L, C), dtype),
        scratch_shapes=[pltpu.VMEM((L if resident else tile, C),
                                   jnp.float32)],
        cost_estimate=cost,
        interpret=interpret,
    )(*inputs[:2], conv_w, conv_b, *inputs[6:])


def _pick_tile(L: int) -> int:
    for cand in (512, 256, 128):
        if L > cand and L % cand == 0:
            return cand
    return L


def pallas_supported(
    local_dim: int, seq_len: int, dtype: str = "bfloat16",
    narrow_taps: int = 9, wide_taps: int = 9, wide_dilation: int = 5,
) -> bool:
    """Whether the fused kernel handles this shape+dtype within the VMEM
    budget (else the model falls back to the XLA path). Up to
    MAX_PALLAS_DIM the whole weight set must fit; beyond it the
    channel-tiled plan (_plan_tiled) must find a tile width. Note
    `seq_len` is the PER-SHARD length the kernel actually sees — under
    sequence parallelism a long global L divides down to supportable
    shards."""
    if not _vb.shape_prechecks(local_dim, seq_len):
        return False
    item = _vb.itemsize(dtype)
    C = local_dim
    halo = max((narrow_taps - 1) // 2, (wide_taps - 1) // 2 * wide_dilation)
    tile = _pick_tile(seq_len)
    if C > MAX_PALLAS_DIM:
        return _plan_tiled(C, seq_len, dtype, narrow_taps, wide_taps,
                           wide_dilation)[0] > 0
    weights = _vb.track_weight_bytes(C, narrow_taps, wide_taps, item)
    row = (seq_len + 2 * halo) * C * item
    temps = _vb.track_temp_bytes(tile, C)
    return _vb.fits(weights, row, temps)


# ------------------------------------------------ segment-aware kernel
# The packed fast path (ISSUE 10 tentpole). Same implicit-GEMM tap
# decomposition as _fused_kernel, with two additions folded into the
# same VMEM-resident block:
#
# - every tap's shifted operand is masked by SEGMENT-ID EQUALITY before
#   its matmul: the one-hot segment matrix rides next to the input row
#   as a (Lp, S) block, and tap t's mask is the lane reduction
#   Σ_s oh[l]·oh[l + off] — exact 0.0/1.0 (multiplication by a zero
#   mask, not a subtraction), the same semantics `_segment_conv` proves
#   bit-level isolation with in tests/test_packing.py;
# - the per-position global→local broadcast is gathered from each
#   position's OWN segment inside the kernel as the one-hot matmul
#   (TL, S) @ (S, C) on the MXU (the operator-fusion-for-inference
#   move, PAPERS.md) — the model passes the tiny per-segment (B, S, C)
#   tensor and never materialises the (B, L, C) gather on this path.
#
# Scope: C <= MAX_PALLAS_DIM runs with the whole weight set
# VMEM-resident; C > MAX_PALLAS_DIM runs the channel-tiled segment
# variant (`_fused_segment_kernel_tiled` — same one-hot operands over
# the tiled grid, ISSUE 13), so ProteinBERT-Large packed shapes no
# longer fall back with reason="segments".


def _seg_tap_matmuls(window, oh_window, kernel, taps, dilation, halo,
                     tile):
    """Σ_t (window[..] · mask_t) @ kernel[t] with mask_t[l] =
    Σ_s oh[l]·oh[l + (t-(K-1)/2)·d] (fp32 acc). `window` is
    (tile + 2·halo, C); `oh_window` the matching (tile + 2·halo, S)
    one-hot rows — all-zero at pad/halo, so masks embed the
    center-is-real check for free."""
    center = (taps - 1) // 2
    oh_center = oh_window[halo:halo + tile]
    acc = None
    for t in range(taps):
        off = halo + (t - center) * dilation
        xs = window[off:off + tile]
        same = jnp.sum(oh_center * oh_window[off:off + tile],
                       axis=-1, keepdims=True)
        part = lax.dot_general(
            xs * same.astype(xs.dtype),
            kernel[t],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc = part if acc is None else acc + part
    return acc


def _fused_segment_kernel(
    x_ref, oh_ref, bcast_ref,
    nk_ref, nb_ref, wk_ref, wb_ref,
    s1_ref, b1_ref, dk_ref, db_ref, s2_ref, b2_ref,
    *rest,
    tile, halo, narrow_taps, wide_taps, narrow_dilation, wide_dilation,
    quantized=False,
):
    out_ref = rest[-1]
    j = pl.program_id(1)
    dtype = x_ref.dtype
    if quantized:
        # int8 weights + per-channel scales are VMEM-resident; the
        # per-tile dequant (q·scale in fp32, cast to the activation
        # dtype) reproduces the HLO dequant's numerics bit-for-bit
        # (ISSUE 16 second leg), but HBM ships int8 bytes.
        nks_ref, wks_ref, dks_ref = rest[0], rest[1], rest[2]
        nk = (nk_ref[:].astype(jnp.float32) * nks_ref[:]).astype(dtype)
        wk = (wk_ref[:].astype(jnp.float32) * wks_ref[:]).astype(dtype)
        dk = (dk_ref[:].astype(jnp.float32) * dks_ref[:]).astype(dtype)
    else:
        nk, wk, dk = nk_ref, wk_ref, dk_ref
    window = x_ref[0, pl.ds(j * tile, tile + 2 * halo), :]
    oh_window = oh_ref[0, pl.ds(j * tile, tile + 2 * halo), :]
    x_center = window[halo:halo + tile].astype(jnp.float32)

    narrow = _seg_tap_matmuls(window, oh_window, nk[:], narrow_taps,
                              narrow_dilation, halo, tile)
    narrow = _gelu(narrow + nb_ref[0].astype(jnp.float32))
    wide = _seg_tap_matmuls(window, oh_window, wk[:], wide_taps,
                            wide_dilation, halo, tile)
    wide = _gelu(wide + wb_ref[0].astype(jnp.float32))

    # Own-segment broadcast gather as a one-hot matmul: a pad
    # position's all-zero one-hot row receives exact 0.0.
    bcast_pos = lax.dot_general(
        oh_window[halo:halo + tile], bcast_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    h = x_center + narrow + wide + bcast_pos
    out_ref[0] = _finish_row(h, s1_ref, b1_ref, dk, db_ref,
                             s2_ref, b2_ref, dtype)


def _segment_tile(
    local_dim: int, seq_len: int, max_segments: int, dtype,
    narrow_taps: int, wide_taps: int, halo: int,
) -> int:
    """L tile of the weights-resident SEGMENT plan, or 0 where none
    fits: the largest of `_pick_tile(seq_len)`, 256, 128 that divides
    the row and whose working set is inside the VMEM budget. The tile
    comes from the budget, not from `_pick_tile` alone, because the
    fixed residents grow with the row while the temporaries grow with
    the tile: at the serving shape (L=1024, C=512, bfloat16, S <= 128)
    the whole weight set (9.96 MB), the padded row and its lane-padded
    one-hot leave no room for three float32 temporaries of 512 rows
    (3.15 MB) and all the room for those of 256 (13.03 MB of 13.63).
    Priced: the weights, the padded row, the one-hot row block
    (lane-padded to 128 on TPU), the (S, C) per-segment broadcast
    block, the tile's three float32 temporaries and its mask lanes."""
    item = _vb.itemsize(dtype)
    Lp = seq_len + 2 * halo
    resident = (
        _vb.track_weight_bytes(local_dim, narrow_taps, wide_taps, item)
        + Lp * local_dim * item
        + Lp * _vb.lanes(max_segments) * item
        + max_segments * local_dim * item)
    first = _pick_tile(seq_len)
    for tile in (first, 256, 128):
        if tile > first or seq_len % tile:
            continue
        temps = (_vb.track_temp_bytes(tile, local_dim)
                 + tile * _vb.lanes(max_segments) * 4)
        if _vb.fits(resident, temps):
            return tile
    return 0


def pallas_segments_supported(
    local_dim: int, seq_len: int, max_segments: int,
    dtype: str = "bfloat16",
    narrow_taps: int = 9, wide_taps: int = 9,
    wide_dilation: int = 5, narrow_dilation: int = 1,
) -> bool:
    """Whether the SEGMENT kernel handles this packed shape+dtype
    within the VMEM budget (else fused_local_track_segments falls back
    to the XLA reference path with reason="segments"). Versus
    `pallas_supported`: taps must be odd (the symmetric-halo tap
    layout), and the budget additionally prices the (Lp, S) one-hot
    row block (lane-padded to 128 on TPU) and the (S, C) per-segment
    broadcast block; up to MAX_PALLAS_DIM the L tile is the largest
    the budget takes (`_segment_tile`). Beyond MAX_PALLAS_DIM the
    channel-tiled SEGMENT plan (`_plan_tiled(max_segments=)`, ISSUE 13)
    must find a tile width — ProteinBERT-Large C=1024 packed rows run
    the fast path."""
    if not _vb.shape_prechecks(local_dim, seq_len, max_segments):
        return False
    if narrow_taps % 2 == 0 or wide_taps % 2 == 0:
        return False
    if local_dim > MAX_PALLAS_DIM:
        return _plan_tiled(local_dim, seq_len, dtype, narrow_taps,
                           wide_taps, wide_dilation,
                           max_segments=max_segments)[0] > 0
    halo = max((narrow_taps - 1) // 2 * narrow_dilation,
               (wide_taps - 1) // 2 * wide_dilation)
    return _segment_tile(local_dim, seq_len, max_segments, dtype,
                         narrow_taps, wide_taps, halo) > 0


def _pallas_segments_forward(
    params: Params, x: jax.Array, broadcast_seg: jax.Array,
    seg_oh: jax.Array,
    narrow_dilation: int, wide_dilation: int, interpret: bool,
) -> jax.Array:
    nk = params["narrow_conv"]["kernel"]
    wk = params["wide_conv"]["kernel"]
    quantized = is_quant_leaf(nk)
    narrow_taps = weight_leaf(nk).shape[0]
    wide_taps = weight_leaf(wk).shape[0]
    halo = max((narrow_taps - 1) // 2 * narrow_dilation,
               (wide_taps - 1) // 2 * wide_dilation)
    B, L, C = x.shape
    S = seg_oh.shape[-1]
    dtype = x.dtype
    x_padded = jnp.pad(x, ((0, 0), (halo, halo), (0, 0)))
    oh_padded = jnp.pad(seg_oh.astype(dtype),
                        ((0, 0), (halo, halo), (0, 0)))
    Lp = L + 2 * halo

    def vec(p):  # (C,) fp32 vector → (1, C) activation-dtype VMEM block
        return p.reshape(1, C)

    ln1, ln2, dn = params["local_ln1"], params["local_ln2"], params["local_dense"]
    if quantized:
        # int8 weight operands ride as-is; scales are reshaped so the
        # in-kernel q·scale multiply broadcasts per output channel
        # exactly like dequantize_params' scale[..., None, :].
        nk_w, wk_w, dk_w = nk["q"], wk["q"], dn["kernel"]["q"]
        scales = (nk["scale"][:, None, :].astype(jnp.float32),
                  wk["scale"][:, None, :].astype(jnp.float32),
                  dn["kernel"]["scale"].reshape(1, C).astype(jnp.float32))
    else:
        nk_w, wk_w = nk.astype(dtype), wk.astype(dtype)
        dk_w = dn["kernel"].astype(dtype)
        scales = ()
    inputs = (
        x_padded, oh_padded, broadcast_seg.astype(dtype),
        nk_w, vec(params["narrow_conv"]["bias"]),
        wk_w, vec(params["wide_conv"]["bias"]),
        vec(ln1["scale"]), vec(ln1["bias"]),
        dk_w, vec(dn["bias"]),
        vec(ln2["scale"]), vec(ln2["bias"]),
    )
    # Masks add one (TL, S) VPU reduction per tap; the broadcast gather
    # adds one (TL, S)@(S, C) matmul — negligible next to the conv
    # FLOPs, so the cost model stays the dense kernel's.
    flops_conv = 2 * B * L * C * C * (narrow_taps + wide_taps + 1)
    cost = pl.CostEstimate(
        flops=flops_conv,
        bytes_accessed=x.size * x.dtype.itemsize * 2,
        transcendentals=3 * B * L * C,
    )
    if C <= MAX_PALLAS_DIM:
        tile = _segment_tile(C, L, S, dtype, narrow_taps, wide_taps, halo)
        if tile == 0:  # callers gate via pallas_segments_supported
            raise ValueError(f"no segment VMEM plan for C={C}, L={L}, S={S}")
        grid = (B, L // tile)
        row_spec = pl.BlockSpec((1, Lp, C), lambda b, j: (b, 0, 0),
                                memory_space=pltpu.VMEM)
        oh_spec = pl.BlockSpec((1, Lp, S), lambda b, j: (b, 0, 0),
                               memory_space=pltpu.VMEM)
        bcast_spec = pl.BlockSpec((1, S, C), lambda b, j: (b, 0, 0),
                                  memory_space=pltpu.VMEM)

        def whole(a):
            return pl.BlockSpec(a.shape, lambda b, j: (0,) * a.ndim,
                                memory_space=pltpu.VMEM)

        kernel = functools.partial(
            _fused_segment_kernel, tile=tile, halo=halo,
            narrow_taps=narrow_taps, wide_taps=wide_taps,
            narrow_dilation=narrow_dilation, wide_dilation=wide_dilation,
            quantized=quantized,
        )
        return pl.pallas_call(
            kernel,
            name="pbt_local_track_segments",
            grid=grid,
            in_specs=[row_spec, oh_spec, bcast_spec]
                     + [whole(a) for a in inputs[3:]]
                     + [whole(a) for a in scales],
            out_specs=pl.BlockSpec((1, tile, C), lambda b, j: (b, j, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((B, L, C), dtype),
            cost_estimate=cost,
            interpret=interpret,
        )(*inputs, *scales)

    if quantized:
        # The channel-tiled variant keeps its HLO dequant (the
        # dispatch dequantizes before reaching it, docs/serving.md).
        raise ValueError(
            f"in-kernel int8 dequant has no channel-tiled plan "
            f"(C={C} > {MAX_PALLAS_DIM}); dequantize first")

    # Channel-tiled SEGMENT variant for C > MAX_PALLAS_DIM (ISSUE 13
    # second leg — ProteinBERT-Large packed rows). Same grid orders as
    # the dense tiled kernel: prefer weights-resident, fall back to the
    # per-row scratch order when the full-row fp32 scratch doesn't fit.
    resident = True
    tc, tile = _plan_tiled(C, L, dtype, narrow_taps, wide_taps,
                           wide_dilation, resident=True, max_segments=S)
    if tc == 0:
        resident = False
        tc, tile = _plan_tiled(C, L, dtype, narrow_taps, wide_taps,
                               wide_dilation, max_segments=S)
    if tc == 0:  # callers gate via pallas_segments_supported
        raise ValueError(f"no segment VMEM plan for C={C}, L={L}, S={S}")
    c_tiles = C // tc
    if resident:
        grid = (B, c_tiles, 2, L // tile)  # L tiles fastest

        def imap(f):  # block index from (c, phase, j)
            return lambda b, c, p, j: f(b, c, p, j)
    else:
        grid = (B, L // tile, c_tiles, 2)  # phase (narrow/wide) fastest

        def imap(f):
            return lambda b, j, c, p: f(b, c, p, j)

    # Both convs stacked on a leading phase axis so each grid step
    # loads ONE conv's weight slice (see _plan_tiled).
    conv_w = jnp.stack([inputs[3], inputs[5]])          # (2, taps, C, C)
    conv_b = jnp.stack([inputs[4], inputs[6]])          # (2, 1, C)

    row_spec = pl.BlockSpec((1, Lp, C), imap(lambda b, c, p, j: (b, 0, 0)),
                            memory_space=pltpu.VMEM)
    oh_spec = pl.BlockSpec((1, Lp, S), imap(lambda b, c, p, j: (b, 0, 0)),
                           memory_space=pltpu.VMEM)
    bcast_spec = pl.BlockSpec((1, S, C), imap(lambda b, c, p, j: (b, 0, 0)),
                              memory_space=pltpu.VMEM)

    def whole4(a):
        return pl.BlockSpec(a.shape, lambda *_: (0,) * a.ndim,
                            memory_space=pltpu.VMEM)

    conv_w_spec = pl.BlockSpec((1, narrow_taps, C, tc),
                               imap(lambda b, c, p, j: (p, 0, 0, c)),
                               memory_space=pltpu.VMEM)
    conv_b_spec = pl.BlockSpec((1, 1, tc),
                               imap(lambda b, c, p, j: (p, 0, c)),
                               memory_space=pltpu.VMEM)

    in_specs = [
        row_spec, oh_spec, bcast_spec, conv_w_spec, conv_b_spec,
        *[whole4(a) for a in inputs[7:]],
    ]
    kernel = functools.partial(
        _fused_segment_kernel_tiled, tile=tile, halo=halo,
        taps=narrow_taps,
        narrow_dilation=narrow_dilation, wide_dilation=wide_dilation,
        c_tiles=c_tiles, resident=resident,
    )
    if resident:
        # Same out-map pinning as the dense tiled kernel: the output
        # block index changes only across the finish sweep's j steps,
        # so exactly the finished blocks are written, once each.
        def out_map(b, c, p, j):
            return (b, jnp.where((c == c_tiles - 1) & (p == 1), j, 0), 0)
    else:
        out_map = imap(lambda b, c, p, j: (b, j, 0))
    return pl.pallas_call(
        kernel,
        name="pbt_local_track_segments_tiled",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tile, C), out_map,
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, L, C), dtype),
        scratch_shapes=[pltpu.VMEM((L if resident else tile, C),
                                   jnp.float32)],
        cost_estimate=cost,
        interpret=interpret,
    )(*inputs[:3], conv_w, conv_b, *inputs[7:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused_segments(
    params: Params, x: jax.Array, broadcast_seg: jax.Array,
    seg_oh: jax.Array,
    narrow_dilation: int = 1, wide_dilation: int = 5,
    interpret: bool = False,
) -> jax.Array:
    """Segment kernel under the same memory contract as
    fused_local_track: Pallas forward, rematerialised backward (the
    VJP recomputes local_track_segment_oh_reference — conv_out remat
    tag intact — saving only params, x, broadcast_seg, seg_oh)."""
    return _pallas_segments_forward(params, x, broadcast_seg, seg_oh,
                                    narrow_dilation, wide_dilation,
                                    interpret)


def _fwd_segments(params, x, broadcast_seg, seg_oh,
                  narrow_dilation, wide_dilation, interpret):
    y = _pallas_segments_forward(params, x, broadcast_seg, seg_oh,
                                 narrow_dilation, wide_dilation, interpret)
    return y, (params, x, broadcast_seg, seg_oh)


def _bwd_segments(narrow_dilation, wide_dilation, interpret, res, g):
    params, x, broadcast_seg, seg_oh = res
    _, vjp = jax.vjp(
        lambda p, xx, bb, oo: local_track_segment_oh_reference(
            p, xx, bb, oo, narrow_dilation, wide_dilation
        ),
        params, x, broadcast_seg, seg_oh,
    )
    return vjp(g)


_fused_segments.defvjp(_fwd_segments, _bwd_segments)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_local_track(
    params: Params, x: jax.Array, broadcast: jax.Array,
    narrow_dilation: int = 1, wide_dilation: int = 5,
    interpret: bool = False,
) -> jax.Array:
    """Fused local-track block: Pallas forward, rematerialised backward.

    Args:
      params: the local-track subset of a block's params (narrow_conv,
        wide_conv, local_ln1, local_dense, local_ln2).
      x: (B, L, C) activations.
      broadcast: (B, C) — the already-projected global→local vector
        (gelu(dense(global)) in block_apply).
    """
    return _pallas_forward(params, x, broadcast,
                           narrow_dilation, wide_dilation, interpret)


def _fwd(params, x, broadcast, narrow_dilation, wide_dilation, interpret):
    y = _pallas_forward(params, x, broadcast,
                        narrow_dilation, wide_dilation, interpret)
    return y, (params, x, broadcast)


def _bwd(narrow_dilation, wide_dilation, interpret, res, g):
    params, x, broadcast = res
    _, vjp = jax.vjp(
        lambda p, xx, bb: local_track_reference(
            p, xx, bb, narrow_dilation, wide_dilation
        ),
        params, x, broadcast,
    )
    return vjp(g)


fused_local_track.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_local_track_valid(
    params: Params, xh: jax.Array, broadcast: jax.Array,
    narrow_dilation: int = 1, wide_dilation: int = 5,
    interpret: bool = False,
) -> jax.Array:
    """Pre-haloed variant for sequence parallelism: `xh` (B, L+2·halo, C)
    carries real neighbor rows (parallel/halo.halo_exchange); returns the
    (B, L, C) center. Ground truth: local_track_valid_reference."""
    return _pallas_forward(params, xh, broadcast,
                           narrow_dilation, wide_dilation, interpret,
                           prehaloed=True)


def _fwd_valid(params, xh, broadcast, narrow_dilation, wide_dilation, interpret):
    y = _pallas_forward(params, xh, broadcast,
                        narrow_dilation, wide_dilation, interpret,
                        prehaloed=True)
    return y, (params, xh, broadcast)


def _bwd_valid(narrow_dilation, wide_dilation, interpret, res, g):
    params, xh, broadcast = res
    _, vjp = jax.vjp(
        lambda p, xx, bb: local_track_valid_reference(
            p, xx, bb, narrow_dilation, wide_dilation
        ),
        params, xh, broadcast,
    )
    return vjp(g)


fused_local_track_valid.defvjp(_fwd_valid, _bwd_valid)
