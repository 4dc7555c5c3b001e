"""Pallas TPU kernels: causal flash attention over packed documents that
walks only the tiles a document needs (forward, backward dK/dV, backward
dQ, one `jax.custom_vjp`).

The core of the decoder's latent attention (`models/glm_moe.py`). A row
of L positions packs several documents, each one contiguous run of one
segment id, padding (id 0) as a last run. Position i attends to j <= i
of its own run, so of the (L / block)^2 tiles of (queries, keys) only
those on or under the diagonal AND between the run bounds can hold a
pair:

    forward, dQ   query tile i of row b walks key tiles lo[b, i] .. i
    dK/dV         key tile j of row b walks query tiles j .. hi[b, j]

From `lo` and `hi` (`ops/attention.segment_tile_bounds`) each call lists
its row's walk, tile pair by tile pair (`_walk`), and the lists reach the
kernel as scalar-prefetch arrays: the grid is (rows, heads, steps), step
t of row b works on the pair (outer[b, t], inner[b, t]), and the block
indices come from the lists, so a tile outside the bounds is neither
fetched nor multiplied nor even a grid step. The number of steps is
static, that of the whole causal walk, n (n + 1) / 2 tiles; a row that
needs fewer idles through the rest on the blocks it holds (a grid step
that does nothing costs ~0.3 us on a v5e, a walked tile ~2). Inside a
walked tile the element mask is causal AND same id, as in
`ops/attention.causal_segment_attention`, whose result this is to
rounding: a row that is one document walks every causal tile, a row of
short documents few. What the bounds rest on is the packer's contract
(`data/packing.py`): an id never comes back after another id followed
it. A row that breaks it loses the pairs that reach across the gap.

GROUPED KEYS, forward only: where k and v hold fewer heads than q (H
query heads on H / group key heads, each key head read by `group`
consecutive query heads), the key and value tile of query head h is
that of key head h // group, chosen by the block index map: K and V are
never repeated to H heads in HBM. The backward kernels write one dK and
dV tile a query head and are not built for it (`segment_flash_attention`
refuses the derivative by name).

Products take the inputs' dtype and accumulate in float32, as do the
online softmax's running maximum and sum. The per-query maximum, sum and
`sum(o * do)` travel 128 lanes wide, the layout the kernel that ships
with jax (`jax.experimental.pallas.ops.tpu.flash_attention`) uses, from
whose structure this one was started.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from proteinbert_tpu.kernels.path_counter import KernelPathCounter

# Which core a traced CCA mixer got (trace time, once a traced mixer):
# `pallas/grouped_keys` where it is this file's forward kernel,
# `reference/not_tpu` and `reference/tiles_do_not_fit` where plain jax
# over keys repeated to the query heads.
_CCA_CORE = KernelPathCounter("cca_core", "cca_core_kernel_path_total")
CCA_CORE_PATH_TOTAL: Dict[Tuple[str, str], int] = _CCA_CORE.total


def register_cca_core_path_observer(cb) -> None:
    _CCA_CORE.register(cb)


def unregister_cca_core_path_observer(cb) -> None:
    _CCA_CORE.unregister(cb)


def note_cca_core_path(path: str, reason: str,
                       shape: Optional[tuple] = None) -> None:
    _CCA_CORE.note(path, reason, shape)


LANES = 128        # a tile's edge and a head's size are multiples of it
SUBLANES = 8
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
_NT = (((1,), (1,)), ((), ()))      # a @ b.T


def _lanes(x, width):
    """(rows, 128) lane copies of a per-row scalar, `width` lanes wide."""
    return x if width == LANES else jnp.tile(x, (1, width // LANES))


def _scores(q, k, qseg, kseg, i, j, scale):
    """Scaled scores of query tile i against key tile j, float32, the
    pairs that are not causal or not in one segment at MASK_VALUE."""
    block = q.shape[0]
    s = lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32) * scale
    rows = i * block + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = j * block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = (cols <= rows) & (_lanes(qseg, block) == kseg)
    return s + jnp.where(keep, 0.0, MASK_VALUE)


def _walk(first, last):
    """The walk of one call, tile pair by tile pair. first, last: (B, n)
    int32, the inner tiles first[b, o] .. last[b, o] that outer tile o of
    row b walks. Returns outer, inner: (B, T) int32 with T = n (n + 1) / 2,
    the pair of step t, and steps: (B,), how many of the T the row needs;
    past them a row stays on its last pair."""
    n = first.shape[1]
    count = last - first + 1
    end = jnp.cumsum(count, axis=1)
    t = jnp.arange(n * (n + 1) // 2, dtype=jnp.int32)
    outer = jnp.minimum((t[None, :, None] >= end[:, None, :]).sum(-1), n - 1)
    of = lambda a: jnp.take_along_axis(a, outer, axis=1)  # noqa: E731
    inner = jnp.minimum(of(first) + t[None] - (of(end) - of(count)), of(last))
    return (outer.astype(jnp.int32), inner.astype(jnp.int32),
            end[:, -1].astype(jnp.int32))


def _seg_operands(segment_ids):
    """Segment ids as the kernels read them: one query a sublane
    (B, L, 128), one key a lane (B, 8, L)."""
    B, L = segment_ids.shape
    ids = segment_ids.astype(jnp.int32)
    return (lax.broadcast_in_dim(ids, (B, L, LANES), (0, 1)),
            lax.broadcast_in_dim(ids, (B, SUBLANES, L), (0, 2)))


def _call(kernel, name, walk, bound, operands, in_specs, out_specs, out_shape,
          scratch, interpret):
    """One kernel over a walk: grid (rows, heads, steps); `walk` and
    `bound` (the inner tile an outer tile's walk starts or ends on) are
    the scalar-prefetch operands."""
    B, H = operands[0].shape[:2]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B, H, walk[0].shape[1]),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape, interpret=interpret, name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*walk, bound, *operands)


def _specs(block, d, dv, q_tile, k_tile, group=1):
    """BlockSpecs of (q, k, v, qseg, kseg) and of a per-query operand and
    a (block, dv) operand on the queries' side; `q_tile` / `k_tile` give
    the query / key tile of a grid step from the walk; query head h reads
    key head h // group."""
    at_q = lambda b, h, t, *walk: (b, h, q_tile(b, t, *walk), 0)  # noqa: E731
    at_k = lambda b, h, t, *walk: (b, h // group, k_tile(b, t, *walk), 0)  # noqa: E731
    five = [pl.BlockSpec((1, 1, block, d), at_q),
            pl.BlockSpec((1, 1, block, d), at_k),
            pl.BlockSpec((1, 1, block, dv), at_k),
            pl.BlockSpec((1, block, LANES),
                         lambda b, h, t, *walk: (b, q_tile(b, t, *walk), 0)),
            pl.BlockSpec((1, SUBLANES, block),
                         lambda b, h, t, *walk: (b, 0, k_tile(b, t, *walk)))]
    return (five, pl.BlockSpec((1, 1, block, LANES), at_q),
            pl.BlockSpec((1, 1, block, dv), at_q), at_k)


def _outer(b, t, outer_ref, inner_ref, steps_ref, bound_ref):
    return outer_ref[b, t]


def _inner(b, t, outer_ref, inner_ref, steps_ref, bound_ref):
    return inner_ref[b, t]


# ---------------------------------------------------------------- forward

def _fwd_kernel(qi_ref, kj_ref, steps_ref, lo_ref, q_ref, k_ref, v_ref,
                qseg_ref, kseg_ref, o_ref, *rest, scale):
    *lm_refs, m_scr, l_scr, acc_scr = rest
    b, t = pl.program_id(0), pl.program_id(2)
    i, j = qi_ref[b, t], kj_ref[b, t]
    live = t < steps_ref[b]

    @pl.when(live & (j == lo_ref[b, i]))
    def _():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(live)
    def _():
        v = v_ref[0, 0]
        s = _scores(q_ref[0, 0], k_ref[0, 0], qseg_ref[0], kseg_ref[0, :1],
                    i, j, scale)
        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - _lanes(m_next, s.shape[1]))
        alpha = jnp.exp(m_prev - m_next)
        m_scr[...] = m_next
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1)[:, None]
        acc_scr[...] = (acc_scr[...] * _lanes(alpha, v.shape[1])
                        + lax.dot(p.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32))

    # The diagonal tile is walked last and gives every query a key (itself).
    @pl.when(live & (j == i))
    def _():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / _lanes(l, acc_scr.shape[1])
                       ).astype(o_ref.dtype)
        if lm_refs:
            lm_refs[0][0, 0] = l
            lm_refs[1][0, 0] = m_scr[...]


def _forward(q, k, v, segment_ids, lo, scale, block, interpret, residuals):
    """o, and with `residuals` the softmax's sum and maximum per query
    (B, H, L), which the backward kernels read."""
    B, H, L, d = q.shape
    dv, n = v.shape[-1], L // block
    tile = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), lo.shape)
    five, per_query, per_query_dv, _ = _specs(block, d, dv, _outer, _inner,
                                              group=H // k.shape[1])
    per_query_shape = jax.ShapeDtypeStruct((B, H, L, LANES), jnp.float32)
    o, *lm = _call(
        functools.partial(_fwd_kernel, scale=scale), "segment_flash_fwd",
        _walk(lo, tile), lo, (q, k, v, *_seg_operands(segment_ids)), five,
        [per_query_dv] + [per_query] * (2 * residuals),
        [jax.ShapeDtypeStruct((B, H, L, dv), q.dtype)]
        + [per_query_shape] * (2 * residuals),
        [pltpu.VMEM((block, LANES), jnp.float32),
         pltpu.VMEM((block, LANES), jnp.float32),
         pltpu.VMEM((block, dv), jnp.float32)], interpret)
    return (o, *(x[..., 0] for x in lm))


# --------------------------------------------------------------- backward

def _probs_and_ds(q, k, v, qseg, kseg, l, m, do, di, i, j, scale):
    """Of one walked tile: the softmax weights p (queries x keys) from
    the forward pass's maximum and sum, and ds = dL/d(scores)."""
    s = _scores(q, k, qseg, kseg, i, j, scale)
    width = s.shape[1]
    p = jnp.exp(s - _lanes(m, width)) * _lanes(1.0 / l, width)
    dp = lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
    return p, (dp - _lanes(di, width)) * p * scale


def _dkv_kernel(kj_ref, qi_ref, steps_ref, hi_ref, q_ref, k_ref, v_ref,
                qseg_ref, kseg_ref, l_ref, m_ref, do_ref, di_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale):
    b, t = pl.program_id(0), pl.program_id(2)
    j, i = kj_ref[b, t], qi_ref[b, t]
    live = t < steps_ref[b]

    @pl.when(live & (i == j))
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    @pl.when(live)
    def _():
        q, do = q_ref[0, 0], do_ref[0, 0]
        p, ds = _probs_and_ds(
            q, k_ref[0, 0], v_ref[0, 0], qseg_ref[0], kseg_ref[0, :1],
            l_ref[0, 0], m_ref[0, 0], do, di_ref[0, 0], i, j, scale)
        dv_scr[...] += lax.dot(p.T.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dk_scr[...] += lax.dot(ds.T.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)

    @pl.when(live & (i == hi_ref[b, j]))
    def _():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(qi_ref, kj_ref, steps_ref, lo_ref, q_ref, k_ref, v_ref,
               qseg_ref, kseg_ref, l_ref, m_ref, do_ref, di_ref,
               dq_ref, dq_scr, *, scale):
    b, t = pl.program_id(0), pl.program_id(2)
    i, j = qi_ref[b, t], kj_ref[b, t]
    live = t < steps_ref[b]

    @pl.when(live & (j == lo_ref[b, i]))
    def _():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    @pl.when(live)
    def _():
        k = k_ref[0, 0]
        _, ds = _probs_and_ds(
            q_ref[0, 0], k, v_ref[0, 0], qseg_ref[0], kseg_ref[0, :1],
            l_ref[0, 0], m_ref[0, 0], do_ref[0, 0], di_ref[0, 0], i, j, scale)
        dq_scr[...] += lax.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32)

    @pl.when(live & (j == i))
    def _():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _backward(q, k, v, segment_ids, lo, hi, o, l, m, do, scale, block,
              interpret):
    B, H, L, d = q.shape
    dv, n = v.shape[-1], L // block
    tile = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), lo.shape)
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    l, m, di = (jnp.broadcast_to(x[..., None], (B, H, L, LANES))
                for x in (l, m, di))
    operands = (q, k, v, *_seg_operands(segment_ids), l, m, do, di)

    # dK/dV: a key tile stays (outer), its query tiles pass under it.
    five, per_query, per_query_dv, at_k = _specs(block, d, dv, _inner, _outer)
    dk, dv_ = _call(
        functools.partial(_dkv_kernel, scale=scale), "segment_flash_bwd_dkv",
        _walk(tile, hi), hi, operands,
        five + [per_query, per_query, per_query_dv, per_query],
        [pl.BlockSpec((1, 1, block, d), at_k),
         pl.BlockSpec((1, 1, block, dv), at_k)],
        [jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [pltpu.VMEM((block, d), jnp.float32),
         pltpu.VMEM((block, dv), jnp.float32)], interpret)

    # dQ: a query tile stays (outer), its key tiles pass under it.
    five, per_query, per_query_dv, _ = _specs(block, d, dv, _outer, _inner)
    dq = _call(
        functools.partial(_dq_kernel, scale=scale), "segment_flash_bwd_dq",
        _walk(lo, tile), lo, operands,
        five + [per_query, per_query, per_query_dv, per_query],
        five[0], jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((block, d), jnp.float32)], interpret)
    return dq, dk, dv_


# ------------------------------------------------------------------ entry

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def segment_flash_attention(q, k, v, segment_ids, lo, hi, scale: float,
                            block: int, interpret: bool = False):
    """Causal attention inside each segment of a packed row.

    q, k: (B, H, L, d), v: (B, H, L, dv), heads first; segment_ids
    (B, L); lo, hi: (B, L // block) int32, the tile bounds of
    `ops/attention.segment_tile_bounds(segment_ids, block)`. L is a
    multiple of `block`, and `block`, d and dv of 128
    (`ops/attention.flash_tiles_fit`). k and v may hold H / group heads
    (grouped keys: forward only). Returns (B, H, L, dv) in q's dtype.
    `interpret` runs the kernels in Pallas's interpreter (the CPU
    tests)."""
    if q.shape[1] % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(f"{q.shape[1]} query heads do not divide over "
                         f"{k.shape[1]} key and {v.shape[1]} value heads")
    return _forward(q, k, v, segment_ids, lo, scale, block, interpret, False)[0]


def _vjp_fwd(q, k, v, segment_ids, lo, hi, scale, block, interpret):
    if k.shape[1] != q.shape[1]:
        raise NotImplementedError(
            "segment_flash_attention with grouped keys "
            f"({q.shape[1]} query heads on {k.shape[1]} key heads) has no "
            "backward pass: its dK/dV kernel writes one tile a query head")
    o, l, m = _forward(q, k, v, segment_ids, lo, scale, block, interpret, True)
    return o, (q, k, v, segment_ids, lo, hi, o, l, m)


def _vjp_bwd(scale, block, interpret, residuals, do):
    q, k, v, segment_ids, lo, hi, o, l, m = residuals
    dq, dk, dv = _backward(q, k, v, segment_ids, lo, hi, o, l, m, do,
                           scale, block, interpret)
    return dq, dk, dv, None, None, None


segment_flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
