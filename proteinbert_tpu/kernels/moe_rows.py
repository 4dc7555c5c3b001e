"""Pallas TPU kernels: the experts' loop's row movers (`ops/moe.py`).

A block of the grouped products takes up to `block` token rows out of
the layer's (tokens, D) array and adds its weighted result back at the
same rows. In a (tokens, D) array a token's row is one sublane of every
tile it crosses, and XLA's gather and scatter walk such rows one at a
time (~0.08 us a row gathered, ~0.23 us scattered at D = 2,560 on a
v5e: a tenth of what the bytes cost), padding rows of a part-full block
with the rest. Here the layer's arrays stand, for the length of the
loop, as SLABS: (tokens + block, S, 128), the token axis LEADING, so
that a token's row is S whole sublanes of 128 lanes lying together in
HBM and one DMA descriptor moves it (`pack` / `unpack`: one relayout a
layer; S is D / 128 rounded up to the 8 sublanes of a tile, the lanes
past D zeros). The kernels move the `n` rows of a block that are real
and no other:

  `gather_rows`       rows src[tok[r]], r < n, HBM -> the (block, S, 128)
                      result in VMEM, `IN_FLIGHT` copies under way; the
                      rows from n on are written as zeros (the backward
                      pass sums products over all `block` rows).
  `scatter_add_rows`  dst[tok[r]] += upd[r], r < n, `dst` in place: the
                      n rows come into VMEM, `upd` is added in float32,
                      and they go back before the kernel returns, so the
                      next block's call finds them (a token recurs in
                      the blocks of the other experts it chose).

`tok` and `n` are scalar-prefetch operands; within a block `tok` holds
no token twice, so no two copies of a call touch one row.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from proteinbert_tpu.kernels.path_counter import KernelPathCounter

LANES = 128
SUBLANES = 8
IN_FLIGHT = 16      # row copies under way at once (8: 7 % slower; 32, 64: no faster)

# Counted at trace time, once a traced loop: `pallas/slabs` where the
# movers are these kernels, `reference/not_tpu` and
# `reference/row_not_lanes` (D no multiple of 128) where they are XLA's
# gather and scatter.
_COUNTER = KernelPathCounter("moe_rows", "moe_rows_kernel_path_total")
MOE_ROWS_PATH_TOTAL: Dict[Tuple[str, str], int] = _COUNTER.total


def register_moe_rows_path_observer(cb) -> None:
    _COUNTER.register(cb)


def unregister_moe_rows_path_observer(cb) -> None:
    _COUNTER.unregister(cb)


def note_moe_rows_path(path: str, reason: str,
                       shape: Optional[tuple] = None) -> None:
    _COUNTER.note(path, reason, shape)


def slabs_fit(width: int) -> bool:
    """Whether rows of `width` numbers are held as slabs."""
    return width % LANES == 0


def pack(a):
    """(N, D) -> (N, S, 128) slabs, zeros in the lanes past D."""
    n, width = a.shape
    sublanes = -(-width // (LANES * SUBLANES)) * SUBLANES
    a = jnp.pad(a, [(0, 0), (0, sublanes * LANES - width)])
    return a.reshape(n, sublanes, LANES)


def unpack(a, width: int):
    """(N, S, 128) slabs -> (N, width)."""
    return a.reshape(a.shape[0], -1)[:, :width]


def _in_flight(n, copy):
    """Start copy(r) for r < n, at most IN_FLIGHT under way, and wait
    for them all."""
    def start(r, _):
        @pl.when(r >= IN_FLIGHT)
        def _():
            copy(r - IN_FLIGHT).wait()

        copy(r).start()
        return _

    def wait(r, _):
        copy(r).wait()
        return _

    lax.fori_loop(0, n, start, None)
    lax.fori_loop(jnp.maximum(n - IN_FLIGHT, 0), n, wait, None)


def _gather_kernel(tok_ref, n_ref, src_ref, out_ref, sems):
    n = n_ref[0]

    def zero(r, _):
        out_ref[r] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)
        return _

    lax.fori_loop(n, out_ref.shape[0], zero, None)
    _in_flight(n, lambda r: pltpu.make_async_copy(
        src_ref.at[tok_ref[r]], out_ref.at[r], sems.at[r % IN_FLIGHT]))


def gather_rows(src, tok, n, interpret: bool = False):
    """src: (N, S, 128) slabs in HBM; tok: (block,) int32; n: () int32.
    -> (block, S, 128): src[tok[r]] for r < n, zeros from n on."""
    shape = (tok.shape[0],) + src.shape[1:]
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(shape, lambda i, *_: (0, 0, 0)),
            scratch_shapes=[pltpu.SemaphoreType.DMA((IN_FLIGHT,))]),
        out_shape=jax.ShapeDtypeStruct(shape, src.dtype),
        interpret=interpret, name="moe_gather_rows",
    )(tok, n.reshape(1), src)


def _scatter_kernel(tok_ref, n_ref, upd_ref, _, dst_ref, rows, sems):
    n = n_ref[0]
    _in_flight(n, lambda r: pltpu.make_async_copy(
        dst_ref.at[tok_ref[r]], rows.at[r], sems.at[r % IN_FLIGHT]))
    # every row of the block, real or not: what lies past n is not sent
    rows[...] = rows[...] + upd_ref[...]
    _in_flight(n, lambda r: pltpu.make_async_copy(
        rows.at[r], dst_ref.at[tok_ref[r]], sems.at[r % IN_FLIGHT]))


def scatter_add_rows(dst, upd, tok, n, interpret: bool = False):
    """dst: (N, S, 128) slabs in HBM, updated in place; upd: (block, S,
    128) in dst's dtype. -> dst with dst[tok[r]] += upd[r] for r < n."""
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[pl.BlockSpec(upd.shape, lambda i, *_: (0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM(upd.shape, dst.dtype),
                            pltpu.SemaphoreType.DMA((IN_FLIGHT,))]),
        out_shape=jax.ShapeDtypeStruct(dst.shape, dst.dtype),
        input_output_aliases={3: 0},
        interpret=interpret, name="moe_scatter_add_rows",
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=32 * 2 ** 20),
    )(tok, n.reshape(1), upd, dst)
