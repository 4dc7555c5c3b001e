"""Pallas TPU kernels of the KDA core (`ops/kda.py` has the mathematics
and the same computation in plain jax).

In plain jax the per-chunk preparation makes a dozen operands of the
size of q, each a round trip through HBM (the v5e's compiler counts 22.7
GB a layer at 2 x 8,192 x 32 heads: 28 ms at the peak bandwidth, for an
algorithm whose inputs and output are 1.3 GB). Here q, k, v and the log
decay are read where they lie, (B, L, H * d) float32, one (chunk, d)
block a grid step, and everything of their size stays in VMEM. Two
kernels with the triangular inverse between them in XLA (forward
substitution is sequential in rows of 16 numbers: no work for a kernel,
and the matrices are chunk x chunk, a quarter of q's size):

  `kda_pairs`  per (row, head, chunk), all parallel: the running sum G of
               the log decay (a product with a triangle of ones), then A
               (strictly lower) and B (lower), each inside one segment,
               a block of SUB rows at a time around the block's first
               position, as `ops/kda.kda_prepare` does and for its reason.
  `kda_walk`   per (row, head) the chunks in order, the state S^T
               (d_v x d_k) in VMEM for the whole row: with T' = (I +
               Diag(beta) A)^-1 Diag(beta) from XLA,
                   W = (T' * reached) (exp(G) * K);  U = T' V - W S
                   O = reached * ((exp(G) * Q) S) + B U
                   S <- keeps * exp(G_C) * S + (reaches * exp(G_C - G) * K)^T U
               kept TRANSPOSED so that the decay, one factor per key
               channel, multiplies along the lanes.

`rows` (B, N, 8, chunk) float32 carries what the kernels know of
segments, one position a lane: row 0 the segment ids, row 1 `reached`
(the state the chunk starts from reaches this position), row 2 `reaches`
(this position reaches the state the chunk ends in), row 3 `keeps` (the
old state reaches the next chunk), each 0 or 1. A mask that scales ROWS
is turned into a column inside the kernel (`_column`). Everything is
float32; the products ask for full precision.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_HI = lax.Precision.HIGHEST
ROWS = 8                            # sublanes of the `rows` operand


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, precision=_HI, preferred_element_type=jnp.float32)
    return lax.dot_general(a, b, dims, precision=_HI,
                           preferred_element_type=jnp.float32)


def _running_sum(g):
    """Inclusive running sum down the rows of g (C, d)."""
    C = g.shape[0]
    lower = (lax.broadcasted_iota(jnp.int32, (C, C), 1)
             <= lax.broadcasted_iota(jnp.int32, (C, C), 0))
    return _dot(lower.astype(jnp.float32), g)


def _column(row):
    """(1, C) -> (C, 1): the diagonal of the row spread over C rows."""
    C = row.shape[1]
    diagonal = (lax.broadcasted_iota(jnp.int32, (C, C), 0)
                == lax.broadcasted_iota(jnp.int32, (C, C), 1))
    return jnp.sum(jnp.where(diagonal, row, 0.0), axis=1, keepdims=True)


def _heads(ref, d):
    """The (C, d) blocks of the heads a grid step holds side by side."""
    return [ref[:, h * d:(h + 1) * d] for h in range(ref.shape[1] // d)]


def _pairs_kernel(q_ref, k_ref, g_ref, rows_ref, a_ref, b_ref, *, sub, d):
    ids = rows_ref[0:1, :]
    C = ids.shape[1]
    t = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    same = _column(ids) == ids
    for h, (q, k, g) in enumerate(zip(_heads(q_ref, d), _heads(k_ref, d),
                                      _heads(g_ref, d))):
        a, b = _pairs(q, k, g, sub)
        a_ref[h] = jnp.where(same & (s < t), a, 0.0)
        b_ref[h] = jnp.where(same & (s <= t), b, 0.0)


def _pairs(q, k, g, sub):
    C = k.shape[0]
    G = _running_sum(g)
    position = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    a_rows, b_rows = [], []
    for lo in range(0, C, sub):
        ref = G[lo:lo + 1, :]
        left = jnp.exp(G[lo:lo + sub, :] - ref)                     # <= 1
        upto = position < lo + sub
        right = jnp.where(upto, jnp.exp(jnp.where(upto, ref - G, 0.0)), 0.0) * k
        both = _dot(jnp.concatenate([k[lo:lo + sub, :] * left,
                                     q[lo:lo + sub, :] * left]), right, _NT)
        a_rows.append(both[:sub])
        b_rows.append(both[sub:])
    return jnp.concatenate(a_rows), jnp.concatenate(b_rows)


def _walk_kernel(q_ref, k_ref, v_ref, g_ref, t_ref, b_ref, rows_ref, o_ref,
                 st_ref, *, dk, dv):
    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    reached, reaches = rows_ref[1:2, :], rows_ref[2:3, :]
    keeps = rows_ref[3:4, 0:1]
    reached_rows = _column(reached)
    C = reached.shape[1]
    for h, (q, k, v, g) in enumerate(zip(_heads(q_ref, dk), _heads(k_ref, dk),
                                         _heads(v_ref, dv), _heads(g_ref, dk))):
        G = _running_sum(g)
        decay, at_end = jnp.exp(G), G[C - 1:C, :]
        t, st = t_ref[h], st_ref[h]                     # st: (d_v, d_k)
        w = _dot(t * reached, decay * k)
        u = _dot(t, v) - _dot(w, st, _NT)               # (C, d_v)
        o_ref[:, h * dv:(h + 1) * dv] = (
            reached_rows * _dot(decay * q, st, _NT) + _dot(b_ref[h], u))
        st_ref[h] = (st * (keeps * jnp.exp(at_end))
                     + _dot(u.T * reaches, jnp.exp(at_end - G) * k))


def _heads_a_step(heads: int) -> int:
    """Heads a grid step holds: a step costs ~0.3 us whatever it does
    (PERF.md, PR 31), about what one head's products take."""
    return next(n for n in (4, 2, 1) if heads % n == 0)


def _specs(C, d, hs):
    per_token = pl.BlockSpec((None, C, hs * d), lambda b, h, n: (b, n, h))
    per_pair = pl.BlockSpec((None, hs, None, C, C),
                            lambda b, h, n: (b, h, n, 0, 0))
    rows = pl.BlockSpec((None, None, ROWS, C), lambda b, h, n: (b, n, 0, 0))
    return per_token, per_pair, rows


def kda_pairs(q, k, g, rows, heads: int, sub: int, interpret: bool = False):
    """q, k, g: (B, L, H * d_k) float32; rows: (B, N, ROWS, C).
    -> (A, B) each (B, H, N, C, C) float32."""
    B, L, width = k.shape
    N, C = rows.shape[1], rows.shape[3]
    hs, d = _heads_a_step(heads), width // heads
    per_token, per_pair, per_chunk = _specs(C, d, hs)
    pair = jax.ShapeDtypeStruct((B, heads, N, C, C), jnp.float32)
    return pl.pallas_call(
        partial(_pairs_kernel, sub=min(sub, C), d=d),
        grid=(B, heads // hs, N),
        in_specs=[per_token, per_token, per_token, per_chunk],
        out_specs=[per_pair, per_pair], out_shape=[pair, pair],
        interpret=interpret, name="kda_pairs",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
    )(q, k, g, rows)


def kda_walk(q, k, v, g, t, b, rows, heads: int, interpret: bool = False):
    """q, k, g: (B, L, H * d_k); v: (B, L, H * d_v); t, b: (B, H, N, C,
    C); rows: (B, N, ROWS, C). -> o (B, L, H * d_v) float32."""
    B, L, width = k.shape
    C = rows.shape[3]
    hs, dk, dv = _heads_a_step(heads), width // heads, v.shape[2] // heads
    per_key, per_pair, per_chunk = _specs(C, dk, hs)
    per_value = _specs(C, dv, hs)[0]
    return pl.pallas_call(
        partial(_walk_kernel, dk=dk, dv=dv),
        grid=(B, heads // hs, L // C),
        in_specs=[per_key, per_key, per_value, per_key, per_pair, per_pair,
                  per_chunk],
        out_specs=per_value,
        out_shape=jax.ShapeDtypeStruct(v.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((hs, dv, dk), jnp.float32)],
        interpret=interpret, name="kda_walk",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v, g, t, b, rows)
