"""Delta-rule linear attention with a per-channel decay (KDA, Kimi Delta
Attention, arXiv:2510.26692) over packed documents, and the short causal
convolution in front of it.

Per head, with a state S (d_k x d_v) that is ZERO at each document's
first token:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                  alpha_t = exp(g_t), g_t in (-5, 0)

The recurrence is computed in chunks of C tokens. Write
u_t = beta_t (v_t - S_{t-1}^T (alpha_t * k_t)); then S_t = Diag(alpha_t)
S_{t-1} + k_t u_t^T, and with G_t the running sum of g inside the chunk
and S_0 the state the chunk starts from:

    A_ts = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])        s <  t
    B_ts = sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])        s <= t
    (I + Diag(beta) A) U = Diag(beta) (V - (exp(G) * K) S_0)
    O   = (exp(G) * Q) S_0 + B U
    S_C = Diag(exp(G_C)) S_0 + (exp(G_C - G) * K)^T U

`kda_prepare` computes, for every chunk at once, everything that does not
need S_0: A, B, T = (I + Diag(beta) A)^-1 (the WY / UT form: forward
substitution inside blocks of 16, the blocks merged by products), U0 = T
Diag(beta) V, W = T Diag(beta) (exp(G) * K), and the decayed q and k.
`kda_scan` then carries the state from chunk to chunk in a `lax.scan`:
U = U0 - W S, O = Q+ S + B U, S <- dec * S + K-^T U. On a TPU the same
computation runs as two Pallas kernels (`kernels/kda.py`: A and B; the
walk of the state with everything of q's size made in VMEM) around the
one step that stays in XLA, the triangular inverse.

exp(G_t - G_s) is a product of two factors around a point between s and
t, the first position of t's block of 16: one factor is at most 1, the
other at most exp(15 * 5) inside a block (which is why the decay has a
lower bound, and float32 holds it) and at most 1 across blocks.

A chunk may hold the end of one document and the start of the next: the
state is reset INSIDE the chunk by the segment ids (A and B keep pairs of
one segment only, S_0 reaches only the tokens of the segment that the
previous chunk ended in, and only the tokens of the chunk's last segment
reach S_C); no document is padded to a chunk. That rests on the packer's
contract that a segment id is one contiguous run (`data/packing.py`).

Everything here is float32 and plain `jax.numpy`, so it is differentiable
as it stands; the TPU kernel is forward only.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
SUB = 16      # tokens per block of the forward substitution


class Prepared(NamedTuple):
    """Per chunk, all (B, H, N, C, .) float32 but `dec` (B, H, N, d_k)."""

    u0: jax.Array    # T Diag(beta) V
    w: jax.Array     # T Diag(beta) (exp(G) * K), rows the old state reaches
    qp: jax.Array    # exp(G) * Q, rows the old state reaches
    b: jax.Array     # B, causal and inside one segment
    km: jax.Array    # exp(G_C - G) * K, rows that reach the next state
    dec: jax.Array   # exp(G_C) if the old state reaches the next, else 0


def segment_conv(x: jax.Array, kernel: jax.Array, segment_ids: jax.Array):
    """Causal depthwise convolution over the SAME document only.
    x: (B, L, C); kernel: (K, C), tap j weighs the token j positions back;
    segment_ids: (B, L). A tap that would reach before the document's
    first position reads zero."""
    out = x * kernel[0]
    for j in range(1, kernel.shape[0]):
        back = jnp.pad(x[:, :-j], ((0, 0), (j, 0), (0, 0)))
        same = jnp.pad(segment_ids[:, :-j], ((0, 0), (j, 0)),
                       constant_values=-1) == segment_ids
        out = out + jnp.where(same[..., None], back, 0) * kernel[j]
    return out


def _unit_lower_inverse(m: jax.Array) -> jax.Array:
    """(I + M)^-1 for M strictly lower triangular, (..., C, C) with C a
    multiple of SUB (or under it): forward substitution row by row inside
    the diagonal blocks of SUB, then [[T1, 0], [-T2 M21 T1, T2]] block
    pair by block pair until one block is left."""
    C = m.shape[-1]
    s = min(SUB, C)
    n = C // s
    lead = m.shape[:-2]
    blocks = m.reshape(lead + (n, s, n, s))
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(n)], axis=-3)
    eye = jnp.eye(s, dtype=m.dtype)
    t = jnp.broadcast_to(eye, diag.shape)
    for i in range(1, s):
        row = eye[i] - jnp.einsum("...j,...jk->...k", diag[..., i, :], t,
                                  precision=_HI)
        t = t.at[..., i, :].set(row)
    # t: (..., n, s, s), the inverses of the diagonal blocks
    while n > 1:
        half, size = n // 2, t.shape[-1]
        full = m.reshape(lead + (half, 2 * size, half, 2 * size))
        m21 = jnp.stack([full[..., i, size:, i, :size] for i in range(half)],
                        axis=-3)
        t1, t2 = t[..., 0::2, :, :], t[..., 1::2, :, :]
        low = -jnp.einsum("...ij,...jk,...kl->...il", t2, m21, t1, precision=_HI)
        zero = jnp.zeros_like(t1)
        t = jnp.concatenate(
            [jnp.concatenate([t1, zero], axis=-1),
             jnp.concatenate([low, t2], axis=-1)], axis=-2)
        n = half
    return t[..., 0, :, :]


def _chunk_masks(segment_ids, chunk: int):
    """segment_ids (B, L) -> per chunk (B, N, C) each: the ids, `reached`
    (the state the chunk starts from reaches the position: it lies in the
    segment the previous chunk ended in), `reaches` (the position reaches
    the state the chunk ends in: it lies in the chunk's last segment),
    and `keeps` (B, N): the old state reaches the next chunk."""
    B, L = segment_ids.shape
    seg = segment_ids.reshape(B, L // chunk, chunk)
    before = jnp.concatenate(                            # the id the chunk follows
        [jnp.full((B, 1), -1, seg.dtype), seg[:, :-1, -1]], axis=1)
    return (seg, seg == before[..., None], seg == seg[..., -1:],
            before == seg[..., -1])


def kda_prepare(q, k, v, g, beta, segment_ids, chunk: int) -> Prepared:
    """q, k, g: (B, L, H, d_k); v: (B, L, H, d_v); beta: (B, L, H);
    segment_ids: (B, L). L is a multiple of `chunk`, `chunk` of SUB (or
    under it)."""
    B, L, H, dk = q.shape
    C, N = chunk, L // chunk
    s = min(SUB, C)
    n_sub = C // s
    f32 = jnp.float32

    def chunks(a):      # (B, L, H, d) -> (B, H, N, C, d)
        return a.astype(f32).reshape(B, N, C, H, -1).transpose(0, 3, 1, 2, 4)

    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    beta = chunks(beta[..., None])                       # (B, H, N, C, 1)
    seg, reached, reaches, keeps = (
        m[:, None] for m in _chunk_masks(segment_ids, chunk))   # (B, 1, N, ..)
    reached, reaches = reached[..., None], reaches[..., None]
    same = seg[..., :, None] == seg[..., None, :]        # (B, 1, N, C, C)
    at = jnp.arange(C)
    G = jnp.cumsum(g, axis=-2)

    # A and B a block of rows at a time, around the block's first position
    sub_of = at // s
    a_rows, b_rows = [], []
    for i in range(n_sub):
        rows = slice(i * s, (i + 1) * s)
        ref = G[..., i * s:i * s + 1, :]
        left = jnp.exp(G[..., rows, :] - ref)                       # <= 1
        right = jnp.where((sub_of <= i)[:, None],
                          jnp.exp(jnp.where((sub_of <= i)[:, None], ref - G, 0.0)),
                          0.0) * k
        a_rows.append(jnp.einsum("...td,...sd->...ts", k[..., rows, :] * left,
                                 right, precision=_HI))
        b_rows.append(jnp.einsum("...td,...sd->...ts", q[..., rows, :] * left,
                                 right, precision=_HI))
    A = jnp.concatenate(a_rows, axis=-2)
    Bm = jnp.concatenate(b_rows, axis=-2)
    A = jnp.where(same & (at[None, :] < at[:, None]), A, 0.0)
    Bm = jnp.where(same & (at[None, :] <= at[:, None]), Bm, 0.0)

    T = _unit_lower_inverse(beta * A)
    decay = jnp.exp(G)
    rhs = jnp.concatenate([beta * v, beta * jnp.where(reached, decay * k, 0.0)],
                          axis=-1)
    solved = jnp.einsum("...ts,...sd->...td", T, rhs, precision=_HI)
    dv = v.shape[-1]
    to_end = jnp.exp(G[..., -1:, :] - G)
    return Prepared(
        u0=solved[..., :dv], w=solved[..., dv:],
        qp=jnp.where(reached, decay * q, 0.0), b=Bm,
        km=jnp.where(reaches, to_end * k, 0.0),
        dec=jnp.where(keeps[..., None], decay[..., -1, :], 0.0))


def kda_scan(p: Prepared) -> jax.Array:
    """The walk over chunks in plain jax. -> o (B, H, N, C, d_v)."""
    B, H = p.u0.shape[:2]
    dk, dv = p.w.shape[-1], p.u0.shape[-1]

    def step(S, xs):
        u0, w, qp, b, km, dec = xs
        mm = partial(jnp.einsum, precision=_HI)
        u = u0 - mm("bhck,bhkv->bhcv", w, S)
        o = mm("bhck,bhkv->bhcv", qp, S) + mm("bhcs,bhsv->bhcv", b, u)
        return dec[..., None] * S + mm("bhck,bhcv->bhkv", km, u), o

    chunk_first = jax.tree.map(lambda a: jnp.moveaxis(a, 2, 0), p)
    _, o = lax.scan(step, jnp.zeros((B, H, dk, dv), jnp.float32),
                    tuple(chunk_first))
    return jnp.moveaxis(o, 0, 2)


def _kda_plain(q, k, v, g, beta, segment_ids, chunk: int):
    B, L, H, _ = q.shape
    o = kda_scan(kda_prepare(q, k, v, g, beta, segment_ids, chunk))
    return o.transpose(0, 2, 3, 1, 4).reshape(B, L, H, -1)


def _kda_tpu(q, k, v, g, beta, segment_ids, chunk: int, interpret: bool = False):
    """The same computation through the Pallas kernels: q, k, v, g are
    read where they lie; of the preparation only the triangular inverse
    (on chunk x chunk matrices) is left to XLA."""
    from proteinbert_tpu.kernels import kda as kernels

    B, L, H, _ = q.shape
    N, f32 = L // chunk, jnp.float32
    flat = lambda a: a.astype(f32).reshape(B, L, -1)  # noqa: E731
    q, k, v, g = flat(q), flat(k), flat(v), flat(g)
    seg, reached, reaches, keeps = _chunk_masks(segment_ids, chunk)
    rows = jnp.stack(
        [seg, reached, reaches, jnp.broadcast_to(keeps[..., None], seg.shape)]
        + [jnp.zeros_like(seg)] * (kernels.ROWS - 4), axis=2).astype(f32)
    a, b = kernels.kda_pairs(q, k, g, rows, H, SUB, interpret=interpret)
    beta = beta.astype(f32).reshape(B, N, chunk, H).transpose(0, 3, 1, 2)
    t = _unit_lower_inverse(beta[..., None] * a) * beta[..., None, :]
    o = kernels.kda_walk(q, k, v, g, t, b, rows, H, interpret=interpret)
    return o.reshape(B, L, H, -1)


def kda_chunked(q, k, v, g, beta, segment_ids, chunk: int = 64) -> jax.Array:
    """o (B, L, H, d_v) float32 of the recurrence above. q and k arrive
    normalised and scaled; g is the log decay, beta in (0, 1)."""
    if q.shape[1] % chunk:
        raise ValueError(
            f"rows of {q.shape[1]} are no multiple of the chunk {chunk}")
    return lax.platform_dependent(
        q, k, v, g, beta, segment_ids,
        tpu=partial(_kda_tpu, chunk=chunk), default=partial(_kda_plain, chunk=chunk))
