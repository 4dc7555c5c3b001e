"""Local→global broadcast attention (reference C9/C10, paper-corrected).

The global track attends over the local (per-residue) track with a single
query set derived from the global vector — O(H·k·L), not O(L²). This is
the architecture's native answer to long sequences (SURVEY C19).

Paper-faithful redesign of the reference implementation, which has three
bugs this module deliberately does not reproduce:
- heads lived in a plain Python list, so their parameters were untrained
  and unserialized (reference modules.py:73-81; here they are pytree
  leaves, stacked on a head axis and computed as one batched einsum
  instead of a Python loop over heads, reference modules.py:87-92);
- softmax ran over the tiled-query axis instead of the sequence axis
  (reference modules.py:34,58; here softmax is over L);
- the reference tiles the global vector `key_dim` times to manufacture a
  (B, k, G) query block (reference modules.py:51) — an artifact of the
  first two bugs; here each head has ONE query, as in the paper.

Shapes (B=batch, L=seq, C=local_dim, G=global_dim, H=heads, k=key_dim,
v=value_dim=G/H):
  q = tanh(global · Wq)        (B,G)·(H,G,k)   -> (B,H,k)
  K = tanh(local · Wk)         (B,L,C)·(H,C,k) -> (B,H,L,k)
  V = gelu(local · Wv)         (B,L,C)·(H,C,v) -> (B,H,L,v)
  scores = q·K / sqrt(k)                       -> (B,H,L)   [pad-masked]
  out = softmax_L(scores)·V                    -> (B,H,v)   -> (B,G)

The tanh/gelu activations on Q/K/V follow the reference heads (reference
modules.py:49-56), which mirror the original Keras ProteinBERT. Projections
are bias-free like the reference's raw `randn` parameter matrices
(reference modules.py:27-32). Softmax is computed in float32.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from proteinbert_tpu.ops.layers import Params

_proj_init = jax.nn.initializers.lecun_normal(in_axis=1, out_axis=2)


def global_attention_init(
    key: jax.Array, local_dim: int, global_dim: int, key_dim: int, num_heads: int
) -> Params:
    assert global_dim % num_heads == 0, (
        f"global_dim {global_dim} % num_heads {num_heads} != 0"
    )  # reference modules.py:108
    value_dim = global_dim // num_heads  # reference modules.py:119
    kq, kk, kv = jax.random.split(key, 3)
    return {
        "wq": _proj_init(kq, (num_heads, global_dim, key_dim), jnp.float32),
        "wk": _proj_init(kk, (num_heads, local_dim, key_dim), jnp.float32),
        "wv": _proj_init(kv, (num_heads, local_dim, value_dim), jnp.float32),
    }


def global_attention_apply(
    params: Params,
    local: jax.Array,
    global_: jax.Array,
    pad_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Attend from the global vector over local positions.

    Args:
      local: (B, L, C) local track.
      global_: (B, G) global track.
      pad_mask: optional (B, L) bool, True at REAL positions. Padding is
        excluded from the softmax (the reference attends over padding,
        reference modules.py:58 — corrected here).
    Returns:
      (B, G) attention output in the activation dtype of `local`.
    """
    dtype = local.dtype
    wq = params["wq"].astype(dtype)
    wk = params["wk"].astype(dtype)
    wv = params["wv"].astype(dtype)
    key_dim = wq.shape[-1]

    q = jnp.tanh(jnp.einsum("bg,hgk->bhk", global_, wq))
    k = jnp.tanh(jnp.einsum("blc,hck->bhlk", local, wk))
    v = jax.nn.gelu(jnp.einsum("blc,hcv->bhlv", local, wv))

    scores = jnp.einsum("bhk,bhlk->bhl", q, k) / jnp.sqrt(
        jnp.asarray(key_dim, dtype)
    )
    scores = scores.astype(jnp.float32)
    if pad_mask is not None:
        scores = jnp.where(pad_mask[:, None, :], scores, jnp.float32(-1e30))
    weights = jax.nn.softmax(scores, axis=-1).astype(dtype)

    out = jnp.einsum("bhl,bhlv->bhv", weights, v)
    b, h, vd = out.shape
    return out.reshape(b, h * vd)


def packed_global_attention_apply(
    params: Params,
    local: jax.Array,
    global_: jax.Array,
    segment_ids: jax.Array,
    real_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Per-SEGMENT global attention over a packed row (data/packing.py).

    Each of a row's S packed proteins carries its own global vector and
    attends ONLY over its own positions: scores outside the segment are
    masked to -1e30, whose exp underflows to exactly 0.0 in float32 —
    so another segment's values contribute exact zeros to the weighted
    sum, and the cross-segment-leakage test can assert bit-identity
    (tests/test_packing.py). Segment slots with no positions in the row
    get a zero output (their uniform softmax over masked scores would
    otherwise mix arbitrary values; they carry zero loss weight either
    way, but zeroing keeps the (B, S, G) state leak-proof too).

    Args:
      local: (B, L, C) local track.
      global_: (B, S, G) per-segment global track.
      segment_ids: (B, L) int, 0 = pad, 1..S = segment index.
      real_mask: optional (B, L) bool, True at REAL (non-<pad>) token
        positions. Training packs carry no pad inside a segment, so it
        defaults to every in-segment position; the ragged SERVING path
        (serve/dispatch.RaggedDispatcher) packs bucket-quantized spans
        whose tails hold <pad> tokens — those must stay out of the
        softmax exactly as the bucketed path's pad_mask keeps them out.
    Returns:
      (B, S, G) attention output in the activation dtype of `local`.
    """
    dtype = local.dtype
    wq = params["wq"].astype(dtype)
    wk = params["wk"].astype(dtype)
    wv = params["wv"].astype(dtype)
    key_dim = wq.shape[-1]
    S = global_.shape[1]

    q = jnp.tanh(jnp.einsum("bsg,hgk->bshk", global_, wq))
    k = jnp.tanh(jnp.einsum("blc,hck->bhlk", local, wk))
    v = jax.nn.gelu(jnp.einsum("blc,hcv->bhlv", local, wv))

    scores = jnp.einsum("bshk,bhlk->bshl", q, k) / jnp.sqrt(
        jnp.asarray(key_dim, dtype)
    )
    scores = scores.astype(jnp.float32)
    seg_mask = (
        segment_ids[:, None, :]
        == jnp.arange(1, S + 1, dtype=segment_ids.dtype)[None, :, None]
    )  # (B, S, L)
    if real_mask is not None:
        seg_mask = seg_mask & real_mask[:, None, :]
    scores = jnp.where(seg_mask[:, :, None, :], scores, jnp.float32(-1e30))
    weights = jax.nn.softmax(scores, axis=-1).astype(dtype)

    out = jnp.einsum("bshl,bhlv->bshv", weights, v)
    seg_exists = seg_mask.any(axis=-1)  # (B, S)
    out = jnp.where(seg_exists[:, :, None, None], out,
                    jnp.zeros((), dtype))
    b, s, h, vd = out.shape
    return out.reshape(b, s, h * vd)


# --------------------------------------- token-to-token attention (glm_moe)

def causal_segment_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, segment_ids: jax.Array,
    scale: float, block: int,
) -> jax.Array:
    """Causal softmax attention inside each segment of a packed row.

    q, k: (B, L, H, d), v: (B, L, H, dv), segment_ids: (B, L). Position i
    attends to j <= i with segment_ids[j] == segment_ids[i]; pad positions
    (segment 0) see only each other, so no row of scores is ever empty.
    k and v may hold H / group heads (grouped keys: query head h reads
    key head h // group); here they are repeated to H.
    Queries go in blocks of `block`, each against the keys up to its own
    end (the keys after it are never multiplied), each block's scores in
    float32 and recomputed in the backward pass rather than kept:
    (B, H, block, L) at a time, never (B, H, L, L)."""
    L = q.shape[1]
    if k.shape[2] != q.shape[2]:
        group = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    outs = []
    for start in range(0, L, block):
        end = min(start + block, L)
        outs.append(_attention_block(
            q[:, start:end], k[:, :end], v[:, :end],
            segment_ids[:, start:end], segment_ids[:, :end], start, scale))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


@partial(jax.checkpoint, static_argnums=(5, 6))
def _attention_block(q, k, v, seg_q, seg_k, q_start, scale):
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    qi = q_start + jnp.arange(q.shape[1])
    ki = jnp.arange(k.shape[1])
    mask = ((ki[None, :] <= qi[:, None])[None]
            & (seg_q[:, :, None] == seg_k[:, None, :]))
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def flash_tiles_fit(seq_len: int, block: int, head_dim: int,
                    v_head_dim: int) -> bool:
    """Whether the flash kernels' tiles take these sizes."""
    from proteinbert_tpu.kernels.segment_flash import LANES

    b = min(block, seq_len)
    return not (b % LANES or seq_len % b or head_dim % LANES
                or v_head_dim % LANES)


def segment_tile_bounds(segment_ids: jax.Array, block: int):
    """(lo, hi), each (B, ceil(L / block)) int32: the tiles of `block`
    positions that causal attention inside segments can reach. Query tile
    i of row b has keys only in key tiles lo[b, i] .. i; key tile j has
    queries only in query tiles j .. hi[b, j].

    Rests on one contract: each segment id occupies ONE contiguous run of
    its row (padding, id 0, one run too), which `data/packing.py`
    guarantees. A run's start never falls as positions rise, so the
    earliest key any query of a tile reaches is the start of the run that
    holds the tile's FIRST position, and the latest query that reaches
    any key of a tile is the end of the run that holds its LAST. Where an
    id comes back after another, the bounds are those of the runs, and
    pairs across the gap fall outside them."""
    B, L = segment_ids.shape
    at = jnp.arange(L, dtype=jnp.int32)
    edge = segment_ids[:, 1:] != segment_ids[:, :-1]
    first = jnp.concatenate([jnp.ones((B, 1), bool), edge], axis=1)
    last = jnp.concatenate([edge, jnp.ones((B, 1), bool)], axis=1)
    run_start = lax.cummax(jnp.where(first, at, 0), axis=1)
    run_end = lax.cummin(jnp.where(last, at, L - 1), axis=1, reverse=True)
    tile_first = at[::block]
    tile_last = jnp.minimum(tile_first + block - 1, L - 1)
    return run_start[:, tile_first] // block, run_end[:, tile_last] // block


def tiles_walked_share(segment_ids: jax.Array, block: int) -> jax.Array:
    """Tiles inside `segment_tile_bounds` over tiles on or under the
    diagonal: what the flash kernels walk of the causal walk. 1.0 for
    rows that are one document each, and for a `block` past the row's
    end (one tile)."""
    lo, _ = segment_tile_bounds(segment_ids, block)
    n = lo.shape[1]
    walked = (jnp.arange(n, dtype=jnp.int32) - lo + 1).sum()
    return walked.astype(jnp.float32) / (lo.shape[0] * n * (n + 1) // 2)


def flash_segment_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, segment_ids: jax.Array,
    scale: float, block: int,
) -> jax.Array:
    """`causal_segment_attention` by this repo's Pallas TPU flash kernels
    (`kernels/segment_flash.py`, forward and both backward kernels):
    online softmax over tiles of keys in float32, nothing of size L x L
    ever in HBM, and of the causal tiles only those between
    `segment_tile_bounds` walked. Same arguments and result (grouped
    keys too, forward only, never repeated in HBM); `block` is
    the tile of queries and of keys. Sizes the tiles do not take are an
    error that names them, never another path."""
    from proteinbert_tpu.kernels.segment_flash import (
        LANES, segment_flash_attention,
    )

    L = q.shape[1]
    if not flash_tiles_fit(L, block, q.shape[-1], v.shape[-1]):
        raise ValueError(
            f"flash attention takes rows of a multiple of the block (itself a "
            f"multiple of {LANES}) and head sizes that are multiples of "
            f"{LANES}; got rows of {L}, block {block}, heads of "
            f"{q.shape[-1]} / {v.shape[-1]}")
    b = min(block, L)
    lo, hi = segment_tile_bounds(segment_ids, b)
    heads_first = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
    out = segment_flash_attention(
        heads_first(q), heads_first(k), heads_first(v), segment_ids, lo, hi,
        scale, b)
    return heads_first(out)
