"""Compressed convolutional attention (CCA; Zyphra, arXiv:2510.04476, as
ZAYA1 uses it): what lies between the mixer's projections and its
attention core.

    z  = conv1(conv0([q~ ; k~]))   conv0 depthwise, conv1 grouped (one
                                   group a head, d -> d), both causal
                                   along the document, both with a bias
    m_q[h] = (q~[h] + k~[h // group]) / 2
    m_k[g] = mean of m_q over the query heads of group g
    q = z_q + m_q,  k = z_k + m_k
    q = sqrt(d) q / |q|,  k = exp(tau_g) sqrt(d) k / |k|     float32
    rotary on the first `partial_rotary_factor` of each head
    v = [v1_t ; v2_(t-1)]          the second half of the value heads is
                                   the PREVIOUS token's

Both convolutions and the value shift read backwards along a packed row,
so each reads zero where it would reach before its document's first
position: a document's answer does not depend on what is packed before
it (`data/packing.py`'s contract: one id, one contiguous run).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from proteinbert_tpu.ops.kda import segment_conv
from proteinbert_tpu.ops.layers import rotary_apply


def segment_back(x: jax.Array, segment_ids: jax.Array, j: int = 1) -> jax.Array:
    """x[:, t - j] at t where t - j lies in t's document, zeros
    elsewhere. x: (B, L, ...); segment_ids: (B, L)."""
    tail = [(0, 0)] * (x.ndim - 2)
    back = jnp.pad(x[:, :-j], [(0, 0), (j, 0)] + tail)
    same = jnp.pad(segment_ids[:, :-j], ((0, 0), (j, 0)),
                   constant_values=-1) == segment_ids
    return jnp.where(same.reshape(same.shape + (1,) * (x.ndim - 2)), back, 0)


def segment_group_conv(x: jax.Array, kernel: jax.Array,
                       segment_ids: jax.Array) -> jax.Array:
    """Causal grouped convolution over the SAME document only.
    x: (B, L, G, d); kernel: (K, G, d, d), tap j weighs the token j
    positions back and group g maps its own d channels to d; products in
    x's dtype, accumulated in float32."""
    tap = lambda a, w: jnp.einsum(  # noqa: E731
        "blgd,gde->blge", a, w, preferred_element_type=jnp.float32)
    out = tap(x, kernel[0])
    for j in range(1, kernel.shape[0]):
        out = out + tap(segment_back(x, segment_ids, j), kernel[j])
    return out


def cca_mix(p, q, k, v1, v2, segment_ids, positions, heads: int,
            kv_heads: int, rotary_dim: int, theta: float, dtype):
    """q: (B, L, heads * d), k: (B, L, kv_heads * d), v1, v2: (B, L,
    kv_heads * d / 2), float32, straight from the projections; p: the
    mixer's `conv0` (K0, C), `conv0_bias` (C,), `conv1` (K1, heads +
    kv_heads, d, d), `conv1_bias` (C,), `tau` (kv_heads,), C = (heads +
    kv_heads) d. -> q (B, L, heads, d), k, v (B, L, kv_heads, d) in
    `dtype`, as the core takes them."""
    f32 = jnp.float32
    B, L, _ = q.shape
    d, group = q.shape[-1] // heads, heads // kv_heads
    G = heads + kv_heads
    z = segment_conv(jnp.concatenate([q, k], axis=-1), p["conv0"].astype(f32),
                     segment_ids) + p["conv0_bias"].astype(f32)
    z = (segment_group_conv(z.reshape(B, L, G, d).astype(dtype),
                            p["conv1"].astype(dtype), segment_ids)
         + p["conv1_bias"].astype(f32).reshape(G, d))
    m_q = 0.5 * (q.reshape(B, L, kv_heads, group, d)
                 + k.reshape(B, L, kv_heads, 1, d))
    unit = lambda a: a * (d ** 0.5 * lax.rsqrt(  # noqa: E731
        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6))
    q = unit(z[:, :, :heads] + m_q.reshape(B, L, heads, d))
    k = unit(z[:, :, heads:] + m_q.mean(axis=3)) * jnp.exp(
        p["tau"].astype(f32))[:, None]
    turn = lambda a: rotary_apply(  # noqa: E731
        a, positions, theta, rotary_dim=rotary_dim).astype(dtype)
    v = jnp.concatenate([v1, segment_back(v2, segment_ids)], axis=-1)
    return turn(q), turn(k), v.reshape(B, L, kv_heads, d).astype(dtype)
