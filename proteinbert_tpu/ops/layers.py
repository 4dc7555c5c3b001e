"""Core functional layers: dense, LayerNorm, dilated Conv1d, embedding.

Design: every layer is a pair of pure functions — `*_init(key, ...) ->
params` (a plain dict pytree, always fp32 leaves) and `*_apply(params, x)`
(computes in the activation dtype of `x`, which the model sets to bfloat16
on TPU so matmuls/convs hit the MXU natively). This replaces the
reference's `nn.Module` layers (reference ProteinBERT/modules.py) with
jit/scan/shard-friendly pytrees; in particular every parameter is a pytree
leaf, fixing the reference bug where attention-head parameters lived in a
plain Python list and were invisible to the optimizer (reference
modules.py:73-81, SURVEY ledger #1).

Numerics:
- LayerNorm statistics are computed in float32 regardless of activation
  dtype, and normalize over the FEATURE axis only. The reference
  normalizes jointly over (seq_len, channels), which hard-codes the
  sequence length into the weight shapes (reference modules.py:148-151,
  SURVEY ledger #4); per-feature LN is paper-correct and required for
  length-bucketing and sequence sharding.
- Conv1d uses feature-last (B, L, C) layout — the natural layout for XLA
  TPU spatial convolution (and for sequence-sharding the L axis). The
  reference keeps channels-first (B, C, L) torch layout (reference
  modules.py:205-211).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

Params = Dict[str, jax.Array]

_dense_init = jax.nn.initializers.lecun_normal()
_conv_init = jax.nn.initializers.lecun_normal(in_axis=(0, 1), out_axis=2)
_embed_init = jax.nn.initializers.normal(stddev=1.0)


def dense_init(key: jax.Array, in_dim: int, out_dim: int, use_bias: bool = True) -> Params:
    p = {"kernel": _dense_init(key, (in_dim, out_dim), jnp.float32)}
    if use_bias:
        p["bias"] = jnp.zeros((out_dim,), jnp.float32)
    return p


def dense_apply(params: Params, x: jax.Array) -> jax.Array:
    """y = x @ W (+ b), contracting the last axis of x."""
    y = x @ params["kernel"].astype(x.dtype)
    if "bias" in params:
        y = y + params["bias"].astype(x.dtype)
    return y


def layer_norm_init(dim: int) -> Params:
    return {"scale": jnp.ones((dim,), jnp.float32),
            "bias": jnp.zeros((dim,), jnp.float32)}


def layer_norm_apply(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Per-position LN over the last (feature) axis; fp32 statistics."""
    x32 = x.astype(jnp.float32)
    mean = x32.mean(axis=-1, keepdims=True)
    var = x32.var(axis=-1, keepdims=True)
    y = (x32 - mean) * lax.rsqrt(var + eps)
    y = y * params["scale"] + params["bias"]
    return y.astype(x.dtype)


def conv1d_init(key: jax.Array, kernel_size: int, in_dim: int, out_dim: int) -> Params:
    return {
        "kernel": _conv_init(key, (kernel_size, in_dim, out_dim), jnp.float32),
        "bias": jnp.zeros((out_dim,), jnp.float32),
    }


def conv1d_apply(params: Params, x: jax.Array, dilation: int = 1) -> jax.Array:
    """'SAME'-padded 1D convolution in (B, L, C) layout.

    TPU-idiomatic lowering of the reference's torch Conv1d pair — the
    narrow k=9 d=1 and wide k=9 d=5 local-track convs (reference
    modules.py:124-147). XLA maps this onto the MXU as an implicit GEMM
    and, under a sequence-sharded `jit`, inserts the halo exchange for the
    (k-1)/2 * dilation boundary rows automatically.
    """
    y = lax.conv_general_dilated(
        x,
        params["kernel"].astype(x.dtype),
        window_strides=(1,),
        padding="SAME",
        rhs_dilation=(dilation,),
        dimension_numbers=("NWC", "WIO", "NWC"),
    )
    # Named for selective rematerialisation: the convs are ~85% of block
    # FLOPs, so model.remat_policy="convs" saves exactly these outputs
    # and recomputes only the cheap elementwise/LN tail in the backward
    # pass (models/proteinbert.encode).
    return checkpoint_name(y + params["bias"].astype(x.dtype), "conv_out")


def embedding_init(key: jax.Array, vocab_size: int, dim: int) -> Params:
    return {"embedding": _embed_init(key, (vocab_size, dim), jnp.float32)}


def embedding_apply(params: Params, ids: jax.Array, dtype: Optional[jnp.dtype] = None) -> jax.Array:
    table = params["embedding"]
    if dtype is not None:
        table = table.astype(dtype)
    return jnp.take(table, ids, axis=0)


# ------------------------------------------------- decoder layers (glm_moe)

def rms_norm_apply(scale: jax.Array, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm over the last axis, statistics in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def segment_positions(segment_ids: jax.Array) -> jax.Array:
    """(B, L) position of every token inside its own segment: the count
    restarts wherever the segment id changes (packed rows, data/packing.py)."""
    idx = jnp.arange(segment_ids.shape[-1], dtype=jnp.int32)
    starts = jnp.concatenate(
        [jnp.ones_like(segment_ids[..., :1], bool),
         segment_ids[..., 1:] != segment_ids[..., :-1]], axis=-1)
    return idx - lax.cummax(jnp.where(starts, idx, 0), axis=segment_ids.ndim - 1)


def rotary_apply(x: jax.Array, positions: jax.Array, theta: float,
                 interleave: bool = False,
                 rotary_dim: Optional[int] = None) -> jax.Array:
    """Rotary position embedding over the last axis, half-split pairing
    (dimension j turns with dimension j + d/2) or, with `interleave`,
    neighbours (2j with 2j + 1). x: (B, L, ..., d), positions: (B, L).
    With `rotary_dim` only the first `rotary_dim` of the d turn (pairs
    and angles are those of a head of that size) and the rest pass
    unchanged. Angles in float32."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        turned = rotary_apply(x[..., :rotary_dim], positions, theta, interleave)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq    # (B, L, d/2)
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x32 = x.astype(jnp.float32)
    if interleave:
        a, b = x32[..., 0::2], x32[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape).astype(x.dtype)
    a, b = x32[..., : d // 2], x32[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def swiglu_apply(params: Params, x: jax.Array) -> jax.Array:
    """W_down(silu(W_gate x) * W_up x), no bias."""
    dt = x.dtype
    gate = x @ params["gate"].astype(dt)
    up = x @ params["up"].astype(dt)
    return (jax.nn.silu(gate) * up) @ params["down"].astype(dt)


def ffn_apply(params: Params, x: jax.Array, kind: str = "swiglu") -> jax.Array:
    """A feed-forward part by its kind: "swiglu" (`swiglu_apply`) or
    "relu2", W_down(relu(W_up x)^2): two matrices, no gate, no bias."""
    if kind == "swiglu":
        return swiglu_apply(params, x)
    dt = x.dtype
    return jnp.square(jax.nn.relu(x @ params["up"].astype(dt))) @ params["down"].astype(dt)
