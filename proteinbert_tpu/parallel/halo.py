"""Explicit sequence-parallel Conv1d via shard_map + ppermute halo exchange.

Long-context story (SURVEY §5): the local conv track is sharded over the
'seq' mesh axis; each shard needs `(k-1)/2 · dilation` boundary residues
from its neighbors (20 for the wide k=9 d=5 conv). Under plain `jit` XLA's
SPMD partitioner inserts this halo exchange automatically — that is the
default path (ops/layers.py). This module is the EXPLICIT version, for
(a) the Pallas kernel path, where the conv body is opaque to the SPMD
partitioner and the exchange must be done by hand, and (b) pinning the
communication pattern (one bidirectional ppermute per conv, pure ICI
neighbor traffic — the conv-track analogue of ring attention).

Edge shards receive zeros, matching 'SAME' zero padding.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from proteinbert_tpu.ops.layers import Params


def halo_exchange(
    x: jax.Array, halo: int, axis_name: str, axis_size: int
) -> jax.Array:
    """Pad the (B, L_shard, C) local block with `halo` rows from each
    side's neighbors along `axis_name` (zeros at the mesh edges).

    Handles halo > L_shard (e.g. the wide dilated conv on small test
    shards) by hopping multiple neighbors: each round forwards the block
    received in the previous round, so round r delivers shard i∓r's rows.
    Real configs need one round (L=2048/seq=4 → 512-row shards vs halo 20).
    """
    if halo == 0:
        return x
    if axis_size == 1:
        pad = jnp.zeros(x.shape[:1] + (halo,) + x.shape[2:], x.dtype)
        return jnp.concatenate([pad, x, pad], axis=1)
    L = x.shape[1]
    rounds = min(-(-halo // L), axis_size - 1)
    right_perm = [(i, i + 1) for i in range(axis_size - 1)]
    left_perm = [(i + 1, i) for i in range(axis_size - 1)]

    # Left context: blocks of shards i-1, i-2, ... (nearest last).
    left_blocks, cur = [], x
    for _ in range(rounds):
        cur = lax.ppermute(cur, axis_name, perm=right_perm)  # shard 0 gets zeros
        left_blocks.insert(0, cur)
    left = jnp.concatenate(left_blocks, axis=1)[:, -halo:, :] if rounds * L >= halo \
        else jnp.concatenate(
            [jnp.zeros(x.shape[:1] + (halo - rounds * L,) + x.shape[2:], x.dtype)]
            + left_blocks, axis=1)

    # Right context: blocks of shards i+1, i+2, ... (nearest first).
    right_blocks, cur = [], x
    for _ in range(rounds):
        cur = lax.ppermute(cur, axis_name, perm=left_perm)  # last shard gets zeros
        right_blocks.append(cur)
    right = jnp.concatenate(right_blocks, axis=1)[:, :halo, :] if rounds * L >= halo \
        else jnp.concatenate(
            right_blocks
            + [jnp.zeros(x.shape[:1] + (halo - rounds * L,) + x.shape[2:], x.dtype)],
            axis=1)

    return jnp.concatenate([left, x, right], axis=1)


def conv1d_halo(
    params: Params,
    x: jax.Array,
    dilation: int,
    axis_name: str,
    axis_size: int,
) -> jax.Array:
    """'SAME' Conv1d on a seq-sharded (B, L_shard, C) block, inside
    shard_map: halo-exchange then VALID conv. Requires odd kernel."""
    kernel = params["kernel"]
    k = kernel.shape[0]
    assert k % 2 == 1, "halo conv requires odd kernel"
    halo = (k - 1) // 2 * dilation
    xh = halo_exchange(x, halo, axis_name, axis_size)
    y = lax.conv_general_dilated(
        xh,
        kernel.astype(x.dtype),
        window_strides=(1,),
        padding="VALID",
        rhs_dilation=(dilation,),
        dimension_numbers=("NWC", "WIO", "NWC"),
    )
    return y + params["bias"].astype(x.dtype)


def seq_parallel_conv1d(
    mesh: Mesh, params: Params, x: jax.Array, dilation: int = 1
) -> jax.Array:
    """Standalone sharded 'SAME' conv over a global (B, L, C) array whose
    L axis is (to be) sharded over mesh axis 'seq' and B over data×fsdp."""
    n_seq = mesh.shape["seq"]

    fn = partial(
        conv1d_halo, dilation=dilation, axis_name="seq", axis_size=n_seq
    )
    from jax import shard_map

    return shard_map(
        lambda p, xb: fn(p, xb),
        mesh=mesh,
        in_specs=(P(), P(("data", "fsdp"), "seq", None)),
        out_specs=P(("data", "fsdp"), "seq", None),
    )(params, x)
