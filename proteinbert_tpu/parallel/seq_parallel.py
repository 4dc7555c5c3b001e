"""Explicit sequence-parallel model path (SURVEY §5 long-context, §7 stage 10).

Under plain `jit`, XLA's SPMD partitioner already sequence-shards the
model (tests/test_parallel.py proves numerical parity) — that is the
default path. This module is the EXPLICIT shard_map version, needed when
the local track runs the Pallas fused kernel: a pallas_call is an opaque
custom call the partitioner cannot split, so the sharded program must be
written by hand. It is also the place where the communication pattern of
the architecture's context parallelism is pinned down and documented:

- local conv track: one bidirectional `ppermute` halo exchange per block
  (20 boundary residues for the k=9/d=5 wide conv) — pure neighbor ICI
  traffic, the conv analogue of ring attention's block rotation;
- global←local attention: a numerically-stable DISTRIBUTED SOFTMAX.
  Each shard computes its local scores; a `pmax` aligns the stabilizer,
  a `psum` of (exp-sum, exp·V) completes softmax(scores)·V exactly —
  per (batch, head) only a scalar + a value_dim vector cross the ICI,
  because this architecture has ONE query per head (ops/attention.py).
  This is the all-to-all-free degenerate case of ring attention: with a
  single query there is nothing to rotate, and context parallelism
  reduces to two tiny collectives per block;
- global track: replicated compute on every seq shard (G=512 is tiny);
  determinism makes the replicas bit-identical, no collective needed.

The result (for both forward and gradients — shard_map is differentiable,
and the halo/psum transpose to their adjoints automatically) matches the
unsharded model exactly; tests/test_seq_parallel.py asserts it.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from proteinbert_tpu.configs import ModelConfig, PretrainConfig
from proteinbert_tpu.data.vocab import PAD_ID
from proteinbert_tpu.kernels.fused_block import (
    fused_local_track_valid,
    local_track_valid_reference,
    pallas_interpret,
    pallas_supported,
    track_halo,
)
from proteinbert_tpu.models import proteinbert
from proteinbert_tpu.models.proteinbert import _cast_blocks, remat_wrap
from proteinbert_tpu.ops.layers import (
    dense_apply, embedding_apply, layer_norm_apply,
)
from proteinbert_tpu.parallel.halo import halo_exchange
from proteinbert_tpu.parallel.zero import zero_extent

Params = Dict[str, Any]

_BATCH_AXES = ("data", "fsdp")
_SEQ_AXIS = "seq"


def sharded_global_attention(
    params: Params,
    local: jax.Array,
    global_: jax.Array,
    pad_mask: jax.Array,
    axis_name: str = _SEQ_AXIS,
) -> jax.Array:
    """global_attention_apply (ops/attention.py) over a seq-sharded local
    track, via distributed softmax: exact same math as the unsharded op,
    with pmax/psum over `axis_name` supplying the global normalization."""
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
    scores = jnp.where(pad_mask[:, None, :], scores, jnp.float32(-1e30))

    # Global max stabilizer: all_gather the (B, H) per-shard maxes (pmax
    # lacks a differentiation rule; the stabilizer is shift-invariant, so
    # it carries no gradient anyway).
    m = lax.stop_gradient(jnp.max(
        lax.all_gather(scores.max(axis=-1), axis_name), axis=0))  # (B, H)
    e = jnp.exp(scores - m[..., None])                      # (B, H, Ls)
    denom = lax.psum(e.sum(axis=-1), axis_name)             # (B, H)
    num = lax.psum(
        jnp.einsum("bhl,bhlv->bhv", e.astype(dtype), v), axis_name
    )                                                       # (B, H, v)
    out = num / jnp.maximum(denom[..., None], 1e-30).astype(dtype)
    b, h, vd = out.shape
    return out.reshape(b, h * vd)


def _seq_block_apply(
    params: Params,
    local: jax.Array,
    global_: jax.Array,
    pad_mask: jax.Array,
    cfg: ModelConfig,
    axis_size: int,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    """models/proteinbert.block_apply on one seq shard (inside shard_map)."""
    track_params = {k: params[k] for k in ("narrow_conv", "wide_conv",
                                           "local_ln1", "local_dense",
                                           "local_ln2")}
    broadcast = jax.nn.gelu(dense_apply(params["global_to_local"], global_))
    H = track_halo(track_params, 1, cfg.wide_dilation)
    xh = halo_exchange(local, H, _SEQ_AXIS, axis_size)
    if cfg.use_pallas and pallas_supported(
        cfg.local_dim, local.shape[1], cfg.dtype,
        cfg.narrow_kernel, cfg.wide_kernel, cfg.wide_dilation,
    ):
        local = fused_local_track_valid(
            track_params, xh, broadcast, 1, cfg.wide_dilation, interpret
        )
    else:
        local = local_track_valid_reference(
            track_params, xh, broadcast, 1, cfg.wide_dilation
        )

    dense1 = jax.nn.gelu(dense_apply(params["global_dense1"], global_))
    attn = sharded_global_attention(params["attention"], local, global_, pad_mask)
    global_ = layer_norm_apply(params["global_ln1"], global_ + dense1 + attn)
    global_ = layer_norm_apply(
        params["global_ln2"],
        global_ + jax.nn.gelu(dense_apply(params["global_dense2"], global_)),
    )
    return local, global_


def _shard_forward(
    params: Params,
    tokens: jax.Array,
    annotations: jax.Array,
    cfg: ModelConfig,
    axis_size: int,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    """Per-shard body: mirrors proteinbert.encode + heads."""
    dtype = jnp.dtype(cfg.dtype)
    pad_mask = tokens != PAD_ID
    local = embedding_apply(params["embedding"], tokens, dtype)
    global_ = jax.nn.gelu(
        dense_apply(params["global_in"], annotations.astype(dtype))
    )

    body = remat_wrap(
        partial(_seq_block_apply, cfg=cfg, axis_size=axis_size,
                interpret=interpret),
        cfg,
    )

    if cfg.scan_blocks:
        def scan_body(carry, blk):
            l, g = carry
            l, g = body(blk, l, g, pad_mask)
            return (l, g), None

        # Same hoist as proteinbert.encode: cast the block stack to the
        # compute dtype ONCE outside the scan, so the f32->bf16 convert
        # is not re-run per block (and per backward recompute) inside
        # the remat-wrapped body.
        (local, global_), _ = lax.scan(
            scan_body, (local, global_),
            _cast_blocks(params["blocks"], dtype),
            unroll=cfg.scan_unroll,
            _split_transpose=cfg.scan_split_transpose)
    else:
        for blk in params["blocks"]:
            local, global_ = body(blk, local, global_, pad_mask)

    local_logits = dense_apply(params["local_head"], local).astype(jnp.float32)
    global_logits = dense_apply(params["global_head"], global_).astype(jnp.float32)
    return local_logits, global_logits


def seq_parallel_apply(
    mesh: Mesh,
    params: Params,
    tokens: jax.Array,
    annotations: jax.Array,
    cfg: ModelConfig,
) -> Tuple[jax.Array, jax.Array]:
    """Forward pass with the sequence axis explicitly sharded over the
    mesh's 'seq' axis (batch over data×fsdp). Interface and results match
    models/proteinbert.apply; use when cfg.use_pallas needs to run under
    sequence parallelism (see module docstring)."""
    axis_size = mesh.shape[_SEQ_AXIS]
    interpret = pallas_interpret()
    fn = partial(_shard_forward, cfg=cfg, axis_size=axis_size,
                 interpret=interpret)
    from jax import shard_map

    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(), P(_BATCH_AXES, _SEQ_AXIS), P(_BATCH_AXES, None)),
        out_specs=(P(_BATCH_AXES, _SEQ_AXIS, None), P(_BATCH_AXES, None)),
        # pallas_call's out_shape carries no varying-mesh-axes metadata,
        # so the vma/rep checker cannot type the fused-kernel path.
        check_vma=False,
    )(params, tokens, annotations)


@lru_cache(maxsize=8)
def make_seq_parallel_train_step(mesh: Mesh, cfg: PretrainConfig):
    """Jitted pretraining step whose forward runs seq_parallel_apply —
    drop-in for train_state.train_step when (seq > 1 and use_pallas).
    Corruption, loss, optimizer update are shared with the default step.

    grad_reduce_dtype="int8" is REJECTED here (typed QuantConfigError,
    mirroring the packing rejection below): the quantized reduce-
    scatter (parallel/quant.py) needs per-replica partial gradients
    from its own data-parallel shard_map, and this step's hand-written
    seq shard_map already owns the gradient computation — its grads
    exit as fully-reduced logical tensors the quantizer cannot
    compress. "bf16" stays the PR-2 cast-only reduction here
    (numerics, not wire — docs/distributed.md)."""
    if cfg.parallel.zero_update and cfg.parallel.grad_reduce_dtype == "int8":
        from proteinbert_tpu.parallel.quant import QuantConfigError

        raise QuantConfigError(
            "grad_reduce_dtype='int8' is not supported by the explicit "
            "sequence-parallel Pallas step: the quantized reduce-"
            "scatter needs per-replica partial gradients from its own "
            "data-parallel shard_map, which this hand-sharded path "
            "cannot provide. Disable model.use_pallas (the implicit-"
            "SPMD jit cannot quantize either — use a data/fsdp mesh), "
            "or keep grad_reduce_dtype to 'fp32'/'bf16' here.")
    import optax

    from proteinbert_tpu.data.corruption import corrupt_batch
    from proteinbert_tpu.train import train_state as ts
    from proteinbert_tpu.train.loss import pretrain_loss
    from proteinbert_tpu.train.schedule import make_optimizer, needs_loss_value

    def step(state, batch):
        if "segment_ids" in batch:
            raise NotImplementedError(
                "packed batches (data.packing) are not supported by the "
                "explicit sequence-parallel Pallas step: the fused kernel "
                "has no segment-boundary support yet (its guard falls "
                "back to XLA, which this hand-sharded path cannot use). "
                "Disable model.use_pallas (the implicit-SPMD jit "
                "seq-shards the boundary-masked packed model fine) or "
                "turn packing off.")
        key, step_key = jax.random.split(state.key)
        X, Y, W = corrupt_batch(
            step_key, batch["tokens"], batch["annotations"],
            token_randomize_prob=cfg.data.token_randomize_prob,
            annotation_corrupt_prob=cfg.data.annotation_corrupt_prob,
            annotation_drop_prob=cfg.data.annotation_drop_prob,
            annotation_add_prob=cfg.data.annotation_add_prob,
        )

        def loss_fn(params):
            local_logits, global_logits = seq_parallel_apply(
                mesh, params, X["local"], X["global"], cfg.model
            )
            return pretrain_loss(local_logits, global_logits, Y, W)

        grads, metrics = jax.grad(loss_fn, has_aux=True)(state.params)
        if cfg.parallel.zero_update and zero_extent(mesh) > 1:
            # ZeRO-1 weight update (parallel/zero.py): same shared
            # optimizer-apply, run on 1/(data*fsdp) shards between a
            # gradient reduce-scatter and a param all-gather.
            from proteinbert_tpu.parallel.zero import zero_gradient_update

            params, opt_state, grad_norm = zero_gradient_update(
                mesh, cfg.optimizer, state.params, grads, state.opt_state,
                metrics["loss"],
                grad_reduce_dtype=cfg.parallel.grad_reduce_dtype,
            )
        else:
            params, opt_state = ts.gradient_update(
                make_optimizer(cfg.optimizer), state.params, grads,
                state.opt_state, metrics["loss"],
                needs_loss_value(cfg.optimizer),
            )
            grad_norm = optax.global_norm(grads)
        metrics = dict(metrics)
        metrics["grad_norm"] = grad_norm
        from proteinbert_tpu.train.schedule import effective_lr

        metrics["lr"] = effective_lr(cfg.optimizer, opt_state, state.step)
        return ts.TrainState(step=state.step + 1, params=params,
                             opt_state=opt_state, key=key), metrics

    return jax.jit(step, donate_argnums=ts.DONATE_STATE)
