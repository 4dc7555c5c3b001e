"""Train state pytree + jitted train/eval steps.

The reference's training engine is an untyped bundle of loop locals —
model, optimizer, two schedulers, and an iteration counter scattered
through `pretrain()` (reference utils.py:220-345). Here the entire
training state is ONE pytree (params, opt_state, PRNG key, step), so it
jits, shards with a NamedSharding tree, and checkpoints (orbax) as a unit
— including the RNG key the reference forgets to checkpoint (SURVEY §5
checkpoint bullet).

The step's stages carry `jax.named_scope`s (`corrupt`, `forward`, `loss`,
`optimizer`, `step_metrics`; the model adds `embed`, `local_track`,
`attention`, `global_track`, `heads`): metadata on the compiled
instructions, read back by `obs/tracing.program_scopes` to give a device
trace's operations their layer. They change no operation.

`train_step` fuses, on device, everything the reference does across the
host/device boundary per iteration (reference utils.py:282-319):
corruption (host DataLoader workers there; `data/corruption.py` here),
forward, dual masked loss, backward, clip, Adam update, metrics. Under a
`jit` with a data-sharded batch, XLA inserts the gradient all-reduce over
the mesh automatically — the psum-over-ICI replacement for the torch DDP
the reference never had (SURVEY C18).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import optax

from proteinbert_tpu.configs import DecoderConfig, PretrainConfig
from proteinbert_tpu.models import glm_moe, proteinbert
from proteinbert_tpu.data.corruption import corrupt_batch, corrupt_packed_batch
from proteinbert_tpu.train.loss import (
    global_ranking_metrics, global_ranking_stats, packed_pretrain_loss,
    pretrain_loss,
)
from proteinbert_tpu.train.schedule import (
    effective_lr, make_optimizer, needs_loss_value, plateau_uses_eval,
)

# Every step that takes a train state donates it (train_step here,
# finetune_step, the ZeRO-1, quantized and explicit seq-parallel steps):
# the update happens in the state's own buffers, which is the HBM
# headroom a 16 GB chip needs at the presets' batch sizes.
DONATE_STATE = (0,)


@flax.struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any
    key: jax.Array


def gradient_update(
    tx, params: Any, grads: Any, opt_state: Any,
    loss: Any = None, needs_value: bool = False,
) -> Tuple[Any, Any]:
    """Shared optimizer-apply: update → params + cast-preserving add.
    Single source of truth for the default, sequence-parallel
    (parallel/seq_parallel.py), ZeRO-1 (parallel/zero.py) and fine-tune
    (train/finetune.py) steps."""
    extra = {"value": loss} if needs_value else {}
    with jax.named_scope("optimizer"):  # clip + Adam + apply
        updates, opt_state = tx.update(grads, opt_state, params, **extra)
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                              params, updates)
    return params, opt_state


def corrupt_for_step(
    state: "TrainState", batch: Dict[str, jax.Array], cfg: PretrainConfig,
):
    """The pretraining step's front QUARTER — split the RNG key and
    corrupt the clean batch — shared by `corrupt_forward_grads` below
    and the quantized-reduction step (parallel/quant.py, whose forward/
    backward runs inside a shard_map but whose corruption must be the
    SAME implicit-SPMD ops on the same step key, so fp32-vs-quantized
    runs see identical masking and their deviation is quantization
    noise alone). Returns (next state key, X, Y, W, segment_ids|None);
    a batch carrying "segment_ids" is a PACKED batch (data/packing.py)
    and corrupts segment-aware."""
    with jax.named_scope("corrupt"):
        key, step_key = jax.random.split(state.key)
        if "segment_ids" in batch:
            seg = batch["segment_ids"]
            X, Y, W = corrupt_packed_batch(
                step_key,
                batch["tokens"],
                seg,
                batch["annotations"],
                token_randomize_prob=cfg.data.token_randomize_prob,
                annotation_corrupt_prob=cfg.data.annotation_corrupt_prob,
                annotation_drop_prob=cfg.data.annotation_drop_prob,
                annotation_add_prob=cfg.data.annotation_add_prob,
            )
            return key, X, Y, W, seg
        X, Y, W = corrupt_batch(
            step_key,
            batch["tokens"],
            batch["annotations"],
            token_randomize_prob=cfg.data.token_randomize_prob,
            annotation_corrupt_prob=cfg.data.annotation_corrupt_prob,
            annotation_drop_prob=cfg.data.annotation_drop_prob,
            annotation_add_prob=cfg.data.annotation_add_prob,
        )
        return key, X, Y, W, None


def corrupt_forward_grads(
    state: "TrainState", batch: Dict[str, jax.Array], cfg: PretrainConfig,
) -> Tuple[jax.Array, Any, Dict[str, jax.Array]]:
    """The pretraining step's front half — split the RNG key, corrupt
    the clean batch, forward, loss, backward — shared verbatim by the
    default step below and the ZeRO-1 step (parallel/zero.py), so the
    corruption plumbing and loss contract cannot drift between them.
    Returns (next state key, grads, loss metrics).

    A batch carrying a "segment_ids" key is a PACKED batch
    (data/packing.py): corruption, model, and loss take the segment-
    aware path (per-segment annotation state + per-segment loss
    normalization), selected at trace time from the batch's pytree
    structure — no config flag needed on device."""
    key, X, Y, W, seg = corrupt_for_step(state, batch, cfg)
    if seg is not None:

        def loss_fn(params):
            with jax.named_scope("forward"):
                local_logits, global_logits = proteinbert.apply(
                    params, X["local"], X["global"], cfg.model,
                    segment_ids=seg,
                )
            with jax.named_scope("loss"):
                return packed_pretrain_loss(
                    local_logits, global_logits, Y, W, seg)

        grads, metrics = jax.grad(loss_fn, has_aux=True)(state.params)
        return key, grads, metrics
    pad_mask = W["local"] > 0

    def loss_fn(params):
        with jax.named_scope("forward"):
            local_logits, global_logits = proteinbert.apply(
                params, X["local"], X["global"], cfg.model, pad_mask
            )
        with jax.named_scope("loss"):
            return pretrain_loss(local_logits, global_logits, Y, W)

    grads, metrics = jax.grad(loss_fn, has_aux=True)(state.params)
    return key, grads, metrics


def _decoder_batch(batch: Dict[str, jax.Array]):
    """(tokens, segment ids) of a decoder batch; a batch without
    segment ids is one document a row."""
    tokens = batch["tokens"]
    return tokens, batch.get("segment_ids", jnp.ones_like(tokens))


def decoder_forward_grads(
    state: "TrainState", batch: Dict[str, jax.Array], cfg: PretrainConfig,
) -> Tuple[jax.Array, Any, Dict[str, jax.Array], Any]:
    """The front half of a step of the causal expert decoder
    (models/glm_moe.py): next-token and multi-token-prediction loss over
    a packed batch of token documents, and its gradients. Nothing is
    corrupted, so the state's key passes through. Returns (key, grads,
    metrics, the expert layers' counters that `update_balance_bias`
    reads); no gradient reaches `params["balance_bias"]`."""
    tokens, seg = _decoder_batch(batch)

    def loss_fn(params):
        with jax.named_scope("forward"):
            return glm_moe.loss_and_stats(params, tokens, seg, cfg.model)

    grads, (out, counters) = jax.grad(loss_fn, has_aux=True)(state.params)
    with jax.named_scope("step_metrics"):
        metrics = glm_moe.step_metrics(out, counters, seg, cfg.model)
        # The one metric that is no scalar: the experts every token chose,
        # (expert layers + prediction module, tokens, k), `n_routed_experts`
        # at a pad. The trainer's log fetch leaves it on the device; a
        # caller of the step may read which way the routing went.
        metrics["route_ids"] = counters["ids"]
    return state.key, grads, metrics, counters


def plateau_observation(cfg_opt, metrics: Dict[str, jax.Array],
                        plateau_value: Any):
    """The value the plateau transform observes this step: the train
    loss, or — under an eval-keyed plateau with a finite caller-provided
    value — the latest cadenced eval loss (+inf means "no eval yet" and
    falls back to the train loss so the placeholder can't tick the
    patience counter). One definition for the default and ZeRO-1 steps."""
    value = metrics["loss"]
    if plateau_uses_eval(cfg_opt) and plateau_value is not None:
        pv = jnp.asarray(plateau_value, dtype=jnp.float32)
        value = jnp.where(jnp.isfinite(pv), pv, metrics["loss"])
    return value


@jax.jit
def snapshot_train_state(state: TrainState) -> TrainState:
    """On-device copy of the whole state pytree, dispatched asynchronously.

    The overlapped checkpoint boundary (trainer/checkpoint.py) needs a
    version of the state whose buffers the training stream can never
    touch: `train_step` donates its state argument, so the buffers of
    `state` are REUSED by the very next step — a background device→host
    fetch reading them directly would either race the overwrite or (at
    the Python level) hit jax's deleted-buffer guard. The jitted copy
    returns fresh buffers that capture exactly the boundary step's
    values; because dispatch is async, this call costs host-enqueue time
    only, and the copy itself is device-side memcpy ordered BEFORE the
    next train step on the stream. The staged saver then device_gets the
    copy from a worker thread while training keeps dispatching."""
    return jax.tree.map(jnp.copy, state)


def create_train_state(key: jax.Array, cfg: PretrainConfig) -> TrainState:
    k_init, k_state = jax.random.split(key)
    model = glm_moe if isinstance(cfg.model, DecoderConfig) else proteinbert
    params = model.init(k_init, cfg.model)
    tx = make_optimizer(cfg.optimizer)
    # optax initialises some scalars (reduce_on_plateau's best/avg value)
    # from Python numbers, i.e. weakly typed; the first step returns them
    # strongly typed, and the SECOND step would then be traced and
    # compiled all over again. Fix the types at birth: one executable.
    opt_state = jax.tree.map(lambda x: jnp.asarray(x, dtype=x.dtype),
                             tx.init(params))
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=opt_state,
        key=k_state,
    )


@partial(jax.jit, static_argnames="cfg", donate_argnums=DONATE_STATE)
def train_step(
    state: TrainState, batch: Dict[str, jax.Array], cfg: PretrainConfig,
    plateau_value: Any = None,
) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """One fused pretraining step on CLEAN {"tokens","annotations"} batch.

    `plateau_value`: host-provided scalar the reduce_on_plateau transform
    observes INSTEAD of this step's train loss, when
    cfg.optimizer.plateau_metric == "eval_loss" (the trainer passes the
    latest cadenced eval loss; +inf means "no eval yet" and falls back
    to the train loss so the placeholder can't tick the patience
    counter). The trainer seeds the stream with an up-front eval
    bracket, so under `train()` the fallback never fires — it exists
    for direct callers of this function, and such callers should know
    the fallback mixes train-scale values into the plateau window
    (ADVICE r4)."""
    counters = None
    if isinstance(cfg.model, DecoderConfig):    # the model, by its config's type
        key, grads, metrics, counters = decoder_forward_grads(state, batch, cfg)
    else:
        key, grads, metrics = corrupt_forward_grads(state, batch, cfg)
    value = plateau_observation(cfg.optimizer, metrics, plateau_value)
    params, opt_state = gradient_update(
        make_optimizer(cfg.optimizer), state.params, grads, state.opt_state,
        value, needs_loss_value(cfg.optimizer),
    )
    if counters is not None:
        # The one state leaf no gradient trains (its gradient, and so
        # Adam's update of it, is exactly zero): moved AFTER the step,
        # from the step's own expert loads.
        with jax.named_scope("optimizer"):
            params = dict(params, balance_bias=glm_moe.update_balance_bias(
                params["balance_bias"], counters, cfg.model))

    metrics = dict(metrics)
    with jax.named_scope("step_metrics"):
        metrics["grad_norm"] = optax.global_norm(grads)
        metrics["lr"] = effective_lr(cfg.optimizer, opt_state, state.step)
    new_state = TrainState(
        step=state.step + 1, params=params, opt_state=opt_state, key=key
    )
    return new_state, metrics


@partial(jax.jit, static_argnames="cfg")
def eval_step(
    state: TrainState, batch: Dict[str, jax.Array], key: jax.Array,
    cfg: PretrainConfig,
) -> Dict[str, jax.Array]:
    """Corrupted-input eval with a caller-provided key (deterministic).

    Packed batches (a "segment_ids" key) are scored with the per-segment
    loss; the ranking metrics see each packed protein as its own row
    ((B, S, A) flattened to (B·S, A) — empty segment slots carry zero
    weight and are excluded by the metrics' own validity masks). The
    decoder has no corruption and no ranking head: its eval is the
    training loss on held-out documents."""
    if isinstance(cfg.model, DecoderConfig):
        tokens, seg = _decoder_batch(batch)
        _, (out, counters) = glm_moe.loss_and_stats(
            state.params, tokens, seg, cfg.model)
        return glm_moe.step_metrics(out, counters, seg, cfg.model)
    if "segment_ids" in batch:
        seg = batch["segment_ids"]
        X, Y, W = corrupt_packed_batch(
            key,
            batch["tokens"],
            seg,
            batch["annotations"],
            token_randomize_prob=cfg.data.token_randomize_prob,
            annotation_corrupt_prob=cfg.data.annotation_corrupt_prob,
            annotation_drop_prob=cfg.data.annotation_drop_prob,
            annotation_add_prob=cfg.data.annotation_add_prob,
        )
        local_logits, global_logits = proteinbert.apply(
            state.params, X["local"], X["global"], cfg.model,
            segment_ids=seg,
        )
        _, metrics = packed_pretrain_loss(
            local_logits, global_logits, Y, W, seg)
        A = global_logits.shape[-1]
        flat = lambda a: a.reshape(-1, A)  # noqa: E731
        gl, gy, gw = (flat(global_logits), flat(Y["global"]),
                      flat(W["global"]))
        metrics.update(global_ranking_metrics(gl, gy, gw))
        metrics["ranking_stats"] = global_ranking_stats(gl, gy, gw)
        return metrics
    X, Y, W = corrupt_batch(
        key,
        batch["tokens"],
        batch["annotations"],
        token_randomize_prob=cfg.data.token_randomize_prob,
        annotation_corrupt_prob=cfg.data.annotation_corrupt_prob,
        annotation_drop_prob=cfg.data.annotation_drop_prob,
        annotation_add_prob=cfg.data.annotation_add_prob,
    )
    pad_mask = W["local"] > 0
    local_logits, global_logits = proteinbert.apply(
        state.params, X["local"], X["global"], cfg.model, pad_mask
    )
    _, metrics = pretrain_loss(local_logits, global_logits, Y, W)
    # Ranking quality of the GO head — eval-only (kept out of the hot
    # train step; the trainer prefixes these with eval_). global_auroc /
    # global_p_at_k are the EXACT in-batch values; ranking_stats is the
    # mergeable histogram evaluate_batches pools into the split-level
    # metrics (a dataset AUROC is not a mean of batch AUROCs).
    metrics.update(global_ranking_metrics(
        global_logits, Y["global"], W["global"]))
    metrics["ranking_stats"] = global_ranking_stats(
        global_logits, Y["global"], W["global"])
    return metrics
