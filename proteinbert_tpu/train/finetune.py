"""Epoch-based fine-tuning engine (SURVEY C14, completed).

The reference's fine-tune `train()`/`test()` pair exists only as
commented-out code — epoch loop, CosineAnnealingLR, grad clip, pluggable
metric dict, per-epoch checkpoints (reference utils.py:348-493). This is
that design finished and made TPU-native:

- one jitted `finetune_step` per iteration (forward + masked task loss +
  backward + clip + Adam with warmup-cosine), trunk and head in one
  gradient — or trunk frozen via an optax mask (task.freeze_trunk);
- epoch-based loop with per-epoch eval and best-metric tracking, the
  epoch/eval structure of the reference's sketch (reference
  utils.py:442-458);
- task losses by TaskConfig.kind: masked softmax CE (per-residue),
  softmax CE (per-protein class), MSE (per-protein scalar), all from
  logits (the reference pairs probability heads with CE — SURVEY ledger
  #3 — never repeated here).
"""

from __future__ import annotations

import logging
from functools import partial
from typing import Any, Dict, Iterable, Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import optax

from proteinbert_tpu.configs import FinetuneConfig
from proteinbert_tpu.data.vocab import PAD_ID
from proteinbert_tpu.models import finetune as ft_model
from proteinbert_tpu.obs.tracing import span
from proteinbert_tpu.train.metrics import DeviceMetricAccumulator
from proteinbert_tpu.train.schedule import make_optimizer, needs_loss_value
from proteinbert_tpu.train.train_state import DONATE_STATE, gradient_update

logger = logging.getLogger(__name__)


@flax.struct.dataclass
class FinetuneState:
    step: jax.Array
    params: Any          # {"trunk", "head"}
    opt_state: Any


def make_finetune_optimizer(cfg: FinetuneConfig) -> optax.GradientTransformation:
    tx = make_optimizer(cfg.optimizer)
    if cfg.task.freeze_trunk:
        # Mask the trunk subtree: its params get zero updates but remain
        # in the tree (so checkpoints and shardings see one structure).
        tx = optax.multi_transform(
            {"train": tx, "freeze": optax.set_to_zero()},
            param_labels=lambda params: {
                "trunk": jax.tree.map(lambda _: "freeze", params["trunk"]),
                "head": jax.tree.map(lambda _: "train", params["head"]),
            },
        )
    return tx


def create_finetune_state(
    key: jax.Array,
    cfg: FinetuneConfig,
    pretrained_trunk: Optional[Any] = None,
) -> FinetuneState:
    params = ft_model.init(key, cfg.model, cfg.task, pretrained_trunk)
    tx = make_finetune_optimizer(cfg)
    return FinetuneState(
        step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params)
    )


def task_loss(
    outputs: jax.Array, batch: Dict[str, jax.Array], kind: str
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Loss + metrics for one batch. `batch["labels"]`: (B, L) int for
    token_classification (pad positions ignored), (B,) int for
    sequence_classification, (B,) float for sequence_regression."""
    labels = batch["labels"]
    if kind == "token_classification":
        # Unlabeled positions are -1 (data/finetune_data.py): <sos>/<eos>,
        # padding, and any residue the source didn't label.
        w = ((batch["tokens"] != PAD_ID) & (labels >= 0)).astype(jnp.float32)
        safe = jnp.maximum(labels, 0)
        ce = optax.softmax_cross_entropy_with_integer_labels(outputs, safe)
        denom = jnp.maximum(w.sum(), 1.0)
        loss = (ce * w).sum() / denom
        acc = ((outputs.argmax(-1) == safe) * w).sum() / denom
        return loss, {"loss": loss, "accuracy": acc}
    if kind == "sequence_classification":
        ce = optax.softmax_cross_entropy_with_integer_labels(outputs, labels)
        loss = ce.mean()
        acc = (outputs.argmax(-1) == labels).mean().astype(jnp.float32)
        return loss, {"loss": loss, "accuracy": acc}
    if kind == "sequence_regression":
        pred = outputs[..., 0]
        err = pred - labels.astype(jnp.float32)
        loss = (err ** 2).mean()
        return loss, {"loss": loss, "mae": jnp.abs(err).mean()}
    raise ValueError(f"unknown task kind {kind!r}")


@partial(jax.jit, static_argnames="cfg", donate_argnums=DONATE_STATE)
def finetune_step(
    state: FinetuneState, batch: Dict[str, jax.Array], cfg: FinetuneConfig
) -> Tuple[FinetuneState, Dict[str, jax.Array]]:
    def loss_fn(params):
        outputs = ft_model.apply(
            params, batch["tokens"], cfg.model, cfg.task,
            batch.get("annotations"),
        )
        return task_loss(outputs, batch, cfg.task.kind)

    grads, metrics = jax.grad(loss_fn, has_aux=True)(state.params)
    params, opt_state = gradient_update(
        make_finetune_optimizer(cfg), state.params, grads, state.opt_state,
        metrics["loss"], needs_loss_value(cfg.optimizer),
    )
    return FinetuneState(step=state.step + 1, params=params,
                         opt_state=opt_state), metrics


@partial(jax.jit, static_argnames="cfg")
def finetune_eval_step(
    state: FinetuneState, batch: Dict[str, jax.Array], cfg: FinetuneConfig
) -> Dict[str, jax.Array]:
    outputs = ft_model.apply(
        state.params, batch["tokens"], cfg.model, cfg.task,
        batch.get("annotations"),
    )
    _, metrics = task_loss(outputs, batch, cfg.task.kind)
    return metrics


def evaluate(
    state: FinetuneState, batches: Iterable[Dict[str, Any]], cfg: FinetuneConfig
) -> Dict[str, float]:
    """Mean metrics over an eval split (the reference's test_step + metric
    aggregation, reference utils.py:171-217)."""
    # Per-batch scalars stay on device; drained in batched device_gets
    # (roundtrip-batching + dispatch backpressure + bounded memory —
    # see metrics.DeviceMetricAccumulator).
    acc = DeviceMetricAccumulator()
    for batch in batches:
        acc.add(finetune_eval_step(state, batch, cfg))
    n = acc.count
    return {k: v / max(n, 1) for k, v in acc.sums().items()}


def finetune(
    cfg: FinetuneConfig,
    train_batches,                      # callable(epoch) -> iterator of batches
    eval_batches=None,                  # callable() -> iterator, or None
    state: Optional[FinetuneState] = None,
    pretrained_trunk: Optional[Any] = None,
    checkpointer=None,                  # train.checkpoint.Checkpointer
    log_fn=None,
    telemetry=None,                     # obs.Telemetry (None = no-op)
    registry=None,                      # heads.HeadRegistry (opt-in: save
                                        # the trained head as a servable
                                        # artifact — ISSUE 8)
    register_name: Optional[str] = None,
) -> Dict[str, Any]:
    """Epoch loop; returns {"state", "history", "best"} (+ "head_id"
    when a `registry` is given).

    `best` tracks the best eval epoch by accuracy (classification) or
    -loss (regression), and with a `checkpointer` each epoch's state is
    saved (epoch number as the step) — the per-epoch-checkpoint +
    model-selection design of the reference's sketch (reference
    utils.py:442-458).

    With `registry`, the trained head is saved as a content-addressed
    artifact carrying the fingerprint of the trunk it was ACTUALLY
    trained against (post-training — with freeze_trunk that equals the
    pretrained trunk, so the head serves directly over the resident
    trunk; without it the fingerprint records the co-trained trunk and
    serving over a different one raises the typed TrunkMismatchError
    instead of silently producing garbage), plus the best eval metrics;
    a `head_registered` event lands on the telemetry stream.
    """
    from proteinbert_tpu.obs import as_telemetry

    tele = as_telemetry(telemetry)
    start_epoch = 0
    history: list = []
    best: Dict[str, Any] = {"epoch": -1, "score": -float("inf")}
    if state is None:
        state = create_finetune_state(
            jax.random.PRNGKey(cfg.train.seed), cfg, pretrained_trunk
        )
        if checkpointer is not None and checkpointer.latest_step() is not None:
            # Resume an interrupted fine-tune: the saved step IS the
            # number of completed epochs, and the saved data carries the
            # pre-resume history + best so model selection still spans
            # the WHOLE run.
            start_epoch = checkpointer.latest_step()
            if start_epoch >= cfg.task.epochs:
                raise ValueError(
                    f"checkpoint dir {checkpointer.directory} already holds "
                    f"{start_epoch} completed epochs >= task.epochs="
                    f"{cfg.task.epochs}; use a fresh directory or raise "
                    "task.epochs to continue training")
            state, data = checkpointer.restore(state)
            data = data or {}
            history = list(data.get("history", []))
            best = dict(data.get("best", best))
            logger.info("resumed fine-tune after epoch %d", start_epoch)

    if tele.enabled:
        import os

        from proteinbert_tpu.configs.config import config_to_dict

        tele.emit("run_start", step=start_epoch, kind="finetune",
                  config=config_to_dict(cfg), jax_version=jax.__version__,
                  pid=os.getpid(), resumed=bool(start_epoch))

    for epoch in range(start_epoch, cfg.task.epochs):
        # Same roundtrip batching as evaluate(): the per-step float(v)
        # fetches made every training step synchronous with the device.
        # Drains are batched and memory-bounded.
        acc = DeviceMetricAccumulator()
        for batch in train_batches(epoch):
            state, metrics = finetune_step(state, batch, cfg)
            acc.add(metrics)
        n = acc.count
        record = {
            "epoch": epoch,
            **{f"train_{k}": v / max(n, 1) for k, v in acc.sums().items()},
        }

        if eval_batches is not None and (
            (epoch + 1) % cfg.task.eval_every_epochs == 0
            or epoch == cfg.task.epochs - 1
        ):
            with span("finetune_eval", tele.spans, step=epoch + 1):
                em = evaluate(state, eval_batches(), cfg)
            record.update({f"eval_{k}": v for k, v in em.items()})
            tele.emit("eval", step=epoch + 1, metrics=em, kind="finetune")
            score = em.get("accuracy", -em.get("loss", float("inf")))
            if score > best["score"]:
                best = {"epoch": epoch, "score": score, **record}

        history.append(record)
        tele.emit("step", step=epoch + 1, metrics=record, kind="finetune")
        logger.info("finetune %s", record)
        if log_fn is not None:
            log_fn(epoch, record)
        if checkpointer is not None:
            checkpointer.save(epoch + 1, state,
                              {"history": history, "best": best})

    if checkpointer is not None:
        checkpointer.wait()

    head_id = None
    if registry is not None:
        import numpy as np

        from proteinbert_tpu.heads.registry import trunk_fingerprint

        # Fingerprint the trunk the head was trained AGAINST (the
        # post-training trunk: identical to the pretrained one under
        # freeze_trunk, the co-trained one otherwise) — the serving
        # side's compatibility check compares resident-trunk
        # fingerprints against exactly this value.
        fp = trunk_fingerprint(state.params["trunk"])
        metrics = {k: v for k, v in (history[-1] if history else {}).items()
                   if isinstance(v, (int, float))}
        metrics.update({k: v for k, v in best.items()
                        if k.startswith(("eval_", "train_"))
                        and isinstance(v, (int, float))})
        head_id = registry.save(
            jax.tree.map(np.asarray, state.params["head"]),
            cfg.task, fp, name=register_name, metrics=metrics,
            model={"local_dim": cfg.model.local_dim,
                   "global_dim": cfg.model.global_dim})
        tele.emit("head_registered", head_id=head_id, kind=cfg.task.kind,
                  name=register_name or head_id, trunk_fingerprint=fp,
                  metrics=metrics)
        logger.info("registered head %s (%s) in %s", head_id,
                    cfg.task.kind, registry.directory)

    # (emit sanitizes: a never-evaluated best's -inf score becomes null)
    tele.emit("run_end", outcome="completed", kind="finetune",
              perf={"best_epoch": best["epoch"],
                    "best_score": best["score"]})
    return {"state": state, "history": history, "best": best,
            "head_id": head_id}
