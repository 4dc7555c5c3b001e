"""Iteration-based pretraining loop (reference utils.py:220-345, TPU-native).

What changed vs the reference `pretrain()`:
- the whole device side of an iteration (corruption, fwd, bwd, clip,
  Adam, metrics) is ONE jitted `train_step` (train_state.py) — the
  reference crosses the host/device boundary several times per iteration
  (reference utils.py:287-301);
- under a mesh, batches are placed with a data-axis NamedSharding and the
  gradient all-reduce is compiled in by XLA (SURVEY C18 — the reference
  has no distributed path at all);
- checkpoints are orbax (sharded/async) and include RNG + data-iterator
  position (checkpoint.py), not a torch.save of partial state dicts;
- logging adds residues/sec/chip + MFU (metrics.py) to the reference's
  loss/LR/step-time line (reference utils.py:306-313).
"""

from __future__ import annotations

import contextlib
import logging
import os
import time
from typing import Any, Dict, Optional

import jax
import numpy as np

from proteinbert_tpu.configs import PretrainConfig
from proteinbert_tpu.obs import as_telemetry
from proteinbert_tpu.obs.tracing import (
    backend as touch_backend, note_program, span, startup_span,
)
from proteinbert_tpu.train import train_state as ts
from proteinbert_tpu.train.checkpoint import Checkpointer
from proteinbert_tpu.train.metrics import DeviceMetricAccumulator, StepTimer
from proteinbert_tpu.train.resilience import (
    GracefulShutdown, check_finite, flush_inflight_checkpoint,
)

logger = logging.getLogger(__name__)


def _parse_fault_secs(secs_s):
    """Seconds for a drill knob, or ValueError. Rejects what time.sleep
    would crash or hang on (negative, NaN, inf): the drill contract is
    "malformed specs are ignored, not fatal" — a drill knob must never
    be able to kill an uncheckpointed run."""
    secs = float(secs_s)
    if not (0 <= secs < float("inf")):
        raise ValueError(secs_s)
    return secs


def _fault_stall_spec():
    """Observability-drill fault injection (VERDICT r4 item 3): parse
    PBT_FAULT_STALL_AT="<1-based step>:<seconds>" into (step, secs).
    The trainer sleeps that long at the top of the named step — INSIDE
    the timed window, like a real host-side stall (slow async-save
    serialization, input starvation) — so a drill can
    assert the window_* metrics and the slow-window summary localize it.
    Never set in production; the spec is logged loudly when active."""
    spec = os.environ.get("PBT_FAULT_STALL_AT")
    if not spec:
        return None
    try:
        step_s, _, secs_s = spec.partition(":")
        step = int(step_s)
        if step < 1:
            raise ValueError(spec)
        return step, _parse_fault_secs(secs_s)
    except ValueError:
        logger.warning("ignoring malformed PBT_FAULT_STALL_AT=%r", spec)
        return None


def _fault_eval_stall_secs():
    """Companion drill knob: PBT_FAULT_EVAL_STALL="<seconds>" sleeps
    inside every eval bracket — INSIDE the discounted region, so the
    drill can assert a slow eval does NOT masquerade as a training
    stall in the window metrics (the negative control for the
    PBT_FAULT_STALL_AT positive). Same ignore-malformed contract."""
    spec = os.environ.get("PBT_FAULT_EVAL_STALL")
    if not spec:
        return None
    try:
        return _parse_fault_secs(spec)
    except ValueError:
        logger.warning("ignoring malformed PBT_FAULT_EVAL_STALL=%r", spec)
        return None


def _losses_said(m: dict, prefix: str = "") -> str:
    """The log line's detail: the two losses a step's total is made of,
    under the model's own names (ProteinBERT: local and global track; the
    decoder: main head and prediction module), and the first's accuracy."""
    first, second = (("main", "mtp") if f"{prefix}main_loss" in m
                     else ("local", "global"))
    return "(%s %.4f %s %.4f) acc %.3f" % (
        first, m[f"{prefix}{first}_loss"], second,
        m[f"{prefix}{second}_loss"], m[f"{prefix}{first}_acc"])


def pretrain(
    cfg: PretrainConfig,
    batch_iterator,
    state: Optional[ts.TrainState] = None,
    checkpointer: Optional[Checkpointer] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    eval_batches=None,
    log_fn=None,
    telemetry=None,
) -> Dict[str, Any]:
    """Run the pretraining loop; returns {"state", "history", "perf"}.

    Args:
      cfg: full config (model/data/optimizer/train/checkpoint).
      batch_iterator: either an iterator of CLEAN {"tokens","annotations"}
        numpy batches (per-host shards under multi-host), or — preferred
        when resuming — a callable `(skip_batches: int) -> iterator` so a
        restored run can fast-forward the data stream without loading the
        already-consumed batches (see make_pretrain_iterator's
        skip_batches). A plain iterator on resume falls back to draining
        the consumed batches one by one.
      state: resume state; fresh-initialized if None (and restored from
        `checkpointer` if it has a saved step).
      checkpointer: optional; enables save/restore at
        cfg.checkpoint.every_steps cadence (reference utils.py:227,324).
      mesh: optional device mesh; batches are sharded over its 'data'
        axis (and train state per parallel/sharding.py rules).
      eval_batches: optional callable() -> iterator of held-out CLEAN
        batches; every cfg.train.eval_every steps they are scored with
        eval_step under a step-derived (deterministic) corruption key and
        the averaged metrics land in the history as eval_* (the held-out
        loop the reference's train/test dataloader split was built for
        but never ran, reference utils.py:71-107).
      log_fn: optional callable(step, metrics_dict) for external loggers.
      telemetry: optional obs.Telemetry — structured run events
        (run_start/step/ckpt_stage/eval/requeue/nan_halt/run_end),
        metrics registry, and flight recorder. None = the NULL facade:
        every instrumented site below becomes a no-op (~zero hot-path
        cost — all emits sit at log/eval/boundary cadence anyway).
    """
    tele = as_telemetry(telemetry)
    batches_consumed = 0
    # Eval-stream state. last_eval_loss feeds the eval-keyed plateau
    # (+inf = "no eval yet" — a fresh run replaces it with a seed eval
    # bracket below, so the plateau window never mixes train-scale
    # values; train_step's train-loss fallback remains as a net);
    # best/stalled drive early stopping. All three are CHECKPOINTED
    # (below, alongside batches_consumed) and restored here: resetting
    # them on resume would (a) let the post-resume steps feed train loss
    # into the restored reduce_on_plateau state — poisoning its
    # best_value with train-scale values in exactly the train<<eval
    # regime the feature targets — and (b) make early stop inert under
    # the exit-75 requeue loop (each requeue would restart the patience
    # counter from a fresh +inf baseline).
    last_eval_loss = np.float32(np.inf)
    best_eval_loss = float("inf")
    stalled_evals = 0
    touch_backend()     # the runtime's start, where nothing touched it yet
    if state is None:
        with startup_span("startup.init_state"):
            state = ts.create_train_state(
                jax.random.PRNGKey(cfg.train.seed), cfg)
        if mesh is not None:
            # Place the fresh state per the sharding rules BEFORE any
            # restore: the checkpoint template's shardings tell orbax
            # where each shard goes (checkpoint.py:49-66) — restoring
            # into an unsharded template under a mesh would land the
            # whole state on one device (and under multi-host, make the
            # collective restore inconsistent). Also makes the fsdp/tp
            # intent of cfg.mesh actually apply to CLI-created states.
            from proteinbert_tpu.parallel.sharding import shard_train_state

            state = shard_train_state(state, mesh,
                                      zero_update=cfg.parallel.zero_update)
        if checkpointer is not None and checkpointer.latest_step() is not None:
            if tele.enabled:
                # A torn final checkpoint salvages to the previous step
                # with a note event (restore() docstring) — wired BEFORE
                # the restore so the fallback is on the run's record.
                checkpointer.on_note = lambda **f: tele.emit("note", **f)
            with startup_span("startup.restore"):
                state, data_state = checkpointer.restore(state)
            batches_consumed = int((data_state or {}).get("batches_consumed", 0))
            es = (data_state or {}).get("eval_stream") or {}
            if es:
                # None encodes +inf (inf is not strict-JSON).
                last_eval_loss = np.float32(
                    es["last"] if es.get("last") is not None else np.inf)
                best_eval_loss = (float(es["best"])
                                  if es.get("best") is not None
                                  else float("inf"))
                stalled_evals = int(es.get("stalled", 0))
            logger.info("resumed from checkpoint at step %d (%d batches consumed)",
                        int(state.step), batches_consumed)

    def data_state_for(consumed: int) -> Dict[str, Any]:
        d: Dict[str, Any] = {"batches_consumed": consumed}
        if np.isfinite(last_eval_loss) or stalled_evals:
            d["eval_stream"] = {
                "last": (float(last_eval_loss)
                         if np.isfinite(last_eval_loss) else None),
                "best": (float(best_eval_loss)
                         if np.isfinite(best_eval_loss) else None),
                "stalled": stalled_evals,
            }
        return d

    if callable(batch_iterator):
        batch_iterator = batch_iterator(batches_consumed)
    elif batches_consumed:
        # Keep the resumed run on the same data stream position it would
        # have had uninterrupted (the reference replays from scratch,
        # reference utils.py:267-282).
        logger.warning(
            "resuming with a plain iterator: draining %d consumed batches "
            "(pass a factory to skip them for free)", batches_consumed)
        for _ in range(batches_consumed):
            next(batch_iterator)

    prefetch_it = None
    if cfg.data.prefetch_depth > 0:
        # Hide host-side batch production (HDF5 reads, tokenization)
        # behind the asynchronously-dispatched device step.
        from proteinbert_tpu.data.prefetch import prefetch

        batch_iterator = prefetch_it = prefetch(batch_iterator,
                                                cfg.data.prefetch_depth)

    put = _make_batch_put(mesh)

    # The implicit-SPMD jit handles every sharding EXCEPT the Pallas fused
    # kernel under sequence parallelism (a pallas_call is opaque to the
    # partitioner) — that combination runs the explicit shard_map step
    # (parallel/seq_parallel.py).
    from proteinbert_tpu.train.schedule import plateau_uses_eval

    eval_keyed_plateau = plateau_uses_eval(cfg.optimizer)
    if eval_keyed_plateau and (eval_batches is None
                               or not cfg.train.eval_every):
        raise ValueError(
            "optimizer.plateau_metric='eval_loss' needs a cadenced eval "
            "stream: pass eval_batches and set train.eval_every > 0")
    if cfg.train.early_stop_patience and (eval_batches is None
                                          or not cfg.train.eval_every):
        raise ValueError(
            "train.early_stop_patience needs a cadenced eval stream: "
            "pass eval_batches and set train.eval_every > 0")

    from proteinbert_tpu.parallel.zero import zero_extent

    zero_on = (mesh is not None and cfg.parallel.zero_update
               and zero_extent(mesh) > 1)
    if cfg.parallel.zero_update and not zero_on:
        logger.warning(
            "parallel.zero_update requested but %s — running the "
            "replicated update",
            "no mesh was passed" if mesh is None
            else "the mesh has data*fsdp == 1 (nothing to shard across)")
    if cfg.parallel.grad_reduce_dtype != "fp32" and not zero_on:
        # The quantized reduce-scatter (parallel/quant.py) only exists
        # on the zero-update path: without it there IS no cross-replica
        # gradient reduction to compress, and silently training at fp32
        # when the config asked for int8/bf16 wire would misreport
        # every comm claim downstream.
        logger.warning(
            "parallel.grad_reduce_dtype=%r has no effect without an "
            "active ZeRO-1 update (zero_update on a data*fsdp > 1 "
            "mesh) — the replicated step reduces gradients at fp32",
            cfg.parallel.grad_reduce_dtype)
    # plateau_step is the eval-keyed variant (extra plateau_value arg);
    # the zero step carries it natively, mirroring train_step.
    plateau_step = (lambda state, batch, v:               # noqa: E731
                    ts.train_step(state, batch, cfg, plateau_value=v))
    if mesh is not None and cfg.mesh.seq > 1 and cfg.model.use_pallas:
        from proteinbert_tpu.parallel.seq_parallel import (
            make_seq_parallel_train_step,
        )

        if eval_keyed_plateau:
            raise ValueError(
                "plateau_metric='eval_loss' is not supported with the "
                "explicit sequence-parallel pallas step (its shard_map "
                "step takes no plateau_value input)")
        seq_step = make_seq_parallel_train_step(mesh, cfg)
        step_fn = lambda state, batch, _cfg: seq_step(state, batch)  # noqa: E731
        logger.info("using explicit sequence-parallel train step (pallas%s)",
                    " + zero-update" if zero_on else "")
    elif zero_on:
        from proteinbert_tpu.parallel.zero import make_zero_train_step

        zero_step = make_zero_train_step(mesh, cfg)
        step_fn = lambda state, batch, _cfg: zero_step(state, batch)  # noqa: E731
        plateau_step = (lambda state, batch, v:           # noqa: E731
                        zero_step(state, batch, v))
        logger.info(
            "using ZeRO-1 sharded-update train step (update sharded over "
            "data*fsdp = %d replicas, grad reduction %s%s)",
            zero_extent(mesh), cfg.parallel.grad_reduce_dtype,
            "" if cfg.parallel.grad_reduce_dtype == "fp32"
            else " — quantized reduce-scatter wire, parallel/quant.py")
    else:
        step_fn = ts.train_step
    if mesh is not None:
        from proteinbert_tpu.parallel.sharding import pin_state_sharding

        step_fn = pin_state_sharding(step_fn, state, static_argnums=2)
        plateau_step = pin_state_sharding(plateau_step, state)

    start_step = int(state.step)
    history: list = []

    if tele.enabled:
        if checkpointer is not None:
            # Checkpoint boundary lifecycle → ckpt_stage events, emitted
            # from wherever the save runs (incl. the stager thread:
            # EventLog is thread-safe).
            checkpointer.on_event = (
                lambda phase, save_step, **info:
                tele.emit("ckpt_stage", step=save_step, phase=phase, **info))
        from proteinbert_tpu.configs.config import config_to_dict

        tele.emit(
            "run_start", step=start_step, config=config_to_dict(cfg),
            jax_version=jax.__version__, pid=os.getpid(),
            mesh=({str(k): int(v) for k, v in mesh.shape.items()}
                  if mesh is not None else None),
            n_chips=(int(mesh.size) if mesh is not None
                     else jax.device_count()),
            resumed=bool(batches_consumed), zero_update=bool(zero_on),
        )
        if mesh is not None:
            # Per-chip persistent state bytes under the sharding rules
            # (the ZeRO-1 HBM claim, from shapes alone — no allocation).
            try:
                from proteinbert_tpu.parallel.zero import per_chip_state_bytes

                abstract = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
                for part, n in per_chip_state_bytes(
                        mesh, abstract,
                        zero_update=cfg.parallel.zero_update).items():
                    tele.metrics.gauge(
                        "per_chip_state_bytes", part=part).set(n)
            except Exception:
                logger.debug("per-chip state-bytes gauge failed",
                             exc_info=True)

    if eval_keyed_plateau and not np.isfinite(last_eval_loss):
        # Seed the plateau stream with ONE up-front eval bracket
        # (ADVICE r4): without it, the pre-first-eval steps feed TRAIN
        # losses into reduce_on_plateau's accumulation window via the
        # +inf fallback, and in the overfit regime this feature targets
        # (train << eval) that mixed-scale window seeds an unreachably
        # low best_value — a premature LR cut right after the first
        # real eval. One eval pass before the timer starts keeps every
        # observed value eval-scale from step 0. The in-step fallback
        # stays as a safety net for direct train_step callers.
        em = _evaluate(state, eval_batches(), put, cfg, start_step)
        last_eval_loss = np.float32(em["eval_loss"])
        best_eval_loss = min(best_eval_loss, float(em["eval_loss"]))
        history.append({"step": start_step, **em})
        tele.emit("eval", step=start_step, metrics=em, seed=True)
        logger.info("seed eval at step %d: eval loss %.4f (plateau "
                    "baseline)", start_step, em["eval_loss"])
        if log_fn is not None:
            log_fn(start_step, em)

    if (cfg.checkpoint.warm_start and checkpointer is not None
            and checkpointer.latest_step() is None):
        # Warm-start save (r3 collapse attribution, BASELINE.md): the
        # FIRST save of a run pays orbax directory init, thread-pool
        # spinup, and the first full device->host state fetch — in r3
        # that one-time cost landed inside the timed stream as the
        # 650-800 stretch. Paying it here, before the StepTimer
        # anchors, keeps the timed windows showing only the steady
        # per-boundary cost. Only on a PRISTINE directory: with any
        # checkpoint present the restore already walked the orbax
        # machinery, and orbax silently skips saves at step <=
        # latest_step anyway — the outcome is checked so "warm" is
        # never logged for a save that did not happen.
        if checkpointer.save(start_step, state, data_state_for(start_step)):
            checkpointer.wait()
            logger.info("warm-start checkpoint at step %d (pre-timer)",
                        start_step)
        else:
            logger.warning("warm-start save at step %d was skipped by "
                           "the checkpoint manager", start_step)

    n_chips = mesh.size if mesh is not None else jax.device_count()
    # Every rate this loop logs names the device it was measured on.
    device_kind = jax.devices()[0].device_kind
    timer = StepTimer(
        cfg.model,
        batch=cfg.data.batch_size,
        seq_len=cfg.data.seq_len,
        n_chips=n_chips,
    )
    preempted = False
    early_stopped = False
    diagnostic_saved = False
    ckpt_since_log = False  # a save started since the last log point
    data_wait_s = 0.0       # the `train.data_wait` spans' seconds, summed
    metrics = None
    # Overlapped boundaries: the checkpoint path needs every shard
    # addressable from this process (device_get assembles the snapshot
    # host-side); under multi-host the synchronous collective save is
    # the only correct path. The eval overlap is legal only when
    # nothing needs the eval value BEFORE the next train step — an
    # eval-keyed plateau feeds it into the optimizer and early stopping
    # decides the break at the boundary, so both keep the synchronous
    # bracket.
    overlap_ckpt = (checkpointer is not None and cfg.checkpoint.overlap
                    and jax.process_count() == 1)
    overlap_eval = (cfg.train.overlap_eval and not eval_keyed_plateau
                    and not cfg.train.early_stop_patience)
    pending_eval = None  # (1-based eval step, dispatch_eval handle)

    def drain_and_sync():
        # Force the enqueued steps to completion and fold the wait into
        # the timing window, so the returned perf summary is device
        # rate even when max_steps is not a multiple of log_every (the
        # in-loop log points do the same; this covers the tail).
        if metrics is not None:
            float(metrics["loss"])
            timer.sync()

    def flush_staged_overlap():
        # Join an in-flight staged save (the backpressure rule: at most
        # one stage, so a second boundary arriving mid-overlap waits
        # here — that wait is real stall and stays IN the timed window).
        # The seconds the stage ran hidden behind training go to the
        # overlap account; worker errors re-raise here.
        if checkpointer is None:
            return
        t0 = time.perf_counter()
        stats = checkpointer.flush_staged()
        if stats:
            stall = time.perf_counter() - t0
            timer.overlap(max(stats.get("overlap_s", 0.0) - stall, 0.0))

    def harvest_staged():
        # Non-blocking: fold a COMPLETED staged save into the overlap
        # account (worker errors surface here too, at the next log
        # point after the failure instead of silently never).
        if checkpointer is None:
            return
        stats = checkpointer.poll_staged()
        if stats:
            timer.overlap(stats.get("overlap_s", 0.0))

    def checked_save(save_step, save_state):
        # Orbax SILENTLY skips saves at step <= the directory's latest
        # (checkpoint.py) — at the preemption/early-stop/final sites a
        # skipped save must at least be loud, or a "state saved,
        # exiting" log could cover for lost progress (e.g. a run
        # started with an explicit `state` against a mismatched
        # directory whose newest checkpoint is ahead of it).
        flush_staged_overlap()  # ordering: one save writing at a time
        if not checkpointer.save(save_step, save_state,
                                 data_state_for(save_step)):
            logger.warning(
                "checkpoint save at step %d was SKIPPED by the manager "
                "(directory already holds a step >= %d) — state was NOT "
                "written", save_step, save_step)
            return False
        return True

    def resolve_pending_eval():
        # Land an overlap-dispatched eval bracket. Called right after
        # the NEXT train step's dispatch (so the single metrics fetch
        # waits only out the eval's remaining device time while the
        # train step is already queued behind it), and at any point
        # that needs the eval stream current (a checkpoint boundary's
        # data_state, the end of the run). The fetch wait is eval
        # device time, not training time — discounted exactly like the
        # synchronous bracket; the host-side reduction it pays for
        # (pooled ranking stats) runs while the device crunches the
        # queued train step.
        nonlocal pending_eval, last_eval_loss, best_eval_loss, stalled_evals
        if pending_eval is None:
            return
        e_step, handle = pending_eval
        pending_eval = None
        t0 = time.perf_counter()
        em, _, _ = resolve_eval(handle)
        timer.discount(time.perf_counter() - t0)
        history.append({"step": e_step, **em})
        tele.emit("eval", step=e_step, metrics=em, overlapped=True)
        logger.info("step %d eval loss %.4f %s", e_step, em["eval_loss"],
                    _losses_said(em, "eval_"))
        if log_fn is not None:
            log_fn(e_step, em)
        last_eval_loss = np.float32(em["eval_loss"])
        # Best/stalled bookkeeping stays identical to the synchronous
        # bracket so the checkpointed eval_stream state is byte-equal
        # between the two modes (early stopping itself is never active
        # here — it is part of the overlap legality gate above).
        if em["eval_loss"] < best_eval_loss - cfg.train.early_stop_min_delta:
            best_eval_loss = em["eval_loss"]
            stalled_evals = 0
        else:
            stalled_evals += 1

    fault_stall = _fault_stall_spec()
    if fault_stall:
        logger.warning("FAULT INJECTION ACTIVE: %.1fs stall at step %d "
                       "(PBT_FAULT_STALL_AT)", fault_stall[1],
                       fault_stall[0])
    fault_eval_stall = _fault_eval_stall_secs()
    if fault_eval_stall:
        logger.warning("FAULT INJECTION ACTIVE: %.1fs stall per eval "
                       "bracket (PBT_FAULT_EVAL_STALL)", fault_eval_stall)

    with GracefulShutdown(
        on_signal=((lambda signum: tele.dump_flight(f"signal_{signum}"))
                   if tele.enabled else None)
    ) as stop:
      for step in range(start_step, cfg.train.max_steps):
        # One span an iteration: every instant of the loop lies inside a
        # `train.*` span, so an idle gap of the device takes one's name.
        with span("train.step", step=step + 1), \
                contextlib.ExitStack() as first_step:
            with span("train.data_wait") as waited:
                batch = next(batch_iterator)
            data_wait_s += waited.seconds
            if fault_stall and step + 1 == fault_stall[0]:
                # Injected host stall, deliberately NOT discounted from the
                # timing window — the drill asserts it shows up there.
                time.sleep(fault_stall[1])
            with span("train.put"):
                batch = put(batch)
            if step == start_step and not eval_keyed_plateau:
                # What the device trace's operations are joined to the
                # scopes by (obs/tracing.program_scopes), kept once.
                note_program(step_fn.__name__, step_fn, (state, batch, cfg))
            if step == start_step:
                # From the first call of the step to its first result
                # fetched (below), or to the error that ends the step:
                # the trace, lowering and compile or load nest inside.
                first_step.enter_context(startup_span("startup.first_step"))
            with span("train.dispatch"):
                if eval_keyed_plateau:
                    state, metrics = plateau_step(state, batch, last_eval_loss)
                else:
                    state, metrics = step_fn(state, batch, cfg)
            timer.update()
            # An overlap-dispatched eval bracket lands HERE — after this
            # step's dispatch, so its metrics fetch runs with the train
            # step already queued behind the eval on the device stream.
            resolve_pending_eval()
            if step - start_step + 1 == timer.warmup_steps:
                # Guaranteed drain at the warmup boundary: t0 was just
                # anchored at host ENQUEUE time, with the compile/warmup
                # backlog still executing remotely. sync()'s re-anchor
                # branch moves t0 past that backlog — without this, a run
                # with log_every=0 and no eval/checkpoint cadence charges
                # compile time to the timed window, deflating perf.
                drain_and_sync()

            if step == start_step:
                # One-time HBM report once the step (incl. compile-time
                # buffers) is resident — the first thing to look at when a
                # bigger batch OOMs. CPU backends report no stats; silent.
                # Dispatch is async, so force the step to completion first
                # via a scalar fetch.
                from proteinbert_tpu.utils.profiling import (
                    device_memory_report,
                )

                if (mesh is not None and mesh.size > 1
                        and not eval_keyed_plateau):
                    # While the device runs that first step: what the
                    # compiled step moves between the chips.
                    _log_collective_census(step_fn, state, batch, cfg, mesh)
                float(metrics["loss"])
                first_step.close()
                stats = next((s for s in device_memory_report().values()
                              if "bytes_in_use" in s), None)
                if stats:
                    logger.info(
                        "HBM after first step: %.2f GB in use (peak %.2f) "
                        "of %.2f GB",
                        stats["bytes_in_use"] / 1e9,
                        stats.get("peak_bytes_in_use", 0) / 1e9,
                        stats.get("bytes_limit", 0) / 1e9,
                    )
                    for k in ("bytes_in_use", "peak_bytes_in_use",
                              "bytes_limit"):
                        if k in stats:
                            tele.metrics.gauge(f"hbm_{k}").set(stats[k])

            if cfg.train.log_every and (step + 1) % cfg.train.log_every == 0:
                # ONE device_get for the whole metrics dict (per-key float()
                # paid ~10 device→host roundtrips per log point).
                with span("train.log_fetch"):
                    m = {k: float(v) for k, v in jax.device_get(
                        {k: v for k, v in metrics.items()
                         if not v.ndim}).items()}   # the scalars
                # That fetch drained the async dispatch queue through this
                # step — fold the wait into the timing window, else
                # summary() reports host enqueue rate.
                timer.sync()
                if cfg.train.on_nan != "off" and not check_finite(
                    m, step + 1, mode="quiet"
                ):
                    # Preserve the state BEFORE halting so the blow-up is
                    # debuggable (reference: no failure handling at all,
                    # SURVEY §5). Saved to a SIBLING directory, once: the
                    # NaN state must never become the checkpoint a restart
                    # resumes from, nor churn the retention window.
                    if checkpointer is not None and not diagnostic_saved:
                        diag = Checkpointer(
                            checkpointer.directory + "-diagnostic",
                            max_to_keep=1, async_save=False)
                        diag.save(step + 1, state,
                                  {**data_state_for(step + 1),
                                   "non_finite": True})
                        diag.close()
                        diagnostic_saved = True
                        logger.warning("non-finite state preserved in %s",
                                       checkpointer.directory + "-diagnostic")
                    tele.emit("nan_halt", step=step + 1, metrics=m,
                              mode=cfg.train.on_nan)
                    if cfg.train.on_nan == "halt":
                        # About to raise: a staged snapshot mid-fetch is the
                        # newest durable state a requeued run could resume
                        # from — flush it before dying (best-effort; the
                        # NaN stays the reported cause).
                        flush_inflight_checkpoint(checkpointer,
                                                  "non-finite halt")
                        tele.emit("run_end", step=step + 1, outcome="nan_halt",
                                  perf=timer.summary())
                        tele.dump_flight("nan_halt")
                    # Raises in halt mode; logs the warning in warn mode.
                    check_finite(m, step + 1, mode=cfg.train.on_nan)
                harvest_staged()  # completed overlap lands in this record
                m.update(timer.summary())
                if checkpointer is not None:
                    # Attribution flag, not a metric: 1.0 when a checkpoint
                    # save overlapped this log window — still writing now OR
                    # started since the last log point (the latch catches a
                    # save that started AND finished inside the window,
                    # which a point sample at the log instant would miss).
                    m["ckpt_in_flight"] = float(checkpointer.in_flight()
                                                or ckpt_since_log)
                    ckpt_since_log = False
                history.append({"step": step + 1, **m})
                if tele.enabled:
                    # All telemetry sits at log cadence — the per-step hot
                    # path stays untouched (overhead <1% of a log interval,
                    # ~0 of a step).
                    extra = {}
                    reg = tele.metrics
                    if prefetch_it is not None:
                        extra["data_wait_s"] = round(data_wait_s, 4)
                        reg.gauge("data_wait_seconds").set(data_wait_s)
                        reg.gauge("data_batches_total").set(
                            prefetch_it.batches)
                    try:
                        import resource
                        import sys as _sys

                        # ru_maxrss: kilobytes on Linux, BYTES on macOS.
                        rss = resource.getrusage(
                            resource.RUSAGE_SELF).ru_maxrss
                        rss *= 1 if _sys.platform == "darwin" else 1024
                        extra["host_max_rss_bytes"] = rss
                        reg.gauge("host_max_rss_bytes").set(rss)
                    except Exception:
                        pass  # non-POSIX host: RSS gauge just absent
                    tele.emit("step", step=step + 1, metrics=m, **extra)
                    reg.counter("steps_total").inc(cfg.train.log_every)
                    reg.set_many(m)  # loss/acc + StepTimer summary as gauges
                logger.info(
                    "step %d loss %.4f %s %s",
                    step + 1, m["loss"], _losses_said(m),
                    (f"{m['residues_per_sec_per_chip']:.0f} res/s/chip "
                     + (f"MFU {m['mfu']:.3f} " if "mfu" in m else "")
                     + f"on {n_chips}x {device_kind}"
                     # The since-last-log rate tells a live operator
                     # "currently slow" apart from "was slow once" — the
                     # cumulative MFU alone re-reports an old stall forever.
                     + (f" (window {m['window_mfu']:.3f})"
                        if "window_mfu" in m else ""))
                    if "residues_per_sec_per_chip" in m else "",
                )
                if log_fn is not None:
                    log_fn(step + 1, m)

            if stop.requested:
                # Preemption (SIGTERM) / operator interrupt: checkpoint at
                # the completed step and exit cleanly; resume picks up
                # exactly here.
                drain_and_sync()
                saved = False
                if checkpointer is not None:
                    # An in-flight staged snapshot must land BEFORE the
                    # exit-75 requeue — best-effort, so a stager failure
                    # cannot turn a clean preemption into a crash.
                    flush_inflight_checkpoint(
                        checkpointer, "preemption (SIGTERM/SIGINT)")
                    saved = checked_save(step + 1, state)
                    checkpointer.wait()
                logger.warning("preempted at step %d: %s, exiting", step + 1,
                               "state saved" if saved else "state NOT saved")
                tele.emit("requeue", step=step + 1,
                          reason=f"signal_{stop.signum}", saved=saved)
                # Second, fuller dump (the signal-time one fired mid-step):
                # now the flush/save outcome and the requeue record are in
                # the ring — the picture a post-mortem actually wants.
                tele.dump_flight(f"signal_{stop.signum}")
                preempted = True
                break

            if (
                eval_batches is not None
                and cfg.train.eval_every
                and (step + 1) % cfg.train.eval_every == 0
            ):
                # Drain BEFORE starting the eval bracket: otherwise the
                # eval's first device fetch waits out the enqueued train
                # steps and discount() below subtracts that real step time
                # from the window, inflating throughput/MFU. (The overlap
                # path needs the drain too — after it, the eval batches are
                # the ONLY queued device work, so the deferred resolve-time
                # fetch waits out eval compute alone and discounting it
                # cannot swallow real step time.)
                drain_and_sync()
                t_eval = time.perf_counter()
                if fault_eval_stall:
                    # Injected INSIDE the discounted bracket: the drill
                    # asserts this does NOT surface as a slow window.
                    time.sleep(fault_eval_stall)
                if overlap_eval:
                    # Overlapped bracket: dispatch every eval batch (host
                    # prep + enqueue — discounted) and defer the metrics
                    # fetch until after the next train step's dispatch; the
                    # eval_step dispatches capture the boundary state's
                    # buffers BEFORE the next (donating) train step reuses
                    # them, so the results are exact. History/log records
                    # and the eval-stream bookkeeping happen at resolve
                    # time — identical values, one step later in the
                    # stream. Keying stays by the 1-based boundary step, so
                    # `evaluate --like-step` reproduces it either way.
                    handle = dispatch_eval(
                        state, eval_batches(), put, cfg,
                        eval_base_key(cfg, step + 1), drain_every=0)
                    timer.discount(time.perf_counter() - t_eval)
                    pending_eval = (step + 1, handle)
                else:
                    # Key the eval by the 1-based step recorded in history,
                    # so `evaluate --like-step <history step>` reproduces it.
                    with span("eval_bracket", tele.spans, step=step + 1):
                        em = _evaluate(state, eval_batches(), put, cfg,
                                       step + 1)
                    timer.discount(time.perf_counter() - t_eval)
                    history.append({"step": step + 1, **em})
                    tele.emit("eval", step=step + 1, metrics=em)
                    logger.info("step %d eval loss %.4f %s", step + 1,
                                em["eval_loss"], _losses_said(em, "eval_"))
                    if log_fn is not None:
                        log_fn(step + 1, em)
                    last_eval_loss = np.float32(em["eval_loss"])
                    if (em["eval_loss"] < best_eval_loss
                            - cfg.train.early_stop_min_delta):
                        best_eval_loss = em["eval_loss"]
                        stalled_evals = 0
                    else:
                        stalled_evals += 1
                        if (cfg.train.early_stop_patience
                                and stalled_evals
                                >= cfg.train.early_stop_patience):
                            # The regime shift the r3 sustained run exposed:
                            # eval rising while train loss falls. Checkpoint
                            # the state and stop — continuing only overfits
                            # further.
                            drain_and_sync()
                            if checkpointer is not None:
                                checked_save(step + 1, state)
                                checkpointer.wait()
                            logger.warning(
                                "early stop at step %d: eval_loss has not "
                                "improved for %d consecutive evals "
                                "(best %.4f)",
                                step + 1, stalled_evals, best_eval_loss)
                            early_stopped = True
                            break

            if (
                checkpointer is not None
                and cfg.checkpoint.every_steps
                and (step + 1) % cfg.checkpoint.every_steps == 0
            ):
                if overlap_ckpt:
                    # Overlapped boundary: no drain, no stop-the-world.
                    # The on-device snapshot captures this step's state
                    # before the next (donating) train step can reuse its
                    # buffers; the stager thread runs the device→host fetch
                    # + orbax write behind the train steps the loop keeps
                    # dispatching. The eval stream must be current FIRST —
                    # a same-step overlapped eval is still pending and its
                    # values belong in this boundary's data_state (resume
                    # must restore them byte-identically).
                    resolve_pending_eval()
                    with span("ckpt_boundary_staged", tele.spans,
                              step=step + 1):
                        # backpressure: one stage in flight
                        flush_staged_overlap()
                        snap = ts.snapshot_train_state(state)
                        checkpointer.save_staged(step + 1, snap,
                                                 data_state_for(step + 1))
                    ckpt_since_log = True
                    # Deliberately NOT discounted: the snapshot dispatch +
                    # thread handoff are the boundary's only in-window cost
                    # (~ms). The hidden fetch+write seconds are credited to
                    # the overlap account when the stage lands
                    # (harvest/flush), so summary() reports them as
                    # overlapped rather than vanishing.
                else:
                    # Drain first (so the save's state reads don't swallow
                    # real step time), then discount the save itself — host
                    # serialization is not training time and must not
                    # deflate the window when a later sync() extends it.
                    drain_and_sync()
                    t_save = time.perf_counter()
                    with span("ckpt_boundary_sync", tele.spans,
                              step=step + 1):
                        checked_save(step + 1, state)
                    ckpt_since_log = True
                    timer.discount(time.perf_counter() - t_save)

    # An eval dispatched at the final step resolves here — before the
    # final save's data_state is built.
    resolve_pending_eval()
    if not preempted and not early_stopped:
        drain_and_sync()
        if checkpointer is not None:
            flush_staged_overlap()
            if checkpointer.latest_step() != cfg.train.max_steps:
                checked_save(cfg.train.max_steps, state)
            checkpointer.wait()

    perf = timer.summary()
    tele.emit("run_end", step=int(state.step),
              outcome=("preempted" if preempted
                       else "early_stopped" if early_stopped
                       else "completed"),
              perf=perf)
    return {"state": state, "history": history, "perf": perf,
            "preempted": preempted, "early_stopped": early_stopped}


def eval_base_key(cfg: PretrainConfig, step: int) -> jax.Array:
    """The corruption base key the periodic eval uses at `step` — public
    so the standalone `evaluate` CLI can reproduce a training run's
    eval_* history exactly (--like-step)."""
    return jax.random.fold_in(jax.random.PRNGKey(cfg.train.seed + 1), step)


def dispatch_eval(
    state, batches, put, cfg: PretrainConfig, base_key: jax.Array,
    max_batches: int = 0, drain_every: int = 8,
):
    """Dispatch eval_step over `batches` (each keyed by
    fold_in(base_key, batch_index) → reproducible) WITHOUT fetching the
    results; returns an opaque pending handle for resolve_eval.

    Per-batch metric scalars stay ON DEVICE; the accumulator fetches
    them in one device_get per drain (bounded memory + dispatch
    backpressure) instead of ~10 device→host roundtrips per batch.
    drain_every=0 defers EVERY fetch to
    resolve time — the overlapped eval bracket's mode, where the single
    resolve-time fetch happens after the next train step has already
    been dispatched, so the host never stands still inside the bracket.
    Row-weighting and the pooled-key rename fold in at drain time on
    host (float64 numerics)."""
    if max_batches:
        # Cap BEFORE pulling: the for-loop must not fetch (and discard)
        # one extra batch's worth of HDF5 reads + tokenization.
        import itertools

        batches = itertools.islice(batches, max_batches)
    pooled = ("global_auroc", "global_p_at_k")
    acc = DeviceMetricAccumulator(drain_every=drain_every)
    rename = lambda k: f"{k}_batch_mean" if k in pooled else k  # noqa: E731
    rank_stats = None
    n = 0
    rows = 0
    for batch in batches:
        b_rows = len(next(iter(batch.values())))
        m = dict(ts.eval_step(state, put(batch),
                              jax.random.fold_in(base_key, n), cfg))
        stats = m.pop("ranking_stats", None)    # the decoder has none
        if stats is not None:
            rank_stats = stats if rank_stats is None else jax.tree.map(
                lambda a, b: a + b, rank_stats, stats)
        acc.add(m, weight=b_rows, key_fn=rename)
        n += 1
        rows += b_rows
    return acc, rank_stats, n, rows


def resolve_eval(pending, prefix: str = "eval_"):
    """Fetch + reduce a dispatch_eval handle → (metrics, n, rows).

    Loss/accuracy metrics are the row-weighted mean of the per-batch
    values (weighting matters only when batch sizes differ — the
    standalone CLI's tail batch). The ranking metrics global_auroc /
    global_p_at_k are POOLED at the split level from each batch's
    mergeable sufficient statistics (loss.global_ranking_stats): a
    dataset micro-AUROC is a property of the joint score distribution,
    not a mean of per-batch AUROCs (VERDICT r2 Weak #5). The per-batch
    means of the exact in-batch values remain available, renamed
    *_batch_mean."""
    from proteinbert_tpu.train.loss import ranking_metrics_from_stats

    acc, rank_stats, n, rows = pending
    metrics = {f"{prefix}{k}": v / max(rows, 1)
               for k, v in acc.sums().items()}
    if rank_stats is not None:
        rank_stats = jax.device_get(rank_stats)
        metrics.update({f"{prefix}{k}": v for k, v in
                        ranking_metrics_from_stats(rank_stats).items()})
    return metrics, n, rows


def evaluate_batches(
    state, batches, put, cfg: PretrainConfig, base_key: jax.Array,
    prefix: str = "eval_", max_batches: int = 0,
):
    """Synchronous eval over `batches` → (metrics dict, n_batches,
    n_rows); dispatch_eval + resolve_eval in one call (the CLI
    `evaluate` path and the trainer's non-overlapped bracket)."""
    return resolve_eval(
        dispatch_eval(state, batches, put, cfg, base_key,
                      max_batches=max_batches),
        prefix)


def _evaluate(state, batches, put, cfg, step) -> Dict[str, float]:
    """Mean eval_step metrics over a held-out split; corruption key is
    derived from the step so evals are reproducible run-to-run."""
    metrics, _, _ = evaluate_batches(
        state, batches, put, cfg, eval_base_key(cfg, step))
    return metrics


def _log_collective_census(step_fn, state, batch, cfg, mesh) -> None:
    """One log line for a step pinned on a mesh: the collectives of its
    compiled module (obs/tracing.collective_census). The executable is
    the one the first call compiled, found again by jit's own caches, so
    this costs its text and no compile. On an `fsdp` mesh a sound step
    reads 0 collectives over activations and a gather for every use of a
    block's weights (docs/distributed.md)."""
    from proteinbert_tpu.obs.tracing import collective_census

    try:
        census = collective_census(
            step_fn.lower(state, batch, cfg).compile().as_text(),
            batch["tokens"].shape[0],
            [leaf.shape for leaf in jax.tree.leaves(state.params)])
    except Exception:   # the compiler's text is not an interface
        logger.debug("collective census failed", exc_info=True)
        return
    logger.info(
        "sharded step on mesh %s: %d collectives over activations with the "
        "global row count%s, %d parameter all-gathers, %.2f GB a step (%s)",
        {a: n for a, n in mesh.shape.items() if n > 1},
        census["activation"],
        f" (results {census['activation_shapes']})"
        if census["activation"] else "",
        census["parameter_gathers"], census["bytes"] / 1e9,
        ", ".join(f"{k} x {v['count']}"
                  for k, v in sorted(census["by_kind"].items())))


def _make_batch_put(mesh: Optional[jax.sharding.Mesh]):
    """Host numpy batch → device array(s), data-sharded under a mesh."""
    if mesh is None:
        return lambda batch: batch
    from proteinbert_tpu.parallel.sharding import batch_sharding

    shardings = None

    def put(batch):
        nonlocal shardings
        if shardings is None:
            shardings = batch_sharding(mesh)
        if jax.process_count() > 1:
            return {
                k: jax.make_array_from_process_local_data(shardings[k], v)
                for k, v in batch.items()
            }
        return jax.device_put(
            batch, {k: shardings[k] for k in batch} if isinstance(batch, dict)
            else shardings
        )

    return put
