"""Orbax-based sharded async checkpointing (reference utils.py:324-343, redone).

The reference `torch.save`s a dict of state_dicts every 1000 iterations
and a final pickled nn.Module (reference utils.py:326-343), losing RNG
state and — because of the head-registration bug — the attention weights
(SURVEY §5). Here the WHOLE TrainState pytree (params, opt_state, PRNG
key, step) plus the data-iterator position is saved through orbax:
sharded (each host writes its own shards), optionally async (save
overlaps the next train steps), with automatic retention of the last
`max_to_keep` checkpoints.

On top of orbax's async write, `save_staged` overlaps the part orbax
keeps synchronous — the device→host state fetch: the trainer snapshots
the state on device (train_state.snapshot_train_state), hands the copy
here, and a stager thread fetches + saves it while the train stream
keeps dispatching. One stage in flight (backpressure via flush);
worker errors re-raise at the next flush/poll/wait; orbax's silent
skip-at-old-step stays loudly surfaced.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Optional

import jax
import orbax.checkpoint as ocp

logger = logging.getLogger(__name__)


class Checkpointer:
    """Thin CheckpointManager wrapper bound to one run directory."""

    def __init__(self, directory: str, max_to_keep: int = 3, async_save: bool = True):
        self.directory = os.path.abspath(directory)
        # Optional telemetry hook: callable(phase, step, **info), phase
        # in obs.events.CKPT_PHASES ("dispatch" at save_staged,
        # "landed" when a stage joins with its overlap_s, "save" for a
        # direct synchronous save). The trainer points this at
        # Telemetry.emit("ckpt_stage", ...); errors in the hook are
        # logged, never allowed to fail a save.
        self.on_event = None
        # Optional restore-side hook: callable(**fields), pointed at
        # Telemetry.emit("note", ...) — reports a torn-final-checkpoint
        # fallback (restore() docstring); errors logged, never raised.
        self.on_note = None
        # Staged (overlapped) save slot: at most ONE in flight — the
        # double-buffer is {the device-side snapshot} + {the host copy
        # the stager fetches into}; a second boundary arriving while a
        # stage is in flight back-pressures through flush_staged().
        self._staged: Optional[tuple] = None  # (future, holder dict)
        # ONE dedicated saver thread for every manager.save call, staged
        # or direct: orbax's CheckpointManager requires all saves to
        # originate from the SAME thread — its wait-for-previous-
        # finalize bookkeeping only resets `_finalize_thread` when the
        # waiter IS the thread that requested the previous save, so a
        # save from any other thread trips `assert _finalize_thread is
        # None` whenever an async finalize is still alive.
        self._saver = ThreadPoolExecutor(max_workers=1,
                                         thread_name_prefix="ckpt-saver")
        self._saver_thread: Optional[threading.Thread] = None
        self._mngr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                enable_async_checkpointing=async_save,
            ),
            # Registering the per-item handlers up front lets
            # item_metadata() (used by restore to detect the optional
            # 'data' item) resolve without orbax's "could not be
            # restored" warning on every CLI restore.
            item_handlers={
                "state": ocp.StandardCheckpointHandler(),
                "data": ocp.JsonCheckpointHandler(),
            },
        )

    def _on_saver(self, fn):
        """Run `fn` on the dedicated saver thread (directly when already
        on it — the staged work function calls save() from there) and
        return its result; exceptions propagate to the caller."""
        if threading.current_thread() is self._saver_thread:
            return fn()

        def run():
            self._saver_thread = threading.current_thread()
            return fn()

        return self._saver.submit(run).result()

    def _notify(self, phase: str, step: int, **info) -> None:
        cb = self.on_event
        if cb is None:
            return
        try:
            cb(phase, step, **info)
        except Exception:
            logger.exception("checkpoint on_event hook failed (phase=%s "
                             "step=%d) — save path unaffected", phase, step)

    def save(self, step: int, state: Any, data_state: Optional[Dict] = None,
             _from_stage: bool = False) -> bool:
        """Returns orbax's outcome: False means the manager SILENTLY
        skipped (it does so for any step <= latest_step, not only
        exact duplicates) — callers that need the save to have
        happened (warm start, preemption) must check, not assume.
        Blocks the caller for the synchronous part of the save (the
        write itself is async when async_save); the manager call runs
        on the saver thread (see __init__)."""
        args = {"state": ocp.args.StandardSave(state)}
        if data_state is not None:
            args["data"] = ocp.args.JsonSave(data_state)
        composite = ocp.args.Composite(**args)
        saved = bool(self._on_saver(
            lambda: self._mngr.save(step, args=composite)))
        if not _from_stage:
            # Staged saves report through "dispatch"/"landed" instead
            # (this synchronous-save event from the stager worker would
            # double-count the boundary).
            self._notify("save", step, saved=saved)
        return saved

    # ---------------------------------------------- overlapped (staged) saves

    def _stage_fetch(self, snapshot: Any) -> Any:
        """Device→host fetch of an (already device-copied) snapshot; runs
        on the stager thread. A method so tests can interpose latency."""
        return jax.device_get(snapshot)

    def save_staged(self, step: int, snapshot: Any,
                    data_state: Optional[Dict] = None) -> None:
        """Hand a DEVICE-SIDE snapshot (train_state.snapshot_train_state)
        to a background fetch+save and return immediately — the caller's
        train stream keeps dispatching while the device→host transfer
        and the orbax write run on the stager thread (the transfer has
        no data dependency on later train steps, so it costs ~zero wall
        time instead of the 19–47 s stop-the-world of a synchronous
        boundary).

        Backpressure rule: one stage in flight. If a previous stage has
        not landed when the next boundary arrives, this call BLOCKS in
        flush_staged() first — that wait is real stall and the trainer
        deliberately leaves it inside the timed window.

        Error/skip semantics: a stager exception is re-raised at the
        next flush_staged()/poll_staged()/wait() (never swallowed); an
        orbax silent skip (step <= latest) is surfaced with the same
        loud warning the synchronous path logs."""
        self.flush_staged()
        holder: Dict[str, Any] = {"step": step}

        def work():
            self._saver_thread = threading.current_thread()
            t0 = time.perf_counter()
            try:
                host_state = self._stage_fetch(snapshot)
                holder["saved"] = self.save(step, host_state, data_state,
                                            _from_stage=True)
            finally:
                holder["overlap_s"] = time.perf_counter() - t0

        self._notify("dispatch", step)
        self._staged = (self._saver.submit(work), holder)

    def flush_staged(self) -> Optional[Dict[str, Any]]:
        """Join the in-flight staged save (no-op when none). Re-raises a
        stager exception; logs the loud SKIPPED warning when orbax
        silently refused the step. Returns the stage's stats
        ({step, saved, overlap_s}) or None."""
        if self._staged is None:
            return None
        fut, holder = self._staged
        self._staged = None
        fut.result()  # joins; re-raises a stager exception
        if not holder.get("saved"):
            logger.warning(
                "staged checkpoint save at step %d was SKIPPED by the "
                "manager (directory already holds a step >= %d) — state "
                "was NOT written", holder["step"], holder["step"])
        self._notify("landed", holder["step"],
                     saved=bool(holder.get("saved")),
                     overlap_s=round(holder.get("overlap_s", 0.0), 6))
        return holder

    def poll_staged(self) -> Optional[Dict[str, Any]]:
        """Non-blocking flush: stats if the in-flight stage has finished
        (errors/skips surfaced exactly as flush_staged), else None."""
        if self._staged is None or not self._staged[0].done():
            return None
        return self.flush_staged()

    def staged_in_flight(self) -> bool:
        return self._staged is not None and not self._staged[0].done()

    def all_steps(self):
        return list(self._mngr.all_steps())

    def restore(self, state_like: Any, step: Optional[int] = None,
                fallback: bool = True):
        """Restore (state, data_state) at `step` (default: latest).

        `state_like` is a concrete or abstract TrainState pytree used as
        the restore target — its shardings tell orbax where each shard
        goes (single-host, multi-host, or an entirely DIFFERENT mesh
        layout than the one that wrote the checkpoint: orbax reshards
        from disk against the template's shardings, which is the restore
        half of mesh-agnostic resharding, parallel/reshard.py).

        Torn-tail tolerance (`fallback=True`, default, applies only when
        `step` is None): when the NEWEST checkpoint is torn or missing —
        a crash mid-write of the final step, the read-side mirror of the
        write-side torn-snapshot guarantees — restore falls back to the
        previous retained step instead of raising, reporting the skip
        through `on_note` (wired to a `note` telemetry event by the
        trainer/CLI). Exactly ONE step is ever skipped: a crash can
        tear at most the in-flight write, so a failure at the fallback
        step too is a REAL error (wrong restore template, corrupted
        store) and raises as itself instead of being smeared into more
        "torn checkpoint" notes. An explicitly requested `step` stays
        strict, and a single-step directory re-raises the original
        error.
        """
        explicit = step is not None
        steps = ([step] if explicit
                 else sorted(self.all_steps(), reverse=True))
        if not steps:
            return None, None
        abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, state_like)
        for i, s in enumerate(steps):
            try:
                args = {"state": ocp.args.StandardRestore(abstract)}
                # 'data' is optional at save time; requesting an absent
                # item raises.
                if "data" in (self._mngr.item_metadata(s) or {}):
                    args["data"] = ocp.args.JsonRestore()
                restored = self._mngr.restore(
                    s, args=ocp.args.Composite(**args))
                return restored["state"], restored.get("data")
            except (FileNotFoundError, ValueError, KeyError,
                    TypeError) as exc:
                # The types orbax surfaces a torn step dir as, depending
                # on which file is missing — and ONLY those: a transient
                # failure restoring an intact step (device OOM, a flaky
                # filesystem read) must raise, not silently roll the run
                # back a checkpoint interval.
                if explicit or not fallback or i > 0 or len(steps) == 1:
                    raise
                logger.warning(
                    "checkpoint at step %d in %s is unreadable (%s: %s) "
                    "— falling back to the previous retained step %d",
                    s, self.directory, type(exc).__name__, exc,
                    steps[i + 1])
                self._note_restore_fallback(s, steps[i + 1], exc)
        raise AssertionError("unreachable: the loop returns or raises")

    def _note_restore_fallback(self, bad_step: int, landed_step: int,
                               exc: Exception) -> None:
        """Report one skipped-torn-step event through `on_note`
        (callable(**fields) — the trainer/CLI points it at
        Telemetry.emit('note', ...)); never allowed to fail a restore.
        The payload carries BOTH the skipped step (`bad_step`) and the
        step the restore falls back to (`landed_step`) so an operator
        reading the stream knows exactly how much history the run lost
        without cross-referencing the directory listing."""
        cb = getattr(self, "on_note", None)
        if cb is None:
            return
        try:
            cb(source="checkpoint", kind="restore_fallback",
               bad_step=int(bad_step), landed_step=int(landed_step),
               error=f"{type(exc).__name__}: {exc}")
        except Exception:
            logger.exception("checkpoint on_note hook failed — restore "
                             "path unaffected")

    def latest_step(self) -> Optional[int]:
        return self._mngr.latest_step()

    def in_flight(self) -> bool:
        """True while an async OR staged save is still writing. The
        trainer ORs this with a started-since-last-log latch and stamps
        the result into each logged metrics record (`ckpt_in_flight`) so
        a slow window in the stream can be attributed to (or cleared of)
        checkpoint I/O contending for host bandwidth — the
        leading suspect for the r3 sustained run's collapse. Under the
        overlapped boundary this latch marks a REAL overlap window (the
        staged fetch+write running behind training), not contention.
        (The latch matters: a point sample alone would miss a save that
        started and finished between two log points.)"""
        return bool(self.staged_in_flight()
                    or self._mngr.is_saving_in_progress())

    def wait(self) -> None:
        """Block until pending staged AND async saves land (call before
        process exit); staged-worker errors propagate from here."""
        self.flush_staged()
        self._mngr.wait_until_finished()

    def close(self) -> None:
        try:
            self.flush_staged()
        finally:
            self._saver.shutdown(wait=True)
            self._mngr.close()
