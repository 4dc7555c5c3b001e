"""What the training drivers PR 28 added share: the readings of the three
checked steps, and the timed window, closed the way a job is preempted.

`drivers/pretrain.py` (not this PR's to edit) arms a plain timer; where
the trainer has not yet installed its handler when the timer fires (a
loaded host and a window of a second, as in the CPU rehearsals) the
signal's default action kills the run. Here the timer waits for the
handler first."""

from __future__ import annotations

import os
import signal
import threading
import time

import numpy as np

from benchmark.drivers.pretrain import _find_mu, _host_norms


def sigterm_after(seconds: float, inside: threading.Event) -> threading.Timer:
    """A timer (not yet started) that sends this process SIGTERM `seconds`
    after its start, if `inside` is still set, and not before the program
    under test handles the signal."""
    def preempt():
        while inside.is_set() and signal.getsignal(signal.SIGTERM) in (
                signal.SIG_DFL, None):
            time.sleep(0.02)
        if inside.is_set():
            os.kill(os.getpid(), signal.SIGTERM)

    return threading.Timer(seconds, preempt)


def first_gradient(state, b1: float):
    """The gradient Adam was handed at the first step, on the host: its
    first moment after one step is (1 - b1) x that gradient."""
    import jax

    return jax.tree.map(lambda x: np.asarray(x) / np.float32(1.0 - b1),
                        jax.device_get(_find_mu(state.opt_state)))


def program_readings(losses, first_grad, start, end) -> dict:
    """What `compare.training_checks` takes of the program's checked steps."""
    import jax

    return {
        "losses": [float(x) for x in losses],
        "first_grad": first_grad,
        "first_grad_norms": _host_norms(first_grad),
        # leaf by leaf: a float64 copy of a whole tree is 5.7 GB at 706.5 M
        "change_norms": jax.tree.map(
            lambda a, b: float(np.sqrt(np.sum(np.square(
                np.asarray(a, np.float64) - np.asarray(b, np.float64))))),
            end, start),
    }


def timed_pretrain(run, cfg, feed, state, mesh=None):
    """`trainer.pretrain` on the state, step and feed of the checked
    steps, inside the run's window, closed by SIGTERM. -> (the trainer's
    result, optimizer steps completed in the window)."""
    import jax

    from proteinbert_tpu.train.trainer import pretrain

    jax.block_until_ready(state)
    inside = threading.Event()
    timer = sigterm_after(run.seconds, inside)
    first_timed = int(state.step)
    with run.window():
        inside.set()
        timer.start()
        try:
            with jax.profiler.TraceAnnotation("trainer.pretrain"):
                out = pretrain(cfg, feed, state=state, mesh=mesh)
        finally:
            inside.clear()
            timer.cancel()
    steps = int(out["state"].step) - first_timed
    if not out["preempted"] or steps < 1:
        raise SystemExit("the trainer did not run to the window's end")
    return out, steps
