"""Dense pretraining through `train.trainer.pretrain`.

Set-up makes the train state on the device in one jitted call from the
seed, drives the program's compiled `train_step` through its first three
steps on the feed's first three batches (reading what the comparison
needs), and hands that same state, step and feed to
`trainer.pretrain`, which the window times from a device sync to the
trainer's own drained return. The window is closed the way a job is
preempted: SIGTERM, which the trainer answers by finishing the step in
flight, draining the device and returning.
"""

from __future__ import annotations

import os
import signal
import threading
import time

import numpy as np

from benchmark import compare, flops, traffic
from benchmark.program import model_sizes, program_config
from benchmark.reference import proteinbert_f32 as ref
from benchmark.device import memory_peak_bytes

CHECKED_STEPS = 3


class Feed:
    """The benchmark's iterator over the program's data pipeline: counts
    what it hands out and keeps the first batches for the reference."""

    def __init__(self, source):
        self._source = source
        self.kept = []
        self.batches = 0

    def __iter__(self):
        return self

    def __next__(self):
        import jax

        with jax.profiler.TraceAnnotation("benchmark.feed.next"):
            batch = next(self._source)
        if len(self.kept) < CHECKED_STEPS:
            self.kept.append(batch)
        self.batches += 1
        return batch


def _find_mu(opt_state):
    """Adam's first moment inside an optax chain's state."""
    import jax

    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise SystemExit("expected one Adam state in the optimizer's state")
    return found[0].mu


def _host_norms(tree):
    import jax

    return jax.tree.map(
        lambda x: float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64))))),
        tree)


def make_feed(run, m):
    """The cell's feed from the seed, and the real residues of a step.
    Every block of `rows` sequences holds the same lengths, so every step
    trains the same number of real residues whatever the seed."""
    from proteinbert_tpu.data.dataset import (
        InMemoryPretrainingDataset, make_pretrain_iterator,
    )

    wl, mix = run.workload, run.mix
    seqs, _ = traffic.sequences(mix, wl["dataset_blocks"], run.seed)
    ann = traffic.annotation_rows(mix, len(seqs), m["num_annotations"], run.seed)
    dataset = InMemoryPretrainingDataset(seqs, ann, wl["seq_len"])
    feed = Feed(make_pretrain_iterator(dataset, wl["rows"], seed=0, shuffle=False))
    return feed, int(traffic.block_lengths(mix).sum())


def cell_config(workload: dict, config: dict):
    """The program's config object as this cell runs it."""
    return program_config(config, {
        "data.batch_size": workload["rows"], "data.seq_len": workload["seq_len"],
        "train.max_steps": 2 ** 31 - 1, **workload.get("overrides", {})})


def product_operands(config):
    """What the configuration states for the operands of its products."""
    return "bf16" if config["dtype"] == "bfloat16" else "f32"


def optimizer_sizes(config):
    return {k: config["optimizer"][k] for k in (
        "learning_rate", "warmup_steps", "grad_clip_norm", "b1", "b2")}


def run(run, devices):
    import jax

    from proteinbert_tpu.train import train_state as ts
    from proteinbert_tpu.train.trainer import pretrain

    wl, mix = run.workload, run.mix
    rows, seq_len = wl["rows"], wl["seq_len"]
    if mix["block"] != rows or mix["lengths"]["max"] > seq_len - 2:
        raise SystemExit("the mix's block and longest sequence have to fit the cell")
    cfg = cell_config(wl, run.config)
    m = model_sizes(run.config)

    feed, residues_per_step = make_feed(run, m)

    make_state = jax.jit(ts.create_train_state, static_argnames="cfg")
    state = make_state(ref.seed_key(run.seed), cfg)
    start = jax.device_get(state.params)
    losses, first_grad = [], None
    for _ in range(CHECKED_STEPS):
        state, metrics = ts.train_step(state, next(feed), cfg)
        losses.append(metrics["loss"])
        if first_grad is None:
            # Adam's first moment after one step is (1 - b1) x the
            # gradient it was handed. Kept on the host, off the chip.
            first_grad = jax.tree.map(
                lambda x: np.asarray(x) / np.float32(1.0 - cfg.optimizer.b1),
                jax.device_get(_find_mu(state.opt_state)))
    end = jax.device_get(state.params)
    program = {
        "losses": [float(x) for x in losses],
        "first_grad": first_grad,
        "first_grad_norms": _host_norms(first_grad),
        "change_norms": _host_norms(jax.tree.map(
            lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
            end, start)),
    }
    del start, end
    jax.block_until_ready(state)

    inside = threading.Event()

    def preempt():
        if inside.is_set():
            os.kill(os.getpid(), signal.SIGTERM)

    timer = threading.Timer(run.seconds, preempt)
    first_timed = int(state.step)
    with run.window():
        inside.set()
        timer.start()
        try:
            with jax.profiler.TraceAnnotation("trainer.pretrain"):
                out = pretrain(cfg, feed, state=state)
        finally:
            inside.clear()
            timer.cancel()
    steps = int(out["state"].step) - first_timed
    if not out["preempted"] or steps < 1:
        raise SystemExit("the trainer did not run to the window's end")
    final_loss = float(out["history"][-1]["loss"]) if out["history"] else 0.0
    memory_peak = memory_peak_bytes(devices)
    del out, state

    t_ref = time.perf_counter()
    reference = ref.follow_steps(
        run.seed, feed.kept, m, run.config["corruption"],
        optimizer_sizes(run.config), rows=wl["reference_rows"],
        operands=product_operands(run.config))
    print(f"reference: {CHECKED_STEPS} steps in "
          f"{time.perf_counter() - t_ref:.1f} s")
    gaps = compare.training_checks(program, reference)
    # A number the cell names under `not_compared` is one its readings
    # could set no limit for (PERF.md section 2); it is printed all the same.
    checks = [(name, gaps[name], wl["limits"][name]) for name in sorted(gaps)
              if name not in wl.get("not_compared", ())]
    for name in wl.get("not_compared", ()):
        print(f"not compared {name}: {gaps[name]:.6g}")
    spread = compare.leaf_dir_spread(program["first_grad"], reference["first_grad"])
    print("first gradient, gap by leaf: median {:.6g}, 75 % {:.6g}, 90 % {:.6g}, "
          "widest {:.6g}".format(*spread))

    return {
        "e2e": {"train_residues_per_s": steps * residues_per_step / run.window_s},
        "attempted": steps,
        "failed": 0 if np.isfinite(final_loss) else steps,
        "checks": checks,
        "memory_peak_bytes": int(memory_peak),
        "obs": {
            "steps": steps, "program": "train_step",
            "grad_dir_by_leaf": spread,
            "call_flops": flops.train_flops(m, rows, seq_len),
            "call_min_bytes": flops.train_min_bytes(m, rows, seq_len),
        },
    }


def cell_program(workload: dict, config: dict):
    """(jitted function, abstract arguments, static keyword arguments) of
    the program the window times, for `benchmark.rehearse`."""
    import jax
    import jax.numpy as jnp

    from proteinbert_tpu.train import train_state as ts

    rows, seq_len = workload["rows"], workload["seq_len"]
    cfg = cell_config(workload, config)
    state = jax.eval_shape(
        lambda k: ts.create_train_state(k, cfg), jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((rows, seq_len), jnp.int32),
             "annotations": jax.ShapeDtypeStruct(
                 (rows, config["num_annotations"]), jnp.float32)}
    return ts.train_step, (state, batch), {"cfg": cfg}
