"""Dense pretraining on a mesh of chips through `trainer.pretrain(mesh=)`.

As `drivers/pretrain.py`, for a state that is SHARDED: the cell's
overrides name the mesh (`mesh.fsdp=4`), the state is made on the mesh
under `parallel/sharding.state_sharding`, set-up drives `train_step`
pinned to that layout as the trainer pins it (`pin_state_sharding`: the
trainer's own wrapper of the same step and layout then finds the
executable compiled, `compiles_in_window.train` 0) through the first
three steps, and the window times
`trainer.pretrain(cfg, feed, state=state, mesh=mesh)`.

`train_residues_per_s`, `call_flops` and `call_min_bytes` are PER CHIP
(the global batch and state over the chips of the mesh). The reference
follows the same three steps over the GLOBAL batch, its rows laid over
the same chips so that it takes a quarter of the time one chip would.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import compare, flops
from benchmark.device import memory_peak_bytes
from benchmark.drivers.preempt import (
    first_gradient, program_readings, timed_pretrain,
)
from benchmark.drivers.pretrain import (
    CHECKED_STEPS, cell_config, make_feed, optimizer_sizes, product_operands,
)
from benchmark.program import model_sizes
from benchmark.reference import proteinbert_f32 as ref


def run(run, devices):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from proteinbert_tpu.parallel.sharding import (
        batch_sharding, pin_state_sharding, state_sharding,
    )
    from proteinbert_tpu.train import train_state as ts

    wl, mix = run.workload, run.mix
    rows, seq_len = wl["rows"], wl["seq_len"]
    if mix["block"] != rows or mix["lengths"]["max"] > seq_len - 2:
        raise SystemExit("the mix's block and longest sequence have to fit the cell")
    cfg = cell_config(wl, run.config)
    chips = cfg.mesh.num_devices
    if chips != len(devices) or rows % chips:
        raise SystemExit(f"the cell's mesh wants {chips} chips and rows that "
                         f"divide by them, the run has {len(devices)}")
    mesh = Mesh(np.array(devices).reshape(cfg.mesh.shape), cfg.mesh.axis_names)
    m = model_sizes(run.config)
    feed, residues_per_step = make_feed(run, m)

    key = ref.seed_key(run.seed)
    layout = state_sharding(
        mesh, jax.eval_shape(lambda k: ts.create_train_state(k, cfg), key))
    state = jax.jit(ts.create_train_state, static_argnames="cfg",
                    out_shardings=layout)(key, cfg)
    step = pin_state_sharding(ts.train_step, state, static_argnums=2)
    where = batch_sharding(mesh)
    start = jax.device_get(state.params)
    losses, first_grad = [], None
    for _ in range(CHECKED_STEPS):
        batch = next(feed)
        state, metrics = step(
            state, jax.device_put(batch, {k: where[k] for k in batch}), cfg)
        losses.append(metrics["loss"])
        if first_grad is None:
            first_grad = first_gradient(state, cfg.optimizer.b1)
    end = jax.device_get(state.params)
    program = program_readings(losses, first_grad, start, end)
    del start, end
    out, steps = timed_pretrain(run, cfg, feed, state, mesh=mesh)
    final_loss = float(out["history"][-1]["loss"]) if out["history"] else 0.0
    memory_peak = memory_peak_bytes(devices)
    del out, state

    # The reference's rows over the same chips: its arithmetic is one
    # jitted call a block of rows, which the partitioner splits by row.
    by_row = NamedSharding(mesh, P(("data", "fsdp")))
    kept = [jax.device_put(b, by_row) for b in feed.kept]
    t_ref = time.perf_counter()
    reference = ref.follow_steps(
        run.seed, kept, m, run.config["corruption"],
        optimizer_sizes(run.config), rows=wl["reference_rows"],
        operands=product_operands(run.config))
    print(f"reference: {CHECKED_STEPS} steps in "
          f"{time.perf_counter() - t_ref:.1f} s")
    gaps = compare.training_checks(program, reference)
    checks = [(name, gaps[name], wl["limits"][name]) for name in sorted(gaps)
              if name not in wl.get("not_compared", ())]
    for name in wl.get("not_compared", ()):
        print(f"not compared {name}: {gaps[name]:.6g}")
    spread = compare.leaf_dir_spread(program["first_grad"], reference["first_grad"])
    print("first gradient, gap by leaf: median {:.6g}, 75 % {:.6g}, 90 % {:.6g}, "
          "widest {:.6g}".format(*spread))

    return {
        "e2e": {"train_residues_per_s":
                steps * residues_per_step / run.window_s / chips},
        "attempted": steps,
        "failed": 0 if np.isfinite(final_loss) else steps,
        "checks": checks,
        "memory_peak_bytes": int(memory_peak),
        "obs": {
            "steps": steps, "program": "train_step",
            "grad_dir_by_leaf": spread,
            "call_flops": flops.train_flops(m, rows, seq_len) / chips,
            "call_min_bytes": flops.train_min_bytes(m, rows, seq_len) / chips,
        },
    }


def cell_program(workload: dict, config: dict):
    """For `benchmark.rehearse`, which compiles for ONE described chip:
    one chip's share of the rows on a state that is whole, which bounds
    the sharded step's memory from above in its parameters and equals it
    in its activations. The mesh's own size is read on the chips."""
    from benchmark.drivers import pretrain

    one = {k: v for k, v in workload.get("overrides", {}).items()
           if not k.startswith("mesh.")}
    chips = int(np.prod([v for k, v in workload.get("overrides", {}).items()
                         if k.startswith("mesh.")] or [1]))
    return pretrain.cell_program(
        dict(workload, rows=workload["rows"] // chips, overrides=one), config)
