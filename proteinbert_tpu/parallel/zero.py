"""ZeRO-1 cross-replica sharded weight update (arXiv:2004.13336).

On a mesh with a pure `data` axis, the default train step replicates
fp32 params AND the Adam mu/nu moments on every replica and pays a full
gradient all-reduce per step — the optimizer math is executed N times on
identical inputs, and 2x params of fp32 Adam state sits in every chip's
HBM. ZeRO-1 (Xu et al., *Automatic Cross-Replica Sharding of Weight
Update in Data-Parallel Training*) removes the redundancy:

  reduce-scatter grads over ('data','fsdp')   [≈ the all-reduce's first
                                               half — same wire bytes]
  apply the optimizer to a 1/(data*fsdp) shard [mu/nu persist SHARDED —
                                               the HBM win]
  all-gather the updated params                [≈ the all-reduce's
                                               second half]

Implementation: the forward/backward stays under the implicit-SPMD jit
exactly as before (so fsdp/model/seq sharding, remat, scan, and the
Pallas seq-parallel path are untouched); only the weight update runs
inside a `shard_map` over the mesh whose in/out specs carry the joint
('data','fsdp') axis per leaf (sharding.zero_update_spec — the same
rule that lays out the persistent mu/nu, so every tree entering the
body is sliced identically and the update math is elementwise-aligned).
At the shard_map boundary the partitioner turns the pending gradient
reduction into a reduce-scatter (each device only ever needs its slice
of the summed gradient) and the exit constraint back to the params'
storage sharding compiles to the all-gather. Gradient clipping needs
the TRUE global norm, which a shard cannot measure locally — the step
computes it once outside (it already does, for the grad_norm metric)
and passes it in; the plateau/warmup schedules and `needs_loss_value`
semantics ride through unchanged because the body calls the SAME shared
optimizer-apply (train_state.gradient_update) on shards.

`parallel.grad_reduce_dtype` in {"bf16", "int8"} routes
`make_zero_train_step` to the QUANTIZED reduce-scatter
(parallel/quant.py): the forward/backward runs inside an explicit
data-parallel shard_map producing per-replica partial gradients, and
the reduction consumes quantized payloads — bf16 (stochastic
rounding) or int8 (per-chunk scale + stochastic rounding) — so the
wire really moves 2x/4x fewer bytes (verified from compiled HLO by
`collective_wire_bytes_from_hlo`, tests/test_zero.py). The
`zero_gradient_update` function below keeps the PR-2 cast-only bf16
behavior for the explicit seq-parallel step, whose shard_map computes
grads itself: there the cast applies to already-reduced gradients and
changes numerics only, not wire bytes (documented limitation;
docs/distributed.md).

Checkpoint compatibility: leaf SHAPES never change (only shardings), so
orbax save/restore — including the PR-1 staged overlapped save — works
with a zero-aware restore template (state_sharding(zero_update=True)),
and checkpoints remain interchangeable with the replicated mode.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from proteinbert_tpu.configs import OptimizerConfig, PretrainConfig
from proteinbert_tpu.parallel.sharding import param_spec, zero_update_spec

ZERO_AXES = ("data", "fsdp")

_REDUCE_DTYPES = ("fp32", "bf16")


def zero_extent(mesh: Mesh) -> int:
    """Replicas the weight update is sharded across (data x fsdp)."""
    n = 1
    for ax in ZERO_AXES:
        n *= mesh.shape.get(ax, 1)
    return n


def _update_specs(mesh: Mesh, tree: Any) -> Any:
    """Per-leaf zero specs for a params-shaped or opt-state-shaped tree
    (scalars — Adam/schedule counts, plateau state — stay replicated)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: zero_update_spec(path, leaf, mesh), tree)


def zero_gradient_update(
    mesh: Mesh,
    opt_cfg: OptimizerConfig,
    params: Any,
    grads: Any,
    opt_state: Any,
    value: Any = None,
    *,
    grad_reduce_dtype: str = "fp32",
) -> Tuple[Any, Any, jax.Array]:
    """ZeRO-1 drop-in for train_state.gradient_update, callable from
    inside any jitted step; returns (params, opt_state, grad_norm).

    The returned params are re-constrained to their ordinary storage
    sharding (param_spec) — the partitioner compiles that exit
    constraint into the all-gather — so callers build the next
    TrainState exactly as in the replicated path and repeated calls see
    stable input shardings (no retrace, donation-safe).

    grad_reduce_dtype here supports "fp32"/"bf16" only, and the bf16
    cast is NUMERICS-ONLY (it applies to already-reduced gradients —
    this entry is what the explicit seq-parallel step calls, whose own
    shard_map produced the grads). The wire-compressing bf16/int8
    reduction lives in parallel/quant.make_quant_zero_train_step,
    which make_zero_train_step routes to."""
    import optax

    from proteinbert_tpu.train.schedule import make_optimizer, needs_loss_value
    from proteinbert_tpu.train.train_state import gradient_update

    if grad_reduce_dtype not in _REDUCE_DTYPES:
        raise ValueError(
            f"unknown grad_reduce_dtype {grad_reduce_dtype!r}; "
            f"expected one of {_REDUCE_DTYPES}")

    needs_value = needs_loss_value(opt_cfg)
    # The one value a shard cannot compute locally: the clip's global
    # norm. Measured here on the full (pre-rounding) gradients — the
    # same tensor the replicated chain's clip sees.
    grad_norm = optax.global_norm(grads)
    if grad_reduce_dtype == "bf16":
        grads = jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads)

    p_specs = _update_specs(mesh, params)
    o_specs = _update_specs(mesh, opt_state)
    # Pin the gradients' layout at production: without the constraint,
    # sharding propagation inside the backward scan is free to pick an
    # interim layout (observed: the stacked-blocks LEADING axis split
    # over every device) whose reshard to the update sharding is a full
    # rematerialization. Constrained here, the pending reduction lowers
    # straight onto the update layout — the reduce-scatter.
    grads = jax.lax.with_sharding_constraint(
        grads, jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                            is_leaf=lambda x: isinstance(x, P)))
    # A dummy replicated scalar keeps the shard_map signature stable
    # when the schedule needs no loss value.
    value_arr = jnp.asarray(
        0.0 if value is None else value, dtype=jnp.float32)

    def body(p, g, o, g_norm, val):
        # bf16-reduced gradients re-enter optimizer precision here, on
        # the 1/(data*fsdp) shard — AFTER the wire.
        g = jax.tree.map(lambda x, ref: x.astype(ref.dtype), g, p)
        tx = make_optimizer(opt_cfg, clip_norm_value=g_norm)
        return gradient_update(tx, p, g, o, val, needs_value)

    new_params, new_opt_state = shard_map(
        body,
        mesh=mesh,
        in_specs=(p_specs, p_specs, o_specs, P(), P()),
        out_specs=(p_specs, o_specs),
        # The body mixes sharded (mu/nu/param shards) and replicated
        # (counts, plateau scalars) values; the rep/vma checker cannot
        # type the replicated outputs without psum evidence, so it is
        # off — parity with the replicated step is asserted by
        # tests/test_zero.py instead.
        check_vma=False,
    )(params, grads, opt_state, grad_norm, value_arr)

    # Exit all-gather: updated params return to their storage layout.
    store = jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, param_spec(path, leaf, mesh)),
        new_params)
    new_params = jax.lax.with_sharding_constraint(new_params, store)
    return new_params, new_opt_state, grad_norm


@lru_cache(maxsize=8)
def make_zero_train_step(mesh: Mesh, cfg: PretrainConfig):
    """Jitted pretraining step whose weight update is ZeRO-1-sharded —
    drop-in for train_state.train_step when cfg.parallel.zero_update
    (the trainer selects it). The front half (corruption, forward,
    loss, backward) and the plateau_value contract are SHARED code with
    the default step (train_state.corrupt_forward_grads /
    plateau_observation), not a copy — only the update differs.

    grad_reduce_dtype "bf16"/"int8" routes to the QUANTIZED
    reduce-scatter step (parallel/quant.py) — real wire-byte
    compression, same signature and plateau contract."""
    if cfg.parallel.grad_reduce_dtype != "fp32":
        from proteinbert_tpu.parallel.quant import (
            make_quant_zero_train_step,
        )

        return make_quant_zero_train_step(mesh, cfg)
    from proteinbert_tpu.train import train_state as ts
    from proteinbert_tpu.train.schedule import effective_lr

    def step(state: ts.TrainState, batch: Dict[str, jax.Array],
             plateau_value: Optional[jax.Array] = None):
        key, grads, metrics = ts.corrupt_forward_grads(state, batch, cfg)
        value = ts.plateau_observation(cfg.optimizer, metrics, plateau_value)
        params, opt_state, grad_norm = zero_gradient_update(
            mesh, cfg.optimizer, state.params, grads, state.opt_state,
            value, grad_reduce_dtype=cfg.parallel.grad_reduce_dtype,
        )

        metrics = dict(metrics)
        metrics["grad_norm"] = grad_norm
        metrics["lr"] = effective_lr(cfg.optimizer, opt_state, state.step)
        new_state = ts.TrainState(
            step=state.step + 1, params=params, opt_state=opt_state, key=key
        )
        return new_state, metrics

    from proteinbert_tpu.train.train_state import DONATE_STATE

    return jax.jit(step, donate_argnums=DONATE_STATE)


# ------------------------------------------------------- comm accounting

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "all-to-all", "collective-permute")


def _iter_collectives(hlo_text: str):
    """Yield (kind, output_bytes, line) for every collective op of one
    compiled per-device HLO module (shared by the output-bytes and
    wire-bytes counters below). `*-start/done` async pairs are counted
    once, at the start op, keeping only the results half of its
    (operands..., results...) tuple."""
    import re

    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    op_re = re.compile(
        r"=\s*(.+?)\s+(" + "|".join(_COLLECTIVES) + r")(-start)?\(")
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = op_re.search(line)
        if m is None:
            continue
        shapes = [(dt, dims) for dt, dims in shape_re.findall(m.group(1))
                  if dt in _DTYPE_BYTES]
        if m.group(3) and len(shapes) >= 2 and len(shapes) % 2 == 0:
            # Async `*-start` ops return an (operands..., results...)
            # tuple — the leading half aliases the inputs; counting it
            # would double every async collective. Keep the results.
            shapes = shapes[len(shapes) // 2:]
        nbytes = 0
        for dt, dims in shapes:
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        yield m.group(2), nbytes, line


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Per-collective output bytes of one compiled (per-device) HLO
    module — the evidence behind the comm claims (tests/test_zero.py
    reads it per reduction mode); under SPMD the module is the per-chip
    program, so shapes are per-chip shapes. The 'total' key sums every
    kind."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for kind, nbytes, _ in _iter_collectives(hlo_text):
        out[kind] += nbytes
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def _group_size(line: str, default: int) -> int:
    """Participant count of one collective op, parsed from its
    replica_groups attribute — `{{0,1,...},...}` (explicit) or
    `[G,N]<=[...]` (iota [num_groups, group_size])."""
    import re

    m = re.search(r"replica_groups=\{\{([\d,\s]*)\}", line)
    if m:
        ids = [x for x in m.group(1).split(",") if x.strip()]
        return max(len(ids), 1)
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return max(int(m.group(2)), 1)
    return default


def collective_wire_bytes_from_hlo(hlo_text: str,
                                   default_group: int = 1,
                                   ) -> Dict[str, int]:
    """Estimated per-device WIRE bytes of one compiled module's
    collectives, from the HLO itself (output shapes + replica_groups;
    never inferred from unreduced source dtypes). The output-bytes
    counter above under-represents a reduce-scatter (its per-device
    output is 1/n of what crossed the wire) and over-represents an
    all-to-all (its output already spans every peer), so quantized-vs-
    fp32 comparisons need the ring-algorithm per-device conversion:

      all-reduce      2(n-1)/n x out   (reduce-scatter + all-gather)
      reduce-scatter  (n-1)   x out    (receives n-1 foreign shards)
      all-gather      (n-1)/n x out
      all-to-all      (n-1)/n x out    (1/n of the output is local)
      collective-permute       out

    `default_group` (pass the mesh's device count) covers ops whose
    replica_groups the backend elided."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for kind, nbytes, line in _iter_collectives(hlo_text):
        n = _group_size(line, default_group)
        if kind == "all-reduce":
            wire = 2 * nbytes * (n - 1) // max(n, 1)
        elif kind == "reduce-scatter":
            wire = nbytes * (n - 1)
        elif kind in ("all-gather", "all-to-all"):
            wire = nbytes * (n - 1) // max(n, 1)
        else:  # collective-permute: point-to-point, output == wire
            wire = nbytes
        out[kind] += wire
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def grad_reduce_wire_bytes(wire: Dict[str, int]) -> int:
    """The gradient-REDUCTION share of a wire-bytes breakdown: the
    collectives a grad reduction can lower to (reduce-scatter under
    implicit SPMD on TPU, all-reduce on backends that fuse the slice,
    all-to-all in the explicit quantized step) — the single number the
    int8-vs-fp32 ratio gate compares (tests/test_zero.py,
    tools/quant_smoke.py)."""
    return (wire["reduce-scatter"] + wire["all-reduce"]
            + wire["all-to-all"])


def record_comm_metrics(registry, hlo_text: str,
                        default_group: int = 1) -> Dict[str, int]:
    """Fold one compiled module's per-collective bytes into a telemetry
    metrics registry (obs/metrics.py) as `collective_bytes{kind=...}`
    (output bytes) and `collective_wire_bytes{kind=...}` (per-device
    wire estimate) gauges — so the tests' evidence and any
    consumer of the unified metrics stream read the SAME accounting
    instead of a private dict. Returns the collective_bytes_from_hlo
    breakdown."""
    out = collective_bytes_from_hlo(hlo_text)
    for kind, n in out.items():
        registry.gauge("collective_bytes", kind=kind).set(n)
    for kind, n in collective_wire_bytes_from_hlo(
            hlo_text, default_group).items():
        registry.gauge("collective_wire_bytes", kind=kind).set(n)
    return out


def per_chip_state_bytes(mesh: Mesh, abstract_state: Any,
                         zero_update: bool = False) -> Dict[str, int]:
    """Per-chip persistent bytes of the train state under the sharding
    rules — {'params', 'opt_state', 'total'}. Computed from shardings
    and abstract shapes alone (no allocation), so it reports the same
    number for a CPU-virtual mesh as for the real pod shape."""
    from proteinbert_tpu.parallel.sharding import state_sharding

    shardings = state_sharding(mesh, abstract_state, zero_update=zero_update)
    sizes = {"params": 0, "opt_state": 0, "other": 0}

    def add(path, leaf, sh):
        shard_shape = sh.shard_shape(leaf.shape)
        n = 1
        for d in shard_shape:
            n *= d
        nbytes = n * jnp.dtype(leaf.dtype).itemsize
        p = path[0]
        key = getattr(p, "key", None) or getattr(p, "name", None)
        sizes["params" if key == "params"
              else "opt_state" if key == "opt_state" else "other"] += nbytes

    jax.tree_util.tree_map_with_path(add, abstract_state, shardings)
    sizes["total"] = sizes["params"] + sizes["opt_state"] + sizes["other"]
    return sizes
