"""NamedSharding rules for train state and batches (SURVEY C18/C19).

The reference has no parallelism of any kind; these rules define how this
framework lays out the ProteinBERT train state and input batches over the
(data, fsdp, model, seq) mesh:

- batch tokens (B, L): B over (data, fsdp), L over seq — sequence
  parallelism enters at the input and propagates through the conv stack
  (XLA adds halo exchange) and the attention softmax (psum over seq).
- batch annotations (B, A): B over (data, fsdp); the 8943-dim annotation
  vector stays whole per example.
- activations of the ProteinBERT trunk (`pin_to_batch_layout`, PR 29): the
  layout their batch came in with — rows over (data, fsdp), positions
  over seq, features whole — stated at the embedding, on the carry of the
  scan over blocks and before the heads, whenever the step is traced
  under a mesh (`pin_state_sharding` runs it under the state's). Batch
  and block weights both name 'fsdp'; with the activations pinned, and
  each block's weights stated whole at the top of the block
  (`gathered_over_fsdp`), that conflict is resolved by all-gathering the
  weights and reducing their gradients, which is what `fsdp` means. Left
  to choose, the partitioner gathered the batch and ran tensor
  parallelism over the axis (obs/tracing.collective_census counts
  which).
- params: tensor parallelism on the two A-sized matmuls — `global_head`
  kernel (G, A) column-sharded and `global_in` kernel (A, G) row-sharded
  over 'model' (the A dim is the big one, SURVEY §7 hard-part (e));
  everything else ≥2D is FSDP-sharded over 'fsdp' on its largest
  divisible axis (skipping the stacked-block leading N axis), scalars and
  vectors replicated.
- optimizer state: Adam's mu/nu mirror the params tree structure, so the
  same path-driven rule applies (their tree paths contain the param
  paths). Under `parallel.zero_update` (ZeRO-1, parallel/zero.py) they
  additionally carry the joint ('data','fsdp') replica axis
  (zero_update_spec below) so each replica persists only a
  1/(data*fsdp) slice of the Adam moments.

All rules are resolved from an ABSTRACT pytree (jax.eval_shape) so no
memory is allocated before shardings are known.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# The batch's layout, which its activations keep (`pin_to_batch_layout`).
BATCH_ROW_AXES = ("data", "fsdp")
BATCH_POSITION_AXIS = "seq"


def batch_sharding(mesh: Mesh) -> Dict[str, NamedSharding]:
    return {
        "tokens": NamedSharding(
            mesh, P(BATCH_ROW_AXES, BATCH_POSITION_AXIS)),
        # Packed batches (data/packing.py): the per-position segment map
        # shards exactly like the tokens it annotates; the per-segment
        # (B, S, A) annotation tensor keeps batch-only sharding (the
        # trailing spec axes replicate, so the 2D unpacked (B, A) shape
        # uses the same entry).
        "segment_ids": NamedSharding(
            mesh, P(BATCH_ROW_AXES, BATCH_POSITION_AXIS)),
        "annotations": NamedSharding(mesh, P(BATCH_ROW_AXES, None)),
    }


def pin_to_batch_layout(x: jax.Array,
                        positions: Optional[int] = None) -> jax.Array:
    """`x`, an activation with the batch's rows on axis 0 (and its
    positions on axis `positions`, where it has them), constrained to the
    layout of `batch_sharding`: rows over ('data','fsdp'), positions over
    'seq', every other axis whole.

    The mesh is the one the step is being traced under (`jax.set_mesh`,
    which `pin_state_sharding` enters; inside a `shard_map`, its mesh).
    With none, or one whose data, fsdp and seq extents are all 1, `x`
    comes back as it is and the traced program gains nothing. An axis
    that is manual where this is traced (the bodies of parallel/zero.py,
    quant.py and seq_parallel.py) already holds its shard and may not be
    named: it is left out."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    free = [a for a in BATCH_ROW_AXES + (BATCH_POSITION_AXIS,)
            if mesh.shape.get(a, 1) > 1 and a not in mesh.manual_axes]
    spec = [None] * x.ndim
    spec[0] = tuple(a for a in free if a in BATCH_ROW_AXES) or None
    if positions is not None and BATCH_POSITION_AXIS in free:
        spec[positions] = BATCH_POSITION_AXIS
    if not any(spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def gathered_over_fsdp(tree: Any) -> Any:
    """A block's weights, every leaf whole on every chip, where the step
    is traced under a mesh whose 'fsdp' axis shards them (not manual,
    extent over 1); otherwise `tree` itself. Stated once at the top of a
    block, it is the all-gather of `fsdp`: one a leaf a block, whatever
    the partitioner would have chosen use by use (for a product with few
    rows it gathers the rows instead), and its transpose reduces the
    leaf's gradient."""
    mesh = jax.sharding.get_abstract_mesh()
    if (mesh.empty or mesh.shape.get("fsdp", 1) == 1
            or "fsdp" in mesh.manual_axes):
        return tree
    return jax.lax.with_sharding_constraint(tree, P())


def serve_batch_sharding(mesh: Mesh) -> Dict[str, NamedSharding]:
    """Sharding for SERVED micro-batches (serve/dispatch.py): batch dim
    over the joint ('data','fsdp') replica axis, sequence dim
    replicated. Unlike training's `batch_sharding`, the L axis does NOT
    carry 'seq' — served batches are sliced to ragged bucket lengths
    that need not divide the seq extent, and a single forward pass has
    no optimizer state to amortize a halo exchange against; batch-dim
    data parallelism is the whole win.

    Ragged PACKED batches (serve/dispatch.RaggedDispatcher under a
    mesh) use the same rules: the per-position segment map shards
    exactly like the tokens it annotates, and the per-segment
    (rows, S, A) annotation tensor keeps batch-only sharding (trailing
    spec axes replicate, so the 2D bucketed (rows, A) shape uses the
    same entry)."""
    return {
        "tokens": NamedSharding(mesh, P(("data", "fsdp"), None)),
        "segment_ids": NamedSharding(mesh, P(("data", "fsdp"), None)),
        "annotations": NamedSharding(mesh, P(("data", "fsdp"), None)),
    }


@functools.lru_cache(maxsize=None)
def on_each_replica(entry, mesh: Mesh):
    """A PACKED serving entry `entry(params, tokens, segment_ids,
    annotations, cfg)` (inference.py, heads/apply.py, the int8 arm of
    parallel/quant.py) as one program over `mesh` in which every replica
    runs `entry` ITSELF on its own rows: a `shard_map` over the whole
    mesh, the batch arguments and every output split over
    `serve_batch_sharding`'s joint ('data','fsdp') axis, the weights
    whole on every device.

    A packed row is a batch of independent proteins and no entry mixes
    rows, so this is the batch-dim data parallelism `serve_batch_sharding`
    asks the partitioner for, stated instead of inferred. It is stated
    because the forward-only entries put a Mosaic kernel in the program
    on a TPU (kernels/fused_block.packed_local_track_forward), and the
    partitioner refuses one ("Mosaic kernels cannot be automatically
    partitioned"): inside the map every axis is manual, each chip
    compiles the one-chip program at rows / replicas, and no collective
    exists. 'model' and 'seq' extents replicate the compute, as they did
    under the partitioner (the served weights are replicated). One
    jitted program an (entry, mesh), whoever asks."""
    from jax import shard_map

    rows = P(("data", "fsdp"))

    def call(params, tokens, segment_ids, annotations, cfg):
        # check_vma=False as in parallel/quant.py's bodies: the kernel's
        # `pallas_call` declares its output with no varying-axes type,
        # which the checker refuses.
        return shard_map(
            lambda p, t, s, a: entry(p, t, s, a, cfg), mesh=mesh,
            in_specs=(P(), rows, rows, rows), out_specs=rows,
            check_vma=False)(params, tokens, segment_ids, annotations)

    call.__name__ = entry.__name__  # tracing.note_program's key
    return jax.jit(call, static_argnames="cfg")


def _path_has(path, name: str) -> bool:
    for p in path:
        key = getattr(p, "key", None)
        if key is None:
            key = getattr(p, "name", None)
        if key == name:
            return True
    return False


def _leaf_spec(path, leaf, mesh: Mesh) -> P:
    shape = leaf.shape
    model_n = mesh.shape.get("model", 1)
    fsdp_n = mesh.shape.get("fsdp", 1)

    # Tensor parallelism over the annotation dimension A.
    if model_n > 1 and _path_has(path, "global_head"):
        if len(shape) >= 1 and shape[-1] % model_n == 0:
            return P(*([None] * (len(shape) - 1) + ["model"]))
    if model_n > 1 and _path_has(path, "global_in") and _path_has(path, "kernel"):
        if len(shape) >= 2 and shape[-2] % model_n == 0:
            return P(*([None] * (len(shape) - 2) + ["model", None]))

    # Expert parallelism: the decoder's routed experts (models/glm_moe.py,
    # leaves (..., E, in, out) under "experts") go over 'model' on their
    # expert axis; the router, the shared expert and attention stay
    # whole on every chip, as in the deployment the preset states.
    if (model_n > 1 and _path_has(path, "experts") and len(shape) >= 3
            and shape[-3] % model_n == 0):
        return P(*([None] * (len(shape) - 3) + ["model", None, None]))

    # The token-embedding table is REPLICATED: at 26 x local_dim it is
    # a few KB at every preset, so FSDP-sharding it saves nothing — and
    # a feature-sharded table makes the token-lookup gather produce
    # feature-sharded (B, L, D) activations that must be resharded to
    # batch sharding, which the partitioner can only do by replicating
    # at fsdp extents > 2 (involuntary full remat on the gather; caught
    # by the 16-device tier, tests/test_parallel16.py).
    if _path_has(path, "embedding"):
        return P()

    # FSDP: shard one axis of big tensors; never the stacked-blocks
    # leading axis (it is num_blocks-sized). Stacked-block leaves take
    # the LAST divisible axis, not the largest: the lax.scan over blocks
    # slices them per iteration, and the SPMD partitioner's forward and
    # backward while-loops settle on a trailing-axis layout for the
    # sliced values — a largest-axis choice forced an involuntary
    # full-rematerialisation reshard between the two loops on every
    # fsdp-bearing mesh (VERDICT r2 Weak #3; reproduced and fixed by
    # this rule on the 8-device dryrun meshes). Non-scanned leaves keep
    # the largest-axis choice (more even splits for oblong matrices
    # like the (A, G) global_in kernel).
    if fsdp_n > 1 and len(shape) >= 2:
        if _path_has(path, "blocks"):
            axes = range(len(shape) - 1, 0, -1)
        else:
            axes = sorted(range(len(shape)), key=lambda i: shape[i],
                          reverse=True)
        for ax in axes:
            if shape[ax] % fsdp_n == 0 and shape[ax] >= 2 * fsdp_n:
                spec = [None] * len(shape)
                spec[ax] = "fsdp"
                return P(*spec)
    return P()


def param_spec(path, leaf, mesh: Mesh) -> P:
    """Public storage spec for one leaf (scalar-safe `_leaf_spec`) — the
    layout params keep BETWEEN steps, zero-update or not (the ZeRO-1
    path all-gathers updated params back to this spec every step)."""
    if not hasattr(leaf, "shape") or len(getattr(leaf, "shape", ())) == 0:
        return P()
    return _leaf_spec(path, leaf, mesh)


def zero_update_spec(path, leaf, mesh: Mesh) -> P:
    """ZeRO-1 spec for one leaf: the storage spec EXTENDED with the
    joint ('data','fsdp') replica axis (arXiv:2004.13336's cross-replica
    weight-update sharding, resolved per-leaf from the abstract tree).

    Used for two things that must agree element-for-element: the
    persistent sharding of Adam mu/nu (state_sharding with
    zero_update=True — the HBM win), and the in/out specs of
    parallel/zero.py's update shard_map (params/grads enter sliced the
    same way, so the update math on each shard lines up).

    Placement, in preference order: (1) widen an existing 'fsdp' axis to
    ('data','fsdp') — data-slicing an already-fsdp-sharded axis further
    is free at the shard_map boundary; (2) the largest spec-free axis
    divisible by data*fsdp; (3) the largest spec-free axis divisible by
    the data extent alone ('data' only, keeping any fsdp placement);
    (4) give up — the leaf stays at its storage spec and the update runs
    replicated across data (identical math on every replica; only small
    leaves land here, so the memory claim is unaffected)."""
    base = param_spec(path, leaf, mesh)
    shape = getattr(leaf, "shape", ())
    if len(shape) == 0:
        return base
    data_n = mesh.shape.get("data", 1)
    fsdp_n = mesh.shape.get("fsdp", 1)
    joint = data_n * fsdp_n
    if joint == 1:
        return base
    entries = list(base) + [None] * (len(shape) - len(base))
    for i, e in enumerate(entries):
        if e == "fsdp" and shape[i] % joint == 0:
            entries[i] = ("data", "fsdp")
            return P(*entries)
    has_fsdp = any(e == "fsdp" for e in entries)
    by_size = sorted(range(len(shape)), key=lambda i: shape[i], reverse=True)
    if not has_fsdp:
        for ax in by_size:
            if entries[ax] is None and shape[ax] % joint == 0:
                entries[ax] = ("data", "fsdp")
                return P(*entries)
    if data_n > 1:
        # 'fsdp' stays where the storage rule put it (a mesh axis can
        # appear in a spec only once); 'data' gets its own axis.
        for ax in by_size:
            if entries[ax] is None and shape[ax] % data_n == 0:
                entries[ax] = "data"
                return P(*entries)
    return base


def _is_opt_state_path(path) -> bool:
    if not path:
        return False
    p = path[0]
    key = getattr(p, "key", None)
    if key is None:
        key = getattr(p, "name", None)
    return key == "opt_state"


def state_sharding(mesh: Mesh, abstract_state: Any,
                   zero_update: bool = False) -> Any:
    """NamedSharding pytree matching `abstract_state` (from jax.eval_shape).

    zero_update=True applies the ZeRO-1 rule to OPTIMIZER-STATE leaves:
    Adam's mu/nu additionally carry the joint ('data','fsdp') axis
    (zero_update_spec), so each replica persists only a 1/(data*fsdp)
    slice of the Adam moments instead of a full fsdp-sharded copy.
    Params keep their ordinary storage spec either way — the zero step
    all-gathers them fresh every update, so their layout between steps
    is unchanged (and checkpoints stay shape-identical across modes)."""
    def rule(path, leaf):
        if not hasattr(leaf, "shape") or len(getattr(leaf, "shape", ())) == 0:
            return NamedSharding(mesh, P())
        if zero_update and _is_opt_state_path(path):
            return NamedSharding(mesh, zero_update_spec(path, leaf, mesh))
        return NamedSharding(mesh, _leaf_spec(path, leaf, mesh))

    return jax.tree_util.tree_map_with_path(rule, abstract_state)


def shard_train_state(state: Any, mesh: Mesh,
                      zero_update: bool = False) -> Any:
    """Place a concrete TrainState onto the mesh per `state_sharding`."""
    shardings = state_sharding(mesh, jax.eval_shape(lambda: state),
                               zero_update=zero_update)
    return jax.device_put(state, shardings)


def pin_state_sharding(step, state, static_argnums=()):
    """`step(state, batch, ...) -> (new_state, metrics)`, jitted so that
    the new state keeps `state`'s layout leaf for leaf (and the old
    state is donated to it).

    Left to itself the partitioner may hand a leaf back in another
    layout than the storage rules chose — on fsdp meshes it does, for
    the replicated embedding table and its Adam moments. The next call
    then sees new input shardings and is traced and COMPILED a second
    time, and the state no longer sits where `state_sharding` put it.
    The trainer wraps every sharded step in this; one executable, one
    layout.

    The step runs (so: is traced) under the state's mesh, which is how
    the model learns there is one (`pin_to_batch_layout`)."""
    pinned = jax.tree.map(lambda a: a.sharding, state)
    jitted = jax.jit(step, static_argnums=static_argnums, donate_argnums=0,
                     out_shardings=(pinned, None))
    meshes = {s.mesh for s in jax.tree.leaves(pinned)
              if isinstance(s, NamedSharding)}
    if len(meshes) != 1:
        return jitted
    return _UnderMesh(jitted, meshes.pop())


class _UnderMesh:
    """A jitted step, called and lowered under `jax.set_mesh(mesh)`. The
    mesh is part of jit's cache key, so two wrappers of one step and
    mesh share one trace and one executable."""

    def __init__(self, jitted, mesh: Mesh):
        self._jitted, self._mesh = jitted, mesh
        self.__name__ = jitted.__name__

    def __call__(self, *args, **kwargs):
        with jax.set_mesh(self._mesh):
            return self._jitted(*args, **kwargs)

    def lower(self, *args, **kwargs):
        with jax.set_mesh(self._mesh):
            return self._jitted.lower(*args, **kwargs)
