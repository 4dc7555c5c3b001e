"""Quantized collectives and int8 serving (EQuARX, arXiv:2506.17615).

Two quantization surfaces share this module because they share the
primitives (symmetric scales, stochastic rounding, int8 payloads):

**Training — the quantized reduce-scatter.** PR 2's
`parallel.grad_reduce_dtype="bf16"` rounded the ALREADY-REDUCED
gradients (numerics only): under the implicit-SPMD step the gradient
tensor carries a pending fp32 psum no cast may hoist ahead of, so the
wire still moved fp32. The quantized step here removes that wall by
computing PER-REPLICA partial gradients explicitly — corruption stays
in the implicit jit (same ops, same step key, so fp32-vs-quantized
runs corrupt identically), while the forward/backward runs inside a
`shard_map` over the joint ('data','fsdp') replica axis on the local
batch shard. The loss decomposes exactly: every term is a ratio of
global sums (train/loss.py), so with the weight-mass denominators
psum'd up front each replica's objective `local_numerator / D` sums to
the global loss, and its gradient is a true partial. The reduction is
then OURS to quantize:

  split each partial into one slice per destination replica along the
  leaf's zero-update axis (sharding.zero_update_spec — the SAME rule
  that lays out the persistent Adam moments, so the reduced shard
  lands exactly where the optimizer wants it)
  → quantize slices (bf16: stochastic round; int8: per-chunk symmetric
    scale + stochastic round, seeded from the step key + replica index
    — deterministic and multi-host lockstep by construction)
  → `all_to_all` the payloads (THIS is the wire: int8 moves ~4x fewer
    bytes than fp32, bf16 2x — verified from compiled HLO by
    `zero.collective_wire_bytes_from_hlo`, tests/test_zero.py)
  → dequantize + sum the n received slices = this replica's shard of
    the summed gradient
  → the SHARED optimizer-apply (train_state.gradient_update) on the
    1/(data*fsdp) shard, params all-gathered back to storage — both
    unchanged from parallel/zero.py.

Leaves whose zero-update spec is not a clean joint-axis slice (the
small replicated remainder of zero_update_spec's fallback) reduce by
plain fp32 psum — honest bytes, negligible share. The gradient-clip
norm is measured on the DEQUANTIZED summed gradient (the tensor the
optimizer actually consumes). `payload="fp32"` runs the identical
explicit reduce-scatter without rounding — the baseline
tests/test_zero.py compares the quantized wire against, and the
isolation control for parity tests (harness error vs quantization
error).

Restrictions (typed `QuantConfigError`): the explicit replica
shard_map replicates model/seq compute, so meshes with model>1 or
seq>1 are rejected, as is the explicit sequence-parallel Pallas step
(parallel/seq_parallel.py — mirroring its packing rejection); the
global batch must split evenly over data*fsdp.

**Serving — the int8 executable arm.** `quantize_params` rewrites
every >=2-D float leaf of a trunk as {q: int8, scale: fp32 per output
channel} (symmetric, deterministic round-to-nearest — serving stays
reproducible); 1-D leaves (biases, LN) stay fp32. The quantized jitted
entries dequantize INSIDE the executable, so HBM holds int8 weights
(~4x smaller trunk — the headroom ROADMAP item 5's two resident trunks
need) and XLA fuses the dequant into first use. `quant="int8_act"`
additionally fake-quantizes the trunk's output activations (dynamic
per-tensor int8) before the output heads — the opt-in activation arm.
Parity vs the fp32 arm is measured per request and surfaced
(serve/dispatch.py parity sampling, `serve_quant_parity_max`), and
tests/test_heads.py holds the heads' downstream scores on the
quantized trunk within 0.1 of the fp32 trunk's.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from proteinbert_tpu.configs import ModelConfig, PretrainConfig

ZERO_AXES = ("data", "fsdp")

# Payload dtypes of the explicit quantized reduce-scatter ("fp32" is
# the unrounded measurement/control baseline, not a config value).
WIRE_PAYLOADS = ("fp32", "bf16", "int8")

# Elements per int8 scale block: one fp32 scale per QUANT_CHUNK int8
# payload elements is <1% wire overhead while keeping a single outlier
# from crushing a whole slice's resolution.
QUANT_CHUNK = 512

# Serving quantization modes (configs.ServeConfig.quant / `pbt serve
# --quant`): fp32 = the ordinary executables; int8 = int8 weights,
# dequantized in-executable; int8_act = int8 weights + dynamic int8
# fake-quant of the trunk's output activations (opt-in).
SERVE_QUANT_MODES = ("fp32", "int8", "int8_act")


class QuantConfigError(ValueError):
    """A quantization knob was combined with a configuration that
    cannot honor it (unknown dtype/mode, model/seq-parallel mesh, the
    explicit seq-parallel Pallas step, indivisible batch)."""


# ----------------------------------------------------------- primitives


def stochastic_round_bf16(x: jax.Array, key: jax.Array) -> jax.Array:
    """Stochastically round fp32 to bf16: add uniform 16-bit noise to
    the raw mantissa bits, then truncate to the bf16 (top-16-bit)
    pattern — P(round up) equals the discarded fraction, so the
    rounding is unbiased (the EQuARX requirement: biased rounding of
    gradient partials accumulates a systematic drift over replicas).
    Deterministic under a fixed key."""
    bits = jax.random.bits(key, x.shape, jnp.uint16).astype(jnp.uint32)
    u = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return lax.bitcast_convert_type(
        (u + bits) & jnp.uint32(0xFFFF0000), jnp.float32
    ).astype(jnp.bfloat16)


def quantize_int8_chunks(
    x: jax.Array, key: Optional[jax.Array],
    chunk: int = QUANT_CHUNK,
) -> Tuple[jax.Array, jax.Array, int]:
    """(..., m) fp32 → (int8 payload (..., k, chunk), fp32 scales
    (..., k), original m). Symmetric per-chunk scale amax/127; with a
    key the round is stochastic (unbiased — the training reduction),
    without it round-to-nearest (deterministic — serving weights)."""
    m = x.shape[-1]
    # Near-equal blocks instead of fixed-size blocks with a ragged
    # tail: k = ceil(m/chunk) blocks of ceil(m/k) elements pads < k
    # elements total, where a fixed 512 grid would pad a 576-element
    # slice by 78% (and a 16-element bias slice by 32x) — padding that
    # quietly eats the wire compression the payload buys.
    k = max(1, -(-m // chunk))
    chunk = -(-m // k)
    pad = k * chunk - m
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xc = x.reshape(x.shape[:-1] + (k, chunk))
    amax = jnp.max(jnp.abs(xc), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    y = xc / scale[..., None]
    if key is not None:
        y = jnp.floor(y + jax.random.uniform(key, y.shape))
    else:
        y = jnp.round(y)
    return jnp.clip(y, -127, 127).astype(jnp.int8), scale, m


def dequantize_int8_chunks(q: jax.Array, scale: jax.Array,
                           m: int) -> jax.Array:
    """Inverse of quantize_int8_chunks (trailing pad dropped)."""
    full = q.astype(jnp.float32) * scale[..., None]
    return full.reshape(full.shape[:-2] + (-1,))[..., :m]


# ------------------------------------------- quantized reduce-scatter


def _axes_of(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, tuple) else (entry,)


def _leaf_plan(spec: P, shape: Tuple[int, ...], joint: int):
    """How one gradient leaf reduces: ("alltoall", dim) when its
    zero-update spec is a single clean ('data','fsdp') slice along
    `dim` (the quantized path), else ("psum", entries) — plain fp32
    psum, then a local slice to the spec's layout (the small
    fallback-leaf remainder; see module doc)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    rep = [i for i, e in enumerate(entries)
           if any(a in ZERO_AXES for a in _axes_of(e))]
    if (len(rep) == 1 and _axes_of(entries[rep[0]]) == ZERO_AXES
            and shape[rep[0]] % joint == 0
            and all(e is None for i, e in enumerate(entries)
                    if i != rep[0])):
        return ("alltoall", rep[0])
    return ("psum", tuple(entries))


def _replica_index(mesh: Mesh) -> jax.Array:
    """This device's linear index along the joint ('data','fsdp') axis
    — data-major, matching both shard_map's boundary slicing and
    all_to_all's destination order over the axis tuple."""
    idx = lax.axis_index("data")
    return idx * mesh.shape.get("fsdp", 1) + lax.axis_index("fsdp")


def _exchange(x: jax.Array) -> jax.Array:
    """all_to_all over the joint replica axis with optimization
    barriers pinning the payload DTYPE at the collective: without
    them, XLA's simplifier hoists the post-exchange dequant converts
    across the all-to-all (convert(all-to-all(q)) →
    all-to-all(convert(q))) and the wire silently moves fp32 again —
    the exact failure mode this module exists to remove (observed on
    the CPU backend; the barriers are identity ops, numerics
    untouched)."""
    x = lax.optimization_barrier(x)
    x = lax.all_to_all(x, ZERO_AXES, 0, 0, tiled=True)
    return lax.optimization_barrier(x)


def _reduce_scatter_leaf(g: jax.Array, dim: int, n: int, payload: str,
                         key: Optional[jax.Array]) -> jax.Array:
    """Inside the shard_map body: reduce this replica's full-shape
    partial `g` across the joint axis and return MY shard (slice along
    `dim`), with the wire carrying `payload`-typed slices."""
    x = jnp.moveaxis(g, dim, 0)
    lead, rest = x.shape[0], x.shape[1:]
    x = x.reshape(n, -1).astype(jnp.float32)
    m = x.shape[1]
    if payload == "int8":
        q, scale, _ = quantize_int8_chunks(x, key)
        q = _exchange(q)
        scale = _exchange(scale)
        red = (q.astype(jnp.float32) * scale[..., None]).sum(0)
        red = red.reshape(-1)[:m]
    elif payload == "bf16":
        q = stochastic_round_bf16(x, key)
        # Exchange the bf16 payload BITCAST to uint16: backends without
        # native bf16 (the CPU virtual meshes the byte evidence is
        # compiled on) float-normalize bf16 collectives up to f32,
        # which would silently double the wire; the u16 view is
        # bit-identical and integer-typed, so it survives every
        # backend's normalization passes at 2 bytes/element.
        q = lax.bitcast_convert_type(q, jnp.uint16)
        q = _exchange(q)
        q = lax.bitcast_convert_type(q, jnp.bfloat16)
        red = q.astype(jnp.float32).sum(0)
    else:  # fp32 — the unquantized explicit baseline
        red = _exchange(x).sum(0)
    red = red.reshape((lead // n,) + rest)
    return jnp.moveaxis(red, 0, dim)


def _slice_to_entries(x: jax.Array, entries, mesh: Mesh) -> jax.Array:
    """Slice a replicated (already-summed) leaf down to this device's
    shard per its spec entries — the psum-fallback leaves' exit."""
    for i, e in enumerate(entries):
        names = _axes_of(e)
        if not names:
            continue
        idx = jnp.int32(0)
        ext = 1
        for name in names:
            idx = idx * mesh.shape[name] + lax.axis_index(name)
            ext *= mesh.shape[name]
        size = x.shape[i] // ext
        x = lax.dynamic_slice_in_dim(x, idx * size, size, axis=i)
    return x


def check_quant_mesh(mesh: Mesh, payload: str,
                     batch_size: Optional[int] = None) -> int:
    """Validate a quantized-reduction request; returns the joint
    replica extent. Raises the typed QuantConfigError otherwise."""
    if payload not in WIRE_PAYLOADS:
        raise QuantConfigError(
            f"unknown quantized-reduction payload {payload!r}; "
            f"expected one of {WIRE_PAYLOADS}")
    joint = 1
    for ax in ZERO_AXES:
        joint *= mesh.shape.get(ax, 1)
    if joint <= 1:
        raise QuantConfigError(
            "quantized gradient reduction needs data*fsdp > 1 — there "
            "is no cross-replica reduction to compress on this mesh")
    for ax in ("model", "seq"):
        if mesh.shape.get(ax, 1) > 1:
            raise QuantConfigError(
                f"grad_reduce_dtype={payload!r} runs the forward/"
                f"backward inside an explicit data-parallel shard_map "
                f"and cannot shard the {ax!r} axis (extent "
                f"{mesh.shape[ax]}); use grad_reduce_dtype='fp32' (or "
                f"'bf16' numerics-only under the explicit seq-parallel "
                f"step) on model/seq-parallel meshes")
    if batch_size is not None and batch_size % joint:
        raise QuantConfigError(
            f"global batch {batch_size} does not split evenly over the "
            f"data*fsdp extent {joint} — the quantized step shards the "
            f"batch explicitly")
    return joint


@lru_cache(maxsize=8)
def make_quant_zero_train_step(mesh: Mesh, cfg: PretrainConfig,
                               payload: Optional[str] = None):
    """Jitted ZeRO-1 pretraining step whose gradient reduction is the
    explicit quantized reduce-scatter (module doc) — the
    `make_zero_train_step` route for grad_reduce_dtype in
    {"bf16","int8"}; `payload` overrides the wire dtype ("fp32" = the
    unrounded measurement baseline). Same signature and plateau_value
    contract as the fp32 zero step."""
    import optax

    from proteinbert_tpu.models import proteinbert
    from proteinbert_tpu.parallel.sharding import param_spec
    from proteinbert_tpu.parallel.zero import _update_specs
    from proteinbert_tpu.train import train_state as ts
    from proteinbert_tpu.train.loss import packed_segment_losses
    from proteinbert_tpu.train.schedule import (
        effective_lr, make_optimizer, needs_loss_value,
    )
    from jax import shard_map

    payload = payload or cfg.parallel.grad_reduce_dtype
    joint = check_quant_mesh(mesh, payload, cfg.data.batch_size)
    opt_cfg = cfg.optimizer
    needs_value = needs_loss_value(opt_cfg)
    batch_spec = P(ZERO_AXES)

    def step(state: ts.TrainState, batch: Dict[str, jax.Array],
             plateau_value: Optional[jax.Array] = None):
        key, X, Y, W, seg = ts.corrupt_for_step(state, batch, cfg)
        # Noise stream for the stochastic rounding: derived from the
        # (replicated, checkpointed) state key, so re-runs and every
        # host of a multi-host run draw the same noise — fold_in
        # keeps it independent of the corruption stream.
        noise_key = jax.random.fold_in(key, 0x5172)
        p_specs = _update_specs(mesh, state.params)
        o_specs = _update_specs(mesh, state.opt_state)
        spec_leaves = jax.tree.leaves(
            p_specs, is_leaf=lambda x: isinstance(x, P))
        has_pv = plateau_value is not None
        value_arr = jnp.asarray(
            0.0 if plateau_value is None else plateau_value, jnp.float32)

        def body(params_full, params_sh, opt_sh, Xs, Ys, Ws, segs,
                 nkey, plateau_v):
            if segs is None:
                pad_mask = Ws["local"] > 0
                D_l = jnp.maximum(
                    lax.psum(Ws["local"].sum(), ZERO_AXES), 1.0)
                D_g = jnp.maximum(
                    lax.psum(Ws["global"].sum(), ZERO_AXES), 1.0)

                def loss_fn(p):
                    ll, gl = proteinbert.apply(
                        p, Xs["local"], Xs["global"], cfg.model, pad_mask)
                    ce = optax.softmax_cross_entropy_with_integer_labels(
                        ll, Ys["local"])
                    nl = (ce * Ws["local"]).sum()
                    bce = optax.sigmoid_binary_cross_entropy(
                        gl, Ys["global"])
                    ng = (bce * Ws["global"]).sum()
                    acc = ((ll.argmax(-1) == Ys["local"])
                           .astype(jnp.float32) * Ws["local"]).sum()
                    return nl / D_l + ng / D_g, (nl, ng, acc)
            else:
                # Packed rows: same decomposition over the per-segment
                # terms (packed_pretrain_loss is a weighted mean of
                # per-segment ratios whose masks are data-only).
                S = Ws["global"].shape[1]
                onehot = (segs[..., None] == jnp.arange(
                    1, S + 1, dtype=segs.dtype)).astype(jnp.float32)
                seg_valid = (jnp.einsum("bl,bls->bs", Ws["local"],
                                        onehot) > 0).astype(jnp.float32)
                seg_weighted = (Ws["global"].sum(-1) > 0).astype(
                    jnp.float32)
                D_l = jnp.maximum(
                    lax.psum(seg_valid.sum(), ZERO_AXES), 1.0)
                D_g = jnp.maximum(
                    lax.psum(seg_weighted.sum(), ZERO_AXES), 1.0)

                def loss_fn(p):
                    ll, gl = proteinbert.apply(
                        p, Xs["local"], Xs["global"], cfg.model,
                        segment_ids=segs)
                    terms = packed_segment_losses(ll, gl, Ys, Ws, segs)
                    nl = (terms["local"] * seg_valid).sum()
                    ng = (terms["global"] * seg_weighted).sum()
                    acc = (terms["local_acc"] * seg_valid).sum()
                    return nl / D_l + ng / D_g, (nl, ng, acc)

            grads, (nl, ng, acc) = jax.grad(
                loss_fn, has_aux=True)(params_full)
            nl = lax.psum(nl, ZERO_AXES)
            ng = lax.psum(ng, ZERO_AXES)
            acc = lax.psum(acc, ZERO_AXES)
            metrics = {
                "loss": nl / D_l + ng / D_g,
                "local_loss": nl / D_l,
                "global_loss": ng / D_g,
                "local_acc": acc / D_l,
            }

            # --- the quantized reduce-scatter, leaf by leaf -----------
            g_leaves, treedef = jax.tree_util.tree_flatten(grads)
            my_idx = _replica_index(mesh)
            reduced: List[jax.Array] = []
            sq_sharded = jnp.float32(0.0)
            sq_replicated = jnp.float32(0.0)
            for i, (g, spec) in enumerate(zip(g_leaves, spec_leaves)):
                kind, info = _leaf_plan(spec, g.shape, joint)
                if kind == "alltoall":
                    rk = jax.random.fold_in(
                        jax.random.fold_in(nkey, i), my_idx)
                    shard = _reduce_scatter_leaf(
                        g, info, joint, payload,
                        None if payload == "fp32" else rk)
                    sq_sharded = sq_sharded + (
                        shard.astype(jnp.float32) ** 2).sum()
                    reduced.append(shard)
                else:
                    full = lax.psum(g.astype(jnp.float32), ZERO_AXES)
                    sq_replicated = sq_replicated + (full ** 2).sum()
                    reduced.append(_slice_to_entries(full, info, mesh))
            grads_sh = jax.tree_util.tree_unflatten(treedef, reduced)
            # Clip norm of the DEQUANTIZED summed gradient — the tensor
            # the optimizer consumes (sharded leaves tile the full
            # tensor across replicas; psum'd leaves are whole already).
            g_norm = jnp.sqrt(
                lax.psum(sq_sharded, ZERO_AXES) + sq_replicated)

            value = ts.plateau_observation(
                opt_cfg, metrics, plateau_v if has_pv else None)
            tx = make_optimizer(opt_cfg, clip_norm_value=g_norm)
            new_p, new_o = ts.gradient_update(
                tx, params_sh, grads_sh, opt_sh, value, needs_value)
            return new_p, new_o, metrics, g_norm

        if seg is None:
            fn = shard_map(
                lambda pf, psh, osh, xs, ys, ws, nk, pv: body(
                    pf, psh, osh, xs, ys, ws, None, nk, pv),
                mesh=mesh,
                in_specs=(P(), p_specs, o_specs, batch_spec, batch_spec,
                          batch_spec, P(), P()),
                out_specs=(p_specs, o_specs, P(), P()),
                # Same rep/vma situation as the fp32 zero body: mixed
                # sharded/replicated outputs the checker cannot type;
                # parity with the replicated step is asserted by
                # tests/test_quant.py instead.
                check_vma=False,
            )
            new_params, new_opt, metrics, g_norm = fn(
                state.params, state.params, state.opt_state, X, Y, W,
                noise_key, value_arr)
        else:
            fn = shard_map(
                lambda pf, psh, osh, xs, ys, ws, sg, nk, pv: body(
                    pf, psh, osh, xs, ys, ws, sg, nk, pv),
                mesh=mesh,
                in_specs=(P(), p_specs, o_specs, batch_spec, batch_spec,
                          batch_spec, batch_spec, P(), P()),
                out_specs=(p_specs, o_specs, P(), P()),
                check_vma=False,
            )
            new_params, new_opt, metrics, g_norm = fn(
                state.params, state.params, state.opt_state, X, Y, W,
                seg, noise_key, value_arr)

        store = jax.tree_util.tree_map_with_path(
            lambda path, leaf: NamedSharding(
                mesh, param_spec(path, leaf, mesh)), new_params)
        new_params = lax.with_sharding_constraint(new_params, store)
        metrics = dict(metrics)
        metrics["grad_norm"] = g_norm
        metrics["lr"] = effective_lr(opt_cfg, new_opt, state.step)
        return ts.TrainState(step=state.step + 1, params=new_params,
                             opt_state=new_opt, key=key), metrics

    return jax.jit(step, donate_argnums=ts.DONATE_STATE)


# ------------------------------------------------- int8 serving weights


def _is_quant_leaf(x) -> bool:
    return isinstance(x, dict) and set(x) == {"q", "scale"}


def quantize_params(params: Any) -> Any:
    """Symmetric per-output-channel int8 weight quantization of a trunk
    at load time: every float leaf with ndim >= 2 (dense/conv kernels,
    embeddings, the stacked block tensors) becomes {"q": int8,
    "scale": fp32} with the scale reduced over the leaf's INPUT axis
    (axis -2), keeping per-(stack/head, output-channel) resolution for
    the scanned block stacks; 1-D leaves (biases, LN scale/offset)
    stay fp32 — their bytes are noise and their dynamic range matters.
    Deterministic (round-to-nearest): the quantized arm serves
    reproducible outputs."""

    def quant(leaf):
        if (not hasattr(leaf, "ndim") or leaf.ndim < 2
                or not jnp.issubdtype(jnp.asarray(leaf).dtype,
                                      jnp.floating)):
            return leaf
        w = jnp.asarray(leaf, jnp.float32)
        amax = jnp.max(jnp.abs(w), axis=-2)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(w / scale[..., None, :]),
                     -127, 127).astype(jnp.int8)
        return {"q": q, "scale": scale}

    return jax.tree.map(quant, params)


def dequantize_params(qparams: Any) -> Any:
    """Quantized tree → fp32 params, traceable (called INSIDE the
    quantized executables, so HBM holds the int8 form and XLA fuses
    the dequant into first use)."""

    def deq(x):
        if _is_quant_leaf(x):
            return x["q"].astype(jnp.float32) * x["scale"][..., None, :]
        return x

    return jax.tree.map(deq, qparams, is_leaf=_is_quant_leaf)


# The block weights the Pallas kernels dequantize IN-KERNEL (ISSUE 16):
# per-tile q·scale inside the one-pass / fused-segment / attention
# programs, so HBM ships int8 bytes on the serving fast path. Everything
# else (embeddings, heads, the block's global-side denses — consumed by
# plain XLA ops) keeps the HLO dequant.
_INKERNEL_QUANT_KEYS = (
    ("narrow_conv", "kernel"),
    ("wide_conv", "kernel"),
    ("local_dense", "kernel"),
    ("attention", "wq"),
    ("attention", "wk"),
    ("attention", "wv"),
)


def partial_dequantize_params(qparams: Any, use_pallas: bool = True) -> Any:
    """Quantized tree → the form the in-kernel-dequant serving arm
    consumes: every quant leaf is HLO-dequantized EXCEPT the block
    kernel weights the Pallas dispatches accept natively
    (`_INKERNEL_QUANT_KEYS` under "blocks"), which stay {"q": int8,
    "scale": fp32} so the kernels load int8 into VMEM and dequantize
    per-tile. With `use_pallas=False` no kernel ever sees the tree, so
    this degenerates to the full `dequantize_params` (the XLA reference
    path computes from HLO-dequantized weights either way — the kernel
    dispatch fallbacks do the same dequant themselves)."""
    if not use_pallas:
        return dequantize_params(qparams)

    def deq(path, x):
        if not _is_quant_leaf(x):
            return x
        keys = tuple(getattr(p, "key", None) for p in path)
        if "blocks" in keys and keys[-2:] in _INKERNEL_QUANT_KEYS:
            return x
        return x["q"].astype(jnp.float32) * x["scale"][..., None, :]

    return jax.tree_util.tree_map_with_path(deq, qparams,
                                            is_leaf=_is_quant_leaf)


def param_bytes(params: Any) -> int:
    """Total bytes of every array leaf — the HBM-footprint evidence for
    the quantized trunk (quant leaves count q + scale)."""
    total = 0
    for leaf in jax.tree.leaves(params):
        if hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
        elif hasattr(leaf, "size"):
            total += int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
    return total


def quantize_rows_int8(x) -> Tuple["np.ndarray", "np.ndarray"]:
    """Symmetric per-channel int8 quantization of a ROW BATCH — the
    store-side counterpart of `quantize_params` (same convention:
    amax/127 scales, deterministic round-to-nearest, zero-range
    channels pinned to scale 1.0). Host numpy on purpose: the neighbor
    index builder (proteinbert_tpu/index/) quantizes residual vectors
    while serializing blocks, where byte-identical re-runs are part of
    the durability contract and device nondeterminism would break the
    chaos drill's byte-identity gate. Returns (codes int8 (n, d),
    scales fp32 (d,))."""
    import numpy as np
    x = np.asarray(x, dtype=np.float32)
    if x.ndim != 2:
        raise QuantConfigError(
            f"quantize_rows_int8 expects (rows, channels), got shape "
            f"{x.shape}")
    amax = np.max(np.abs(x), axis=0) if x.shape[0] else \
        np.zeros(x.shape[1], np.float32)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    codes = np.clip(np.rint(x / scale), -127, 127).astype(np.int8)
    return codes, scale


def dequantize_rows_int8(codes, scale) -> "np.ndarray":
    """Inverse of quantize_rows_int8 (up to rounding): codes * scale,
    fp32. The offline/reference dequant — the jitted scorer fuses the
    same arithmetic into its executable."""
    import numpy as np
    return (np.asarray(codes, np.float32)
            * np.asarray(scale, np.float32)[None, :])


def fake_quant_act(x: jax.Array) -> jax.Array:
    """Dynamic per-tensor symmetric int8 fake-quantization (the opt-in
    activation arm): quantize-dequantize in the activation dtype, so
    the numerics are int8's while the executable layout is unchanged."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return (jnp.clip(jnp.round(xf / scale), -127, 127) * scale).astype(
        x.dtype)


# Quantized jitted serving entries: thin wrappers that dequantize
# in-jit and inline the EXISTING entry bodies (inference.py /
# heads/apply.py), so the quantized arm cannot drift from the fp32
# arm's semantics. The act variants re-compose encode + output heads
# (models/proteinbert.apply is exactly that) with the trunk's output
# activations fake-quantized in between.


@partial(jax.jit, static_argnames="cfg")
def _q_encode_batch(qparams, tokens, annotations, cfg: ModelConfig):
    from proteinbert_tpu import inference

    return inference._encode_batch(
        partial_dequantize_params(qparams, cfg.use_pallas), tokens,
                                   annotations, cfg)


@partial(jax.jit, static_argnames="cfg")
def _q_go_probs_batch(qparams, tokens, annotations, cfg: ModelConfig):
    from proteinbert_tpu import inference

    return inference._go_probs_batch(
        partial_dequantize_params(qparams, cfg.use_pallas), tokens,
                                     annotations, cfg)


@partial(jax.jit, static_argnames="cfg")
def _q_residue_probs_batch(qparams, tokens, annotations,
                           cfg: ModelConfig):
    from proteinbert_tpu import inference

    return inference._residue_probs_batch(
        partial_dequantize_params(qparams, cfg.use_pallas),
                                          tokens, annotations, cfg)


def _act_logits(params, tokens, annotations, cfg: ModelConfig):
    """models/proteinbert.apply with the trunk outputs fake-quantized
    before the output heads (the activation arm's cut point); the pad
    mask derives from tokens exactly as apply's default does."""
    from proteinbert_tpu.models import proteinbert
    from proteinbert_tpu.ops.layers import dense_apply

    local, global_ = proteinbert.encode(params, tokens, annotations,
                                        cfg)
    local = fake_quant_act(local)
    global_ = fake_quant_act(global_)
    local_logits = dense_apply(params["local_head"],
                               local).astype(jnp.float32)
    global_logits = dense_apply(params["global_head"],
                                global_).astype(jnp.float32)
    return local, global_, local_logits, global_logits


@partial(jax.jit, static_argnames="cfg")
def _q_act_encode_batch(qparams, tokens, annotations, cfg: ModelConfig):
    from proteinbert_tpu.data.vocab import PAD_ID

    params = dequantize_params(qparams)
    local, global_, _, _ = _act_logits(params, tokens, annotations, cfg)
    mask = (tokens != PAD_ID).astype(jnp.float32)[:, :, None]
    local = local.astype(jnp.float32)
    return {
        "local_mean": (local * mask).sum(1)
        / jnp.maximum(mask.sum(1), 1.0),
        "global": global_.astype(jnp.float32),
    }


@partial(jax.jit, static_argnames="cfg")
def _q_act_go_probs_batch(qparams, tokens, annotations,
                          cfg: ModelConfig):
    params = dequantize_params(qparams)
    _, _, _, gl = _act_logits(params, tokens, annotations, cfg)
    return jax.nn.sigmoid(gl)


@partial(jax.jit, static_argnames="cfg")
def _q_act_residue_probs_batch(qparams, tokens, annotations,
                               cfg: ModelConfig):
    params = dequantize_params(qparams)
    _, _, ll, _ = _act_logits(params, tokens, annotations, cfg)
    return jax.nn.softmax(ll, -1)


@partial(jax.jit, static_argnames="cfg")
def _q_packed_encode_batch(qparams, tokens, segment_ids, annotations,
                           cfg: ModelConfig):
    from proteinbert_tpu import inference

    return inference._packed_encode_batch(
        partial_dequantize_params(qparams, cfg.use_pallas), tokens, segment_ids, annotations,
        cfg)


@partial(jax.jit, static_argnames="cfg")
def _q_packed_go_probs_batch(qparams, tokens, segment_ids, annotations,
                             cfg: ModelConfig):
    from proteinbert_tpu import inference

    return inference._packed_go_probs_batch(
        partial_dequantize_params(qparams, cfg.use_pallas), tokens, segment_ids, annotations,
        cfg)


@partial(jax.jit, static_argnames="cfg")
def _q_packed_residue_probs_batch(qparams, tokens, segment_ids,
                                  annotations, cfg: ModelConfig):
    from proteinbert_tpu import inference

    return inference._packed_residue_probs_batch(
        partial_dequantize_params(qparams, cfg.use_pallas), tokens, segment_ids, annotations,
        cfg)


@partial(jax.jit, static_argnames="cfg")
def _q_trunk_batch(qparams, tokens, annotations, cfg: ModelConfig):
    from proteinbert_tpu.heads import apply as heads_apply

    return heads_apply.trunk_batch(
        partial_dequantize_params(qparams, cfg.use_pallas), tokens,
                                   annotations, cfg)


@partial(jax.jit, static_argnames="cfg")
def _q_packed_trunk_batch(qparams, tokens, segment_ids, annotations,
                          cfg: ModelConfig):
    from proteinbert_tpu.heads import apply as heads_apply

    return heads_apply.packed_trunk_batch(
        partial_dequantize_params(qparams, cfg.use_pallas), tokens, segment_ids, annotations,
        cfg)


def quant_entry(kind: str, act: bool = False):
    """The quantized executable for one request kind (bucketed path);
    predict_task trunks use `quant_trunk_entry`. Activation fake-quant
    is only defined for the pretrain kinds (heads trunks stay
    weight-only — documented in docs/serving.md)."""
    table = {
        ("embed", False): _q_encode_batch,
        ("predict_go", False): _q_go_probs_batch,
        ("predict_residues", False): _q_residue_probs_batch,
        ("embed", True): _q_act_encode_batch,
        ("predict_go", True): _q_act_go_probs_batch,
        ("predict_residues", True): _q_act_residue_probs_batch,
    }
    try:
        return table[(kind, act)]
    except KeyError:
        raise ValueError(f"no quantized entry for request kind "
                         f"{kind!r} (act={act})") from None


def quant_packed_entry(kind: str):
    table = {
        "embed": _q_packed_encode_batch,
        "predict_go": _q_packed_go_probs_batch,
        "predict_residues": _q_packed_residue_probs_batch,
    }
    try:
        return table[kind]
    except KeyError:
        raise ValueError(f"no quantized packed entry for request kind "
                         f"{kind!r}") from None
