"""Sparse-expert layer: a sigmoid router over ALL experts, and grouped
products over the experts THIS CHIP HOLDS, with no dropped assignment.

The router keeps its published width (`n_routed_experts`) and its experts
per token whatever share of the experts lives here; the chip computes the
part of the layer's result that its own experts give for the tokens
routed to them, and what the absent experts would add is left out (one
chip of an expert-parallel group, without its exchange).

    s       = sigmoid(x W_r)                 float32, all experts
    chosen  = top-k of s + b                 b: balance bias, no gradient
              (group-limited where the router has groups: the experts in
              `n_group` equal groups, a group's score the sum of its two
              best s + b, the best `topk_group` groups kept, the top k
              among their experts)
    weights = s[chosen] / (sum + 1e-20) * routed_scaling_factor
    y       = sum over chosen experts HELD HERE of weight * Expert_e(x)

A second router (`route_mlp`, ZAYA1's) is not one matrix: a
down-projection to a narrow state that adds the previous layer's, a
norm, a small MLP, a softmax, the top k with the chosen probabilities
themselves as weights; it hands its state on to the next layer.

Stages, each under its `jax.named_scope`:

- `moe_router`: the scores, the choice, the weights.
- `moe_dispatch`: the (token, slot) assignments to held experts sorted by
  expert, cut into blocks of `block` rows of ONE expert each (the last
  block of an expert is part full). For the length of the loop the
  layer's token arrays stand as SLABS, (tokens + block, S, 128) with the
  token axis leading, where the width is whole lanes (one relayout a
  layer each way; `kernels/moe_rows.py`), and two movers carry a block's
  rows, forward and backward alike: `_gather_rows` brings the block's
  REAL rows (`plan.block_rows[i]` of them, ascending, no token twice)
  with zeros after them, `_scatter_add_rows` adds the block's weighted
  result at the same rows, in float32, in place, block after block in
  the loop's order. On a TPU they are Pallas kernels that move a token's
  slab as one DMA and never touch a row past the real ones; elsewhere,
  and where the width is not whole lanes, XLA's gather and scatter-add
  over unique indices, the rows past the real ones pointing at
  `block` spare rows of zeros. `kernels/moe_rows.MOE_ROWS_PATH_TOTAL`
  counts which of the two a traced loop got.
- `moe_experts`: the grouped products: a loop over the blocks that hold
  anything, each block three products against its expert's matrices
  (`kind` "swiglu": down(silu(gate x) * up x)) or two ("relu2":
  down(relu(up x)^2), no gate matrix).
  The trip count is the number of blocks the step's routing filled, so
  the work follows the assignments that really fell here, and every one
  of them is taken whatever the imbalance: the block table is sized for
  every token choosing held experts in all its slots.

Where the experts are NARROWER than the stream (`cfg.moe_latent_size`:
LatentMoE) the layer's tokens go down to the latent once before the loop
(`to_latent`) and the loop's sum comes up once after it (`from_latent`),
both under `moe_latent`; the router still reads the stream, and the
loop's rows, slabs and movers are the latent's width.

A loop with a data-dependent trip count has no automatic transpose, so
`expert_ffn` carries its own backward pass: the same loop, each block
recomputing its hidden activations and adding its share of the
gradients of the inputs, the weights and the experts' matrices.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from proteinbert_tpu.kernels import moe_rows
from proteinbert_tpu.ops.layers import rms_norm_apply


class Plan(NamedTuple):
    """The step's assignments to held experts, by block."""

    order: jax.Array        # (A + block,) assignment ids sorted by held expert
    block_expert: jax.Array  # (max_blocks,) local expert id of each block
    block_start: jax.Array  # (max_blocks,) first sorted position of the block
    block_rows: jax.Array   # (max_blocks,) rows of the block that are real
    n_blocks: jax.Array     # () blocks that hold anything
    held_counts: jax.Array  # (experts_held,) tokens per held expert
    dropped: jax.Array      # () assignments to held experts in no block


def route(x, router_kernel, bias, top_k: int, scaling: float,
          norm_topk: bool = True, n_group: int = 1, topk_group: int = 1):
    """x: (T, D) -> (ids (T, k) int32, weights (T, k) float32). The
    product runs in float32 at full precision, as published; the bias
    moves the choice only."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(x.astype(jnp.float32),
                         router_kernel.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        choice = scores + lax.stop_gradient(bias)
        if n_group > 1:
            T, R = choice.shape
            group_score = lax.top_k(
                choice.reshape(T, n_group, R // n_group), 2)[0].sum(-1)
            kept = lax.top_k(group_score, topk_group)[1]
            open_ = (kept[:, :, None] == jnp.arange(n_group)).any(1)
            choice = jnp.where(jnp.repeat(open_, R // n_group, axis=1),
                               choice, -jnp.inf)
        _, ids = lax.top_k(choice, top_k)
        weights = jnp.take_along_axis(scores, ids, axis=-1)
        if norm_topk:
            weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
        return ids.astype(jnp.int32), weights * scaling


def router_probs(p: Dict, r, eps: float):
    """The MLP router from its state on: r (T, R) float32 ->
    softmax(W_3 gelu(W_2 gelu(W_1 RMSNorm(r) + b_1) + b_2)), (T, experts),
    float32 at full precision."""
    f32 = jnp.float32
    w = lambda name: p[name].astype(f32)  # noqa: E731
    dot = partial(jnp.dot, precision=lax.Precision.HIGHEST)
    h = rms_norm_apply(w("norm"), r, eps)
    h = jax.nn.gelu(dot(h, w("w1")) + w("b1"), approximate=False)
    h = jax.nn.gelu(dot(h, w("w2")) + w("b2"), approximate=False)
    return jax.nn.softmax(dot(h, w("w3")), axis=-1)


def route_mlp(x, p: Dict, state, bias, top_k: int, eps: float):
    """ZAYA1's router. x: (T, D); p: `proj` (D, R), `proj_bias`, `carry`
    (the R scales on the previous layer's state), `norm`, `w1`, `b1`,
    `w2`, `b2` (R, R), `w3` (R, experts); state: (T, R) float32, the
    previous layer's r (zeros before the first layer).

        r = x W_proj + b_proj + carry * state
        p = `router_probs(r)`;  chosen = top-k of p + b;  weights = p[chosen]

    -> (ids (T, k) int32, weights (T, k) float32, r (T, R) float32: what
    the next layer's router adds). Float32 at full precision throughout,
    as `route`; the bias moves the choice only."""
    with jax.named_scope("moe_router"):
        f32 = jnp.float32
        r = (jnp.dot(x.astype(f32), p["proj"].astype(f32),
                     precision=lax.Precision.HIGHEST)
             + p["proj_bias"].astype(f32) + p["carry"].astype(f32) * state)
        probs = router_probs(p, r, eps)
        _, ids = lax.top_k(probs + lax.stop_gradient(bias), top_k)
        return (ids.astype(jnp.int32),
                jnp.take_along_axis(probs, ids, axis=-1), r)


def max_blocks(tokens: int, top_k: int, experts_held: int, block: int) -> int:
    """Blocks enough for EVERY token to choose held experts in every
    slot it can: nothing is ever dropped for want of room."""
    most = tokens * min(top_k, experts_held)
    return -(-most // block) + experts_held


def plan_dispatch(ids, experts_held: int, expert_offset: int,
                  block: int) -> Plan:
    with jax.named_scope("moe_dispatch"):
        T, K = ids.shape
        local = ids.reshape(-1) - expert_offset
        held = (local >= 0) & (local < experts_held)
        key = jnp.where(held, local, experts_held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        counts = jnp.zeros((experts_held + 1,), jnp.int32).at[key].add(1)
        counts = counts[:experts_held]
        starts = jnp.cumsum(counts) - counts
        per_expert = (counts + block - 1) // block
        ends = jnp.cumsum(per_expert)
        n = max_blocks(T, K, experts_held, block)
        i = jnp.arange(n, dtype=jnp.int32)
        e = jnp.minimum(jnp.searchsorted(ends, i, side="right"),
                        experts_held - 1).astype(jnp.int32)
        j = i - (ends[e] - per_expert[e])
        rows = jnp.where(i < ends[-1],
                         jnp.clip(counts[e] - j * block, 0, block), 0)
        return Plan(
            order=jnp.concatenate([order, jnp.zeros((block,), jnp.int32)]),
            block_expert=e, block_start=starts[e] + j * block,
            block_rows=rows.astype(jnp.int32),
            n_blocks=jnp.minimum(ends[-1], n).astype(jnp.int32),
            held_counts=counts,
            dropped=counts.sum() - rows.sum())


def _block_rows(plan: Plan, i, top_k: int, tokens: int, block: int):
    """(local expert, assignment ids, token ids, valid) of block i; the
    rows past the block's real ones point at spare rows of their own."""
    with jax.named_scope("moe_dispatch"):
        spare = jnp.arange(block, dtype=jnp.int32)
        a = lax.dynamic_slice(plan.order, (plan.block_start[i],), (block,))
        valid = spare < plan.block_rows[i]
        tok = jnp.where(valid, a // top_k, tokens + spare)
        return plan.block_expert[i], a, tok, valid


def _loop_form(a, block: int):
    """(T, D) -> the form the loop moves rows of: `block` spare rows of
    zeros after the T, as slabs where D is whole lanes."""
    a = jnp.pad(a, [(0, block), (0, 0)])
    return moe_rows.pack(a) if moe_rows.slabs_fit(a.shape[1]) else a


def _tokens_form(a, tokens: int, width: int):
    """The loop's form -> (tokens, width)."""
    return (moe_rows.unpack(a, width) if a.ndim == 3 else a)[:tokens]


def _note_path(tokens: int, width: int, block: int) -> None:
    """Which movers this traced loop gets (trace time, once a loop)."""
    if not moe_rows.slabs_fit(width):
        moe_rows.note_moe_rows_path("reference", "row_not_lanes",
                                    (tokens, width, block))
    elif jax.default_backend() != "tpu":
        moe_rows.note_moe_rows_path("reference", "not_tpu",
                                    (tokens, width, block))
    else:
        moe_rows.note_moe_rows_path("pallas", "slabs")


# A block's `tok` is ascending too, but XLA is NOT told so: on a v5e its
# gather and scatter over (T, D) run 4.7 x slower with
# `indices_are_sorted=True` (PERF.md section 6, PR 34).
_UNIQUE = dict(unique_indices=True)


def _plain_gather(src, tok, n):
    """The rows past n come from the spare rows tok points at there."""
    return src.at[tok].get(**_UNIQUE)


def _plain_scatter_add(dst, upd, tok, n):
    """The zeros past n are added to the spare rows."""
    return dst.at[tok].add(upd, **_UNIQUE)


def _gather_rows(src, tok, n, width: int):
    """(block, width): src's rows tok[r] for r < n, zeros from n on."""
    if src.ndim == 2:
        return _plain_gather(src, tok, n)
    return moe_rows.unpack(lax.platform_dependent(
        src, tok, n, tpu=moe_rows.gather_rows, default=_plain_gather), width)


def _scatter_add_rows(dst, upd, tok, n):
    """dst with upd[r] (block, width) float32 added at row tok[r] for
    r < n."""
    if dst.ndim == 2:
        return _plain_scatter_add(dst, upd, tok, n)
    return lax.platform_dependent(
        dst, moe_rows.pack(upd), tok, n, tpu=moe_rows.scatter_add_rows,
        default=_plain_scatter_add)


def _hidden(xb, gate_e, up_e):
    hg = jnp.dot(xb, gate_e, preferred_element_type=jnp.float32)
    hu = jnp.dot(xb, up_e, preferred_element_type=jnp.float32)
    return hg, hu


KINDS = ("swiglu", "relu2")


@partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def expert_ffn(x, weights, gate, up, down, plan: Plan, top_k: int, block: int,
               kind: str = "swiglu"):
    """y (T, D) float32 = for every assignment (t, slot) to a held expert
    e, weights[t, slot] * Expert_e(x[t]) added at row t. x: (T, D) in the
    compute dtype, D the experts' own input width (the stream's, or the
    latent's); up: (E, D, F); down: (E, F, D); gate: (E, D, F) for the
    kind "swiglu", None for "relu2" (down(relu(up x)^2))."""
    return _expert_fwd(x, weights, gate, up, down, plan, top_k, block, kind)[0]


def _expert_fwd(x, weights, gate, up, down, plan, top_k, block, kind="swiglu",
                at=()):
    """`at`: leading indices of the held experts' matrices inside stacks
    of several layers' (the served hybrid decoder hands the whole stack
    and the layer's place in it, so that no layer's experts are copied
    out of the stack before the loop)."""
    T, D = x.shape
    dt = x.dtype
    if kind not in KINDS or (gate is None) != (kind == "relu2"):
        raise ValueError(f"expert kind {kind!r} (one of {KINDS}) with "
                         f"{'no' if gate is None else 'a'} gate matrix")
    _note_path(T, D, block)
    with jax.named_scope("moe_dispatch"):
        xp = _loop_form(x, block)
        y0 = jnp.zeros_like(xp, jnp.float32)
    w_flat = weights.reshape(-1)

    def body(i, y):
        e, a, tok, valid = _block_rows(plan, i, top_k, T, block)
        with jax.named_scope("moe_dispatch"):
            xb = _gather_rows(xp, tok, plan.block_rows[i], D)
            wb = jnp.where(valid, w_flat[a], 0.0)
        with jax.named_scope("moe_experts"):
            # the expert's matrices are rounded here, a block at a time:
            # a copy of every held expert in the compute dtype would
            # stand in HBM for the whole layer
            if kind == "swiglu":
                hg, hu = _hidden(xb, gate[(*at, e)].astype(dt), up[(*at, e)].astype(dt))
                h = (jax.nn.silu(hg) * hu).astype(dt)
            else:
                hu = jnp.dot(xb, up[(*at, e)].astype(dt),
                             preferred_element_type=jnp.float32)
                h = jnp.square(jax.nn.relu(hu)).astype(dt)
            ob = jnp.dot(h, down[(*at, e)].astype(dt),
                         preferred_element_type=jnp.float32)
        with jax.named_scope("moe_dispatch"):
            return _scatter_add_rows(y, wb[:, None] * ob, tok,
                                     plan.block_rows[i])

    y = lax.fori_loop(0, plan.n_blocks, body, y0)
    with jax.named_scope("moe_dispatch"):
        y = _tokens_form(y, T, D)
    return y, (x, weights, gate, up, down, plan)


def _expert_bwd(top_k, block, kind, saved, dy):
    x, weights, gate, up, down, plan = saved
    T, D = x.shape
    dt = x.dtype
    A = weights.size
    swiglu = kind == "swiglu"
    _note_path(T, D, block)
    with jax.named_scope("moe_dispatch"):
        xp = _loop_form(x, block)
        dyp = _loop_form(dy.astype(jnp.float32), block)
        dx0 = jnp.zeros_like(dyp)
    w_flat = weights.reshape(-1)
    spare = jnp.arange(block, dtype=jnp.int32)

    def body(i, carry):
        dx, dw, dgate, dup, ddown = carry
        e, a, tok, valid = _block_rows(plan, i, top_k, T, block)
        n = plan.block_rows[i]
        with jax.named_scope("moe_dispatch"):
            xb, dyb = _gather_rows(xp, tok, n, D), _gather_rows(dyp, tok, n, D)
            wb = jnp.where(valid, w_flat[a], 0.0)
        with jax.named_scope("moe_experts"):
            u16, d16 = up[e].astype(dt), down[e].astype(dt)
            if swiglu:
                g16 = gate[e].astype(dt)
                hg, hu = _hidden(xb, g16, u16)
                sg = jax.nn.sigmoid(hg)
                act = hg * sg
                h = (act * hu).astype(dt)
            else:
                hu = jnp.dot(xb, u16, preferred_element_type=jnp.float32)
                act = jax.nn.relu(hu)
                h = jnp.square(act).astype(dt)
            ob = jnp.dot(h, d16, preferred_element_type=jnp.float32)
            dwb = jnp.sum(dyb * ob, axis=-1)
            dob = (wb[:, None] * dyb).astype(dt)
            ddown_e = jnp.dot(h.T, dob, preferred_element_type=jnp.float32)
            dh = jnp.dot(dob, d16.T, preferred_element_type=jnp.float32)
            if swiglu:
                dhu = (dh * act).astype(dt)
                dhg = (dh * hu * sg * (1.0 + hg * (1.0 - sg))).astype(dt)
                dgate_e = jnp.dot(xb.T, dhg, preferred_element_type=jnp.float32)
                dup_e = jnp.dot(xb.T, dhu, preferred_element_type=jnp.float32)
                dxb = (jnp.dot(dhg, g16.T, preferred_element_type=jnp.float32)
                       + jnp.dot(dhu, u16.T, preferred_element_type=jnp.float32))
                dgate = dgate.at[e].add(dgate_e)
            else:
                dhu = (dh * 2.0 * act).astype(dt)
                dup_e = jnp.dot(xb.T, dhu, preferred_element_type=jnp.float32)
                dxb = jnp.dot(dhu, u16.T, preferred_element_type=jnp.float32)
            dup, ddown = dup.at[e].add(dup_e), ddown.at[e].add(ddown_e)
        with jax.named_scope("moe_dispatch"):
            dx = _scatter_add_rows(dx, dxb, tok, n)
            dw = dw.at[jnp.where(valid, a, A + spare)].set(
                dwb, unique_indices=True)
        return dx, dw, dgate, dup, ddown

    init = (dx0, jnp.zeros((A + block,), jnp.float32),
            jnp.zeros(gate.shape, jnp.float32) if swiglu else None,
            jnp.zeros(up.shape, jnp.float32), jnp.zeros(down.shape, jnp.float32))
    dx, dw, dgate, dup, ddown = lax.fori_loop(0, plan.n_blocks, body, init)
    with jax.named_scope("moe_dispatch"):
        dx = _tokens_form(dx, T, D)
    return (dx.astype(dt), dw[:A].reshape(weights.shape).astype(weights.dtype),
            dgate.astype(gate.dtype) if swiglu else None, dup.astype(up.dtype),
            ddown.astype(down.dtype), None)


expert_ffn.defvjp(_expert_fwd, _expert_bwd)


def moe_apply(params: Dict, bias, x, real, cfg, at=(), router_x=None,
              router_state=None):
    """The routed part of an expert layer over x: (T, D); `real` (T,)
    marks the tokens that are not padding: a pad token is routed
    nowhere and counted nowhere (every pad has the same input, so they
    would all fall on the same experts). Returns (y (T, D) in x's dtype,
    stats): `load` counts all `n_routed_experts` (what the balance bias
    follows), `held_counts` / `dropped` are the step's counters for this
    chip's share, `block_rows` the rows of the blocks they filled (held
    assignments over it: the share of a block's rows that are real and
    moved), `ids` the experts chosen (`n_routed_experts` at a pad).
    With `at` the experts' matrices are stacks of several layers' and
    this layer's lie at those leading indices: forward only. The router
    reads `router_x` (x where None); the MLP router (`cfg.router` "mlp")
    also the previous layer's `router_state`, and its own is `stats["router_state"]`.
    With `cfg.moe_latent_size` the experts read x `to_latent` and their
    sum comes back `from_latent`; the router reads x itself."""
    stats = {}
    if cfg.router == "mlp":
        ids, weights, stats["router_state"] = route_mlp(
            x if router_x is None else router_x, params["router"],
            router_state, bias, cfg.num_experts_per_tok, cfg.rms_norm_eps)
    else:
        ids, weights = route(x if router_x is None else router_x,
                             params["router"], bias, cfg.num_experts_per_tok,
                             cfg.routed_scaling_factor, cfg.norm_topk_prob,
                             cfg.n_group, cfg.topk_group)
    ids = jnp.where(real[:, None], ids, cfg.n_routed_experts)
    plan = plan_dispatch(ids, cfg.experts_held, cfg.expert_offset,
                         cfg.expert_block)
    experts, dt = params["experts"], x.dtype
    latent = cfg.moe_latent_size is not None
    if latent:
        with jax.named_scope("moe_latent"):
            x = x @ params["to_latent"].astype(dt)
    operands = (x, weights, experts.get("gate"), experts["up"], experts["down"],
                plan, cfg.num_experts_per_tok, cfg.expert_block, cfg.expert_kind)
    y = _expert_fwd(*operands, at=at)[0] if at else expert_ffn(*operands)
    if latent:
        with jax.named_scope("moe_latent"):
            y = jnp.dot(y.astype(dt), params["from_latent"].astype(dt),
                        preferred_element_type=jnp.float32)
    with jax.named_scope("moe_router"):
        load = jnp.zeros((cfg.n_routed_experts + 1,), jnp.int32).at[
            ids.reshape(-1)].add(1)[:-1]
    stats.update(load=load, held_counts=plan.held_counts, dropped=plan.dropped,
                 ids=ids, block_rows=plan.n_blocks * cfg.expert_block)
    return y.astype(dt), stats
