"""Pretraining of the causal expert decoder on packed token documents,
through `train.trainer.pretrain`.

As `drivers/pretrain.py` times ProteinBERT: set-up makes the train state
on the device from the seed, drives the program's one compiled
`train_step` through its first three steps on the feed's first three
batches (reading what the comparison needs), and hands that same state,
step and feed to `trainer.pretrain`, which the window times from a device
sync to the trainer's own drained return, closed by SIGTERM.

The feed is the program's own pipeline: `TokenDocumentDataset` ->
`make_packed_iterator` (the `PackPlanner`, first-fit) -> the trainer's
prefetch thread. Documents: every block the mix's fixed multiset of
lengths in another order, the orders one draw for every seed where the
mix states an `order_seed`; ids Zipf-distributed over the vocabulary slice
under a seeded permutation. A residue of this model is a TOKEN:
`train_residues_per_s` counts the real (non-pad) positions of the steps
completed, batch by batch as the feed handed them out.

`correct`: `compare.training_checks` against
`benchmark/reference/glm4_moe_lite_f32.py` following the same three
steps, plus `route_mismatch_share`, the share of the first step's
(token, slot) assignments in which program and reference chose another
expert, read from the experts that the first `train_step` itself reports
it chose (`metrics["route_ids"]`), and the parameter count the
configuration file states. Every compared number comes from the one compiled `train_step`
that the window then times. A dropped assignment counts in `failed`.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import compare, lm_flops, traffic
from benchmark.device import memory_peak_bytes
from benchmark.drivers.preempt import (
    first_gradient, program_readings, timed_pretrain,
)
from benchmark.drivers.pretrain import (
    CHECKED_STEPS, optimizer_sizes, product_operands,
)
from benchmark.reference import glm4_moe_lite_f32 as ref

# configuration file key -> DecoderConfig field, where the names differ
RENAMED = {"n_routed_experts": "experts_held", "router_width": "n_routed_experts"}
SIZES = ("vocab_size", "hidden_size", "num_hidden_layers", "first_k_dense_replace",
         "intermediate_size", "moe_intermediate_size", "n_routed_experts",
         "router_width", "n_shared_experts", "num_experts_per_tok",
         "routed_scaling_factor", "norm_topk_prob", "num_attention_heads",
         "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "rope_theta", "rms_norm_eps", "num_nextn_predict_layers",
         "dtype", "param_dtype")
ASSUMED = ("mtp_loss_weight", "bias_update_speed", "init_std", "expert_offset")


def cell_config(workload: dict, config: dict):
    """The program's config object as this cell runs it; refuses a file
    whose sizes are not the program's."""
    from benchmark.program import _replace
    from proteinbert_tpu.configs.config import get_preset

    cfg = get_preset(config["preset"])
    for path, value in {
            **config.get("overrides", {}),
            "data.batch_size": workload["rows"], "data.seq_len": workload["seq_len"],
            "data.pack_max_segments": workload["max_segments"],
            "data.pack_open_bins": workload["open_bins"],
            "train.max_steps": 2 ** 31 - 1, **workload.get("overrides", {})}.items():
        cfg = _replace(cfg, path, value)
    for key in SIZES:
        runs = getattr(cfg.model, RENAMED.get(key, key))
        if runs != config[key]:
            raise SystemExit(f"configuration file says {key}={config[key]!r}, "
                             f"the program runs {runs!r}")
    for key, value in config["optimizer"].items():
        if getattr(cfg.optimizer, key) != value:
            raise SystemExit(f"configuration file says optimizer.{key}={value!r}, "
                             f"the program runs {getattr(cfg.optimizer, key)!r}")
    return cfg


def reference_sizes(config: dict, cfg) -> dict:
    """The configuration as the reference and `lm_flops` take it: the
    file's published key names, the router's width under
    `n_routed_experts`, this chip's share under `experts_held`, and the
    values the file lists as assumed, read from the program's config."""
    c = {k: config[k] for k in SIZES if k not in ("dtype", "param_dtype")}
    c["experts_held"], c["n_routed_experts"] = c["n_routed_experts"], c.pop("router_width")
    c.update({k: getattr(cfg.model, k) for k in ASSUMED})
    return c


def documents(mix: dict, n_blocks: int, seed: int) -> list:
    """n_blocks x block documents of token ids: each block the mix's
    lengths in an order of its own; ids drawn with probability
    proportional to rank ** -exponent, ranks laid over the vocabulary
    slice by a permutation of the seed's own.

    Where the mix states an `order_seed` (the rehearsal's states none, by
    null), the orders are drawn from that number and not from the run's
    seed: the packer turns the order of the lengths into the rows' segment
    layout, and the attention core walks only the tiles inside a segment
    (a third of a step), so another order is another amount of work. Every
    seed then trains on the same layout with ids and weights of its own."""
    rng = np.random.default_rng([seed, 1])
    order = (np.random.default_rng([mix["order_seed"], 0])
             if mix.get("order_seed") is not None else rng)
    spec = mix["ids"]
    base = traffic.block_lengths(mix)
    lengths = np.concatenate([order.permutation(base) for _ in range(n_blocks)])
    weights = np.arange(1, spec["vocab_size"] + 1, dtype=np.float64) ** -spec["zipf_exponent"]
    ranks = np.searchsorted(np.cumsum(weights / weights.sum()),
                            rng.random(int(lengths.sum())), side="right")
    ids = rng.permutation(spec["vocab_size"])[
        np.minimum(ranks, spec["vocab_size"] - 1)].astype(np.int32)
    return np.split(ids, np.cumsum(lengths)[:-1])


class Feed:
    """The benchmark's iterator over the program's data pipeline: keeps
    the first batches for the reference and counts, batch by batch, the
    real positions and the (query, key) pairs inside the segments."""

    def __init__(self, source):
        self._source = source
        self.kept, self.real, self.pairs = [], [], []

    def __iter__(self):
        return self

    def __next__(self):
        import jax

        with jax.profiler.TraceAnnotation("benchmark.feed.next"):
            batch = next(self._source)
        if len(self.kept) < CHECKED_STEPS:
            self.kept.append(batch)
        seg = batch["segment_ids"]
        self.real.append(int((seg > 0).sum()))
        self.pairs.append(segment_pairs(seg))
        return batch


def segment_pairs(segment_ids: np.ndarray) -> int:
    """(query, key) pairs that are causal and in one segment."""
    total = 0
    for row in segment_ids:
        n = np.bincount(row[row > 0]).astype(np.int64)
        total += int((n * (n + 1) // 2).sum())
    return total


def make_feed(run, cfg):
    from proteinbert_tpu.data.dataset import TokenDocumentDataset
    from proteinbert_tpu.data.packing import make_packed_iterator

    wl = run.workload
    docs = documents(run.mix, wl["dataset_blocks"], run.seed)
    dataset = TokenDocumentDataset(docs, wl["seq_len"])
    return Feed(make_packed_iterator(
        dataset, wl["rows"], seed=0, shuffle=False,
        max_segments=cfg.data.pack_max_segments, max_open=cfg.data.pack_open_bins))


def _without_bias(tree):
    return {k: v for k, v in tree.items() if k != "balance_bias"}


def route_mismatch_share(program_ids, reference_ids, segment_ids) -> float:
    """Share of the program's (real token, slot) assignments whose expert
    is not among those the reference chose for that token in that layer.
    Both: (rows, layers + module, L, k)."""
    program_ids, reference_ids = np.asarray(program_ids), np.asarray(reference_ids)
    real = np.asarray(segment_ids)[:, None, :, None] > 0
    shared = (program_ids[..., :, None] == reference_ids[..., None, :]).any(-1)
    return float((~shared & real).sum()
                 / max(1, np.broadcast_to(real, shared.shape).sum()))


def gaps_against(program: dict, reference: dict, first_segments,
                 dir_gaps=None) -> dict:
    """Every number of the comparison: `compare.training_checks` and the
    routing of the first step. `program` is the program's readings or,
    for a control, the reference's own in its place; both carry
    `first_ids`. `dir_gaps`: as `compare.training_checks` takes them."""
    gaps = compare.training_checks(program, reference, dir_gaps)
    gaps["route_mismatch_share"] = route_mismatch_share(
        program["first_ids"], reference["first_ids"], first_segments)
    return gaps


def limit_checks(gaps: dict, workload: dict) -> list:
    """[(name, value, limit)] of the numbers the cell compares."""
    return [(name, gaps[name], workload["limits"][name]) for name in sorted(gaps)
            if name not in workload.get("not_compared", ())]


def follow_reference(run, batches, c: dict, precision: str = "f32") -> dict:
    """The reference over the cell's checked steps."""
    return ref.follow_steps(run.seed, batches, c, optimizer_sizes(run.config),
                            precision=precision,
                            operands=product_operands(run.config))


def run(run, devices):
    import jax

    from proteinbert_tpu.models import glm_moe
    from proteinbert_tpu.train import train_state as ts

    wl, mix = run.workload, run.mix
    rows, seq_len = wl["rows"], wl["seq_len"]
    if mix["lengths"]["max"] > seq_len or mix["ids"]["vocab_size"] != run.config["vocab_size"]:
        raise SystemExit("the mix's longest document and its ids have to fit the cell")
    cfg = cell_config(wl, run.config)
    c = reference_sizes(run.config, cfg)
    stated = run.config["parameters"]
    if not glm_moe.param_count(cfg.model) == lm_flops.param_count(c) == stated:
        raise SystemExit(
            f"configuration file states {stated} parameters, the program has "
            f"{glm_moe.param_count(cfg.model)}, lm_flops counts {lm_flops.param_count(c)}")
    print(f"decoder: {stated / 1e6:.1f} M parameters on this chip, "
          f"{16 * stated / 2 ** 30:.2f} GiB of state")

    feed = make_feed(run, cfg)
    make_state = jax.jit(ts.create_train_state, static_argnames="cfg")
    state = make_state(ref.seed_key(run.seed), cfg)
    start = jax.device_get(state.params)
    losses, first_grad, first_ids, dropped = [], None, None, 0.0
    for _ in range(CHECKED_STEPS):
        state, metrics = ts.train_step(state, next(feed), cfg)
        losses.append(metrics["loss"])
        dropped += float(metrics["dropped_assignments"])
        if first_grad is None:
            first_grad = _without_bias(first_gradient(state, cfg.optimizer.b1))
            # (layers + module, rows x L, k) -> (rows, layers + module, L, k)
            ids = np.asarray(metrics["route_ids"])
            first_ids = ids.reshape(ids.shape[0], rows, seq_len, -1).transpose(1, 0, 2, 3)
    end = jax.device_get(state.params)
    program = program_readings(losses, first_grad, _without_bias(start),
                               _without_bias(end))
    program["first_ids"] = first_ids
    program_bias = end["balance_bias"]
    del start, end
    out, steps = timed_pretrain(run, cfg, feed, state)
    timed = slice(CHECKED_STEPS, CHECKED_STEPS + steps)
    real, pairs = feed.real[timed], feed.pairs[timed]
    counters = [{k: h[k] for k in ("expert_load_max_over_mean", "routed_here_share",
                                   "dropped_assignments", "assignments_held")}
                for h in out["history"] if "assignments_held" in h]
    dropped += sum(h["dropped_assignments"] for h in counters)
    final_loss = float(out["history"][-1]["loss"]) if out["history"] else 0.0
    memory_peak = memory_peak_bytes(devices)
    del out, state
    gc.collect()    # the trainer's closures may hold the last state in a cycle
    held = (devices[0].memory_stats() or {}).get("bytes_in_use", 0)
    print(f"on the device before the reference: {held / 2 ** 30:.2f} GiB")

    t_ref = time.perf_counter()
    reference = follow_reference(run, feed.kept, c)
    print(f"reference: {CHECKED_STEPS} steps in {time.perf_counter() - t_ref:.1f} s")
    t_cmp = time.perf_counter()
    dir_gaps = compare.leaf_dir_gaps(program["first_grad"], reference["first_grad"])
    gaps = gaps_against(program, reference, feed.kept[0]["segment_ids"], dir_gaps)
    checks = limit_checks(gaps, wl)
    checks.append(("param_count", float(glm_moe.param_count(cfg.model)), float(stated)))
    for name in wl.get("not_compared", ()):
        print(f"not compared {name}: {gaps[name]:.6g}")
    spread = compare.spread_of(dir_gaps)
    print("first gradient, gap by leaf: median {:.6g}, 75 % {:.6g}, 90 % {:.6g}, "
          "widest {:.6g}".format(*spread))
    print(f"comparison: {time.perf_counter() - t_cmp:.1f} s", flush=True)
    bias_off = max(float(np.abs(np.asarray(program_bias[k]) - reference["bias"][k]).max())
                   for k in program_bias)
    print(f"balance bias after {CHECKED_STEPS} steps, widest gap from the "
          f"reference's: {bias_off:.6g} (one update is {c['bias_update_speed']:g})")

    # Operations of a mean step of the window, from what its batches held
    # and what its routing sent here (the counters fetched at the log cadence).
    held = (float(np.mean([h["assignments_held"] for h in counters])) if counters
            else lm_flops.expected_assignments(c, float(np.mean(real))))
    return {
        "e2e": {"train_residues_per_s": sum(real) / run.window_s},
        "attempted": steps,
        "failed": int(dropped) if np.isfinite(final_loss) else steps,
        "checks": checks,
        "memory_peak_bytes": int(memory_peak),
        "obs": {
            "steps": steps, "program": "train_step",
            "grad_dir_by_leaf": spread,
            "call_flops": lm_flops.train_flops(
                c, float(np.mean(real)), float(np.mean(pairs)), held),
            "call_min_bytes": lm_flops.train_min_bytes(c, rows * seq_len),
            "counters": counters,
            "moe_experts_flops": lm_flops.moe_experts_flops(c, held),
            "moe_experts_min_bytes": lm_flops.moe_experts_min_bytes(c),
        },
    }


def cell_program(workload: dict, config: dict):
    """(jitted function, abstract arguments, static keyword arguments) of
    the program the window times, for `benchmark.rehearse`."""
    import jax
    import jax.numpy as jnp

    from proteinbert_tpu.train import train_state as ts

    cfg = cell_config(workload, config)
    state = jax.eval_shape(
        lambda k: ts.create_train_state(k, cfg), jax.random.PRNGKey(0))
    shape = jax.ShapeDtypeStruct((workload["rows"], workload["seq_len"]), jnp.int32)
    return ts.train_step, (state, {"tokens": shape, "segment_ids": shape}), {"cfg": cfg}
