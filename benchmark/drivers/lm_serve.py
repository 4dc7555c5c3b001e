"""Ragged packed serving of the causal hybrid decoder, in process, through
`serve.server.Server.submit`.

As `drivers/serve.py` times ProteinBERT's `embed`: set-up makes the
weights on the device from the seed (leaf by leaf, bfloat16), boots the
server (which warms its packed executable at every row class), and sends
one block of warm-up documents through the whole path. The generator
(one thread of this process) then offers `embed` requests open loop at
the mix's fixed rate from the window's first instant, each timed from
when it was due, exactly as `drivers/serve.py` does: the window opens on
an idle server, and `embed_residues_per_s` is that cell's count, the
tokens of the documents answered inside the window over the window. A
request is one document of token ids: every block the mix's fixed
multiset of lengths in another order; ids Zipf-distributed over the
vocabulary slice under a seeded permutation. A residue of this model is a
TOKEN.

`correct`: a seeded sample of the window's answers, the longest answered
document among them, each against `benchmark/reference/
bailing_hybrid_f32.py` run on that document ALONE (so neither state,
taps nor keys can have crossed a boundary of the packed row):
`compare.embedding_checks` over the last-token vector (`global`) and the
mean vector (`local_mean`), plus the batches' own count of assignments no
block took and the parameter count the configuration file states.
"""

from __future__ import annotations

import contextlib
import gc
import time
from types import SimpleNamespace

import numpy as np

from benchmark import compare, hybrid_flops, traffic
from benchmark.device import memory_peak_bytes
from benchmark.drivers.serve import (
    _aborted, _capture_telemetry, _Load, _print_pace, _print_window,
)
from benchmark.reference import bailing_hybrid_f32 as ref

PROGRAM = "_packed_decoder_embed_batch"
# configuration file key -> DecoderConfig field, where the names differ
RENAMED = {"num_experts": "experts_held", "router_width": "n_routed_experts",
           "num_shared_experts": "n_shared_experts", "head_dim": "kda_head_dim"}
SIZES = ("vocab_size", "hidden_size", "num_hidden_layers", "first_k_dense_replace",
         "first_layer_index", "layer_group_size", "intermediate_size",
         "moe_intermediate_size", "num_experts", "router_width",
         "num_shared_experts", "num_experts_per_tok", "n_group", "topk_group",
         "routed_scaling_factor", "norm_topk_prob", "num_attention_heads",
         "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "head_dim", "short_conv_kernel_size", "kda_lower_bound",
         "rope_theta", "rope_interleave", "rms_norm_eps", "dtype", "param_dtype")
ASSUMED = ("init_std", "embed_init_std", "out_init_std", "expert_offset")
LIMIT_LISTS = ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list")


def cell_config(workload: dict, config: dict):
    """The program's config object as this cell runs it; refuses a file
    whose sizes are not the program's, and one whose held layers clamp
    their SwiGLU (the program has no clamp: a limit other than 0 would be
    ignored, so it is refused)."""
    from benchmark.program import _replace
    from proteinbert_tpu.configs.config import get_preset

    cfg = get_preset(config["preset"])
    for path, value in {**config.get("overrides", {}),
                        **workload.get("overrides", {})}.items():
        cfg = _replace(cfg, path, value)
    for key in SIZES:
        runs = getattr(cfg.model, RENAMED.get(key, key))
        if runs != config[key]:
            raise SystemExit(f"configuration file says {key}={config[key]!r}, "
                             f"the program runs {runs!r}")
    m = cfg.model
    if (config["moe_shared_expert_intermediate_size"]
            != m.n_shared_experts * m.moe_intermediate_size
            or config.get("qk_head_dim", m.qk_head_dim) != m.qk_head_dim):
        raise SystemExit("configuration file's shared expert or qk_head_dim is "
                         "not the program's")
    first = config["first_layer_index"]
    for name in LIMIT_LISTS:
        held = config[name][first:first + config["num_hidden_layers"]]
        if any(held):
            raise SystemExit(
                f"{name}: a layer held here (published {first} to "
                f"{first + len(held) - 1}) has a limit other than 0 ({held}); "
                "the program has no SwiGLU clamp")
    return cfg


def reference_sizes(config: dict, cfg) -> dict:
    """The configuration as the reference and `hybrid_flops` take it."""
    c = {RENAMED.get(k, k): config[k] for k in SIZES
         if k not in ("dtype", "param_dtype")}
    c.update({k: getattr(cfg.model, k) for k in ASSUMED})
    return c


def documents(mix: dict, n_blocks: int, seed: int, stream: int = 1) -> list:
    """n_blocks x block documents of token ids (`stream` tells apart
    draws that must differ, as warm-up and window)."""
    rng = np.random.default_rng([seed, stream])
    spec = mix["ids"]
    base = traffic.block_lengths(mix)
    lengths = np.concatenate([rng.permutation(base) for _ in range(n_blocks)])
    weights = np.arange(1, spec["vocab_size"] + 1, dtype=np.float64) ** -spec["zipf_exponent"]
    ranks = np.searchsorted(np.cumsum(weights / weights.sum()),
                            rng.random(int(lengths.sum())), side="right")
    ids = rng.permutation(spec["vocab_size"])[
        np.minimum(ranks, spec["vocab_size"] - 1)].astype(np.int32)
    return np.split(ids, np.cumsum(lengths)[:-1]), lengths


def run(run, devices):
    """One run of the cell: the window, then the comparison of a sample
    of its answers with the plain reference."""
    out, sample = measure(run, devices)
    gc.collect()
    held = (devices[0].memory_stats() or {}).get("bytes_in_use", 0)
    print(f"on the device before the reference: {held / 2 ** 30:.2f} GiB")
    t_ref = time.perf_counter()
    reference = ref.embed_documents(run.seed, sample["docs"], sample["c"])
    print(f"reference: {len(reference)} documents, "
          f"{sum(len(d) for d in sample['docs'])} tokens, the longest "
          f"{max(len(d) for d in sample['docs'])}, in "
          f"{time.perf_counter() - t_ref:.1f} s")
    for key in ("global", "local_mean"):
        errs = document_errors(sample["served"], reference, key)
        print(f"{key} error by document (tokens: error): " + ", ".join(
            f"{len(d)}: {e:.5f}" for d, e in zip(sample["docs"], errs)))
    out["checks"] += limit_checks(gaps(sample["served"], reference), run.workload)
    return out


def gaps(served: list, reference: list) -> dict:
    """`compare.embedding_checks`'s six numbers and, for each vector, the
    FIRST QUARTILE over the sample of an answer's error. A routed model's
    answer is not continuous in its arithmetic: where a token's eighth
    and ninth expert score within a rounding of each other the choice
    flips, and a flip between a held and an absent expert moves that
    token's vector by 0.005 to 0.1 whatever the precision. On the chip a
    quarter of a sample's last-token vectors read 0.004 and more against
    a floor of 0.0007-0.0013, with int8 products a half against a floor
    of 0.0051-0.0056 (PERF.md section 2). The rms and the maximum over a
    sample read those flips, and the median still moves with their
    number; the first quartile reads the arithmetic of the documents
    without one, and is the number that tells bfloat16 products from
    int8."""
    out = compare.embedding_checks(served, reference)
    for key in ("global", "local_mean"):
        out[f"{key}_rel_err_q1"] = float(np.percentile(
            document_errors(served, reference, key), 25))
    return out


def document_errors(served: list, reference: list, key: str) -> list:
    """Each answer's error: the norm of its difference from the
    reference's over the reference's norm."""
    return [float(np.linalg.norm(np.asarray(a[key], np.float64) - b[key])
                  / np.linalg.norm(np.asarray(b[key], np.float64)))
            for a, b in zip(served, reference)]


def limit_checks(gaps: dict, workload: dict) -> list:
    return [(name, gaps[name], workload["limits"][name]) for name in sorted(gaps)]


def measure(run, devices):
    """Set-up and the measured window, with the server closed and its
    state freed on return: (the run's result with the checks that need no
    reference, the sample of the window's answers the reference is held
    against)."""
    with serving(run) as boot:
        seen = offer(run, devices, boot)
    return account(run, boot, seen)


@contextlib.contextmanager
def serving(run):
    """Set-up: the weights on the device, the server booted and warm, one
    block of warm-up documents through the whole path; the server is
    closed on the way out. The knee sweep (`benchmark.find_lm_knee`)
    opens one window after another on what this yields."""
    from proteinbert_tpu.models import glm_moe
    from proteinbert_tpu.serve.server import Server

    wl, mix = run.workload, run.mix
    cfg = cell_config(wl, run.config)
    c = reference_sizes(run.config, cfg)
    stated = run.config["parameters"]
    if mix["lengths"]["max"] > cfg.data.seq_len or mix["ids"]["vocab_size"] != c["vocab_size"]:
        raise SystemExit("the mix's longest document and its ids have to fit the cell")
    if not glm_moe.served_param_count(cfg.model) == hybrid_flops.param_count(c) == stated:
        raise SystemExit(
            f"configuration file states {stated} parameters, the program has "
            f"{glm_moe.served_param_count(cfg.model)}, hybrid_flops counts "
            f"{hybrid_flops.param_count(c)}")
    itemsize = np.dtype(cfg.model.param_dtype).itemsize
    print(f"decoder: {stated / 1e6:.1f} M parameters on this chip, "
          f"{itemsize * stated / 2 ** 30:.2f} GiB in {cfg.model.param_dtype}")
    warm_docs, _ = documents(mix, wl["warm_blocks"], run.seed, stream=4)

    params = glm_moe.init_served(ref.seed_key(run.seed), cfg.model)
    tele = _capture_telemetry() if run.trace else None
    server = Server(params, cfg, warm_kinds=("embed",), telemetry=tele,
                    trace_sample_rate=1.0 if run.trace else None, **wl["server"])
    del params
    server.start()
    boot = SimpleNamespace(
        server=server, tele=tele, c=c, stated=stated,
        served_params=glm_moe.served_param_count(cfg.model),
        ladder_rows=sorted(int(k) for k in server.dispatcher.batch_classes),
        counted_before=server.stats()["batched_rows"], submitted=0)
    try:
        for f in [server.submit("embed", d) for d in warm_docs]:
            f.result(timeout=600)
        boot.submitted = len(warm_docs)
        yield boot
    finally:
        server.close(drain=False)


def offer(run, devices, boot):
    """One window on a booted server: the mix's load from the window's
    first instant for `run.seconds`. -> what `account` reads."""
    wl, mix, server = run.workload, run.mix, boot.server
    n_blocks = traffic.blocks_for(mix, run.seconds)
    docs, lengths = documents(mix, n_blocks, run.seed)
    due = traffic.due_times(mix, n_blocks, run.seed)
    # The scheduler counts a batch after it has answered its riders: wait
    # until the last batch of what went before is counted.
    before = server.stats()
    patience = time.perf_counter() + 30.0
    while (before["batched_rows"] - boot.counted_before < boot.submitted
           and time.perf_counter() < patience):
        time.sleep(0.01)
        before = server.stats()
    if boot.tele is not None:
        boot.tele.stages.clear()
    load = _Load(server, docs, due, run.seconds)
    with run.window():
        load.start()
        load.join()
        time.sleep(max(0.0, run.seconds - (time.perf_counter() - load.t0)))
        # Read before the window closes: a traced run then spends
        # seconds on its trace while the server goes on dispatching.
        after = server.stats()
        stages = list(boot.tele.stages) if boot.tele is not None else None
    memory_peak = memory_peak_bytes(devices)
    if wl["judged"] == "latency":
        deadline = time.perf_counter() + wl["straggler_seconds"]
        for f in load.futures:
            if f is not None and not f.done():
                try:
                    f.result(timeout=max(0.0, deadline - time.perf_counter()))
                except Exception:
                    pass
    boot.submitted += len(load.futures)
    return SimpleNamespace(
        load=load, docs=docs, lengths=lengths, due=due, before=before,
        after=after, stages=stages, memory_peak=memory_peak,
        closed=time.perf_counter() - load.t0)


def account(run, boot, seen):
    """The run's result from what a window saw, counted as
    `drivers/serve.measure` counts ProteinBERT's."""
    wl, c, load = run.workload, boot.c, seen.load
    before, after = seen.before, seen.after
    n = len(load.futures)
    ok = np.array([f is not None and f.done() and f.exception() is None
                   for f in load.futures])
    done_at = np.array([load.done.get(i, np.inf) for i in range(n)])
    due_n, lengths_n = seen.due[:n], seen.lengths[:n]
    in_window = ok & (done_at <= run.seconds)
    errors = sum(1 for f in load.futures
                 if f is None or (f.done() and f.exception() is not None
                                  and not _aborted(f)))
    latency = np.where(ok, done_at, seen.closed) - due_n
    e2e = {}
    if wl["judged"] == "latency":
        failed = int(n - ok.sum())
        e2e["embed_latency_p95_ms"] = float(np.percentile(latency, 95) * 1e3)
    else:
        failed = int(errors)
        e2e["embed_residues_per_s"] = float(
            lengths_n[in_window].sum() / run.window_s)

    pool = np.flatnonzero(in_window if in_window.any() else ok)
    rng = np.random.default_rng([run.seed, 5])
    pick = set(rng.choice(pool, min(wl["sample"] - 1, len(pool)),
                          replace=False).tolist())
    pick.add(int(pool[np.argmax(lengths_n[pool])]))
    pick = sorted(pick)
    sample = {"docs": [seen.docs[i] for i in pick], "c": c,
              "served": [load.futures[i].result() for i in pick]}

    # The batches the window counted and what they held: a batch is
    # counted with its riders, and riders are answered in the order of
    # their batches, so they are the first to have completed.
    batches = after["batches"] - before["batches"]
    riders = after["batched_rows"] - before["batched_rows"]
    first = [i for i in sorted(load.done, key=load.done.get)[:riders] if ok[i]]
    tokens_in_batches = int(sum(lengths_n[i] for i in first))
    pairs = float(sum(int(lengths_n[i]) * (int(lengths_n[i]) + 1) // 2 for i in first))
    routing = {k: after["routing"][k] - before["routing"][k] for k in after["routing"]}
    class_counts = {
        int(k): int(v) - int(before["batch_class_counts"].get(k, 0))
        for k, v in after["batch_class_counts"].items()}
    class_counts = {k: v for k, v in sorted(class_counts.items()) if v}
    positions = int(after["batched_positions"] - before["batched_positions"])
    kda_layers, _, _, moe_layers = hybrid_flops.layer_counts(c)
    # What the mathematics needs for the window's batches, as a mean
    # batch: every class is given the same, so that batches x mean is the
    # window's total whatever classes ran.
    per_batch = 1.0 / max(batches, 1)
    needed = hybrid_flops.forward_flops(
        c, tokens_in_batches, pairs, routing["assignments_held"]) * per_batch
    obs = {
        "program": PROGRAM,
        "batches": batches,
        "residues_in_batches": tokens_in_batches,
        "batch_class_counts": class_counts,
        "batched_positions": positions,
        "requests_in_window": int(in_window.sum()),
        "residues_in_window": int(lengths_n[in_window].sum()),
        "latency_s": latency,
        "due_s": due_n,
        "seconds": run.seconds,
        "late_s": np.asarray(load.sent) - due_n[:len(load.sent)],
        "stages": seen.stages,
        "classes": {cls: {"flops": needed} for cls in boot.ladder_rows},
        "class_program": lambda cls: cell_program(wl, run.config, rows=cls),
        "routing": dict(routing, expert_layers=moe_layers,
                        top_k=c["num_experts_per_tok"]),
        # one KDA layer's core over a mean batch of the window, times the
        # KDA layers of a run (what the `kda_core` scope sums)
        "kda_core_flops": kda_layers * hybrid_flops.kda_core_flops(
            c, tokens_in_batches * per_batch),
        "kda_core_min_bytes": kda_layers * hybrid_flops.kda_core_min_bytes(
            c, tokens_in_batches * per_batch),
    }
    _print_pace(np.sort(done_at[in_window]), load, due_n, run.seconds)
    _print_window(n, obs, run)
    print(f"routing: {routing}")
    return {
        "e2e": e2e,
        "attempted": n,
        "failed": failed,
        "checks": [("dropped_assignments", float(routing["dropped_assignments"]), 0.0),
                   ("param_count", float(boot.served_params), float(boot.stated))],
        "memory_peak_bytes": int(seen.memory_peak),
        "obs": obs,
    }, sample


def cell_program(workload: dict, config: dict, rows=None):
    """(jitted function, abstract arguments, static keyword arguments) of
    the program the window times at its largest row class, or at `rows`:
    for `benchmark.rehearse` and for the scope map of each class."""
    import jax
    import jax.numpy as jnp

    from proteinbert_tpu import inference
    from proteinbert_tpu.models import glm_moe

    cfg = cell_config(workload, config)
    rows = rows or workload["server"]["max_batch"]
    grid = jax.ShapeDtypeStruct((rows, cfg.data.seq_len), jnp.int32)
    ann = jax.ShapeDtypeStruct(
        (rows, workload["server"]["pack_max_segments"], 0), jnp.float32)
    return (inference._packed_decoder_embed_batch,
            (glm_moe.served_abstract(cfg.model), grid, grid, ann),
            {"cfg": cfg.model})
