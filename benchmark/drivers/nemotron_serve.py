"""Ragged packed serving of Nemotron 3 (`nemotron_h`: Mamba-2, attention
over grouped keys and LatentMoE layers, one sublayer a layer), in process,
through `serve.server.Server.submit`.

`drivers/lm_serve.py` with another model behind the same server, as
`drivers/cca_serve.py` is: the load generator and its window (`offer`),
the documents (`documents`), the comparison's numbers (`lm_serve.gaps`,
`document_errors`, `limit_checks`) and `drivers/serve.py`'s printed lines
are THAT driver's, imported; what is this file's own is what names the
model: the configuration's keys (`cell_config`, `reference_sizes`), the
reference (`benchmark/reference/nemotron_h_f32.py`), the operations a
batch needs (`benchmark/nemotron_flops.py`) and what the new readers read.
The window opens on an idle server with the load, and
`embed_residues_per_s` is the ONE count the serving cells have: the tokens
of the documents answered inside the window over the window.

`correct`: a seeded sample of the window's answers, the longest answered
document among them, each against the reference run on that document
ALONE (so neither the recurrence's state, a convolution's tap nor a key
can have crossed a boundary of the packed row), plus the batches' own
count of assignments no block took and the parameter count the
configuration file states.
"""

from __future__ import annotations

import contextlib
import gc
import time
from types import SimpleNamespace

import numpy as np

from benchmark import nemotron_flops
from benchmark.drivers import lm_serve
from benchmark.drivers.lm_serve import (
    document_errors, documents, limit_checks, offer,
)
from benchmark.drivers.serve import (
    _aborted, _capture_telemetry, _print_pace, _print_window,
)
from benchmark.reference import nemotron_h_f32 as ref

PROGRAM = "_packed_decoder_embed_batch"
# configuration file key -> DecoderConfig field, where the names differ
RENAMED = {"n_routed_experts": "experts_held", "router_width": "n_routed_experts",
           "head_dim": "cca_head_dim", "layer_norm_epsilon": "rms_norm_eps"}
SIZES = ("vocab_size", "hidden_size", "num_hidden_layers", "first_layer_index",
         "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
         "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "n_routed_experts", "router_width", "expert_offset", "n_shared_experts",
         "num_experts_per_tok", "n_group", "topk_group", "norm_topk_prob",
         "routed_scaling_factor", "moe_latent_size", "moe_intermediate_size",
         "moe_shared_expert_intermediate_size", "mlp_hidden_act",
         "layer_norm_epsilon", "time_step_min", "time_step_max",
         "time_step_floor", "dtype", "param_dtype")
ASSUMED = ("init_std", "embed_init_std", "out_init_std")
# published keys the program has no other value for
FIXED = {"attention_bias": False, "mamba_proj_bias": False, "mlp_bias": False,
         "use_bias": False, "use_conv_bias": True, "mamba_hidden_act": "silu",
         "sliding_window": None, "model_type": "nemotron_h"}


def cell_config(workload: dict, config: dict):
    """The program's config object as this cell runs it; refuses a file
    whose sizes are not the program's, a bias or an activation the
    program does not build, and a Mamba width that is not `expand` times
    the stream's."""
    from benchmark.program import _replace
    from proteinbert_tpu.configs.config import get_preset

    cfg = get_preset(config["preset"])
    for path, value in {**config.get("overrides", {}),
                        **workload.get("overrides", {})}.items():
        cfg = _replace(cfg, path, value)
    m = cfg.model
    for key in SIZES:
        runs = getattr(m, RENAMED.get(key, key))
        if runs != config[key]:
            raise SystemExit(f"configuration file says {key}={config[key]!r}, "
                             f"the program runs {runs!r}")
    for key, only in FIXED.items():
        if config[key] != only:
            raise SystemExit(f"configuration file says {key}={config[key]!r}; "
                             f"the program builds {only!r} alone")
    if (config["expand"] * config["hidden_size"] != m.mamba_inner
            or config["norm_eps"] != config["layer_norm_epsilon"]
            or set(m.pattern_held) - set(ref.KINDS)):
        raise SystemExit(
            f"expand x hidden_size ({config['expand']} x {config['hidden_size']}) "
            f"has to be the Mamba heads' {m.mamba_inner}, both norm epsilons one, "
            f"and the layers held ({m.pattern_held!r}) of {sorted(ref.KINDS)}")
    return cfg


def reference_sizes(config: dict, cfg) -> dict:
    """The configuration as the reference and `nemotron_flops` take it."""
    c = {k: config[k] for k in SIZES if k not in ("dtype", "param_dtype")}
    c.update({k: getattr(cfg.model, k) for k in ASSUMED})
    return c


def gaps(served: list, reference: list) -> dict:
    """`lm_serve.gaps`' eight numbers: `compare.embedding_checks` over
    the two vectors and the first quartile over the sample of an answer's
    error (the statistic that reads the arithmetic of the documents
    without a flipped choice: PERF.md section 2)."""
    return lm_serve.gaps(served, reference)


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
    closed on the way out (`benchmark.find_lm_knee` opens one window
    after another on what this yields)."""
    from proteinbert_tpu.models import glm_moe
    from proteinbert_tpu.serve.server import Server

    wl, mix = run.workload, run.mix
    cfg = cell_config(wl, run.config)
    c = reference_sizes(run.config, cfg)
    stated = run.config["parameters"]
    if mix["lengths"]["max"] > cfg.data.seq_len or mix["ids"]["vocab_size"] != c["vocab_size"]:
        raise SystemExit("the mix's longest document and its ids have to fit the cell")
    has = glm_moe.served_param_count(cfg.model)
    if not has == nemotron_flops.param_count(c) == ref.param_count(c) == stated:
        raise SystemExit(
            f"configuration file states {stated} parameters, the program has "
            f"{has}, nemotron_flops counts {nemotron_flops.param_count(c)}, the "
            f"reference {ref.param_count(c)}")
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
        server=server, tele=tele, c=c, stated=stated, served_params=has,
        ladder_rows=sorted(int(k) for k in server.dispatcher.batch_classes),
        counted_before=server.stats()["batched_rows"], submitted=0)
    try:
        for f in [server.submit("embed", d) for d in warm_docs]:
            f.result(timeout=600)
        boot.submitted = len(warm_docs)
        yield boot
    finally:
        server.close(drain=False)


def account(run, boot, seen):
    """The run's result from what a window saw, counted as
    `drivers/lm_serve.account` and `drivers/serve.measure` count."""
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
    if wl["judged"] == "latency":       # the knee sweep: every request waited for
        failed = int(n - ok.sum())
        e2e = {"embed_latency_p95_ms": float(np.percentile(latency, 95) * 1e3)}
    else:
        failed = int(errors)
        e2e = {"embed_residues_per_s": float(
            lengths_n[in_window].sum() / run.window_s)}

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
    layers = nemotron_flops.layer_counts(c)
    # What the mathematics needs for the window's batches, as a MEAN
    # batch: every class is given the same, so that batches x mean is the
    # window's total whatever classes ran; the scopes' device times are
    # means over the batches run too.
    per_batch = 1.0 / max(batches, 1)
    obs = {
        "program": PROGRAM,
        "batches": batches,
        "residues_in_batches": tokens_in_batches,
        "batch_class_counts": class_counts,
        "batched_positions": int(after["batched_positions"]
                                 - before["batched_positions"]),
        "requests_in_window": int(in_window.sum()),
        "residues_in_window": int(lengths_n[in_window].sum()),
        "latency_s": latency,
        "due_s": due_n,
        "seconds": run.seconds,
        "late_s": np.asarray(load.sent) - due_n[:len(load.sent)],
        "stages": seen.stages,
        "classes": {cls: {"flops": per_batch * nemotron_flops.forward_flops(
            c, tokens_in_batches, pairs, routing["assignments_held"])}
            for cls in boot.ladder_rows},
        "class_program": lambda cls: cell_program(wl, run.config, rows=cls),
        "routing": dict(routing, expert_layers=layers["latent_moe"],
                        top_k=c["num_experts_per_tok"]),
        # every Mamba layer's recurrence, and every expert layer's grouped
        # products, over a mean batch of the window (what the two scopes sum)
        "ssd_core_flops": per_batch * layers["mamba"] * nemotron_flops.ssd_core_flops(
            c, tokens_in_batches),
        "ssd_core_min_bytes": per_batch * layers["mamba"]
        * nemotron_flops.ssd_core_min_bytes(c, tokens_in_batches),
        "served_experts_flops": per_batch * nemotron_flops.experts_flops(
            c, routing["assignments_held"]),
        "served_experts_min_bytes": nemotron_flops.experts_min_bytes(
            c, layers["latent_moe"]),
    }
    _print_pace(np.sort(done_at[in_window]), load, due_n, run.seconds)
    _print_window(n, obs, run)
    print(f"routing: {routing}")
    print(f"kernel paths: moe_rows {after.get('moe_rows_path')}")
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
