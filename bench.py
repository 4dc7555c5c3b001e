"""Benchmark: base-model pretraining throughput on the available chip(s).

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline"} stamped,
like every line this script prints, with the device that produced it:
"platform", "device_kind" and "device_count" as JAX reports them. A
number without its device is not a measurement: a run that finds no
accelerator and was not explicitly asked for the CPU
(JAX_PLATFORMS=cpu) exits non-zero and prints no record, and no older
record is ever printed in a live one's place.

Metric: residues/sec/chip on the BASELINE.json NORTH-STAR config — the
6-block/d=512 base model at seq_len 1024 ("≥40% MFU ... at seq_len 1024",
BASELINE.json) — denoising pretrain, synthetic data (the reference has
no published numbers to compare against — BASELINE.md; vs_baseline is
therefore measured MFU / the 0.40 north-star MFU target, so 1.0 means
"hit the ≥40% MFU goal"). Rounds 1-2 measured seq_len 512; the sweep
keeps one 512 variant for cross-round continuity.

A small sweep of execution variants is timed, in this one process (a
chip belongs to one process at a time), and the best reported:
- remat with the "convs" policy at large batch (save the two conv
  outputs per block — ~85% of block FLOPs — and recompute only the
  cheap tail in backward; measured +8% over full remat);
- xla+remat at large batch (full rematerialisation removes the fp32
  LayerNorm saves that otherwise cap batch at 64 on a 16G chip and make
  the non-remat step HBM-bound);
- the Pallas fused local-track kernel (kernels/fused_block.py) at the
  batch its VMEM plan likes — its custom VJP already rematerialises, so
  it runs WITHOUT cfg.remat (pairing them recomputes twice).
A variant that fails (an over-large batch running out of HBM is part of
the sweep's design) is named on stderr and under "failed_variants" in
the record. Timing syncs by fetching the loss scalar to host.
"""

import dataclasses
import json
import os
import sys
import time

import numpy as np

BENCH_EVENTS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "bench_events.jsonl")


def bench_device(cpu_requested_in_code: bool = False) -> dict:
    """The stamp every line of a run carries — platform, device_kind,
    device_count as JAX reports them — and the one place that refuses
    to measure on a CPU nobody asked for: with no accelerator and no
    JAX_PLATFORMS=cpu the run ends here, non-zero, before any number
    exists. `cpu_requested_in_code` is for the one mode that IS a
    virtual-CPU-mesh byte count (--comm). Also arms the shared
    compile cache (utils/compat.configure_compile_cache); call before
    the first compile."""
    import jax

    from proteinbert_tpu.utils.compat import configure_compile_cache

    configure_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if (platform == "cpu" and not cpu_requested_in_code
            and os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu"):
        raise SystemExit(
            "bench.py: JAX found no accelerator and JAX_PLATFORMS does "
            "not ask for the cpu — refusing to print numbers from a "
            "backend nobody chose (set JAX_PLATFORMS=cpu for a CPU "
            "rehearsal)")
    return {"platform": platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def build_record(best, device):
    res_per_sec, mfu, name, seq_len, batch = best
    return {
        "metric": "residues_per_sec_per_chip",
        "value": round(res_per_sec, 1),
        "unit": "residues/s",
        "vs_baseline": round(mfu / 0.40, 4),
        **device,
        # Full shape provenance: the 512-seq continuity variant is within
        # ~1.5% of the 1024 north-star shape, and a record without
        # seq/batch could pass one off as the other on a noisy run.
        "variant": name,
        "seq_len": seq_len,
        "batch": batch,
    }


def time_step(cfg, batch_np, steps):
    """ms/step with a device→host scalar fetch as the hard sync."""
    import jax

    from proteinbert_tpu.train import create_train_state, train_step

    state = create_train_state(jax.random.PRNGKey(0), cfg)
    dbatch = jax.device_put(batch_np)

    state, m = train_step(state, dbatch, cfg)  # compile
    float(m["loss"])
    for _ in range(3):  # settle caches / power state
        state, m = train_step(state, dbatch, cfg)
    float(m["loss"])

    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = train_step(state, dbatch, cfg)
    float(m["loss"])
    return (time.perf_counter() - t0) / steps


def build_variants(on_tpu):
    """The variant list, as (name, model_cfg, seq_len, batch) plus the
    timing-step count. Pallas variants whose shape has no VMEM plan are
    filtered HERE (use_pallas would bench the XLA path under the
    kernel's name). `on_tpu=False` is the tiny list of an explicit CPU
    rehearsal."""
    from proteinbert_tpu.configs import ModelConfig

    if on_tpu:
        base = ModelConfig(local_dim=512, global_dim=512, key_dim=64,
                           num_heads=8, num_blocks=6, dtype="bfloat16")
        convs = dataclasses.replace(base, remat=True, remat_policy="convs")
        # ORDER = PRIORITY: a chip call has a time limit, so the rows a
        # short run must refresh come first — the north-star shape and
        # the headline long-context row, then the large/long provenance
        # rows, then the settled scan-boundary levers (measured round 5,
        # null result — kept as regression rows, no longer urgent) and
        # the re-confirmation shapes.
        variants = [  # (name, model, seq_len, batch)
            # North-star shape: seq_len 1024 (same tokens/step as 512@512).
            ("remat-convs", convs, 1024, 256),
        ]
        # Large (12-block/d=1024) and long-context (L=2048) preset shapes
        # at their measured-best single-chip batches, so the flagship
        # BASELINE.md claims (0.69 MFU Large, 0.57 long) get timestamped
        # machine-readable provenance instead of living only in round-2
        # prose. Small batches keep each row short. The
        # models come FROM the presets so a preset change can never make
        # these rows silently certify a different shape than they claim.
        from proteinbert_tpu.configs import get_preset

        variants += [
            # The repo headline row (fastest measured shape) right after
            # the north-star: a short window refreshes both.
            ("long", get_preset("long").model, 8192, 8),
            ("large", get_preset("large").model, 1024, 32),
            ("large", get_preset("large").model, 1024, 64),
            # The rest of the single-chip long-context curve — 2048/32,
            # 4096/16, and 16384/4 are iso-tokens/step with 8192/8
            # (65,536; the 2048/64 row is the double-batch point, NOT
            # part of the iso curve): the model is position-embedding-
            # free (conv local track + global attention), so L extends
            # freely (flat MFU through 8192; the 16384 row marks the
            # B=4 batch floor where the seq-parallel path takes over).
            ("long", get_preset("long").model, 2048, 32),
            ("long", get_preset("long").model, 2048, 64),
            ("long", get_preset("long").model, 4096, 16),
            ("long", get_preset("long").model, 16384, 4),
        ]
        variants += [
            # Scan-boundary levers: measured round 5 at the north-star
            # shape, NULL result (st -0.1%, u2 -5.4%, u3 -6.8%, u2st
            # -5.2% — experiments/sweep_decision_r5.txt). Kept as
            # regression rows so a compiler upgrade that flips the
            # trade shows up in the sweep; no longer priority-ordered.
            ("remat-convs-u2",
             dataclasses.replace(convs, scan_unroll=2), 1024, 256),
            ("remat-convs-u3",
             dataclasses.replace(convs, scan_unroll=3), 1024, 256),
            ("remat-convs-st",
             dataclasses.replace(convs, scan_split_transpose=True),
             1024, 256),
            ("remat-convs-u2st",
             dataclasses.replace(convs, scan_unroll=2,
                                 scan_split_transpose=True),
             1024, 256),
            # Batch is the biggest lever (docs/performance.md); push the
            # north-star shape until HBM says stop — the in-loop skip
            # keeps an OOM from killing the sweep.
            ("remat-convs", convs, 1024, 128),
            ("remat-convs", convs, 1024, 384),
            ("remat-convs", convs, 1024, 512),
            # Full remat at the same shape so the convs-policy comparison
            # stays same-batch (ADVICE r1).
            ("xla-remat", dataclasses.replace(base, remat=True), 1024, 256),
            # Cross-round continuity with the rounds-1/2 seq_len-512 record.
            ("remat-convs", convs, 512, 512),
            ("remat-convs", convs, 512, 256),
            # Pallas at its supported shape (C=512/L=512: full weights
            # VMEM-resident — the kernel's official scope, BASELINE.md).
            # At L=1024 pallas_supported is False and use_pallas would
            # silently bench the XLA fallback, so it is gated below.
            # B=256/512 rows answer VERDICT r2 item 3's same-batch
            # kernel-vs-remat-convs question (the VJP saves only
            # (params, x, broadcast) — nothing forbids large B).
            ("pallas", dataclasses.replace(base, use_pallas=True), 512, 64),
            ("pallas", dataclasses.replace(base, use_pallas=True), 512, 256),
            ("pallas", dataclasses.replace(base, use_pallas=True), 512, 512),
        ]
        steps = 15
        from proteinbert_tpu.kernels import pallas_supported

        variants = [
            v for v in variants
            if not (v[1].use_pallas
                    and not pallas_supported(v[1].local_dim, v[2],
                                             v[1].dtype))
        ]
    else:  # explicit CPU rehearsal (JAX_PLATFORMS=cpu): plumbing only
        base = ModelConfig(local_dim=64, global_dim=128, key_dim=16,
                           num_heads=4, num_blocks=2, num_annotations=512,
                           dtype="float32")
        variants = [("xla", base, 128, 8)]
        steps = 5
    return variants, steps


def run_variant(variant, steps, device, seed=0):
    """Measure ONE (name, model, seq_len, batch) variant and return its
    sweep row; the stderr line carries the device stamp like every
    other line."""
    from proteinbert_tpu.configs import (
        DataConfig, OptimizerConfig, PretrainConfig, TrainConfig,
    )
    from proteinbert_tpu.train.metrics import (
        peak_flops_per_chip, train_flops,
    )

    name, model, seq_len, batch = variant
    cfg = PretrainConfig(
        model=model,
        data=DataConfig(seq_len=seq_len, batch_size=batch),
        optimizer=OptimizerConfig(warmup_steps=100),
        train=TrainConfig(max_steps=steps),
    )
    rng = np.random.default_rng(seed)
    batch_np = {
        "tokens": rng.integers(4, 26, size=(batch, seq_len)
                               ).astype(np.int32),
        "annotations": (rng.random((batch, model.num_annotations)) < 0.01
                        ).astype(np.float32),
    }
    dt = time_step(cfg, batch_np, steps)
    res_per_sec = batch * seq_len / dt
    # MFU from the ACTUAL per-batch FLOPs (non-pad tokens), not the
    # padded shape — identical for this all-real synthetic batch, but
    # the denominator is now honest for any future padded row (the
    # --pack bench relies on the same fix).
    mfu = (train_flops(model, batch, seq_len,
                       nonpad_tokens=int((batch_np["tokens"] != 0).sum()))
           / dt / peak_flops_per_chip())
    print(f"variant={name} seq={seq_len} batch={batch}: "
          f"{dt * 1e3:.1f} ms/step "
          f"res/s={res_per_sec:,.0f} MFU={mfu:.3f} on "
          f"{device['device_count']}x {device['device_kind']} "
          f"({device['platform']})", file=sys.stderr)
    return {
        "variant": name, "seq_len": seq_len, "batch": batch,
        "ms_per_step": round(dt * 1e3, 2),
        "residues_per_sec": round(res_per_sec, 1),
        "mfu": round(mfu, 4),
    }


def run_boundary():
    """`bench.py --boundary`: train-stream stall seconds per checkpoint
    boundary, synchronous vs overlapped, on the backend it finds
    (stamped in the record). Emits ONE JSON line.

    The measured quantity is the host-side stall: how long the dispatch
    loop stands inside the boundary instead of enqueuing train steps.
    Both modes drain (fetch the loss) BEFORE the measured region — the
    drain is train work, not boundary cost — then time:
      sync:       device→host fetch + orbax save call
      overlapped: on-device snapshot dispatch + stager handoff
    The overlapped stage is flushed between boundaries OUTSIDE the
    measured region (its fetch+write runs behind the inter-boundary
    train steps, exactly as in the trainer), and its hidden seconds are
    reported as overlap_hidden_s_per_boundary.
    """
    import shutil
    import tempfile

    import jax

    device = bench_device()

    from proteinbert_tpu.configs import (
        DataConfig, ModelConfig, OptimizerConfig, PretrainConfig,
        TrainConfig,
    )
    from proteinbert_tpu.train import (
        Checkpointer, create_train_state, snapshot_train_state, train_step,
    )
    from proteinbert_tpu.utils.profiling import BoundaryStallMeter

    # Default 5: an odd sample count makes the median a real middle
    # element, not the upper of two — the gate statistic on a noisy
    # shared-CPU host.
    boundaries = int(os.environ.get("PBT_BOUNDARY_BENCH_BOUNDARIES", 5))
    steps_between = int(os.environ.get("PBT_BOUNDARY_BENCH_STEPS", 8))
    # Big enough that the sync fetch+save is a measurable host cost on
    # CPU (tens of MB of fp32 params + 2x Adam moments), small enough to
    # stay comfortably inside CI memory. PBT_BOUNDARY_BENCH_DIM scales
    # the shape down for plumbing tests (compile time dominates there);
    # the ≥5x acceptance claim is the default-size run.
    dim = int(os.environ.get("PBT_BOUNDARY_BENCH_DIM", 96))
    model = ModelConfig(local_dim=dim, global_dim=2 * dim, key_dim=16,
                        num_heads=4, num_blocks=2,
                        num_annotations=max(32 * dim, 512),
                        dtype="float32")
    cfg = PretrainConfig(
        model=model,
        data=DataConfig(seq_len=128, batch_size=8),
        optimizer=OptimizerConfig(warmup_steps=10),
        train=TrainConfig(max_steps=10_000),
    )
    rng = np.random.default_rng(0)
    batch = {
        "tokens": rng.integers(4, 26, size=(8, 128)).astype(np.int32),
        "annotations": (rng.random((8, model.num_annotations)) < 0.01
                        ).astype(np.float32),
    }

    def run_mode(overlapped):
        tmp = tempfile.mkdtemp(prefix="pbt_boundary_bench_")
        ck = Checkpointer(os.path.join(tmp, "ck"), max_to_keep=2,
                          async_save=True)
        meter = BoundaryStallMeter()
        hidden = []
        try:
            state = create_train_state(jax.random.PRNGKey(0), cfg)
            state, m = train_step(state, batch, cfg)  # compile
            float(m["loss"])
            # Untimed warmup boundary: the FIRST save pays one-time
            # orbax directory init + thread spinup (and the snapshot
            # jit's compile) — the warm_start story; both modes must be
            # measured at their steady per-boundary cost.
            if overlapped:
                ck.save_staged(1, snapshot_train_state(state))
                ck.flush_staged()
            else:
                ck.save(1, jax.device_get(state))
            ck.wait()
            step = 1
            for _ in range(boundaries):
                for _ in range(steps_between):
                    state, m = train_step(state, batch, cfg)
                    step += 1
                # A production cadence puts minutes of steps between
                # boundaries; the smoke steps here are milliseconds, so
                # give the in-flight stage the room a real cadence has
                # by TRAINING until it lands — those extra steps are the
                # overlap itself (dispatched while the stager fetches
                # and writes), not idle waiting. The trainer's
                # backpressure rule (flush-before-next-stage) still
                # covers the pathological cadence and is exercised by
                # tests/test_train.py.
                extra = 0
                while overlapped and ck.staged_in_flight() and extra < 50_000:
                    state, m = train_step(state, batch, cfg)
                    step += 1
                    extra += 1
                stats = ck.poll_staged()
                if stats:
                    hidden.append(stats["overlap_s"])
                float(m["loss"])  # drain: train work, outside the stall
                if overlapped:
                    with meter.boundary():
                        snap = snapshot_train_state(state)
                        ck.save_staged(step, snap)
                else:
                    with meter.boundary():
                        host_state = jax.device_get(state)
                        ck.save(step, host_state)
            # The final stage is joined with NO training dispatched
            # behind it — its seconds were not hidden, so they must not
            # inflate the overlap_hidden mean.
            ck.flush_staged()
            ck.wait()
        finally:
            ck.close()
            shutil.rmtree(tmp, ignore_errors=True)
        out = meter.summary()
        if hidden:
            out["hidden_mean_s"] = sum(hidden) / len(hidden)
        return out

    sync = run_mode(overlapped=False)
    over = run_mode(overlapped=True)
    # Median per-boundary stall: with a handful of boundaries, one GC
    # pause inside a single measurement swings the mean 2-3x on a
    # loaded CI host; the median is the stable comparison statistic
    # (both means stay in the record for completeness).
    record = {
        "metric": "ckpt_boundary_stall_s",
        **device,
        "boundaries": boundaries,
        "steps_between": steps_between,
        "sync_stall_s_per_boundary": round(sync["median_s"], 4),
        "overlapped_stall_s_per_boundary": round(over["median_s"], 4),
        "sync_stall_mean_s": round(sync["mean_s"], 4),
        "overlapped_stall_mean_s": round(over["mean_s"], 4),
        "stall_reduction_x": round(sync["median_s"] / max(over["median_s"],
                                                          1e-9), 1),
        "overlap_hidden_s_per_boundary": round(
            over.get("hidden_mean_s", 0.0), 4),
    }
    print(json.dumps(record))


def run_pack():
    """`bench.py --pack`: packed vs unpacked pretraining throughput on a
    realistic UniRef-like length distribution — one JSON line, CPU-
    measurable (ISSUE 4 acceptance).

    Two iterators over the SAME synthetic corpus (lognormal lengths,
    median ~350) at the SAME batch shape (B, L): the plain padded
    iterator and the segment-aware packed one (data/packing.py). Each
    mode times its own jitted train step and reports BOTH raw
    residues/s (B·L positions per second — the number that flatters
    padding) and pad-adjusted EFFECTIVE residues/s (non-pad tokens per
    second — the number that measures useful work). MFU likewise comes
    in raw (padded-shape FLOPs) and effective (actual per-batch FLOPs,
    train_flops(..., nonpad_tokens=...) — the satellite's honest-MFU
    fix) flavors. The capture is mirrored as a `note` event on the
    bench event stream (bench_events.jsonl), like the TPU sweeps.

    Knobs: PBT_PACK_BENCH_SEQ_LEN (default 1024), PBT_PACK_BENCH_BATCH
    (8), PBT_PACK_BENCH_DIM (64; plumbing tests shrink it),
    PBT_PACK_BENCH_STEPS (5), PBT_PACK_BENCH_MEDIAN_LEN (350).
    """
    import jax

    device = bench_device()

    from proteinbert_tpu.configs import (
        DataConfig, ModelConfig, OptimizerConfig, PretrainConfig,
        TrainConfig,
    )
    from proteinbert_tpu.data import (
        InMemoryPretrainingDataset, make_packed_iterator,
        make_pretrain_iterator,
    )
    from proteinbert_tpu.train import create_train_state, train_step
    from proteinbert_tpu.train.metrics import (
        peak_flops_per_chip, train_flops,
    )

    seq_len = int(os.environ.get("PBT_PACK_BENCH_SEQ_LEN", 1024))
    batch = int(os.environ.get("PBT_PACK_BENCH_BATCH", 8))
    dim = int(os.environ.get("PBT_PACK_BENCH_DIM", 64))
    steps = int(os.environ.get("PBT_PACK_BENCH_STEPS", 5))
    median = int(os.environ.get("PBT_PACK_BENCH_MEDIAN_LEN", 350))

    model = ModelConfig(local_dim=dim, global_dim=2 * dim, key_dim=16,
                        num_heads=4, num_blocks=2,
                        num_annotations=max(8 * dim, 256), dtype="float32")
    cfg = PretrainConfig(
        model=model,
        data=DataConfig(seq_len=seq_len, batch_size=batch),
        optimizer=OptimizerConfig(warmup_steps=10),
        train=TrainConfig(max_steps=steps))

    # UniRef-like lengths: lognormal with the requested median, clipped
    # to the crop cap (sequences longer than seq_len-2 pack alone).
    rng = np.random.default_rng(0)
    n = max(64 * batch, 512)
    lengths = np.clip(
        rng.lognormal(mean=np.log(median), sigma=0.6, size=n),
        20, 4 * median).astype(np.int64)
    from proteinbert_tpu.data.vocab import ALPHABET

    alphabet = np.array(list(ALPHABET))
    seqs = ["".join(rng.choice(alphabet, size=int(L))) for L in lengths]
    ann = (rng.random((n, model.num_annotations)) < 0.01).astype(np.float32)
    ds = InMemoryPretrainingDataset(seqs, ann, seq_len)

    def measure(batch_np):
        dt = time_step(cfg, batch_np, steps)
        nonpad = int((batch_np["tokens"] != 0).sum())
        total = batch_np["tokens"].size
        peak = peak_flops_per_chip()
        return {
            "ms_per_step": round(dt * 1e3, 2),
            "pad_fraction": round(1.0 - nonpad / total, 4),
            "raw_residues_per_sec": round(total / dt, 1),
            "effective_residues_per_sec": round(nonpad / dt, 1),
            "mfu_raw": round(
                train_flops(model, batch, seq_len) / dt / peak, 4),
            "mfu_effective": round(
                train_flops(model, batch, seq_len, nonpad_tokens=nonpad)
                / dt / peak, 4),
        }

    unpacked = measure(next(make_pretrain_iterator(ds, batch, seed=0)))
    packed = measure(next(make_packed_iterator(ds, batch, seed=0)))

    # ---- fused-vs-reference packed A/B (ISSUE 10 satellite) ----------
    failures = []
    fused_ab = None
    if int(os.environ.get("PBT_PACK_BENCH_FUSED_AB", 1)):
        fused_ab = _pack_fused_ab(model, ds, batch, failures)

    # ---- attention fused-vs-reference A/B (ISSUE 13 satellite) -------
    attn_ab = None
    if int(os.environ.get("PBT_PACK_BENCH_ATTN_AB", 1)):
        attn_ab = _pack_attn_ab(model, ds, batch, failures)

    # ---- one-pass vs two-kernel trunk A/B (ISSUE 16 tentpole) --------
    onepass_ab = None
    if int(os.environ.get("PBT_PACK_BENCH_ONEPASS_AB", 1)):
        onepass_ab = _pack_onepass_ab(model, ds, batch, failures)

    record = {
        "metric": "packed_throughput",
        **device,
        "seq_len": seq_len, "batch": batch, "model_dim": dim,
        "median_len": median,
        "unpacked": unpacked,
        "packed": packed,
        "effective_speedup_x": round(
            packed["effective_residues_per_sec"]
            / max(unpacked["effective_residues_per_sec"], 1e-9), 2),
        "fused_ab": fused_ab,
        "attn_ab": attn_ab,
        "onepass_ab": onepass_ab,
        "failures": failures,
    }
    try:  # mirror onto the shared bench event stream (best-effort)
        from proteinbert_tpu.obs.events import EventLog

        ev = EventLog(BENCH_EVENTS_PATH)
        ev.emit("note", source="bench", kind="pack_capture",
                platform=record["platform"], seq_len=seq_len, batch=batch,
                effective_speedup_x=record["effective_speedup_x"],
                packed_pad_fraction=packed["pad_fraction"],
                unpacked_pad_fraction=unpacked["pad_fraction"])
        if fused_ab is not None:
            # Separate note so tools/bench_trajectory.py fits the
            # fused-packed series independently of the pack capture.
            ev.emit("note", source="bench", kind="pack_fused_capture",
                    platform=record["platform"], seq_len=seq_len,
                    batch=batch, fused_dim=fused_ab["fused_dim"],
                    fused_supported=fused_ab["supported"],
                    fused_speedup_x=fused_ab["fused_speedup_x"],
                    parity_max_abs_diff=fused_ab["parity_max_abs_diff"],
                    pallas_executables=fused_ab["pallas_executables"],
                    segment_fallbacks=fused_ab["segment_fallbacks"],
                    failures=len(failures))
        if attn_ab is not None:
            # The attention-arm capture (ISSUE 13): its speedup feeds
            # the pack_attn_speedup_x sentinel series, and the packed
            # step's MFU rides along as the pack_mfu_effective series —
            # the compound packing × fused-kernels claim, recorded on
            # whatever platform actually ran (the `platform` field
            # splits CPU-interpret plumbing numbers from TPU captures).
            ev.emit("note", source="bench", kind="pack_attn_capture",
                    platform=record["platform"], seq_len=seq_len,
                    batch=batch, attn_dim=attn_ab["attn_dim"],
                    attn_supported=attn_ab["supported"],
                    attn_speedup_x=attn_ab["attn_speedup_x"],
                    parity_max_abs_diff=attn_ab["parity_max_abs_diff"],
                    pallas_executables=attn_ab["pallas_executables"],
                    segment_fallbacks=attn_ab["segment_fallbacks"],
                    mfu_raw=packed["mfu_raw"],
                    mfu_effective=packed["mfu_effective"],
                    failures=len(failures))
        if onepass_ab is not None:
            # The one-pass-trunk capture (ISSUE 16): its speedup feeds
            # the pack_onepass_speedup_x sentinel series and the packed
            # step's MFU rides along as onepass_mfu_effective — the
            # whole-block-in-VMEM claim, recorded on whatever platform
            # actually ran (the `platform` field splits CPU-interpret
            # plumbing numbers from TPU captures).
            ev.emit("note", source="bench", kind="onepass_capture",
                    platform=record["platform"], seq_len=seq_len,
                    batch=batch, onepass_dim=onepass_ab["onepass_dim"],
                    onepass_supported=onepass_ab["supported"],
                    onepass_speedup_x=onepass_ab["onepass_speedup_x"],
                    parity_max_abs_diff=onepass_ab["parity_max_abs_diff"],
                    pallas_executables=onepass_ab["pallas_executables"],
                    segment_fallbacks=onepass_ab["segment_fallbacks"],
                    onepass_pallas_calls=onepass_ab["onepass_pallas_calls"],
                    mfu_raw=packed["mfu_raw"],
                    mfu_effective=packed["mfu_effective"],
                    failures=len(failures))
        ev.close()
    except Exception as e:
        print(f"bench events stream unavailable: {e}", file=sys.stderr)
    print(json.dumps(record))
    if failures:
        for f in failures:
            print(f"PACK CONTRACT FAILURE: {f}", file=sys.stderr)
        sys.exit(1)


def _pack_fused_ab(model, ds, batch, failures):
    """Fused-vs-reference packed A/B (`bench.py --pack`, ISSUE 10): the
    SAME packed batch runs the segment-aware Pallas fused path and the
    XLA reference path at a lane-aligned dim (the fused kernel needs
    C % 128 == 0, so the main capture's historical dim series stays
    untouched and the A/B gets its own PBT_PACK_BENCH_FUSED_DIM,
    default 128).

    GATED (appended to `failures`, nonzero exit):
    - fused-vs-reference parity within the documented jitted 1e-5
      tolerance on local and global logits;
    - on a supported shape, the fused arm must actually take the
      Pallas path — since the one-pass trunk fusion (ISSUE 16) the
      model-level dispatch lands on
      `onepass_kernel_path_total{path=pallas,reason=packed}` (the
      fused-block family only counts when the one-pass plan doesn't
      fit), so the gate accepts a bump on EITHER family, with ZERO
      reason=segments fallbacks on both;
    - the PBT_FORCE_REFERENCE_KERNEL debug override must route a fresh
      trace onto the reference path (and agree with it bit-for-bit).

    Wall-clock speedup is REPORTED, not gated: off-TPU the kernel runs
    in interpret mode, so the CPU number is a plumbing check — the TPU
    capture is the MFU claim (docs/performance.md, packed fast path).
    """
    from functools import partial

    import jax
    import jax.numpy as jnp

    from proteinbert_tpu.configs import ModelConfig
    from proteinbert_tpu.data import make_packed_iterator
    from proteinbert_tpu.kernels import fused_block as fb
    from proteinbert_tpu.kernels import one_pass as op
    from proteinbert_tpu.models import proteinbert

    fused_dim = int(os.environ.get("PBT_PACK_BENCH_FUSED_DIM", 128))
    reps = int(os.environ.get("PBT_PACK_BENCH_FUSED_REPS", 3))
    forced_env = fb.force_reference_requested()

    pbatch = next(make_packed_iterator(ds, batch, seed=0))
    seq_len = int(pbatch["tokens"].shape[1])
    S = int(pbatch["annotations"].shape[1])
    fused_model = ModelConfig(**{**model.__dict__,
                                 "local_dim": fused_dim,
                                 "use_pallas": True})
    ref_model = ModelConfig(**{**model.__dict__,
                               "local_dim": fused_dim,
                               "use_pallas": False})
    params = proteinbert.init(jax.random.PRNGKey(0), fused_model)

    @partial(jax.jit, static_argnames="mcfg")
    def fwd(p, tokens, seg, ann, mcfg):
        return proteinbert.apply(p, tokens, ann, mcfg, segment_ids=seg)

    t = jnp.asarray(pbatch["tokens"])
    s = jnp.asarray(pbatch["segment_ids"])
    a = jnp.asarray(pbatch["annotations"])
    supported = fb.pallas_segments_supported(
        fused_dim, seq_len, S, fused_model.dtype,
        fused_model.narrow_kernel, fused_model.wide_kernel,
        fused_model.wide_dilation)

    before = dict(fb.PATH_TOTAL)
    op_before = dict(op.ONEPASS_PATH_TOTAL)
    out_f = jax.block_until_ready(fwd(params, t, s, a, fused_model))
    after = dict(fb.PATH_TOTAL)
    op_after = dict(op.ONEPASS_PATH_TOTAL)
    pallas_bumps = (after.get(("pallas", "packed"), 0)
                    - before.get(("pallas", "packed"), 0)
                    + op_after.get(("pallas", "packed"), 0)
                    - op_before.get(("pallas", "packed"), 0))
    seg_falls = (after.get(("reference", "segments"), 0)
                 - before.get(("reference", "segments"), 0)
                 + op_after.get(("reference", "segments"), 0)
                 - op_before.get(("reference", "segments"), 0))
    out_r = jax.block_until_ready(fwd(params, t, s, a, ref_model))

    max_diff = max(
        float(np.abs(np.asarray(x, np.float32)
                     - np.asarray(y, np.float32)).max())
        for x, y in zip(out_f, out_r))
    if not all(np.allclose(np.asarray(x, np.float32),
                           np.asarray(y, np.float32),
                           atol=1e-5, rtol=1e-5)
               for x, y in zip(out_f, out_r)):
        failures.append(
            f"packed fused-vs-reference parity broke: max |diff| "
            f"{max_diff:.2e} outside the documented 1e-5 jitted "
            "tolerance")
    if supported and not forced_env:
        if pallas_bumps < 1:
            failures.append(
                "packed fused arm did not take the Pallas path on a "
                f"supported shape (C={fused_dim}, L={seq_len}, S={S})")
        if seg_falls:
            failures.append(
                f"{seg_falls} reason=segments fallback(s) on a "
                "supported shape — the packed fast path regressed")

    def clock(mcfg):
        # Await the warm dispatch: an un-awaited async call would bleed
        # up to one full forward of device work into the timed loop.
        jax.block_until_ready(fwd(params, t, s, a, mcfg))
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fwd(params, t, s, a, mcfg))
        return (time.perf_counter() - t0) / reps

    dt_f, dt_r = clock(fused_model), clock(ref_model)

    # Debug-override probe: a FRESH jit function forces a new trace, so
    # the env var (read at trace time) must land it on the reference
    # path — and the reference path is deterministic, so the outputs
    # match the use_pallas=False arm bit-for-bit.
    forced = None
    if not forced_env:
        os.environ[fb.FORCE_REFERENCE_ENV] = "1"
        try:
            b2 = dict(fb.PATH_TOTAL)
            forced_fn = jax.jit(
                lambda p, tt, ss, aa: proteinbert.apply(
                    p, tt, aa, fused_model, segment_ids=ss))
            out_fo = jax.block_until_ready(forced_fn(params, t, s, a))
            a2 = dict(fb.PATH_TOTAL)
            bumps = (a2.get(("reference", "forced"), 0)
                     - b2.get(("reference", "forced"), 0))
            bit = all(np.array_equal(np.asarray(x), np.asarray(y))
                      for x, y in zip(out_fo, out_r))
            forced = {"forced_bumps": bumps, "bit_identical": bit}
            if bumps < 1:
                failures.append(
                    "PBT_FORCE_REFERENCE_KERNEL did not route a fresh "
                    "trace onto the reference path")
            elif not bit:
                failures.append(
                    "forced-reference probe diverged from the "
                    "use_pallas=False reference arm")
        finally:
            del os.environ[fb.FORCE_REFERENCE_ENV]

    return {
        "fused_dim": fused_dim, "seq_len": seq_len, "max_segments": S,
        "supported": bool(supported),
        "pallas_executables": int(pallas_bumps),
        "segment_fallbacks": int(seg_falls),
        "parity_max_abs_diff": float(f"{max_diff:.3e}"),
        "fused_ms_per_fwd": round(dt_f * 1e3, 2),
        "reference_ms_per_fwd": round(dt_r * 1e3, 2),
        # Reported, not gated: interpret-mode CPU wall-clock is a
        # plumbing number, the TPU capture is the claim.
        "fused_speedup_x": round(dt_r / max(dt_f, 1e-9), 3),
        "forced_reference_probe": forced,
        "path_total": {f"{p}/{r}": n
                       for (p, r), n in sorted(fb.PATH_TOTAL.items())},
    }


def _pack_attn_ab(model, ds, batch, failures):
    """Attention fused-vs-reference A/B (`bench.py --pack`, ISSUE 13):
    the SAME packed batch's segment layout drives the ragged Pallas
    attention kernel (kernels/attention.fused_packed_attention) and the
    masked-XLA reference (`packed_global_attention_apply`) at a
    lane-aligned local dim (PBT_PACK_BENCH_ATTN_DIM, default 128 — the
    kernel needs C % 128 == 0, so the main capture's dim series stays
    untouched).

    GATED (appended to `failures`, nonzero exit):
    - fused-vs-reference parity within the documented jitted 1e-5
      tolerance on the per-segment (B, S, G) attention output;
    - on a supported shape, the fused arm must take the Pallas path
      (`attention_kernel_path_total{path=pallas,reason=packed}` bumps)
      with ZERO reason=segments fallbacks;
    - the PBT_FORCE_REFERENCE_KERNEL debug override must route a fresh
      trace onto the reference path (and agree with it bit-for-bit).

    Wall-clock speedup is REPORTED, not gated: off-TPU the kernel runs
    in interpret mode, so the CPU number is a plumbing check — the TPU
    capture is the MFU claim (docs/performance.md, packed fast path)."""
    import jax
    import jax.numpy as jnp

    from proteinbert_tpu.data import make_packed_iterator
    from proteinbert_tpu.kernels import attention as ka
    from proteinbert_tpu.ops.attention import (
        global_attention_init, packed_global_attention_apply,
    )

    attn_dim = int(os.environ.get("PBT_PACK_BENCH_ATTN_DIM", 128))
    reps = int(os.environ.get("PBT_PACK_BENCH_ATTN_REPS", 3))
    from proteinbert_tpu.kernels import fused_block as fb

    forced_env = fb.force_reference_requested()

    pbatch = next(make_packed_iterator(ds, batch, seed=0))
    seg = jnp.asarray(pbatch["segment_ids"])
    B, L = seg.shape
    S = int(pbatch["annotations"].shape[1])
    G, key_dim, H = model.global_dim, model.key_dim, model.num_heads
    params = global_attention_init(jax.random.PRNGKey(0), attn_dim, G,
                                   key_dim, H)
    local = jax.random.normal(jax.random.PRNGKey(1), (B, L, attn_dim),
                              jnp.float32)
    gseg = jax.random.normal(jax.random.PRNGKey(2), (B, S, G),
                             jnp.float32)
    supported = ka.pallas_attention_supported(attn_dim, G, L, S,
                                              key_dim, H, "float32")

    interp = fb.pallas_interpret()
    fused_fn = jax.jit(lambda p, x, g, s: ka.fused_packed_attention(
        p, x, g, s, interpret=interp))
    ref_fn = jax.jit(lambda p, x, g, s: packed_global_attention_apply(
        p, x, g, s))
    before = dict(ka.ATTN_PATH_TOTAL)
    out_f = jax.block_until_ready(fused_fn(params, local, gseg, seg))
    after = dict(ka.ATTN_PATH_TOTAL)
    pallas_bumps = (after.get(("pallas", "packed"), 0)
                    - before.get(("pallas", "packed"), 0))
    seg_falls = (after.get(("reference", "segments"), 0)
                 - before.get(("reference", "segments"), 0))
    out_r = jax.block_until_ready(ref_fn(params, local, gseg, seg))

    max_diff = float(np.abs(np.asarray(out_f, np.float32)
                            - np.asarray(out_r, np.float32)).max())
    if not np.allclose(np.asarray(out_f, np.float32),
                       np.asarray(out_r, np.float32),
                       atol=1e-5, rtol=1e-5):
        failures.append(
            f"attention fused-vs-reference parity broke: max |diff| "
            f"{max_diff:.2e} outside the documented 1e-5 jitted "
            "tolerance")
    if supported and not forced_env:
        if pallas_bumps < 1:
            failures.append(
                "attention fused arm did not take the Pallas path on a "
                f"supported shape (C={attn_dim}, L={L}, S={S})")
        if seg_falls:
            failures.append(
                f"{seg_falls} attention reason=segments fallback(s) on "
                "a supported shape — the packed fast path regressed")

    def clock(fn):
        jax.block_until_ready(fn(params, local, gseg, seg))
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(params, local, gseg, seg))
        return (time.perf_counter() - t0) / reps

    dt_f, dt_r = clock(fused_fn), clock(ref_fn)

    # Debug-override probe (same contract as the fused-block arm): a
    # fresh jit forces a new trace, so the env var (read at trace
    # time) must land it on the reference path bit-for-bit.
    forced = None
    if not forced_env:
        os.environ[fb.FORCE_REFERENCE_ENV] = "1"
        try:
            b2 = dict(ka.ATTN_PATH_TOTAL)
            forced_fn = jax.jit(
                lambda p, x, g, s: ka.fused_packed_attention(
                    p, x, g, s, interpret=interp))
            out_fo = jax.block_until_ready(
                forced_fn(params, local, gseg, seg))
            a2 = dict(ka.ATTN_PATH_TOTAL)
            bumps = (a2.get(("reference", "forced"), 0)
                     - b2.get(("reference", "forced"), 0))
            bit = np.array_equal(np.asarray(out_fo), np.asarray(out_r))
            forced = {"forced_bumps": bumps, "bit_identical": bit}
            if bumps < 1:
                failures.append(
                    "PBT_FORCE_REFERENCE_KERNEL did not route a fresh "
                    "attention trace onto the reference path")
            elif not bit:
                failures.append(
                    "forced-reference attention probe diverged from "
                    "the masked-XLA reference arm")
        finally:
            del os.environ[fb.FORCE_REFERENCE_ENV]

    return {
        "attn_dim": attn_dim, "seq_len": L, "max_segments": S,
        "global_dim": G, "key_dim": key_dim, "num_heads": H,
        "supported": bool(supported),
        "pallas_executables": int(pallas_bumps),
        "segment_fallbacks": int(seg_falls),
        "parity_max_abs_diff": float(f"{max_diff:.3e}"),
        "fused_ms_per_fwd": round(dt_f * 1e3, 2),
        "reference_ms_per_fwd": round(dt_r * 1e3, 2),
        # Reported, not gated: interpret-mode CPU wall-clock is a
        # plumbing number, the TPU capture is the claim. Floored at
        # 1e-3 so the schema's positive-finite contract on the
        # sentinel series holds even on a pathologically slow
        # interpret run.
        "attn_speedup_x": max(round(dt_r / max(dt_f, 1e-9), 3), 1e-3),
        "forced_reference_probe": forced,
        "path_total": {f"{p}/{r}": n
                       for (p, r), n in sorted(ka.ATTN_PATH_TOTAL.items())},
    }


def _pack_onepass_ab(model, ds, batch, failures):
    """One-pass-vs-two-kernel trunk A/B (`bench.py --pack`, ISSUE 16):
    the SAME packed batch's segment layout drives the fused one-pass
    trunk kernel (kernels/one_pass.fused_onepass_segments — local track
    AND ragged attention in ONE VMEM-resident grid program) against the
    two-kernel Pallas composition (fused_local_track_segments →
    fused_packed_attention) at a lane-aligned local dim
    (PBT_PACK_BENCH_ONEPASS_DIM, default 128).

    GATED (appended to `failures`, nonzero exit):
    - one-pass vs composition parity within the documented jitted 1e-5
      tolerance on BOTH outputs (the (B, L, C) local track and the
      (B, S, G) per-segment attention);
    - on a supported shape, the one-pass arm must take the Pallas path
      (`onepass_kernel_path_total{path=pallas,reason=packed}` bumps)
      with ZERO reason=segments fallbacks;
    - the HBM round-trip is ACTUALLY eliminated: the one-pass trace
      contains exactly ONE pallas_call (the composition two), so the
      inter-track (B, L, C) activation never leaves VMEM between the
      local track and attention — no intermediate buffer exists for
      XLA to spill;
    - the PBT_FORCE_REFERENCE_KERNEL debug override must route a fresh
      trace onto the reference composition (and agree bit-for-bit).

    Wall-clock speedup is REPORTED, not gated: off-TPU both arms run
    in interpret mode, so the CPU number is a plumbing check — the TPU
    capture is the MFU claim (docs/performance.md, one-pass trunk)."""
    import jax
    import jax.numpy as jnp

    from proteinbert_tpu.configs import ModelConfig
    from proteinbert_tpu.data import make_packed_iterator
    from proteinbert_tpu.kernels import attention as ka
    from proteinbert_tpu.kernels import fused_block as fb
    from proteinbert_tpu.kernels import one_pass as op
    from proteinbert_tpu.models import proteinbert

    onepass_dim = int(os.environ.get("PBT_PACK_BENCH_ONEPASS_DIM", 128))
    reps = int(os.environ.get("PBT_PACK_BENCH_ONEPASS_REPS", 3))
    forced_env = fb.force_reference_requested()
    interp = fb.pallas_interpret()

    pbatch = next(make_packed_iterator(ds, batch, seed=0))
    seg = jnp.asarray(pbatch["segment_ids"])
    B, L = seg.shape
    S = int(pbatch["annotations"].shape[1])
    G, key_dim, H = model.global_dim, model.key_dim, model.num_heads
    bcfg = ModelConfig(**{**model.__dict__, "local_dim": onepass_dim,
                          "use_pallas": True})
    block = proteinbert.block_init(jax.random.PRNGKey(0), bcfg)
    track = {k: block[k] for k in ("narrow_conv", "wide_conv",
                                   "local_ln1", "local_dense",
                                   "local_ln2")}
    attn = block["attention"]
    x = jax.random.normal(jax.random.PRNGKey(1), (B, L, onepass_dim),
                          jnp.float32)
    bcast = jax.random.normal(jax.random.PRNGKey(2), (B, S, onepass_dim),
                              jnp.float32)
    gseg = jax.random.normal(jax.random.PRNGKey(3), (B, S, G),
                             jnp.float32)
    supported = op.pallas_onepass_supported(onepass_dim, G, L, S,
                                            key_dim, H, "float32")

    def one(tp, ap, xx, bb, gg, ss):
        return op.fused_onepass_segments(tp, ap, xx, bb, gg, ss,
                                         interpret=interp)

    def two(tp, ap, xx, bb, gg, ss):
        loc = fb.fused_local_track_segments(tp, xx, bb, ss, 1, 5, interp)
        return loc, ka.fused_packed_attention(ap, loc, gg, ss,
                                              interpret=interp)

    one_fn, two_fn = jax.jit(one), jax.jit(two)
    before = dict(op.ONEPASS_PATH_TOTAL)
    out_f = jax.block_until_ready(
        one_fn(track, attn, x, bcast, gseg, seg))
    after = dict(op.ONEPASS_PATH_TOTAL)
    pallas_bumps = (after.get(("pallas", "packed"), 0)
                    - before.get(("pallas", "packed"), 0))
    seg_falls = (after.get(("reference", "segments"), 0)
                 - before.get(("reference", "segments"), 0))
    out_r = jax.block_until_ready(
        two_fn(track, attn, x, bcast, gseg, seg))

    max_diff = max(
        float(np.abs(np.asarray(a, np.float32)
                     - np.asarray(b, np.float32)).max())
        for a, b in zip(out_f, out_r))
    if not all(np.allclose(np.asarray(a, np.float32),
                           np.asarray(b, np.float32),
                           atol=1e-5, rtol=1e-5)
               for a, b in zip(out_f, out_r)):
        failures.append(
            f"one-pass vs two-kernel parity broke: max |diff| "
            f"{max_diff:.2e} outside the documented 1e-5 jitted "
            "tolerance")
    kernel_calls = comp_calls = None
    if supported and not forced_env:
        if pallas_bumps < 1:
            failures.append(
                "one-pass arm did not take the Pallas path on a "
                f"supported shape (C={onepass_dim}, L={L}, S={S})")
        if seg_falls:
            failures.append(
                f"{seg_falls} one-pass reason=segments fallback(s) on "
                "a supported shape — the fast path regressed")
        # The HBM-round-trip claim, checked structurally: one kernel
        # boundary in the one-pass trace (vs two in the composition)
        # means the inter-track activation has no buffer to spill to —
        # it lives in VMEM for the whole block pass.
        kernel_calls = str(jax.make_jaxpr(one)(
            track, attn, x, bcast, gseg, seg)).count("pallas_call")
        comp_calls = str(jax.make_jaxpr(two)(
            track, attn, x, bcast, gseg, seg)).count("pallas_call")
        if kernel_calls != 1:
            failures.append(
                f"one-pass trace has {kernel_calls} pallas_call "
                "boundaries (want exactly 1) — the inter-track "
                "activation round-trips HBM")

    def clock(fn):
        jax.block_until_ready(fn(track, attn, x, bcast, gseg, seg))
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(track, attn, x, bcast, gseg, seg))
        return (time.perf_counter() - t0) / reps

    dt_f, dt_r = clock(one_fn), clock(two_fn)

    # Debug-override probe: forcing routes the one-pass dispatch onto
    # the two-kernel composition whose own force checks land both legs
    # on the XLA reference — deterministic, so a forced fresh trace
    # matches a forced composition trace bit-for-bit.
    forced = None
    if not forced_env:
        os.environ[fb.FORCE_REFERENCE_ENV] = "1"
        try:
            b2 = dict(op.ONEPASS_PATH_TOTAL)

            # Fresh function objects: re-jitting the SAME function can
            # hit the trace cache and skip the trace-time env read.
            def one_probe(tp, ap, xx, bb, gg, ss):
                return op.fused_onepass_segments(tp, ap, xx, bb, gg, ss,
                                                 interpret=interp)

            def two_probe(tp, ap, xx, bb, gg, ss):
                loc = fb.fused_local_track_segments(tp, xx, bb, ss,
                                                    1, 5, interp)
                return loc, ka.fused_packed_attention(ap, loc, gg, ss,
                                                      interpret=interp)

            out_fo = jax.block_until_ready(
                jax.jit(one_probe)(track, attn, x, bcast, gseg, seg))
            out_ro = jax.block_until_ready(
                jax.jit(two_probe)(track, attn, x, bcast, gseg, seg))
            a2 = dict(op.ONEPASS_PATH_TOTAL)
            bumps = (a2.get(("reference", "forced"), 0)
                     - b2.get(("reference", "forced"), 0))
            bit = all(np.array_equal(np.asarray(a), np.asarray(b))
                      for a, b in zip(out_fo, out_ro))
            forced = {"forced_bumps": bumps, "bit_identical": bit}
            if bumps < 1:
                failures.append(
                    "PBT_FORCE_REFERENCE_KERNEL did not route a fresh "
                    "one-pass trace onto the reference path")
            elif not bit:
                failures.append(
                    "forced-reference one-pass probe diverged from the "
                    "forced two-kernel composition")
        finally:
            del os.environ[fb.FORCE_REFERENCE_ENV]

    return {
        "onepass_dim": onepass_dim, "seq_len": L, "max_segments": S,
        "global_dim": G, "key_dim": key_dim, "num_heads": H,
        "supported": bool(supported),
        "pallas_executables": int(pallas_bumps),
        "segment_fallbacks": int(seg_falls),
        "onepass_pallas_calls": kernel_calls,
        "composition_pallas_calls": comp_calls,
        "parity_max_abs_diff": float(f"{max_diff:.3e}"),
        "onepass_ms_per_fwd": round(dt_f * 1e3, 2),
        "composition_ms_per_fwd": round(dt_r * 1e3, 2),
        # Reported, not gated: interpret-mode CPU wall-clock is a
        # plumbing number, the TPU capture is the claim. Floored at
        # 1e-3 so the schema's positive-finite contract on the
        # sentinel series holds even on a pathologically slow
        # interpret run.
        "onepass_speedup_x": max(round(dt_r / max(dt_f, 1e-9), 3), 1e-3),
        "forced_reference_probe": forced,
        "path_total": {f"{p}/{r}": n for (p, r), n
                       in sorted(op.ONEPASS_PATH_TOTAL.items())},
    }


def parse_length_mix(spec):
    """`--serve-length-mix` spec → (median, sigma, seed) for the
    log-normal request-length population (clamped to the model window
    downstream). Accepts 'median=48,sigma=0.6,seed=7' with any subset
    of keys; None means 'use the historical defaults' (median
    seq_len//10, sigma 0.45, seed 0 — byte-identical traffic to every
    earlier capture)."""
    out = {"median": None, "sigma": 0.45, "seed": 0}
    if spec:
        for part in spec.split(","):
            if not part:
                continue
            key, _, val = part.partition("=")
            key = key.strip()
            if key not in out:
                raise SystemExit(
                    f"--serve-length-mix: unknown key {key!r} "
                    f"(have {sorted(out)})")
            out[key] = float(val) if key == "sigma" else int(float(val))
    return out["median"], out["sigma"], out["seed"]


def _serve_ragged_ab(Server, params, cfg, seqs, max_batch, max_wait_s,
                     n_clients, failures):
    """Phase 4 of `bench.py --serve` (ISSUE 9): bucketed vs ragged
    packed serving on IDENTICAL traffic. Gates (appended to `failures`):
    per-request parity within the documented jitted ≤1e-5 tolerance,
    no lost requests, ragged warm-executable count O(kinds). Reports:
    sustained requests/s per mode (median over interleaved rounds),
    executable/warmup accounting, and pad_wasted (pad_fraction-weighted
    execute seconds) per mode from the serve_batch event streams."""
    import shutil
    import tempfile
    import threading
    from statistics import median as _median

    from proteinbert_tpu.obs import Telemetry, read_events

    rounds = int(os.environ.get("PBT_SERVE_BENCH_RAGGED_ROUNDS", 3))
    # Ragged row count: the executable's fixed (rows, seq_len) grid
    # should hold about the same REQUEST count per dispatch as the
    # bucketed max_batch does at the traffic's typical span — a grid
    # sized for max_batch full-length rows would run mostly-empty at
    # short-sequence loads and pay full-grid FLOPs for it (the
    # capacity-matching rule, docs/serving.md "ragged batching").
    seq_len = cfg.data.seq_len
    buckets = np.asarray(cfg.data.buckets or (seq_len,))
    spans = buckets[np.searchsorted(
        buckets, np.minimum([len(s) + 2 for s in seqs], seq_len))]
    auto_rows = int(np.clip(round(max_batch * float(spans.mean())
                                  / seq_len), 1, max_batch))
    ragged_rows = int(os.environ.get("PBT_SERVE_BENCH_RAGGED_ROWS",
                                     auto_rows))
    # The dense span ladder: in ragged mode the bucket set is purely a
    # span-quantization rule (the compiled shape stays (rows, seq_len)),
    # so a ladder 2x denser than the compiled bucketed one costs ZERO
    # executables — the pad_wasted lever. Its numerics are gated against
    # the offline dense-bucketed reference below (same span semantics).
    step = int(buckets[0])
    dense_buckets = tuple(range(step, seq_len + 1, step))
    if dense_buckets[-1] != seq_len:
        dense_buckets = dense_buckets + (seq_len,)
    tdir = tempfile.mkdtemp(prefix="pbt_serve_ragged_")
    # Fused-path coverage across the whole A/B (ISSUE 10): under
    # use_pallas, the ragged arms' packed executables must land on the
    # Pallas fast path when the kernel supports the shape — gated
    # below from the trace-time PATH_TOTAL delta. The attention kernel
    # (ISSUE 13) is gated the same way from ATTN_PATH_TOTAL.
    from proteinbert_tpu.kernels import attention as _ka
    from proteinbert_tpu.kernels import fused_block as _fb

    path_before = dict(_fb.PATH_TOTAL)
    attn_before = dict(_ka.ATTN_PATH_TOTAL)
    arms = (("bucketed", "bucketed", None),
            ("ragged", "ragged", None),
            ("ragged_dense", "ragged", dense_buckets))
    servers, teles, warm = {}, {}, {}
    for name, mode, arm_buckets in arms:
        tele = Telemetry(events_path=os.path.join(tdir, f"{name}.jsonl"))
        srv = Server(params, cfg, buckets=arm_buckets,
                     max_batch=(ragged_rows if mode == "ragged"
                                else max_batch),
                     max_wait_s=max_wait_s, queue_depth=4 * len(seqs),
                     cache_size=0, warm_kinds=("embed",), telemetry=tele,
                     trace_sample_rate=0.0, serve_mode=mode)
        # Timed batches: pad_fraction lands on every serve_batch event
        # (the pad_wasted accounting below); sampled-out traces keep
        # the per-request hot path at its measured <1% cost.
        srv.scheduler.time_batches = True
        t0 = time.perf_counter()
        srv.start()
        warm[name] = round(time.perf_counter() - t0, 2)
        servers[name], teles[name] = srv, tele

    def run_load(srv, clients):
        results = {}

        def client(worker):
            for i in range(worker, len(seqs), clients):
                try:
                    results[i] = srv.embed(seqs[i], timeout=120)
                except Exception as e:  # noqa: BLE001 — report, don't hang
                    failures.append(f"ragged A/B request {i}: "
                                    f"{type(e).__name__}: {e}")
        threads = [threading.Thread(target=client, args=(w,))
                   for w in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        dt = time.perf_counter() - t0
        deadline = time.monotonic() + 5.0
        prev = -1
        while time.monotonic() < deadline:  # quiesce (phase 2's rule)
            cur = srv.scheduler.stats_counts()[1]  # locked read
            if (cur == prev and len(srv.queue) == 0
                    and srv.scheduler.pending_rows() == 0):
                break
            prev = cur
            time.sleep(0.02)
        return results, dt

    # Warm pass per mode (its results double as the parity population —
    # per-request outputs are independent of batch composition in both
    # modes), then interleaved measured rounds.
    ref = {}
    for mode, srv in servers.items():
        ref[mode], _ = run_load(srv, n_clients)
        if len(ref[mode]) != len(seqs):
            failures.append(
                f"ragged A/B ({mode}): lost requests — "
                f"{len(seqs) - len(ref[mode])} of {len(seqs)} never "
                "resolved")
    rps = {m: [] for m in servers}
    for _ in range(rounds):
        for mode, srv in servers.items():
            res, dt = run_load(srv, n_clients)
            rps[mode].append(len(res) / dt)

    # ---- parity gates (deterministic numerics, so GATED) -------------
    # (a) matched-ladder ragged vs the live bucketed server, per
    # request; (b) dense-ladder ragged vs the OFFLINE dense-bucketed
    # reference (`inference.embed(bucketed=True)` at the dense ladder —
    # same span semantics, compiled the classic way).
    from proteinbert_tpu import inference as _inf

    dense_offline = _inf.embed(params, cfg, seqs, bucketed=True,
                               buckets=dense_buckets,
                               batch_size=max_batch)

    def parity_of(get_ref, name):
        checked = within = bit = 0
        max_diff = 0.0
        for i in range(len(seqs)):
            b, r = get_ref(i), ref[name].get(i)
            if b is None or r is None:
                continue  # the lost-request failure above already fired
            checked += 1
            ok = True
            for k in ("global", "local_mean"):
                max_diff = max(max_diff,
                               float(np.abs(b[k] - r[k]).max()))
                if not np.allclose(b[k], r[k], atol=1e-5, rtol=1e-5):
                    ok = False
            within += ok
            bit += all(np.array_equal(b[k], r[k])
                       for k in ("global", "local_mean"))
        return {"checked": checked, "within_tolerance": within,
                "bit_identical": bit,
                "max_abs_diff": float(f"{max_diff:.3e}")}

    parity = parity_of(ref["bucketed"].get, "ragged")
    if parity["within_tolerance"] != parity["checked"]:
        failures.append(
            f"ragged parity broke: "
            f"{parity['checked'] - parity['within_tolerance']}"
            f"/{parity['checked']} requests outside the documented "
            f"1e-5 tolerance (max |diff| {parity['max_abs_diff']:.2e})")
    parity_dense = parity_of(
        lambda i: {k: dense_offline[k][i]
                   for k in ("global", "local_mean")}, "ragged_dense")
    if parity_dense["within_tolerance"] != parity_dense["checked"]:
        failures.append(
            f"dense-ladder ragged parity vs the offline dense-bucketed "
            f"reference broke: "
            f"{parity_dense['checked'] - parity_dense['within_tolerance']}"
            f"/{parity_dense['checked']} outside 1e-5 "
            f"(max |diff| {parity_dense['max_abs_diff']:.2e})")

    stats = {m: servers[m].stats() for m in servers}
    # O(kinds x row classes) executable gate: one warm kind ("embed")
    # must mean one ragged executable a row class (at most four) —
    # deterministic, so gated (unlike wall-clock) — for BOTH ladders
    # (the dense ladder must cost zero executables).
    for name in ("ragged", "ragged_dense"):
        classes = len(servers[name].dispatcher.batch_classes)
        if stats[name]["executables"] > classes:
            failures.append(
                f"{name} executable count {stats[name]['executables']} "
                f"> {classes} row classes for the single warmed kind")
    # ---- fused fast-path coverage gate (ISSUE 10 acceptance) ---------
    path_delta = {k: _fb.PATH_TOTAL.get(k, 0) - path_before.get(k, 0)
                  for k in set(_fb.PATH_TOTAL) | set(path_before)
                  if _fb.PATH_TOTAL.get(k, 0) != path_before.get(k, 0)}
    fused_path = {
        "use_pallas": bool(cfg.model.use_pallas),
        "delta": {f"{p}/{r}": n for (p, r), n in sorted(path_delta.items())},
    }
    if cfg.model.use_pallas and not _fb.force_reference_requested():
        seg_supported = _fb.pallas_segments_supported(
            cfg.model.local_dim, seq_len,
            servers["ragged"].dispatcher.max_segments, cfg.model.dtype,
            cfg.model.narrow_kernel, cfg.model.wide_kernel,
            cfg.model.wide_dilation)
        fused_path["segments_supported"] = bool(seg_supported)
        if seg_supported:
            if path_delta.get(("pallas", "packed"), 0) < 1:
                failures.append(
                    "ragged A/B under use_pallas: no packed executable "
                    "took the Pallas fast path on a supported shape")
            if path_delta.get(("reference", "segments"), 0):
                failures.append(
                    f"ragged A/B under use_pallas: "
                    f"{path_delta[('reference', 'segments')]} "
                    "reason=segments fallback(s) on a supported shape")
    # ---- attention fast-path coverage gate (ISSUE 13 acceptance) -----
    attn_delta = {k: _ka.ATTN_PATH_TOTAL.get(k, 0) - attn_before.get(k, 0)
                  for k in set(_ka.ATTN_PATH_TOTAL) | set(attn_before)
                  if _ka.ATTN_PATH_TOTAL.get(k, 0) != attn_before.get(k, 0)}
    fused_path["attention_delta"] = {
        f"{p}/{r}": n for (p, r), n in sorted(attn_delta.items())}
    if cfg.model.use_pallas and not _fb.force_reference_requested():
        attn_supported = _ka.pallas_attention_supported(
            cfg.model.local_dim, cfg.model.global_dim, seq_len,
            servers["ragged"].dispatcher.max_segments,
            cfg.model.key_dim, cfg.model.num_heads, cfg.model.dtype)
        fused_path["attention_supported"] = bool(attn_supported)
        if attn_supported:
            if attn_delta.get(("pallas", "packed"), 0) < 1:
                failures.append(
                    "ragged A/B under use_pallas: no packed executable "
                    "took the Pallas ATTENTION fast path on a "
                    "supported shape")
            if attn_delta.get(("reference", "segments"), 0):
                failures.append(
                    f"ragged A/B under use_pallas: "
                    f"{attn_delta[('reference', 'segments')]} attention "
                    "reason=segments fallback(s) on a supported shape")
    for srv in servers.values():
        srv.drain(timeout=60)
    for tele in teles.values():
        tele.close()

    def pad_stats(mode):
        recs = [r for r in read_events(
            os.path.join(tdir, f"{mode}.jsonl"), strict=True)
            if r["event"] == "serve_batch"]
        exec_s = sum(r.get("batch_seconds") or 0.0 for r in recs)
        pad_s = sum((r.get("pad_fraction") or 0.0)
                    * (r.get("batch_seconds") or 0.0) for r in recs)
        pads = [r["pad_fraction"] for r in recs
                if isinstance(r.get("pad_fraction"), (int, float))]
        segs = [r["segments"] for r in recs
                if isinstance(r.get("segments"), int)]
        return {
            "batches": len(recs),
            "execute_s": round(exec_s, 4),
            "pad_wasted_s": round(pad_s, 4),
            "pad_wasted_share": (round(pad_s / exec_s, 4)
                                 if exec_s else None),
            "mean_pad_fraction": (round(sum(pads) / len(pads), 4)
                                  if pads else None),
            "mean_segments_per_batch": (round(sum(segs) / len(segs), 2)
                                        if segs else None),
        }

    per_mode = {}
    for name in servers:
        per_mode[name] = {
            "requests_per_sec": round(_median(rps[name]), 2),
            "rps_per_round": [round(v, 2) for v in rps[name]],
            "executables": stats[name]["executables"],
            "warmup_s": warm[name],
            "warmup_seconds_gauge": stats[name]["warmup_seconds"],
            "batches": stats[name]["batches"],
            "pad": pad_stats(name),
        }
    shutil.rmtree(tdir, ignore_errors=True)
    speedup = (per_mode["ragged"]["requests_per_sec"]
               / max(per_mode["bucketed"]["requests_per_sec"], 1e-9))
    speedup_dense = (per_mode["ragged_dense"]["requests_per_sec"]
                     / max(per_mode["bucketed"]["requests_per_sec"],
                           1e-9))
    return {
        "rounds": rounds,
        "requests": len(seqs),
        "ragged_rows": ragged_rows,
        "mean_span": round(float(spans.mean()), 1),
        "dense_buckets": list(dense_buckets),
        "bucketed": per_mode["bucketed"],
        "ragged": per_mode["ragged"],
        "ragged_dense": per_mode["ragged_dense"],
        # Wall-clock: REPORTED, not gated (the CPU capture for the
        # ≥1.2x acceptance claim lives in docs/performance.md).
        "ragged_speedup_x": round(speedup, 2),
        "ragged_dense_speedup_x": round(speedup_dense, 2),
        "speedup_ge_1_2x": bool(max(speedup, speedup_dense) >= 1.2),
        "parity": parity,
        "parity_dense": parity_dense,
        "fused_path": fused_path,
    }


def _mirror_ragged_note(record):
    """Best-effort mirror of the ragged A/B capture onto the shared
    bench event stream (the sentinel's input)."""
    try:
        from proteinbert_tpu.obs.events import EventLog

        ab = record["ragged_ab"]
        ev = EventLog(BENCH_EVENTS_PATH)
        ev.emit("note", source="bench", kind="serve_ragged_capture",
                platform=record["platform"], seq_len=record["seq_len"],
                n_requests=record["n_requests"],
                ragged_speedup_x=ab["ragged_speedup_x"],
                bucketed_rps=ab["bucketed"]["requests_per_sec"],
                ragged_rps=ab["ragged"]["requests_per_sec"],
                bucketed_executables=ab["bucketed"]["executables"],
                ragged_executables=ab["ragged"]["executables"],
                bucketed_pad_wasted_share=(
                    ab["bucketed"]["pad"]["pad_wasted_share"]),
                ragged_pad_wasted_share=(
                    ab["ragged"]["pad"]["pad_wasted_share"]),
                parity_within_tolerance=ab["parity"]["within_tolerance"],
                parity_checked=ab["parity"]["checked"],
                failures=len(record["failures"]))
        ev.close()
    except Exception as e:
        print(f"bench events stream unavailable: {e}", file=sys.stderr)


def _serve_quant_ab(Server, params, cfg, seqs, max_batch, max_wait_s,
                    n_clients, failures):
    """Phase 5 (ISSUE 12): the SAME request population through a fp32
    bucketed server and a quant=int8 server (weight-only int8
    executables, fp32 parity shadow sampling EVERY batch so the live
    `serve_quant_parity_max` machinery is exercised end to end).

    GATED: every request served on both arms; per-request output
    deviation between the arms within PBT_SERVE_BENCH_QUANT_TOL
    (default 0.15 — weight quantization is a lossy compression, so
    the gate is the documented bound, not the jitted 1e-5); the
    dispatcher's own sampled parity agrees with the externally
    measured one; the quantized trunk's resident weight bytes <= 0.40x
    fp32 (the HBM-footprint claim at these tiny dims; large dims do
    better). REPORTED: per-arm throughput and warmup — wall-clock on a
    shared box is evidence, not a gate."""
    import threading

    from proteinbert_tpu.obs import Telemetry

    rounds = int(os.environ.get("PBT_SERVE_BENCH_QUANT_ROUNDS", 2))
    tol = float(os.environ.get("PBT_SERVE_BENCH_QUANT_TOL", 0.15))
    arms = {}
    outputs = {}
    for arm in ("fp32", "int8"):
        kw = ({"quant": "int8", "quant_parity_every": 1}
              if arm == "int8" else {})
        srv = Server(params, cfg, max_batch=max_batch,
                     max_wait_s=max_wait_s, queue_depth=4 * len(seqs),
                     cache_size=0, warm_kinds=("embed",),
                     telemetry=Telemetry(), trace_sample_rate=None,
                     **kw)
        t0 = time.perf_counter()
        srv.start()
        warm_s = time.perf_counter() - t0
        results = {}

        def client(worker):
            for i in range(worker, len(seqs), n_clients):
                try:
                    results[i] = srv.embed(seqs[i], timeout=120)
                except Exception as e:  # noqa: BLE001
                    failures.append(f"quant A/B ({arm}) request {i}: "
                                    f"{type(e).__name__}: {e}")

        t0 = time.perf_counter()
        for _ in range(rounds):
            threads = [threading.Thread(target=client, args=(w,))
                       for w in range(n_clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        dt = time.perf_counter() - t0
        if len(results) != len(seqs):
            failures.append(f"quant A/B ({arm}) lost requests: "
                            f"{len(results)}/{len(seqs)}")
        outputs[arm] = results
        stats = srv.stats()
        arms[arm] = {
            "requests_per_sec": round(rounds * len(seqs) / dt, 2),
            "warmup_s": round(warm_s, 3),
            "executables": stats["executables"],
            "quant": stats["quant"],
        }
        srv.drain(timeout=60)
    parity_max = 0.0
    for i in outputs["fp32"]:
        if i not in outputs["int8"]:
            continue
        for k in outputs["fp32"][i]:
            parity_max = max(parity_max, float(np.max(np.abs(
                outputs["fp32"][i][k] - outputs["int8"][i][k]))))
    if parity_max > tol:
        failures.append(f"quant arm drifted past the documented bound: "
                        f"per-request parity max {parity_max:.5f} > "
                        f"{tol}")
    q = arms["int8"]["quant"] or {}
    sampled_max = q.get("parity_max", 0.0)
    if not q.get("parity_samples"):
        failures.append("quantized arm recorded no live parity samples "
                        "(quant_parity_every machinery broken)")
    elif sampled_max > tol:
        failures.append(f"dispatcher-sampled quant parity "
                        f"{sampled_max:.5f} > {tol}")
    elif abs(sampled_max - parity_max) > 0.25 * max(parity_max, 1e-6) \
            + 1e-4:
        # The AGREEMENT gate: with parity_every=1 every live batch is
        # shadowed, so the dispatcher's own max over requests must
        # track the externally measured cross-server max (slack covers
        # jitted shape-dependent reassociation between the two servers'
        # batch formations). A shadow that measures nothing (e.g.
        # comparing an arm against itself → 0.0) fails HERE instead of
        # passing both independent bounds.
        failures.append(
            f"dispatcher-sampled parity {sampled_max:.6f} does not "
            f"track the externally measured {parity_max:.6f} — the "
            f"live parity shadow is not measuring real deviation")
    ratio = q.get("weight_bytes_ratio", 1.0)
    if ratio > 0.40:
        failures.append(f"quantized trunk weight bytes ratio {ratio} "
                        "> 0.40x fp32 — the HBM-footprint claim broke")
    return {
        "fp32": arms["fp32"],
        "int8": arms["int8"],
        "quant_speedup_x": round(
            arms["int8"]["requests_per_sec"]
            / max(arms["fp32"]["requests_per_sec"], 1e-9), 3),
        "parity": {"max_abs": round(parity_max, 9), "tolerance": tol,
                   "sampled": q.get("parity_samples", 0),
                   "sampled_max": q.get("parity_max")},
        "weight_bytes_ratio": ratio,
    }


def _mirror_quant_note(record):
    """Best-effort mirror of the quantized-arm A/B capture onto the
    shared bench event stream (the sentinel fits
    serve_quant_requests_per_sec / serve_quant_parity_max from it)."""
    try:
        from proteinbert_tpu.obs.events import EventLog

        ab = record["quant_ab"]
        ev = EventLog(BENCH_EVENTS_PATH)
        ev.emit("note", source="bench", kind="serve_quant_capture",
                platform=record["platform"], seq_len=record["seq_len"],
                n_requests=record["n_requests"],
                quant_requests_per_sec=ab["int8"]["requests_per_sec"],
                fp32_requests_per_sec=ab["fp32"]["requests_per_sec"],
                quant_speedup_x=ab["quant_speedup_x"],
                parity_max=ab["parity"]["max_abs"],
                weight_bytes_ratio=ab["weight_bytes_ratio"],
                failures=len(record["failures"]))
        ev.close()
    except Exception as e:
        print(f"bench events stream unavailable: {e}", file=sys.stderr)


def _serve_fleet_ab(Server, params, cfg, seqs, max_batch, max_wait_s,
                    n_clients, failures):
    """Phase 6 (ISSUE 18): trace-propagation overhead across a real
    two-replica fleet — the SAME request population routed through two
    identically configured routers over the SAME two HTTP replicas,
    one with `propagate_trace=True` (X-PBT-Trace header + one
    fleet_attempt record per try) and one with it off. Measured rounds
    INTERLEAVE arm-by-arm (matched pairs, like the phase-2c tracing
    A/B) and the per-arm MEDIAN is compared.

    GATED (invariants, not wall-clock): every request on both arms
    returns 200 through the router with an X-PBT-Request-Id header,
    and a replica answers a directly injected X-PBT-Trace id back as
    its X-PBT-Request-Id — the end-to-end join. REPORTED:
    `fleet_trace_overhead_pct` (on-vs-off throughput delta, the
    lower-is-better sentinel series — the PR 6 <1% per-request gate in
    phase 2c prices the stamping itself deterministically)."""
    import threading
    import urllib.request

    from proteinbert_tpu.obs import Telemetry
    from proteinbert_tpu.serve.fleet import FleetRouter
    from proteinbert_tpu.serve.http import make_http_server

    rounds = int(os.environ.get("PBT_SERVE_BENCH_FLEET_ROUNDS", 3))
    bodies = [json.dumps({"seq": s}).encode() for s in seqs]

    replicas, httpds, urls = [], [], []
    for i in range(2):
        srv = Server(params, cfg, max_batch=max_batch,
                     max_wait_s=max_wait_s, queue_depth=4 * len(seqs),
                     cache_size=0, warm_kinds=("embed",),
                     telemetry=Telemetry(), trace_sample_rate=0.0,
                     replica_id=f"r{i}")
        srv.start()  # shares the process-wide jit cache — cheap
        httpd = make_http_server(srv, port=0)
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        replicas.append(srv)
        httpds.append(httpd)
        urls.append(f"http://127.0.0.1:{httpd.server_address[1]}")

    # The end-to-end join, checked directly at one replica: an
    # injected fleet id must come back as X-PBT-Request-Id.
    probe = urllib.request.Request(
        urls[0] + "/v1/embed", data=bodies[0],
        headers={"Content-Type": "application/json",
                 "X-PBT-Trace": "bench-fleet-probe"})
    with urllib.request.urlopen(probe, timeout=60) as resp:
        echoed = resp.headers.get("X-PBT-Request-Id")
        resp.read()
    if echoed != "bench-fleet-probe":
        failures.append(
            f"fleet A/B: replica answered X-PBT-Request-Id {echoed!r} "
            "for an injected X-PBT-Trace 'bench-fleet-probe' — the "
            "propagated join is broken")

    arms = []
    for arm, propagate in (("on", True), ("off", False)):
        router = FleetRouter(
            [(f"r{i}", urls[i]) for i in range(2)],
            telemetry=Telemetry(), health_interval_s=0.0,
            max_retries=1, cache_size=0, request_timeout_s=120.0,
            propagate_trace=propagate).start()
        arms.append((arm, router))

    def run_round(router) -> float:
        results = {}

        def client(worker: int) -> None:
            for i in range(worker, len(seqs), n_clients):
                try:
                    status, _body, hdrs = router.route("/v1/embed",
                                                       bodies[i])
                    results[i] = (status, hdrs.get("X-PBT-Request-Id"))
                except Exception as e:  # noqa: BLE001 — report, not hang
                    failures.append(f"fleet A/B request {i}: "
                                    f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=client, args=(w,))
                   for w in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        dt = time.perf_counter() - t0
        bad = [i for i, (status, rid) in results.items()
               if status != 200 or not rid]
        if len(results) != len(seqs) or bad:
            failures.append(
                f"fleet A/B: {len(seqs) - len(results)} lost, "
                f"{len(bad)} non-200/unlabeled of {len(seqs)}")
        return len(seqs) / dt

    rps = {arm: [] for arm, _ in arms}
    for arm, router in arms:
        run_round(router)  # warm pass (connection setup, jit reuse)
    for _ in range(rounds):
        for arm, router in arms:
            rps[arm].append(run_round(router))

    for _, router in arms:
        router.drain()
    for httpd in httpds:
        httpd.shutdown()
        httpd.server_close()
    for srv in replicas:
        srv.drain(timeout=60)

    from statistics import median as _median

    rps_on = _median(rps["on"])
    rps_off = _median(rps["off"])
    overhead_pct = (1.0 - rps_on / max(rps_off, 1e-9)) * 100.0
    return {
        "rounds": rounds,
        "rps_per_round": {a: [round(v, 2) for v in vals]
                          for a, vals in rps.items()},
        "fleet_rps_on": round(rps_on, 2),
        "fleet_rps_off": round(rps_off, 2),
        "fleet_trace_overhead_pct": round(overhead_pct, 3),
    }


def _mirror_fleet_note(record):
    """Best-effort mirror of the fleet propagation A/B onto the shared
    bench event stream (the sentinel fits fleet_trace_overhead_pct
    from it, lower-is-better). The pct is the MEDIAN over `rounds` A/B
    rounds and the note carries that round count (ISSUE 19 satellite:
    the series is a near-zero-centered difference, so the sentinel
    holds an absolute noise floor for it — see tools/bench_trajectory
    `_ABS_FLOOR` — and the rounds field keeps the capture auditable)."""
    try:
        from proteinbert_tpu.obs.events import EventLog

        ab = record["fleet_ab"]
        ev = EventLog(BENCH_EVENTS_PATH)
        ev.emit("note", source="bench", kind="fleet_trace_capture",
                platform=record["platform"], seq_len=record["seq_len"],
                n_requests=record["n_requests"],
                fleet_trace_overhead_pct=ab["fleet_trace_overhead_pct"],
                fleet_rps_on=ab["fleet_rps_on"],
                fleet_rps_off=ab["fleet_rps_off"],
                rounds=ab["rounds"],
                failures=len(record["failures"]))
        ev.close()
    except Exception as e:
        print(f"bench events stream unavailable: {e}", file=sys.stderr)


def _serve_pipeline_ab(Server, params, cfg, seqs, max_batch, max_wait_s,
                       n_clients, failures):
    """Phase 7 (ISSUE 19): pipelined-dispatch A/B — the SAME request
    population through a depth-1 server (strictly serial submit →
    fetch → seal per batch) and a depth-2 server (bounded in-flight
    window: the scheduler forms batch N+1 while the completer thread
    finalizes batch N).

    GATED (invariants, not wall-clock — appended to `failures`):
    - async-vs-sync BIT-parity: one full same-bucket micro-batch,
      formed deterministically on both depths (phase 3a's rule:
      max_wait 60s + exactly max_batch same-bucket submits in FIFO
      order → identical rows through the identical executable), must
      produce bit-identical per-request outputs — the submit/fetch
      split may move the host fetch, never the math;
    - zero lost/duplicate seals under drain() with work in flight: a
      full burst submitted and immediately drained must resolve every
      future exactly once, and the fully-traced serve_request stream
      must carry exactly one record per submitted request with no
      duplicated ids;
    - overlap observed on the serve path: the depth-2 window actually
      filled (pipeline inflight_max >= 2) under sustained load;
    - the map path: a tiny `run_map` pipeline-on vs pipeline-off over
      the same corpus writes BYTE-identical stores (same digest maps —
      commit order is the contract), with overlap observed
      (map overlap_ratio > 0) on the pipelined run.

    REPORTED: sustained requests/s per depth (median over interleaved
    rounds) and `serve_pipeline_speedup_x` — the sentinel series
    (platform-split). Wall-clock is evidence, not a gate (the honest-
    CPU rule): off-TPU the host fetch the pipeline overlaps is
    microseconds, so the ratio hovers near 1.0 — the CPU points keep
    the series alive and honestly labeled while the gates above carry
    the contract."""
    import shutil
    import tempfile
    import threading
    from statistics import median as _median

    from proteinbert_tpu.obs import Telemetry, read_events

    rounds = int(os.environ.get("PBT_SERVE_BENCH_PIPELINE_ROUNDS", 3))
    tdir = tempfile.mkdtemp(prefix="pbt_serve_pipeline_")

    servers, teles = {}, {}
    for name, depth in (("serial", 1), ("pipelined", 2)):
        tele = Telemetry(events_path=os.path.join(tdir, f"{name}.jsonl"))
        srv = Server(params, cfg, max_batch=max_batch,
                     max_wait_s=max_wait_s, queue_depth=4 * len(seqs),
                     cache_size=0, warm_kinds=("embed",), telemetry=tele,
                     trace_sample_rate=1.0, pipeline_depth=depth)
        srv.start()
        servers[name], teles[name] = srv, tele

    def run_load(srv, clients):
        results = {}

        def client(worker):
            for i in range(worker, len(seqs), clients):
                try:
                    results[i] = srv.embed(seqs[i], timeout=120)
                except Exception as e:  # noqa: BLE001 — report, don't hang
                    failures.append(f"pipeline A/B request {i}: "
                                    f"{type(e).__name__}: {e}")
        threads = [threading.Thread(target=client, args=(w,))
                   for w in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        dt = time.perf_counter() - t0
        deadline = time.monotonic() + 5.0
        prev = -1
        while time.monotonic() < deadline:  # quiesce (phase 2's rule)
            cur = srv.scheduler.stats_counts()[1]  # locked read
            if (cur == prev and len(srv.queue) == 0
                    and srv.scheduler.pending_rows() == 0):
                break
            prev = cur
            time.sleep(0.02)
        return results, dt

    # Warm pass per depth (lost-request gate), then interleaved
    # measured rounds (matched pairs, like every other serve A/B).
    for name, srv in servers.items():
        res, _ = run_load(srv, n_clients)
        if len(res) != len(seqs):
            failures.append(
                f"pipeline A/B ({name}): lost requests — "
                f"{len(seqs) - len(res)} of {len(seqs)} never resolved")
    rps = {m: [] for m in servers}
    for _ in range(rounds):
        for name, srv in servers.items():
            res, dt = run_load(srv, n_clients)
            rps[name].append(len(res) / dt)

    # ---- async-vs-sync bit-parity on a deterministic batch -----------
    by_bucket = {}
    for s in seqs:
        blen = servers["serial"].dispatcher.bucket_len(len(s))
        by_bucket.setdefault(blen, []).append(s)
    group = max(by_bucket.values(), key=len)
    group = (group * max_batch)[:max_batch]
    outs = {}
    for depth in (1, 2):
        psrv = Server(params, cfg, max_batch=len(group), max_wait_s=60.0,
                      cache_size=0, warm_kinds=(), pipeline_depth=depth)
        psrv.start()  # depth 2 needs the live completer thread
        futs = [psrv.submit("embed", s) for s in group]
        outs[depth] = [f.result(timeout=120) for f in futs]
        psrv.drain(timeout=60)
    bit = sum(
        all(np.array_equal(a[k], b[k]) for k in ("global", "local_mean"))
        for a, b in zip(outs[1], outs[2]))
    if bit != len(group):
        failures.append(
            f"pipeline A/B parity broke: {len(group) - bit}/{len(group)} "
            "async-path outputs not BIT-identical to the serial path on "
            "an identical deterministically formed batch")

    # ---- exactly-once sealing under drain with work in flight --------
    burst = [servers["pipelined"].submit("embed", s) for s in seqs]
    servers["pipelined"].drain(timeout=120)
    unresolved = sum(1 for f in burst if not f.done())
    errored = sum(1 for f in burst if f.done() and f.exception())
    if unresolved or errored:
        failures.append(
            f"pipeline A/B drain-with-work-in-flight: {unresolved} "
            f"unresolved / {errored} errored of {len(burst)} burst "
            "futures — the window lost or poisoned seals")

    pstats = servers["pipelined"].scheduler.pipeline_stats()
    if pstats["inflight_max"] < 2:
        failures.append(
            f"pipeline A/B: depth-2 window never filled (inflight_max "
            f"{pstats['inflight_max']} < 2) — no overlap observed on "
            "the serve path")

    servers["serial"].drain(timeout=60)
    for tele in teles.values():
        tele.close()

    # Every submitted request → exactly one fully-traced serve_request
    # record, no duplicated ids (the exactly-once seal, observed from
    # the event stream rather than asserted from the implementation).
    recs = [r for r in read_events(
        os.path.join(tdir, "pipelined.jsonl"), strict=True)
        if r["event"] == "serve_request"]
    ids = [r["request_id"] for r in recs]
    expected = (1 + rounds) * len(seqs) + len(burst)
    if len(ids) != expected or len(set(ids)) != len(ids):
        failures.append(
            f"pipeline A/B seal accounting: {len(ids)} serve_request "
            f"records ({len(ids) - len(set(ids))} duplicated ids) for "
            f"{expected} submitted requests — lost or duplicate seals")

    # ---- map path: pipelined run_map writes the SAME bytes -----------
    from proteinbert_tpu.mapper import run_map, store_digests

    map_seqs = [seqs[i % len(seqs)] for i in range(24)]
    map_ids = [f"m{i}" for i in range(len(map_seqs))]
    map_res, map_dirs = {}, {}
    for name, flag in (("on", True), ("off", False)):
        sdir = os.path.join(tdir, f"map_{name}")
        map_dirs[name] = sdir
        map_res[name] = run_map(params, cfg, map_ids, map_seqs, sdir,
                                num_shards=2, block_size=4,
                                rows_per_batch=max_batch,
                                pipeline=flag)
        if map_res[name]["outcome"] != "completed":
            failures.append(
                f"pipeline A/B map ({name}): outcome "
                f"{map_res[name]['outcome']!r}, expected 'completed'")
    map_identical = (store_digests(map_dirs["on"])
                     == store_digests(map_dirs["off"]))
    if not map_identical:
        failures.append(
            "pipeline A/B map: pipelined store digests differ from the "
            "serial store — commit order or bytes drifted")
    if map_res["on"].get("overlap_ratio", 0.0) <= 0.0:
        failures.append(
            "pipeline A/B map: overlap_ratio is 0 with pipelining on — "
            "no overlap observed on the map path")

    shutil.rmtree(tdir, ignore_errors=True)

    rps_serial = _median(rps["serial"])
    rps_pipe = _median(rps["pipelined"])
    return {
        "rounds": rounds,
        "rps_per_round": {m: [round(v, 2) for v in vals]
                          for m, vals in rps.items()},
        "serial_rps": round(rps_serial, 2),
        "pipeline_rps": round(rps_pipe, 2),
        "serve_pipeline_speedup_x": round(
            rps_pipe / max(rps_serial, 1e-9), 3),
        "serve_overlap_ratio": pstats["overlap_ratio"],
        "inflight_max": pstats["inflight_max"],
        "finalize_seconds_total": pstats["finalize_seconds_total"],
        "parity": {"checked": len(group), "bit_identical": bit},
        "seal": {"expected": expected, "serve_request_events": len(ids),
                 "unique_ids": len(set(ids))},
        "map": {"overlap_ratio": map_res["on"].get("overlap_ratio", 0.0),
                "byte_identical": map_identical},
    }


def _mirror_pipeline_note(record):
    """Best-effort mirror of the pipelined-dispatch A/B onto the shared
    bench event stream (the sentinel fits serve_pipeline_speedup_x
    from it; platform-split, so off-TPU points stay honestly labeled
    rather than polluting a TPU trajectory)."""
    try:
        from proteinbert_tpu.obs.events import EventLog

        ab = record["pipeline_ab"]
        ev = EventLog(BENCH_EVENTS_PATH)
        ev.emit("note", source="bench", kind="serve_pipeline_capture",
                platform=record["platform"], seq_len=record["seq_len"],
                n_requests=record["n_requests"],
                serve_pipeline_speedup_x=ab["serve_pipeline_speedup_x"],
                pipeline_rps=ab["pipeline_rps"],
                serial_rps=ab["serial_rps"],
                serve_overlap_ratio=ab["serve_overlap_ratio"],
                inflight_max=ab["inflight_max"],
                failures=len(record["failures"]))
        ev.close()
    except Exception as e:
        print(f"bench events stream unavailable: {e}", file=sys.stderr)


def run_serve(length_mix=None):
    """`bench.py --serve`: sustained-load online serving vs the
    one-request-at-a-time offline baseline — one JSON line, CPU-
    measurable (ISSUE 5 acceptance).

    Three phases over one tiny trunk (untrained params: FLOPs and
    dispatch behavior are weight-independent):

    1. **baseline** — sequential single-request `inference.embed`
       calls (batch 1, every request padded to the full seq_len): the
       only serving story the repo had before the serve/ subsystem.
    2. **served** — the same request population pushed through
       `serve.Server` (continuous micro-batching over length buckets,
       cache OFF so every row pays a real model call), in two load
       shapes: a SATURATED closed loop (N concurrent client threads,
       enough to keep every bucket's group full — the throughput
       number and the ≥3x-vs-baseline claim), then a LIGHT load
       (fewer clients than one micro-batch) where end-to-end latency
       is the scheduler's contract rather than queueing theory: p99
       must stay under max_wait + one batch time (slowest observed
       batch, plus a small OS-jitter allowance on a shared CI box).
    3. **contracts** — (a) served-vs-offline BIT-parity per bucket: a
       full micro-batch formed deterministically through submit()+
       poll() must equal `inference.embed(bucketed=True)` at the same
       (bucket_len, batch_class) shape; (b) queue overflow on a server
       with a tiny bounded queue: every overflow victim observes a
       typed QueueFullError (rejected, never dropped).

    Exit code is nonzero when a CONTRACT fails (parity, lost requests,
    un-rejected overflow); the speedup is reported, not gated — wall-
    clock ratios on a noisy CI box are evidence, not invariants. The
    capture is mirrored as a `note` on bench_events.jsonl like the
    other sweeps.

    4. **ragged A/B** (ISSUE 9) — the SAME mixed-length population
       through a bucketed server and a ragged packed server
       (`serve_mode="ragged"`: requests pack into fixed-shape
       (max_batch, seq_len) rows, one warm executable per kind).
       GATED: every ragged per-request output matches the bucketed
       dispatcher's within the documented jitted ≤1e-5 tolerance
       (bucket-quantized spans — docs/serving.md), no request lost,
       ragged warm-executable count stays O(kinds). REPORTED: the
       sustained-load speedup (the ≥1.2x acceptance capture), warm
       executable counts, warmup seconds, and per-mode `pad_wasted`
       (pad_fraction-weighted execute seconds) from the serve_batch
       streams.

    `length_mix` (--serve-length-mix 'median=48,sigma=0.9,seed=7')
    reshapes the log-normal request-length population so the benchmark
    measures the padding waste ragged serving exists to remove; default
    traffic is byte-identical to earlier captures.

    PBT_SERVE_BENCH_PHASES selects phases: "all" (default), "core"
    (1-3 only — the historical smoke), "ragged" (phase 4 only — the
    tier-1 ragged stage), "quant" (phase 5), "fleet" (phase 6 — the
    ISSUE 18 trace-propagation on-vs-off A/B over two HTTP replicas,
    feeding the fleet_trace_overhead_pct sentinel series), "pipeline"
    (phase 7 — the ISSUE 19 pipelined-dispatch depth-1 vs depth-2 A/B:
    async-vs-sync bit-parity, exactly-once sealing under drain with
    work in flight, overlap observed on BOTH the serve and map paths,
    feeding the serve_pipeline_speedup_x sentinel series).

    Knobs: PBT_SERVE_BENCH_SEQ_LEN (512), PBT_SERVE_BENCH_DIM (64),
    PBT_SERVE_BENCH_REQUESTS (96), PBT_SERVE_BENCH_CLIENTS (16),
    PBT_SERVE_BENCH_MAX_BATCH (8), PBT_SERVE_BENCH_TRACE_ROUNDS (5),
    PBT_SERVE_BENCH_RAGGED_ROUNDS (3), PBT_SERVE_BENCH_FLEET_ROUNDS
    (3), PBT_SERVE_BENCH_PIPELINE_ROUNDS (3),
    PBT_SERVE_BENCH_MEDIAN_LEN (seq_len // 8).
    """
    import threading

    import jax

    device = bench_device()

    from proteinbert_tpu import inference
    from proteinbert_tpu.configs import (
        DataConfig, ModelConfig, OptimizerConfig, PretrainConfig,
        TrainConfig,
    )
    from proteinbert_tpu.data.vocab import ALPHABET
    from proteinbert_tpu.serve import QueueFullError, Server
    from proteinbert_tpu.train import create_train_state

    phases_env = os.environ.get("PBT_SERVE_BENCH_PHASES", "all").strip()
    wanted = ({"core", "ragged", "quant", "fleet", "pipeline"}
              if phases_env == "all"
              else {p for p in phases_env.split(",") if p})
    bad = wanted - {"core", "ragged", "quant", "fleet", "pipeline"}
    if bad or not wanted:
        raise SystemExit(f"PBT_SERVE_BENCH_PHASES must name phases from "
                         f"core,ragged,quant,fleet,pipeline or 'all'; "
                         f"got {phases_env!r}")

    seq_len = int(os.environ.get("PBT_SERVE_BENCH_SEQ_LEN", 512))
    dim = int(os.environ.get("PBT_SERVE_BENCH_DIM", 64))
    n_requests = int(os.environ.get("PBT_SERVE_BENCH_REQUESTS", 96))
    n_clients = int(os.environ.get("PBT_SERVE_BENCH_CLIENTS", 32))
    max_batch = int(os.environ.get("PBT_SERVE_BENCH_MAX_BATCH", 8))
    median = int(os.environ.get("PBT_SERVE_BENCH_MEDIAN_LEN", seq_len // 10))
    max_wait_s = 0.01

    # PBT_SERVE_BENCH_USE_PALLAS=1: serve through the fused Pallas
    # local track (interpret mode off-TPU) — with a lane-aligned DIM
    # (128+) the ragged arms run the segment-aware packed fast path and
    # phase 4 GATES that coverage (ISSUE 10 acceptance).
    use_pallas = bool(int(os.environ.get("PBT_SERVE_BENCH_USE_PALLAS", 0)))
    model = ModelConfig(local_dim=dim, global_dim=2 * dim, key_dim=16,
                        num_heads=4, num_blocks=2,
                        num_annotations=max(4 * dim, 128),
                        dtype="float32", use_pallas=use_pallas)
    buckets = tuple(sorted({max(16, seq_len // 8), seq_len // 4,
                            seq_len // 2, seq_len}))
    cfg = PretrainConfig(
        model=model,
        data=DataConfig(seq_len=seq_len, batch_size=max_batch,
                        buckets=buckets),
        optimizer=OptimizerConfig(warmup_steps=10),
        train=TrainConfig(max_steps=1))
    params = create_train_state(jax.random.PRNGKey(0), cfg).params

    # UniRef-like ragged lengths, clipped to the model window. With no
    # --serve-length-mix this is BYTE-IDENTICAL traffic to every
    # earlier capture (median seq_len//10, sigma 0.45, seed 0).
    mix_median, mix_sigma, mix_seed = parse_length_mix(length_mix)
    if mix_median is None:
        mix_median = median
    else:
        median = mix_median
    rng = np.random.default_rng(mix_seed)
    lengths = np.clip(
        rng.lognormal(mean=np.log(mix_median), sigma=mix_sigma,
                      size=n_requests),
        10, seq_len - 2).astype(np.int64)
    alphabet = np.array(list(ALPHABET))
    seqs = ["".join(rng.choice(alphabet, size=int(L))) for L in lengths]

    if "core" not in wanted:
        # Off-core run (the tier-1 ragged/quant smoke stages): skip the
        # baseline/tracing/overflow phases and gate just the selected
        # A/B contracts.
        failures = []
        record = {
            "metric": ("serve_ragged" if "ragged" in wanted
                       else "serve_quant" if "quant" in wanted
                       else "serve_fleet" if "fleet" in wanted
                       else "serve_pipeline"),
            **device,
            "seq_len": seq_len, "model_dim": dim, "median_len": median,
            "length_sigma": mix_sigma, "buckets": list(buckets),
            "max_batch": max_batch, "n_requests": n_requests,
            "failures": failures,
        }
        if "ragged" in wanted:
            record["ragged_ab"] = _serve_ragged_ab(
                Server, params, cfg, seqs, max_batch, max_wait_s,
                n_clients, failures)
            _mirror_ragged_note(record)
        if "quant" in wanted:
            record["quant_ab"] = _serve_quant_ab(
                Server, params, cfg, seqs, max_batch, max_wait_s,
                n_clients, failures)
            _mirror_quant_note(record)
        if "fleet" in wanted:
            record["fleet_ab"] = _serve_fleet_ab(
                Server, params, cfg, seqs, max_batch, max_wait_s,
                n_clients, failures)
            _mirror_fleet_note(record)
        if "pipeline" in wanted:
            record["pipeline_ab"] = _serve_pipeline_ab(
                Server, params, cfg, seqs, max_batch, max_wait_s,
                n_clients, failures)
            _mirror_pipeline_note(record)
        print(json.dumps(record))
        if failures:
            for f in failures:
                print(f"SERVE CONTRACT FAILURE: {f}", file=sys.stderr)
            sys.exit(1)
        return

    # ---- phase 1: sequential single-request offline baseline ----------
    inference.embed(params, cfg, [seqs[0]], batch_size=1)  # compile
    base_n = min(n_requests, max(2 * max_batch, 24))
    t0 = time.perf_counter()
    for s in seqs[:base_n]:
        inference.embed(params, cfg, [s], batch_size=1)
    base_dt = time.perf_counter() - t0
    baseline = {"requests": base_n,
                "requests_per_sec": round(base_n / base_dt, 2),
                "ms_per_request": round(base_dt / base_n * 1e3, 2)}

    # ---- phase 2: sustained concurrent load through the server --------
    from proteinbert_tpu.obs import Telemetry

    failures = []
    # Metrics-only telemetry (no events file): the registry's
    # serve_batch_seconds histogram supplies the p99-bound batch time.
    # trace_sample_rate=None: the headline server is UNTRACED — the
    # tracing cost is measured separately in phase 2c.
    server = Server(params, cfg, max_batch=max_batch, max_wait_s=max_wait_s,
                    queue_depth=4 * n_requests, cache_size=0,
                    warm_kinds=("embed",), telemetry=Telemetry(),
                    trace_sample_rate=None)
    t0 = time.perf_counter()
    server.start()
    warm_s = time.perf_counter() - t0
    def run_load(srv, indices, clients) -> tuple:
        results = {}

        def client(worker: int) -> None:
            for i in indices[worker::clients]:
                try:
                    results[i] = srv.embed(seqs[i], timeout=120)
                except Exception as e:  # noqa: BLE001 — report, don't hang
                    failures.append(f"request {i}: {type(e).__name__}: {e}")

        threads = [threading.Thread(target=client, args=(w,))
                   for w in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        dt = time.perf_counter() - t0
        # Quiesce: a request's future resolves BEFORE the scheduler
        # records its latency, so returning the moment all futures are
        # done races the last batch's bookkeeping (and a stale
        # saturated-phase sample landing in the light window would be a
        # spurious p99 failure). rows_total is bumped after the whole
        # batch's latencies are observed — wait for it to go stable
        # with nothing queued or pending.
        deadline = time.monotonic() + 5.0
        prev = -1
        while time.monotonic() < deadline:
            cur = srv.scheduler.stats_counts()[1]  # locked read
            pending = srv.scheduler.pending_rows()
            if cur == prev and len(srv.queue) == 0 and pending == 0:
                break
            prev = cur
            time.sleep(0.02)
        return results, dt

    # Saturated closed loop: enough concurrent clients that every
    # bucket's group keeps filling — the throughput measurement.
    sat_results, sat_dt = run_load(server, list(range(n_requests)),
                                   n_clients)
    sat_stats = server.stats()
    if len(sat_results) != n_requests:
        failures.append(
            f"lost requests: {n_requests - len(sat_results)} of "
            f"{n_requests} never resolved")

    # Light load: fewer clients than one micro-batch, so nothing queues
    # behind a saturated device — end-to-end latency is the scheduler
    # contract (≤ max_wait + one batch time), not queueing delay.
    light_n = max(max_batch, n_requests // 4)
    light_window = type(server.latencies)()
    server.latencies = light_window  # fresh percentile ring
    light_results, _ = run_load(server, list(range(light_n)),
                                max(2, max_batch // 2))
    batch_h = server.tele.metrics.histogram("serve_batch_seconds")
    max_batch_s = batch_h.max if batch_h.count else 0.0
    server.drain(timeout=60)
    p99 = light_window.percentile(99) or 0.0
    # Allowance on top of the contract bound: the scheduler's idle park
    # (max_wait/2) plus thread-wakeup jitter on a shared CI box. The
    # bound is REPORTED (light_p99_within_bound), not a gate failure:
    # wall-clock on a noisy CI box is evidence, not an invariant — the
    # light window holds ~light_n samples, so its p99 is effectively
    # the max sample and one OS scheduling hiccup would flake tier-1.
    p99_bound = max_wait_s + max_batch_s + max_wait_s / 2 + 0.01
    if len(light_results) != light_n:
        failures.append(f"light phase lost requests: "
                        f"{light_n - len(light_results)} of {light_n} "
                        "never resolved")
    served = {
        "requests": len(sat_results),
        "clients": n_clients,
        "requests_per_sec": round(n_requests / sat_dt, 2),
        "saturated_p50_ms": round(
            (sat_stats["latency"]["p50_s"] or 0.0) * 1e3, 2),
        "saturated_p99_ms": round(
            (sat_stats["latency"]["p99_s"] or 0.0) * 1e3, 2),
        "light_p50_ms": round((light_window.percentile(50) or 0.0) * 1e3,
                              2),
        "light_p99_ms": round(p99 * 1e3, 2),
        "max_wait_ms": round(max_wait_s * 1e3, 2),
        "max_batch_ms": round(max_batch_s * 1e3, 2),
        "light_p99_bound_ms": round(p99_bound * 1e3, 2),
        "light_p99_within_bound": bool(p99 <= p99_bound),
        "batches": sat_stats["batches"],
        "mean_rows_per_batch": round(
            sat_stats["batched_rows"] / max(sat_stats["batches"], 1), 2),
        "warmup_s": round(warm_s, 2),
    }

    # ---- phase 2c: request tracing — overhead + correctness -----------
    # Three matched conditions over the same saturated population:
    #   null        — telemetry NULL (the must-stay-a-no-op path);
    #   sampled_out — telemetry on, trace_sample_rate=0: every request
    #                 carries the cheap clock marks but nothing emits
    #                 (the "<1% of served-request latency" claim);
    #   full        — sample rate 1.0 + events file + span collector.
    # All three servers warm first, then measured passes INTERLEAVE
    # round-robin (matched pairs): CPU-frequency/contention drift on a
    # shared box hits every condition equally instead of whichever ran
    # last, and the per-condition MEDIAN over rounds is compared.
    # CORRECTNESS is GATED on the full condition (invariants, not
    # wall-clock): every request yields a schema-valid serve_request
    # event whose contiguous stages sum to its e2e latency, and spans
    # land in the collector. The overhead percentages are REPORTED —
    # wall-clock ratios on a shared CI box are evidence, not a gate.
    import tempfile

    from proteinbert_tpu.obs import read_events

    trace_dir = tempfile.mkdtemp(prefix="pbt_serve_trace_")
    trace_events = os.path.join(trace_dir, "events.jsonl")
    # Measured A/B passes per condition (report-only medians; the <1%
    # gate below is the deterministic timeit measurement) — tunable so
    # budgeted runs (tier-1 smoke) can trim the load matrix.
    rounds = int(os.environ.get("PBT_SERVE_BENCH_TRACE_ROUNDS", 5))

    sampled_tele = Telemetry(events_path=os.path.join(trace_dir,
                                                      "sampled.jsonl"))
    ttele = Telemetry(events_path=trace_events, spans=True)
    conditions = (("null", None, None),
                  ("sampled_out", sampled_tele, 0.0),
                  ("full", ttele, 1.0))
    ab_servers = []
    rps = {}
    for name, tele_c, rate in conditions:
        srv = Server(params, cfg, max_batch=max_batch,
                     max_wait_s=max_wait_s, queue_depth=4 * n_requests,
                     cache_size=0, warm_kinds=("embed",),
                     telemetry=tele_c, trace_sample_rate=rate)
        srv.start()  # reuses the process-wide jit cache — cheap
        run_load(srv, list(range(n_requests)), n_clients)  # warm pass
        ab_servers.append((name, srv))
        rps[name] = []
    for _ in range(rounds):
        for name, srv in ab_servers:
            results, dt = run_load(srv, list(range(n_requests)),
                                   n_clients)
            rps[name].append(len(results) / dt)
    for _, srv in ab_servers:
        srv.drain(timeout=60)
    sampled_tele.close()
    ttele.close()

    from statistics import median as _median

    null_rps = _median(rps["null"])
    sampled_rps = _median(rps["sampled_out"])
    full_rps = _median(rps["full"])
    sampled_overhead = (1.0 - sampled_rps / max(null_rps, 1e-9)) * 100.0
    full_overhead = (1.0 - full_rps / max(null_rps, 1e-9)) * 100.0
    trace_recs = [r for r in read_events(trace_events, strict=True)
                  if r["event"] == "serve_request"]
    expected = (rounds + 1) * n_requests  # warm + measured passes
    if len(trace_recs) != expected:
        failures.append(
            f"tracing: expected {expected} serve_request events "
            f"at sample rate 1.0, got {len(trace_recs)}")
    bad_sums = 0
    for r in trace_recs:
        if abs(sum(r["stages"].values()) - r["e2e_s"]) > 1e-5:
            bad_sums += 1
    if bad_sums:
        failures.append(
            f"tracing: {bad_sums}/{len(trace_recs)} serve_request "
            "events whose stages do not sum to e2e_s")
    if len(ttele.spans or ()) == 0:
        failures.append("tracing: span collector stayed empty")
    # Sampled-out emissions would break the sampling contract: at rate
    # 0 no SUCCESSFUL request may emit (errors/rejections always do,
    # by design — only ok/cache_hit outcomes are violations here).
    sampled_recs = [r for r in read_events(
        os.path.join(trace_dir, "sampled.jsonl"), strict=True)
        if r["event"] == "serve_request"
        and r["outcome"] in ("ok", "cache_hit")]
    if sampled_recs:
        failures.append(
            f"tracing: {len(sampled_recs)} successful serve_request "
            "events emitted at sample rate 0")
    # The "<1% of served-request latency" contract, measured the way
    # the claim is stated: the EXACT per-request hot path a sampled-out
    # request pays (trace create + every clock mark + batch stamp +
    # seal, no stage dict — Server._seal skips it with no consumer),
    # timed deterministically, against the FASTEST latency any request
    # sees (the sequential baseline — saturated/light served latencies
    # are strictly larger, so <1% here is <1% everywhere). The A/B
    # throughput medians above are kept for honesty, but on a 2-core
    # box their round-to-round swing is far wider than 1%: the ratio
    # measures scheduler-thread contention, not the trace cost.
    import timeit as _timeit

    from proteinbert_tpu.serve.trace import RequestTrace

    def _trace_hot_path():
        tr = RequestTrace("bench-1f", "embed", time.monotonic(),
                          sampled=False)
        # Fleet propagation rides the same hot path (ISSUE 18): every
        # routed request joins the router's trace id and answers with
        # public_id() — so the <1% gate prices that stamping in too.
        tr.join("f1a2-3f", "r0")
        tr.public_id()
        tr.mark_enqueued(time.monotonic())
        tr.mark_ingested(time.monotonic())
        tr.mark_popped(time.monotonic())
        t0 = time.monotonic()
        tr.mark_run(t0, time.monotonic())
        tr.mark_batch(seq_len, max_batch, max_batch, 0.3, 0.001, 0.002)
        tr.finish("ok", time.monotonic())
        return tr.e2e_s()

    reps = 20000
    trace_cost_us = min(
        _timeit.timeit(_trace_hot_path, number=reps) / reps * 1e6
        for _ in range(3))
    baseline_latency_us = baseline["ms_per_request"] * 1e3
    trace_cost_pct = 100.0 * trace_cost_us / baseline_latency_us

    tracing = {
        "rounds": rounds,
        "rps_per_round": {name: [round(v, 2) for v in vals]
                          for name, vals in rps.items()},
        "null_requests_per_sec": round(null_rps, 2),
        "sampled_out_requests_per_sec": round(sampled_rps, 2),
        "full_requests_per_sec": round(full_rps, 2),
        "sampled_out_overhead_pct": round(sampled_overhead, 2),
        "full_overhead_pct": round(full_overhead, 2),
        "trace_cost_us_per_request": round(trace_cost_us, 2),
        "trace_cost_pct_of_fastest_latency": round(trace_cost_pct, 3),
        "sampled_out_within_1pct": bool(trace_cost_pct < 1.0),
        "serve_request_events": len(trace_recs),
        "stage_sum_mismatches": bad_sums,
        "spans": len(ttele.spans or ()),
    }
    if trace_cost_pct >= 1.0:
        failures.append(
            f"tracing: sampled-out per-request cost {trace_cost_us:.1f}us "
            f"is {trace_cost_pct:.2f}% of the fastest served-request "
            f"latency ({baseline_latency_us:.0f}us) — breaks the <1% "
            "contract")
    import shutil

    shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- phase 3a: served-vs-offline bit-parity per bucket ------------
    parity = {}
    by_bucket = {}
    for s in seqs:
        by_bucket.setdefault(server.dispatcher.bucket_len(len(s)), []).append(s)
    for bucket, group in sorted(by_bucket.items()):
        group = group[:max_batch]
        psrv = Server(params, cfg, max_batch=len(group), max_wait_s=60.0,
                      cache_size=0, warm_kinds=())
        futures = [psrv.submit("embed", s) for s in group]
        psrv.scheduler.poll()  # deterministic single-batch formation
        offline = inference.embed(params, cfg, group, bucketed=True,
                                  buckets=buckets, batch_size=len(group))
        ok = all(
            np.array_equal(f.result(timeout=0)["global"],
                           offline["global"][i])
            and np.array_equal(f.result(timeout=0)["local_mean"],
                               offline["local_mean"][i])
            for i, f in enumerate(futures))
        parity[str(bucket)] = {"rows": len(group), "bit_identical": ok}
        if not ok:
            failures.append(f"served-vs-offline parity broke in "
                            f"bucket {bucket}")

    # ---- phase 3b: overflow is rejected, never dropped ----------------
    depth = max(2, max_batch // 2)
    osrv = Server(params, cfg, max_batch=max_batch, max_wait_s=60.0,
                  queue_depth=depth, cache_size=0, warm_kinds=())
    burst = [osrv.submit("embed", s) for s in seqs[: depth + 6]]
    rejected = sum(
        1 for f in burst
        if f.done() and isinstance(f.exception(), QueueFullError))
    osrv.abort()
    resolved = sum(1 for f in burst if f.done())
    overflow = {"submitted": len(burst), "queue_depth": depth,
                "rejected_queue_full": rejected,
                "all_observed": resolved == len(burst)}
    if rejected != 6:
        failures.append(f"expected 6 overflow rejections, saw {rejected}")
    if resolved != len(burst):
        failures.append("overflow burst had silently dropped requests")

    # ---- phase 4: ragged packed serving A/B (ISSUE 9) -----------------
    ragged_ab = (_serve_ragged_ab(Server, params, cfg, seqs, max_batch,
                                  max_wait_s, n_clients, failures)
                 if "ragged" in wanted else None)

    # ---- phase 5: quantized executable arm A/B (ISSUE 12) -------------
    quant_ab = (_serve_quant_ab(Server, params, cfg, seqs, max_batch,
                                max_wait_s, n_clients, failures)
                if "quant" in wanted else None)

    # ---- phase 6: fleet trace-propagation A/B (ISSUE 18) --------------
    fleet_ab = (_serve_fleet_ab(Server, params, cfg, seqs, max_batch,
                                max_wait_s, n_clients, failures)
                if "fleet" in wanted else None)

    # ---- phase 7: pipelined-dispatch depth A/B (ISSUE 19) -------------
    pipeline_ab = (_serve_pipeline_ab(Server, params, cfg, seqs,
                                      max_batch, max_wait_s, n_clients,
                                      failures)
                   if "pipeline" in wanted else None)

    record = {
        "metric": "serve_load",
        **device,
        "seq_len": seq_len, "model_dim": dim, "median_len": median,
        "length_sigma": mix_sigma,
        "buckets": list(buckets), "max_batch": max_batch,
        "n_requests": n_requests,
        "baseline_sequential": baseline,
        "served": served,
        "speedup_x": round(served["requests_per_sec"]
                           / max(baseline["requests_per_sec"], 1e-9), 2),
        "tracing": tracing,
        "parity_per_bucket": parity,
        "overflow": overflow,
        "ragged_ab": ragged_ab,
        "quant_ab": quant_ab,
        "fleet_ab": fleet_ab,
        "pipeline_ab": pipeline_ab,
        "failures": failures,
    }
    if ragged_ab is not None:
        _mirror_ragged_note(record)
    if quant_ab is not None:
        _mirror_quant_note(record)
    if fleet_ab is not None:
        _mirror_fleet_note(record)
    if pipeline_ab is not None:
        _mirror_pipeline_note(record)
    try:  # mirror onto the shared bench event stream (best-effort)
        from proteinbert_tpu.obs.events import EventLog

        ev = EventLog(BENCH_EVENTS_PATH)
        ev.emit("note", source="bench", kind="serve_capture",
                platform=record["platform"], seq_len=seq_len,
                n_requests=n_requests, speedup_x=record["speedup_x"],
                served_requests_per_sec=served["requests_per_sec"],
                light_p99_ms=served["light_p99_ms"],
                trace_overhead_pct=tracing["sampled_out_overhead_pct"],
                trace_full_overhead_pct=tracing["full_overhead_pct"],
                rejected_queue_full=overflow["rejected_queue_full"],
                failures=len(failures))
        ev.close()
    except Exception as e:
        print(f"bench events stream unavailable: {e}", file=sys.stderr)
    print(json.dumps(record))
    if failures:
        for f in failures:
            print(f"SERVE CONTRACT FAILURE: {f}", file=sys.stderr)
        sys.exit(1)


def run_neighbors():
    """`bench.py --neighbors`: the serve-the-index-not-the-trunk claim
    (ISSUE 17 acceptance) — one JSON line, CPU-measurable.

    One tiny trunk (untrained params: dispatch behavior and index
    geometry are weight-independent) drives the WHOLE production
    pipeline: `mapper.run_map` embeds a corpus into a durable store,
    `index.build_index` quantizes it into the int8 IVF index, and a
    ragged `serve.Server` with the index attached answers
    `/v1/neighbors` requests end to end.

    GATED (nonzero exit on failure):
    - **recall@10 ≥ 0.95** vs exact brute-force cosine over the fp32
      store vectors, at the served nprobe (the `heads_eval_score_min`-
      style quality floor — quantization + coarse probing must not
      change what the index answers);
    - **int8 index ≤ 0.30x** the fp32 vector bytes (builder-reported
      `bytes_ratio`);
    - **sustained lookup QPS ≥ 10x the trunk-embed QPS** — the batched
      warm scorer vs the served trunk path on the same box. The ratio
      compares the index lookup leg to the trunk leg: a neighbors
      query is index-bound, not trunk-bound, once its embedding
      exists;
    - **served-vs-offline parity**: `/v1/neighbors` through the server
      returns the same ids, in order, as `index.lookup_one` over the
      offline `inference.embed` vector;
    - every request served, no lost futures.

    Mirrored as `note(kind=neighbors_capture)` on bench_events.jsonl →
    the `neighbors_qps` / `neighbors_recall_at_10` sentinel series
    (tools/bench_trajectory.py; recall is higher-is-better).

    Knobs: PBT_NEIGHBORS_BENCH_CORPUS (192), _QUERIES (32),
    _CENTROIDS (16), _NPROBE (8), _SEQ_LEN (128), _DIM (32),
    _ROUNDS (8), _CLIENTS (8), _EMBED_REQUESTS (32).
    """
    import tempfile
    import threading

    import jax

    device = bench_device()

    from proteinbert_tpu.configs import (
        DataConfig, ModelConfig, OptimizerConfig, PretrainConfig,
        TrainConfig,
    )
    from proteinbert_tpu.data.vocab import ALPHABET
    from proteinbert_tpu.index import build_index
    from proteinbert_tpu.index.scorer import (
        NeighborIndex, evaluate_recall, store_vectors_in_index_order,
    )
    from proteinbert_tpu.mapper.engine import run_map
    from proteinbert_tpu.serve import Server
    from proteinbert_tpu.train import create_train_state

    corpus_n = int(os.environ.get("PBT_NEIGHBORS_BENCH_CORPUS", 192))
    n_queries = int(os.environ.get("PBT_NEIGHBORS_BENCH_QUERIES", 32))
    centroids = int(os.environ.get("PBT_NEIGHBORS_BENCH_CENTROIDS", 16))
    nprobe = int(os.environ.get("PBT_NEIGHBORS_BENCH_NPROBE", 8))
    seq_len = int(os.environ.get("PBT_NEIGHBORS_BENCH_SEQ_LEN", 128))
    dim = int(os.environ.get("PBT_NEIGHBORS_BENCH_DIM", 32))
    rounds = int(os.environ.get("PBT_NEIGHBORS_BENCH_ROUNDS", 8))
    n_clients = int(os.environ.get("PBT_NEIGHBORS_BENCH_CLIENTS", 8))
    n_embed = int(os.environ.get("PBT_NEIGHBORS_BENCH_EMBED_REQUESTS", 32))

    # global_dim = 2*dim ≥ 64 keeps the int8 bytes ratio under the
    # 0.30x gate: ratio ≈ 1/4 (codes) + 1/(2*dim) (int32 assign)
    # + blocks/N (per-block fp32 scales) — at dim < 32 the assign
    # overhead alone pushes past the bound (docs/neighbors.md, sizing).
    model = ModelConfig(local_dim=dim, global_dim=2 * dim, key_dim=16,
                        num_heads=4, num_blocks=2,
                        num_annotations=128, dtype="float32")
    buckets = tuple(sorted({max(16, seq_len // 4), seq_len // 2,
                            seq_len}))
    cfg = PretrainConfig(
        model=model,
        data=DataConfig(seq_len=seq_len, batch_size=8, buckets=buckets),
        optimizer=OptimizerConfig(warmup_steps=10),
        train=TrainConfig(max_steps=1))
    params = create_train_state(jax.random.PRNGKey(0), cfg).params

    rng = np.random.default_rng(17)
    alphabet = np.array(list(ALPHABET))
    lengths = np.clip(
        rng.lognormal(mean=np.log(seq_len // 4), sigma=0.45,
                      size=corpus_n),
        10, seq_len - 2).astype(np.int64)
    ids = [f"seq{i:05d}" for i in range(corpus_n)]
    seqs = ["".join(rng.choice(alphabet, size=int(L))) for L in lengths]

    failures = []
    record = {
        "metric": "neighbors",
        **device,
        "seq_len": seq_len, "model_dim": dim,
        "global_dim": 2 * dim, "corpus_n": corpus_n,
        "centroids": centroids, "nprobe": nprobe,
        "failures": failures,
    }

    with tempfile.TemporaryDirectory(prefix="pbt_nbr_bench_") as tmp:
        store_dir = os.path.join(tmp, "store")
        index_dir = os.path.join(tmp, "index")

        # ---- corpus → store → index (the production build path) ----
        t0 = time.perf_counter()
        map_out = run_map(params, cfg, ids, seqs, store_dir,
                          num_shards=2, block_size=64)
        record["map_seconds"] = round(time.perf_counter() - t0, 3)
        if map_out["outcome"] != "completed":
            failures.append(f"map outcome {map_out['outcome']!r}")
        t0 = time.perf_counter()
        stats = build_index(store_dir, index_dir,
                            num_centroids=centroids, block_size=256)
        record["index_build_seconds"] = round(time.perf_counter() - t0,
                                              3)
        record["index_bytes_ratio"] = round(stats["bytes_ratio"], 4)
        record["index_vectors"] = stats["vectors"]
        if stats["outcome"] != "completed":
            failures.append(f"index outcome {stats['outcome']!r}")
        # GATE: the compression claim — int8 codes + int32 assign +
        # per-block scales vs 4 bytes/channel fp32.
        if stats["bytes_ratio"] > 0.30:
            failures.append(
                f"int8 index is {stats['bytes_ratio']:.3f}x the fp32 "
                "vector bytes (gate: <= 0.30x)")

        index = NeighborIndex.load(index_dir)
        vectors = store_vectors_in_index_order(store_dir)

        # ---- GATE: recall@10 vs exact brute force, at served nprobe --
        q_rows = rng.choice(corpus_n, size=min(n_queries, corpus_n),
                            replace=False)
        recall = evaluate_recall(index, vectors,
                                 np.asarray(vectors[q_rows]),
                                 k=10, nprobe=nprobe)
        record["recall_at_10"] = round(recall, 4)
        if recall < 0.95:
            failures.append(
                f"recall@10 {recall:.3f} at nprobe={nprobe} "
                "(gate: >= 0.95 vs exact brute force)")

        # ---- sustained lookup QPS: the batched warm scorer ----------
        qbatch = np.asarray(vectors[q_rows])
        index.lookup_rows(qbatch, k=10, nprobe=nprobe)  # warm/compile
        t0 = time.perf_counter()
        for _ in range(rounds):
            index.lookup_rows(qbatch, k=10, nprobe=nprobe)
        lookup_dt = time.perf_counter() - t0
        neighbors_qps = rounds * len(q_rows) / lookup_dt
        record["neighbors_qps"] = round(neighbors_qps, 1)
        record["lookup_executables"] = index.executables()

        # ---- trunk-embed QPS: the served trunk path -----------------
        server = Server(params, cfg, max_batch=8, max_wait_s=0.005,
                        queue_depth=4 * n_embed, cache_size=0,
                        serve_mode="ragged", trace_sample_rate=None,
                        index=index, nprobe=nprobe)
        server.start()
        try:
            results = {}

            def client(worker: int) -> None:
                for i in range(worker, n_embed, n_clients):
                    try:
                        results[i] = server.embed(seqs[i], timeout=120)
                    except Exception as e:  # noqa: BLE001
                        failures.append(f"embed {i}: "
                                        f"{type(e).__name__}: {e}")

            threads = [threading.Thread(target=client, args=(w,))
                       for w in range(n_clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(300)
            embed_dt = time.perf_counter() - t0
            if len(results) != n_embed:
                failures.append(f"served {len(results)}/{n_embed} "
                                "embed requests")
            embed_qps = n_embed / embed_dt
            record["embed_qps"] = round(embed_qps, 2)
            ratio = neighbors_qps / embed_qps if embed_qps else 0.0
            record["neighbors_qps_ratio"] = round(ratio, 1)
            # GATE: serving the index must beat re-serving the trunk by
            # an order of magnitude — the reason the subsystem exists.
            if ratio < 10.0:
                failures.append(
                    f"lookup QPS is only {ratio:.1f}x trunk-embed QPS "
                    "(gate: >= 10x)")

            # ---- GATE: served-vs-offline parity ---------------------
            # Offline leg reuses the server's own embedding (the same
            # ragged executable — trunk numerics differ across batch
            # shapes, so a bucketed inference.embed vector is not the
            # comparison target): the claim is that the served lookup
            # leg IS the offline scorer, bit for bit.
            checked = 0
            for i in map(int, q_rows[:8]):
                served = server.neighbors(seqs[i], k=5,
                                          timeout=120)["neighbors"]
                off_vec = server.embed(seqs[i], timeout=120)["global"]
                offline = index.lookup_one(off_vec, k=5, nprobe=nprobe)
                if [x[0] for x in served] != [x[0] for x in offline]:
                    failures.append(
                        f"served/offline top-k mismatch for {ids[i]}: "
                        f"{[x[0] for x in served]} vs "
                        f"{[x[0] for x in offline]}")
                checked += 1
            record["parity_checked"] = checked
            record["serve_stats"] = {
                k: server.stats()["neighbors"][k]
                for k in ("num_vectors", "nprobe",
                          "lookup_executables", "by_outcome")}
        finally:
            server.drain(timeout=60)

    # Mirror onto the shared bench stream (the sentinel's input).
    try:
        from proteinbert_tpu.obs.events import EventLog

        ev = EventLog(BENCH_EVENTS_PATH)
        ev.emit("note", source="bench", kind="neighbors_capture",
                platform=record["platform"],
                corpus_n=corpus_n, centroids=centroids, nprobe=nprobe,
                neighbors_qps=record["neighbors_qps"],
                neighbors_recall_at_10=record["recall_at_10"],
                embed_qps=record["embed_qps"],
                neighbors_qps_ratio=record["neighbors_qps_ratio"],
                index_bytes_ratio=record["index_bytes_ratio"],
                failures=len(failures))
        ev.close()
    except Exception as e:
        print(f"bench events stream unavailable: {e}", file=sys.stderr)

    print(json.dumps(record))
    if failures:
        for f in failures:
            print(f"NEIGHBORS GATE FAILURE: {f}", file=sys.stderr)
        sys.exit(1)


def run_heads():
    """`bench.py --heads`: the multi-tenant platform loop end to end —
    finetune → register → serve mixed-head traffic → eval — one JSON
    line, CPU-measurable (ISSUE 8 acceptance; the run_tier1.sh heads
    smoke stage).

    Phases over one tiny trunk:

    1. **finetune + register** — K tiny heads (one per task kind, 1
       epoch, synthetic labeled data, freeze_trunk so the registered
       trunk fingerprint IS the resident trunk's) land in a registry
       via the `train/finetune.finetune(registry=)` path, emitting
       `head_registered` events.
    2. **eval harness** — every head scored by heads/eval.py
       (per-residue accuracy / accuracy+AUC proxy / Spearman),
       `head_eval` events schema-validated; `eval_score_min` is the
       worst normalized score across heads — the finetune-quality
       series the bench-trajectory sentinel fits.
    3. **serving A/B** — the same mixed request population through two
       servers: MIXED (requests group by bucket only, every micro-batch
       runs ONE shared trunk pass and per-head tails) vs PARTITIONED
       (`partition_heads=True`: per-head groups — what serving degrades
       to without the shared-trunk insight). Median requests/s over
       PBT_HEADS_BENCH_ROUNDS interleaved rounds; the speedup is
       REPORTED (wall-clock on a shared box is evidence, not a gate).
    4. **contracts, GATED** — one deterministic micro-batch mixing ≥3
       distinct heads is bit-identical per row to sequential
       split-apply offline inference; the shared-trunk executable count
       stays FLAT across all serving traffic including a hot
       `add_head` on the live server; no request is ever lost; all
       emitted events validate against the schema.

    Knobs: PBT_HEADS_BENCH_SEQ_LEN (128), PBT_HEADS_BENCH_DIM (32),
    PBT_HEADS_BENCH_REQUESTS (60), PBT_HEADS_BENCH_CLIENTS (12),
    PBT_HEADS_BENCH_MAX_BATCH (8), PBT_HEADS_BENCH_ROUNDS (3),
    PBT_HEADS_BENCH_EPOCHS (1).
    """
    import tempfile
    import threading
    from statistics import median as _median

    import jax

    device = bench_device()

    from proteinbert_tpu.configs import (
        DataConfig, FinetuneConfig, ModelConfig, OptimizerConfig,
        PretrainConfig, TaskConfig, TrainConfig,
    )
    from proteinbert_tpu.data.synthetic import make_task_batches
    from proteinbert_tpu.data.vocab import ALPHABET
    from proteinbert_tpu.heads import HeadRegistry, trunk_fingerprint
    from proteinbert_tpu.heads import apply as heads_apply
    from proteinbert_tpu.heads.eval import evaluate_heads
    from proteinbert_tpu.obs import Telemetry, read_events
    from proteinbert_tpu.serve import TASK_KIND, Server
    from proteinbert_tpu.train import create_train_state
    from proteinbert_tpu.train.finetune import finetune

    seq_len = int(os.environ.get("PBT_HEADS_BENCH_SEQ_LEN", 128))
    dim = int(os.environ.get("PBT_HEADS_BENCH_DIM", 32))
    n_requests = int(os.environ.get("PBT_HEADS_BENCH_REQUESTS", 60))
    n_clients = int(os.environ.get("PBT_HEADS_BENCH_CLIENTS", 12))
    max_batch = int(os.environ.get("PBT_HEADS_BENCH_MAX_BATCH", 8))
    rounds = int(os.environ.get("PBT_HEADS_BENCH_ROUNDS", 3))
    epochs = int(os.environ.get("PBT_HEADS_BENCH_EPOCHS", 1))

    model = ModelConfig(local_dim=dim, global_dim=2 * dim, key_dim=16,
                        num_heads=4, num_blocks=2, num_annotations=128,
                        dtype="float32")
    buckets = (seq_len // 2, seq_len)
    cfg = PretrainConfig(
        model=model,
        data=DataConfig(seq_len=seq_len, batch_size=max_batch,
                        buckets=buckets),
        optimizer=OptimizerConfig(warmup_steps=10),
        train=TrainConfig(max_steps=1))
    params = create_train_state(jax.random.PRNGKey(0), cfg).params
    # finetune_step donates its state — and the finetune state's trunk
    # ALIASES pretrained_trunk's arrays — so hand finetune a host copy
    # and keep `params` (the resident serving trunk) untouched.
    trunk_host = jax.tree.map(np.asarray, params)

    failures = []
    work = tempfile.mkdtemp(prefix="pbt_heads_bench_")
    events_path = os.path.join(work, "events.jsonl")
    tele = Telemetry(events_path=events_path)
    registry = HeadRegistry(os.path.join(work, "registry"))

    # ---- phase 1: finetune K heads and register them ------------------
    tasks = [("token_classification", 4), ("sequence_classification", 3),
             ("sequence_regression", 1)]
    rng = np.random.default_rng(0)
    head_ids = []
    ft_s = {}
    for i, (kind, n_out) in enumerate(tasks):
        fcfg = FinetuneConfig(
            model=model,
            task=TaskConfig(kind=kind, num_outputs=n_out, epochs=epochs,
                            freeze_trunk=True),
            data=DataConfig(seq_len=seq_len, batch_size=8),
            optimizer=OptimizerConfig(learning_rate=3e-3, warmup_steps=5,
                                      schedule="warmup_cosine",
                                      total_steps=200),
            train=TrainConfig(seed=i))
        batches = make_task_batches(32, np.random.default_rng(i), kind,
                                    n_out, seq_len, 8)
        t0 = time.perf_counter()
        out = finetune(fcfg, lambda epoch: iter(batches),
                       eval_batches=lambda: iter(batches),
                       pretrained_trunk=trunk_host, telemetry=tele,
                       registry=registry, register_name=f"bench-{kind}")
        ft_s[kind] = round(time.perf_counter() - t0, 2)
        head_ids.append(out["head_id"])
    if len(set(head_ids)) != len(tasks):
        failures.append(f"expected {len(tasks)} distinct registered "
                        f"heads, got {head_ids}")

    # ---- phase 2: downstream eval harness -----------------------------
    fp = trunk_fingerprint(params)
    heads = [registry.load(h, trunk_fp=fp) for h in head_ids]
    eval_results = evaluate_heads(
        params, model, heads,
        lambda head: make_task_batches(
            32, np.random.default_rng(99), head.task.kind,
            head.task.num_outputs, seq_len, 8),
        telemetry=tele)
    eval_score_min = min(m["score"] for m in eval_results.values())

    # ---- phase 2b: downstream eval through the QUANTIZED trunk --------
    # The int8 serving arm's numerics exactly (ISSUE 12): dequantize∘
    # quantize is precisely what the quantized executables compute from
    # their int8 weights, so evaluating the heads on that trunk scores
    # the quantized arm's downstream quality without spinning a server.
    # GATED: the worst quantized score must stay within
    # PBT_HEADS_BENCH_QUANT_SCORE_DELTA (default 0.1) of the fp32
    # worst — the `heads_eval_score_min` sentinel's green-light for the
    # quantized arm (ROADMAP item 1 acceptance; the
    # heads_eval_score_min_quant series tracks it across rounds).
    from proteinbert_tpu.parallel.quant import (
        dequantize_params, quantize_params,
    )

    quant_trunk = dequantize_params(quantize_params(params))
    eval_results_quant = evaluate_heads(
        quant_trunk, model, heads,
        lambda head: make_task_batches(
            32, np.random.default_rng(99), head.task.kind,
            head.task.num_outputs, seq_len, 8),
        telemetry=tele)
    eval_score_min_quant = min(
        m["score"] for m in eval_results_quant.values())
    quant_score_delta = float(os.environ.get(
        "PBT_HEADS_BENCH_QUANT_SCORE_DELTA", 0.1))
    if eval_score_min_quant < eval_score_min - quant_score_delta:
        failures.append(
            f"quantized-trunk downstream eval degraded past the "
            f"documented delta: min score {eval_score_min_quant:.4f} "
            f"vs fp32 {eval_score_min:.4f} "
            f"(allowed -{quant_score_delta})")

    # ---- phase 3: mixed vs head-partitioned serving -------------------
    lengths = np.clip(rng.lognormal(mean=np.log(seq_len // 6), sigma=0.4,
                                    size=n_requests),
                      8, seq_len - 2).astype(np.int64)
    alphabet = np.array(list(ALPHABET))
    seqs = ["".join(rng.choice(alphabet, size=int(L))) for L in lengths]
    assign = [head_ids[i % len(head_ids)] for i in range(n_requests)]

    def run_load(srv, clients):
        results = {}

        def client(worker):
            for i in range(worker, n_requests, clients):
                try:
                    results[i] = srv.predict_task(assign[i], seqs[i],
                                                  timeout=120)
                except Exception as e:  # noqa: BLE001
                    failures.append(
                        f"request {i}: {type(e).__name__}: {e}")
        threads = [threading.Thread(target=client, args=(w,))
                   for w in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        dt = time.perf_counter() - t0
        deadline = time.monotonic() + 5.0
        prev = -1
        while time.monotonic() < deadline:
            cur = srv.scheduler.stats_counts()[1]  # locked read
            if cur == prev and len(srv.queue) == 0 \
                    and srv.scheduler.pending_rows() == 0:
                break
            prev = cur
            time.sleep(0.02)
        return results, dt

    rps = {"mixed": [], "partitioned": []}
    # One batch class keeps the warmup to one trunk compile per bucket
    # (the A/B measures scheduling, not the compile matrix).
    servers = {}
    for name, part in (("mixed", False), ("partitioned", True)):
        srv = Server(params, cfg, max_batch=max_batch, max_wait_s=0.005,
                     queue_depth=4 * n_requests, cache_size=0,
                     warm_kinds=(), batch_classes=(max_batch,),
                     telemetry=Telemetry(), trace_sample_rate=None,
                     registry=registry, heads=head_ids,
                     partition_heads=part)
        srv.start()
        run_load(srv, n_clients)  # warm pass
        servers[name] = srv
    for _ in range(rounds):  # interleaved matched rounds
        for name, srv in servers.items():
            results, dt = run_load(srv, n_clients)
            rps[name].append(len(results) / dt)
            if len(results) != n_requests:
                failures.append(
                    f"{name}: lost {n_requests - len(results)} of "
                    f"{n_requests} requests")
    mixed_stats = servers["mixed"].stats()
    part_stats = servers["partitioned"].stats()
    trunk_execs_before = servers["mixed"].dispatcher.trunk_executable_count

    # Hot add on the LIVE mixed server: a fresh head (same structure as
    # the sequence head → its tail executable is already warm) must
    # not add a trunk compile.
    from proteinbert_tpu.models import finetune as ft_model

    extra_task = TaskConfig(kind="sequence_classification", num_outputs=3)
    extra_params = ft_model.head_init(jax.random.PRNGKey(42), model,
                                      extra_task)
    extra_id = registry.save(
        jax.tree.map(np.asarray, extra_params), extra_task, fp,
        name="bench-hot-add")
    servers["mixed"].add_head(extra_id)
    got = servers["mixed"].predict_task(extra_id, seqs[0], timeout=60)
    trunk_execs_after = servers["mixed"].dispatcher.trunk_executable_count
    if trunk_execs_after != trunk_execs_before:
        failures.append(
            f"hot add_head recompiled the trunk: executable count "
            f"{trunk_execs_before} -> {trunk_execs_after}")
    if got.shape != (3,):
        failures.append(f"hot-added head returned shape {got.shape}")
    for srv in servers.values():
        srv.drain(timeout=60)

    mixed_rps = _median(rps["mixed"])
    part_rps = _median(rps["partitioned"])
    serving = {
        "requests": n_requests, "clients": n_clients,
        "n_heads": len(head_ids),
        "rps_per_round": {k: [round(v, 2) for v in vs]
                          for k, vs in rps.items()},
        "mixed_requests_per_sec": round(mixed_rps, 2),
        "partitioned_requests_per_sec": round(part_rps, 2),
        "mixed_speedup_x": round(mixed_rps / max(part_rps, 1e-9), 2),
        "mixed_batches": mixed_stats["batches"],
        "partitioned_batches": part_stats["batches"],
        "mixed_mean_rows_per_batch": round(
            mixed_stats["batched_rows"] / max(mixed_stats["batches"], 1),
            2),
        "partitioned_mean_rows_per_batch": round(
            part_stats["batched_rows"] / max(part_stats["batches"], 1),
            2),
        "trunk_executables": trunk_execs_after,
    }

    # ---- phase 4: deterministic mixed-batch bit-parity ----------------
    # Fixed short lengths: every row lands in the SAME bucket, so one
    # poll() forms exactly one micro-batch mixing all the heads.
    from proteinbert_tpu import inference

    group = ["".join(rng.choice(alphabet, size=10 + 3 * i))
             for i in range(2 * len(head_ids))]
    gassign = [head_ids[i % len(head_ids)] for i in range(len(group))]
    psrv = Server(params, cfg, max_batch=len(group), max_wait_s=60.0,
                  cache_size=0, warm_kinds=(),
                  batch_classes=(len(group),), registry=registry,
                  heads=head_ids)
    n_trunk0 = psrv.dispatcher.trunk_executable_count
    futures = [psrv.submit(TASK_KIND, s, head_id=h)
               for s, h in zip(group, gassign)]
    psrv.scheduler.poll()  # deterministic single-batch formation
    mixed_out = [f.result(timeout=30) for f in futures]
    # Read AFTER the dispatch: the whole mixed-head batch must have
    # compiled exactly ONE shared trunk executable (n_trunk0 was 0 on
    # the cold, unwarmed server).
    n_trunk_parity = psrv.dispatcher.trunk_executable_count
    if n_trunk0 != 0 or n_trunk_parity != 1:
        failures.append(
            f"parity batch expected exactly one shared trunk executable "
            f"(cold {n_trunk0} -> warm {n_trunk_parity})")
    mixed_batches = psrv.scheduler.stats_counts()[0]  # locked read
    if mixed_batches != 1:
        failures.append(
            f"parity phase expected ONE mixed micro-batch, got "
            f"{mixed_batches}")
    heads_in_batch = len(set(gassign))
    if heads_in_batch < 3:
        failures.append(f"parity batch mixed only {heads_in_batch} heads")
    psrv.abort()

    # BIT-identity gate: mixed-head batch vs PER-HEAD SEQUENTIAL
    # serving at the same (batch_class, bucket) shape — the same
    # executables run, so mixing tenants into one batch must change
    # NOTHING (per-row independence of the trunk forward).
    # max_batch = rows-per-head so each per-head group dispatches full;
    # batch_classes pins the SAME padded class shape the mixed batch
    # ran, so both paths hit the identical executable.
    ssrv = Server(params, cfg,
                  max_batch=len(group) // heads_in_batch,
                  max_wait_s=60.0, cache_size=0, warm_kinds=(),
                  batch_classes=(len(group),), registry=registry,
                  heads=head_ids, partition_heads=True)
    sfutures = [ssrv.submit(TASK_KIND, s, head_id=h)
                for s, h in zip(group, gassign)]
    for _ in range(heads_in_batch):  # one per-head batch per poll
        ssrv.scheduler.poll()
    seq_out = [f.result(timeout=30) for f in sfutures]
    parity_ok = all(np.array_equal(m, s)
                    for m, s in zip(mixed_out, seq_out))
    if not parity_ok:
        failures.append("mixed-head micro-batch is not bit-identical "
                        "to per-head sequential serving")
    seq_batches = ssrv.scheduler.stats_counts()[0]  # locked read
    if seq_batches != heads_in_batch:
        failures.append(
            f"partitioned parity server formed "
            f"{seq_batches} batches, expected "
            f"{heads_in_batch}")
    ssrv.abort()

    # Sanity vs OFFLINE single-row split-apply inference: same math,
    # different batch shape → documented fp32 tolerance (XLA reassoc-
    # iates reductions per shape; measured ~1e-6 — docs/serving.md).
    by_head = {h.head_id: h for h in heads}
    L = psrv.dispatcher.bucket_len(max(len(s) for s in group))
    offline_tol_ok = True
    for i, (s, h) in enumerate(zip(group, gassign)):
        want = heads_apply.predict_task_rows(
            params, model, by_head[h],
            inference._tokenize_masked([s], seq_len)[:, :L])[0]
        if not np.allclose(mixed_out[i], want, rtol=0, atol=1e-5):
            offline_tol_ok = False
    if not offline_tol_ok:
        failures.append("mixed-head serving drifted past the 1e-5 fp32 "
                        "tolerance vs offline split-apply inference")

    # ---- events validate ----------------------------------------------
    tele.close()
    recs = read_events(events_path, strict=True)
    n_reg = sum(1 for r in recs if r["event"] == "head_registered")
    n_ev = sum(1 for r in recs if r["event"] == "head_eval")
    # (the hot-add head was saved via registry.save directly — only the
    # finetune(registry=) path emits head_registered)
    if n_reg != len(tasks):
        failures.append(f"expected {len(tasks)} head_registered "
                        f"events, got {n_reg}")
    # Two eval passes per head: the fp32 harness and the quantized-
    # trunk arm (phase 2b).
    if n_ev != 2 * len(tasks):
        failures.append(f"expected {2 * len(tasks)} head_eval events, "
                        f"got {n_ev}")

    record = {
        "metric": "heads_load",
        **device,
        "seq_len": seq_len, "model_dim": dim,
        "buckets": list(buckets), "max_batch": max_batch,
        "finetune_s": ft_s,
        "head_ids": head_ids,
        "eval": {h.head_id: eval_results[h.head_id] for h in heads},
        "eval_score_min": round(eval_score_min, 6),
        "eval_quant": {h.head_id: eval_results_quant[h.head_id]
                       for h in heads},
        "eval_score_min_quant": round(eval_score_min_quant, 6),
        "serving": serving,
        "parity": {"rows": len(group), "heads_mixed": heads_in_batch,
                   "bit_identical_vs_sequential": parity_ok,
                   "offline_within_1e-5": offline_tol_ok,
                   "trunk_executables": n_trunk_parity},
        "events": {"head_registered": n_reg, "head_eval": n_ev,
                   "total": len(recs)},
        "failures": failures,
    }
    try:  # mirror onto the shared bench event stream (best-effort)
        from proteinbert_tpu.obs.events import EventLog

        ev = EventLog(BENCH_EVENTS_PATH)
        ev.emit("note", source="bench", kind="heads_capture",
                platform=record["platform"], seq_len=seq_len,
                n_heads=len(head_ids), n_requests=n_requests,
                mixed_requests_per_sec=serving["mixed_requests_per_sec"],
                partitioned_requests_per_sec=serving[
                    "partitioned_requests_per_sec"],
                mixed_speedup_x=serving["mixed_speedup_x"],
                eval_score_min=record["eval_score_min"],
                eval_score_min_quant=record["eval_score_min_quant"],
                failures=len(failures))
        ev.close()
    except Exception as e:
        print(f"bench events stream unavailable: {e}", file=sys.stderr)
    import shutil

    shutil.rmtree(work, ignore_errors=True)
    print(json.dumps(record))
    if failures:
        for f in failures:
            print(f"HEADS CONTRACT FAILURE: {f}", file=sys.stderr)
        sys.exit(1)


def run_comm():
    """`bench.py --comm`: per-step collective bytes + per-chip state
    bytes, replicated vs ZeRO-1 zero-update, on a CPU-virtual mesh —
    one JSON line, so the memory/comm win is a recorded artifact
    (ISSUE 2 acceptance).

    Three numbers per mode, all derived from the COMPILED per-device
    program (not from claims): collective bytes by kind from the HLO
    (parallel/zero.collective_bytes_from_hlo), per-chip persistent
    params/opt-state bytes from the sharding rules
    (zero.per_chip_state_bytes — identical for a virtual mesh and the
    real pod shape), and the executable's memory analysis where the
    backend reports one. Knobs: PBT_COMM_MESH="dataxfsdp" (default 4x2,
    matching the 8-device test harness), PBT_COMM_DIM scales the model
    (default 64; plumbing tests use smaller). Numbers are CPU-virtual:
    byte counts are exact properties of the partitioned program;
    collective TIME on real ICI is not measured here (PARITY.md)."""
    import jax

    from proteinbert_tpu.utils.compat import request_cpu_devices

    mesh_spec = os.environ.get("PBT_COMM_MESH", "4x2")
    data_n, fsdp_n = (int(x) for x in mesh_spec.lower().split("x"))
    n_devices = data_n * fsdp_n
    request_cpu_devices(n_devices)
    device = bench_device(cpu_requested_in_code=True)

    import numpy as np

    from proteinbert_tpu.configs import (
        DataConfig, MeshConfig, ModelConfig, OptimizerConfig, ParallelConfig,
        PretrainConfig, TrainConfig,
    )
    from proteinbert_tpu.parallel import batch_sharding, make_mesh
    from proteinbert_tpu.parallel.quant import make_quant_zero_train_step
    from proteinbert_tpu.parallel.sharding import state_sharding
    from proteinbert_tpu.parallel.zero import (
        collective_bytes_from_hlo, collective_wire_bytes_from_hlo,
        grad_reduce_wire_bytes, make_zero_train_step,
        per_chip_state_bytes,
    )
    from proteinbert_tpu.train import create_train_state
    from proteinbert_tpu.train import train_state as ts

    if jax.device_count() < n_devices:
        raise SystemExit(
            f"--comm needs {n_devices} virtual devices, have "
            f"{jax.device_count()} (backend initialized too early?)")

    dim = int(os.environ.get("PBT_COMM_DIM", 64))
    mesh_cfg = MeshConfig(data=data_n, fsdp=fsdp_n)
    model = ModelConfig(local_dim=dim, global_dim=2 * dim, key_dim=16,
                        num_heads=4, num_blocks=2,
                        num_annotations=max(8 * dim, 256), dtype="float32")
    base_cfg = PretrainConfig(
        model=model,
        data=DataConfig(seq_len=128, batch_size=2 * n_devices),
        optimizer=OptimizerConfig(warmup_steps=10),
        mesh=mesh_cfg, train=TrainConfig(max_steps=1))
    mesh = make_mesh(mesh_cfg, jax.devices()[:n_devices])
    abstract = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), base_cfg))
    bsh = batch_sharding(mesh)
    batch_abs = {
        "tokens": jax.ShapeDtypeStruct(
            (base_cfg.data.batch_size, base_cfg.data.seq_len), np.int32,
            sharding=bsh["tokens"]),
        "annotations": jax.ShapeDtypeStruct(
            (base_cfg.data.batch_size, model.num_annotations), np.float32,
            sharding=bsh["annotations"]),
    }

    # Mode table: replicated (no zero), zero (implicit fp32 reduce-
    # scatter), zero_rs_fp32 (the EXPLICIT reduce-scatter at fp32
    # payload — the like-for-like baseline the quantized wire is
    # measured against: identical program, only the payload dtype
    # differs), zero_bf16 / zero_int8 (quantized payloads).
    _GRD = {"zero_bf16": "bf16", "zero_int8": "int8"}

    def analyze(mode):
        zero = mode != "replicated"
        grd = _GRD.get(mode, "fp32")
        cfg = base_cfg.replace(parallel=ParallelConfig(
            zero_update=zero, grad_reduce_dtype=grd))
        sh = state_sharding(mesh, abstract, zero_update=zero)
        st = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            abstract, sh)
        if mode == "zero_rs_fp32":
            step = make_quant_zero_train_step(mesh, cfg, payload="fp32")
            lowered = step.lower(st, batch_abs)
        elif zero:
            lowered = make_zero_train_step(mesh, cfg).lower(st, batch_abs)
        else:
            lowered = ts.train_step.lower(st, batch_abs, cfg)
        compiled = lowered.compile()
        hlo = compiled.as_text()
        wire = collective_wire_bytes_from_hlo(hlo, n_devices)
        row = {"mode": mode,
               "collective_bytes": collective_bytes_from_hlo(hlo),
               "wire_bytes": wire,
               "grad_reduce_wire_bytes": grad_reduce_wire_bytes(wire),
               "state_bytes_per_chip": per_chip_state_bytes(
                   mesh, abstract, zero_update=zero)}
        try:  # not every backend reports memory stats
            ma = compiled.memory_analysis()
            row["hbm"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
            }
        except Exception:
            row["hbm"] = None
        return row

    modes = ("replicated", "zero", "zero_rs_fp32", "zero_bf16",
             "zero_int8")
    rows = [analyze(m) for m in modes]
    by_mode = {r["mode"]: r for r in rows}
    rep, zero = by_mode["replicated"], by_mode["zero"]
    # The quantization ratios compare the SAME explicit reduce-scatter
    # program at int8/bf16 payload vs fp32 payload — wire bytes of the
    # gradient-reduction collectives, counted from compiled HLO
    # (outputs + replica_groups), never inferred from source dtypes.
    fp32_rs = max(by_mode["zero_rs_fp32"]["grad_reduce_wire_bytes"], 1)
    int8_ratio = round(
        by_mode["zero_int8"]["grad_reduce_wire_bytes"] / fp32_rs, 4)
    bf16_ratio = round(
        by_mode["zero_bf16"]["grad_reduce_wire_bytes"] / fp32_rs, 4)
    record = {
        "metric": "zero_update_comm",
        **device,
        # The mesh is virtual: bytes are counts, nothing here is a time.
        "platform": "cpu-virtual",
        "mesh": {"data": data_n, "fsdp": fsdp_n},
        "model_dim": dim,
        "modes": rows,
        "opt_state_bytes_reduction_x": round(
            rep["state_bytes_per_chip"]["opt_state"]
            / max(zero["state_bytes_per_chip"]["opt_state"], 1), 2),
        "collective_bytes_ratio": round(
            zero["collective_bytes"]["total"]
            / max(rep["collective_bytes"]["total"], 1), 3),
        "int8_grad_wire_ratio": int8_ratio,
        "bf16_grad_wire_ratio": bf16_ratio,
    }
    try:  # mirror onto the shared bench event stream (best-effort)
        from proteinbert_tpu.obs.events import EventLog

        ev = EventLog(BENCH_EVENTS_PATH)
        ev.emit("note", source="bench", kind="comm_quant",
                platform=record["platform"], model_dim=dim,
                mesh=record["mesh"],
                int8_grad_wire_ratio=int8_ratio,
                bf16_grad_wire_ratio=bf16_ratio,
                int8_grad_wire_bytes=by_mode["zero_int8"][
                    "grad_reduce_wire_bytes"],
                fp32_grad_wire_bytes=fp32_rs)
        ev.close()
    except Exception as e:
        print(f"bench events stream unavailable: {e}", file=sys.stderr)
    print(json.dumps(record))
    # GATED (ROADMAP item 1 acceptance): the int8 reduce-scatter must
    # move <= 0.30x the fp32 wire bytes. bf16 is reported, not gated
    # (its ~0.5x is arithmetic, but the gate names int8).
    if int8_ratio > 0.30:
        print(f"COMM QUANT FAILURE: int8 grad-reduction wire ratio "
              f"{int8_ratio} > 0.30 vs the fp32 reduce-scatter",
              file=sys.stderr)
        sys.exit(1)


def variant_matches(pat, variant):
    """--only matching: the bare name AND the 'name:seq/batch' shape
    key, so anchored name patterns ('u2st$') and row-targeted ones
    ('remat-convs:1024/512$') both work."""
    name, _, seq, batch = variant
    return bool(pat.search(name) or pat.search(f"{name}:{seq}/{batch}"))


def main():
    # Optional variant filter (regex on the variant name or its
    # 'name:seq/batch' shape key — `bench.py --only 'u[23]'`, or one
    # row via `--only 'remat-convs:1024/512$'`): lets a short chip call
    # be spent on exactly the rows that need refreshing instead of the
    # whole sweep. The driver invokes bench.py with no args, so the
    # default (everything) and the emitted JSON contract are unchanged.
    import argparse
    import re

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, metavar="REGEX",
                    help="run only variants whose name OR shape key "
                         "'name:seq/batch' matches REGEX (e.g. "
                         "'remat-convs:1024/512$' for one row; name-"
                         "only patterns keep working unchanged)")
    ap.add_argument("--boundary", action="store_true",
                    help="measure train-stream stall per checkpoint "
                         "boundary (sync vs overlapped) and emit one "
                         "JSON line")
    ap.add_argument("--pack", action="store_true",
                    help="measure packed vs unpacked throughput (raw AND "
                         "pad-adjusted effective residues/s, raw AND "
                         "effective MFU) on a realistic length "
                         "distribution and emit one JSON line — "
                         "CI-measurable without a TPU")
    ap.add_argument("--serve", action="store_true",
                    help="sustained-load online serving vs the "
                         "sequential single-request baseline: "
                         "throughput, p50/p99 latency, per-bucket "
                         "bit-parity, queue-overflow rejection, plus a "
                         "ragged-vs-bucketed packed-serving A/B with a "
                         "per-request parity gate — one JSON line, "
                         "CI-measurable without a TPU")
    ap.add_argument("--serve-length-mix", default=None, metavar="SPEC",
                    help="--serve request-length mix: log-normal "
                         "'median=48,sigma=0.9,seed=7' (any subset of "
                         "keys), clamped to the model window — the "
                         "mixed-length workload ragged serving exists "
                         "to speed up; default traffic is identical "
                         "to earlier captures")
    ap.add_argument("--neighbors", action="store_true",
                    help="the ANN serving claim end to end: map a "
                         "corpus into an embedding store, build the "
                         "int8 IVF index, then gate recall@10 >= 0.95 "
                         "vs brute force, index bytes <= 0.30x fp32, "
                         "lookup QPS >= 10x trunk-embed QPS, and "
                         "served-vs-offline top-k parity — one JSON "
                         "line, CPU-measurable")
    ap.add_argument("--heads", action="store_true",
                    help="the multi-tenant head platform end to end: "
                         "finetune → register → serve mixed-head "
                         "traffic vs head-partitioned batching → "
                         "downstream eval; mixed-batch bit-parity and "
                         "flat-trunk-executable contracts gated — one "
                         "JSON line, CI-measurable without a TPU")
    ap.add_argument("--comm", action="store_true",
                    help="compile the train step replicated vs ZeRO-1 "
                         "zero-update on a CPU-virtual mesh and emit one "
                         "JSON line of per-step collective bytes (from "
                         "the HLO) and per-chip state bytes (from the "
                         "sharding rules)")
    cli = ap.parse_args()

    if cli.boundary:
        run_boundary()
        return

    if cli.pack:
        run_pack()
        return

    if cli.serve:
        run_serve(length_mix=cli.serve_length_mix)
        return

    if cli.neighbors:
        run_neighbors()
        return

    if cli.heads:
        run_heads()
        return

    if cli.comm:
        run_comm()
        return

    device = bench_device()
    on_tpu = device["platform"] != "cpu"
    variants, steps = build_variants(on_tpu)
    indices = list(range(len(variants)))
    if cli.only is not None:
        pat = re.compile(cli.only)
        indices = [i for i in indices if variant_matches(pat, variants[i])]
        if not indices:
            raise SystemExit(f"--only {cli.only!r} matches no variant")

    # One process for the whole sweep: the chip belongs to the process
    # that holds it, so there is no parent to hand it between children.
    best = None
    failed = []
    for i in indices:
        name, _, seq_len, batch = variants[i]
        try:
            row = run_variant(variants[i], steps, device, seed=i)
        except Exception as e:  # noqa: BLE001 — e.g. the over-large
            # batch rows running out of HBM; named, never silent.
            failed.append(f"{name}:{seq_len}/{batch}")
            print(f"variant {name}:{seq_len}/{batch} failed "
                  f"({type(e).__name__}: {str(e)[:200]})", file=sys.stderr)
            continue
        if best is None or row["residues_per_sec"] > best[0]:
            best = (row["residues_per_sec"], row["mfu"], row["variant"],
                    row["seq_len"], row["batch"])
    if best is None:
        raise SystemExit("all bench variants failed")
    print(json.dumps({**build_record(best, device),
                      "failed_variants": failed}))


if __name__ == "__main__":
    main()
