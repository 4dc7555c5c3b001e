"""Unified telemetry subsystem (ISSUE 3): event schema round-trip,
metrics registry, span tracing, flight recorder, diagnose, and the
trainer wiring end-to-end on a CPU mesh."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from proteinbert_tpu import obs
from proteinbert_tpu.obs.diagnose import render, summarize

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- events

def test_every_event_type_roundtrips_the_validator(tmp_path):
    """The tier-1 schema round trip: each event type → EventLog → JSONL
    → read back → validate_record, plus the validator tool itself."""
    path = tmp_path / "ev.jsonl"
    log = obs.EventLog(str(path))
    for event in sorted(obs.EVENT_FIELDS):
        example = obs.make_example(event)
        payload = {k: v for k, v in example.items()
                   if k not in ("v", "event", "seq", "t")}
        assert log.emit(event, **payload) is not None
    log.close()
    recs = obs.read_events(str(path), strict=True)
    assert [r["event"] for r in recs] == sorted(obs.EVENT_FIELDS)
    assert [r["seq"] for r in recs] == list(range(len(recs)))
    # And through the CLI validator (no jax import — fast).
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "validate_events.py"),
         str(path)], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 errors" in out.stdout


def test_validator_self_test_and_rejection(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "validate_events.py"),
         "--self-test"], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"v": 1, "event": "step", "seq": 0,
                               "t": 0.0}) + "\n")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "validate_events.py"),
         str(bad)], capture_output=True, text=True, timeout=60)
    assert out.returncode == 1
    assert "missing required field" in out.stdout


def test_torn_tail_is_skipped_not_fatal(tmp_path):
    path = tmp_path / "ev.jsonl"
    log = obs.EventLog(str(path))
    log.emit("note", source="t")
    log.emit("note", source="t")
    log.close()
    with open(path, "a") as f:
        f.write('{"v": 1, "event": "note", "se')  # crash mid-write
    recs = obs.read_events(str(path), strict=True)
    assert len(recs) == 2  # torn tail dropped silently even under strict
    # A malformed MIDDLE line is a real corruption: strict raises.
    with open(path, "a") as f:
        f.write("\n" + json.dumps(obs.make_example("note")) + "\n")
    with pytest.raises(ValueError):
        obs.read_events(str(path), strict=True)
    assert len(obs.read_events(str(path))) == 3  # lax mode skips it


def test_emit_survives_record_key_collision(tmp_path):
    """A payload field colliding with a record key (t/seq/event/v) must
    be dropped, not raise out of emit (the never-raises contract —
    tools forward arbitrary status dicts into note events)."""
    log = obs.EventLog(str(tmp_path / "ev.jsonl"))
    assert log.emit("note", source="x", t=123.0) is None
    assert log.emit("note", source="x", seq=7) is None
    assert log.emit("note", source="x") is not None
    log.close()
    t = obs.Telemetry()  # flight-only mode has the same contract
    assert t.emit("note", source="x", t=123.0) is None
    assert t.emit("note", source="x") is not None


def test_sanitize_makes_nan_and_numpy_json_safe():
    rec = obs.sanitize({"loss": float("nan"), "inf": float("inf"),
                        "np": np.float32(1.5), "arr": (1, 2),
                        "nested": {"x": float("-inf")}})
    assert rec == {"loss": None, "inf": None, "np": 1.5,
                   "arr": [1, 2], "nested": {"x": None}}
    json.dumps(rec)  # strict-JSON safe


def test_emit_never_raises_on_bad_payload(tmp_path):
    log = obs.EventLog(str(tmp_path / "ev.jsonl"))
    assert log.emit("step", step=1) is None          # missing metrics
    assert log.emit("no_such_event") is None
    assert log.emit("step", step=1, metrics={"a": 1}) is not None
    log.close()
    assert len(obs.read_events(str(tmp_path / "ev.jsonl"),
                               strict=True)) == 1


# ------------------------------------------------------------ metrics

def test_metrics_registry_instruments_and_exports(tmp_path):
    reg = obs.MetricsRegistry()
    reg.counter("steps_total").inc(5)
    reg.gauge("mfu", window="cum").set(0.5)
    h = reg.histogram("stage_s")
    for v in (1.0, 3.0, 2.0):
        h.observe(v)
    with reg.timer("phase"):
        pass
    snap = reg.snapshot()
    assert snap["counters"]["steps_total"] == 5
    assert snap["gauges"]['mfu{window="cum"}'] == 0.5
    assert snap["histograms"]["stage_s"]["count"] == 3
    assert snap["histograms"]["stage_s"]["max"] == 3.0
    assert snap["histograms"]["phase"]["count"] == 1
    text = reg.prometheus_text()
    assert "# TYPE pbt_steps_total counter" in text
    assert 'pbt_mfu{window="cum"} 0.5' in text
    assert "pbt_stage_s_sum 6" in text
    # TYPE lines are per sample family, labels stripped — a labeled
    # histogram types pbt_<name>_count, never a bare pbt_<name>.
    lreg = obs.MetricsRegistry()
    with lreg.timer("phase", part="a"):
        pass
    ltext = lreg.prometheus_text()
    assert "# TYPE pbt_phase_count counter" in ltext
    assert 'pbt_phase_count{part="a"} 1' in ltext
    assert "# TYPE pbt_phase counter" not in ltext
    prom = tmp_path / "metrics.prom"
    reg.write_prometheus(str(prom))
    assert prom.read_text() == text
    reg.write_snapshot(str(tmp_path / "snap.jsonl"))
    line = json.loads((tmp_path / "snap.jsonl").read_text())
    assert line["counters"]["steps_total"] == 5


def test_zero_comm_bytes_land_in_registry():
    """The registry absorbs the ZeRO comm accounting: the same HLO
    parser tests/test_zero.py reads, exported as labeled gauges."""
    from proteinbert_tpu.parallel.zero import record_comm_metrics

    hlo = ("  x = f32[8,128]{1,0} all-reduce(f32[8,128]{1,0} p), "
           "replica_groups={}\n"
           "  y = f32[4,64]{1,0} reduce-scatter(f32[8,64]{1,0} q)\n")
    reg = obs.MetricsRegistry()
    out = record_comm_metrics(reg, hlo)
    gauges = reg.snapshot()["gauges"]
    assert gauges['collective_bytes{kind="all-reduce"}'] == 8 * 128 * 4
    assert gauges['collective_bytes{kind="reduce-scatter"}'] == 4 * 64 * 4
    assert gauges['collective_bytes{kind="total"}'] == out["total"]


def test_disabled_registry_is_a_noop():
    reg = obs.MetricsRegistry(enabled=False)
    reg.counter("c").inc()
    reg.gauge("g").set(1)
    reg.histogram("h").observe(1)
    with reg.timer("t"):
        pass
    reg.set_many({"a": 1.0})
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


def test_null_facade_is_inert_and_is_the_default():
    # obs.NULL is the do-nothing telemetry every instrumented call
    # site runs through when none is configured: emit returns None,
    # there is no span collector, the registry is disabled, and
    # as_telemetry(None) hands back exactly this object.
    assert obs.as_telemetry(None) is obs.NULL
    assert obs.NULL.enabled is False
    assert obs.NULL.emit("step", step=1, metrics={}) is None
    assert obs.NULL.spans is None
    assert obs.NULL.dump_flight("reason") is None
    obs.NULL.metrics.counter("c").inc()
    assert obs.NULL.metrics.snapshot() == {
        "counters": {}, "gauges": {}, "histograms": {}}
    tele = obs.Telemetry(metrics=False)
    assert obs.as_telemetry(tele) is tele


def test_profiler_shim_keeps_api_and_feeds_registry():
    from proteinbert_tpu.utils.profiling import Profiler

    reg = obs.MetricsRegistry()
    prof = Profiler(registry=reg)
    with prof.measure("etl"):
        pass
    with prof.measure("etl"):
        pass
    s = prof.summary()
    assert s["etl"]["count"] == 2
    assert s["etl"]["total_s"] >= 0
    assert "etl" in prof.report()
    # The sections landed in the SHARED registry, not a private dict.
    assert reg.snapshot()["histograms"]["etl"]["count"] == 2


def test_prefetch_counts_deliveries_and_keeps_no_clock_of_its_own():
    from proteinbert_tpu.data.prefetch import prefetch

    it = prefetch(iter([{"a": 1}] * 5), depth=2)
    assert sum(1 for _ in it) == 5
    assert it.batches == 5
    # the wait is the consumer's `train.data_wait` span (tests/test_tracing)
    assert not hasattr(it, "wait_s")


# ------------------------------------------------------------- flight

def test_flight_recorder_ring_and_dump(tmp_path):
    fr = obs.FlightRecorder(capacity=3, directory=str(tmp_path))
    for i in range(5):
        fr.record(obs.make_record("note", seq=i, t=float(i), source="t"))
    assert [r["seq"] for r in fr.snapshot()] == [2, 3, 4]  # bounded ring
    path = fr.dump("unit_test")
    assert path == obs.flight_path(str(tmp_path))
    payload = json.load(open(path))
    obs.validate_flight_dump(payload)
    assert payload["reason"] == "unit_test"
    assert [r["seq"] for r in payload["events"]] == [2, 3, 4]


def test_flight_excepthook_dumps_then_defers(tmp_path):
    fr = obs.FlightRecorder(capacity=8, directory=str(tmp_path))
    fr.record(obs.make_record("note", seq=0, t=0.0, source="t"))
    seen = []
    prev = sys.excepthook
    sys.excepthook = lambda *a: seen.append(a)
    try:
        fr.install_excepthook()
        sys.excepthook(RuntimeError, RuntimeError("boom"), None)
        assert seen, "previous hook was not chained"
        payload = json.load(open(obs.flight_path(str(tmp_path))))
        obs.validate_flight_dump(payload)
        assert payload["reason"] == "unhandled_RuntimeError"
    finally:
        fr.uninstall_excepthook()
        sys.excepthook = prev


# ----------------------------------------------------------- diagnose

def _synthetic_stream(path):
    t = obs.Telemetry(events_path=str(path))
    t.emit("run_start", step=0, config={"train": {}}, jax_version="0",
           pid=os.getpid(), mesh={"data": 8}, n_chips=8, resumed=False)
    for i, (win_ms, ckpt) in enumerate(
            [(100.0, 0.0), (105.0, 0.0), (900.0, 1.0), (110.0, 0.0)]):
        t.emit("step", step=10 * (i + 1), metrics={
            "loss": 1.0 / (i + 1), "steps_per_sec": 9.5,
            "window_steps_per_sec": 1000.0 / win_ms,
            "window_step_ms": win_ms, "ckpt_in_flight": ckpt})
    t.emit("ckpt_stage", step=20, phase="dispatch")
    t.emit("ckpt_stage", step=20, phase="landed", saved=True,
           overlap_s=2.0)
    t.emit("eval", step=20, metrics={"eval_loss": 0.5})
    t.emit("run_end", outcome="completed", step=40,
           perf={"steps_per_sec": 9.5, "overlap_s": 2.0})
    t.close()


def test_diagnose_summary_and_render(tmp_path):
    path = tmp_path / "ev.jsonl"
    _synthetic_stream(path)
    recs = obs.read_events(str(path), strict=True)
    s = summarize(recs, slow_top=2, last=3)
    assert s["outcome"] == "completed"
    assert s["manifest"]["mesh"] == {"data": 8}
    assert s["step_rate"]["steps_per_sec"] == 9.5
    # The injected 900ms window tops the stall list, latch attached.
    assert s["stalls"][0]["step"] == 30
    assert s["stalls"][0]["ckpt_in_flight"] is True
    assert s["boundary"]["ckpt_stages_landed"] == 1
    assert s["boundary"]["overlap_s"] == 2.0
    assert s["boundary"]["overlap_ratio"] is not None
    assert len(s["last_events"]) == 3
    text = render(s)
    assert "[ckpt]" in text and "900.00" in text


def test_diagnose_segments_requeued_stream(tmp_path):
    """A requeued run appends a fresh run_start to the same file; the
    summary's manifest/rates must cover the LAST incarnation, not mix
    the dead run's pid and the restart gap into the numbers."""
    path = tmp_path / "ev.jsonl"
    t = obs.Telemetry(events_path=str(path))
    t.emit("run_start", step=0, config={}, jax_version="0", pid=111)
    t.emit("step", step=10, metrics={"loss": 1.0})
    t.emit("requeue", step=10, reason="signal_15")
    t.emit("run_start", step=10, config={}, jax_version="0", pid=222,
           resumed=True)
    t.emit("step", step=20, metrics={"loss": 0.5, "steps_per_sec": 3.0})
    t.emit("run_end", outcome="completed", step=20, perf={})
    t.close()
    s = summarize(obs.read_events(str(path), strict=True))
    assert s["incarnations"] == 2
    assert s["manifest"]["pid"] == 222          # the live incarnation
    assert s["counts"]["run_start"] == 2        # whole file still counted
    assert s["counts"]["requeue"] == 1


def test_diagnose_cli_json_and_flight(tmp_path, capsys):
    from proteinbert_tpu.cli.main import main

    path = tmp_path / "ev.jsonl"
    _synthetic_stream(path)
    # A flight dump from the same stream.
    fr = obs.FlightRecorder(capacity=4, directory=str(tmp_path))
    for r in obs.read_events(str(path)):
        fr.record(r)
    fpath = fr.dump("sigterm_test")
    assert main(["diagnose", str(path), "--flight", fpath, "--json"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["outcome"] == "completed"
    assert out["flight"]["reason"] == "sigterm_test"
    assert out["last_events"][-1]["event"] == "run_end"
    # Human report mode on the same artifacts.
    assert main(["diagnose", str(path)]) == 0
    assert "step rate" in capsys.readouterr().out


# ------------------------------------------------- trainer end-to-end

def test_pretrain_emits_validating_stream_matching_steptimer(tmp_path):
    """The acceptance dryrun: a short CPU-mesh training run produces one
    events JSONL that validates, holds every lifecycle record, and from
    which diagnose reports step rate and boundary overlap matching
    StepTimer within 1%."""
    from proteinbert_tpu.configs import (
        CheckpointConfig, DataConfig, MeshConfig, ModelConfig,
        OptimizerConfig, PretrainConfig, TrainConfig,
    )
    from proteinbert_tpu.data import (
        InMemoryPretrainingDataset, make_pretrain_iterator,
    )
    from proteinbert_tpu.data.synthetic import make_random_proteins
    from proteinbert_tpu.parallel import make_mesh
    from proteinbert_tpu.train import Checkpointer
    from proteinbert_tpu.train.trainer import pretrain

    cfg = PretrainConfig(
        model=ModelConfig(local_dim=16, global_dim=32, key_dim=8,
                          num_heads=4, num_blocks=1, num_annotations=64,
                          dtype="float32"),
        data=DataConfig(seq_len=64, batch_size=8),
        optimizer=OptimizerConfig(warmup_steps=4),
        mesh=MeshConfig(data=2),
        checkpoint=CheckpointConfig(directory=str(tmp_path / "ck"),
                                    every_steps=4, overlap=True),
        train=TrainConfig(max_steps=8, log_every=2, eval_every=4),
    )
    rng = np.random.default_rng(0)
    seqs, ann = make_random_proteins(64, rng, num_annotations=64)
    ds = InMemoryPretrainingDataset(seqs, ann, 64)
    ck = Checkpointer(cfg.checkpoint.directory, async_save=False)
    tele = obs.Telemetry(events_path=str(tmp_path / "ev.jsonl"))
    out = pretrain(
        cfg, lambda skip: make_pretrain_iterator(ds, 8, seed=0),
        checkpointer=ck,
        mesh=make_mesh(cfg.mesh, devices=jax.devices()[:2]),
        eval_batches=lambda: make_pretrain_iterator(ds, 8, seed=1,
                                                    num_epochs=1),
        telemetry=tele)
    ck.close()
    tele.close()

    recs = obs.read_events(str(tmp_path / "ev.jsonl"), strict=True)
    kinds = {r["event"] for r in recs}
    assert {"run_start", "step", "ckpt_stage", "eval", "run_end"} <= kinds
    assert recs[0]["event"] == "run_start"
    assert recs[0]["jax_version"]
    assert recs[0]["config"]["train"]["max_steps"] == 8
    assert recs[0]["mesh"] == {"data": 2, "fsdp": 1, "model": 1, "seq": 1}
    assert recs[-1]["event"] == "run_end"
    assert recs[-1]["outcome"] == "completed"
    # The per-chip state-bytes gauges landed (sharding-rule accounting).
    gauges = tele.metrics.snapshot()["gauges"]
    assert gauges.get('per_chip_state_bytes{part="total"}', 0) > 0

    s = summarize(recs)
    perf = out["perf"]
    assert s["step_rate"]["steps_per_sec"] == pytest.approx(
        perf["steps_per_sec"], rel=0.01)
    assert s["boundary"]["overlap_s"] == pytest.approx(
        perf.get("overlap_s", 0.0), rel=0.01, abs=1e-9)
    # Step events carry the data-pipeline wait gauge (prefetch_depth=2).
    step_recs = [r for r in recs if r["event"] == "step"]
    assert all("data_wait_s" in r for r in step_recs)
    # The registry absorbed the run: counters + StepTimer gauges live.
    snap = tele.metrics.snapshot()
    assert snap["counters"]["steps_total"] == 8
    assert "steps_per_sec" in snap["gauges"]
