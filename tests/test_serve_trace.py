"""Per-request serve tracing (ISSUE 6).

Two tiers in one file:

- **RequestTrace invariants** — stages are contiguous clock intervals,
  so they tile [submit, done] and sum to the end-to-end latency by
  construction; a trace seals exactly once; stride sampling emits an
  exact fraction with no RNG state.
- **propagation** — fake-clock scheduler tests (queue-wait recorded
  even when tracing is off, rejections carry queue depth, terminal
  complete callbacks) and end-to-end Server tests over a real tiny
  trunk: drain vs abort leave no orphaned spans, failed batches close
  their traces with error status, sampling suppresses ok-requests but
  never failures, SLO burn rates surface on stats()/metrics/events.
"""

import threading
from concurrent.futures import Future

import numpy as np
import pytest

import jax

from proteinbert_tpu.configs import (
    CheckpointConfig, DataConfig, ModelConfig, OptimizerConfig,
    PretrainConfig, TrainConfig,
)
from proteinbert_tpu.obs import Telemetry, read_events
from proteinbert_tpu.obs.events import validate_record
from proteinbert_tpu.serve import (
    MicroBatchScheduler, Request, RequestQueue, RequestTrace, Server,
    ServerClosedError,
)
from proteinbert_tpu.serve.trace import STAGES, stride_sampled
from proteinbert_tpu.train import create_train_state

SEQ_LEN = 48
BUCKETS = (16, 32, 48)


def _cfg():
    return PretrainConfig(
        model=ModelConfig(local_dim=16, global_dim=32, key_dim=8,
                          num_heads=2, num_blocks=2, num_annotations=32,
                          dtype="float32"),
        data=DataConfig(seq_len=SEQ_LEN, batch_size=4),
        optimizer=OptimizerConfig(warmup_steps=5),
        train=TrainConfig(seed=0, max_steps=1),
        checkpoint=CheckpointConfig(),
    )


@pytest.fixture(scope="module")
def trunk():
    cfg = _cfg()
    state = create_train_state(jax.random.PRNGKey(cfg.train.seed), cfg)
    return state.params, cfg


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


# ------------------------------------------------- trace invariants

class TestRequestTrace:
    def test_stages_tile_submit_to_done(self):
        tr = RequestTrace("r1", "embed", now=10.0, wall=0.0)
        tr.mark_enqueued(10.1)
        tr.mark_ingested(10.3)
        tr.mark_popped(10.6)
        tr.mark_run(11.0, 11.5)
        tr.mark_batch(32, 4, rows=3, pad_fraction=0.25,
                      prep_s=0.4, device_s=0.5)
        assert tr.finish("ok", now=11.7)
        stages = tr.stages()
        # `lookup` exists only on neighbors requests (ISSUE 17) — a
        # plain embed tiles the remaining stages exactly.
        assert list(stages) == [s for s in STAGES if s != "lookup"]
        assert stages["submit"] == pytest.approx(0.1)
        assert stages["queue"] == pytest.approx(0.2)
        assert stages["batch_form"] == pytest.approx(0.3)
        assert stages["dispatch"] == pytest.approx(0.4)
        assert stages["execute"] == pytest.approx(0.5)
        assert stages["finalize"] == pytest.approx(0.2)
        # The acceptance property: contiguous intervals sum to e2e.
        assert sum(stages.values()) == pytest.approx(tr.e2e_s(), abs=1e-9)
        assert tr.e2e_s() == pytest.approx(1.7)

    def test_lookup_mark_splits_tail_and_still_tiles(self):
        tr = RequestTrace("r1n", "neighbors", now=10.0, wall=0.0)
        tr.mark_enqueued(10.1)
        tr.mark_ingested(10.3)
        tr.mark_popped(10.6)
        tr.mark_run(11.0, 11.5)
        tr.mark_lookup(11.62)
        assert tr.finish("ok", now=11.7)
        stages = tr.stages()
        assert list(stages) == list(STAGES)
        assert stages["execute"] == pytest.approx(0.5)
        assert stages["lookup"] == pytest.approx(0.12)
        assert stages["finalize"] == pytest.approx(0.08)
        assert sum(stages.values()) == pytest.approx(tr.e2e_s(), abs=1e-9)

    def test_early_exit_has_fewer_marks_still_tiles(self):
        tr = RequestTrace("r2", "embed", now=5.0, wall=0.0)
        assert tr.finish("rejected", now=5.01)
        assert tr.stages() == {"submit": pytest.approx(0.01)}
        tr2 = RequestTrace("r3", "embed", now=5.0, wall=0.0)
        tr2.mark_enqueued(5.1)
        assert tr2.finish("evicted", now=5.5)
        stages = tr2.stages()
        assert list(stages) == ["submit", "queue"]
        assert sum(stages.values()) == pytest.approx(tr2.e2e_s())

    def test_seals_exactly_once(self):
        tr = RequestTrace("r4", "embed", now=0.0, wall=0.0)
        assert tr.finish("error", now=1.0, error=RuntimeError("boom"))
        assert not tr.finish("ok", now=2.0)
        assert tr.outcome == "error"
        assert tr.e2e_s() == pytest.approx(1.0)
        assert "RuntimeError: boom" == tr.error

    def test_out_of_order_marks_clamp_monotonic(self):
        """Marks come from two threads' reads of one clock: a poll()
        that took `now` before a concurrent submit finished stamps
        ingest EARLIER than enqueue. The derived chain clamps, so the
        tiling invariant holds exactly anyway."""
        tr = RequestTrace("r6", "embed", now=10.0, wall=0.0)
        tr.mark_enqueued(10.5)
        tr.mark_ingested(10.4)     # scheduler's stale poll-entry now
        tr.mark_popped(10.6)
        tr.mark_run(10.7, 10.9)
        tr.finish("ok", now=10.8)  # completion read also stale
        stages = tr.stages()
        assert all(v >= 0 for v in stages.values())
        assert sum(stages.values()) == pytest.approx(tr.e2e_s(),
                                                     abs=1e-9)
        assert stages["batch_form"] == pytest.approx(0.1)  # clamped
        assert tr.e2e_s() == pytest.approx(0.9)  # end = last mark

    def test_stride_sampling_exact_fraction(self):
        for rate, expect in ((0.0, 0), (0.25, 250), (1.0, 1000)):
            hits = sum(stride_sampled(n, rate) for n in range(1, 1001))
            assert hits == expect

    def test_event_fields_round_trip_schema(self):
        from proteinbert_tpu.obs.events import make_record

        tr = RequestTrace("r5", "embed", now=0.0, wall=0.0)
        tr.mark_enqueued(0.1)
        tr.mark_batch(16, 2, rows=2, pad_fraction=0.5)
        tr.finish("ok", now=0.4)
        rec = make_record("serve_request", seq=0, t=0.0,
                          **tr.event_fields())
        validate_record(rec)
        assert rec["bucket_len"] == 16 and rec["pad_fraction"] == 0.5

    def test_spans_per_request_lanes(self):
        from proteinbert_tpu.obs import SpanCollector

        col = SpanCollector()
        for rid in ("a", "b"):
            tr = RequestTrace(rid, "embed", now=0.0, wall=100.0)
            tr.mark_enqueued(0.1)
            tr.finish("ok", now=0.3)
            tr.export_spans(col)
        spans = [s for s in col.to_perfetto()["traceEvents"]
                 if s["ph"] == "X"]
        parents = [s for s in spans if s["name"] == "serve.request"]
        assert len(parents) == 2
        # Distinct synthetic lanes: concurrent requests never nest.
        assert len({s["tid"] for s in parents}) == 2
        for p in parents:
            kids = [s for s in spans if s["tid"] == p["tid"]
                    and s["name"] != "serve.request"]
            assert {k["name"] for k in kids} == {"serve.submit",
                                                 "serve.queue"}
            assert sum(k["dur"] for k in kids) \
                == pytest.approx(p["dur"], rel=1e-6)


# -------------------------------------------- scheduler propagation

class FakeDispatcher:
    def __init__(self, fail_kinds=()):
        self.cfg = type("C", (), {})()
        self.cfg.model = type("M", (), {"num_annotations": 4})()
        self.fail_kinds = set(fail_kinds)

    def batch_class(self, rows):
        c = 1
        while c < rows:
            c *= 2
        return c

    def run(self, kind, tokens, annotations=None):
        if kind in self.fail_kinds:
            raise RuntimeError(f"injected dispatch failure for {kind}")
        return np.arange(tokens.shape[0], dtype=np.float32)


def _req(clock, kind="embed", bucket_len=16, deadline=None, trace=None):
    return Request(kind=kind, seq="MKT",
                   tokens=np.zeros(bucket_len, np.int32),
                   bucket_len=bucket_len, future=Future(),
                   enqueued_at=clock(), deadline=deadline, trace=trace)


def _sched(clock, telemetry=None, fail_kinds=(), **kw):
    queue = RequestQueue(max_depth=64)
    done = []
    completed = []
    s = MicroBatchScheduler(
        queue, FakeDispatcher(fail_kinds),
        lambda req, row: req.future.set_result(row) or done.append(req),
        max_batch=2, max_wait_s=0.5, clock=clock, telemetry=telemetry,
        complete_observer=lambda req, outcome, now, err, ctx:
            completed.append((req, outcome, err, ctx)))
    return s, queue, completed


class TestSchedulerPropagation:
    def test_queue_wait_recorded_without_traces(self, tmp_path):
        """The cheap always-on histogram: tracing entirely off (no
        trace objects), yet every dispatched request's queue wait
        lands in serve_queue_wait_seconds AND the stats mirror."""
        clock = FakeClock()
        tele = Telemetry(events_path=str(tmp_path / "ev.jsonl"))
        s, queue, completed = _sched(clock, telemetry=tele)
        queue.push(_req(clock))
        queue.push(_req(clock))
        assert s.poll(now=clock.advance(0.25)) == 2
        assert s.queue_wait.count == 2
        assert s.queue_wait.max == pytest.approx(0.25)
        snap = tele.metrics.snapshot()
        assert snap["histograms"]["serve_queue_wait_seconds"]["count"] == 2
        assert [o for _, o, _, _ in completed] == ["ok", "ok"]
        tele.close()

    def test_expiry_emits_queue_depth_and_completes_expired(
            self, tmp_path):
        clock = FakeClock()
        path = str(tmp_path / "ev.jsonl")
        tele = Telemetry(events_path=path)
        s, queue, completed = _sched(clock, telemetry=tele)
        tr = RequestTrace("rx", "embed", clock.t)
        queue.push(_req(clock, deadline=clock.t + 0.1, trace=tr))
        queue.push(_req(clock))  # alive: still pending after expiry
        s.poll(now=clock.advance(0.2))
        tele.close()
        rej = [r for r in read_events(path, strict=True)
               if r["event"] == "serve_reject"]
        assert len(rej) == 1 and rej[0]["reason"] == "deadline"
        # Depth at rejection: the one surviving pending request.
        assert rej[0]["queue_depth"] == 1
        validate_record(rej[0])
        assert [(o, type(e).__name__ if e else None)
                for _, o, e, _ in completed] == [("expired", None)]
        # Expired requests count in the queue-wait histogram too.
        assert s.queue_wait.count == 1
        assert tr.t_ingested is not None  # marks up to the expiry

    def test_dispatch_failure_completes_error_with_context(self):
        clock = FakeClock()
        s, queue, completed = _sched(clock, fail_kinds=("embed",))
        tr = RequestTrace("rf", "embed", clock.t)
        queue.push(_req(clock, trace=tr))
        queue.push(_req(clock, trace=RequestTrace("rg", "embed", clock.t)))
        s.poll(now=clock.advance(0.01))
        assert [o for _, o, _, _ in completed] == ["error", "error"]
        _, _, err, ctx = completed[0]
        assert isinstance(err, RuntimeError)
        assert ctx["rows"] == 2 and ctx["bucket_len"] == 16
        # The failed batch still closed the trace's run interval.
        assert tr.t_run0 is not None and tr.rows == 2


# ----------------------------------------------- server end-to-end

RAGGED = ["MKTAYIAKQR", "ACDEFGHIKLMNPQRSTVWY", "GG",
          "ACDEFGHIKLMNPQRSTVWY" * 2, "MKTAYIAKQRMKTAYIAKQRAC"]


def _server(trunk, tele, **kw):
    params, cfg = trunk
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_wait_s", 0.002)
    kw.setdefault("queue_depth", 64)
    kw.setdefault("cache_size", 8)
    kw.setdefault("warm_kinds", ())
    return Server(params, cfg, telemetry=tele, **kw)


class TestServerTracing:
    def test_drain_traces_sum_and_no_orphaned_spans(self, trunk,
                                                    tmp_path):
        path = str(tmp_path / "ev.jsonl")
        tele = Telemetry(events_path=path, spans=True)
        srv = _server(trunk, tele)
        srv.start()
        for seq in RAGGED:
            srv.embed(seq, timeout=30)
        srv.embed(RAGGED[0], timeout=30)  # cache hit
        srv.drain(timeout=30)
        tele.close()
        recs = read_events(path, strict=True)
        for rec in recs:
            validate_record(rec)
        reqs = [r for r in recs if r["event"] == "serve_request"]
        assert len(reqs) == len(RAGGED) + 1
        outcomes = [r["outcome"] for r in reqs]
        assert outcomes.count("ok") == len(RAGGED)
        assert outcomes.count("cache_hit") == 1
        ids = [r["request_id"] for r in reqs]
        assert len(set(ids)) == len(ids)  # sealed exactly once each
        for r in reqs:
            assert set(r["stages"]) <= set(STAGES)
            # Contiguous stages tile the request exactly.
            assert sum(r["stages"].values()) \
                == pytest.approx(r["e2e_s"], abs=1e-5)
            if r["outcome"] == "ok":
                assert r["bucket_len"] in BUCKETS
                assert r["rows"] >= 1 and 0 <= r["pad_fraction"] < 1
                assert {"queue", "execute"} <= set(r["stages"])
            assert r["cache"] == ("hit" if r["outcome"] == "cache_hit"
                                  else "miss")
        # Spans: one closed parent lane per emitted trace, no orphans.
        spans = [s for s in tele.spans.to_perfetto()["traceEvents"]
                 if s["ph"] == "X"]
        parents = [s for s in spans if s["name"] == "serve.request"]
        assert sorted(p["args"]["request_id"] for p in parents) \
            == sorted(ids)
        assert all(p["args"]["outcome"] in ("ok", "cache_hit")
                   for p in parents)

    def test_sampled_out_suppresses_ok_never_failures(self, trunk,
                                                      tmp_path):
        path = str(tmp_path / "ev.jsonl")
        tele = Telemetry(events_path=path)
        srv = _server(trunk, tele, on_long="reject",
                      trace_sample_rate=0.0)
        srv.start()
        fut = srv.submit("embed", RAGGED[0])
        assert fut.pbt_request_id  # traced (cheap marks) even at rate 0
        fut.result(timeout=30)
        from proteinbert_tpu.serve import SequenceTooLongError

        with pytest.raises(SequenceTooLongError) as ei:
            srv.embed("A" * (SEQ_LEN + 10), timeout=30)
        srv.drain(timeout=30)
        tele.close()
        reqs = [r for r in read_events(path, strict=True)
                if r["event"] == "serve_request"]
        # The ok request is sampled out; the rejection always emits.
        assert [r["outcome"] for r in reqs] == ["rejected"]
        assert reqs[0]["sampled"] is False
        # Synchronous rejections carry the trace id on the exception
        # (the HTTP layer's X-PBT-Request-Id for 400/503 responses).
        assert ei.value.pbt_request_id == reqs[0]["request_id"]

    def test_abort_seals_every_trace_no_orphans(self, trunk, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        tele = Telemetry(events_path=path, spans=True)
        # max_wait high + max_batch high: submits sit pending/queued
        # until the abort kills them.
        srv = _server(trunk, tele, max_batch=64, max_wait_s=60.0)
        srv.start()
        futs = [srv.submit("embed", seq) for seq in RAGGED[:3]]
        ids = {f.pbt_request_id for f in futs}
        srv.abort()
        tele.close()
        for f in futs:
            with pytest.raises(ServerClosedError):
                f.result(timeout=5)
        reqs = [r for r in read_events(path, strict=True)
                if r["event"] == "serve_request"]
        assert {r["request_id"] for r in reqs} == ids
        assert all(r["outcome"] == "aborted" for r in reqs)
        assert all("ServerClosedError" in r["error"] for r in reqs)
        parents = [s for s in tele.spans.to_perfetto()["traceEvents"]
                   if s.get("name") == "serve.request"]
        assert {p["args"]["request_id"] for p in parents} == ids
        assert all(p["args"]["outcome"] == "aborted" for p in parents)
        end = [r for r in read_events(path) if r["event"] == "serve_end"]
        assert end and end[-1]["outcome"] == "aborted"

    def test_failed_batch_closes_traces_with_error_status(
            self, trunk, tmp_path, monkeypatch):
        path = str(tmp_path / "ev.jsonl")
        tele = Telemetry(events_path=path, spans=True)
        srv = _server(trunk, tele, cache_size=0)
        def boom(*a, **kw):
            raise RuntimeError("injected device failure")
        monkeypatch.setattr(srv.dispatcher, "run_timed_async", boom)
        monkeypatch.setattr(srv.dispatcher, "run_timed", boom)
        monkeypatch.setattr(srv.dispatcher, "run", boom)
        srv.start()
        futs = [srv.submit("embed", s) for s in RAGGED[:2]]
        for f in futs:
            with pytest.raises(RuntimeError, match="injected"):
                f.result(timeout=30)
        srv.drain(timeout=30)
        tele.close()
        reqs = [r for r in read_events(path, strict=True)
                if r["event"] == "serve_request"]
        assert len(reqs) == 2
        for r in reqs:
            assert r["outcome"] == "error"
            assert "injected device failure" in r["error"]
            # The failed batch still closed its execute interval.
            assert "execute" in r["stages"]
            assert sum(r["stages"].values()) \
                == pytest.approx(r["e2e_s"], abs=1e-5)

    def test_eviction_seals_trace_with_queue_depth(self, trunk,
                                                   tmp_path):
        from proteinbert_tpu.serve import QueueFullError

        path = str(tmp_path / "ev.jsonl")
        tele = Telemetry(events_path=path)
        # Scheduler never started: the queue overflows synchronously.
        srv = _server(trunk, tele, queue_depth=1, cache_size=0)
        f1 = srv.submit("embed", RAGGED[0])
        srv.submit("embed", RAGGED[1])
        with pytest.raises(QueueFullError):
            f1.result(timeout=5)
        srv.abort()
        tele.close()
        recs = read_events(path, strict=True)
        rej = [r for r in recs if r["event"] == "serve_reject"]
        assert rej[0]["reason"] == "queue_full"
        assert rej[0]["queue_depth"] == 1
        by_outcome = {r["outcome"]: r for r in recs
                      if r["event"] == "serve_request"}
        assert by_outcome["evicted"]["request_id"] == f1.pbt_request_id
        assert "aborted" in by_outcome  # the survivor sealed too

    def test_stats_api_shape_kept_and_single_ring(self, trunk):
        """Satellite: the latency ring lives in the obs registry; the
        stats() surface (ISSUE 5 shape) must not change, and /metrics
        must read the SAME ring at scrape time."""
        tele = Telemetry()
        srv = _server(trunk, tele)
        srv.start()
        srv.embed(RAGGED[0], timeout=30)
        stats = srv.stats()
        assert {"n", "p50_s", "p99_s", "mean_s"} == set(stats["latency"])
        assert stats["latency"]["n"] == 1
        assert stats["queue_wait"]["count"] == 1
        assert stats["queue_wait"]["mean_s"] >= 0.0
        # One ring: the registry window IS the server's window.
        assert tele.metrics.quantile_window("serve_latency") \
            is srv.latencies
        prom = tele.metrics.prometheus_text()
        assert "pbt_serve_latency_p50_s" in prom
        assert "pbt_serve_queue_wait_seconds_count 1" in prom
        srv.drain(timeout=30)

    def test_null_telemetry_creates_no_traces_stats_still_real(
            self, trunk):
        srv = _server(trunk, None)
        srv.start()
        fut = srv.submit("embed", RAGGED[0])
        assert not hasattr(fut, "pbt_request_id")
        fut.result(timeout=30)
        stats = srv.stats()
        assert stats["latency"]["n"] == 1  # live unregistered ring
        assert stats["queue_wait"]["count"] == 1
        assert srv.trace_sample_rate is None
        srv.drain(timeout=30)

    def test_slo_surfaces_on_stats_metrics_events(self, trunk,
                                                  tmp_path):
        path = str(tmp_path / "ev.jsonl")
        tele = Telemetry(events_path=path)
        srv = _server(trunk, tele, cache_size=0,
                      slos=["kind=latency,threshold_s=1e-9,target=0.99",
                            "kind=error_rate,target=0.999"])
        srv.start()
        for seq in RAGGED[:3]:
            srv.embed(seq, timeout=30)
        # The future resolves a beat before the scheduler thread feeds
        # the SLO evaluator: drain first, then read.
        srv.drain(timeout=30)
        stats = srv.stats()
        slo = stats["slo"]["latency_e2e"]
        assert slo["breached"] and slo["burn_rate"] > 1.0
        assert slo["total"] == 3 and slo["bad"] == 3
        # Violation attribution includes the padding-waste lever.
        assert "pad_wasted" in slo["attribution"]
        assert "execute" in slo["attribution"]
        assert stats["slo"]["error_rate"]["bad"] == 0
        # Exemplars link a histogram bucket to a traced request id.
        exemplars = [b["exemplar"] for b in slo["histogram"]
                     if b["exemplar"]]
        assert exemplars and all(
            e["request_id"].endswith(("1", "2", "3"))
            for e in exemplars)
        prom = tele.metrics.prometheus_text()
        assert 'pbt_slo_burn_rate{objective="latency_e2e"}' in prom
        srv.drain(timeout=30)
        tele.close()
        breaches = [r for r in read_events(path, strict=True)
                    if r["event"] == "slo_breach"]
        assert breaches and breaches[0]["objective"] == "latency_e2e"
        assert breaches[0]["burn_rate"] > 1.0

    def test_stage_scoped_slo_requires_tracing(self, trunk):
        """A stage objective with tracing off would never observe —
        the Server rejects the dead config at init."""
        with pytest.raises(ValueError, match="stage-scoped"):
            _server(trunk, None,
                    slos=["kind=latency,stage=execute,threshold_ms=50"])
        with pytest.raises(ValueError, match="stage-scoped"):
            _server(trunk, Telemetry(), trace_sample_rate=None,
                    slos=["kind=latency,stage=execute,threshold_ms=50"])
        # e2e objectives work without tracing: no error.
        _server(trunk, Telemetry(), trace_sample_rate=None,
                slos=["kind=latency,threshold_ms=250"])

    def test_diagnose_serve_section(self, trunk, tmp_path, capsys):
        from proteinbert_tpu.obs.diagnose import (
            render_serve, summarize_serve,
        )

        path = str(tmp_path / "ev.jsonl")
        tele = Telemetry(events_path=path)
        srv = _server(trunk, tele,
                      slos=["kind=latency,threshold_s=1e-9,target=0.99"])
        srv.start()
        for seq in RAGGED:
            srv.embed(seq, timeout=30)
        srv.drain(timeout=30)
        tele.close()
        records = read_events(path, strict=True)
        s = summarize_serve(records)
        assert s["outcome"] == "drained"
        assert s["requests_traced"] == len(RAGGED)
        assert s["e2e"]["n"] == len(RAGGED)
        assert s["e2e"]["p99_s"] >= s["e2e"]["p50_s"] > 0
        attr = s["stage_attribution"]
        assert "execute" in attr and "queue" in attr
        # Wall-clock stages share out to 1.0; pad_wasted overlaps
        # execute, so it is reported beside them, not inside the sum.
        shares = [a["share"] for k, a in attr.items()
                  if a["share"] is not None and "(" not in k]
        assert sum(shares) == pytest.approx(1.0, abs=0.02)
        assert "pad_wasted(of execute)" in attr
        assert len(s["slowest"]) == min(5, len(RAGGED))
        assert s["batches"]["rows"] == len(RAGGED)
        assert s["final_slo"]["latency_e2e"]["burn_rate"] > 1.0
        text = render_serve(s)
        assert "where the time went" in text
        assert "e2e latency" in text
