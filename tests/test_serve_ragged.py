"""Ragged packed serving (proteinbert_tpu/serve/, ISSUE 9).

Two tiers, mirroring tests/test_serve.py:

- **pure-logic tests**: `PackedBatchScheduler` formation against a stub
  packed dispatcher and a fake clock — first-fit placement geometry,
  the open-frontier dispatch trigger, max-wait, deadline expiry inside
  open rows, drain, fail_pending. Deterministic via `poll(now=)`.
- **end-to-end tests**: one tiny untrained trunk (module fixture)
  proving THE parity contract — every ragged-mode per-request output
  matches the bucketed dispatcher's on identical traffic within the
  documented jitted ≤1e-5 tolerance (PR 7 split-parity precedent;
  bucket-quantized spans make the two programs compute the same math —
  serve/dispatch.RaggedDispatcher module doc) — plus the
  O(kinds x row classes) executable-count collapse, packed telemetry
  fields round-tripping the schema validator, `pbt diagnose --serve`
  surfacing, and the fused-kernel fallback counter satellite.
- **row classes** (ISSUE 25): the class an under-full dispatch runs at,
  the rows it leaves open, its decay from a backlog (no hysteresis), and
  on the real dispatcher the same answer at every class, every class
  warm after `warmup()`, and classes a mesh cannot split left out.
"""

import dataclasses
import logging
import threading
import time
from concurrent.futures import Future
from types import SimpleNamespace

import numpy as np
import pytest

import jax

from proteinbert_tpu import inference
from proteinbert_tpu.configs import (
    CheckpointConfig, DataConfig, ModelConfig, OptimizerConfig,
    PretrainConfig, TaskConfig, TrainConfig,
)
from proteinbert_tpu.data.vocab import ALPHABET
from proteinbert_tpu.heads.registry import LoadedHead
from proteinbert_tpu.models import finetune as ft_model
from proteinbert_tpu.obs import tracing
from proteinbert_tpu.serve import (
    DeadlineExceededError, PackedBatchScheduler, RaggedDispatcher,
    Request, RequestQueue, Server, ServerClosedError,
)
from proteinbert_tpu.serve.dispatch import default_row_classes
from proteinbert_tpu.train import create_train_state

SEQ_LEN = 48
BUCKETS = (16, 32, 48)
DENSE_BUCKETS = (8, 16, 24, 32, 40, 48)
MODEL = ModelConfig(local_dim=16, global_dim=32, key_dim=8, num_heads=2,
                    num_blocks=2, num_annotations=32, dtype="float32")


def _cfg():
    return PretrainConfig(
        model=MODEL,
        data=DataConfig(seq_len=SEQ_LEN, batch_size=4, buckets=BUCKETS),
        optimizer=OptimizerConfig(warmup_steps=5),
        train=TrainConfig(seed=0, max_steps=1),
        checkpoint=CheckpointConfig(),
    )


@pytest.fixture(scope="module")
def trunk():
    cfg = _cfg()
    state = create_train_state(jax.random.PRNGKey(cfg.train.seed), cfg)
    return state.params, cfg


@pytest.fixture(scope="module")
def seqs():
    rng = np.random.default_rng(11)
    return ["".join(rng.choice(list(ALPHABET), size=int(n)))
            for n in rng.integers(4, SEQ_LEN - 2, size=14)]


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


class StubRaggedDispatcher:
    """Records packed batches; returns one token-of-proof per rider."""

    def __init__(self, seq_len=SEQ_LEN, num_ann=4, batch_classes=None,
                 device=None):
        self.cfg = SimpleNamespace(
            data=SimpleNamespace(seq_len=seq_len),
            model=SimpleNamespace(num_annotations=num_ann))
        self.calls = []
        self.fail_with = None
        if batch_classes is not None:   # else: a stub that knows of none
            self.batch_classes = tuple(batch_classes)
        # (fake clock, seconds a row): the device a batch keeps busy
        self.device = device

    def run_packed(self, kind, tokens, segment_ids, annotations, riders,
                   heads=None):
        if self.fail_with is not None:
            raise self.fail_with
        self.calls.append({
            "kind": kind, "tokens": tokens.copy(),
            "segment_ids": segment_ids.copy(),
            "riders": [tuple(r) for r in riders]})
        if self.device is not None:
            clock, row_seconds = self.device
            clock.advance(tokens.shape[0] * row_seconds)
        return [("ok", kind) + tuple(r) for r in riders]

    def run_packed_timed(self, kind, tokens, segment_ids, annotations,
                         riders, heads=None, timed=True):
        # `timed` mirrors the real dispatcher's contract: the scheduler
        # now always calls this entry (timed=False on untimed batches,
        # so the quantized arm's event fields flow either way).
        outs = self.run_packed(kind, tokens, segment_ids, annotations,
                               riders, heads=heads)
        if not timed:
            return outs, {}
        real = int((tokens != 0).sum())
        grid = tokens.size
        return outs, {"pad_fraction": round(1 - real / grid, 6),
                      "segments": len(riders),
                      "segments_per_row": round(
                          len(riders) / tokens.shape[0], 4)}


def _req(kind="embed", seq="MKT", span=16, clock=None, deadline=None):
    tokens = np.full(span, 7, np.int32)
    return Request(kind=kind, seq=seq, tokens=tokens, bucket_len=span,
                   future=Future(), enqueued_at=clock() if clock else 0.0,
                   deadline=deadline)


def _sched(dispatcher=None, rows=2, max_wait=0.01, clock=None,
           max_segments=4, depth=64, **kw):
    q = RequestQueue(max_depth=depth)
    done = []

    def finalize(req, row):  # the Server's _finalize resolves futures
        done.append((req, row))
        if not req.future.done():
            req.future.set_result(row)

    sched = PackedBatchScheduler(
        q, dispatcher or StubRaggedDispatcher(), finalize,
        rows_per_batch=rows, max_wait_s=max_wait,
        clock=clock or FakeClock(), max_segments=max_segments, **kw)
    return q, sched, done


# ----------------------------------------------------- formation logic

class TestPackedFormation:
    def test_first_fit_geometry_rides_the_batch(self):
        clock = FakeClock()
        disp = StubRaggedDispatcher()
        q, sched, done = _sched(disp, rows=2, clock=clock)
        # spans 20+20 fill row0 to 40 (<48-2 left over), 20 opens row1
        for s in ("a", "b", "c"):
            q.push(_req(seq=s, span=20, clock=clock))
        q.close()
        assert sched.poll(clock()) == 3
        (call,) = disp.calls
        # riders: (row, seg0based, start, span), row-major
        assert call["riders"] == [(0, 0, 0, 20), (0, 1, 20, 20),
                                  (1, 0, 0, 20)]
        assert (call["segment_ids"][0, :20] == 1).all()
        assert (call["segment_ids"][0, 20:40] == 2).all()
        assert (call["segment_ids"][0, 40:] == 0).all()
        assert (call["segment_ids"][1, :20] == 1).all()
        assert len(done) == 3

    def test_open_frontier_trigger_keeps_newest_row(self):
        clock = FakeClock()
        disp = StubRaggedDispatcher()
        q, sched, done = _sched(disp, rows=1, clock=clock)
        # Two full-ish rows + a third opens: dispatch pops the OLDEST
        # row only; the frontier row stays open for more fill.
        for s in "abc":
            q.push(_req(seq=s, span=40, clock=clock))
        assert sched.poll(clock()) == 1      # >1 open rows -> oldest
        assert sched.pending_rows() == 2
        assert sched.poll(clock()) == 1      # still >1 (b, c)
        assert sched.pending_rows() == 1
        assert sched.poll(clock()) == 0      # one open row, not overdue
        clock.advance(0.02)                  # max_wait trigger
        assert sched.poll(clock()) == 1
        assert sched.pending_rows() == 0

    def test_max_wait_dispatches_underfull(self):
        clock = FakeClock()
        q, sched, done = _sched(rows=4, clock=clock)
        q.push(_req(span=16, clock=clock))
        assert sched.poll(clock()) == 0
        clock.advance(0.005)
        assert sched.poll(clock()) == 0      # not overdue yet
        clock.advance(0.006)
        assert sched.poll(clock()) == 1      # overdue -> ships 1 rider
        assert len(done) == 1

    def test_deadline_expires_inside_open_row(self):
        clock = FakeClock()
        q, sched, done = _sched(rows=4, clock=clock)
        doomed = _req(span=16, clock=clock, deadline=clock() + 0.002)
        live = _req(span=16, clock=clock)
        q.push(doomed)
        q.push(live)
        sched.poll(clock())                  # ingest + pack, no dispatch
        clock.advance(0.005)                 # past doomed's deadline
        sched.poll(clock())
        with pytest.raises(DeadlineExceededError):
            doomed.future.result(timeout=0)
        assert sched.expired_total == 1
        clock.advance(0.01)
        assert sched.poll(clock()) == 1      # live one still ships
        assert live.future.result(timeout=0)[0] == "ok"

    def test_dispatch_failure_fails_batch_only(self):
        clock = FakeClock()
        disp = StubRaggedDispatcher()
        q, sched, done = _sched(disp, rows=1, clock=clock)
        boom = RuntimeError("device on fire")
        disp.fail_with = boom
        r1 = _req(span=16, clock=clock)
        q.push(r1)
        clock.advance(0.02)
        assert sched.poll(clock()) == 1
        assert r1.future.exception(timeout=0) is boom
        disp.fail_with = None
        r2 = _req(span=16, clock=clock)
        q.push(r2)
        clock.advance(0.02)
        assert sched.poll(clock()) == 1      # scheduler survived
        assert r2.future.result(timeout=0)[0] == "ok"

    def test_fail_pending_drains_packed_rows(self):
        clock = FakeClock()
        q, sched, done = _sched(rows=8, clock=clock)
        reqs = [_req(seq=s, span=16, clock=clock) for s in "abcd"]
        for r in reqs:
            q.push(r)
        sched.poll(clock())                  # packed, not dispatched
        failed = sched.fail_pending(ServerClosedError("abort"))
        assert [id(r) for r in failed] == [id(r) for r in reqs]
        for r in reqs:
            with pytest.raises(ServerClosedError):
                r.future.result(timeout=0)
        assert sched.pending_rows() == 0

    def test_formation_deterministic_under_fake_clock(self):
        def run():
            clock = FakeClock()
            disp = StubRaggedDispatcher()
            q, sched, _ = _sched(disp, rows=2, clock=clock)
            rng = np.random.default_rng(5)
            for i in range(12):
                q.push(_req(seq=str(i), span=int(rng.choice(BUCKETS)),
                            clock=clock))
                clock.advance(0.001)
                sched.poll(clock())
            q.close()
            while sched.poll(clock()):
                pass
            return [c["riders"] for c in disp.calls]

        assert run() == run()


# --------------------------------------------------------- row classes

CLASSES = (2, 4, 8, 16)


def _row_sched(clock, max_wait=0.01):
    """R = 16 over the ladder (2, 4, 8, 16); `_row` requests fill a row
    each, so open rows are requests."""
    disp = StubRaggedDispatcher(batch_classes=CLASSES)
    q, sched, done = _sched(disp, rows=16, max_wait=max_wait, clock=clock,
                            depth=4096)
    return disp, q, sched, done


def _row(clock, seq="r"):
    return _req(seq=seq, span=SEQ_LEN, clock=clock)


class TestRowClasses:
    """ISSUE 25: an under-full packed batch runs at the largest class
    its open rows fill, the newer rows stay open, and only under the
    smallest class is a batch padded."""

    @pytest.mark.parametrize("rows,multiple,want", [
        (512, 1, (64, 128, 256, 512)),
        (16, 1, (2, 4, 8, 16)),
        (4, 1, (1, 2, 4)),
        (6, 1, (3, 6)),
        (3, 1, (3,)),
        (1, 1, (1,)),
        (8, 2, (2, 4, 8)),
        (12, 4, (12,)),
        (512, 4, (64, 128, 256, 512)),
    ])
    def test_the_derived_ladder(self, rows, multiple, want):
        assert default_row_classes(rows, multiple) == want

    @pytest.mark.parametrize("n,cls,left", [
        (1, 2, 0),        # under the smallest class: run it, padded
        (2, 2, 0),        # at a class
        (3, 2, 1),        # between: round DOWN, the newest stays open
        (4, 4, 0),
        (7, 4, 3),
        (8, 8, 0),
        (15, 8, 7),
        (16, 16, 0),      # the largest class, full
    ])
    def test_class_chosen_for_open_rows(self, n, cls, left):
        clock = FakeClock()
        disp, q, sched, done = _row_sched(clock)
        reqs = [_row(clock, seq=str(i)) for i in range(n)]
        for r in reqs:
            q.push(r)
        assert sched.poll(clock()) == 0       # nothing overdue yet
        clock.advance(0.02)
        popped = min(n, cls)
        assert sched.poll(clock()) == popped
        (call,) = disp.calls
        assert call["tokens"].shape == (cls, SEQ_LEN)
        # the OLDEST rows ride, in order; the newer ones stay open
        assert [r[0] for r in call["riders"]] == list(range(popped))
        assert all(r.future.done() for r in reqs[:popped])
        assert not any(r.future.done() for r in reqs[popped:])
        assert sched.pending_rows() == left
        # rows past the popped ones are padding: all zeros
        assert not call["tokens"][popped:].any()
        assert sched.class_counts() == ({cls: 1}, cls * SEQ_LEN)

    @pytest.mark.parametrize("n", [17, 32, 49])
    def test_more_than_R_open_rows_always_runs_R(self, n):
        clock = FakeClock()
        disp, q, sched, done = _row_sched(clock, max_wait=60.0)
        for i in range(n):
            q.push(_row(clock, seq=str(i)))
        left = n
        while left > 16:                      # no wait: throughput bound
            assert sched.poll(clock()) == 16
            assert disp.calls[-1]["tokens"].shape == (16, SEQ_LEN)
            left -= 16
        assert sched.pending_rows() == left
        assert sched.poll(clock()) == 0       # at most R and not overdue

    def test_a_stub_without_classes_runs_rows_per_batch(self):
        clock = FakeClock()
        disp = StubRaggedDispatcher()
        q, sched, done = _sched(disp, rows=4, clock=clock)
        assert sched.row_classes == (4,)
        q.push(_row(clock))
        clock.advance(0.02)
        assert sched.poll(clock()) == 1
        assert disp.calls[0]["tokens"].shape == (4, SEQ_LEN)

    def test_drain_flushes_by_classes(self):
        clock = FakeClock()
        disp, q, sched, done = _row_sched(clock, max_wait=60.0)
        for i in range(13):
            q.push(_row(clock, seq=str(i)))
        q.close()
        ran = []
        while sched.poll(clock()):
            ran.append(disp.calls[-1]["tokens"].shape[0])
        assert ran == [8, 4, 2]               # 13 = 8 + 4 + 1 (padded to 2)
        assert len(done) == 13
        assert sched.class_counts() == ({8: 1, 4: 1, 2: 1}, 14 * SEQ_LEN)

    @staticmethod
    def _simulate(backlog, every_ms, ms=1500.0, max_wait_ms=20.0,
                  other_kind_at_ms=None):
        """R = 64 over (8, 16, 32, 64) on a serial device that takes
        1 ms a row, padding rows too (so full batches drain one row a
        ms), a row arriving every `every_ms`, `backlog` rows open at the
        start; at `other_kind_at_ms` one `logits` request arrives among
        the embeds. Returns [(kind, class run, rows popped, when
        dispatched)]."""
        clock = FakeClock(0.0)
        disp = StubRaggedDispatcher(batch_classes=(8, 16, 32, 64),
                                    device=(clock, 1e-3))
        q, sched, done = _sched(disp, rows=64, clock=clock, depth=4096,
                                max_wait=max_wait_ms * 1e-3)
        for i in range(backlog):
            q.push(_row(clock, seq=f"b{i}"))
        ran, k = [], 0
        while clock() < ms * 1e-3:
            while k * every_ms * 1e-3 <= clock():     # what has arrived
                r = _row(clock, seq=str(k))
                r.enqueued_at = k * every_ms * 1e-3
                q.push(r)
                k += 1
            if other_kind_at_ms is not None \
                    and clock() >= other_kind_at_ms * 1e-3:
                q.push(_req(kind="logits", span=SEQ_LEN, clock=clock))
                other_kind_at_ms = None
            at = clock()
            popped = sched.poll(at)                   # the device's time
            if popped:                                # passes inside it
                call = disp.calls[-1]
                ran.append((call["kind"], call["tokens"].shape[0],
                            popped, at))
            else:
                clock.advance(0.25e-3)
        return ran

    def test_no_hysteresis_from_a_full_backlog(self):
        """Arrivals at 0.8 x the rate a full batch drains: from a
        64-row backlog the class decays to the class an empty start
        settles at, and stays."""
        cold = [c for _, c, _, _ in self._simulate(backlog=0, every_ms=1.25)]
        hot = [c for _, c, _, _ in self._simulate(backlog=64, every_ms=1.25)]
        assert hot[0] == 64 and cold[0] == 16
        assert set(cold) == {16}              # 20 ms of arrivals: 16 rows
        settled = hot.index(16)
        assert 0 < settled < 12
        # down through the classes, never back up
        assert all(a >= b for a, b in zip(hot, hot[1:]))
        assert sorted(set(hot), reverse=True) == [64, 32, 16]
        assert hot[-40:] == cold[-40:] == [16] * 40

    def test_overload_climbs_to_full_batches_by_itself(self):
        """Arrivals at 1.1 x the rate the device drains: no class keeps
        up, the open rows grow by a tenth a batch, the class climbs with
        them, and once more than R are open every batch is R rows. No
        batch on the way runs a padding row."""
        ran = self._simulate(backlog=0, every_ms=1 / 1.1, ms=2500.0)
        classes = [c for _, c, _, _ in ran]
        assert all(n == c for _, c, n, _ in ran)      # never padded
        assert all(a <= b for a, b in zip(classes, classes[1:]))
        assert sorted(set(classes)) == [16, 32, 64]
        assert classes[-10:] == [64] * 10

    def test_a_minority_kind_is_not_held_by_a_busy_one(self):
        """One `logits` request among embeds arriving at 0.8 x the
        device's rate rides within max_wait and the batch then on the
        device, alone in the smallest class: another kind's load never
        holds it, nor pads it to that kind's batch."""
        ran = self._simulate(backlog=0, every_ms=1.25, ms=600.0,
                             other_kind_at_ms=300.0)
        ((kind, cls, popped, at),) = [r for r in ran if r[0] != "embed"]
        assert (kind, cls, popped) == ("logits", 8, 1)
        assert 0.300 + 0.020 <= at <= 0.300 + 0.020 + 0.016 + 1e-3
        # ... and the embeds go on at their class on both sides of it
        assert {c for k, c, _, _ in ran if k == "embed"} == {16}

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_bursts_run_no_padding_above_the_smallest_class(self, seed):
        """Rows arriving in bursts of any size (a stalled client's
        catch-up): every batch is filled by the rows it pops, except one
        under the smallest class."""
        rng = np.random.default_rng(seed)
        clock = FakeClock()
        disp, q, sched, done = _row_sched(clock)
        pushed = 0
        for _ in range(60):
            for _ in range(int(rng.integers(0, 40))):
                q.push(_row(clock))
                pushed += 1
            clock.advance(0.0125)
            while sched.poll(clock()):
                pass
        clock.advance(0.0125)
        while sched.poll(clock()):
            pass
        assert len(done) == pushed
        for call in disp.calls:
            cls = call["tokens"].shape[0]
            assert len(call["riders"]) == cls or cls == CLASSES[0]
        classes, positions = sched.class_counts()
        assert sum(classes.values()) == len(disp.calls)
        assert positions == SEQ_LEN * sum(c * n for c, n in classes.items())

    def test_spans_and_events_carry_rows_and_class(self):
        clock = FakeClock()
        seen = []

        from proteinbert_tpu.obs import Telemetry

        class Capture(Telemetry):
            def emit(self, event, **fields):
                seen.append((event, fields))

        disp = StubRaggedDispatcher(batch_classes=CLASSES)
        q, sched, done = _sched(disp, rows=16, clock=clock, depth=4096,
                                telemetry=Capture(metrics=False))
        for i in range(5):
            q.push(_row(clock, seq=str(i)))
        clock.advance(0.02)
        assert sched.poll(clock()) == 4
        (batch,) = [f for e, f in seen if e == "serve_batch"]
        assert (batch["rows"], batch["batch_class"]) == (4, 4)
        clock.advance(0.02)
        assert sched.poll(clock()) == 1
        batch = [f for e, f in seen if e == "serve_batch"][-1]
        assert (batch["rows"], batch["batch_class"]) == (1, 2)


# ------------------------------------------------------- end to end

def _drain_poll(srv, futs):
    srv.queue.close()
    while srv.scheduler.poll():
        pass
    return [f.result(timeout=5) for f in futs]


def _serve(trunk, mode, kind, seqs, heads=None, head_of=None, **kw):
    params, cfg = trunk
    srv = Server(params, cfg, max_batch=4, max_wait_s=60.0, cache_size=0,
                 warm_kinds=(), serve_mode=mode, heads=heads, **kw)
    futs = [srv.submit(kind, s,
                       head_id=head_of(i) if head_of else None)
            for i, s in enumerate(seqs)]
    out = _drain_poll(srv, futs)
    stats = srv.stats()
    srv.drain(timeout=10)
    return out, stats


class TestRaggedParity:
    """THE acceptance gate: identical traffic, bucketed vs ragged,
    per-request outputs within the documented jitted ≤1e-5 tolerance."""

    @pytest.mark.parametrize("ladder", [BUCKETS, DENSE_BUCKETS],
                             ids=["matched", "dense"])
    def test_embed_parity_and_executable_collapse(self, trunk, seqs,
                                                  ladder):
        """In ragged mode the ladder only quantizes spans (the compiled
        shape stays rows x seq_len), so one twice as dense costs the
        ragged server no executable and the bucketed one a shape a
        rung."""
        b, bs = _serve(trunk, "bucketed", "embed", seqs, buckets=ladder)
        r, rs = _serve(trunk, "ragged", "embed", seqs, buckets=ladder)
        for x, y in zip(b, r):
            np.testing.assert_allclose(x["global"], y["global"],
                                       atol=1e-5, rtol=1e-5)
            np.testing.assert_allclose(x["local_mean"], y["local_mean"],
                                       atol=1e-5, rtol=1e-5)
        # O(kinds x row classes): the one kind served, at the classes
        # of max_batch=4 that were run, and never a shape per bucket.
        assert set(rs["batch_class_counts"]) <= {1, 2, 4}
        assert rs["executables"] == len(rs["batch_class_counts"]) <= 3
        assert rs["batched_positions"] == SEQ_LEN * sum(
            c * n for c, n in rs["batch_class_counts"].items())
        assert bs["executables"] > rs["executables"]
        assert rs["serve_mode"] == "ragged"

    def test_predict_go_parity(self, trunk, seqs):
        b, _ = _serve(trunk, "bucketed", "predict_go", seqs)
        r, _ = _serve(trunk, "ragged", "predict_go", seqs)
        for x, y in zip(b, r):
            np.testing.assert_allclose(x, y, atol=1e-5, rtol=1e-5)

    def test_predict_residues_parity_shapes_and_fill(self, trunk, seqs):
        masked = [s[:2] + "?" + s[3:] if len(s) > 3 else s for s in seqs]
        b, _ = _serve(trunk, "bucketed", "predict_residues", masked)
        r, _ = _serve(trunk, "ragged", "predict_residues", masked)
        for (bf, bp), (rf, rp) in zip(b, r):
            assert bp.shape == rp.shape  # (bucket_len == span, V)
            np.testing.assert_allclose(bp, rp, atol=1e-5, rtol=1e-5)
            assert bf == rf              # same argmax fills

    def test_predict_task_mixed_heads_parity(self, trunk, seqs):
        tasks = [TaskConfig(kind="token_classification", num_outputs=4),
                 TaskConfig(kind="sequence_classification", num_outputs=3),
                 TaskConfig(kind="sequence_regression", num_outputs=1)]
        heads = [LoadedHead(f"h{i}", f"h{i}", t,
                            ft_model.head_init(jax.random.PRNGKey(i + 1),
                                               MODEL, t), {})
                 for i, t in enumerate(tasks)]
        b, _ = _serve(trunk, "bucketed", "predict_task", seqs,
                      heads=heads, head_of=lambda i: f"h{i % 3}")
        r, rs = _serve(trunk, "ragged", "predict_task", seqs,
                       heads=heads, head_of=lambda i: f"h{i % 3}")
        for i, (x, y) in enumerate(zip(b, r)):
            assert x.shape == y.shape, i
            np.testing.assert_allclose(x, y, atol=1e-5, rtol=1e-5)
        # One shared packed trunk a row class run; tails don't count
        # as trunk shapes.
        assert rs["executables"] == len(rs["batch_class_counts"]) <= 3

    def test_ragged_cache_short_circuits(self, trunk):
        params, cfg = trunk
        srv = Server(params, cfg, max_batch=2, max_wait_s=60.0,
                     cache_size=8, warm_kinds=(), serve_mode="ragged")
        f1 = srv.submit("embed", "MKTAYIAK")
        _drain_poll(srv, [f1])
        f2 = srv.submit("embed", "MKTAYIAK")  # hit: resolved future
        assert f2.done()
        np.testing.assert_array_equal(f1.result()["global"],
                                      f2.result()["global"])
        assert srv.cache_hit_returns == 1
        srv.drain(timeout=10)

    def test_ragged_drain_no_loss_under_threads(self, trunk, seqs):
        params, cfg = trunk
        srv = Server(params, cfg, max_batch=2, max_wait_s=0.002,
                     cache_size=0, warm_kinds=("embed",),
                     serve_mode="ragged").start()
        futs = []
        lock = threading.Lock()

        def client(w):
            for s in seqs[w::4]:
                f = srv.submit("embed", s)
                with lock:
                    futs.append(f)

        threads = [threading.Thread(target=client, args=(w,))
                   for w in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert srv.drain(timeout=30)
        assert len(futs) == len(seqs)
        for f in futs:
            assert f.result(timeout=5)["global"].shape == (
                MODEL.global_dim,)
        srv.close()


class TestRaggedTelemetry:
    def test_packed_events_validate_and_diagnose(self, trunk, seqs,
                                                 tmp_path):
        from proteinbert_tpu.obs import Telemetry, read_events
        from proteinbert_tpu.obs.diagnose import (
            render_serve, summarize_serve,
        )

        params, cfg = trunk
        path = tmp_path / "events.jsonl"
        tele = Telemetry(events_path=str(path))
        srv = Server(params, cfg, max_batch=2, max_wait_s=0.005,
                     cache_size=0, warm_kinds=("embed",),
                     serve_mode="ragged", telemetry=tele,
                     trace_sample_rate=1.0)
        srv.scheduler.time_batches = True
        srv.start()
        futs = [srv.submit("embed", s) for s in seqs]
        for f in futs:
            f.result(timeout=30)
        srv.drain(timeout=30)
        tele.close()

        recs = read_events(str(path), strict=True)  # schema-valid
        batches = [r for r in recs if r["event"] == "serve_batch"]
        assert batches
        for b in batches:
            assert b["mode"] == "ragged"
            assert b["bucket_len"] == SEQ_LEN
            assert 1 <= b["rows"] <= b["batch_class"] <= 2
            assert 1 <= b["segments"] <= 2 * 8
            assert 0.0 <= b["pad_fraction"] <= 1.0
        reqs = [r for r in recs if r["event"] == "serve_request"]
        assert reqs
        for r in reqs:
            assert r["mode"] == "ragged"
            assert r["segments"] >= 1
            # span rides the bucket_len field: a real bucket, not L
            assert r["bucket_len"] in BUCKETS
        start = next(r for r in recs if r["event"] == "serve_start")
        assert start["config"]["serve_mode"] == "ragged"

        summary = summarize_serve(recs)
        assert summary["batches"]["modes"] == {"ragged": len(batches)}
        assert summary["batches"]["segments"] == len(seqs)
        assert summary["batches"]["mean_segments_per_row"] > 0
        # one warm kind x the row classes (1, 2) of max_batch=2
        assert summary["executables"]["count"] == 2
        assert summary["executables"]["serve_mode"] == "ragged"
        text = render_serve(summary)
        assert "packed:" in text and "executables: 2 warm" in text
        # pad_wasted attribution (the ragged lever) present
        assert any("pad_wasted" in k
                   for k in summary["stage_attribution"])

    def test_executable_gauges_track_warmup(self, trunk):
        from proteinbert_tpu.obs import Telemetry

        params, cfg = trunk
        tele = Telemetry()
        srv = Server(params, cfg, max_batch=2, max_wait_s=60.0,
                     cache_size=0, warm_kinds=("embed", "predict_go"),
                     serve_mode="ragged", telemetry=tele)
        srv.start()
        m = tele.metrics
        # O(kinds x row classes): 2 kinds x the classes (1, 2)
        assert m.gauge("serve_executable_count").value == 4
        assert m.gauge("serve_warmup_seconds_total").value > 0
        assert srv.stats()["executables"] == 4
        srv.drain(timeout=10)


def _tiny_track_params(C=4):
    import jax.numpy as jnp

    return {
        "narrow_conv": {"kernel": jnp.zeros((3, C, C)),
                        "bias": jnp.zeros(C)},
        "wide_conv": {"kernel": jnp.zeros((3, C, C)),
                      "bias": jnp.zeros(C)},
        "local_ln1": {"scale": jnp.ones(C), "bias": jnp.zeros(C)},
        "local_dense": {"kernel": jnp.eye(C), "bias": jnp.zeros(C)},
        "local_ln2": {"scale": jnp.ones(C), "bias": jnp.zeros(C)},
    }


class TestFusedPathCounter:
    """ISSUE 10 satellite: the two-sided fused_kernel_path counter and
    the per-(reason, shape) one-time warning (the per-process latch
    misled a server that built a reference executable for a NEW shape
    after a fused one)."""

    def test_two_sided_counter_and_shape_keyed_warning(self):
        import jax.numpy as jnp

        from proteinbert_tpu.kernels import fused_block as fb

        params = _tiny_track_params()
        seen_path, records = [], []

        def path_cb(p, r):
            seen_path.append((p, r))

        # Handler attached straight to the kernel logger: caplog relies
        # on propagation to root, which an earlier start_log() test may
        # have reconfigured.
        handler = logging.Handler()
        handler.emit = records.append
        fb.logger.addHandler(handler)
        fb.register_path_observer(path_cb)
        key = ("reference", "segments")
        before = fb.PATH_TOTAL.get(key, 0)
        # The deprecated one-release fused_kernel_fallback_total mirror
        # is GONE (removed in ISSUE 12, as PR 9 scheduled).
        assert not hasattr(fb, "FALLBACK_TOTAL")
        assert not hasattr(fb, "register_fallback_observer")
        # Reset the warn latch for exactly the shapes this test uses so
        # the count below is deterministic whatever ran earlier.
        shapes = [(1, 24, 4, 2, "float32"), (1, 40, 4, 2, "float32")]
        for sh in shapes:
            fb._FALLBACK_WARNED.discard(("segments", sh))
        try:
            x24 = jnp.zeros((1, 24, 4))
            x40 = jnp.zeros((1, 40, 4))
            bc = jnp.zeros((1, 2, 4))  # per-SEGMENT (B, S, C)
            seg24 = jnp.ones((1, 24), jnp.int32)
            seg40 = jnp.ones((1, 40), jnp.int32)
            # C=4 is not lane-aligned → reference, reason=segments.
            fb.fused_local_track_segments(params, x24, bc, seg24)
            fb.fused_local_track_segments(params, x24, bc, seg24)
            fb.fused_local_track_segments(params, x40, bc, seg40)
        finally:
            fb.logger.removeHandler(handler)
            fb.unregister_path_observer(path_cb)
        assert fb.PATH_TOTAL[key] == before + 3
        assert seen_path == [key] * 3
        warnings = [r for r in records
                    if "XLA reference" in r.getMessage()]
        # Same shape twice → ONE warning; the new shape → its own.
        assert len(warnings) == 2

    def test_server_mirrors_path_into_registry(self, trunk):
        from proteinbert_tpu.kernels import attention as ka
        from proteinbert_tpu.kernels import fused_block as fb
        from proteinbert_tpu.obs import Telemetry

        params, cfg = trunk
        tele = Telemetry()
        srv = Server(params, cfg, max_batch=2, max_wait_s=60.0,
                     cache_size=0, warm_kinds=(), serve_mode="ragged",
                     telemetry=tele)
        fb.note_kernel_path("reference", "segments", ("test-shape",))
        fb.note_kernel_path("pallas", "packed", ("test-shape",))
        # The attention counter mirrors alongside (ISSUE 13 satellite).
        ka.note_attention_path("pallas", "packed", ("test-shape",))
        ka.note_attention_path("reference", "segments", ("test-shape",))
        c_ref = tele.metrics.counter("fused_kernel_path_total",
                                     path="reference", reason="segments")
        c_pal = tele.metrics.counter("fused_kernel_path_total",
                                     path="pallas", reason="packed")
        a_ref = tele.metrics.counter("attention_kernel_path_total",
                                     path="reference", reason="segments")
        a_pal = tele.metrics.counter("attention_kernel_path_total",
                                     path="pallas", reason="packed")
        assert c_ref.value == 1 and c_pal.value == 1
        assert a_ref.value == 1 and a_pal.value == 1
        stats = srv.stats()
        assert stats["fused_path"]["reference/segments"] >= 1
        assert stats["fused_path"]["pallas/packed"] >= 1
        assert stats["attention_path"]["pallas/packed"] >= 1
        assert stats["attention_path"]["reference/segments"] >= 1
        # The deprecated one-sided stats mirror is gone (ISSUE 12).
        assert "fused_fallback" not in stats
        srv.drain(timeout=10)
        fb.note_kernel_path("pallas", "packed")  # observer released
        ka.note_attention_path("pallas", "packed")
        assert c_pal.value == 1
        assert a_pal.value == 1

    def test_ragged_packed_takes_pallas_path(self):
        """THE ragged-serve fast-path smoke (ISSUE 10/13/16
        acceptance): on a shape the kernels support, the packed
        executable the ragged dispatcher builds must land on the
        Pallas ONE-PASS path — the whole trunk block in one kernel —
        with zero fallbacks on any of the three counter families."""
        from proteinbert_tpu.kernels import attention as ka
        from proteinbert_tpu.kernels import fused_block as fb
        from proteinbert_tpu.kernels import one_pass as op

        pcfg = PretrainConfig(
            model=ModelConfig(local_dim=128, global_dim=32, key_dim=8,
                              num_heads=2, num_blocks=1,
                              num_annotations=32, dtype="float32",
                              use_pallas=True),
            data=DataConfig(seq_len=SEQ_LEN, batch_size=2,
                            buckets=BUCKETS),
            optimizer=OptimizerConfig(warmup_steps=5),
            train=TrainConfig(seed=0, max_steps=1),
            checkpoint=CheckpointConfig(),
        )
        assert op.pallas_onepass_supported(128, 32, SEQ_LEN, 4, 8, 2,
                                           "float32")
        params = create_train_state(jax.random.PRNGKey(0), pcfg).params
        disp = RaggedDispatcher(params, pcfg, rows_per_batch=2,
                                max_segments=4)
        before = dict(op.ONEPASS_PATH_TOTAL)
        fb_before = dict(fb.PATH_TOTAL)
        attn_before = dict(ka.ATTN_PATH_TOTAL)
        assert disp.warmup(("embed",)) == 2      # row classes (1, 2)
        delta = {k: op.ONEPASS_PATH_TOTAL.get(k, 0) - before.get(k, 0)
                 for k in op.ONEPASS_PATH_TOTAL}
        assert delta.get(("pallas", "packed"), 0) >= 1
        assert delta.get(("reference", "segments"), 0) == 0
        # The supported shape never degrades to the two-kernel
        # composition, so the per-kernel families stay silent too.
        fb_delta = {k: fb.PATH_TOTAL.get(k, 0) - fb_before.get(k, 0)
                    for k in fb.PATH_TOTAL}
        assert fb_delta.get(("reference", "segments"), 0) == 0
        attn_delta = {k: ka.ATTN_PATH_TOTAL.get(k, 0)
                      - attn_before.get(k, 0)
                      for k in ka.ATTN_PATH_TOTAL}
        assert attn_delta.get(("reference", "segments"), 0) == 0


class TestRaggedMesh:
    """PR 8 residual closed (ISSUE 11 satellite): ragged packed batches
    shard over the mesh batch dim via serve_batch_sharding — parity
    against the unsharded ragged dispatcher within the jitted ≤1e-5
    tolerance, and indivisible row counts still rejected clearly."""

    def test_ragged_mesh_parity_vs_unsharded(self, trunk, seqs):
        from proteinbert_tpu.parallel import mesh_for_devices

        mesh = mesh_for_devices(2)
        b, _ = _serve(trunk, "ragged", "embed", seqs)
        r, rs = _serve(trunk, "ragged", "embed", seqs, mesh=mesh)
        for x, y in zip(b, r):
            np.testing.assert_allclose(x["global"], y["global"],
                                       atol=1e-5, rtol=1e-5)
            np.testing.assert_allclose(x["local_mean"], y["local_mean"],
                                       atol=1e-5, rtol=1e-5)
        # sharding adds no executables: the classes (2, 4) that two
        # replicas split, as many of them as were run
        assert set(rs["batch_class_counts"]) <= {2, 4}
        assert rs["executables"] == len(rs["batch_class_counts"])

    def test_ragged_mesh_sharded_placement(self, trunk):
        from proteinbert_tpu.parallel import mesh_for_devices

        params, cfg = trunk
        mesh = mesh_for_devices(2)
        d = RaggedDispatcher(params, cfg, rows_per_batch=2, mesh=mesh)
        assert d._shardings is not None
        assert set(d._shardings) >= {"tokens", "segment_ids",
                                     "annotations"}
        tokens, seg, ann, _ = d._dummy_packed()
        tb, sb, ab = d._place_packed(tokens, seg, ann)
        for arr in (tb, sb, ab):
            assert len(arr.sharding.device_set) == 2

    def test_ragged_mesh_indivisible_rows_rejected(self, trunk):
        from proteinbert_tpu.parallel import mesh_for_devices

        params, cfg = trunk
        mesh = mesh_for_devices(2)
        with pytest.raises(ValueError, match="not divisible"):
            RaggedDispatcher(params, cfg, rows_per_batch=3, mesh=mesh)

    def test_mesh_serves_committed_params(self, trunk):
        """Regression: orbax-restored trunks arrive COMMITTED to one
        device, and a jitted call mixing them with batch-dim-sharded
        inputs is an 'incompatible devices' error — the dispatcher must
        replicate the trunk over the mesh (both modes; fresh
        uncommitted test params used to mask this)."""
        from proteinbert_tpu.parallel import mesh_for_devices
        from proteinbert_tpu.serve import BucketDispatcher

        params, cfg = trunk
        committed = jax.device_put(params, jax.devices()[0])
        mesh = mesh_for_devices(2)
        d = RaggedDispatcher(committed, cfg, rows_per_batch=2, mesh=mesh)
        tokens, seg, ann, riders = d._dummy_packed()
        out = d.run_packed("embed", tokens, seg, ann, riders)
        assert out[0]["global"].shape == (cfg.model.global_dim,)
        b = BucketDispatcher(committed, cfg, max_batch=2, mesh=mesh)
        res = b.run("embed", np.zeros((2, BUCKETS[0]), np.int32))
        assert res["global"].shape == (2, cfg.model.global_dim)
        # Registry-loaded HEADS arrive committed too — add_head must
        # replicate them the same way (predict_task tails mix head
        # params with mesh-sharded trunk outputs).
        task = TaskConfig(kind="sequence_classification", num_outputs=3)
        hp = jax.device_put(
            ft_model.head_init(jax.random.PRNGKey(5), MODEL, task),
            jax.devices()[0])
        b.add_head(LoadedHead("hx", "hx", task, hp, {}))
        rows = np.zeros((2, BUCKETS[0]), np.int32)
        outs = b.run("predict_task", rows,
                     heads=[b.get_head("hx")] * 2)
        assert outs[0].shape == (3,)


class TestRaggedDispatcherContracts:

    def test_bucketed_api_refuses_packed_dispatcher(self, trunk):
        params, cfg = trunk
        d = RaggedDispatcher(params, cfg, rows_per_batch=2)
        with pytest.raises(NotImplementedError, match="run_packed"):
            d.run("embed", np.zeros((2, SEQ_LEN), np.int32))

    def test_server_mode_validation(self, trunk):
        params, cfg = trunk
        with pytest.raises(ValueError, match="serve_mode"):
            Server(params, cfg, serve_mode="packed")
        with pytest.raises(ValueError, match="partition_heads"):
            Server(params, cfg, serve_mode="ragged",
                   partition_heads=True)
        # the ragged row classes are derived from max_batch, not passed
        with pytest.raises(ValueError, match="batch_classes"):
            Server(params, cfg, serve_mode="ragged",
                   batch_classes=(2, 4))
        srv = Server(params, cfg, serve_mode="ragged", max_batch=4,
                     warm_kinds=())
        assert srv.dispatcher.batch_classes == (1, 2, 4)
        assert srv.scheduler.row_classes == (1, 2, 4)
        srv.drain(timeout=10)


@pytest.fixture
def session(tmp_path):
    """A live CPU profiler session: what switches the recording of
    `jax.compile` spans on (tests/test_tracing.py)."""
    tracing.recorder().clear()
    jax.profiler.start_trace(str(tmp_path / "profile"))
    yield tracing.recorder()
    jax.profiler.stop_trace()
    tracing.recorder().clear()


def _compiles(recorder) -> int:
    return sum(s["name"] == "jax.compile" for s in recorder.spans())


@pytest.fixture(scope="module")
def ladder(trunk):
    params, cfg = trunk
    return RaggedDispatcher(params, cfg, rows_per_batch=8, max_segments=4)


def _alone(disp, cls, seq):
    """`seq` alone in row 0 of an otherwise empty batch of class `cls`."""
    span = disp.bucket_len(len(seq))
    tokens = inference._tokenize_masked([seq], SEQ_LEN,
                                        on_overflow="count")[0]
    tok = np.zeros((cls, SEQ_LEN), np.int32)
    seg = np.zeros((cls, SEQ_LEN), np.int32)
    ann = np.zeros((cls, 4, MODEL.num_annotations), np.float32)
    tok[0, :span] = tokens[:span]
    seg[0, :span] = 1
    return tok, seg, ann, [(0, 0, 0, span)]


class TestRowClassesOnTheDispatcher:
    """ISSUE 25 on the real dispatcher at tiny widths."""

    def test_the_ladder_of_eight_rows(self, ladder):
        assert ladder.batch_classes == (1, 2, 4, 8)

    @pytest.mark.parametrize("kind", ["embed", "predict_go",
                                      "predict_residues"])
    @pytest.mark.parametrize("cls", [1, 2, 4])
    def test_one_request_alone_reads_the_same_in_every_class(
            self, ladder, seqs, kind, cls):
        seq = max(seqs, key=len)
        (full,) = ladder.run_packed(kind, *_alone(ladder, 8, seq))
        (got,) = ladder.run_packed(kind, *_alone(ladder, cls, seq))
        for x, y in zip(jax.tree.leaves(full), jax.tree.leaves(got)):
            assert x.shape == y.shape
            np.testing.assert_allclose(x, y, atol=1e-5, rtol=1e-5)

    def test_a_shape_outside_the_ladder_is_refused(self, ladder, seqs):
        with pytest.raises(ValueError, match="none of the compiled"):
            ladder.run_packed("embed", *_alone(ladder, 3, seqs[0]))

    def test_warmup_warms_every_class_and_nothing_compiles_after(
            self, trunk, seqs, session):
        params, cfg = trunk
        # a sequence length of its own: nothing here is in jit's cache
        cfg = dataclasses.replace(cfg, data=dataclasses.replace(
            cfg.data, seq_len=40, buckets=(16, 40)))
        disp = RaggedDispatcher(params, cfg, rows_per_batch=4,
                                max_segments=4)
        with tracing.span("arms.the.listener"):
            start = _compiles(session)
        assert disp.warmup(("embed",)) == 3       # classes (1, 2, 4)
        warm = _compiles(session)
        assert warm >= start + 3
        assert disp.executable_count == 3
        assert {k[2] for k in disp._warm} == {1, 2, 4}
        assert disp.warmup(("embed",)) == 0       # all warm already
        for cls in disp.batch_classes:
            tokens, seg, ann, riders = disp._dummy_packed(cls)
            outs = disp.run_packed("embed", tokens, seg, ann, riders)
            assert len(outs) == cls
        assert _compiles(session) == warm

    def test_the_packed_trunk_and_tails_warm_at_every_class(self, trunk):
        params, cfg = trunk
        disp = RaggedDispatcher(params, cfg, rows_per_batch=4,
                                max_segments=4)
        task = TaskConfig(kind="sequence_classification", num_outputs=3)
        head = LoadedHead("hw", "hw", task, ft_model.head_init(
            jax.random.PRNGKey(3), MODEL, task), {})
        disp.add_head(head)
        assert disp.warmup(("predict_task",)) == 3
        assert disp.trunk_executable_count == 3
        assert disp.warmup_report["trunk_executables"] == 3
        assert disp.warmup_report["heads"]["hw"] > 0
        # a hot-added head's tail compiles against every warm class,
        # and never the trunk
        other = LoadedHead("hx", "hx", task, ft_model.head_init(
            jax.random.PRNGKey(4), MODEL, task), {})
        assert disp.add_head(other, warm=True) > 0
        assert disp.trunk_executable_count == 3

    def test_the_candidate_arm_is_warmed_at_every_warm_class(
            self, trunk, monkeypatch):
        params, cfg = trunk
        disp = RaggedDispatcher(params, cfg, rows_per_batch=4,
                                max_segments=4)
        disp.warmup(("embed", "predict_go"))
        disp.load_candidate(jax.tree.map(lambda x: x * 1.01, params))
        seen = []
        real = disp._packed_fn

        def spy(kind, quantized=None):
            fn = real(kind, quantized)

            def run(p, tb, sb, ab, m):
                seen.append((kind, tb.shape[0], ab.shape[0]))
                return fn(p, tb, sb, ab, m)

            return run

        monkeypatch.setattr(disp, "_packed_fn", spy)
        assert disp.warm_candidate() > 0
        assert sorted(seen) == sorted(
            (k, c, c) for k in ("embed", "predict_go") for c in (1, 2, 4))

    def test_classes_a_mesh_cannot_split_are_left_out(self, trunk):
        from proteinbert_tpu.parallel import mesh_for_devices

        params, cfg = trunk
        disp = RaggedDispatcher(params, cfg, rows_per_batch=4,
                                mesh=mesh_for_devices(2))
        assert disp.batch_classes == (2, 4)       # 1 dropped, no error
        disp = RaggedDispatcher(params, cfg, rows_per_batch=6,
                                mesh=mesh_for_devices(2))
        assert disp.batch_classes == (6,)         # 3 is odd
        # rows_per_batch itself has to split, as before
        with pytest.raises(ValueError, match="not divisible"):
            RaggedDispatcher(params, cfg, rows_per_batch=3,
                             mesh=mesh_for_devices(2))

    def test_a_shadow_request_rides_the_smallest_class(self, trunk, seqs):
        params, cfg = trunk
        srv = Server(params, cfg, max_batch=4, max_wait_s=60.0,
                     cache_size=0, warm_kinds=("embed",),
                     serve_mode="ragged")
        srv.dispatcher.load_candidate(params)
        srv.dispatcher.warm_candidate()
        shapes = []
        real = srv.dispatcher.run_packed_candidate

        def spy(kind, tokens, *a, **kw):
            shapes.append(tokens.shape)
            return real(kind, tokens, *a, **kw)

        srv.dispatcher.run_packed_candidate = spy
        got = srv.shadow_submit("embed", seqs[0])
        assert shapes == [(1, SEQ_LEN)]
        fut = srv.submit("embed", seqs[0])
        (live,) = _drain_poll(srv, [fut])
        np.testing.assert_allclose(got["global"], live["global"],
                                   atol=1e-5, rtol=1e-5)
        srv.drain(timeout=10)


def test_stats_read_the_share_of_small_batches_and_the_real_fill(
        trunk, seqs):
    """`Server.stats()`: `batch_class_counts` gives the share of batches
    run under the largest class, and real residues over
    `batched_positions` the fill the device really ran at."""
    params, cfg = trunk
    srv = Server(params, cfg, max_batch=4, max_wait_s=60.0, cache_size=0,
                 warm_kinds=("embed",), serve_mode="ragged")
    before = srv.stats()
    assert before["batch_class_counts"] == {}
    assert before["batched_positions"] == 0
    # one short request: one row, the smallest class
    _drain_poll(srv, [srv.submit("embed", seqs[0])])
    stats = srv.stats()
    assert stats["batch_class_counts"] == {1: 1}
    assert stats["batched_positions"] == SEQ_LEN
    below = sum(n for c, n in stats["batch_class_counts"].items()
                if c < srv.dispatcher.rows_per_batch)
    assert below / stats["batches"] == 1.0
    assert 0 < len(seqs[0]) / stats["batched_positions"] <= 1
    srv.drain(timeout=10)


class TestNeighborsRideOnePass:
    """ISSUE 17: the embed leg of a /v1/neighbors request is not a new
    code path — it is the SAME packed one-pass executable the ragged
    trunk serves embeds with. Proven the same way as the fast-path
    smoke above: by counter delta, on a Pallas-supported shape."""

    def test_neighbors_query_takes_pallas_onepass_path(self, tmp_path):
        from proteinbert_tpu.heads import trunk_fingerprint
        from proteinbert_tpu.index import build_index
        from proteinbert_tpu.index.scorer import NeighborIndex
        from proteinbert_tpu.kernels import one_pass as op
        from tests.test_index import make_store

        pcfg = PretrainConfig(
            model=ModelConfig(local_dim=128, global_dim=32, key_dim=8,
                              num_heads=2, num_blocks=1,
                              num_annotations=32, dtype="float32",
                              use_pallas=True),
            data=DataConfig(seq_len=SEQ_LEN, batch_size=2,
                            buckets=BUCKETS),
            optimizer=OptimizerConfig(warmup_steps=5),
            train=TrainConfig(seed=0, max_steps=1),
            checkpoint=CheckpointConfig(),
        )
        assert op.pallas_onepass_supported(128, 32, SEQ_LEN, 4, 8, 2,
                                           "float32")
        params = create_train_state(jax.random.PRNGKey(0), pcfg).params
        store = str(tmp_path / "store")
        make_store(store, n=32, dim=pcfg.model.global_dim,
                   fingerprint=trunk_fingerprint(params))
        index_dir = str(tmp_path / "index")
        build_index(store, index_dir, num_centroids=4, block_size=8,
                    kmeans_iters=4)
        index = NeighborIndex.load(index_dir)

        srv = Server(params, pcfg, max_batch=4, max_wait_s=60.0,
                     cache_size=0, warm_kinds=(), serve_mode="ragged",
                     index=index, nprobe=4)
        before = dict(op.ONEPASS_PATH_TOTAL)
        fut = srv.submit("neighbors", "MKTAYIAKQRQISFVK", top_k=3)
        got = _drain_poll(srv, [fut])[0]
        delta = {k: op.ONEPASS_PATH_TOTAL.get(k, 0) - before.get(k, 0)
                 for k in op.ONEPASS_PATH_TOTAL}
        assert delta.get(("pallas", "packed"), 0) >= 1
        assert delta.get(("reference", "segments"), 0) == 0
        assert len(got["neighbors"]) == 3
        # The lookup leg rides the trunk's packed executable — it must
        # not have compiled a second trunk program.
        assert srv.stats()["executables"] == 1
        srv.drain(timeout=10)
