"""Online serving subsystem tests (proteinbert_tpu/serve/, ISSUE 5).

Two tiers in one file:

- **pure-logic tests** (queue, cache, scheduler formation) run against
  stub dispatchers and a fake clock — no jax, microseconds each. The
  scheduler is exercised through `poll(now=)` single-threaded, so batch
  formation is a deterministic function of arrival order and the clock.
- **end-to-end tests** share one tiny untrained trunk (module fixture)
  and prove the serving results against the offline inference surface:
  served-vs-offline `embed` BIT-parity per bucket, drain with nothing
  lost, cache short-circuits, HTTP status mapping, and `serve_*`
  events that round-trip the schema validator.
"""

import json
import logging
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

import jax

from proteinbert_tpu import inference
from proteinbert_tpu.configs import (
    CheckpointConfig, DataConfig, ModelConfig, OptimizerConfig,
    PretrainConfig, TrainConfig,
)
from proteinbert_tpu.serve import (
    BucketDispatcher, DeadlineExceededError, EmbeddingCache,
    MicroBatchScheduler, QueueFullError, Request, RequestQueue,
    SequenceTooLongError, Server, ServerClosedError, content_key,
)
from proteinbert_tpu.serve.dispatch import (
    default_batch_classes, resolve_buckets,
)
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


def _req(kind="embed", seq="MKT", bucket_len=16, clock=None, deadline=None,
         tokens=None):
    if tokens is None:
        tokens = np.zeros(bucket_len, np.int32)
    return Request(kind=kind, seq=seq, tokens=tokens, bucket_len=bucket_len,
                   future=Future(), enqueued_at=clock() if clock else 0.0,
                   deadline=deadline)


# ---------------------------------------------------------------- queue

class TestRequestQueue:
    def test_push_pop_fifo(self):
        q = RequestQueue(max_depth=4)
        reqs = [_req(seq=s) for s in "abc"]
        for r in reqs:
            q.push(r)
        assert len(q) == 3
        assert q.pop_all() == reqs
        assert len(q) == 0

    def test_overflow_evicts_oldest_with_typed_error(self):
        q = RequestQueue(max_depth=2)
        a, b, c = (_req(seq=s) for s in "abc")
        assert q.push(a) == []
        assert q.push(b) == []
        evicted = q.push(c)
        assert evicted == [a]
        assert q.evicted_total == 1
        with pytest.raises(QueueFullError):
            a.future.result(timeout=0)
        # The newer requests survive, in order.
        assert q.pop_all() == [b, c]

    def test_closed_queue_rejects_push_keeps_drain(self):
        q = RequestQueue(max_depth=4)
        r = _req()
        q.push(r)
        q.close()
        with pytest.raises(ServerClosedError):
            q.push(_req())
        assert q.pop_all() == [r]  # queued work survives the close

    def test_fail_all_empties_onto_exception(self):
        q = RequestQueue(max_depth=4)
        reqs = [_req(seq=s) for s in "ab"]
        for r in reqs:
            q.push(r)
        exc = ServerClosedError("aborted")
        assert q.fail_all(exc) == reqs
        for r in reqs:
            with pytest.raises(ServerClosedError):
                r.future.result(timeout=0)
        assert len(q) == 0


# ---------------------------------------------------------------- cache

class TestEmbeddingCache:
    def test_hit_miss_eviction_counters(self):
        c = EmbeddingCache(capacity=2)
        k1, k2, k3 = (content_key("embed", s) for s in ("a", "b", "c"))
        assert c.get(k1) is None and c.misses == 1
        c.put(k1, 1)
        c.put(k2, 2)
        assert c.get(k1) == 1 and c.hits == 1
        c.put(k3, 3)  # k2 is now LRU → evicted
        assert c.evictions == 1
        assert c.get(k2) is None
        assert c.get(k1) == 1 and c.get(k3) == 3
        assert c.stats()["size"] == 2
        assert 0.0 < c.hit_rate < 1.0

    def test_content_key_addresses_content(self):
        base = content_key("embed", "MKT")
        assert content_key("embed", "MKT") == base
        assert content_key("predict_go", "MKT") != base
        assert content_key("embed", "MKV") != base
        ann = np.zeros(4, np.float32)
        with_ann = content_key("embed", "MKT", ann)
        assert with_ann != base  # None != explicit all-zero vector
        ann2 = ann.copy()
        ann2[1] = 1.0
        assert content_key("embed", "MKT", ann2) != with_ann

    def test_capacity_zero_disables(self):
        c = EmbeddingCache(capacity=0)
        c.put("k", 1)
        assert c.get("k") is None
        assert len(c) == 0


# ------------------------------------------------- scheduler (fake clock)

class FakeDispatcher:
    """Stub with the dispatcher surface the scheduler touches; records
    every dispatched batch and echoes row indices as results."""

    def __init__(self, fail_kinds=()):
        self.cfg = type("C", (), {})()
        self.cfg.model = type("M", (), {"num_annotations": 4})()
        self.batches = []
        self.fail_kinds = set(fail_kinds)

    def batch_class(self, rows):
        c = 1
        while c < rows:
            c *= 2
        return c

    def run(self, kind, tokens, annotations=None):
        if kind in self.fail_kinds:
            raise RuntimeError(f"injected dispatch failure for {kind}")
        self.batches.append((kind, tokens.shape))
        return np.arange(tokens.shape[0], dtype=np.float32)


def _sched(queue, dispatcher, clock, **kw):
    done = []
    s = MicroBatchScheduler(
        queue, dispatcher, lambda req, row: done.append((req, row))
        or req.future.set_result(row),
        clock=clock, **kw)
    return s, done


class TestSchedulerFormation:
    def test_full_group_dispatches_immediately(self):
        clock = FakeClock()
        q = RequestQueue()
        d = FakeDispatcher()
        s, done = _sched(q, d, clock, max_batch=3, max_wait_s=10.0)
        for i in range(3):
            q.push(_req(seq=f"s{i}", clock=clock))
        assert s.poll() == 3  # full batch: no wait needed
        assert [r.seq for r, _ in done] == ["s0", "s1", "s2"]  # FIFO
        assert d.batches == [("embed", (3, 16))]
        assert s.poll() == 0

    def test_underfull_group_waits_for_max_wait(self):
        clock = FakeClock()
        q = RequestQueue()
        s, done = _sched(q, FakeDispatcher(), clock,
                         max_batch=8, max_wait_s=0.5)
        q.push(_req(seq="a", clock=clock))
        assert s.poll() == 0          # not full, not old enough
        clock.advance(0.49)
        assert s.poll() == 0
        clock.advance(0.02)           # head is now past max_wait
        assert s.poll() == 1
        assert done[0][0].seq == "a"

    def test_groups_split_by_kind_and_bucket(self):
        clock = FakeClock()
        q = RequestQueue()
        d = FakeDispatcher()
        s, _ = _sched(q, d, clock, max_batch=2, max_wait_s=10.0)
        q.push(_req(kind="embed", bucket_len=16, clock=clock))
        q.push(_req(kind="embed", bucket_len=32, clock=clock,
                    tokens=np.zeros(32, np.int32)))
        q.push(_req(kind="predict_go", bucket_len=16, clock=clock))
        assert s.poll() == 0  # three singleton groups, none full/overdue
        q.push(_req(kind="embed", bucket_len=16, clock=clock))
        assert s.poll() == 2  # (embed, 16) reached max_batch
        assert d.batches == [("embed", (2, 16))]

    def test_fullest_group_wins_tie_to_oldest(self):
        clock = FakeClock()
        q = RequestQueue()
        d = FakeDispatcher()
        s, _ = _sched(q, d, clock, max_batch=2, max_wait_s=10.0)
        q.push(_req(kind="predict_go", bucket_len=16, clock=clock))
        q.push(_req(kind="embed", bucket_len=16, clock=clock))
        q.push(_req(kind="embed", bucket_len=16, clock=clock))
        assert s.poll() == 2           # embed group is full; go is not
        assert d.batches[0][0] == "embed"
        clock.advance(11.0)
        assert s.poll() == 1           # go group dispatches on max_wait
        assert d.batches[1][0] == "predict_go"

    def test_oversize_group_dispatches_in_max_batch_chunks(self):
        clock = FakeClock()
        q = RequestQueue(max_depth=16)
        d = FakeDispatcher()
        s, done = _sched(q, d, clock, max_batch=4, max_wait_s=10.0)
        for i in range(6):
            q.push(_req(seq=f"s{i}", clock=clock))
        assert s.poll() == 4
        clock.advance(11.0)            # remainder rides the wait trigger
        assert s.poll() == 2
        assert [b[1][0] for b in d.batches] == [4, 2]
        assert [r.seq for r, _ in done] == [f"s{i}" for i in range(6)]

    def test_pending_deadline_expiry(self):
        clock = FakeClock()
        q = RequestQueue()
        s, done = _sched(q, FakeDispatcher(), clock,
                         max_batch=4, max_wait_s=0.1)
        late = _req(seq="late", clock=clock, deadline=clock.t + 0.05)
        fine = _req(seq="fine", clock=clock)
        q.push(late)
        q.push(fine)
        assert s.poll() == 0           # ingested, neither trigger fired
        clock.advance(0.2)             # late expired AND group overdue
        assert s.poll() == 1
        with pytest.raises(DeadlineExceededError):
            late.future.result(timeout=0)
        assert s.expired_total == 1
        assert [r.seq for r, _ in done] == ["fine"]

    def test_dispatch_failure_fails_batch_keeps_scheduler(self):
        clock = FakeClock()
        q = RequestQueue()
        d = FakeDispatcher(fail_kinds={"embed"})
        s, done = _sched(q, d, clock, max_batch=2, max_wait_s=10.0)
        bad = [_req(kind="embed", clock=clock) for _ in range(2)]
        for r in bad:
            q.push(r)
        assert s.poll() == 2
        for r in bad:
            with pytest.raises(RuntimeError, match="injected"):
                r.future.result(timeout=0)
        ok = [_req(kind="predict_go", clock=clock) for _ in range(2)]
        for r in ok:
            q.push(r)
        assert s.poll() == 2           # still serving after the failure
        assert len(done) == 2

    def test_drain_flushes_underfull_groups(self):
        clock = FakeClock()
        q = RequestQueue()
        s, done = _sched(q, FakeDispatcher(), clock,
                         max_batch=8, max_wait_s=60.0)
        q.push(_req(seq="a", clock=clock))
        q.push(_req(seq="b", clock=clock))
        assert s.poll() == 0           # neither trigger fired
        q.close()                      # drain: closed queue flushes
        assert s.poll() == 2
        assert len(done) == 2


# ------------------------------- pipelined dispatch window (ISSUE 19)

class AsyncFakeDispatcher(FakeDispatcher):
    """FakeDispatcher wearing the `run_timed_async` in-flight surface:
    submit records the batch and returns a handle; the row-index echo
    materializes only at finalize() — device completion decoupled from
    the host fetch, like the real BucketDispatcher. An optional
    `finalize_gate` Event holds every finalize until set, so threaded
    tests can pin work in flight deterministically."""

    def __init__(self, fail_kinds=(), finalize_gate=None):
        super().__init__(fail_kinds)
        self.finalized = []
        self.finalize_gate = finalize_gate

    def run_timed_async(self, kind, tokens, annotations=None,
                        timed=False, **extra):
        if kind in self.fail_kinds:
            raise RuntimeError(f"injected dispatch failure for {kind}")
        self.batches.append((kind, tokens.shape))
        disp = self

        class _Handle:
            def finalize(self):
                if disp.finalize_gate is not None:
                    disp.finalize_gate.wait(10)
                disp.finalized.append((kind, tokens.shape))
                return (np.arange(tokens.shape[0], dtype=np.float32), {})

        return _Handle()


class TestPipelinedWindow:
    def test_fake_clock_formation_deterministic_with_async_dispatch(self):
        """Single-threaded poll() has no completer, so the async entry
        sync-drains: formation, seal order, and results are
        byte-for-byte what the blocking stub produced — the fake-clock
        determinism contract survives the pipeline."""
        results = []
        for d in (FakeDispatcher(), AsyncFakeDispatcher()):
            clock = FakeClock()
            q = RequestQueue(max_depth=16)
            s, done = _sched(q, d, clock, max_batch=4, max_wait_s=0.5)
            for i in range(6):
                q.push(_req(seq=f"s{i}", clock=clock))
            assert s.poll() == 4       # full group, sealed before return
            assert len(done) == 4
            clock.advance(0.6)
            assert s.poll() == 2       # remainder on the wait trigger
            assert s.poll() == 0
            results.append((
                [r.seq for r, _ in done],
                [b[1] for b in d.batches],
                [float(r.future.result(timeout=0)) for r, _ in done]))
        assert results[0] == results[1]

    def test_sync_drain_never_accumulates_inflight(self):
        q = RequestQueue()
        s, _ = _sched(q, AsyncFakeDispatcher(), FakeClock(),
                      max_batch=2, max_wait_s=10.0)
        for i in range(4):
            q.push(_req(seq=f"s{i}"))
        assert s.poll() == 2 and s.poll() == 2
        stats = s.pipeline_stats()
        assert stats["inflight_max"] == 1   # submit → inline finalize
        assert stats["finalize_seconds_total"] > 0.0

    def test_submit_failure_rides_window_fails_batch_keeps_scheduler(self):
        clock = FakeClock()
        q = RequestQueue()
        d = AsyncFakeDispatcher(fail_kinds={"embed"})
        s, done = _sched(q, d, clock, max_batch=2, max_wait_s=10.0)
        bad = [_req(kind="embed", clock=clock) for _ in range(2)]
        for r in bad:
            q.push(r)
        assert s.poll() == 2
        for r in bad:
            with pytest.raises(RuntimeError, match="injected"):
                r.future.result(timeout=0)
        ok = [_req(kind="predict_go", clock=clock) for _ in range(2)]
        for r in ok:
            q.push(r)
        assert s.poll() == 2           # still serving after the failure
        assert len(done) == 2

    def _run_threaded(self, n_requests, finish):
        """Start a real scheduler+completer, pin the FIRST finalize
        behind a gate until `n_requests/4` batches are submitted (work
        genuinely in flight), then run `finish(s, q, reqs)` and join.
        Returns (scheduler, dispatcher, reqs, done)."""
        gate = threading.Event()
        d = AsyncFakeDispatcher(finalize_gate=gate)
        q = RequestQueue(max_depth=2 * n_requests)
        done = []
        s = MicroBatchScheduler(
            q, d, lambda req, row: done.append(req)
            or req.future.set_result(row),
            max_batch=4, max_wait_s=0.005, pipeline_depth=2)
        reqs = [_req(seq=f"s{i}") for i in range(n_requests)]
        for r in reqs:
            q.push(r)
        s.start()
        # Completer blocks on the gate; the scheduler keeps submitting
        # until the depth-2 window is full — batches pile up in flight.
        deadline = time.monotonic() + 5.0
        while len(d.batches) < 3 and time.monotonic() < deadline:
            time.sleep(0.002)
        assert len(d.batches) >= 3, "scheduler never filled the window"
        finish(s, q, reqs)
        gate.set()
        assert s.join(10), "scheduler thread failed to drain"
        return s, d, reqs, done

    def test_drain_with_batches_in_flight_seals_exactly_once(self):
        s, d, reqs, done = self._run_threaded(
            12, lambda s, q, reqs: q.close())
        # Every future sealed exactly once, results correct, nothing
        # finalized twice.
        assert len(done) == len(reqs)
        assert len({id(r) for r in done}) == len(reqs)
        for r in reqs:
            assert r.future.done() and r.future.exception() is None
        assert len(d.finalized) == len(d.batches) == 3
        assert s.stats_counts()[:2] == (3, 12)
        # The window genuinely overlapped: gate held finalize #1 while
        # later batches were submitted into the depth-2 window.
        assert s.pipeline_stats()["inflight_max"] == 2

    def test_abort_with_batch_in_flight_seals_exactly_once(self):
        boom = ServerClosedError("aborted")

        def finish(s, q, reqs):
            s.stop()  # abort: loop exits, epilogue resolves the window

        s, d, reqs, done = self._run_threaded(16, finish)
        failed = s.fail_pending(boom)  # what Server.abort does next
        # Disjoint exactly-once partition: every submitted batch's rows
        # sealed ok by the drain epilogue, every undispatched row
        # failed with the abort error — no request in both, none lost.
        sealed = {id(r) for r in done}
        aborted = {id(r) for r in failed}
        assert not (sealed & aborted)
        assert len(sealed) + len(aborted) == len(reqs)
        assert len(done) == len(d.finalized) * 4
        for r in reqs:
            assert r.future.done()
            exc = r.future.exception()
            assert exc is None or exc is boom


# --------------------------------------------------- dispatcher routing

class TestDispatchRouting:
    def test_resolve_buckets_validation(self, trunk):
        _, cfg = trunk
        assert resolve_buckets(cfg) == (SEQ_LEN,)
        assert resolve_buckets(cfg, BUCKETS) == BUCKETS
        with pytest.raises(ValueError, match="ascending"):
            resolve_buckets(cfg, (32, 16, 48))
        with pytest.raises(ValueError, match="seq_len"):
            resolve_buckets(cfg, (16, 32))
        with pytest.raises(ValueError, match="ints"):
            resolve_buckets(cfg, ("a", 48))

    def test_default_batch_classes(self):
        assert default_batch_classes(8) == (1, 2, 4, 8)
        assert default_batch_classes(12) == (1, 2, 4, 8, 12)
        assert default_batch_classes(1) == (1,)

    def test_default_batch_classes_mesh_multiple(self):
        # Mesh-aware ladder: every rung divisible by the replica count
        # (data*fsdp extent), so `pbt serve --mesh` starts out of the box.
        assert default_batch_classes(16, multiple=4) == (4, 8, 16)
        assert default_batch_classes(8, multiple=8) == (8,)
        assert default_batch_classes(12, multiple=2) == (2, 4, 8, 12)
        with pytest.raises(ValueError, match="not divisible"):
            default_batch_classes(8, multiple=3)

    def test_bucket_and_class_routing(self, trunk):
        params, cfg = trunk
        d = BucketDispatcher(params, cfg, buckets=BUCKETS, max_batch=8)
        assert d.bucket_len(10) == 16   # 12 tokens with sos/eos
        assert d.bucket_len(14) == 16
        assert d.bucket_len(15) == 32
        assert d.bucket_len(46) == SEQ_LEN
        assert d.bucket_len(1000) == SEQ_LEN  # over-window caps
        assert d.batch_class(1) == 1
        assert d.batch_class(3) == 4
        with pytest.raises(ValueError, match="exceed"):
            d.batch_class(9)


# ------------------------------------------------------- e2e: parity

@pytest.fixture(scope="module")
def server(trunk):
    params, cfg = trunk
    srv = Server(params, cfg, buckets=BUCKETS, max_batch=4,
                 max_wait_s=0.002, queue_depth=64, cache_size=32,
                 warm_kinds=())
    srv.start()
    yield srv
    srv.close(drain=True, timeout=30)


# Lengths chosen to hit all three buckets.
RAGGED = ["MKTAYIAKQR", "ACDEFGHIKLMNPQRSTVWY", "GG",
          "ACDEFGHIKLMNPQRSTVWY" * 2, "MKTAYIAKQRMKTAYIAKQRAC"]


class TestServedParity:
    def test_served_embed_bit_parity_per_bucket(self, trunk):
        """A full micro-batch of same-bucket requests, formed
        deterministically through submit()+poll(), must be BIT-identical
        to the offline bucketed path: both run the same jitted kernel at
        the same (bucket_len, batch_class) shape."""
        params, cfg = trunk
        for bucket, seqs in ((16, ["MKTAYIAKQR", "GG", "ACDEF", "MKT"]),
                             (32, ["ACDEFGHIKLMNPQRSTVWY"] * 4)):
            srv = Server(params, cfg, buckets=BUCKETS, max_batch=4,
                         max_wait_s=60.0, cache_size=0, warm_kinds=())
            # No scheduler thread: form the batch by hand for determinism.
            futures = [srv.submit("embed", s) for s in seqs]
            assert srv.scheduler.poll() == 4
            served = [f.result(timeout=0) for f in futures]
            offline = inference.embed(params, cfg, seqs, bucketed=True,
                                      buckets=BUCKETS, batch_size=4)
            for i, row in enumerate(served):
                assert srv.dispatcher.bucket_len(len(seqs[i])) == bucket
                np.testing.assert_array_equal(row["global"],
                                              offline["global"][i])
                np.testing.assert_array_equal(row["local_mean"],
                                              offline["local_mean"][i])

    @pytest.mark.parametrize("depth", [1, 2])
    def test_started_server_bit_parity_at_pipeline_depth(self, trunk,
                                                         depth):
        """The in-flight window changes when a batch is fetched, not
        what it computes: a STARTED server (depth 2 hands batches to
        the live completer thread, depth 1 fetches in line) answers a
        full same-bucket batch bit-identically to the offline bucketed
        path, and so the two depths bit-identically to each other."""
        params, cfg = trunk
        seqs = ["MKTAYIAKQR", "GG", "ACDEF", "MKT"]
        srv = Server(params, cfg, buckets=BUCKETS, max_batch=4,
                     max_wait_s=60.0, cache_size=0, warm_kinds=(),
                     pipeline_depth=depth).start()
        try:
            served = [f.result(timeout=120)
                      for f in [srv.submit("embed", s) for s in seqs]]
            assert srv.scheduler.pipeline_stats()["depth"] == depth
        finally:
            srv.close(drain=True, timeout=30)
        offline = inference.embed(params, cfg, seqs, bucketed=True,
                                  buckets=BUCKETS, batch_size=4)
        for i, row in enumerate(served):
            for key in ("global", "local_mean"):
                np.testing.assert_array_equal(row[key], offline[key][i])

    def test_sync_facade_ragged_traffic(self, server, trunk):
        params, cfg = trunk
        offline = inference.embed(params, cfg, RAGGED, bucketed=True,
                                  buckets=BUCKETS, batch_size=4)
        for i, seq in enumerate(RAGGED):
            got = server.embed(seq, timeout=30)
            np.testing.assert_allclose(got["global"], offline["global"][i],
                                       rtol=2e-5, atol=2e-5)

    def test_predict_go_and_top_k(self, server, trunk):
        params, cfg = trunk
        probs = server.predict_go(RAGGED[0], timeout=30)
        assert probs.shape == (cfg.model.num_annotations,)
        assert ((probs >= 0) & (probs <= 1)).all()
        top = server.predict_go(RAGGED[0], top_k=3, timeout=30)
        assert len(top) == 3
        assert top[0][1] >= top[1][1] >= top[2][1]
        assert top[0][1] == pytest.approx(float(probs.max()), rel=1e-6)

    def test_predict_residues_fills_masks(self, server):
        filled, probs = server.predict_residues("MK?AYIA?QR", timeout=30)
        assert len(filled) == 10
        assert "?" not in filled
        assert filled[0] == "M" and filled[3] == "A"  # unmasked untouched
        assert probs.shape[0] >= 12  # bucket length ≥ tokenized length

    def test_concurrent_clients(self, server, trunk):
        params, cfg = trunk
        offline = inference.embed(params, cfg, RAGGED, bucketed=True,
                                  buckets=BUCKETS, batch_size=4)
        results = {}

        def client(i):
            results[i] = server.embed(RAGGED[i % len(RAGGED)], timeout=30)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert len(results) == 12
        for i, got in results.items():
            np.testing.assert_allclose(
                got["global"], offline["global"][i % len(RAGGED)],
                rtol=2e-5, atol=2e-5)


# ------------------------------------------- e2e: cache + backpressure

class TestServerContracts:
    def test_cache_short_circuits_repeats(self, trunk):
        params, cfg = trunk
        srv = Server(params, cfg, buckets=BUCKETS, max_batch=2,
                     max_wait_s=0.002, cache_size=8, warm_kinds=())
        with srv:
            first = srv.embed("MKTAYIAKQR", timeout=30)
            assert srv.cache.misses >= 1
            hits_before = srv.cache.hits
            again = srv.embed("MKTAYIAKQR", timeout=30)
            assert srv.cache.hits == hits_before + 1
            assert srv.cache_hit_returns == 1
            np.testing.assert_array_equal(first["global"], again["global"])

    def test_queue_overflow_rejected_not_dropped(self, trunk):
        params, cfg = trunk
        srv = Server(params, cfg, buckets=BUCKETS, max_batch=4,
                     max_wait_s=60.0, queue_depth=2, cache_size=0,
                     warm_kinds=())
        # Scheduler never started: the queue can only fill.
        futures = [srv.submit("embed", s) for s in ("MKT", "ACD", "GGG")]
        with pytest.raises(QueueFullError):
            futures[0].result(timeout=0)       # oldest evicted
        assert srv.rejected_total["queue_full"] == 1
        assert not futures[1].done() and not futures[2].done()
        srv.abort()                            # survivors observe the end
        for f in futures[1:]:
            with pytest.raises(ServerClosedError):
                f.result(timeout=5)

    def test_deadline_expiry_e2e(self, trunk):
        params, cfg = trunk
        clock = FakeClock()
        srv = Server(params, cfg, buckets=BUCKETS, max_batch=8,
                     max_wait_s=60.0, cache_size=0, warm_kinds=(),
                     clock=clock)
        f = srv.submit("embed", "MKT", deadline_s=0.5)
        clock.advance(1.0)
        assert srv.scheduler.poll() == 0
        with pytest.raises(DeadlineExceededError):
            f.result(timeout=0)
        # An expiry IS a rejection: it must land in the rejected stats
        # (and thus /metrics and the CLI's --max-requests accounting),
        # not only in scheduler.expired_total.
        assert srv.stats()["rejected"]["deadline"] == 1
        assert srv.scheduler.expired_total == 1

    def test_drain_completes_queued_work(self, trunk):
        """Nothing in flight is lost: requests queued behind a long
        max_wait all complete when the server drains."""
        params, cfg = trunk
        srv = Server(params, cfg, buckets=BUCKETS, max_batch=8,
                     max_wait_s=60.0, cache_size=0, warm_kinds=())
        srv.start()
        futures = [srv.submit("embed", s) for s in RAGGED]
        assert srv.drain(timeout=60)
        for f in futures:
            out = f.result(timeout=0)          # resolved, not dropped
            assert np.isfinite(out["global"]).all()
        assert srv.completed_total == len(RAGGED)
        with pytest.raises(ServerClosedError):
            srv.submit("embed", "MKT")
        assert srv.rejected_total["closed"] == 1

    def test_abort_fails_pending_with_typed_error(self, trunk):
        params, cfg = trunk
        srv = Server(params, cfg, buckets=BUCKETS, max_batch=8,
                     max_wait_s=60.0, cache_size=0, warm_kinds=())
        futures = [srv.submit("embed", s) for s in ("MKT", "ACD")]
        srv.abort()
        for f in futures:
            with pytest.raises(ServerClosedError):
                f.result(timeout=0)

    def test_on_long_reject_and_truncate(self, trunk):
        params, cfg = trunk
        window = cfg.data.seq_len - 2
        long_seq = "A" * (window + 10)
        rej = Server(params, cfg, buckets=BUCKETS, on_long="reject",
                     cache_size=0, warm_kinds=())
        with pytest.raises(SequenceTooLongError):
            rej.submit("embed", long_seq)
        assert rej.rejected_total["too_long"] == 1
        tr = Server(params, cfg, buckets=BUCKETS, on_long="truncate",
                    max_batch=1, max_wait_s=0.002, cache_size=0,
                    warm_kinds=())
        with tr:
            out = tr.embed(long_seq, timeout=30)
            assert tr.truncated_total == 1
            assert np.isfinite(out["global"]).all()
            # A '?' beyond the window can never be filled → reject even
            # under truncate.
            with pytest.raises(SequenceTooLongError):
                tr.submit("predict_residues", "A" * window + "?")


# -------------------------------------------- satellite: tokenization

class TestTokenizeOverflow:
    @pytest.fixture(autouse=True)
    def _propagate_package_logger(self):
        """utils.logging.start_log() (run by any earlier in-process CLI
        test) sets propagate=False on the package logger, which hides
        records from caplog's root handler — restore propagation for
        the duration of these assertions."""
        pkg = logging.getLogger("proteinbert_tpu")
        saved = pkg.propagate
        pkg.propagate = True
        yield
        pkg.propagate = saved

    def test_error_mode_raises_typed(self):
        with pytest.raises(SequenceTooLongError, match="model window"):
            inference._tokenize_masked(["A" * 47], 48, on_overflow="error")

    def test_warn_mode_counts_and_logs(self, caplog):
        before = inference.TRUNCATED_TOTAL[0]
        with caplog.at_level("WARNING", logger="proteinbert_tpu.inference"):
            out = inference._tokenize_masked(["A" * 50, "MKT"], 48)
        assert inference.TRUNCATED_TOTAL[0] == before + 1
        assert any("truncating" in r.message for r in caplog.records)
        assert out.shape == (2, 48)

    def test_count_mode_is_quiet(self, caplog):
        before = inference.TRUNCATED_TOTAL[0]
        with caplog.at_level("WARNING", logger="proteinbert_tpu.inference"):
            inference._tokenize_masked(["A" * 50], 48, on_overflow="count")
        assert inference.TRUNCATED_TOTAL[0] == before + 1
        assert not caplog.records

    def test_in_window_never_counts(self):
        before = inference.TRUNCATED_TOTAL[0]
        inference._tokenize_masked(["A" * 46], 48, on_overflow="error")
        assert inference.TRUNCATED_TOTAL[0] == before

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError, match="on_overflow"):
            inference._tokenize_masked(["MKT"], 48, on_overflow="quiet")


# --------------------------------------- satellite: offline bucketed=

class TestOfflineBucketed:
    def test_full_length_bucket_bit_identical(self, trunk):
        """buckets=(seq_len,) feeds the exact shapes the unbucketed path
        feeds → bit-identical results (the satellite's contract)."""
        params, cfg = trunk
        plain = inference.embed(params, cfg, RAGGED, batch_size=4)
        bucketed = inference.embed(params, cfg, RAGGED, batch_size=4,
                                   bucketed=True, buckets=(SEQ_LEN,))
        for k in plain:
            np.testing.assert_array_equal(plain[k], bucketed[k])

    def test_bucket_results_independent_of_traffic_mix(self, trunk):
        """The serving determinism contract: a sequence's bucketed
        result depends only on its own bucket — never on which other
        rows rode in the batch or in which order. (Cross-SHAPE equality
        is deliberately NOT claimed: the reference architecture's convs
        read the pad tail near boundaries, so the padded length is part
        of the model function — docs/serving.md. Per-shape parity is
        the contract, proved bit-exact above and in
        test_full_length_bucket_bit_identical.)"""
        params, cfg = trunk
        solo = inference.embed(params, cfg, [RAGGED[0]], batch_size=4,
                               bucketed=True, buckets=BUCKETS)
        mixed = inference.embed(params, cfg, RAGGED, batch_size=4,
                                bucketed=True, buckets=BUCKETS)
        shuffled = inference.embed(params, cfg, RAGGED[::-1], batch_size=4,
                                   bucketed=True, buckets=BUCKETS)
        np.testing.assert_array_equal(solo["global"][0],
                                      mixed["global"][0])
        np.testing.assert_array_equal(mixed["global"],
                                      shuffled["global"][::-1])

    def test_predict_go_bucketed(self, trunk):
        params, cfg = trunk
        plain = inference.predict_go(params, cfg, RAGGED, batch_size=4)
        full = inference.predict_go(params, cfg, RAGGED, batch_size=4,
                                    bucketed=True, buckets=(SEQ_LEN,))
        np.testing.assert_array_equal(full, plain)  # equal lengths: bits
        bucketed = inference.predict_go(params, cfg, RAGGED, batch_size=4,
                                        bucketed=True, buckets=BUCKETS)
        assert bucketed.shape == plain.shape
        assert ((bucketed >= 0) & (bucketed <= 1)).all()
        top = inference.predict_go(params, cfg, RAGGED[:1], top_k=3,
                                   bucketed=True, buckets=BUCKETS)
        assert len(top[0]) == 3

    def test_predict_residues_bucketed_zero_fills_tail(self, trunk):
        params, cfg = trunk
        seqs = ["MK?AYIA?QR", "AC?EF"]
        plain_f, plain_p = inference.predict_residues(params, cfg, seqs,
                                                      batch_size=4)
        full_f, full_p = inference.predict_residues(
            params, cfg, seqs, batch_size=4, bucketed=True,
            buckets=(SEQ_LEN,))
        assert full_f == plain_f           # equal lengths: same fills
        np.testing.assert_array_equal(full_p, plain_p)
        buck_f, buck_p = inference.predict_residues(
            params, cfg, seqs, batch_size=4, bucketed=True, buckets=BUCKETS)
        assert "?" not in "".join(buck_f)
        assert buck_p.shape == plain_p.shape
        assert (buck_p[0, :16] > 0).any()
        assert (buck_p[0, 16:] == 0).all()  # beyond the bucket: zeros
        assert (buck_p[1, 16:] == 0).all()

    def test_per_residue_incompatible(self, trunk):
        params, cfg = trunk
        with pytest.raises(ValueError, match="per_residue"):
            inference.embed(params, cfg, RAGGED, bucketed=True,
                            per_residue=True)


# ----------------------------------------------- e2e: telemetry + HTTP

class TestServeTelemetry:
    def test_events_validate_and_cover_lifecycle(self, trunk, tmp_path):
        from proteinbert_tpu.obs import Telemetry, read_events
        from proteinbert_tpu.obs.events import validate_record

        params, cfg = trunk
        path = str(tmp_path / "events.jsonl")
        tele = Telemetry(events_path=path)
        srv = Server(params, cfg, buckets=BUCKETS, max_batch=4,
                     max_wait_s=0.002, queue_depth=2, cache_size=8,
                     warm_kinds=(), telemetry=tele)
        srv.start()
        srv.embed("MKTAYIAKQR", timeout=30)
        srv.embed("MKTAYIAKQR", timeout=30)  # cache hit
        srv.drain(timeout=30)
        tele.close()
        recs = list(read_events(path))
        for rec in recs:
            validate_record(rec)
        kinds = [r["event"] for r in recs]
        assert kinds[0] == "serve_start"
        assert "serve_batch" in kinds
        assert kinds[-1] == "serve_end"
        end = recs[-1]
        assert end["outcome"] == "drained"
        assert end["stats"]["completed"] == 1
        assert end["stats"]["cache_hit_returns"] == 1
        batch = next(r for r in recs if r["event"] == "serve_batch")
        assert batch["bucket_len"] == 16 and batch["rows"] == 1
        # Metrics registry carries the serve instruments.
        snap = tele.metrics.snapshot()
        assert snap["counters"]['serve_requests_total{kind="embed"}'] == 2
        assert snap["counters"]["serve_cache_hits_total"] == 1
        assert snap["histograms"]["serve_latency_seconds"]["count"] == 1

    def test_validator_knows_serve_events(self):
        from proteinbert_tpu.obs.events import make_example, validate_record

        for event in ("serve_start", "serve_batch", "serve_reject",
                      "serve_end"):
            validate_record(make_example(event))
        with pytest.raises(ValueError, match="serve_end.outcome"):
            validate_record({**make_example("serve_end"),
                             "outcome": "bogus"})
        with pytest.raises(ValueError, match="serve_reject.reason"):
            validate_record({**make_example("serve_reject"),
                             "reason": "bogus"})


class TestHTTP:
    @pytest.fixture(scope="class")
    def endpoint(self, trunk):
        from proteinbert_tpu.serve.http import make_http_server

        params, cfg = trunk
        srv = Server(params, cfg, buckets=BUCKETS, max_batch=4,
                     max_wait_s=0.002, cache_size=8, warm_kinds=())
        srv.start()
        httpd = make_http_server(srv, port=0)
        port = httpd.server_address[1]
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        yield srv, f"http://127.0.0.1:{port}"
        httpd.shutdown()
        httpd.server_close()
        srv.close(drain=True, timeout=30)

    def _post(self, url, payload):
        import urllib.error
        import urllib.request

        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def test_embed_roundtrip_matches_in_process(self, endpoint, trunk):
        srv, base = endpoint
        status, body = self._post(base + "/v1/embed",
                                  {"seq": "MKTAYIAKQR"})
        assert status == 200
        local = srv.embed("MKTAYIAKQR", timeout=30)
        np.testing.assert_allclose(body["global"], local["global"],
                                   rtol=1e-6, atol=1e-7)

    def test_injected_trace_id_is_the_answers_request_id(self, trunk):
        """The replica's half of the fleet join: a traced server
        answers the id a router sent as X-PBT-Trace back as
        X-PBT-Request-Id, so one id names the request in both
        processes."""
        import urllib.request

        from proteinbert_tpu.obs import Telemetry
        from proteinbert_tpu.serve.http import make_http_server

        params, cfg = trunk
        srv = Server(params, cfg, buckets=BUCKETS, max_batch=4,
                     max_wait_s=0.002, cache_size=0, warm_kinds=(),
                     telemetry=Telemetry(), trace_sample_rate=0.0,
                     replica_id="r0").start()
        httpd = make_http_server(srv, port=0)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{httpd.server_address[1]}/v1/embed",
                data=json.dumps({"seq": "MKTAY"}).encode(),
                headers={"Content-Type": "application/json",
                         "X-PBT-Trace": "f1a2-probe"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.status == 200
                assert resp.headers.get("X-PBT-Request-Id") == "f1a2-probe"
        finally:
            httpd.shutdown()
            httpd.server_close()
            srv.close(drain=True, timeout=30)

    def test_predict_routes(self, endpoint):
        _, base = endpoint
        status, body = self._post(base + "/v1/predict_go",
                                  {"seq": "MKTAYIAKQR", "top_k": 2})
        assert status == 200 and len(body["top"]) == 2
        status, body = self._post(base + "/v1/predict_residues",
                                  {"seq": "MK?AYIAKQR"})
        assert status == 200 and "?" not in body["filled"]

    def test_error_status_mapping(self, endpoint, trunk):
        _, cfg = trunk
        _, base = endpoint
        status, body = self._post(base + "/v1/predict_residues",
                                  {"seq": "A" * (cfg.data.seq_len - 2)
                                   + "?"})
        assert status == 400 and body["type"] == "too_long"
        status, body = self._post(base + "/v1/embed", {"nope": 1})
        assert status == 400 and body["type"] == "bad_request"
        status, _ = self._post(base + "/v1/nope", {"seq": "MKT"})
        assert status == 404

    def test_healthz_and_metrics(self, endpoint):
        import urllib.request

        _, base = endpoint
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            body = json.loads(r.read())
        assert body["ok"] and "cache" in body["stats"]
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            assert r.status == 200


# --------------------------------------------------------- CLI wiring

def test_cli_serve_registered():
    from proteinbert_tpu.cli.main import build_parser, cmd_serve

    args = build_parser().parse_args(
        ["serve", "--pretrained", "/tmp/x", "--max-batch", "4",
         "--max-wait-ms", "5", "--queue-depth", "8", "--on-long",
         "reject", "--port", "0"])
    assert args.fn is cmd_serve
    assert args.max_batch == 4
    assert args.max_wait_ms == 5.0
    assert args.on_long == "reject"
