"""Fleet-scope causal tracing tests (serve/fleet.py, ISSUE 18).

Five areas, all against stub HTTP replicas (canned JSON, no jax):

- **propagation round-trip**: the router's request id rides every
  attempt as `X-PBT-Trace`, seals as `fleet_request.trace_id`, and
  answers the client as `X-PBT-Request-Id` — one id end-to-end; the
  off arm (`propagate_trace=False`) sends no
  header and emits no `fleet_attempt`;
- **sibling-attempt accounting**: attempts on record == retries spent
  + 1 per trace, indices dense from 0, `backoff_s` rides exactly the
  failed attempts a retry followed, and per-trace retries sum to the
  router's `retries_spent`;
- **merged-stream ordering**: `FleetCollector` sorts by
  `(t, src, src_seq)`, re-stamps `seq` 0..N-1, tolerates a torn tail,
  and defaults `replica_id` to the source name without overwriting an
  existing stamp;
- **exactly-once fleet sealing**: one `fleet_request` per trace_id in
  the merged stream; `seal_violations` flags a doctored duplicate;
- **metrics-merge arithmetic**: `fleet_metrics()` sums counters,
  re-labels gauges per replica, merges histogram count/sum/min/max,
  and recomputes window percentiles over the CONCATENATED raw values
  — checked against hand-computed `nearest_rank` answers, plus the
  `GET /fleet/metrics` HTTP route and the unreachable-replica
  `missing` contract.

The cross-process half (a real replica's RequestTrace joining the
propagated id) is covered by tools/fleet_drill.py via
tests/test_fleet.py::TestFleetDrill.
"""

import json
import threading
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from proteinbert_tpu.obs import Telemetry, read_events
from proteinbert_tpu.obs.events import validate_record
from proteinbert_tpu.obs.metrics import nearest_rank
from proteinbert_tpu.serve.fleet import (
    FaultInjector, FleetCollector, FleetRouter, make_fleet_http_server,
)


class TraceStub:
    """Canned-JSON replica that RECORDS the X-PBT-Trace header of every
    POST (None when absent) and serves a scriptable /metrics.json — the
    two capture points the tracing tests need beyond test_fleet.py's
    StubReplica."""

    def __init__(self, name, metrics_payload=None):
        self.name = name
        self.trace_headers = []
        self.metrics_payload = metrics_payload
        self.lock = threading.Lock()
        stub = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _send(self, status, body: bytes):
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthz":
                    self._send(200, b'{"ok": true, "stats": {}}')
                elif self.path == "/metrics.json":
                    if stub.metrics_payload is None:
                        self._send(404, b"{}")
                    else:
                        self._send(200, json.dumps(
                            stub.metrics_payload).encode())
                else:
                    self._send(404, b"{}")

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                self.rfile.read(n)
                with stub.lock:
                    stub.trace_headers.append(
                        self.headers.get("X-PBT-Trace"))
                self._send(200, json.dumps({"from": stub.name}).encode())

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.httpd.daemon_threads = True
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def seen_traces(self):
        with self.lock:
            return list(self.trace_headers)

    def kill(self):
        self.httpd.shutdown()
        self.httpd.server_close()


@pytest.fixture()
def stubs():
    reps = [TraceStub(f"s{i}") for i in range(3)]
    yield reps
    for r in reps:
        r.kill()


def _router(stubs, **kw):
    kw.setdefault("health_interval_s", 0)  # tests drive health_tick()
    kw.setdefault("sleep", lambda s: None)  # no real backoff waits
    kw.setdefault("cache_size", 0)
    return FleetRouter([(r.name, r.url) for r in stubs], **kw).start()


def _body(seq="MKTAYIAK"):
    return json.dumps({"seq": seq}).encode()


def _events(path):
    return read_events(path, strict=True)


# ------------------------------------------------------- propagation


class TestPropagation:
    def test_trace_id_rides_header_seal_and_response(self, stubs,
                                                     tmp_path):
        tele = Telemetry(events_path=str(tmp_path / "ev.jsonl"))
        r = _router(stubs, telemetry=tele)
        status, body, headers = r.route("/v1/embed", _body())
        assert status == 200
        rid = headers["X-PBT-Request-Id"]
        # One id end-to-end: the fleet header IS the request id IS the
        # sealed trace_id IS the forwarded X-PBT-Trace.
        assert headers["X-PBT-Fleet-Request-Id"] == rid
        served = json.loads(body)["from"]
        forwarded = [h for s in stubs for h in s.seen_traces()]
        assert forwarded == [rid]
        r.drain()
        tele.close()
        evs = _events(str(tmp_path / "ev.jsonl"))
        seal = [e for e in evs if e["event"] == "fleet_request"]
        assert len(seal) == 1
        assert seal[0]["trace_id"] == seal[0]["request_id"] == rid
        assert seal[0]["replica_id"] == seal[0]["replica"] == served
        atts = [e for e in evs if e["event"] == "fleet_attempt"]
        assert [a["trace_id"] for a in atts] == [rid]
        assert atts[0]["attempt"] == 0
        assert atts[0]["outcome"] == "ok"
        assert atts[0]["replica"] == served

    def test_off_arm_sends_no_header_emits_no_attempts(self, stubs,
                                                       tmp_path):
        tele = Telemetry(events_path=str(tmp_path / "ev.jsonl"))
        r = _router(stubs, telemetry=tele, propagate_trace=False)
        status, _, headers = r.route("/v1/embed", _body())
        assert status == 200
        # The A/B baseline: no propagated context on the wire...
        assert [h for s in stubs for h in s.seen_traces()] == [None]
        # ...but the router still answers its own id and seals once —
        # sealing is the funnel invariant, not a tracing feature.
        assert headers["X-PBT-Request-Id"].startswith("f")
        r.drain()
        tele.close()
        evs = _events(str(tmp_path / "ev.jsonl"))
        assert [e for e in evs if e["event"] == "fleet_attempt"] == []
        assert len([e for e in evs
                    if e["event"] == "fleet_request"]) == 1

    def test_ids_are_unique_per_request(self, stubs):
        r = _router(stubs)
        rids = set()
        for i in range(8):
            _, _, headers = r.route("/v1/embed", _body(f"SEQ{i}" * 3))
            rids.add(headers["X-PBT-Request-Id"])
        assert len(rids) == 8
        r.drain()


# ------------------------------------------- sibling-attempt records


def _group_by_trace(evs):
    seals, attempts = {}, {}
    for e in evs:
        if e["event"] == "fleet_request":
            seals.setdefault(e["trace_id"], []).append(e)
        elif e["event"] == "fleet_attempt":
            attempts.setdefault(e["trace_id"], []).append(e)
    return seals, attempts


class TestAttemptAccounting:
    def test_attempts_equal_retries_plus_one(self, stubs, tmp_path):
        inj = FaultInjector()
        inj.kill("s0")  # transport failures force retries
        tele = Telemetry(events_path=str(tmp_path / "ev.jsonl"))
        r = _router(stubs, telemetry=tele, fault_injector=inj,
                    max_retries=2)
        for i in range(6):
            status, _, _ = r.route("/v1/embed", _body(f"SEQ{i}" * 3))
            assert status == 200
        st = r.stats()
        r.drain()
        tele.close()
        seals, attempts = _group_by_trace(
            _events(str(tmp_path / "ev.jsonl")))
        assert len(seals) == 6
        retried = 0
        for tid, seal in seals.items():
            assert len(seal) == 1  # exactly-once per trace
            retries = seal[0]["retries"]
            atts = attempts[tid]
            # THE accounting invariant: siblings == retries + 1, with
            # dense 0-based indices in emission order.
            assert len(atts) == retries + 1
            assert [a["attempt"] for a in atts] == list(range(retries + 1))
            # backoff rides exactly the failed attempts a retry
            # followed; the final attempt carries none.
            for a in atts[:-1]:
                assert a["outcome"] == "transport_failed"
                assert a["replica"] == "s0"
                assert a["backoff_s"] >= 0
            assert "backoff_s" not in atts[-1]
            assert atts[-1]["outcome"] == "ok"
            assert atts[-1]["replica"] == seal[0]["replica"]
            assert seal[0]["outcome"] == ("retried_ok" if retries
                                          else "ok")
            retried += retries
        assert retried >= 1  # the kill actually forced a failover
        assert retried == st["retries_spent"]

    def test_exhausted_budget_still_balances(self, stubs, tmp_path):
        inj = FaultInjector()
        for s in stubs:
            inj.kill(s.name)  # nothing routable after retries burn out
        tele = Telemetry(events_path=str(tmp_path / "ev.jsonl"))
        r = _router(stubs, telemetry=tele, fault_injector=inj,
                    max_retries=2)
        status, _, _ = r.route("/v1/embed", _body())
        assert status == 502
        r.drain()
        tele.close()
        seals, attempts = _group_by_trace(
            _events(str(tmp_path / "ev.jsonl")))
        (tid, seal), = seals.items()
        assert seal[0]["outcome"] == "failed"
        assert len(attempts[tid]) == seal[0]["retries"] + 1
        assert all(a["outcome"] == "transport_failed"
                   for a in attempts[tid])


# ---------------------------------------------- merged-stream funnel


def _write_stream(path, n, source):
    """n schema-valid note records via a real Telemetry writer."""
    tele = Telemetry(events_path=str(path))
    for i in range(n):
        tele.emit("note", source=source, kind=f"mark{i}")
    tele.close()


def _rewrite_t(path, ts, extra=None):
    """Re-stamp the t of each record (records stay schema-valid) so the
    merge order is deterministic; `extra` patches fields per index."""
    recs = [json.loads(ln) for ln in open(path) if ln.strip()]
    assert len(recs) == len(ts)
    with open(path, "w") as f:
        for i, rec in enumerate(recs):
            rec["t"] = ts[i]
            for k, v in (extra or {}).get(i, {}).items():
                rec[k] = v
            f.write(json.dumps(rec) + "\n")


class TestMergedStream:
    def test_order_restamp_and_replica_default(self, tmp_path):
        router_p = tmp_path / "router.jsonl"
        ra_p = tmp_path / "ra.jsonl"
        rb_p = tmp_path / "rb.jsonl"
        _write_stream(router_p, 3, "router")
        _write_stream(ra_p, 3, "ra")
        _write_stream(rb_p, 3, "rb")
        # Interleaved wall clocks with a 3-way tie at t=4.0 — the tie
        # must break by (src, src_seq), never by input order.
        _rewrite_t(router_p, [1.0, 4.0, 7.0])
        _rewrite_t(ra_p, [2.0, 4.0, 8.0],
                   extra={0: {"replica_id": "stamped"}})
        _rewrite_t(rb_p, [4.0, 4.0, 3.0])
        with open(rb_p, "a") as f:
            f.write('{"event": "note", "t": 9')  # torn tail (crash)
        coll = FleetCollector({"router": str(router_p)})
        coll.add_source("ra", str(ra_p))
        coll.add_source("rb", str(rb_p))
        merged = coll.collect()
        assert len(merged) == 9  # torn tail skipped, nothing else lost
        keys = [(r["t"], r["src"], r["src_seq"]) for r in merged]
        assert keys == sorted(keys)
        # rb's t went 4.0, 4.0, 3.0: src_seq breaks the intra-source
        # tie and t reorders across sources.
        assert keys[:2] == [(1.0, "router", 0), (2.0, "ra", 0)]
        assert [k[1] for k in keys if k[0] == 4.0] == \
            ["ra", "rb", "rb", "router"]
        # Dense re-sequencing: the merged stream passes the same
        # monotonic-seq validation as any single stream.
        assert [r["seq"] for r in merged] == list(range(9))
        for rec in merged:
            validate_record(rec)
        # replica_id defaults to the source name; an existing stamp
        # (a fleet_request's serving replica) is never overwritten.
        by_src = {}
        for rec in merged:
            by_src.setdefault(rec["src"], []).append(rec["replica_id"])
        assert by_src["router"] == ["router"] * 3
        assert by_src["rb"] == ["rb"] * 3
        assert sorted(by_src["ra"]) == ["ra", "ra", "stamped"]

    def test_missing_source_skipped(self, tmp_path):
        p = tmp_path / "only.jsonl"
        _write_stream(p, 2, "router")
        coll = FleetCollector({"router": str(p),
                               "gone": str(tmp_path / "never.jsonl")})
        assert len(coll.collect()) == 2

    def test_write_roundtrips_strict(self, tmp_path):
        p = tmp_path / "s.jsonl"
        _write_stream(p, 4, "router")
        coll = FleetCollector({"router": str(p)})
        out = tmp_path / "merged.jsonl"
        n = coll.write(str(out))
        assert n == 4
        back = read_events(str(out), strict=True)
        assert [r["seq"] for r in back] == list(range(4))


# --------------------------------------------- exactly-once sealing


class TestFleetSealing:
    def test_one_seal_per_trace_in_merged_stream(self, stubs, tmp_path):
        tele = Telemetry(events_path=str(tmp_path / "router.jsonl"))
        r = _router(stubs, telemetry=tele)
        rids = [r.route("/v1/embed", _body(f"SEQ{i}" * 3))[2]
                ["X-PBT-Request-Id"] for i in range(5)]
        r.drain()
        tele.close()
        merged = FleetCollector(
            {"router": str(tmp_path / "router.jsonl")}).collect()
        seals = [e for e in merged if e["event"] == "fleet_request"]
        assert sorted(e["trace_id"] for e in seals) == sorted(rids)
        assert FleetCollector.seal_violations(merged) == {}

    def test_violations_flag_duplicates_and_gaps(self):
        def seal(tid):
            return {"event": "fleet_request", "trace_id": tid}

        records = [seal("f1-1"), seal("f1-2"), seal("f1-2"),
                   {"event": "fleet_attempt", "trace_id": "f1-3"}]
        assert FleetCollector.seal_violations(records) == {"f1-2": 2}

    def test_request_id_fallback_for_old_streams(self):
        # Pre-ISSUE-18 fleet_request records carry request_id only;
        # sealing audits must still count them.
        records = [{"event": "fleet_request", "request_id": "f1-9"}] * 2
        assert FleetCollector.seal_violations(records) == {"f1-9": 2}


# ------------------------------------------------- aggregation plane


R0_METRICS = {
    "replica_id": "s0",
    "snapshot": {
        "counters": {"serve_requests_total": 3.0,
                     'serve_rejects_total{reason="queue_full"}': 1.0},
        "gauges": {"serve_queue_depth": 2.0},
        "histograms": {"serve_batch_rows": {
            "count": 2, "sum": 0.5, "min": 0.1, "max": 0.4}},
    },
    "windows": {"serve_e2e_seconds": [0.1, 0.2, 0.3]},
}
R1_METRICS = {
    "replica_id": "s1",
    "snapshot": {
        "counters": {"serve_requests_total": 4.0},
        "gauges": {"serve_queue_depth": 7.0},
        "histograms": {"serve_batch_rows": {
            "count": 1, "sum": 0.2, "min": 0.2, "max": 0.2}},
    },
    "windows": {"serve_e2e_seconds": [0.9, 0.05]},
}


@pytest.fixture()
def metric_stubs():
    reps = [TraceStub("s0", metrics_payload=R0_METRICS),
            TraceStub("s1", metrics_payload=R1_METRICS)]
    yield reps
    for r in reps:
        r.kill()


class TestMetricsMerge:
    def test_merge_arithmetic_vs_hand_computed(self, metric_stubs):
        r = _router(metric_stubs)
        fm = r.fleet_metrics()
        r.drain()
        assert fm["replicas"] == ["s0", "s1"]
        assert fm["missing"] == []
        # Counters SUM across replicas (labels and all); a counter only
        # one replica reports still surfaces.
        assert fm["counters"]["serve_requests_total"] == 7.0
        assert fm["counters"][
            'serve_rejects_total{reason="queue_full"}'] == 1.0
        # Gauges stay per-replica under a replica= label — a mean of
        # queue depths would hide the hot one.
        assert fm["gauges"]['serve_queue_depth{replica="s0"}'] == 2.0
        assert fm["gauges"]['serve_queue_depth{replica="s1"}'] == 7.0
        # Histograms: count/sum added, min/max combined.
        assert fm["histograms"]["serve_batch_rows"] == {
            "count": 3, "sum": 0.7, "min": 0.1, "max": 0.4}
        # Windows: percentiles over the CONCATENATED raw values — the
        # fleet p99 (0.9) is NOT any function of s0's p99 (0.3).
        concat = sorted([0.1, 0.2, 0.3, 0.9, 0.05])
        w = fm["windows"]["serve_e2e_seconds"]
        assert w["n"] == 5
        assert w["p50_s"] == round(nearest_rank(concat, 0.50), 6) == 0.2
        assert w["p99_s"] == round(nearest_rank(concat, 0.99), 6) == 0.9
        assert w["mean_s"] == round(sum(concat) / 5, 6)

    def test_unreachable_replica_listed_missing(self, metric_stubs):
        dead = TraceStub("s2")  # no /metrics.json payload -> 404
        dead.kill()             # and no socket either
        r = FleetRouter(
            [(s.name, s.url) for s in metric_stubs]
            + [("s2", dead.url)],
            health_interval_s=0, cache_size=0,
            health_timeout_s=0.5).start()
        fm = r.fleet_metrics()
        r.drain()
        # Partial view that says so beats a hang: the live replicas
        # still merge, the dead one is named.
        assert fm["replicas"] == ["s0", "s1"]
        assert fm["missing"] == ["s2"]
        assert fm["counters"]["serve_requests_total"] == 7.0

    def test_http_route_serves_merged_view(self, metric_stubs):
        r = _router(metric_stubs)
        httpd = make_fleet_http_server(r)
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        port = httpd.server_address[1]
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/fleet/metrics",
                    timeout=5) as resp:
                assert resp.status == 200
                fm = json.loads(resp.read())
        finally:
            httpd.shutdown()
            httpd.server_close()
            r.drain()
        assert fm["counters"]["serve_requests_total"] == 7.0
        assert fm["windows"]["serve_e2e_seconds"]["p99_s"] == 0.9
