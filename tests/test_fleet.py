"""Serve fleet tests (serve/fleet.py, ISSUE 11).

Three tiers:

- **router-logic tests** against stub HTTP replicas (canned JSON, no
  jax): routing, retry-with-backoff on dead replicas, the 429/504
  shed-don't-retry contract, retry budget exhaustion, drain/re-admit,
  the shared content-addressed result cache, torn-health handling, and
  the exactly-once seal accounting;
- **the fleet drill** (tools/fleet_drill.run_drill): three REAL
  in-process serve replicas behind a real router under concurrent
  load, one killed mid-request — zero lost accepted requests, router
  metrics show the failover, every router/replica event schema-valid;
- **warm boot** (tests/serve_warm_child.py): two subprocess boots
  against one fresh persistent compilation cache — the second must be
  faster (the compile-cache satellite).
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from types import SimpleNamespace

import pytest

from proteinbert_tpu.obs import Telemetry, read_events
from proteinbert_tpu.serve.fleet import (
    FaultInjector, FleetRouter, make_fleet_http_server,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class StubReplica:
    """Canned-JSON serve replica: scriptable per-path status/payload,
    request counting, torn-health mode, and a hard kill (socket gone)."""

    def __init__(self, name):
        self.name = name
        self.requests = []
        self.responses = {}  # path -> (status, payload dict)
        self.health = {"ok": True, "stats": {}}
        self.torn_health = False
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
                    if stub.torn_health:
                        # A replica dying mid-write: half a JSON object.
                        self._send(200, b'{"ok": tru')
                    else:
                        self._send(200, json.dumps(stub.health).encode())
                else:
                    self._send(404, b"{}")

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(n)
                with stub.lock:
                    stub.requests.append((self.path, body))
                status, payload = stub.responses.get(
                    self.path, (200, {"from": stub.name}))
                self._send(status, json.dumps(payload).encode())

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.httpd.daemon_threads = True
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()
        self.alive = True

    def request_count(self):
        with self.lock:
            return len(self.requests)

    def kill(self):
        if self.alive:
            self.alive = False
            self.httpd.shutdown()
            self.httpd.server_close()


@pytest.fixture()
def stubs():
    reps = [StubReplica(f"s{i}") for i in range(3)]
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


class TestRouting:
    def test_ok_routes_and_seals_once(self, stubs):
        r = _router(stubs)
        status, body, headers = r.route("/v1/embed", _body())
        assert status == 200
        assert json.loads(body)["from"] in {"s0", "s1", "s2"}
        assert headers["X-PBT-Fleet-Replica"] == json.loads(body)["from"]
        st = r.stats()
        assert st["accepted"] == st["sealed"] == 1
        assert st["outcomes"] == {"ok": 1}
        r.drain()

    def test_least_inflight_spreads_load(self, stubs):
        r = _router(stubs)
        for i in range(9):
            r.route("/v1/embed", _body(f"SEQ{i}" * 3))
        counts = [s.request_count() for s in stubs]
        assert sum(counts) == 9
        assert all(c >= 1 for c in counts)  # round-robin tiebreak
        r.drain()

    def test_retry_on_dead_replica_then_ok(self, stubs):
        stubs[0].kill()
        stubs[1].kill()
        r = _router(stubs, max_retries=3)
        status, body, _ = r.route("/v1/embed", _body())
        assert status == 200
        assert json.loads(body)["from"] == "s2"
        st = r.stats()
        assert st["outcomes"] == {"retried_ok": 1}
        assert st["retries_spent"] >= 1
        r.drain()

    def test_replica_503_is_retried(self, stubs):
        stubs[0].responses["/v1/embed"] = (503, {"type": "closed"})
        stubs[1].responses["/v1/embed"] = (503, {"type": "closed"})
        r = _router(stubs, max_retries=3)
        status, body, _ = r.route("/v1/embed", _body())
        assert status == 200 and json.loads(body)["from"] == "s2"
        r.drain()

    def test_429_sheds_without_retry(self, stubs):
        for s in stubs:
            s.responses["/v1/embed"] = (429, {"type": "queue_full"})
        r = _router(stubs, max_retries=3)
        status, body, _ = r.route("/v1/embed", _body())
        assert status == 429
        assert json.loads(body)["type"] == "queue_full"
        # Exactly ONE replica was asked — backpressure never amplified.
        assert sum(s.request_count() for s in stubs) == 1
        assert r.stats()["outcomes"] == {"shed": 1}
        assert r.stats()["retries_spent"] == 0
        r.drain()

    def test_504_deadline_sheds_without_retry(self, stubs):
        stubs[0].responses["/v1/embed"] = (504, {"type": "deadline"})
        stubs[1].responses["/v1/embed"] = (504, {"type": "deadline"})
        stubs[2].responses["/v1/embed"] = (504, {"type": "deadline"})
        r = _router(stubs)
        status, _, _ = r.route("/v1/embed", _body())
        assert status == 504
        assert sum(s.request_count() for s in stubs) == 1
        r.drain()

    def test_client_error_passes_through_as_failed(self, stubs):
        for s in stubs:
            s.responses["/v1/predict_task"] = (404,
                                               {"type": "unknown_head"})
        r = _router(stubs)
        status, body, _ = r.route(
            "/v1/predict_task",
            json.dumps({"seq": "MKT", "head_id": "nope"}).encode())
        assert status == 404
        assert r.stats()["outcomes"] == {"failed": 1}
        assert sum(s.request_count() for s in stubs) == 1  # no retry
        r.drain()

    def test_all_dead_returns_typed_502_failed(self, stubs):
        for s in stubs:
            s.kill()
        r = _router(stubs, max_retries=2)
        status, body, _ = r.route("/v1/embed", _body())
        assert status == 502
        assert json.loads(body)["type"] == "replica_unavailable"
        assert r.stats()["outcomes"] == {"failed": 1}
        r.drain()

    def test_retry_budget_caps_retry_storm(self, stubs):
        for s in stubs:
            s.kill()
        r = _router(stubs, max_retries=10, retry_budget_floor=3,
                    retry_budget_ratio=0.0)
        statuses = [r.route("/v1/embed", _body(f"S{i}" * 4))[0]
                    for i in range(4)]
        # Every request seals TYPED (502 unreachable / 503 no-capacity
        # shed once the dead replicas leave the rotation) — and the
        # budget floor of 3 bounds fleet-wide retries no matter how
        # high the per-request cap is.
        assert all(s in (502, 503) for s in statuses), statuses
        st = r.stats()
        assert st["retries_spent"] == 3
        assert st["sealed"] == 4
        assert set(st["outcomes"]) <= {"failed", "shed"}
        r.drain()


class TestHealthAndLifecycle:
    def test_torn_health_kills_then_readmits(self, stubs, tmp_path):
        tele = Telemetry(events_path=str(tmp_path / "ev.jsonl"))
        r = _router(stubs, telemetry=tele, fail_threshold=2,
                    readmit_threshold=2)
        stubs[0].torn_health = True
        for _ in range(2):
            r.health_tick()
        assert r.replica_status()[0]["state"] == "dead"
        stubs[0].torn_health = False
        for _ in range(2):
            r.health_tick()
        assert r.replica_status()[0]["state"] == "up"
        r.drain()
        tele.close()
        recs = read_events(str(tmp_path / "ev.jsonl"), strict=True)
        states = [x["state"] for x in recs
                  if x["event"] == "fleet_replica"
                  and x["replica"] == "s0"]
        assert states == ["dead", "admitted"]

    def test_dead_replica_not_routed(self, stubs):
        r = _router(stubs, fail_threshold=1)
        stubs[0].torn_health = True
        r.health_tick()
        for i in range(6):
            r.route("/v1/embed", _body(f"Q{i}" * 3))
        assert stubs[0].request_count() == 0
        r.drain()

    def test_slo_burn_degrades_and_deprioritizes(self, stubs):
        stubs[0].health = {"ok": True, "stats": {"slo": {
            "latency_e2e": {"burn_rate": 2.5}}}}
        r = _router(stubs, degrade_burn=1.0)
        r.health_tick()
        assert r.replica_status()[0]["state"] == "degraded"
        for i in range(6):
            r.route("/v1/embed", _body(f"W{i}" * 3))
        # Healthy replicas absorb everything while any exist.
        assert stubs[0].request_count() == 0
        # ...but a degraded replica is still the last resort.
        stubs[1].kill()
        stubs[2].kill()
        status, body, _ = r.route("/v1/embed", _body("LASTRESORT"))
        assert status == 200 and json.loads(body)["from"] == "s0"
        r.drain()

    def test_drain_admit_round_trip_no_capacity_shed(self, stubs):
        r = _router(stubs)
        for s in ("s0", "s1", "s2"):
            r.drain_replica(s)
        status, body, _ = r.route("/v1/embed", _body())
        assert status == 503
        assert json.loads(body)["type"] == "no_capacity"
        assert r.stats()["outcomes"] == {"shed": 1}
        r.admit_replica("s1")
        status, body, _ = r.route("/v1/embed", _body("AGAIN"))
        assert status == 200 and json.loads(body)["from"] == "s1"
        with pytest.raises(KeyError):
            r.drain_replica("nope")
        r.drain()

    def test_shared_cache_survives_failover(self, stubs):
        r = _router(stubs, cache_size=16)
        status, body1, _ = r.route("/v1/embed", _body("CACHEDSEQ"))
        assert status == 200
        served_by = json.loads(body1)["from"]
        # Kill EVERY replica: the warm result must still be served.
        for s in stubs:
            s.kill()
        status, body2, headers = r.route("/v1/embed", _body("CACHEDSEQ"))
        assert status == 200
        assert body2 == body1
        assert headers.get("X-PBT-Fleet-Cache") == "hit"
        st = r.stats()
        assert st["outcomes"]["cache_hit"] == 1
        assert st["cache"]["hits"] == 1, served_by
        r.drain()

    def test_cache_key_scopes_kind_head_topk(self, stubs):
        r = _router(stubs, cache_size=16)
        r.route("/v1/embed", _body("SCOPESEQ"))
        # Same seq, different kind/top_k: MISS, not a wrong-kind hit.
        r.route("/v1/predict_go", json.dumps(
            {"seq": "SCOPESEQ", "top_k": 3}).encode())
        r.route("/v1/predict_go", json.dumps(
            {"seq": "SCOPESEQ", "top_k": 5}).encode())
        assert r.stats()["cache"]["hits"] == 0
        assert sum(s.request_count() for s in stubs) == 3
        r.drain()


class TestFleetHTTPFront:
    def test_http_front_routes_and_controls(self, stubs, tmp_path):
        tele = Telemetry(events_path=str(tmp_path / "ev.jsonl"))
        r = _router(stubs, telemetry=tele)
        httpd = make_fleet_http_server(r, "127.0.0.1", 0)
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            req = urllib.request.Request(
                base + "/v1/embed", data=_body(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as resp:
                assert resp.status == 200
                assert resp.headers["X-PBT-Fleet-Request-Id"]
            with urllib.request.urlopen(base + "/healthz",
                                        timeout=10) as resp:
                health = json.loads(resp.read())
            assert health["ok"] and len(health["replicas"]) == 3
            req = urllib.request.Request(
                base + "/fleet/drain",
                data=json.dumps({"replica": "s0"}).encode())
            with urllib.request.urlopen(req, timeout=10) as resp:
                out = json.loads(resp.read())
            assert out["ok"]
            assert [x for x in out["replicas"]
                    if x["name"] == "s0"][0]["state"] == "draining"
            req = urllib.request.Request(
                base + "/fleet/admit",
                data=json.dumps({"replica": "s0"}).encode())
            with urllib.request.urlopen(req, timeout=10) as resp:
                assert json.loads(resp.read())["ok"]
            with urllib.request.urlopen(base + "/fleet/status",
                                        timeout=10) as resp:
                st = json.loads(resp.read())
            assert st["stats"]["accepted"] == st["stats"]["sealed"] == 1
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=10) as resp:
                text = resp.read().decode()
            assert "fleet_requests_total" in text
        finally:
            httpd.shutdown()
            httpd.server_close()
            r.drain()
            tele.close()
        recs = read_events(str(tmp_path / "ev.jsonl"), strict=True)
        events = [x["event"] for x in recs]
        assert "fleet_start" in events and "fleet_end" in events
        assert events.count("fleet_request") == 1
        # Operator drain/admit are on the record as replica states.
        states = [x["state"] for x in recs
                  if x["event"] == "fleet_replica"]
        assert "draining" in states and "admitted" in states


class TestFleetDrill:
    """The acceptance drill: one of three REAL replicas killed
    mid-request under concurrent load — zero lost accepted requests,
    failover visible in router metrics, every event schema-valid.
    Small knobs of the same harness tier-1 runs bigger."""

    def test_kill_one_of_three_zero_lost(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            from fleet_drill import run_drill
        finally:
            sys.path.pop(0)
        summary = run_drill(SimpleNamespace(
            replicas=3, requests=24, clients=4, kill_frac=0.25, seed=3,
            outdir=str(tmp_path)))
        assert summary["ok"], summary["failures"]
        assert summary["router"]["accepted"] == 24
        assert summary["router"]["outcomes"].get("retried_ok", 0) >= 1
        # Router metrics show the failover (retries spent, dead seen).
        assert summary["router"]["retries_spent"] >= 1
        assert "dead" in summary["replica_states_seen"]


class TestWarmBoot:
    """Compile-cache satellite: the second boot of an identical
    replica against one persistent compilation cache must be faster —
    two subprocess jax boots, because the in-process jit cache would
    fake the win. The fleet story rides on this: a replacement replica
    boots warm."""

    def test_second_boot_is_faster(self, tmp_path):
        cache = tmp_path / "compile_cache"
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(cache))
        # Serve executables at this width compile in well under the
        # harness's 0.3 s floor; the child caches all of them.
        env.pop("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", None)

        def boot():
            out = subprocess.run(
                [sys.executable,
                 os.path.join(REPO, "tests", "serve_warm_child.py")],
                env=env, capture_output=True, text=True, timeout=600)
            assert out.returncode == 0, out.stderr[-3000:]
            return json.loads(out.stdout.strip().splitlines()[-1])

        cold = boot()
        cached_files = [f for _, _, fs in os.walk(cache) for f in fs]
        assert cached_files, "first boot populated no cache entries"
        warm = boot()
        assert warm["executables"] == cold["executables"]
        assert warm["warmup_seconds"] < cold["warmup_seconds"], (
            cold, warm)
        # Report the saving the serve_warmup_seconds_total gauge shows.
        print(f"warm boot: {cold['warmup_seconds']:.2f}s -> "
              f"{warm['warmup_seconds']:.2f}s")
