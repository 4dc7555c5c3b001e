#!/usr/bin/env python
"""Fleet fault-injection drill (ISSUE 11): kill a replica mid-request,
inject latency spikes and torn health responses, and PROVE — via the
router's exactly-once seal funnel plus each replica's PR 6 trace
funnel — that every accepted request terminates in exactly one sealed
outcome (served or typed-rejected; none lost, none double-sealed).

The drill runs a real fleet in one process on CPU: N in-process serve
replicas (each a full `serve.Server` + HTTP endpoint with its own
telemetry stream) behind a real `FleetRouter` + HTTP front, driven by
concurrent HTTP clients. Mid-run it

  1. injects a latency spike on the victim replica (so requests are
     genuinely in flight on it),
  2. KILLS the victim — `Server.abort()` + socket close, the
     hardest-landing kill an in-process replica can take: pending
     futures fail with ServerClosedError (HTTP 503) and new
     connections are refused — the router must retry both shapes,
  3. tears another replica's health responses for a few checks (it
     must go dead and then be re-admitted once the tear clears).

Gates (exit nonzero on violation — tier-1 runs this as a smoke stage):
  - router accepted == router sealed == sum(outcomes); every client
    call got exactly one typed response (2xx or typed-error JSON);
  - zero lost: client 200s == ok+retried_ok+cache_hit,
    typed rejections == shed+failed;
  - failover actually happened: retried_ok >= 1 and the victim's
    stream shows aborted/rejected seals;
  - every router/replica event record round-trips the schema
    validator; no request_id seals twice within a stream;
  - fleet tracing (ISSUE 18): every client 200 carries an
    X-PBT-Request-Id naming a sealed trace; the MERGED stream
    (FleetCollector over router + every replica) is schema-valid and
    re-sequenced 0..N-1 with exactly-once sealing and attempts ==
    retries + 1 per trace; a request whose FIRST attempt died on the
    killed victim reconstructs as one COMPLETE causal chain via a
    `pbt diagnose --fleet --trace-id` subprocess over the merged
    stream alone;
  - grey failure: with one replica answering health checks SLOWLY
    (injector.set_health_latency), the health loop keeps visiting
    every replica (scrape counts advance — no starvation), measured
    by the fleet_health_scrape_seconds histogram.

Latency/shed ratios are reported, not gated (a 1-core CI box is noisy).

Usage:
  python tools/fleet_drill.py [--replicas 3] [--requests 60]
      [--clients 8] [--kill-frac 0.3] [--outdir DIR] [--json]
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(1, os.path.dirname(os.path.abspath(__file__)))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

SEQ_LEN = 48
BUCKETS = (16, 32, 48)
AA = "ACDEFGHIKLMNPQRSTVWY"


def _tiny_cfg():
    from proteinbert_tpu.configs import (
        DataConfig, ModelConfig, OptimizerConfig, PretrainConfig,
        TrainConfig,
    )

    return PretrainConfig(
        model=ModelConfig(local_dim=16, global_dim=32, key_dim=8,
                          num_heads=2, num_blocks=2, num_annotations=32,
                          dtype="float32"),
        data=DataConfig(seq_len=SEQ_LEN, batch_size=4),
        optimizer=OptimizerConfig(warmup_steps=5),
        train=TrainConfig(seed=0, max_steps=1),
    )


class LocalReplica:
    """One in-process serve replica: Server + HTTP endpoint + its own
    telemetry events stream (the PR 6 per-request trace funnel)."""

    def __init__(self, name: str, params, cfg, events_path: str):
        from proteinbert_tpu.obs import Telemetry
        from proteinbert_tpu.serve import Server
        from proteinbert_tpu.serve.http import make_http_server

        self.name = name
        self.events_path = events_path
        self.tele = Telemetry(events_path=events_path)
        self.server = Server(
            params, cfg, buckets=BUCKETS, max_batch=4, max_wait_s=0.005,
            queue_depth=64, cache_size=256, telemetry=self.tele,
            trace_sample_rate=1.0, replica_id=name)
        self.server.start()
        self.httpd = make_http_server(self.server, "127.0.0.1", 0)
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        self.thread = threading.Thread(target=self.httpd.serve_forever,
                                       daemon=True, name=f"{name}-http")
        self.thread.start()
        self.killed = False

    def kill(self):
        """Mid-request hard landing: pending work fails typed (503),
        then the socket goes away (connection refused)."""
        self.killed = True
        self.server.abort()
        self.httpd.shutdown()
        self.httpd.server_close()
        self.tele.close()

    def drain(self):
        if self.killed:
            return
        self.httpd.shutdown()
        self.httpd.server_close()
        self.server.drain(timeout=30)
        self.tele.close()


def _post(url: str, payload: dict, timeout: float = 60.0):
    """POST returning (status, body, fleet id) — the X-PBT-Request-Id
    header is the trace id `pbt diagnose --fleet --trace-id` takes."""
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return (resp.status, json.loads(resp.read()),
                    resp.headers.get("X-PBT-Request-Id"))
    except urllib.error.HTTPError as e:
        rid = e.headers.get("X-PBT-Request-Id") if e.headers else None
        try:
            return e.code, json.loads(e.read()), rid
        except ValueError:
            return e.code, None, rid


def run_drill(args) -> dict:
    import numpy as np

    from faults import FaultInjector  # tools/faults.py: the one shared
    # injection surface of the fleet and map drills (ISSUE 14)
    from proteinbert_tpu.obs import Telemetry, read_events
    from proteinbert_tpu.serve.fleet import (
        FleetRouter, make_fleet_http_server,
    )
    from proteinbert_tpu.train import create_train_state

    outdir = args.outdir or tempfile.mkdtemp(prefix="pbt_fleet_drill_")
    os.makedirs(outdir, exist_ok=True)
    cfg = _tiny_cfg()
    import jax

    params = create_train_state(jax.random.PRNGKey(0), cfg).params

    replicas = [
        LocalReplica(f"r{i}", params, cfg,
                     os.path.join(outdir, f"replica{i}.events.jsonl"))
        for i in range(args.replicas)
    ]
    router_events = os.path.join(outdir, "router.events.jsonl")
    tele = Telemetry(events_path=router_events)
    injector = FaultInjector()
    router = FleetRouter(
        [(r.name, r.url) for r in replicas], telemetry=tele,
        health_interval_s=0.1, health_timeout_s=1.0,
        fail_threshold=2, readmit_threshold=2,
        max_retries=args.replicas, backoff_base_s=0.02,
        backoff_cap_s=0.2, retry_budget_ratio=0.5,
        retry_budget_floor=max(8, args.requests // 2),
        request_timeout_s=60.0, cache_size=512,
        fault_injector=injector,
    ).start()
    httpd = make_fleet_http_server(router, "127.0.0.1", 0)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    threading.Thread(target=httpd.serve_forever, daemon=True,
                     name="router-http").start()

    rng = np.random.default_rng(args.seed)
    payloads = []
    for i in range(args.requests):
        n = int(rng.integers(5, SEQ_LEN - 2))
        seq = "".join(rng.choice(list(AA), size=n))
        if i % 3 == 2:
            payloads.append(("/v1/predict_go", {"seq": seq, "top_k": 3}))
        else:
            payloads.append(("/v1/embed", {"seq": seq}))

    results: list = [None] * args.requests
    done_count = [0]
    done_lock = threading.Lock()
    victim = replicas[1 % len(replicas)]
    torn = replicas[0]

    def client(worker: int):
        for i in range(worker, args.requests, args.clients):
            path, payload = payloads[i]
            results[i] = _post(base + path, payload)
            with done_lock:
                done_count[0] += 1

    threads = [threading.Thread(target=client, args=(w,), daemon=True)
               for w in range(args.clients)]
    for t in threads:
        t.start()

    # Fault sequence: latency spike on the victim (requests pile onto
    # it), kill it mid-flight, tear another replica's health for a few
    # checks, then clear the tear (it must come back).
    kill_at = max(1, int(args.requests * args.kill_frac))
    while True:
        with done_lock:
            if done_count[0] >= kill_at:
                break
        time.sleep(0.005)
    injector.set_latency(victim.name, 0.15)
    time.sleep(0.05)  # let some requests enter the spike window
    victim.kill()
    injector.set_latency(victim.name, 0.0)
    injector.tear_health(torn.name)
    time.sleep(0.35)  # >= fail_threshold * health_interval → dead
    injector.tear_health(torn.name, torn=False)

    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads), "client hang"
    # Let the torn replica's re-admission land on the record.
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        st = {r["name"]: r["state"] for r in router.replica_status()}
        if st[torn.name] in ("up", "degraded"):
            break
        time.sleep(0.05)

    # Grey-failure window (ISSUE 18): one replica answers health
    # checks SLOWLY (not dead, not torn — the failure mode health
    # binaries miss). The health loop must keep visiting EVERY
    # replica: scrape counts all advance across the window, and the
    # slow replica's latency lands in fleet_health_scrape_seconds.
    grey_failures = []
    injector.set_health_latency(torn.name, 0.35)
    before = {name: h.count for name, h in router._scrape_h.items()}
    time.sleep(1.6)  # several sweeps even at ~0.35s+interval each
    after = {name: h.count for name, h in router._scrape_h.items()}
    injector.set_health_latency(torn.name, 0.0)
    starved = sorted(n for n in before if after[n] <= before[n])
    if starved:
        grey_failures.append(
            f"health loop starved under a slow replica: no new scrape "
            f"of {starved} during the grey window")
    slow_max = router._scrape_h[torn.name].max
    if slow_max < 0.3:
        grey_failures.append(
            f"fleet_health_scrape_seconds never measured the injected "
            f"0.35s health latency (max {slow_max:.3f}s)")

    httpd.shutdown()
    httpd.server_close()
    router.drain()
    for r in replicas:
        r.drain()
    tele.close()

    # ------------------------------------------------------------ audit
    failures = []
    stats = router.stats()
    outcomes = stats["outcomes"]
    if stats["accepted"] != stats["sealed"]:
        failures.append(f"router accepted {stats['accepted']} != sealed "
                        f"{stats['sealed']}")
    if sum(outcomes.values()) != stats["sealed"]:
        failures.append(f"outcome sum {sum(outcomes.values())} != sealed "
                        f"{stats['sealed']}")
    if stats["accepted"] != args.requests:
        failures.append(f"router accepted {stats['accepted']} != "
                        f"{args.requests} sent")

    lost = sum(1 for r in results if r is None)
    if lost:
        failures.append(f"{lost} client requests got NO response")
    ok_like = sum(1 for r in results if r and r[0] == 200)
    typed_rejects = sum(
        1 for r in results
        if r and r[0] != 200 and isinstance(r[1], dict) and "type" in r[1])
    untyped = args.requests - lost - ok_like - typed_rejects
    if untyped:
        failures.append(f"{untyped} client responses were neither 200 "
                        "nor typed-error JSON")
    want_ok = (outcomes.get("ok", 0) + outcomes.get("retried_ok", 0)
               + outcomes.get("cache_hit", 0))
    if ok_like != want_ok:
        failures.append(f"client 200s {ok_like} != router ok-like "
                        f"{want_ok}")
    want_reject = outcomes.get("shed", 0) + outcomes.get("failed", 0)
    if typed_rejects != want_reject:
        failures.append(f"client typed rejections {typed_rejects} != "
                        f"router shed+failed {want_reject}")
    if not outcomes.get("retried_ok"):
        failures.append("no retried_ok outcome — the kill never "
                        "exercised failover")

    # Schema validity + per-stream exactly-once sealing.
    from proteinbert_tpu.obs.events import validate_record  # noqa: F401

    rrecs = read_events(router_events, strict=True)
    freqs = [r for r in rrecs if r["event"] == "fleet_request"]
    if len(freqs) != stats["sealed"]:
        failures.append(f"{len(freqs)} fleet_request events != "
                        f"{stats['sealed']} sealed")
    rids = [r["request_id"] for r in freqs if "request_id" in r]
    dupes = [k for k, n in collections.Counter(rids).items() if n > 1]
    if dupes:
        failures.append(f"router double-sealed request ids: {dupes[:5]}")
    states_seen = [r["state"] for r in rrecs
                   if r["event"] == "fleet_replica"]
    if "dead" not in states_seen:
        failures.append("no fleet_replica{state=dead} transition on "
                        "the record")
    if "admitted" not in states_seen:
        failures.append("torn-health replica was never re-admitted")

    victim_aborted = 0
    for r in replicas:
        recs = read_events(r.events_path, strict=True)
        seals = [x for x in recs if x["event"] == "serve_request"]
        per_id = collections.Counter(x["request_id"] for x in seals)
        dup = [k for k, n in per_id.items() if n > 1]
        if dup:
            failures.append(f"replica {r.name} double-sealed: {dup[:5]}")
        if r is victim:
            victim_aborted = sum(1 for x in seals
                                 if x["outcome"] in ("aborted", "error"))

    failures.extend(grey_failures)

    # ------------------------------------ fleet trace plane (ISSUE 18)
    from proteinbert_tpu.obs.diagnose import summarize_fleet
    from proteinbert_tpu.serve.fleet import FleetCollector

    # Every client 200 must carry the fleet id, and every id a client
    # saw must name a sealed trace — one id end-to-end.
    sealed_ids = {r.get("trace_id") or r.get("request_id")
                  for r in freqs}
    no_header = sum(1 for r in results if r and r[0] == 200 and not r[2])
    if no_header:
        failures.append(f"{no_header} client 200s carried no "
                        "X-PBT-Request-Id header")
    unknown_ids = sorted({r[2] for r in results if r and r[2]}
                         - sealed_ids)
    if unknown_ids:
        failures.append(f"client-visible fleet ids never sealed: "
                        f"{unknown_ids[:5]}")

    # One merged, seq-ordered fleet stream: router + every replica
    # through the torn-tail-tolerant reader, re-sequenced 0..N-1.
    collector = FleetCollector({"router": router_events})
    for r in replicas:
        collector.add_source(r.name, r.events_path)
    merged_path = os.path.join(outdir, "merged.events.jsonl")
    merged_n = collector.write(merged_path)
    merged = read_events(merged_path, strict=True)
    if len(merged) != merged_n:
        failures.append(f"merged stream re-read {len(merged)} of "
                        f"{merged_n} written records")
    for i, rec in enumerate(merged):
        try:
            validate_record(rec)
        except ValueError as e:
            failures.append(f"merged stream schema break at record "
                            f"{i}: {e}")
            break
    if [r["seq"] for r in merged] != list(range(len(merged))):
        failures.append("merged stream seq is not a dense 0..N-1 "
                        "re-sequencing")
    viol = FleetCollector.seal_violations(merged)
    if viol:
        failures.append(f"exactly-once sealing broke in the merged "
                        f"stream: {dict(list(viol.items())[:5])}")
    fsum = summarize_fleet(merged)
    if fsum["attempt_mismatches"]:
        failures.append(f"attempts != retries + 1 for traces "
                        f"{fsum['attempt_mismatches'][:5]}")
    if fsum["incomplete"]:
        failures.append(f"incomplete causal chains in the merged "
                        f"stream: {fsum['incomplete'][:5]}")

    # The headline gate: a request whose attempt DIED on the killed
    # victim must reconstruct as one complete causal chain — via the
    # actual CLI subprocess, from the merged stream ALONE.
    attempts_by_tid: dict = {}
    for rec in merged:
        if rec["event"] == "fleet_attempt":
            attempts_by_tid.setdefault(rec["trace_id"], []).append(rec)
    victim_tid = None
    for rec in freqs:
        if rec.get("outcome") != "retried_ok":
            continue
        atts = sorted(attempts_by_tid.get(rec.get("trace_id"), []),
                      key=lambda a: a["attempt"])
        if (atts and atts[-1]["outcome"] == "ok"
                and any(a["replica"] == victim.name
                        and a["outcome"] in ("transport_failed",
                                             "retryable")
                        for a in atts)):
            victim_tid = rec["trace_id"]
            break
    chain = None
    if victim_tid is None:
        failures.append(
            "no retried_ok trace with a failed attempt on the killed "
            "victim — the reconstruction gate never ran")
    else:
        import subprocess

        perfetto_path = os.path.join(outdir, "fleet_trace.json")
        proc = subprocess.run(
            [sys.executable, "-m", "proteinbert_tpu", "diagnose",
             merged_path, "--fleet", "--trace-id", victim_tid,
             "--trace-perfetto", perfetto_path, "--json"],
            capture_output=True, text=True, timeout=120, cwd=REPO)
        if proc.returncode != 0:
            failures.append(f"pbt diagnose --fleet subprocess failed "
                            f"rc={proc.returncode}: "
                            f"{proc.stderr.strip()[:300]}")
        else:
            # --trace-perfetto logs a line before the JSON summary.
            chain = json.loads(
                proc.stdout.strip().splitlines()[-1])["fleet"].get(
                "chain")
            if chain is None:
                failures.append(f"diagnose found no chain for trace "
                                f"{victim_tid} in the merged stream")
            elif not chain["complete"]:
                failures.append(f"trace {victim_tid} reconstructed "
                                f"INCOMPLETE: {chain}")
            elif not any(a["replica"] == victim.name
                         for a in chain["attempts"]):
                failures.append(f"reconstructed chain for {victim_tid} "
                                "lost the victim attempt")
            elif chain["attempts"][-1]["serve"] is None:
                failures.append(
                    f"winning attempt of {victim_tid} joined no "
                    "replica-side serve_request (stage tiling missing)")
            with open(perfetto_path) as f:
                lanes = {e.get("tid") for e in
                         json.load(f)["traceEvents"]
                         if e.get("ph") == "X"}
            if len(lanes) < 3:
                failures.append(
                    f"cross-process Perfetto export has {len(lanes)} "
                    "lane(s); want router + one per attempt (>= 3)")

    summary = {
        "requests": args.requests,
        "clients": args.clients,
        "replicas": args.replicas,
        "router": {k: stats[k] for k in
                   ("accepted", "sealed", "outcomes", "retries_spent")},
        "client_200": ok_like,
        "client_typed_rejects": typed_rejects,
        "victim": victim.name,
        "victim_aborted_or_errored_seals": victim_aborted,
        "replica_states_seen": sorted(set(states_seen)),
        "cache": stats["cache"],
        "outdir": outdir,
        "merged_stream": merged_path,
        "merged_records": merged_n,
        "traces": fsum["traces"],
        "attempts_recorded": fsum["attempts_recorded"],
        "reconstructed_trace": victim_tid,
        "reconstructed_attempts": (len(chain["attempts"])
                                   if chain else None),
        "health_scrapes_in_grey_window": {
            n: after[n] - before[n] for n in sorted(after)},
        "slow_health_scrape_max_s": round(slow_max, 3),
        "failures": failures,
        "ok": not failures,
    }
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--requests", type=int, default=60)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--kill-frac", type=float, default=0.3,
                    help="kill the victim after this fraction of "
                         "requests completed")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--outdir", help="artifact dir (default: temp)")
    ap.add_argument("--json", action="store_true",
                    help="print the summary as one JSON object only")
    args = ap.parse_args(argv)
    if args.replicas < 2:
        ap.error("the drill needs >= 2 replicas (one dies)")
    summary = run_drill(args)
    if args.json:
        print(json.dumps(summary))
    else:
        print(json.dumps(summary, indent=2))
    if not summary["ok"]:
        print("FLEET DRILL FAILED:", "; ".join(summary["failures"]),
              file=sys.stderr)
        return 1
    print(f"fleet drill OK: {summary['requests']} accepted, all sealed "
          f"exactly once ({summary['router']['outcomes']}), victim "
          f"{summary['victim']} killed mid-request, "
          f"{summary['router']['retries_spent']} retries; merged "
          f"{summary['merged_records']} records across "
          f"{summary['traces']} traces, killed-victim trace "
          f"{summary['reconstructed_trace']} reconstructed with "
          f"{summary['reconstructed_attempts']} attempts",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
