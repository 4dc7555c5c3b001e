#!/usr/bin/env python
"""Blue-green rollout drill (ISSUE 20): prove the shadow → gate →
flip → rollback lifecycle end to end against a REAL in-process fleet.

Three serve replicas (each a full `serve.Server` + HTTP endpoint with
its own telemetry stream) run behind a real `FleetRouter` + HTTP
front under concurrent client traffic. The drill then:

  1. ships a deliberately DEGRADED candidate trunk (large weight
     perturbation) — the parity gate must refuse it after
     `windows_required` consecutive red windows, unload it everywhere,
     and the shadow traffic must have been INVISIBLE: live responses
     stay bit-identical to the resident baseline, the seal funnel
     never counts a shadow, and the candidate arm leaves no residue;
  2. ships a GOOD candidate (tiny perturbation) under continuous
     traffic — the gates (parity, SLO burn, heads-eval delta, zero
     shadow failures) go green, auto-promotion flips each replica
     atomically, and the drill KILLS one replica immediately before
     its flip verb (`_pre_flip_hook`, the hardest-landing mid-flip
     crash) — the fleet must converge anyway: survivors on the
     candidate fingerprint, victim dead (not mixed), zero lost
     requests, exactly-once sealing intact; frozen heads re-pin via
     `registry.migrate_fingerprint` with an audit trail while the
     unfrozen head gets the typed refusal;
  3. breaches the promoted rollout — instant rollback to the
     host-parked trunk, head pins restored, and post-rollback probes
     BIT-IDENTICAL (parity 0.0) to the pre-rollout baseline.

Gates (exit nonzero on violation — tier-1 runs this as a smoke stage):
  - degraded candidate refused; good candidate promoted; rollback
    restores bit-identical numerics;
  - router accepted == sealed == client calls across ALL phases; the
    merged fleet stream (FleetCollector) is schema-valid with
    exactly-once sealing and attempts == retries + 1 per trace —
    shadows never contaminate the attempt plane;
  - every rollout_* event round-trips the schema validator.

Usage:
  python tools/rollout_drill.py [--outdir DIR] [--json]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

SEQ_LEN = 48
BUCKETS = (24, 48)
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
    """One in-process serve replica with a rollout-capable candidate
    arm: Server + HTTP endpoint + its own telemetry stream."""

    def __init__(self, name, params, cfg, events_path, loader):
        from proteinbert_tpu.obs import Telemetry
        from proteinbert_tpu.serve import Server
        from proteinbert_tpu.serve.http import make_http_server

        self.name = name
        self.events_path = events_path
        self.tele = Telemetry(events_path=events_path)
        self.server = Server(
            params, cfg, buckets=BUCKETS, max_batch=4, max_wait_s=0.005,
            queue_depth=64, cache_size=256, telemetry=self.tele,
            trace_sample_rate=1.0, replica_id=name,
            candidate_loader=loader)
        self.server.start()
        self.httpd = make_http_server(self.server, "127.0.0.1", 0)
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        self.thread = threading.Thread(target=self.httpd.serve_forever,
                                       daemon=True, name=f"{name}-http")
        self.thread.start()
        self.killed = False

    def kill(self):
        """Mid-flip hard landing: pending work fails typed (503), then
        the socket goes away (connection refused for the flip verb)."""
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


class SpyTele:
    """Telemetry pass-through that records every finite shadow parity —
    the good rollout must have measured at least one."""

    def __init__(self, inner):
        self._inner = inner
        self.metrics = inner.metrics
        self.parities = []

    def emit(self, event, **fields):
        if event == "rollout_shadow" and "parity_max" in fields:
            self.parities.append(float(fields["parity_max"]))
        return self._inner.emit(event, **fields)


def _post(url, payload, timeout=60.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read())
        except ValueError:
            return e.code, None


def run_drill(args) -> dict:
    import jax
    import numpy as np

    from proteinbert_tpu.configs import TaskConfig
    from proteinbert_tpu.data.synthetic import make_task_batches
    from proteinbert_tpu.heads import HeadRegistry, trunk_fingerprint
    from proteinbert_tpu.models import finetune as ft_model
    from proteinbert_tpu.obs import Telemetry, read_events, validate_record
    from proteinbert_tpu.obs.diagnose import summarize_fleet
    from proteinbert_tpu.rollout import HeadsEvalGate, RolloutController
    from proteinbert_tpu.rollout.controller import parity_delta
    from proteinbert_tpu.serve.fleet import (
        FleetCollector, FleetRouter, make_fleet_http_server,
    )
    from proteinbert_tpu.train import create_train_state

    outdir = args.outdir or tempfile.mkdtemp(prefix="pbt_rollout_drill_")
    os.makedirs(outdir, exist_ok=True)
    cfg = _tiny_cfg()
    params = create_train_state(jax.random.PRNGKey(0), cfg).params

    def perturb(tree, scale, seed):
        leaves, treedef = jax.tree.flatten(tree)
        rng = np.random.default_rng(seed)
        out = []
        for leaf in leaves:
            a = np.asarray(leaf)
            out.append(a + (scale * rng.standard_normal(a.shape))
                       .astype(a.dtype))
        return jax.tree.unflatten(treedef, out)

    # Good candidate: numerically close (parity gate passes but the
    # fingerprint differs). Bad candidate: large perturbation — its
    # shadow outputs diverge far past any sane parity threshold.
    good_params = perturb(params, 1e-5, 1)
    bad_params = perturb(params, 0.5, 2)
    resident_fp = trunk_fingerprint(params)
    good_fp = trunk_fingerprint(good_params)
    bad_fp = trunk_fingerprint(bad_params)
    assert len({resident_fp, good_fp, bad_fp}) == 3

    # Registry: one FROZEN head (migrates on promotion) + one UNFROZEN
    # head (typed migration refusal; still scores in the eval gate).
    registry = HeadRegistry(os.path.join(outdir, "registry"))
    frozen_task = TaskConfig(kind="sequence_classification",
                             num_outputs=3, freeze_trunk=True)
    unfrozen_task = TaskConfig(kind="sequence_regression",
                               num_outputs=1, freeze_trunk=False)
    frozen_id = registry.save(
        jax.tree.map(np.asarray,
                     ft_model.head_init(jax.random.PRNGKey(1), cfg.model,
                                        frozen_task)),
        frozen_task, resident_fp, name="frozen")
    unfrozen_id = registry.save(
        jax.tree.map(np.asarray,
                     ft_model.head_init(jax.random.PRNGKey(2), cfg.model,
                                        unfrozen_task)),
        unfrozen_task, resident_fp, name="unfrozen")

    def batches_for(head):
        return make_task_batches(8, np.random.default_rng(5),
                                 head.task.kind, head.task.num_outputs,
                                 SEQ_LEN, 4)

    loader = lambda src: {"good": good_params, "bad": bad_params}[src]  # noqa: E731
    replicas = [
        LocalReplica(f"r{i}", params, cfg,
                     os.path.join(outdir, f"replica{i}.events.jsonl"),
                     loader)
        for i in range(3)
    ]
    router_events = os.path.join(outdir, "router.events.jsonl")
    tele = Telemetry(events_path=router_events)
    router = FleetRouter(
        [(r.name, r.url) for r in replicas], telemetry=tele,
        health_interval_s=0.1, health_timeout_s=1.0,
        fail_threshold=2, readmit_threshold=2,
        max_retries=3, backoff_base_s=0.02, backoff_cap_s=0.2,
        retry_budget_ratio=0.5, retry_budget_floor=64,
        request_timeout_s=60.0, cache_size=512,
    ).start()
    httpd = make_fleet_http_server(router, "127.0.0.1", 0)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    threading.Thread(target=httpd.serve_forever, daemon=True,
                     name="router-http").start()

    failures = []
    sent = [0]
    seq_rng = np.random.default_rng(args.seed)
    seq_lock = threading.Lock()

    def _fresh_seq():
        # Unique sequences so no request ever cache-hits: every live
        # 200 must travel the forwarded (mirrorable) path.
        with seq_lock:
            n = int(seq_rng.integers(6, SEQ_LEN - 2))
            return "".join(seq_rng.choice(list(AA), size=n))

    def traffic(n, clients=4):
        """n unique requests over concurrent clients; every reply must
        be 200 or typed-error JSON. Returns the (status, body) list."""
        results = [None] * n
        payloads = []
        for i in range(n):
            seq = _fresh_seq()
            if i % 3 == 2:
                payloads.append(("/v1/predict_go",
                                 {"seq": seq, "top_k": 3}))
            else:
                payloads.append(("/v1/embed", {"seq": seq}))

        def client(w):
            for i in range(w, n, clients):
                path, payload = payloads[i]
                results[i] = _post(base + path, payload)

        threads = [threading.Thread(target=client, args=(w,),
                                    daemon=True)
                   for w in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        sent[0] += n
        for st, body in results:
            if st != 200 and not (isinstance(body, dict)
                                  and "type" in body):
                failures.append(f"untyped client reply (HTTP {st}): "
                                f"{str(body)[:120]}")
        return results

    def wait_for(pred, timeout, what):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if pred():
                return True
        failures.append(f"timed out waiting for {what}")
        return False

    # All replicas admitted before any traffic.
    wait_for(lambda: all(r["state"] in ("up", "degraded")
                         for r in router.replica_status()),
             30, "replica admission")

    # -------------------------------------------------- baseline probes
    probe_seqs = ["".join(seq_rng.choice(list(AA), size=20))
                  for _ in range(4)]
    baseline = []
    for s in probe_seqs:
        st, body = _post(base + "/v1/embed", {"seq": s})
        sent[0] += 1
        if st != 200:
            failures.append(f"baseline probe failed: HTTP {st}")
        baseline.append(body)

    def drive_until_terminal(ctl, timeout, what):
        deadline = time.monotonic() + timeout
        while not ctl.terminal() and time.monotonic() < deadline:
            traffic(8)
            time.sleep(0.02)
        if not ctl.terminal():
            failures.append(f"rollout never terminated during {what} "
                            f"(state {ctl.state!r})")

    # ------------------------------------------- phase 1: bad candidate
    spy_bad = SpyTele(tele)
    ctl_bad = RolloutController(
        router, telemetry=spy_bad, source="bad", sample_every=1,
        window_requests=4, windows_required=2, shadow_parity_max=1e-3,
        slo_burn_delta_max=5.0, auto_promote=True)
    router.attach_rollout(ctl_bad)
    ctl_bad.start()
    for r in replicas:
        cand = r.server.rollout_status()["candidate_fingerprint"]
        if cand != bad_fp:
            failures.append(f"{r.name}: bad candidate not loaded "
                            f"(fingerprint {cand})")
    drive_until_terminal(ctl_bad, 120, "the degraded rollout")
    if ctl_bad.state != "refused":
        failures.append(f"degraded candidate ended {ctl_bad.state!r}, "
                        "want 'refused'")
    shadow_n = sum(r.server.rollout_status()["shadow_requests"]
                   for r in replicas)
    if shadow_n < 8:
        failures.append(f"only {shadow_n} shadow requests ran during "
                        "the degraded rollout (want >= 8)")
    for r in replicas:
        st = r.server.rollout_status()
        if st["candidate_fingerprint"] is not None:
            failures.append(f"{r.name}: refused candidate not unloaded")
        if r.server.trunk_fp() != resident_fp:
            failures.append(f"{r.name}: resident trunk changed during "
                            "a refused rollout")
    # Shadow invisibility: live numerics stayed the resident trunk's.
    for s, base_body in zip(probe_seqs, baseline):
        st, body = _post(base + "/v1/embed", {"seq": s})
        sent[0] += 1
        if st != 200 or parity_delta(base_body, body) != 0.0:
            failures.append("live response drifted during the degraded "
                            "rollout — shadow traffic was not invisible")
            break

    # ------------------------- phase 2: good candidate, mid-flip crash
    gate = HeadsEvalGate(registry, cfg.model, batches_for,
                         params, good_params, resident_fp, good_fp,
                         telemetry=tele)
    spy = SpyTele(tele)
    ctl = RolloutController(
        router, telemetry=spy, source="good", sample_every=1,
        window_requests=4, windows_required=2, shadow_parity_max=0.1,
        slo_burn_delta_max=5.0, heads_eval_drop_max=0.2,
        heads_eval=gate, auto_promote=True)
    victim = replicas[-1]
    killed = []

    def pre_flip(name):
        # The chaos seam: SIGKILL-equivalent on the victim IMMEDIATELY
        # before its flip verb — the flip must fail on it, land on the
        # survivors, and the fleet must converge via the health plane.
        if name == victim.name and not killed:
            killed.append(name)
            victim.kill()

    ctl._pre_flip_hook = pre_flip
    router.attach_rollout(ctl)
    ctl.start()
    drive_until_terminal(ctl, 300, "the good rollout")
    survivors = [r for r in replicas if r is not victim]
    if ctl.state != "promoted":
        failures.append(f"good candidate ended {ctl.state!r}, "
                        "want 'promoted'")
    else:
        if killed != [victim.name]:
            failures.append("the pre-flip kill never fired — the "
                            "mid-flip crash path was not exercised")
        if sorted(ctl.flipped) != sorted(r.name for r in survivors):
            failures.append(f"flipped {ctl.flipped}, want exactly the "
                            f"survivors {[r.name for r in survivors]}")
        if ctl._flip_seconds is None:
            failures.append("promotion recorded no flip_seconds")
    for r in survivors:
        if r.server.trunk_fp() != good_fp:
            failures.append(f"{r.name}: resident fingerprint is not "
                            "the candidate's after the flip")
    # Head migration: frozen re-pinned with an audit record, unfrozen
    # refused (typed) and left on the old trunk.
    frozen_meta = registry._read_meta(frozen_id)
    if frozen_meta["trunk_fingerprint"] != good_fp:
        failures.append("frozen head was not re-pinned on promotion")
    if len(frozen_meta.get("migrations") or []) != 1:
        failures.append("frozen head migration left no audit record")
    if registry._read_meta(unfrozen_id)["trunk_fingerprint"] \
            != resident_fp:
        failures.append("unfrozen head was re-pinned — the typed "
                        "refusal did not hold")
    if [r["head_id"] for r in gate.refused] != [unfrozen_id]:
        failures.append(f"migration refusals {gate.refused} do not "
                        "name exactly the unfrozen head")
    # Fleet convergence: victim dead (not mixed), survivors coherent on
    # the candidate fingerprint.
    wait_for(lambda: {r["name"]: r["state"]
                      for r in router.replica_status()}[victim.name]
             == "dead", 15, "the killed replica to be marked dead")
    survivor_names = {r.name for r in survivors}
    wait_for(lambda: router.fingerprint_status()["fleet_state"]
             == "coherent"
             and all(fp == good_fp for name, fp in
                     router.fingerprint_status()["fingerprints"]
                     .items() if name in survivor_names),
             15, "post-flip fingerprint coherence")
    traffic(8)  # the flipped fleet still serves

    # --------------------------------- phase 3: breach → instant rollback
    ctl.breach(reason="drill_breach")
    if ctl.state != "rolled_back":
        failures.append(f"breach ended {ctl.state!r}, want "
                        "'rolled_back'")
    frozen_meta = registry._read_meta(frozen_id)
    if frozen_meta["trunk_fingerprint"] != resident_fp:
        failures.append("rollback did not restore the frozen head's "
                        "trunk pin")
    if len(frozen_meta.get("migrations") or []) != 2:
        failures.append("rollback re-pin left no audit record")
    for r in survivors:
        if r.server.trunk_fp() != resident_fp:
            failures.append(f"{r.name}: rollback did not restore the "
                            "resident fingerprint")
    # The headline numerics gate: post-rollback responses BIT-IDENTICAL
    # to the pre-rollout baseline (parked-trunk restoration).
    rollback_parity = 0.0
    for s, base_body in zip(probe_seqs, baseline):
        st, body = _post(base + "/v1/embed", {"seq": s})
        sent[0] += 1
        delta = parity_delta(base_body, body) if st == 200 else math.inf
        rollback_parity = max(rollback_parity, delta)
    if rollback_parity != 0.0:
        failures.append(f"rollback numerics are NOT bit-identical to "
                        f"the baseline (parity {rollback_parity})")

    # ------------------------------------------------ teardown + audits
    finite = [p for p in spy.parities if math.isfinite(p)]
    if not finite:
        failures.append("the good rollout produced no finite shadow "
                        "parity sample")

    httpd.shutdown()
    httpd.server_close()
    router.drain()
    for r in replicas:
        r.drain()
    tele.close()

    stats = router.stats()
    if stats["accepted"] != stats["sealed"]:
        failures.append(f"router accepted {stats['accepted']} != "
                        f"sealed {stats['sealed']}")
    if stats["accepted"] != sent[0]:
        failures.append(f"router accepted {stats['accepted']} != "
                        f"{sent[0]} client calls — shadow traffic "
                        "leaked into the seal funnel")

    rrecs = read_events(router_events, strict=True)
    states = [r["state"] for r in rrecs if r["event"] == "rollout_state"]
    for want in ("shadowing", "refused", "promoting", "promoted",
                 "rolled_back"):
        if want not in states:
            failures.append(f"no rollout_state{{state={want}}} on the "
                            "router stream")
    windows = [r for r in rrecs if r["event"] == "rollout_window"]
    verdicts = {r["verdict"] for r in windows}
    if not {"pass", "fail"} <= verdicts:
        failures.append(f"rollout windows never recorded both verdicts "
                        f"(saw {sorted(verdicts)})")
    shadows = [r for r in rrecs if r["event"] == "rollout_shadow"]
    if len(shadows) < 16:
        failures.append(f"only {len(shadows)} rollout_shadow events "
                        "(want >= 16 across both rollouts)")
    sealed_ids = {r.get("trace_id") or r.get("request_id")
                  for r in rrecs if r["event"] == "fleet_request"}
    orphan = [r["trace_id"] for r in shadows
              if r["trace_id"] not in sealed_ids]
    if orphan:
        failures.append(f"shadow events reference unsealed traces: "
                        f"{orphan[:5]}")

    collector = FleetCollector({"router": router_events})
    for r in replicas:
        collector.add_source(r.name, r.events_path)
    merged_path = os.path.join(outdir, "merged.events.jsonl")
    merged_n = collector.write(merged_path)
    merged = read_events(merged_path, strict=True)
    for i, rec in enumerate(merged):
        try:
            validate_record(rec)
        except ValueError as e:
            failures.append(f"merged stream schema break at record "
                            f"{i}: {e}")
            break
    viol = FleetCollector.seal_violations(merged)
    if viol:
        failures.append(f"exactly-once sealing broke: "
                        f"{dict(list(viol.items())[:5])}")
    fsum = summarize_fleet(merged)
    if fsum["attempt_mismatches"]:
        failures.append(f"attempts != retries + 1 for traces "
                        f"{fsum['attempt_mismatches'][:5]} — shadows "
                        "contaminated the attempt plane")
    flips = [r for r in merged if r["event"] == "rollout_flip"]
    flip_phases = [r["phase"] for r in flips]
    if flip_phases.count("flip") != len(survivors) \
            or flip_phases.count("rollback") != len(survivors):
        failures.append(f"rollout_flip events {flip_phases} do not "
                        f"match {len(survivors)} flips + rollbacks")

    summary = {
        "client_calls": sent[0],
        "router": {k: stats[k] for k in
                   ("accepted", "sealed", "outcomes", "retries_spent")},
        "bad_rollout_state": ctl_bad.state,
        "good_rollout_state": ctl.state,
        "victim": victim.name,
        "flipped": sorted(ctl.flipped),
        "flip_seconds": ctl._flip_seconds,
        "shadow_events": len(shadows),
        "shadow_parity_max": max(finite, default=None),
        "heads_eval_delta": gate.delta,
        "migrated_then_restored": frozen_id,
        "migration_refused": unfrozen_id,
        "rollback_parity": rollback_parity,
        "merged_records": merged_n,
        "outdir": outdir,
        "failures": failures,
        "ok": not failures,
    }
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--outdir", help="artifact dir (default: temp)")
    ap.add_argument("--json", action="store_true",
                    help="print the summary as one JSON object only")
    args = ap.parse_args(argv)
    summary = run_drill(args)
    if args.json:
        print(json.dumps(summary))
    else:
        print(json.dumps(summary, indent=2))
    if not summary["ok"]:
        print("ROLLOUT DRILL FAILED:", "; ".join(summary["failures"]),
              file=sys.stderr)
        return 1
    print(f"rollout drill OK: degraded candidate refused after "
          f"{summary['shadow_events']} shadows, good candidate "
          f"promoted (flip {summary['flip_seconds']}s, victim "
          f"{summary['victim']} killed mid-flip, survivors "
          f"{summary['flipped']} converged), rollback bit-identical "
          f"(parity {summary['rollback_parity']}); "
          f"{summary['client_calls']} client calls all sealed exactly "
          f"once ({summary['router']['outcomes']})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
