#!/usr/bin/env python
"""Telemetry events-schema validator (CI/tooling satellite, ISSUE 3).

Validates an events JSONL (every line against obs.events.validate_record,
plus per-stream seq monotonicity) and, optionally, a flight-recorder
dump. `--self-test` round-trips one synthetic record of EVERY event type
through the validator — and asserts a deliberately broken record fails —
so a schema/fixture drift breaks CI immediately; tools/run_tier1.sh runs
it after the pytest tier.

No jax import (the obs package is stdlib-only): artifacts validate on
any machine.

Usage:
  python tools/validate_events.py events.jsonl [--flight flight_123.json]
  python tools/validate_events.py --self-test
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from proteinbert_tpu.obs.events import (  # noqa: E402
    EVENT_FIELDS, make_example, validate_record,
)
from proteinbert_tpu.obs.flight import validate_flight_dump  # noqa: E402


# Negative control: records the validator MUST reject, at least one
# per event type in EVENT_FIELDS (the --schema-sync mode asserts that
# coverage — a new event type cannot ship without a validator
# negative). Module-level so self_test and schema_sync share one list.
NEGATIVE_CASES = [
        {"v": 99, "event": "step", "seq": 0, "t": 0.0,
         "step": 1, "metrics": {}},
        {"v": 1, "event": "run_start", "seq": 0, "t": 0.0,
         "config": {}, "jax_version": "0.0.0"},  # missing pid
        {"v": 1, "event": "eval", "seq": 0, "t": 0.0,
         "step": -1, "metrics": {}},  # step must be >= 0
        {"v": 1, "event": "requeue", "seq": 0, "t": 0.0,
         "step": 1},  # missing reason
        {"v": 1, "event": "nan_halt", "seq": 0, "t": 0.0,
         "step": 1},  # missing metrics
        {"v": 1, "event": "serve_start", "seq": 0, "t": 0.0,
         "config": {}},  # missing pid
        {"v": 1, "event": "serve_end", "seq": 0, "t": 0.0,
         "outcome": "collapsed", "stats": {}},  # outcome drained|aborted
        {"v": 1, "event": "no_such_event", "seq": 0, "t": 0.0},
        {"v": 1, "event": "step", "seq": 0, "t": 0.0},  # missing fields
        {"v": 1, "event": "ckpt_stage", "seq": 0, "t": 0.0,
         "step": 1, "phase": "bogus"},
        {"v": 1, "event": "run_end", "seq": -1, "t": 0.0,
         "outcome": "completed", "perf": {}},
        # serve tracing / SLO types (ISSUE 6):
        {"v": 1, "event": "serve_request", "seq": 0, "t": 0.0,
         "kind": "embed", "outcome": "vanished", "request_id": "r1",
         "stages": {}},
        {"v": 1, "event": "serve_request", "seq": 0, "t": 0.0,
         "kind": "embed", "outcome": "ok", "request_id": "r1",
         "stages": {"queue": -0.5}},
        {"v": 1, "event": "serve_reject", "seq": 0, "t": 0.0,
         "reason": "queue_full", "queue_depth": -3},
        {"v": 1, "event": "slo_breach", "seq": 0, "t": 0.0,
         "objective": "latency_e2e"},  # missing burn_rate
        {"v": 1, "event": "slo_breach", "seq": 0, "t": 0.0,
         "objective": "latency_e2e", "burn_rate": float("nan")},
        # multi-tenant head registry (ISSUE 8):
        {"v": 1, "event": "head_registered", "seq": 0, "t": 0.0,
         "kind": "token_classification"},  # missing head_id
        {"v": 1, "event": "head_eval", "seq": 0, "t": 0.0,
         "head_id": "a1b2", "metrics": {"score": [0.5]}},  # non-scalar
        {"v": 1, "event": "serve_request", "seq": 0, "t": 0.0,
         "kind": "predict_task", "outcome": "ok", "request_id": "r1",
         "stages": {}, "head_id": 17},  # head_id must be a string
        {"v": 1, "event": "serve_reject", "seq": 0, "t": 0.0,
         "reason": "no_such_reason"},  # unknown_head is valid; this isn't
        # ragged packed serving (ISSUE 9): packed fields are optional
        # but TYPED — a writer bug must not slip through as "extra".
        {"v": 1, "event": "serve_batch", "seq": 0, "t": 0.0,
         "kind": "embed", "bucket_len": 256, "rows": 4,
         "segments": -1},  # segments must be >= 0
        {"v": 1, "event": "serve_batch", "seq": 0, "t": 0.0,
         "kind": "embed", "bucket_len": 256, "rows": 4,
         "mode": "bogus"},  # mode is bucketed|ragged
        {"v": 1, "event": "serve_batch", "seq": 0, "t": 0.0,
         "kind": "embed", "bucket_len": 256, "rows": 4,
         "pad_fraction": 1.5},  # pad_fraction in [0, 1]
        {"v": 1, "event": "serve_request", "seq": 0, "t": 0.0,
         "kind": "embed", "outcome": "ok", "request_id": "r1",
         "stages": {}, "segments_per_row": -2.0},  # must be >= 0
        {"v": 1, "event": "serve_request", "seq": 0, "t": 0.0,
         "kind": "embed", "outcome": "ok", "request_id": "r1",
         "stages": {}, "mode": "packed"},  # not a serve mode
        {"v": 1, "event": "serve_request", "seq": 0, "t": 0.0,
         "kind": "embed", "outcome": "ok", "request_id": "r1",
         "stages": {}, "batch": "7"},  # the batch's sequence number: int
        # elastic topology (ISSUE 11): reshard + fleet events.
        {"v": 1, "event": "reshard", "seq": 0, "t": 0.0,
         "step": 1, "target_mesh": {"data": 4}},  # missing wire_bytes
        {"v": 1, "event": "reshard", "seq": 0, "t": 0.0,
         "step": 1, "target_mesh": {"data": 4},
         "wire_bytes": {"total": -8}},  # bytes must be >= 0
        {"v": 1, "event": "reshard", "seq": 0, "t": 0.0,
         "step": 1, "target_mesh": {"data": 4},
         "wire_bytes": {"total": 1.5}},  # bytes are ints, not floats
        {"v": 1, "event": "fleet_replica", "seq": 0, "t": 0.0,
         "replica": "r0", "state": "limping"},  # unknown state
        {"v": 1, "event": "fleet_request", "seq": 0, "t": 0.0,
         "outcome": "vanished", "path": "/v1/embed"},  # unknown outcome
        {"v": 1, "event": "fleet_request", "seq": 0, "t": 0.0,
         "outcome": "ok", "path": "/v1/embed",
         "retries": -1},  # retries must be >= 0
        {"v": 1, "event": "fleet_request", "seq": 0, "t": 0.0,
         "outcome": "ok", "path": "/v1/embed",
         "status": 42},  # not an HTTP status code
        {"v": 1, "event": "fleet_request", "seq": 0, "t": 0.0,
         "outcome": "ok"},  # missing path
        {"v": 1, "event": "fleet_end", "seq": 0, "t": 0.0,
         "outcome": "collapsed", "stats": {}},  # outcome is drained|aborted
        {"v": 1, "event": "fleet_start", "seq": 0, "t": 0.0,
         "config": {}},  # missing pid
        # quantized serving arm (ISSUE 12): optional but TYPED fields.
        {"v": 1, "event": "serve_request", "seq": 0, "t": 0.0,
         "kind": "embed", "outcome": "ok", "request_id": "r1",
         "stages": {}, "quant": "int4"},  # not a quant mode
        {"v": 1, "event": "serve_batch", "seq": 0, "t": 0.0,
         "kind": "embed", "bucket_len": 256, "rows": 4,
         "quant": "quantized"},  # not a quant mode
        {"v": 1, "event": "serve_batch", "seq": 0, "t": 0.0,
         "kind": "embed", "bucket_len": 256, "rows": 4,
         "quant": "int8", "quant_parity_max": -0.5},  # must be >= 0
        {"v": 1, "event": "serve_request", "seq": 0, "t": 0.0,
         "kind": "embed", "outcome": "ok", "request_id": "r1",
         "stages": {}, "quant_parity_max": float("inf")},  # finite
        # offline batch inference (ISSUE 14): map_* rows are typed —
        # the chaos drill audits streams with this validator, so a
        # writer bug must fail here, not corrupt the drill's verdict.
        {"v": 1, "event": "map_start", "seq": 0, "t": 0.0,
         "config": {"num_shards": 2}},  # missing pid
        {"v": 1, "event": "map_shard", "seq": 0, "t": 0.0,
         "shard": 0, "state": "crawling"},  # unknown shard state
        {"v": 1, "event": "map_shard", "seq": 0, "t": 0.0,
         "shard": -1, "state": "start"},  # shard must be >= 0
        {"v": 1, "event": "map_block", "seq": 0, "t": 0.0,
         "shard": 0, "block": 0, "digest": "xyz",
         "n": 8},  # digest must be a sha256 hex
        {"v": 1, "event": "map_block", "seq": 0, "t": 0.0,
         "shard": 0, "block": 0, "digest": "0" * 64,
         "n": 8, "retries": -2},  # retries must be >= 0
        {"v": 1, "event": "map_block", "seq": 0, "t": 0.0,
         "shard": 0, "block": 0, "digest": "0" * 64, "n": 8,
         "seqs_per_s": float("inf")},  # finite when present
        {"v": 1, "event": "map_end", "seq": 0, "t": 0.0,
         "outcome": "vanished", "stats": {}},  # unknown outcome
        # the checkpointer's restore_fallback note: bad_step required,
        # landed_step (ISSUE 14 satellite) typed when present.
        {"v": 1, "event": "note", "seq": 0, "t": 0.0,
         "source": "checkpoint", "kind": "restore_fallback"},  # no step
        {"v": 1, "event": "note", "seq": 0, "t": 0.0,
         "source": "checkpoint", "kind": "restore_fallback",
         "bad_step": 3, "landed_step": -2},  # landed_step >= 0
        {"v": 1, "event": "note", "seq": 0, "t": 0.0,
         "source": "checkpoint", "kind": "restore_fallback",
         "bad_step": 3, "landed_step": 2.5},  # landed_step is an int
        # the ANN index + /v1/neighbors subsystem (ISSUE 17): build
        # lifecycle, shard durability, and served-lookup rows are
        # typed — the index drill audits streams with this validator.
        {"v": 1, "event": "index_build", "seq": 0, "t": 0.0,
         "state": "running", "stats": {}},  # unknown build state
        {"v": 1, "event": "index_build", "seq": 0, "t": 0.0,
         "state": "completed"},  # missing stats
        {"v": 1, "event": "index_shard", "seq": 0, "t": 0.0,
         "shard": 0, "state": "crawling"},  # unknown shard state
        {"v": 1, "event": "index_shard", "seq": 0, "t": 0.0,
         "shard": -1, "state": "start"},  # shard must be >= 0
        {"v": 1, "event": "index_shard", "seq": 0, "t": 0.0,
         "shard": 0, "state": "resume",
         "tail_reworked": -1},  # rework count must be >= 0
        {"v": 1, "event": "neighbor_query", "seq": 0, "t": 0.0,
         "k": 0, "nprobe": 8},  # k must be >= 1
        {"v": 1, "event": "neighbor_query", "seq": 0, "t": 0.0,
         "k": 10, "nprobe": 8,
         "lookup_s": -0.001},  # lookup leg must be >= 0
        {"v": 1, "event": "neighbor_query", "seq": 0, "t": 0.0,
         "k": 10, "nprobe": 8,
         "outcome": "vanished"},  # not a request outcome
        # fleet-scope causal tracing (ISSUE 18): the propagated trace
        # context is optional but TYPED on every carrier event, and
        # fleet_attempt (one sibling record per router try) is fully
        # constrained — the fleet drill audits the MERGED stream with
        # this validator, so a propagation bug must fail here.
        {"v": 1, "event": "serve_request", "seq": 0, "t": 0.0,
         "kind": "embed", "outcome": "ok", "request_id": "r1",
         "stages": {}, "trace_id": 17},  # trace_id must be a string
        {"v": 1, "event": "serve_request", "seq": 0, "t": 0.0,
         "kind": "embed", "outcome": "ok", "request_id": "r1",
         "stages": {}, "replica_id": 0},  # replica_id must be a string
        {"v": 1, "event": "serve_batch", "seq": 0, "t": 0.0,
         "kind": "embed", "bucket_len": 256, "rows": 4,
         "replica_id": ["r0"]},  # replica_id must be a string
        {"v": 1, "event": "fleet_request", "seq": 0, "t": 0.0,
         "outcome": "ok", "path": "/v1/embed",
         "trace_id": 3.5},  # trace_id must be a string
        {"v": 1, "event": "fleet_attempt", "seq": 0, "t": 0.0,
         "trace_id": "f1-1", "attempt": 0, "replica": "r0",
         "outcome": "vanished"},  # not an attempt outcome
        {"v": 1, "event": "fleet_attempt", "seq": 0, "t": 0.0,
         "trace_id": "f1-1", "attempt": -1, "replica": "r0",
         "outcome": "ok"},  # attempt index must be >= 0
        {"v": 1, "event": "fleet_attempt", "seq": 0, "t": 0.0,
         "trace_id": "f1-1", "attempt": 0, "replica": "r0",
         "outcome": "retryable", "status": 42},  # not an HTTP status
        {"v": 1, "event": "fleet_attempt", "seq": 0, "t": 0.0,
         "trace_id": "f1-1", "attempt": 0, "replica": "r0",
         "outcome": "retryable", "backoff_s": -0.02},  # wait >= 0
        {"v": 1, "event": "fleet_attempt", "seq": 0, "t": 0.0,
         "trace_id": 99, "attempt": 0, "replica": "r0",
         "outcome": "ok"},  # trace_id must be a string
        # blue-green trunk rollout (ISSUE 20): lifecycle, window
        # verdicts, shadow siblings, flips, and fleet coherence are
        # typed — the rollout drill audits the merged stream with this
        # validator, so a controller bug must fail here.
        {"v": 1, "event": "rollout_state", "seq": 0, "t": 0.0,
         "state": "sideways"},  # unknown rollout state
        {"v": 1, "event": "rollout_state", "seq": 0, "t": 0.0,
         "state": "promoted", "windows_green": -1},  # streak >= 0
        {"v": 1, "event": "rollout_state", "seq": 0, "t": 0.0,
         "state": "promoted",
         "flip_seconds": float("inf")},  # finite when present
        {"v": 1, "event": "rollout_window", "seq": 0, "t": 0.0,
         "window": 0, "verdict": "maybe"},  # verdict is pass|fail
        {"v": 1, "event": "rollout_window", "seq": 0, "t": 0.0,
         "window": -1, "verdict": "pass"},  # window index >= 0
        {"v": 1, "event": "rollout_window", "seq": 0, "t": 0.0,
         "window": 0, "verdict": "pass",
         "parity_max": -0.5},  # parity must be >= 0
        {"v": 1, "event": "rollout_window", "seq": 0, "t": 0.0,
         "window": 0, "verdict": "fail",
         "slo_burn_delta": float("nan")},  # finite when present
        {"v": 1, "event": "rollout_shadow", "seq": 0, "t": 0.0,
         "trace_id": "f1-1", "replica": "r0", "outcome": "ok",
         "shadow": False},  # a shadow record MUST flag shadow=true
        {"v": 1, "event": "rollout_shadow", "seq": 0, "t": 0.0,
         "trace_id": "f1-1", "replica": "r0", "outcome": "mirrored",
         "shadow": True},  # outcome is ok|failed
        {"v": 1, "event": "rollout_shadow", "seq": 0, "t": 0.0,
         "trace_id": "f1-1", "replica": "r0", "outcome": "failed",
         "shadow": True, "status": 42},  # HTTP status or 0
        {"v": 1, "event": "rollout_flip", "seq": 0, "t": 0.0,
         "replica": "r0", "phase": "sideways",
         "seconds": 0.01},  # phase is flip|rollback
        {"v": 1, "event": "rollout_flip", "seq": 0, "t": 0.0,
         "replica": "r0", "phase": "flip",
         "seconds": -0.5},  # swap latency must be >= 0
        {"v": 1, "event": "rollout_fleet", "seq": 0, "t": 0.0,
         "state": "mixed"},  # state is coherent|degraded
        {"v": 1, "event": "rollout_fleet", "seq": 0, "t": 0.0,
         "state": "degraded", "fingerprints": -2},  # count >= 0
]


def self_test() -> int:
    for event in sorted(EVENT_FIELDS):
        rec = make_example(event)
        try:
            validate_record(rec)
            # And through a JSON round trip, like real consumers see it.
            validate_record(json.loads(json.dumps(rec)))
        except ValueError as e:
            print(f"SELF-TEST FAIL: example {event!r} does not validate: {e}")
            return 1
    for rec in NEGATIVE_CASES:
        try:
            validate_record(rec)
        except ValueError:
            continue
        print(f"SELF-TEST FAIL: accepted invalid record {rec!r}")
        return 1
    print(f"self-test OK: {len(EVENT_FIELDS)} event types round-trip, "
          f"{len(NEGATIVE_CASES)} invalid records rejected")
    return 0


def schema_sync() -> int:
    """--schema-sync (ISSUE 15 satellite): every event type in
    EVENT_FIELDS must have at least one negative case above — adding
    an event without teaching the validator's negative suite what a
    BROKEN record of it looks like fails the `pbt check` tier-1 stage,
    so schema growth and validator coverage move together."""
    covered = {rec.get("event") for rec in NEGATIVE_CASES}
    covered.discard(None)
    missing = sorted(set(EVENT_FIELDS) - covered)
    if missing:
        print("SCHEMA-SYNC FAIL: event type(s) with no negative case "
              f"in tools/validate_events.py: {missing} — add at least "
              "one deliberately-broken record per type")
        return 1
    extra = sorted(c for c in covered
                   if c not in EVENT_FIELDS and c != "no_such_event")
    if extra:
        print(f"SCHEMA-SYNC FAIL: negative cases reference unknown "
              f"event type(s) {extra}")
        return 1
    print(f"schema-sync OK: all {len(EVENT_FIELDS)} event types have "
          "validator negatives")
    return 0


def validate_file(path: str) -> int:
    errors = 0
    count = 0
    last_seq: dict = {}
    with open(path) as f:
        for lineno, line in enumerate(f, start=1):
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except ValueError as e:
                print(f"{path}:{lineno}: not JSON: {e}")
                errors += 1
                continue
            try:
                validate_record(rec)
            except ValueError as e:
                print(f"{path}:{lineno}: {e}")
                errors += 1
                continue
            # seq must be monotonic within one emitting process; seq 0
            # legitimately restarts the stream (a requeued run appends
            # its fresh run_start to the same file).
            prev = last_seq.get("run")
            if prev is not None and rec["seq"] <= prev and rec["seq"] != 0:
                print(f"{path}:{lineno}: seq {rec['seq']} not > previous "
                      f"{prev} (and not a fresh stream)")
                errors += 1
            last_seq["run"] = rec["seq"]
            count += 1
    print(f"{path}: {count} records, {errors} errors")
    return 1 if errors else 0


def validate_flight(path: str) -> int:
    with open(path) as f:
        try:
            payload = json.load(f)
        except ValueError as e:
            print(f"{path}: not JSON: {e}")
            return 1
    try:
        validate_flight_dump(payload)
    except ValueError as e:
        print(f"{path}: invalid flight dump: {e}")
        return 1
    print(f"{path}: valid flight dump ({len(payload['events'])} events, "
          f"reason={payload['reason']!r})")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("events", nargs="?", help="events JSONL to validate")
    ap.add_argument("--flight", help="flight-recorder dump to validate")
    ap.add_argument("--self-test", action="store_true",
                    help="validate the schema fixtures themselves")
    ap.add_argument("--schema-sync", action="store_true",
                    help="assert the negative-case list covers every "
                         "event type in EVENT_FIELDS (the `pbt check` "
                         "stage's coverage gate)")
    args = ap.parse_args(argv)
    if not any((args.events, args.flight, args.self_test,
                args.schema_sync)):
        ap.error("give an events JSONL, --flight, --self-test, or "
                 "--schema-sync")
    # All requested checks COMPOSE — combining --schema-sync with an
    # events file must validate both, never silently skip one.
    rc = 0
    if args.schema_sync:
        rc |= schema_sync()
    if args.self_test:
        rc |= self_test()
    if args.events:
        rc |= validate_file(args.events)
    if args.flight:
        rc |= validate_flight(args.flight)
    return rc


if __name__ == "__main__":
    sys.exit(main())
