#!/usr/bin/env bash
# Tier-1, then the smokes and drills. The FIRST stage is the gate: the
# pytest command as the driver runs it after every PR (the `commands`
# of /root/TESTS_LAST_RUN.json: six xdist workers, --dist loadfile, a
# 1,470 s cap; passes counted from the junit file). It asserts
# behaviour and counts, never a time or a rate: speed is measured by
# `python -m benchmark.run` on the chip and recorded in
# PERF_LEDGER.jsonl. The driver runs ONLY that first command; the
# stages after it (schema self-test, `pbt check`, smokes, drills) are
# for a local run and repeat, end to end, what tier-1 tests hold piece
# by piece (ROADMAP C14).
#
# --pod64: ALSO run the opt-in 64-virtual-device pod-shape tier
# (tests/test_parallel64.py) after the tier-1 suite. It is slow-marked
# and env-gated, so the tier-1 pass itself is byte-identical with or
# without the flag; the pod tier's pass/fail is OR-ed into the exit
# code but its dots are reported separately (the DOTS_PASSED contract
# counts tier-1 only).
#
# --packed-md: ALSO run the opt-in multi-device PACKED-batch parity
# tier (tests/test_packing.py slow lane, PBT_RUN_PACKED_MD gate — same
# style as --pod64): fresh 8-virtual-device children prove the packed
# sharding rules (segment_ids like tokens) under plain DP+fsdp and the
# ZeRO-1 zero-update.
set -o pipefail

# Per-stage wall-time accounting (ISSUE 19 satellite): each stage calls
# mark_stage <name> when it finishes; the one-line summary printed at
# exit makes "which stage ate the tier-1 budget" a grep, not a rerun
# (the tier-1 timeout is host-bound — see ROADMAP).
STAGE_SUMMARY=""
stage_t0=$SECONDS
mark_stage() {
  local now=$SECONDS
  STAGE_SUMMARY="$STAGE_SUMMARY $1=$((now - stage_t0))s"
  stage_t0=$now
}

POD64=0
PACKED_MD=0
for arg in "$@"; do
  case "$arg" in
    --pod64) POD64=1 ;;
    --packed-md) PACKED_MD=1 ;;
    *) echo "unknown flag: $arg (supported: --pod64, --packed-md)" >&2; exit 2 ;;
  esac
done

rm -rf /tmp/_t1.log /tmp/_t1.xml
timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}')
echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)}
echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null)
mark_stage pytest

# Events-schema validator self-test (ISSUE 3 satellite): every telemetry
# event type must round-trip the validator, and garbage must be
# rejected. --schema-sync (ISSUE 15) additionally asserts the negative
# suite covers every event type, so a new event cannot ship without a
# validator negative. Stdlib-only (<2 s, no jax) — runs even when the
# pytest tier timed out, and its failure fails the gate.
echo "=== telemetry events-schema validator self-test + schema-sync ==="
python "$(dirname "$0")/validate_events.py" --self-test --schema-sync
rcv=$?
mark_stage events_schema
[ "$rc" -eq 0 ] && rc=$rcv

# Project-invariant static analyzer (ISSUE 15 tentpole): six AST rules
# (jit purity, lock discipline, durability protocol, event-schema call
# sites, obs-doc drift, dead exports) over the whole tree, GATED — a
# non-baselined finding fails tier-1. Pure python, no jax import
# (tools/pbt_check.py stub-imports the analysis package past the jax-
# importing package root). docs/analysis.md is the rule catalog +
# suppression format; tools/check_baseline.json holds the suppressions.
echo "=== pbt check (project-invariant static analyzer, gated) ==="
timeout -k 10 120 python "$(dirname "$0")/pbt_check.py"
rcc=$?
mark_stage pbt_check
[ "$rc" -eq 0 ] && rc=$rcc

# Pipeline smoke (ISSUE 19 satellite): the pipelined-dispatch window on
# an in-process depth-1 vs depth-2 server pair. GATED: overlap observed
# (inflight_max >= 2, the serve_inflight_batches high-water mark),
# async-vs-sync BIT-parity on a deterministically formed batch, and
# exactly-once seals with schema-valid event streams on both arms.
echo "=== pipeline smoke (pipelined dispatch window, CPU) ==="
timeout -k 10 300 python "$(dirname "$0")/pipeline_smoke.py"
rcpl=$?
mark_stage pipeline_smoke
[ "$rc" -eq 0 ] && rc=$rcpl

# Packed attention smoke (ISSUE 13): the ragged Pallas attention
# kernel and the tiled-segment fused block through their real dispatch
# entries on tiny shapes. GATED: packed/dense/serving-real_mask parity
# within the documented 1e-5 jitted tolerance, custom-VJP gradient
# parity, supported shapes take the Pallas path with ZERO
# reason=segments fallbacks (attention AND the C=1024 tiled segment
# fused block), and PBT_FORCE_REFERENCE_KERNEL routes attention onto
# the reference path.
echo "=== packed attention smoke (Pallas attention + tiled segment, CPU) ==="
timeout -k 10 420 python "$(dirname "$0")/attn_smoke.py"
rca=$?
mark_stage attn_smoke
[ "$rc" -eq 0 ] && rc=$rca

# One-pass trunk smoke (ISSUE 16 tentpole): the whole block pass —
# local conv track + ragged attention — as ONE VMEM-resident Pallas
# grid program through the real dispatch entries. GATED: packed/dense/
# serving-real_mask BIT-identity vs the two-kernel composition, exactly
# one pallas_call boundary in the one-pass trace (the HBM round-trip
# is eliminated, not just faster), custom-VJP gradient parity, the
# PBT_FORCE_REFERENCE_KERNEL override, and int8 in-kernel dequant
# bit-matching the HLO dequant.
echo "=== one-pass trunk smoke (fused block pass + int8 dequant, CPU) ==="
timeout -k 10 420 python "$(dirname "$0")/onepass_smoke.py"
rco=$?
mark_stage onepass_smoke
[ "$rc" -eq 0 ] && rc=$rco

# Reshard smoke (ISSUE 11): save a tiny ZeRO-1 train state on a 4x2
# CPU-virtual mesh, reshard 4x2 -> 8x1 -> 1 -> 4x2 through the real
# reshard verb. GATED: byte-identical round-trip parity (params + Adam
# moments), collective wire bytes counted on the same-device-set leg
# (honest host_staged on the cross-set legs), schema-valid `reshard`
# events.
echo "=== reshard smoke (mesh-agnostic checkpoint resharding, CPU) ==="
timeout -k 10 300 python "$(dirname "$0")/reshard_smoke.py"
rcre=$?
mark_stage reshard_smoke
[ "$rc" -eq 0 ] && rc=$rcre

# Fleet drill smoke (ISSUE 11): 3 in-process serve replicas behind the
# FleetRouter, one KILLED mid-request under concurrent load (latency
# spike first so requests are genuinely in flight), torn health on
# another. GATED: every accepted request seals exactly once (served or
# typed-rejected, none lost), failover observed (retried_ok >= 1, dead
# + re-admitted on the record), router/replica events schema-valid.
echo "=== fleet drill smoke (kill one of three replicas under load) ==="
timeout -k 10 420 python "$(dirname "$0")/fleet_drill.py" --json \
  --replicas 3 --requests 48 --clients 8
rcfd=$?
mark_stage fleet_drill
[ "$rc" -eq 0 ] && rc=$rcfd

# Rollout drill smoke (ISSUE 20): the blue-green trunk lifecycle on 3
# in-process replicas behind the FleetRouter — a deliberately-degraded
# candidate (the parity gate must refuse it, shadow traffic invisible),
# then a good one (gates green → atomic flip with one replica KILLED
# immediately before its flip verb — fleet must converge with zero
# lost requests and exactly-once sealing), then a forced breach
# (rollback bit-identical to the pre-rollout baseline, head pins
# restored). GATED: all of the above + schema-valid rollout_* events.
echo "=== rollout drill smoke (shadow → gate → flip → rollback, CPU) ==="
timeout -k 10 420 python "$(dirname "$0")/rollout_drill.py" --json
rcro=$?
mark_stage rollout_drill
[ "$rc" -eq 0 ] && rc=$rcro

# Map drill smoke (ISSUE 14): kill-anywhere offline inference through
# real `pbt map` subprocesses — SIGKILL between a block's object write
# and its cursor advance, a torn cursor, a torn block object, one
# poisoned record, and an injected transient dispatch failure. GATED:
# the resumed store is byte-identical to an uninterrupted control,
# re-work <= 1 block per shard, quarantined == injected poison,
# `pbt map --verify` detects a flipped byte (typed) and a deleted
# block (hole), all events schema-valid.
echo "=== map drill smoke (SIGKILL + torn artifacts, resume, verify) ==="
timeout -k 10 480 python "$(dirname "$0")/map_drill.py" --json
rcmd=$?
mark_stage map_drill
[ "$rc" -eq 0 ] && rc=$rcmd

# Index drill smoke (ISSUE 17): kill-anywhere ANN index construction
# through real `pbt index` subprocesses over a synthetic store —
# SIGKILL between an index block's object write and its cursor advance,
# then resume. GATED: the resumed index is byte-identical to an
# uninterrupted control (digests + object bytes + index_identity),
# re-work <= 1 block per shard, `pbt index --verify` detects a flipped
# byte (typed digest_mismatch) and a deleted object (hole), a rebuild
# against a different store is a typed refusal BEFORE any write, all
# events schema-valid. Store is hand-written through commit_block (no
# model forward) — seconds, not minutes.
echo "=== index drill smoke (SIGKILL mid-build, resume, verify) ==="
timeout -k 10 300 python "$(dirname "$0")/index_drill.py" --json
rcid=$?
mark_stage index_drill
[ "$rc" -eq 0 ] && rc=$rcid

# Quant smoke (ISSUE 12): tiny int8 ZeRO-1 steps on the 4x2 CPU-virtual
# mesh vs the replicated fp32 reference + the quantized serve arm.
# GATED: step-1 loss identity, param deviation within the documented
# quantization bounds (fp32-payload control isolates harness error),
# int8 determinism, int8 grad-reduction wire bytes <= 0.30x the fp32
# reduce-scatter FROM COMPILED HLO, serve-arm parity + weight-bytes
# ratio, schema-valid quant-tagged events.
echo "=== quant smoke (int8 reduce-scatter + int8 serve arm, CPU) ==="
timeout -k 10 420 python "$(dirname "$0")/quant_smoke.py"
rcq=$?
mark_stage quant_smoke
[ "$rc" -eq 0 ] && rc=$rcq

if [ "$PACKED_MD" = "1" ]; then
  echo "=== packed multi-device parity tier (8 virtual devices, opt-in) ==="
  timeout -k 10 900 env JAX_PLATFORMS=cpu PBT_RUN_PACKED_MD=1 \
    python -m pytest tests/test_packing.py -q -m 'slow' \
    -p no:cacheprovider -p no:xdist -p no:randomly
  rcp=$?
  mark_stage packed_md
  [ "$rc" -eq 0 ] && rc=$rcp
fi

if [ "$POD64" = "1" ]; then
  echo "=== pod64 tier (64 virtual devices, opt-in) ==="
  timeout -k 10 2700 env JAX_PLATFORMS=cpu PBT_RUN_TIER64=1 \
    python -m pytest tests/test_parallel64.py -q -m 'tier64' \
    -p no:cacheprovider -p no:xdist -p no:randomly
  rc64=$?
  mark_stage pod64
  [ "$rc" -eq 0 ] && rc=$rc64
fi

echo "STAGE_WALL_TIMES:${STAGE_SUMMARY} total=${SECONDS}s"
exit $rc
