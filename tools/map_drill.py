#!/usr/bin/env python
"""Map chaos drill (ISSUE 14): prove `pbt map` loses NOTHING when
killed anywhere.

A seeded corpus (with one deliberately poisoned record) is mapped twice
through REAL `pbt map` subprocesses:

- the CHAOS line: run 1 is SIGKILLed deterministically in the worst
  window (between a block's object write and its cursor advance —
  PBT_MAP_FAULTS crash hook); while it is down the drill TEARS the
  dead run's artifacts the way hostile storage would — shard 0's
  recorded tail block object is truncated mid-file and shard 1's main
  cursor is torn — then run 2 resumes under an injected transient
  dispatch failure (2 retries) and must complete;
- the WINDOW line (ISSUE 19): a run SIGKILLed at the NEW
  `block_fetched` crash point — the pipelined dispatch window where a
  block's device compute AND host fetch have completed but its commit
  (object write + cursor advance) has not happened yet, while the
  NEXT block is already in flight — then one plain resume;
- the CONTROL line: one uninterrupted run over the same corpus into a
  fresh store.

Gates (exit nonzero on violation — tier-1 runs this as a smoke stage):
  - the resumed chaos store is BYTE-IDENTICAL to the control store
    (same (shard, block) → digest map, same object bytes), and so is
    the resumed WINDOW store (a device-complete-but-uncommitted block
    is re-worked, never half-committed);
  - both stores pass `verify_store` complete+ok, and `pbt map
    --verify` (the real CLI) exits 0 on the chaos store;
  - re-work is bounded: map_block events across both chaos runs exceed
    the unique block count by at most ONE block per shard;
  - quarantined count == the ONE injected poison record, in both
    stores, with the typed reason;
  - the injected transient failure was retried (retries observed) and
    still changed nothing;
  - `pbt map --verify` DETECTS a deliberately flipped byte in a block
    (typed digest_mismatch, nonzero exit) and reports a hole when an
    object is deleted;
  - every emitted event validates against the schema (strict reader),
    and `pbt diagnose --map` over the concatenated chaos streams
    reports the same bounded re-work.

Usage:
  python tools/map_drill.py [--outdir DIR] [--json] [--seed N]
      [--corpus N]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(1, os.path.dirname(os.path.abspath(__file__)))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

SEQ_LEN = 48
BUCKETS = "[16,32,48]"
NUM_SHARDS = 2
BLOCK_SIZE = 8
ROWS = 2
MAX_SEGMENTS = 4
AA = "ACDEFGHIKLMNPQRSTVWY"
POISON_INDEX = 5  # lands in shard 0 block 0


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


def _make_run_dir(outdir: str) -> str:
    """A real pretrained-run directory (checkpoint + config.json) for
    the subprocesses' --pretrained."""
    import jax

    from proteinbert_tpu.cli.main import _save_run_config
    from proteinbert_tpu.train import Checkpointer, create_train_state

    cfg = _tiny_cfg()
    rundir = os.path.join(outdir, "run")
    state = create_train_state(jax.random.PRNGKey(0), cfg)
    ck = Checkpointer(rundir, async_save=False)
    ck.save(0, state, {"batches_consumed": 0})
    ck.close()
    _save_run_config(cfg, rundir)
    return rundir


def _make_corpus(outdir: str, n: int, seed: int) -> str:
    import numpy as np

    rng = np.random.default_rng(seed)
    path = os.path.join(outdir, "corpus.tsv")
    with open(path, "w") as f:
        for i in range(n):
            if i == POISON_INDEX:
                # Typed poison: an interior space survives the seqs-file
                # round trip and classifies as invalid_char.
                f.write(f"p{i}\tAC DEFG\n")
                continue
            ln = int(rng.integers(5, 29))
            f.write(f"p{i}\t" + "".join(rng.choice(list(AA), size=ln))
                    + "\n")
    return path


def _map_cmd(rundir: str, store: str, corpus: str, events: str):
    return [sys.executable, "-m", "proteinbert_tpu", "--platform", "cpu",
            "map", "--pretrained", rundir, "--store", store,
            "--seqs-file", corpus, "--num-shards", str(NUM_SHARDS),
            "--block-size", str(BLOCK_SIZE),
            "--rows-per-batch", str(ROWS),
            "--max-segments", str(MAX_SEGMENTS), "--buckets", BUCKETS,
            "--events-jsonl", events]


def _run(cmd, env_extra=None, log_path=None, timeout=600):
    env = dict(os.environ)
    env.update(env_extra or {})
    with open(log_path, "ab") as lf:
        proc = subprocess.run(cmd, stdout=lf, stderr=lf, env=env,
                              timeout=timeout)
    return proc.returncode


def run_drill(args) -> dict:
    from faults import map_fault_spec, tear_file, flip_byte
    from proteinbert_tpu.mapper import (
        FAULT_ENV, EmbeddingStore, ShardCursor, store_digests,
        verify_store,
    )
    from proteinbert_tpu.obs import read_events
    from proteinbert_tpu.obs.diagnose import summarize_map

    outdir = args.outdir or tempfile.mkdtemp(prefix="pbt_map_drill_")
    os.makedirs(outdir, exist_ok=True)
    log_path = os.path.join(outdir, "drill.log")
    rundir = _make_run_dir(outdir)
    corpus = _make_corpus(outdir, args.corpus, args.seed)
    chaos_store = os.path.join(outdir, "chaos_store")
    control_store = os.path.join(outdir, "control_store")
    ev1 = os.path.join(outdir, "chaos_run1.events.jsonl")
    ev2 = os.path.join(outdir, "chaos_run2.events.jsonl")
    evc = os.path.join(outdir, "control.events.jsonl")
    failures = []
    t0 = time.monotonic()

    # ---- chaos run 1: SIGKILL between object write and cursor advance
    # of shard 0 block 1 (after s0b0 and s1b0 committed — round-robin).
    rc1 = _run(_map_cmd(rundir, chaos_store, corpus, ev1),
               env_extra={FAULT_ENV: map_fault_spec(
                   crash=(0, 1, "after_object"))},
               log_path=log_path)
    if rc1 not in (-9, 137):
        failures.append(f"chaos run 1 exited {rc1}, expected a SIGKILL "
                        "death (-9/137) — the crash hook never fired")
    run1_blocks = [r for r in read_events(ev1, strict=True)
                   if r["event"] == "map_block"]
    if len(run1_blocks) != 2:
        failures.append(f"chaos run 1 committed {len(run1_blocks)} "
                        "block(s), expected 2 (s0b0, s1b0) before the "
                        "mid-block kill")

    # ---- while it is down: tear shard 0's recorded tail block object
    # and shard 1's main cursor (the injected torn-cursor + torn-block
    # faults the resume path must absorb with <= 1 block re-work each).
    store = EmbeddingStore(chaos_store)
    s0_state, _ = ShardCursor(chaos_store, 0).load()
    if not s0_state["blocks"]:
        failures.append("shard 0 cursor holds no blocks after run 1")
        torn_digest = None
    else:
        torn_digest = s0_state["blocks"][-1]["digest"]
        tear_file(store.object_path(torn_digest))
    tear_file(ShardCursor(chaos_store, 1).path)

    # ---- chaos run 2: resume under an injected transient dispatch
    # failure (shard 1 block 1 fails twice, then succeeds).
    rc2 = _run(_map_cmd(rundir, chaos_store, corpus, ev2),
               env_extra={FAULT_ENV: map_fault_spec(fail=(1, 1, 2))},
               log_path=log_path)
    if rc2 != 0:
        failures.append(f"chaos run 2 (resume) exited {rc2}; see "
                        f"{log_path}")

    # ---- control: one uninterrupted run.
    rcc = _run(_map_cmd(rundir, control_store, corpus, evc),
               log_path=log_path)
    if rcc != 0:
        failures.append(f"control run exited {rcc}; see {log_path}")

    # ---- window line (ISSUE 19): SIGKILL at the NEW block_fetched
    # point of shard 0 block 1 — fired after that block's device
    # compute and host fetch completed, before its object write and
    # cursor advance, while the pipelined drive loop has the NEXT
    # block already submitted. The cursor never moved, so a plain
    # resume must re-work exactly the uncommitted tail.
    window_store = os.path.join(outdir, "window_store")
    evw1 = os.path.join(outdir, "window_run1.events.jsonl")
    evw2 = os.path.join(outdir, "window_run2.events.jsonl")
    rcw1 = _run(_map_cmd(rundir, window_store, corpus, evw1),
                env_extra={FAULT_ENV: map_fault_spec(
                    crash=(0, 1, "block_fetched"))},
                log_path=log_path)
    if rcw1 not in (-9, 137):
        failures.append(f"window run 1 exited {rcw1}, expected a "
                        "SIGKILL death at block_fetched (-9/137)")
    rcw2 = _run(_map_cmd(rundir, window_store, corpus, evw2),
                log_path=log_path)
    if rcw2 != 0:
        failures.append(f"window run 2 (resume) exited {rcw2}; see "
                        f"{log_path}")

    # ------------------------------------------------------------ audit
    chaos_rep = control_rep = None
    retries = 0
    rework = None
    window_rework = None
    if not failures:
        # Byte identity: same (shard, block) → digest map, same bytes.
        dg_chaos = store_digests(chaos_store)
        dg_control = store_digests(control_store)
        if dg_chaos != dg_control:
            failures.append(
                f"stores differ: chaos {sorted(dg_chaos.items())} vs "
                f"control {sorted(dg_control.items())}")
        else:
            ctrl = EmbeddingStore(control_store)
            for dg in dg_chaos.values():
                with open(store.object_path(dg), "rb") as a, \
                        open(ctrl.object_path(dg), "rb") as b:
                    if a.read() != b.read():
                        failures.append(f"object {dg[:16]}… bytes "
                                        "differ between stores")

        chaos_rep = verify_store(chaos_store)
        control_rep = verify_store(control_store)
        for name, rep in (("chaos", chaos_rep), ("control", control_rep)):
            if not (rep["ok"] and rep["complete"]):
                failures.append(
                    f"{name} store failed verification: "
                    f"holes={rep['holes']} corrupt={rep['corrupt']} "
                    f"coverage={rep['coverage_errors']} "
                    f"complete={rep['complete']}")
            if rep["quarantined"] != 1:
                failures.append(
                    f"{name} store quarantined {rep['quarantined']} "
                    "record(s), expected exactly the 1 injected poison")
        qrec = ShardCursor(chaos_store, 0).read_quarantine()
        if not any(r["id"] == f"p{POISON_INDEX}"
                   and r["reason"] == "invalid_char" for r in qrec):
            failures.append(f"poison p{POISON_INDEX} missing from the "
                            f"quarantine sidecar (got {qrec})")

        # Bounded re-work: committed-block events across both chaos
        # runs vs unique blocks; and retries observed.
        run2_recs = read_events(ev2, strict=True)
        read_events(evc, strict=True)  # control events schema-valid
        run2_blocks = [r for r in run2_recs if r["event"] == "map_block"]
        all_blocks = run1_blocks + run2_blocks
        unique = {(r["shard"], r["block"]) for r in all_blocks}
        rework = len(all_blocks) - len(unique)
        if rework > NUM_SHARDS:
            failures.append(f"re-work {rework} blocks > bound of 1 per "
                            f"shard ({NUM_SHARDS})")
        retries = sum(r.get("retries") or 0 for r in run2_blocks)
        if retries < 2:
            failures.append(f"injected transient failure retried "
                            f"{retries} time(s), expected >= 2")
        ends = [r for r in run2_recs if r["event"] == "map_end"]
        if not ends or ends[-1]["outcome"] != "completed":
            failures.append("chaos run 2 did not seal map_end/completed")

        # Window line audit: byte-identity vs control, verification,
        # and the same 1-block-per-shard re-work bound — the pipelined
        # device-complete-but-uncommitted window adds no new loss mode.
        dg_window = store_digests(window_store)
        if dg_window != dg_control:
            failures.append(
                "window store differs from control after the "
                "block_fetched kill + resume: "
                f"{sorted(dg_window.items())} vs "
                f"{sorted(dg_control.items())}")
        wrep = verify_store(window_store)
        if not (wrep["ok"] and wrep["complete"]):
            failures.append(
                f"window store failed verification: holes="
                f"{wrep['holes']} corrupt={wrep['corrupt']} "
                f"complete={wrep['complete']}")
        w_blocks = [r for p in (evw1, evw2)
                    for r in read_events(p, strict=True)
                    if r["event"] == "map_block"]
        w_unique = {(r["shard"], r["block"]) for r in w_blocks}
        window_rework = len(w_blocks) - len(w_unique)
        if window_rework > NUM_SHARDS:
            failures.append(f"window re-work {window_rework} blocks > "
                            f"bound of 1 per shard ({NUM_SHARDS})")

        # diagnose --map over the concatenated chaos streams agrees on
        # the re-work count (the operator-facing view of the drill).
        combined = []
        for p in (ev1, ev2):
            combined.extend(read_events(p, strict=True))
        diag = summarize_map(combined)
        if diag["rework_blocks"] != rework:
            failures.append(
                f"diagnose --map rework {diag['rework_blocks']} != "
                f"event-audit rework {rework}")

        # ---- the --verify detection gates, through the REAL CLI ----
        import contextlib
        import io

        from proteinbert_tpu.cli.main import main as cli_main

        def cli_verify():
            # The CLI prints its report JSON; keep the drill's own
            # stdout to the one summary object (--json contract).
            with contextlib.redirect_stdout(io.StringIO()):
                try:
                    return cli_main(["map", "--store", chaos_store,
                                     "--verify"])
                except SystemExit as e:
                    return int(e.code or 0)

        if cli_verify() != 0:
            failures.append("pbt map --verify failed on the intact "
                            "chaos store")
        victim = sorted(dg_chaos.values())[0]
        vpath = store.object_path(victim)
        backup = vpath + ".backup"
        shutil.copyfile(vpath, backup)
        flip_byte(vpath)
        if cli_verify() == 0:
            failures.append("pbt map --verify MISSED a flipped byte")
        else:
            rep = verify_store(chaos_store)
            if not any(c["reason"] == "digest_mismatch"
                       for c in rep["corrupt"]):
                failures.append("flipped byte not typed digest_mismatch:"
                                f" {rep['corrupt']}")
        os.replace(backup, vpath)
        shutil.copyfile(vpath, backup)
        os.remove(vpath)
        if cli_verify() == 0:
            failures.append("pbt map --verify MISSED a deleted block")
        else:
            rep = verify_store(chaos_store)
            if not any(h["digest"] == victim for h in rep["holes"]):
                failures.append(f"deleted block not reported as a hole: "
                                f"{rep['holes']}")
        os.replace(backup, vpath)
        if cli_verify() != 0:
            failures.append("chaos store did not verify clean after "
                            "restoring the mauled object")

    summary = {
        "corpus": args.corpus,
        "shards": NUM_SHARDS,
        "blocks": (chaos_rep or {}).get("blocks_checked"),
        "embedded": (chaos_rep or {}).get("embedded"),
        "quarantined": (chaos_rep or {}).get("quarantined"),
        "rework_blocks": rework,
        "window_rework_blocks": window_rework,
        "retries": retries,
        "torn_block": (torn_digest or "")[:16],
        "wall_s": round(time.monotonic() - t0, 1),
        "outdir": outdir,
        "failures": failures,
        "ok": not failures,
    }
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--corpus", type=int, default=44,
                    help="corpus size (2 shards x 3 blocks at the "
                         "default geometry)")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--outdir", help="artifact dir (default: temp)")
    ap.add_argument("--json", action="store_true",
                    help="print the summary as one JSON object only")
    args = ap.parse_args(argv)
    if args.corpus < 3 * NUM_SHARDS * BLOCK_SIZE - BLOCK_SIZE + 1:
        ap.error(f"--corpus must give every shard >= 3 blocks "
                 f"(>= {3 * NUM_SHARDS * BLOCK_SIZE - BLOCK_SIZE + 1})")
    summary = run_drill(args)
    if args.json:
        print(json.dumps(summary))
    else:
        print(json.dumps(summary, indent=2))
    if not summary["ok"]:
        print("MAP DRILL FAILED:", "; ".join(summary["failures"]),
              file=sys.stderr)
        return 1
    print(f"map drill OK: SIGKILL mid-block + torn cursor + torn block "
          f"+ poison + transient failure → byte-identical store, "
          f"{summary['rework_blocks']} re-worked block(s) "
          f"(bound {NUM_SHARDS}), {summary['quarantined']} quarantined, "
          f"{summary['retries']} retries, --verify catches "
          f"flip/hole ({summary['wall_s']}s)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
