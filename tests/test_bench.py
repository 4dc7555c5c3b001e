"""bench.py sweep plumbing (no hardware): variant-list invariants, the
in-process sweep loop, the device stamp every record carries, and the
refusal to measure on a CPU nobody asked for."""

import json
import os

import pytest

import bench


def test_variant_rows_unique():
    """Rows are keyed by (variant, seq_len, batch) — `--only` and the
    failed_variants list name them so — and the list is deterministic."""
    v1, _ = bench.build_variants(True)
    v2, _ = bench.build_variants(True)
    keys = [(name, seq, b) for name, _, seq, b in v1]
    assert len(set(keys)) == len(keys)
    assert keys == [(name, seq, b) for name, _, seq, b in v2]


def test_only_filter_matches_names_and_shape_keys():
    """--only matches the bare variant name (backward compat, anchored
    patterns included) and the 'name:seq/batch' shape key (so one row
    of a multi-shape variant can be refreshed in a short window)."""
    import re

    variants, _ = bench.build_variants(True)

    def hits(pattern):
        pat = re.compile(pattern)
        return [(v[0], v[2], v[3]) for v in variants
                if bench.variant_matches(pat, v)]

    # Name-anchored pattern keeps matching despite the shape-key text.
    assert hits("u2st$") == [("remat-convs-u2st", 1024, 256)]
    # Row-targeted: exactly one shape of a six-shape variant.
    assert hits("remat-convs:1024/512$") == [("remat-convs", 1024, 512)]
    # Plain substring still matches every shape of the variant family.
    assert len(hits("pallas")) == 3
    assert hits("nonexistent") == []


def test_cpu_rehearsal_variant_is_tiny():
    (name, model, seq, batch), steps = bench.build_variants(False)[0][0], \
        bench.build_variants(False)[1]
    assert name == "xla" and model.num_blocks <= 2 and steps <= 5


def test_preset_provenance_variants_track_presets():
    """The large/long sweep rows exist to certify the PRESET shapes
    (VERDICT r3 Weak #3) — they must be the presets' own model configs,
    not hand-copied twins that can drift."""
    from proteinbert_tpu.configs import get_preset

    by_name = {}
    for name, model, _, _ in bench.build_variants(True)[0]:
        by_name.setdefault(name, model)
    assert by_name["large"] == get_preset("large").model
    assert by_name["long"] == get_preset("long").model


def _fake_device(platform="tpu"):
    return {"platform": platform,
            "device_kind": "TPU v5 lite" if platform == "tpu" else "cpu",
            "device_count": 1}


def test_sweep_runs_in_process_names_failures_and_stamps_device(
        monkeypatch, capsys):
    """The sweep loop runs every variant in THIS process (a chip belongs
    to one process), names a failed variant on stderr and in the record
    instead of skipping it silently, headlines the best row, and stamps
    the record with the device."""
    monkeypatch.setattr(bench, "bench_device", _fake_device)
    n = len(bench.build_variants(True)[0])
    seen = []

    def fake_run_variant(variant, steps, device, seed=0):
        assert device == _fake_device() and steps == 15
        i = seed
        seen.append(i)
        name, _, seq, batch = variant
        assert variant == bench.build_variants(True)[0][i]
        if i in (0, 2):
            raise RuntimeError("RESOURCE_EXHAUSTED: fake OOM")
        return {"variant": name, "seq_len": seq, "batch": batch,
                "ms_per_step": 1.0, "residues_per_sec": 1000.0 + i,
                "mfu": 0.5}

    monkeypatch.setattr(bench, "run_variant", fake_run_variant)
    monkeypatch.setattr(bench.sys, "argv", ["bench.py"])
    bench.main()
    out = capsys.readouterr()
    assert seen == list(range(n))
    record = json.loads(out.out.strip().splitlines()[-1])
    assert record["platform"] == "tpu"
    assert record["device_kind"] == "TPU v5 lite"
    assert record["device_count"] == 1
    assert record["value"] == 1000.0 + (n - 1)
    v = bench.build_variants(True)[0]
    assert record["failed_variants"] == [
        f"{v[i][0]}:{v[i][2]}/{v[i][3]}" for i in (0, 2)]
    assert out.err.count("failed (RuntimeError") == 2
    assert "stale" not in record and "live_fallback" not in record


def test_refuses_cpu_nobody_asked_for(monkeypatch, capsys):
    """No accelerator and no JAX_PLATFORMS=cpu: the run ends non-zero
    before any number exists, and prints no record (old or new). With
    the explicit request the same backend is accepted and stamped."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as e:
        bench.bench_device()
    assert e.value.code not in (0, None)
    assert "refusing" in str(e.value.code)
    assert capsys.readouterr().out == ""
    # "tpu, else cpu" is a request for the chip: a CPU reached through it
    # is still a CPU nobody asked for.
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(SystemExit):
        bench.bench_device()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench.bench_device()["platform"] == "cpu"
    assert bench.bench_device()["device_kind"] == "cpu"


def test_only_filter_that_matches_nothing_is_an_error(monkeypatch):
    monkeypatch.setattr(bench, "bench_device", _fake_device)
    monkeypatch.setattr(bench.sys, "argv",
                        ["bench.py", "--only", "nonexistent"])
    with pytest.raises(SystemExit, match="matches no variant"):
        bench.main()


def test_boundary_bench_emits_record_and_overlap_wins():
    """`bench.py --boundary` (the CI-measurable overlap win): one JSON
    line with both per-boundary stall numbers, and the overlapped
    boundary strictly cheaper than the synchronous one. Sizes are
    shrunk via the env knobs so this stays a plumbing-and-direction
    test; the ≥5x magnitude claim is the bench's own default-size run."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PBT_BOUNDARY_BENCH_BOUNDARIES="2",
               PBT_BOUNDARY_BENCH_STEPS="3",
               PBT_BOUNDARY_BENCH_DIM="32")
    p = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"), "--boundary"],
        capture_output=True, text=True, timeout=420, env=env, cwd=repo)
    assert p.returncode == 0, p.stderr[-2000:]
    record = json.loads(p.stdout.strip().splitlines()[-1])
    assert record["metric"] == "ckpt_boundary_stall_s"
    assert record["platform"] == "cpu"
    assert record["device_kind"] == "cpu" and record["device_count"] >= 1
    assert record["boundaries"] == 2
    assert record["overlapped_stall_s_per_boundary"] > 0
    assert record["sync_stall_s_per_boundary"] > \
        record["overlapped_stall_s_per_boundary"]
    assert record["stall_reduction_x"] > 1
    # The hidden work really ran (fetch+write seconds were recorded).
    assert record["overlap_hidden_s_per_boundary"] > 0


@pytest.mark.slow
def test_comm_bench_records_zero_update_win():
    """`bench.py --comm` (the ZeRO-1 memory/comm artifact): one JSON
    line comparing replicated vs zero-update compiled programs. The
    acceptance-criteria numbers asserted here come from the COMPILED
    HLO and the sharding rules, not from the docstring: per-chip
    optimizer-state bytes reduced by ~(1 - 1/data_extent), per-step
    collective bytes within ~1.5x of the replicated all-reduce, int8
    grad-reduction wire <= 0.30x the fp32 explicit reduce-scatter.
    Model dim shrunk via env, but the subprocess still pays five full
    sharded compiles — slow lane; tier-1 covers the helpers in-process
    (tests/test_zero.py, tests/test_quant.py + the quant smoke stage)
    and the docs/performance.md row records the default-size capture."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PBT_COMM_MESH="4x2", PBT_COMM_DIM="32")
    # Scrub the 8-device flag so the child's own request can't fight it.
    from proteinbert_tpu.utils.compat import scrub_device_count_flag

    env["XLA_FLAGS"] = scrub_device_count_flag(env.get("XLA_FLAGS", ""))
    p = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"), "--comm"],
        capture_output=True, text=True, timeout=780, env=env, cwd=repo)
    assert p.returncode == 0, p.stderr[-2000:]
    record = json.loads(p.stdout.strip().splitlines()[-1])
    assert record["metric"] == "zero_update_comm"
    assert record["platform"] == "cpu-virtual"
    assert record["mesh"] == {"data": 4, "fsdp": 2}
    modes = {r["mode"]: r for r in record["modes"]}
    assert set(modes) == {"replicated", "zero", "zero_rs_fp32",
                          "zero_bf16", "zero_int8"}
    # Memory: Adam state per chip shrinks ~data_extent (4), params don't.
    assert record["opt_state_bytes_reduction_x"] >= 3.0
    assert (modes["zero"]["state_bytes_per_chip"]["params"]
            == modes["replicated"]["state_bytes_per_chip"]["params"])
    # Comm: reduce-scatter + all-gather stays within ~1.5x all-reduce.
    assert 0 < record["collective_bytes_ratio"] <= 1.5
    for r in record["modes"]:
        assert r["collective_bytes"]["total"] > 0
        assert r["wire_bytes"]["total"] > 0
    # Quantized wire (ISSUE 12, the ROADMAP item 1 acceptance): the
    # int8 payload moves <= 0.30x the fp32 explicit reduce-scatter's
    # grad-reduction wire bytes (bench exits 1 past the gate; asserted
    # here too so the record itself carries the evidence), bf16 ~0.5x.
    assert 0 < record["int8_grad_wire_ratio"] <= 0.30
    assert 0 < record["bf16_grad_wire_ratio"] <= 0.60
