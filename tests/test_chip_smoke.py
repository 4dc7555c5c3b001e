"""chip_smoke.py's sandbox rehearsal, the cache helper it leans on, and the
peak-rate table's refusal of a device it does not know.

The rehearsal is the smoke's own code (`--platform cpu`: `tiny` width,
Pallas in interpret mode, every phase a child process) — what it proves is
paths, arguments, control flow and the checks themselves. It proves nothing
about the chip and must never say it did: its last line names the cpu.
"""

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (never imports jax: safe in any process)

PHASES = ["device", "pretrain", "resume", "serve_bucketed", "serve_ragged",
          "map", "kernels"]


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """ONE run of the smoke for all the cases below — also under xdist,
    where a module fixture is otherwise built once per worker: the first
    worker to take the lock runs it, the others read its result."""
    import fcntl

    root = tmp_path_factory.getbasetemp()
    if os.environ.get("PYTEST_XDIST_WORKER"):
        root = root.parent  # shared by the workers of one session
    result = root / "chip_smoke_rehearsal.json"
    with open(root / "chip_smoke_rehearsal.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not result.exists():
            p = subprocess.run(
                [sys.executable, os.path.join(REPO, "chip_smoke.py"),
                 "--platform", "cpu"],
                cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                capture_output=True, text=True, timeout=600)
            result.write_text(json.dumps(
                [p.returncode, p.stdout, p.stderr]))
    rc, out, err = json.loads(result.read_text())
    p = SimpleNamespace(returncode=rc, stdout=out, stderr=err)
    lines = [json.loads(l) for l in p.stdout.splitlines()
             if l.startswith("{")]
    return p, lines


def test_rehearsal_runs_every_phase(rehearsal):
    p, lines = rehearsal
    assert p.returncode == 0, p.stderr[-3000:]
    phases = [l for l in lines if "phase" in l]
    assert [l["phase"] for l in phases] == PHASES
    for l in phases:  # name, seconds, compile seconds, what was checked
        assert l["seconds"] > 0 and l["compile_seconds"] is not None
        assert isinstance(l["checked"], dict) and l["checked"]


def test_rehearsal_never_claims_the_chip(rehearsal):
    p, lines = rehearsal
    assert p.stdout.strip().splitlines()[-1] == json.dumps(
        {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                "count": 1}})
    assert '"tpu"' not in p.stdout


def test_rehearsal_checks_what_the_chip_run_checks(rehearsal):
    by = {l["phase"]: l["checked"] for l in rehearsal[1] if "phase" in l}
    assert by["pretrain"]["train_step_compiles"] == 1
    assert by["pretrain"]["loss_last5_mean"] < by["pretrain"]["loss_first"]
    assert "cpu" in by["pretrain"]["measured_on"]  # rates beside the device
    assert by["resume"]["losses_equal_uninterrupted"] is True
    for mode in ("bucketed", "ragged"):
        s = by[f"serve_{mode}"]
        assert s["accepted"] == s["sealed"] > 0 and s["drained"] is True
        assert s["by_status"].get("400") == 1 and s["truncated"] >= 1
        assert s["warm_executables"] >= 1
    assert by["map"]["embedded"] == by["map"]["sequences"]
    assert by["map"]["verify_exit"] == 0
    rows = by["kernels"]["rows"]
    assert len(rows) == 4 and by["kernels"]["interpret"] is True
    assert {r["decisions"].get("onepass") for r in rows} == {
        "pallas/dense", "pallas/packed", "reference/unsupported_shape",
        "reference/segments"}
    # The served packed local track (ISSUE 42): the kernel at its
    # budget-chosen tile against XLA's composition and the float32 track.
    served = by["kernels"]["served"]
    assert served["decision"] == "pallas/packed" and served["tile"] > 0
    assert served["kernel_vs_f32"]["rel"] <= served["xla_vs_f32"]["rel"]
    assert "kernel_ms" not in served  # a time is the chip's to give


def test_rehearsal_shares_one_cache_and_a_second_process_hits_it(rehearsal):
    by = {l["phase"]: l for l in rehearsal[1] if "phase" in l}
    want = os.path.abspath(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                           or os.path.join(REPO, ".jax_cache"))
    assert by["device"]["checked"]["compile_cache_dir"] == want
    # The resumed trainer is a fresh process compiling the same step.
    assert by["resume"]["checked"]["train_step_from_cache"] is True
    assert by["resume"]["cache_hits"] > 0


def _fake_device(opts):
    return {"platform": "cpu", "kind": "cpu", "count": opts.chips}


def test_a_failed_phase_exits_nonzero_and_prints_no_ok(monkeypatch, capsys,
                                                       tmp_path):
    """The pretrain child exits 3: the run ends there, non-zero, and the
    last line is never reached."""
    monkeypatch.setattr(chip_smoke, "LOG_DIR", str(tmp_path / "logs"))
    monkeypatch.setattr(chip_smoke, "phase_device", _fake_device)
    monkeypatch.setattr(
        chip_smoke, "cli_cmd",
        lambda opts, *a: [sys.executable, "-c", "import sys; sys.exit(3)"])
    with pytest.raises(SystemExit) as e:
        chip_smoke.main(["--platform", "cpu"])
    assert e.value.code not in (0, None)
    assert "phase 'pretrain' FAILED" in str(e.value.code)
    assert "exited 3" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_without_an_accelerator_it_refuses(monkeypatch, capsys, tmp_path):
    """No `--platform cpu` and JAX reports the cpu: no result."""
    monkeypatch.setattr(chip_smoke, "LOG_DIR", str(tmp_path / "logs"))
    monkeypatch.setattr(
        chip_smoke, "run_child",
        lambda *a, **k: json.dumps({"platform": "cpu", "kind": "cpu",
                                    "count": 1, "bytes_limit": None}))
    monkeypatch.setattr(chip_smoke, "compile_stats", lambda *a: {})
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert "needs 'tpu'" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_alone_in_a_directory_it_fails(tmp_path):
    """chip_smoke.py without the program beside it proves nothing."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "chip_smoke.py", "--platform", "cpu"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "No module named 'proteinbert_tpu'" in p.stderr


# ------------------------------------------------- the compile-cache helper

_PRINT_CACHE = (
    "import jax; from proteinbert_tpu.utils.compat import "
    "configure_compile_cache as c; d = c(); "
    "assert jax.config.jax_compilation_cache_dir == d; print(d)")


def _cache_dir_of_a_process(env, *cli):
    p = subprocess.run([sys.executable, *cli], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    return p.stdout.strip().splitlines()[-1]


@pytest.mark.parametrize("case", ["variable_set", "unset_fixed_in_checkout",
                                  "two_processes_same_path"])
def test_compile_cache_is_placed_from_outside(case, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if case == "variable_set":
        # That directory, and no other: `serve`/`fleet` no longer have a
        # flag that could name another.
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "c")
        assert _cache_dir_of_a_process(env, "-c", _PRINT_CACHE) \
            == str(tmp_path / "c")
        assert os.path.isdir(tmp_path / "c")
        from proteinbert_tpu.cli.main import build_parser

        with pytest.raises(SystemExit):
            build_parser().parse_args(
                ["serve", "--pretrained", "x", "--compile-cache-dir", "y"])
    elif case == "unset_fixed_in_checkout":
        assert _cache_dir_of_a_process(env, "-c", _PRINT_CACHE) \
            == os.path.join(REPO, ".jax_cache")
    else:
        # No pid, time or temporary name in the path: a second process
        # (another cwd, even) lands on the same directory.
        a = _cache_dir_of_a_process(env, "-c", _PRINT_CACHE)
        code = f"import os; os.chdir({str(tmp_path)!r}); " + _PRINT_CACHE
        assert _cache_dir_of_a_process(env, "-c", code) == a


def test_cli_arms_the_cache_before_any_handler(monkeypatch, tmp_path):
    """`cli.main.main` places the cache once, for every subcommand."""
    import importlib

    import jax

    cli = importlib.import_module("proteinbert_tpu.cli.main")
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    seen = {}
    monkeypatch.setattr(
        cli, "cmd_check",
        lambda args: seen.setdefault(
            "dir", jax.config.jax_compilation_cache_dir) and 0)
    try:
        assert cli.main(["check"]) == 0
        assert seen["dir"] == str(tmp_path / "cc")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ------------------------------------------------------ the peak-rate table

class _Device:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_peak_rate_knows_the_v5e_and_refuses_the_unknown():
    from proteinbert_tpu.train.metrics import peak_flops_per_chip

    assert peak_flops_per_chip(_Device("TPU v5 lite")) == 197e12
    assert peak_flops_per_chip(_Device("cpu")) == 5e11  # its own entry
    with pytest.raises(ValueError, match="no peak FLOP/s entry"):
        peak_flops_per_chip(_Device("TPU v9 mega"))
