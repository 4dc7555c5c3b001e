"""`benchmark.run --rehearse --trace 1` of a training and a serving cell,
twice on one new compile cache: the line prints every start-up metric the
manifest lists for the cell, the first run's compiles are misses of the
cache and the second run has none."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _traced_rehearsal(cell, cache):
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell, "--seed",
         "2147484029", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored",
             "JAX_COMPILATION_CACHE_DIR": cache,
             # the suite's own floor of 0.3 s would leave the small
             # programs out of the cache, and the second run compiling them
             "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    return {k: v["value"] for k, v in line["metrics"].items()}


@pytest.mark.parametrize("cell", ["pretrain-base-dense", "serve-base-sat"])
def test_a_traced_rehearsal_prints_the_start_up_metrics_cold_then_warm(
        cell, tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]
                  if cell in m["workloads"]
                  and m["name"].startswith(("startup_", "scope_map_s"))}
    assert len(listed) == (6 if cell.startswith("serve") else 5)
    cold = _traced_rehearsal(cell, str(tmp_path / "cache"))
    assert listed <= set(cold)
    assert cold["startup_compiles"] > 0 and cold["startup_compile_s"] > 0
    assert cold["startup_trace_lower_s"] > 0
    warm = _traced_rehearsal(cell, str(tmp_path / "cache"))
    assert listed <= set(warm)
    assert warm["startup_compiles"] == 0 and warm["startup_compile_s"] == 0
    assert warm["startup_cache_load_s"] > 0 and warm["startup_trace_lower_s"] > 0
    assert warm["compiles_in_window." + ("tput" if cell.startswith("serve")
                                         else "train")] == 0
    if cell.startswith("serve"):
        assert 0 < warm["startup_warmup_s"] < cold["startup_warmup_s"]
    # a CPU trace has no device plane: no reader asked for a scope map
    assert cold[next(n for n in listed if n.startswith("scope_map_s"))] == 0


def test_the_second_served_decoders_traced_rehearsal_prints_its_own_and_these():
    """What `test_zaya_cell.py::test_rehearsal_prints_the_contracts_line[1]`
    held (tests/conftest.py marks it: it allows the cell's twenty-six
    metrics and no other), with this PR's six allowed and asked for."""
    from tests.benchmark import test_zaya_cell as zaya

    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", zaya.CELL, "--seed",
         "3000000019", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    assert "window: " in done.stdout and "routing: " in done.stdout
    assert "kernel paths: cca_core {'reference/tiles_do_not_fit'" in done.stdout
    mine = {"startup_compile_s", "startup_compiles", "startup_cache_load_s",
            "startup_trace_lower_s", "startup_warmup_s", "scope_map_s.tput"}
    assert mine <= set(line["metrics"]) <= zaya.SHARED | set(zaya.NEW) | mine
    assert line["metrics"]["dropped_assignments.tput"]["value"] == 0
    assert line["metrics"]["routed_here_share_pct.tput"]["value"] == 100
    assert line["metrics"]["expert_load_max_over_mean.tput"]["value"] >= 1
    # no device plane on the CPU: the scopes' readers find nothing
    assert not set(zaya.NEW) & set(line["metrics"])
    assert set(line["compared"]) == zaya.COMPARED
    assert line["compared"]["param_count"]["value"] == 546344
