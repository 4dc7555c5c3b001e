"""Every cell's driver, end to end on the CPU at `tiny` widths: the result
line's contract, the refusal to run without a TPU, the agreement of the
plain reference with the system, the control coming out not correct, and
a broken timed path coming out not correct."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = ["pretrain-base-dense", "serve-base-sat", "pretrain-large-dense",
         "serve-base-steady"]
HELD_OUT = {"serve-base-steady"}     # benchmark/held_out/: files kept, not judged
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _manifest(held_out=False):
    from benchmark.run import load_manifest

    return load_manifest(held_out)


def _run(cell, *extra, seconds="1"):
    if cell in HELD_OUT:
        extra = (*extra, "--held-out")
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", "3000000019", "--seconds", seconds, *extra],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"})


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_the_contracts_line(cell, trace):
    done = _run(cell, "--trace", trace, "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert LINE_KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])
    manifest = _manifest(cell in HELD_OUT)
    if trace == "0":
        named = {m["name"] for m in manifest["end_to_end"]
                 if "workloads" not in m or cell in m["workloads"]}
        assert set(line["metrics"]) == named and "setup_s" in named
        assert len(named) >= 2
    else:
        # a CPU trace has no device plane: the device readers find
        # nothing and leave their metric out; nothing else is named
        assert set(line["metrics"]) <= {m["name"] for m in manifest["per_layer"]}
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"])
    # every number compared is printed beside its limit
    assert done.stdout.count("check ") >= 3 and "(limit " in done.stdout


def test_without_a_tpu_the_run_is_an_error_and_prints_no_result():
    done = _run(CELLS[0], "--trace", "0")
    assert done.returncode != 0
    assert "needs a TPU" in done.stderr
    assert not any(ln.startswith("{") for ln in done.stdout.splitlines())


def test_a_directory_with_only_the_benchmark_is_an_error(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert done.returncode != 0
    assert not any(ln.startswith("{") for ln in done.stdout.splitlines())


def test_a_held_out_cell_runs_only_where_it_is_asked_for():
    """`serve-base-steady` is out of BENCHMARK.json (PERF.md section 7);
    its files stay, and `held_out/<cell>.json` holds the entries that
    bring it back: the driver's command does not know the cell."""
    names = {w["name"] for w in _manifest()["workloads"]}
    assert not names & HELD_OUT
    assert HELD_OUT <= {w["name"] for w in _manifest(True)["workloads"]}
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "serve-base-steady",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0 and "no workload named" in done.stderr
    assert not any(ln.startswith("{") for ln in done.stdout.splitlines())


@pytest.mark.parametrize("held_out", [False, True])
def test_manifest_names_files_that_exist(held_out):
    manifest = _manifest(held_out)
    for c in manifest["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in manifest["workloads"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "workloads", w["name"] + ".json"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
    for m in manifest["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
        assert m["moves"] in {e["name"] for e in manifest["end_to_end"]}


# ------------------------------------------------------------------ controls

def _tiny():
    from benchmark import traffic
    from benchmark.program import model_sizes

    with open(os.path.join(ROOT, "benchmark", "configs", "tiny.json")) as f:
        conf = json.load(f)
    mix = {"lengths": {"median": 40, "sigma": 0.6, "min": 8, "max": 62},
           "block": 8, "annotations": {"positives": 8, "share_without": 0.2}}
    return conf, model_sizes(conf), mix, traffic


def _tiny_batches(conf, m, mix, traffic, seed):
    from benchmark.drivers.serve import _tokens

    seqs, _ = traffic.sequences(mix, 3, seed)
    ann = traffic.annotation_rows(mix, len(seqs), m["num_annotations"], seed)
    toks = _tokens(seqs, 64)
    return [{"tokens": toks[i:i + 8], "annotations": ann[i:i + 8]}
            for i in range(0, 24, 8)]


@pytest.mark.parametrize("cell,precision,number", [
    (CELLS[0], "bf16_params", "change_norm_gap"),
    (CELLS[2], "bf16_params", "change_norm_gap"),
    (CELLS[0], "int8", "grad_dir_gap")])
def test_training_control_in_lower_precision_is_not_correct(cell, precision,
                                                            number):
    """The reference in the program's place, one precision down (the
    configuration states float32 parameters: bfloat16; bfloat16
    products: int8), has to pass the cell's limit on the number that is
    there to catch it (PERF.md section 2 has the chip's readings; at
    Large the readings set no limit for int8 products, and the cell
    names the number under `not_compared`). At
    `tiny` widths and the cell's own depth: the error of products in a
    lower precision grows with the number of blocks it passes."""
    from benchmark import compare
    from benchmark.reference import proteinbert_f32 as ref

    conf, m, mix, traffic = _tiny()
    entry = next(c for c in _manifest()["configs"] if c["name"] in cell.split("-"))
    with open(os.path.join(ROOT, entry["file"])) as f:
        m = dict(m, num_blocks=json.load(f)["num_blocks"])
    with open(os.path.join(ROOT, "benchmark", "workloads", cell + ".json")) as f:
        limits = json.load(f)["limits"]
    opt = {k: conf["optimizer"][k] for k in (
        "learning_rate", "warmup_steps", "grad_clip_norm", "b1", "b2")}
    batches = _tiny_batches(conf, m, mix, traffic, 11)
    sound = ref.follow_steps(11, batches, m, conf["corruption"], opt, rows=8)
    control = ref.follow_steps(11, batches, m, conf["corruption"], opt,
                               precision=precision, rows=8)
    gaps = compare.training_checks(control, sound)
    assert gaps[number] > limits[number], gaps
    same = compare.training_checks(sound, sound)
    assert all(v == 0.0 for v in same.values())


def test_serving_control_with_int8_weights_is_not_correct():
    from benchmark import compare
    from benchmark.drivers.serve import reference_answers

    conf, m, mix, traffic = _tiny()
    with open(os.path.join(ROOT, "benchmark", "workloads", CELLS[1] + ".json")) as f:
        limits = json.load(f)["limits"]
    seqs, _ = traffic.sequences(mix, 2, 13)
    sound = reference_answers(13, seqs, [32, 64], m, rows=8)
    control = reference_answers(13, seqs, [32, 64], m, precision="int8", rows=8)
    gaps = compare.embedding_checks(control, sound)
    assert any(gaps[k] > limits[k] for k in gaps), gaps


# ------------------------------------------------------- a broken timed path

def _main_in_process(capsys, cell):
    from benchmark import run as bench_run

    rc = bench_run.main(["--workload", cell, "--seed", "17", "--seconds", "1",
                         "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    return rc, json.loads(out.strip().splitlines()[-1]), out


def test_a_step_that_returns_its_parameters_unchanged_is_not_correct(
        monkeypatch, capsys):
    import jax
    import jax.numpy as jnp

    from proteinbert_tpu.train import train_state as ts

    real = ts.train_step

    def broken(state, batch, cfg, **kw):
        kept = jax.tree.map(jnp.copy, state.params)
        new, metrics = real(state, batch, cfg, **kw)
        return new.replace(params=kept), metrics

    monkeypatch.setattr(ts, "train_step", broken)
    rc, line, out = _main_in_process(capsys, "pretrain-base-dense")
    assert rc == 0 and line["correct"] is False
    assert "check change_norm_gap: 1 " in out and "FAILED" in out


def test_an_answer_altered_where_it_is_produced_is_not_correct(
        monkeypatch, capsys):
    from proteinbert_tpu import inference

    real = inference._packed_encode_batch

    def broken(params, tokens, segment_ids, annotations, cfg):
        out = dict(real(params, tokens, segment_ids, annotations, cfg))
        out["global"] = out["global"][:, ::-1]   # segments answer each other
        return out

    monkeypatch.setattr(inference, "_packed_encode_batch", broken)
    rc, line, out = _main_in_process(capsys, "serve-base-sat")
    assert rc == 0 and line["correct"] is False and "FAILED" in out


def test_memdiag_prints_the_compilers_account_and_the_counters():
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.memdiag", "--workload", CELLS[1],
         "--rehearse"], cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["compiler"]["temporaries"] > 0
    assert set(line["counters"]) == {"before", "operands_made", "first_run",
                                     "second_run"}
