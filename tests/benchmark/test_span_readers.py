"""The readers of the program's span spine (benchmark/span_readers.py and the
22 per-layer metrics that use it): on spans made by hand, on the fixture
recorded on a v5e (`benchmark/record_span_fixture.py`: the spans, the
instruction -> scope map and the device plane of three runs of each
program at rehearsal sizes), on nothing, and end to end in a rehearsal."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import span_readers
from benchmark.run import _layer_metric

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURE = os.path.join(ROOT, "tests", "benchmark", "fixtures", "spans_v5e.json")

TRAIN = ["data_wait_ms.train", "fwd_device_ms.train", "bwd_device_ms.train",
         "opt_device_ms.train", "local_track_device_ms.train",
         "attention_device_ms.train", "scan_save_device_ms.train",
         "scope_coverage_pct.train", "compiles_in_window.train"]
SERVE = ["compiles_in_window", "pack_ms", "assemble_ms", "slot_wait_ms",
         "finalize_host_ms"]
PACKED = ["packed_local_track_device_ms", "packed_attention_device_ms",
          "packed_scope_coverage_pct"]
NEW = (TRAIN + [f"{m}.{sfx}" for m in SERVE for sfx in ("tput", "lat")]
       + PACKED)


def _manifest():
    """With the held-out steady cell's entries (`benchmark/held_out/`):
    its `.lat` readers stay listed, with a reader each."""
    from benchmark.run import load_manifest

    return load_manifest(held_out=True)


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def _obs(recorded, cell):
    got = recorded[cell]
    return {"program": got["program"], "spans": got["spans"],
            "scopes": got["scopes"], "trace": {"plane": got["plane"]}}


def _cell_of(metric):
    return ("pretrain-base-dense" if metric.endswith(".train")
            else "serve-base-sat")


def _span(name, start_ms, ms, span_id=0, parent=None, **ids):
    return {"name": name, "start_ns": int(start_ms * 1e6),
            "end_ns": int((start_ms + ms) * 1e6), "tid": 1, "id": span_id,
            "parent": parent, "ids": ids}


# ------------------------------------------------------------ the manifest

def test_the_22_metrics_are_listed_with_their_cells_and_a_reader_each():
    manifest = _manifest()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert set(NEW) <= set(by_name)
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in (by_name[name] for name in NEW):
        assert set(m["workloads"]) <= cells and m["workloads"]
        # each cell listed reports the end-to-end metric the metric moves
        assert set(m["workloads"]) <= set(e2e[m["moves"]]["workloads"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))


# ------------------------------------------------------ spans made by hand

def test_host_readers_on_spans_made_by_hand():
    spans = [
        _span("train.data_wait", 0, 0.2), _span("train.data_wait", 10, 0.4),
        _span("serve.ingest", 0, 2.0, batch=1, n=10),
        _span("serve.ingest", 5, 4.0, batch=1, n=30),
        _span("serve.ingest", 40, 3.0, batch=2, n=20),
        _span("serve.assemble", 10, 8.0, batch=1),
        _span("serve.assemble", 50, 12.0, batch=2),
        _span("serve.wait_slot", 18, 100.0, batch=1),
        _span("serve.wait_slot", 62, 300.0, batch=2),
        _span("serve.launch", 120, 1.0, batch=1),
        _span("serve.launch", 365, 1.0, batch=2),
        _span("serve.launch", 700, 1.0, batch=3),
        _span("serve.fan_out", 500, 6.0, batch=1),
        _span("serve.seal", 506, 14.0, batch=1),
        _span("serve.fan_out", 900, 10.0, batch=2),
        _span("serve.seal", 910, 30.0, batch=2),
        _span("jax.compile", 3, 1.0),
    ]
    obs = {"spans": spans}
    assert _layer_metric("data_wait_ms.train")(obs) == pytest.approx(0.3)
    assert _layer_metric("pack_ms.tput")(obs) == pytest.approx(9.0 / 3)
    assert _layer_metric("assemble_ms.lat")(obs) == pytest.approx(10.0)
    assert _layer_metric("slot_wait_ms.tput")(obs) == pytest.approx(200.0)
    assert _layer_metric("finalize_host_ms.lat")(obs) == pytest.approx(30.0)
    assert _layer_metric("compiles_in_window.tput")(obs) == 1
    # nothing compiled is a reading of 0, not a reading that is missing
    assert _layer_metric("compiles_in_window.train")({"spans": spans[:2]}) == 0


def test_device_readers_on_a_plane_made_by_hand():
    ms = 1_000_000
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_train_step(1)", 0, 100 * ms], ["jit_other(2)", 100 * ms, 9 * ms],
            ["jit_train_step(1)", 110 * ms, 100 * ms]]},
        {"name": "XLA Ops", "events": [
            ["%fusion.1 = f32[] fusion()", 0, 10 * ms],
            ["%while.1 = () while()", 10 * ms, 80 * ms],
            ["%fusion.2 = f32[] fusion()", 10 * ms, 20 * ms],
            ["%fusion.3 = f32[] fusion()", 30 * ms, 40 * ms],
            ["%fusion.4 = f32[] fusion()", 70 * ms, 20 * ms],
            ["%fusion.5 = f32[] fusion()", 90 * ms, 6 * ms],
            ["%copy.9 = f32[] copy()", 96 * ms, 4 * ms],
            ["%fusion.1 = f32[] fusion()", 101 * ms, 8 * ms],     # jit_other's
            ["%fusion.1 = f32[] fusion()", 110 * ms, 10 * ms],
            ["%fusion.3 = f32[] fusion()", 140 * ms, 40 * ms]]}]}
    scopes = {"fusion.1": "corrupt", "fusion.2": "jvp(forward)/while/local_track",
              "fusion.3": "transpose(jvp(forward))/while/rematted_computation/"
                          "local_track",
              "fusion.4": "transpose(jvp(forward))/while",
              "fusion.5": "optimizer", "while.1": "jvp(forward)"}
    obs = {"program": "train_step", "scopes": scopes, "trace": {"plane": plane}}
    runs, by_scope = span_readers.scope_seconds(obs)
    assert runs == 2 and by_scope[""] == pytest.approx(0.004)
    assert _layer_metric("fwd_device_ms.train")(obs) == pytest.approx(20.0)
    assert _layer_metric("bwd_device_ms.train")(obs) == pytest.approx(50.0)
    assert _layer_metric("opt_device_ms.train")(obs) == pytest.approx(3.0)
    assert _layer_metric("local_track_device_ms.train")(obs) \
        == pytest.approx(50.0)
    assert _layer_metric("scan_save_device_ms.train")(obs) \
        == pytest.approx(10.0)
    assert _layer_metric("attention_device_ms.train")(obs) == 0.0
    assert _layer_metric("scope_coverage_pct.train")(obs) \
        == pytest.approx(100.0 * 146 / 150)
    scopes_line, missed_line, scan_line = span_readers.scope_table(
        obs, span_readers.TRAIN_FORWARD | span_readers.TRAIN_UPDATE)
    assert scopes_line.startswith(
        "scopes, device ms a run: transpose(jvp(forward))/while/"
        "rematted_computation/local_track 40.000; corrupt 10.000")
    assert "copy.9 [no op_name] 2.000" in missed_line
    assert scan_line.endswith(
        "fusion.4 [transpose(jvp(forward))/while] 10.000")


def test_readers_print_nothing_and_log_the_table_when_asked(recorded, capsys,
                                                            caplog):
    obs = _obs(recorded, "pretrain-base-dense")
    value = _layer_metric("scope_coverage_pct.train")(obs)
    assert capsys.readouterr().out == "" and not caplog.records
    with caplog.at_level("INFO", logger="benchmark.span_readers"):
        assert _layer_metric("scope_coverage_pct.train")(obs) == value
    assert capsys.readouterr().out == ""
    assert [r.getMessage().split(":")[0] for r in caplog.records] == [
        "scopes, device ms a run", "outside every scope, device ms a run",
        "in the scan's body outside every block, device ms a run"]


def test_a_recorder_that_dropped_spans_reads_as_nothing(monkeypatch):
    """A window with more spans than the recorder holds: a sum over what
    is left would under-read, so every host reader leaves its metric out."""
    from proteinbert_tpu.obs import tracing

    small = tracing.SpanCollector(capacity=4)
    monkeypatch.setattr(tracing, "_RECORDER", small)
    for batch in range(4):
        with tracing.span("serve.launch", small, batch=batch):
            pass
    assert _layer_metric("compiles_in_window.tput")({}) == 0
    assert _layer_metric("pack_ms.tput")({}) == 0.0
    with tracing.span("serve.launch", small, batch=4):
        pass
    assert small.dropped == 1
    for metric in ("compiles_in_window.tput", "pack_ms.tput",
                   "assemble_ms.lat", "data_wait_ms.train"):
        assert _layer_metric(metric)({}) is None


# ------------------------------------------------- the fixture from a v5e

@pytest.mark.parametrize("metric", NEW)
def test_reader_reads_the_recorded_fixture(recorded, metric):
    value = _layer_metric(metric)(_obs(recorded, _cell_of(metric)))
    assert value is not None and np.isfinite(value) and value >= 0.0
    if metric.endswith("coverage_pct") or "coverage_pct." in metric:
        assert 50.0 < value <= 100.0
    if "compiles_in_window" in metric:
        assert value == 0


@pytest.mark.parametrize("metric", NEW)
def test_reader_finds_nothing_in_an_empty_obs(metric, monkeypatch):
    monkeypatch.setattr(span_readers, "recorded", lambda obs: [])
    assert _layer_metric(metric)({}) is None


def test_a_program_without_the_spine_reads_as_nothing(recorded, monkeypatch):
    """The parent commit under this PR's benchmark files: no recorder, no
    scope map. Every reader leaves its metric out and none raises."""
    monkeypatch.setattr(span_readers, "_spine", lambda: None)
    obs = _obs(recorded, "pretrain-base-dense")
    del obs["spans"], obs["scopes"]
    for metric in NEW:
        assert _layer_metric(metric)(obs) is None


def test_the_recorded_train_step_adds_up(recorded):
    obs = _obs(recorded, "pretrain-base-dense")
    runs, by_op, scopes = span_readers.op_seconds(obs)
    assert runs == 3 and len(scopes) == 1       # one executable, one map
    assert all(name in scopes[exe] for exe, name in by_op
               if scopes[exe].get(name))
    per_step = 1e3 * sum(by_op.values()) / runs
    parts = sum(span_readers.train_part_ms(obs, part)
                for part in ("fwd", "bwd", "opt"))
    coverage = span_readers.train_coverage_pct(obs)
    assert parts == pytest.approx(per_step * coverage / 100.0, rel=1e-9)
    assert all(span_readers.train_part_ms(obs, part) > 0.0
               for part in ("fwd", "bwd", "opt"))
    inside = (span_readers.scope_ms(obs, "local_track")
              + span_readers.scope_ms(obs, "attention")
              + span_readers.scan_save_ms(obs))
    assert 0.0 < inside < per_step


def test_the_recorded_spans_nest_as_the_loop_does(recorded):
    spans = recorded["pretrain-base-dense"]["spans"]
    steps = {s["id"]: s for s in spans if s["name"] == "train.step"}
    assert len(steps) == 3
    for name in ("train.data_wait", "train.put", "train.dispatch"):
        inside = [s for s in spans if s["name"] == name]
        assert len(inside) == 3 and all(s["parent"] in steps for s in inside)
    serve = recorded["serve-base-sat"]["spans"]
    batches = {s["ids"]["batch"] for s in serve if "batch" in s["ids"]}
    assert len(batches) == 3
    for name in ("serve.assemble", "serve.wait_slot", "serve.place",
                 "serve.launch", "serve.fetch", "serve.fan_out", "serve.seal"):
        assert {s["ids"]["batch"] for s in serve if s["name"] == name} \
            == batches, name


def _runs(got):
    return sorted((s, s + d) for name, s, d in got["plane"]["lines"][1]["events"]
                  if got["program"] in name)


def _annotated(got, name):
    return [(s, s + d) for n, s, d in got["annotations"] if n == name]


def test_each_packed_batch_runs_between_its_launch_and_its_fetch(recorded):
    """On the xplane's own clock: every run of `_packed_encode_batch` is
    preceded by an assemble / wait_slot / place / launch of its own and
    followed by the end of a fetch / fan_out / seal of its own."""
    got = recorded["serve-base-sat"]
    runs = _runs(got)
    assert len(runs) == 3
    before = None
    for name in ("serve.assemble", "serve.wait_slot", "serve.place",
                 "serve.launch"):
        spans = _annotated(got, name)
        # the last one that started before the run: one per run, in order
        last = [max(s for s, e in spans if s <= run[0]) for run in runs]
        assert last == sorted(set(last)), name
        assert before is None or all(a <= b for a, b in zip(before, last))
        before = last
    after = [run[1] for run in runs]
    for name in ("serve.fetch", "serve.fan_out", "serve.seal"):
        spans = _annotated(got, name)
        first = [min(e for s, e in spans if e >= run[1]) for run in runs]
        assert first == sorted(set(first)), name
        assert all(a <= b for a, b in zip(after, first))
        after = first


@pytest.mark.parametrize("cell,name", [("serve-base-sat", "serve.launch"),
                                       ("serve-base-sat", "serve.seal"),
                                       ("pretrain-base-dense", "train.dispatch")])
def test_the_recorder_and_the_xplane_agree_after_one_offset(recorded, cell,
                                                            name):
    """The recorder's dump loads beside the xplane: its spans are the
    xplane's annotations of the same name, one clock offset apart."""
    got = recorded[cell]
    mine = sorted((s["start_ns"], s["end_ns"]) for s in got["spans"]
                  if s["name"] == name)
    theirs = _annotated(got, name)
    assert len(mine) == 3 and len(theirs) >= 3
    slack = 50_000      # ns: the annotation wraps the span's own clock reads
    matches = 0
    for start, _ in theirs:
        offset = start - mine[0][0]
        if all(any(abs(s + offset - a) < slack and abs(e + offset - b) < slack
                   for a, b in theirs) for s, e in mine):
            matches += 1
    assert matches == 1


# ---------------------------------------------------- end to end, rehearsed

@pytest.mark.parametrize("tool,cell,expected", [
    (["benchmark.run", "--trace", "1"], "pretrain-large-dense",
     {"data_wait_ms.train", "compiles_in_window.train"}),
    (["benchmark.scope_table"], "serve-base-steady",
     {"compiles_in_window.lat", "pack_ms.lat", "assemble_ms.lat",
      "slot_wait_ms.lat", "finalize_host_ms.lat"})])
def test_a_traced_rehearsal_prints_the_host_span_metrics(tool, cell, expected):
    done = subprocess.run(
        [sys.executable, "-m", *tool, "--workload", cell,
         "--seed", "2147483999", "--seconds", "1", "--rehearse",
         *(["--held-out"] if cell == "serve-base-steady" else [])],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert expected <= set(line["metrics"])
    assert line["metrics"]["compiles_in_window." + (
        "train" if "pretrain" in cell else "lat")]["value"] == 0
    for name in expected:
        assert np.isfinite(line["metrics"][name]["value"])
