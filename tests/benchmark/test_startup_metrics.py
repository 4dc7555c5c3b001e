"""The start-up metrics PR 39 added (`benchmark/startup_readers.py`): each
reader on hand-made records and on the recorded fixture
(`fixtures/startup_v5e.json`, two runs a cell on one compile cache, made by
`benchmark.record_startup_fixture` on the chip), what a parent commit and a
filled collector read, the manifest's new entries found BY NAME, and what
`test_zaya_cell.py::test_the_manifest_lists_the_cell_its_configuration_
and_its_metrics` held, every line of it (tests/conftest.py marks that
test because of ONE line, the whole set of metrics that list its cell: the
new metrics list every cell)."""

import importlib.util
import json
import os

import pytest

from benchmark import startup_readers as readers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TRAIN = ["pretrain-base-dense", "pretrain-large-dense",
         "pretrain-glm47flash-packed8k", "pretrain-large-fsdp4"]
SERVE = ["serve-base-sat", "serve-ling3flash-sat", "serve-zaya1-8b-sat"]
# name -> (unit, layer, the end-to-end metric it moves, the cells it lists)
NEW = {
    "startup_compile_s": ("s", "Device", "setup_s", TRAIN + SERVE),
    "startup_compiles": ("count", "Device", "setup_s", TRAIN + SERVE),
    "startup_cache_load_s": ("s", "Device", "setup_s", TRAIN + SERVE),
    "startup_trace_lower_s": ("s", "Model", "setup_s", TRAIN + SERVE),
    "startup_warmup_s": ("s", "Dispatcher (serve/dispatch.py)", "setup_s", SERVE),
    "scope_map_s.train": ("s", "Tracing (obs/tracing.py)", "train_residues_per_s", TRAIN),
    "scope_map_s.tput": ("s", "Tracing (obs/tracing.py)", "embed_residues_per_s", SERVE),
}


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _fixture():
    with open(os.path.join(ROOT, "tests", "benchmark", "fixtures",
                           "startup_v5e.json")) as f:
        return json.load(f)


def _layer_metric(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("lm_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _span(name, start_ms, ms, tid=1, span_id=0, parent=None, **ids):
    return {"name": name, "start_ns": int(start_ms * 1e6),
            "end_ns": int((start_ms + ms) * 1e6), "tid": tid, "id": span_id,
            "parent": parent, "ids": ids}


# ------------------------------------------------------------ the manifest

@pytest.mark.parametrize("name", sorted(NEW))
def test_the_manifest_lists_the_metric_by_name_with_a_reader(name):
    unit, layer, moves, cells = NEW[name]
    by_name = {m["name"]: m for m in _manifest()["per_layer"]}
    entry = by_name[name]
    assert sorted(entry.pop("workloads")) == sorted(cells)
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_span", "layer": layer, "moves": moves}
    read = _layer_metric(name)
    assert callable(read) and read({"startup_spans": [], "spans": []}) is None


def test_the_scope_maps_seconds_are_read_after_every_reader_that_asks():
    names = [m["name"] for m in _manifest()["per_layer"]]
    asks = [n for n in names if n.endswith(("_device_ms.train", "_device_ms.tput",
                                            "_roofline", "coverage_pct"))
            or "scope_coverage_pct" in n]
    assert asks
    for mine in ("scope_map_s.train", "scope_map_s.tput"):
        assert all(names.index(n) < names.index(mine) for n in asks)


def test_the_metrics_that_list_the_second_served_decoders_cell():
    """What `test_zaya_cell.py::test_the_manifest_lists_the_cell_its_
    configuration_and_its_metrics` held as a whole set: the cell's own
    six and the twenty it shares, and now the six of this PR, no other."""
    from tests.benchmark import test_zaya_cell as zaya

    listed = {m["name"] for m in _manifest()["per_layer"]
              if zaya.CELL in m.get("workloads", ())}
    mine = {n for n, (_, _, _, cells) in NEW.items() if zaya.CELL in cells}
    assert len(mine) == 6
    assert listed == zaya.SHARED | set(zaya.NEW) | mine


# The rest of that assertion, line for line and by name: tests/conftest.py
# marks the whole test, so nothing it held may go unheld.

def test_the_second_served_decoders_cell_and_configuration_stand_as_written():
    from tests.benchmark import test_zaya_cell as zaya

    m = zaya._manifest()
    cell = m["workloads"][zaya.CELL]
    assert cell == {"name": zaya.CELL, "config": zaya.CONFIG, "traffic": zaya.MIX,
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "RATE" not in cell["why"]
    rate = zaya._json("benchmark", "traffic", zaya.MIX + ".json")["arrivals"]["rate_per_s"]
    assert f"Poisson {rate:g}/s = 2.0 x the knee" in cell["why"]
    entry = m["configs"][zaya.CONFIG]
    assert entry["file"] == f"benchmark/configs/{zaya.CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers"] and len(entry["why"]) <= 200
    assert entry["source"].startswith(
        "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json")
    assert "catalog row ZAYA1-8B" in entry["source"]
    tput = m["end_to_end"]["embed_residues_per_s"]
    assert zaya.CELL in tput["workloads"] and tput["bound"] == 0.07


def _zayas(which):
    from tests.benchmark import test_zaya_cell as zaya

    return sorted(getattr(zaya, which))


@pytest.mark.parametrize("name", _zayas("NEW"))
def test_the_second_served_decoders_own_metric_stands_as_written(name):
    from tests.benchmark import test_zaya_cell as zaya

    unit, better, layer = zaya.NEW[name]
    assert zaya._manifest()["per_layer"][name] == {
        "name": name, "unit": unit, "better": better, "source": "device_trace",
        "layer": layer, "moves": "embed_residues_per_s", "workloads": [zaya.CELL]}


@pytest.mark.parametrize("name", _zayas("SHARED"))
def test_a_metric_the_second_served_decoder_shares_lists_its_cell(name):
    from tests.benchmark import test_zaya_cell as zaya

    assert zaya.CELL in zaya._manifest()["per_layer"][name]["workloads"]


# --------------------------------------------------- records made by hand

def _start():
    """A start as the listener tells it: a compile, a load with its
    retrieval inside, the lowerings, a trace with two traces inside it
    and one on another thread, and two warm-ups."""
    return [
        _span("startup.warmup", 0, 900, span_id=1, cls=4, kind="embed"),
        _span("jax.trace", 10, 40, span_id=2, parent=1, program="inner"),
        _span("jax.trace", 60, 20, span_id=3, parent=1, program="inner2"),
        _span("jax.trace", 5, 100, span_id=4, parent=1, program="outer"),
        _span("jax.trace", 50, 30, tid=2, span_id=5, program="elsewhere"),
        _span("jax.lower", 110, 50, span_id=6, parent=1, program="jit(f)"),
        _span("jax.compile", 170, 700, span_id=7, parent=1, program="jit(f)", cached=0),
        _span("startup.warmup", 1000, 80, span_id=8, cls=2, kind="embed"),
        _span("jax.lower", 1005, 10, span_id=9, parent=8, program="jit(f)"),
        _span("jax.cache_load", 1020, 30, span_id=10, parent=8),
        _span("jax.compile", 1018, 45, span_id=11, parent=8, program="jit(f)", cached=1),
    ]


def test_each_reader_sums_its_own_leaf_names():
    obs = {"startup_spans": _start()}
    assert readers.compile_s(obs) == pytest.approx(0.700)
    assert readers.compiles(obs) == 1
    assert readers.cache_load_s(obs) == pytest.approx(0.045)
    # two lowerings, the outer trace alone on thread 1, the one on thread 2
    assert readers.trace_lower_s(obs) == pytest.approx(0.060 + 0.100 + 0.030)
    assert readers.warmup_s(obs) == pytest.approx(0.980)


def test_a_warm_start_reads_zero_compiles_not_nothing():
    warm = [s for s in _start() if not (s["name"] == "jax.compile"
                                        and not s["ids"]["cached"])]
    obs = {"startup_spans": warm}
    assert readers.compiles(obs) == 0 and readers.compile_s(obs) == 0.0
    training = [s for s in warm if s["name"] != "startup.warmup"]
    assert readers.warmup_s({"startup_spans": training}) is None


def test_the_scope_maps_seconds_come_from_the_recorder_and_read_zero_for_none():
    window = [_span("train.step", 0, 5, span_id=1, step=1)]
    maps = [_span("tracing.program_scopes", 9000, 2500, span_id=2,
                  program="train_step", source="stored"),
            _span("tracing.program_scopes", 12000, 500, span_id=3,
                  program="other", source="own_compile")]
    assert readers.scope_map_s({"spans": window}) == 0.0
    assert readers.scope_map_s({"spans": window + maps}) == pytest.approx(3.0)
    assert readers.scope_map_s({"spans": []}) is None


def test_a_program_without_the_collector_reads_nothing(monkeypatch):
    monkeypatch.setattr(readers, "_spine", lambda: None)
    for read in (readers.compile_s, readers.compiles, readers.cache_load_s,
                 readers.trace_lower_s, readers.warmup_s, readers.scope_map_s):
        assert read({}) is None


def test_a_collector_that_was_filled_reads_nothing(monkeypatch):
    class Filled:
        STARTUP_CAPACITY = 3

        @staticmethod
        def startup_spans():
            return _start()[:3]

    monkeypatch.setattr(readers, "_spine", lambda: Filled)
    assert readers.started({}) == []
    assert readers.compile_s({}) is None and readers.trace_lower_s({}) is None


# ----------------------------------------------------- the recorded fixture

CELLS = {"pretrain-base-dense": "scope_map_s.train", "serve-base-sat": "scope_map_s.tput"}


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("run", ["first", "second"])
def test_the_readers_give_what_the_recorded_run_printed(cell, run):
    got = _fixture()[cell][run]
    obs = {"startup_spans": got["startup_spans"], "spans": got["spans"]}
    mine = {n for n, (_, _, _, cells) in NEW.items() if cell in cells}
    assert mine <= set(got["metrics"])
    for name in mine:
        if name.startswith("scope_map_s"):
            # the recorder's window spans were not kept: the maps' alone
            want = sum(readers._seconds(s) for s in got["spans"])
            assert got["metrics"][name] == pytest.approx(want)
        else:
            assert _layer_metric(name)(obs) == pytest.approx(got["metrics"][name])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_first_recorded_run_compiled_and_the_second_loaded(cell):
    first, second = (_fixture()[cell][run] for run in ("first", "second"))
    assert first["metrics"]["startup_compiles"] > 0
    assert first["metrics"]["startup_compile_s"] > 0
    assert second["metrics"]["startup_compiles"] == 0
    assert second["metrics"]["startup_compile_s"] == 0
    assert second["metrics"]["startup_cache_load_s"] > 0
    # every load's retrieval lies inside its `jax.compile` record
    spans = second["startup_spans"]
    loads = [s for s in spans if s["name"] == "jax.compile" and s["ids"]["cached"]]
    inside = [r for r in spans if r["name"] == "jax.cache_load"
              if any(c["tid"] == r["tid"] and c["start_ns"] <= r["start_ns"]
                     and r["end_ns"] <= c["end_ns"] + 1e6 for c in loads)]
    assert loads and len(inside) == len(
        [r for r in spans if r["name"] == "jax.cache_load"])
    names = {s["name"] for s in spans}
    assert names <= {"jax.compile", "jax.cache_load", "jax.lower", "jax.trace",
                     "startup.warmup", "startup.backend", "startup.init_state",
                     "startup.restore", "startup.first_step"}
    assert ("startup.warmup" in names) == cell.startswith("serve")
