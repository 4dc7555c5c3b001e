"""The serve cells after row classes (PR 27): the whole window's tail and
the typical quarter-second's beside it on made arrays, the backlog's growth
and the knee sweep's decision, the packed program's runs told apart by
executable id and reckoned at their own class, fill and MFU from the
window's class counts, and the serve cells rehearsed on the CPU with every
metric the manifest lists for them that a CPU run can read."""

import ast
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from benchmark import find_knee, flops, readers, span_readers
from benchmark.run import _layer_metric

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SERVE = {"tput": "serve-base-sat", "lat": "serve-base-steady"}
MS = 1_000_000


def _manifest():
    """BENCHMARK.json with the held-out steady cell's entries beside it
    (`benchmark/held_out/`, PERF.md section 7): its readers stay tested."""
    from benchmark.run import load_manifest

    return load_manifest(held_out=True)


# ---------------------------------------- the typical quarter-second's p95

def _window(seconds=10.0, rate=2000, seed=0):
    """Due times at a fixed rate and latencies of 80-140 ms: every
    interval's p95 is ~137 ms."""
    rng = np.random.default_rng(seed)
    due = np.arange(int(seconds * rate)) / rate
    return due, 0.080 + 0.060 * rng.random(len(due))


def _stalled(due, latency, at=4.2, stall=0.3, recover=0.7):
    """One process stall at `at`: nothing is answered for `stall`
    seconds, and what queued behind it drains by `at + recover`."""
    extra = np.clip(stall * (1.0 - (due - at) / recover), 0.0, None)
    return latency + np.where(due >= at, extra, 0.0)


def _obs(due, latency, seconds=10.0):
    return {"latency_s": latency, "due_s": due, "seconds": seconds}


def _whole_p95_ms(latency):
    """The steady cell's judged number, as `drivers/serve.py` takes it."""
    return float(np.percentile(latency, 95) * 1e3)


def test_stalls_move_the_judged_tail_and_not_the_typical_intervals():
    """Three collections of 0.1 s with their backlog and one stall of
    0.3 s: a quarter of the window. The tail over ALL requests, which is
    what is judged, moves by a tenth and more; the typical
    quarter-second's, recorded beside it, by under 2 %; and the stalled
    seconds are counted."""
    due, latency = _window()
    typical = _layer_metric("latency_p95_typical_ms.lat")
    quiet = typical(_obs(due, latency))
    hit = latency
    for at, stall in ((2.6, 0.1), (4.2, 0.3), (5.3, 0.1), (7.9, 0.1)):
        hit = _stalled(due, hit, at, stall)
    assert typical(_obs(due, hit)) == pytest.approx(quiet, rel=0.02)
    assert _whole_p95_ms(hit) > 1.1 * _whole_p95_ms(latency)
    assert readers.latency_p95_ms(_obs(due, hit)) == _whole_p95_ms(hit)
    assert _whole_p95_ms(latency) == pytest.approx(quiet, rel=0.02)
    stalled = _layer_metric("stalled_seconds.lat")
    assert stalled(_obs(due, latency)) == 0.0
    assert stalled(_obs(due, _stalled(due, latency))) == 0.75  # 4.0-4.75 s
    assert 0.75 <= stalled(_obs(due, hit)) <= 2.0
    # the median of whole SECONDS' p95s (the form ISSUE 27 asked for
    # first) flips once half the seconds hold a stall or its backlog:
    # six do here, as on the chip
    per_second = readers.interval_p95s_ms(hit, due, 10.0, width=1.0)
    assert (per_second > 1.05 * quiet).sum() == 6
    assert np.median(per_second) > 1.05 * quiet


def test_the_steady_cell_judges_the_tail_of_every_request():
    """The driver's own line: `embed_latency_p95_ms` is the percentile of
    the whole `latency` array, and no reader of intervals."""
    import inspect

    from benchmark.drivers import serve

    source = inspect.getsource(serve.measure)
    assert 'e2e["embed_latency_p95_ms"] = float(np.percentile(latency, 95)' in source
    assert "typical" not in source and "interval" not in source


@pytest.mark.parametrize("reader", ["whole", "latency_p95_typical_ms.lat",
                                    "stalled_seconds.lat"])
def test_an_unanswered_request_counts_with_the_time_it_waited(reader):
    """The driver's own arithmetic: a request never answered has waited
    from when it was due to when the run gave up on it."""
    due, latency = _window()
    closed, ok = 30.0, np.ones(len(due), bool)
    ok[(due >= 6.0) & (due < 7.0)] = np.arange(2000) % 50 != 0   # 2 % lost
    latency = np.where(ok, due + latency, closed) - due
    per_interval = readers.interval_p95s_ms(
        np.where((due >= 6.0) & (due < 7.0) & (np.arange(len(due)) % 10 == 0),
                 closed - due, latency), due, 10.0)
    assert (per_interval[24:28] > 23_000).all()
    assert (np.delete(per_interval, range(24, 28)) < 140).all()
    everywhere = np.where(np.arange(len(due)) % 10 != 0, latency, closed - due)
    if reader == "whole":
        read = _whole_p95_ms
    else:
        read = lambda lat: _layer_metric(reader)(_obs(due, lat))  # noqa: E731
    in_one_second, in_all = read(latency), read(everywhere)
    if reader == "stalled_seconds.lat":
        assert (in_one_second, in_all) == (0.0, 0.0)
        lost_a_tenth = np.where((due >= 6.0) & (due < 7.0)
                                & (np.arange(len(due)) % 10 == 0),
                                closed - due, latency)
        assert read(lost_a_tenth) == 1.0
    else:
        # 40 of 20,000 lost is under the percentile; a tenth is not
        assert in_one_second < 140 and in_all > 20_000


@pytest.mark.parametrize("seconds,whole", [(3.6, 14), (10.0, 40), (0.25, 1)])
def test_a_partial_last_interval_is_left_out(seconds, whole):
    due, latency = _window(seconds)
    part = due >= readers.INTERVAL_S * whole
    latency = np.where(part, 9.0, latency)
    assert len(readers.interval_p95s_ms(latency, due, seconds)) == whole
    assert readers.typical_p95_ms(_obs(due, latency, seconds)) < 140


def test_a_window_under_one_interval_has_no_typical_interval():
    due, latency = _window(0.2)
    assert readers.typical_p95_ms(_obs(due, latency, 0.2)) is None
    assert readers.stalled_seconds(_obs(due, latency, 0.2)) is None
    assert readers.latency_p95_ms(_obs(due, latency, 0.2)) \
        == pytest.approx(1e3 * np.percentile(latency, 95))


# ------------------------------------------------- the backlog's growth

def _served(rate, capacity, seconds=10.0, base=0.1):
    """Due times at `rate`; a server that answers `capacity` a second:
    under it every request takes `base`, past it the queue grows."""
    due = np.arange(int(seconds * rate)) / rate
    done = np.maximum(due + base, base + np.arange(len(due)) / capacity)
    return due, done - due


@pytest.mark.parametrize("rate,capacity,growth", [
    (2000, 2800, 0.0), (2800, 2800, 0.0), (3000, 2800, 200.0),
    (3400, 2800, 600.0)])
def test_backlog_growth_reads_the_rate_less_what_the_server_carries(
        rate, capacity, growth):
    due, latency = _served(rate, capacity)
    assert readers.backlog_growth_per_s(latency, due, 10.0) \
        == pytest.approx(growth, abs=0.03 * rate * 0.25)


@pytest.mark.parametrize("at", [9.85, 8.4, 6.0])
def test_a_stall_on_the_close_does_not_read_as_a_growing_backlog(at):
    """A 0.1 s stall with 0.5 s of backlog behind it, on the close, in
    the last quarter or in the third: what is left at close grows by half,
    the growth stays within 2 % of the rate."""
    due, latency = _served(2250, 2800)
    hit = _stalled(due, latency, at=at, stall=0.1, recover=0.5)
    growth = readers.backlog_growth_per_s(hit, due, 10.0)
    assert abs(growth) <= 0.02 * 2250
    if at == 9.85:
        left = lambda lat: int((due + lat > 10.0).sum())  # noqa: E731
        assert left(hit) > 1.4 * left(latency)


# ------------------------------------- runs of two classes under one name

PROGRAM = "_packed_encode_batch"
SMALL, LARGE = 64, 128
MODEL = {"local_dim": 512, "global_dim": 512, "num_annotations": 8943,
         "vocab_size": 26, "num_heads": 8, "key_dim": 64, "num_blocks": 6,
         "narrow_kernel": 9, "wide_kernel": 9, "wide_dilation": 5}


def _classes(ladder=(SMALL, LARGE)):
    return {cls: {"flops": flops.forward_flops(MODEL, cls, 1024, 8, heads=False),
                  "min_bytes": flops.embed_min_bytes(MODEL, cls, 1024, 8)}
            for cls in ladder}


def _launch(k, start_ms, cls):
    return {"name": "serve.launch", "start_ns": start_ms * MS,
            "end_ns": (start_ms + 1) * MS, "tid": 1, "id": k, "parent": None,
            "ids": {"batch": k, "rows": cls - 3, "cls": cls}}


def _two_class_obs(order=(SMALL, SMALL, LARGE, SMALL, LARGE), ahead=1,
                   ms={SMALL: 40, LARGE: 90}, exe={SMALL: "11", LARGE: "22"},
                   head_exe=None):
    """`ahead` runs of the small class launched before the recorder was
    live, then one run a launch in `order`: the same program name, one
    executable id a class, the same instruction names in both
    executables with other scopes behind them."""
    modules, ops, at = [], [], 0
    for k, cls in enumerate((SMALL,) * ahead + tuple(order)):
        d = ms[cls] * MS
        run_exe = head_exe if head_exe and k < ahead else exe[cls]
        modules.append([f"jit_{PROGRAM}({run_exe})", at, d])
        ops.append(["%fusion.1 = bf16[] fusion()", at, d // 2])
        ops.append(["%fusion.2 = bf16[] fusion()", at + d // 2, d // 4])
        ops.append(["%copy.3 = bf16[] copy()", at + 3 * d // 4, d // 4])
        at += d + 2 * MS
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops}]}
    spans = [_launch(k, 5 * k, cls) for k, cls in enumerate(order)]
    scopes = {SMALL: {"fusion.1": "encode/while/local_track",
                      "fusion.2": "encode/while/attention", "copy.3": "pool"},
              LARGE: {"fusion.1": "encode/while/attention",
                      "fusion.2": "encode/while/local_track"}}
    return {"program": PROGRAM, "trace": {"plane": plane}, "spans": spans,
            "class_scopes": scopes, "classes": _classes(),
            "peaks": flops.peaks_for("TPU v5 lite")}


@pytest.mark.parametrize("ahead", [0, 1, 2, 3])
def test_runs_are_split_by_executable_id_and_take_the_launches_class(ahead):
    obs = _two_class_obs(ahead=ahead)
    got = span_readers.class_runs(obs)
    assert [cls for cls, _ in got] == [SMALL] * ahead + [
        SMALL, SMALL, LARGE, SMALL, LARGE]
    assert [round(1e3 * t) for _, t in got][-2:] == [40, 90]


def test_an_executable_no_launch_told_has_no_class():
    """Two runs at the head of the trace of an executable that never ran
    again: launched before the recorder was live, class untold, left out
    of the roofline and of the scope sums."""
    order = (SMALL, LARGE, LARGE, SMALL, LARGE, SMALL, SMALL, LARGE)
    obs = _two_class_obs(order=order, ahead=2, head_exe="33")
    assert [cls for cls, _ in span_readers.class_runs(obs)] == [
        None, None, *order]
    without = _two_class_obs(order=order, ahead=0)
    assert readers.packed_roofline_pct(obs) \
        == pytest.approx(readers.packed_roofline_pct(without))
    runs, _ = span_readers.scope_seconds(obs)
    assert runs == len(order)


@pytest.mark.parametrize("fault", ["untold", "out_of_step", "shared_class"])
def test_a_join_that_is_not_sound_reads_as_nothing(fault, caplog):
    """More runs whose executable no launch told, or more launches out
    of step with their executable's class, than a window's edges
    explain, or two executables told one class: no share over the runs
    that did join is reported as the window's, and the counts are
    logged."""
    order = (SMALL, SMALL, LARGE, SMALL, LARGE, LARGE, SMALL, LARGE,
             SMALL, SMALL, LARGE, LARGE, SMALL, LARGE, LARGE, SMALL)
    obs = _two_class_obs(order=order, ahead=0)
    modules = obs["trace"]["plane"]["lines"][0]["events"]
    if fault == "untold":        # four runs whose launch spans were lost
        at = modules[-1][1] + modules[-1][2]
        modules += [[f"jit_{PROGRAM}(44)", at + k * 50 * MS, 40 * MS]
                    for k in range(1, 5)]
    elif fault == "out_of_step":
        for k in (2, 4, 5, 7):
            obs["spans"][k]["ids"]["cls"] = SMALL
    else:                        # every launch says the small class
        for span in obs["spans"]:
            span["ids"]["cls"] = SMALL
    with caplog.at_level("WARNING", logger="benchmark.span_readers"):
        assert span_readers.class_runs(obs) is None
    assert "NOT sound" in caplog.text and "16 launches" in caplog.text
    for metric in ("packed_encode_roofline", "packed_local_track_device_ms",
                   "packed_attention_device_ms", "packed_scope_coverage_pct"):
        assert _layer_metric(metric)(obs) is None
    sound = _two_class_obs(order=order, ahead=0)
    assert [cls for cls, _ in span_readers.class_runs(sound)] == list(order)


def test_the_roofline_is_each_runs_own_class_over_the_same_runs_time():
    obs = _two_class_obs()
    least = {cls: flops.roofline(c["flops"], c["min_bytes"], obs["peaks"])["min_s"]
             for cls, c in obs["classes"].items()}
    expected = 100.0 * (4 * least[SMALL] + 2 * least[LARGE]) / (
        4 * 0.040 + 2 * 0.090)
    assert _layer_metric("packed_encode_roofline")(obs) == pytest.approx(expected)
    # the old reader divided the largest class's work by the MEAN run
    old = dict(obs, trace={"plane": obs["trace"]["plane"], "programs": {
        PROGRAM: [6, 4 * 0.040 + 2 * 0.090]}},
        call_flops=obs["classes"][LARGE]["flops"],
        call_min_bytes=obs["classes"][LARGE]["min_bytes"])
    assert readers.program_roofline_pct(old) > 1.3 * expected


def test_a_trace_of_the_largest_class_alone_reads_what_the_old_reader_read():
    obs = _two_class_obs(order=(LARGE,) * 4, ahead=0)
    old = dict(obs, trace={"plane": obs["trace"]["plane"],
                           "programs": {PROGRAM: [4, 4 * 0.090]}},
               call_flops=obs["classes"][LARGE]["flops"],
               call_min_bytes=obs["classes"][LARGE]["min_bytes"])
    assert _layer_metric("packed_encode_roofline")(obs) \
        == pytest.approx(readers.program_roofline_pct(old), rel=1e-12)
    assert _layer_metric("batch_device_ms.tput")(old) == pytest.approx(90.0)


def test_launches_without_a_class_read_as_nothing():
    """A parent whose `serve.launch` carries no `cls=`: the class of a run
    is never assumed, so the readers leave their metrics out."""
    obs = _two_class_obs()
    for s in obs["spans"]:
        del s["ids"]["cls"]
    assert span_readers.class_runs(obs) is None
    for metric in ("packed_encode_roofline", "packed_local_track_device_ms",
                   "packed_attention_device_ms", "packed_scope_coverage_pct"):
        assert _layer_metric(metric)(obs) is None


def test_operations_are_joined_to_the_map_of_their_own_executable():
    """`fusion.1` is the local track in the small executable and
    attention in the large one; `copy.3` has a scope in the small one
    alone. Device ms a batch: summed over every class's runs, over the
    batches run."""
    obs = _two_class_obs()
    local = (4 * 20 + 2 * 22.5) / 6
    attention = (4 * 10 + 2 * 45) / 6
    assert _layer_metric("packed_local_track_device_ms")(obs) \
        == pytest.approx(local)
    assert _layer_metric("packed_attention_device_ms")(obs) \
        == pytest.approx(attention)
    named = 4 * 40 + 2 * 67.5
    assert _layer_metric("packed_scope_coverage_pct")(obs) \
        == pytest.approx(100.0 * named / (4 * 40 + 2 * 90))


def test_disagreeing_launches_lose_to_the_majority():
    """One launch span out of step (a batch the trace lost): the
    executable keeps the class most of its runs were launched with."""
    order = (SMALL, LARGE) * 6
    obs = _two_class_obs(order=order, ahead=0)
    obs["spans"][4]["ids"]["cls"] = LARGE
    assert [cls for cls, _ in span_readers.class_runs(obs)] == list(order)


# ------------------------------------------ fill and MFU from the counters

@pytest.mark.parametrize("suffix", ["tput", "lat"])
def test_batch_fill_is_residues_over_the_positions_really_computed(suffix):
    counts = {64: 10, 128: 2}
    positions = sum(cls * 1024 * n for cls, n in counts.items())
    obs = {"batches": 12, "batch_class_counts": counts,
           "batched_positions": positions,
           "residues_in_batches": int(0.65 * positions)}
    assert _layer_metric(f"batch_fill_pct.{suffix}")(obs) \
        == pytest.approx(65.0, abs=1e-4)
    # the old denominator, 512 rows a batch, would have read a fifth of it
    assert 100.0 * obs["residues_in_batches"] / (12 * 512 * 1024) < 13.0
    assert _layer_metric(f"batch_fill_pct.{suffix}")({"batches": 3}) is None


@pytest.mark.parametrize("suffix", ["tput", "lat"])
def test_serve_mfu_is_the_windows_batches_at_their_classes_over_the_window(suffix):
    peaks = flops.peaks_for("TPU v5 lite")
    classes = _classes((64, 128, 256, 512))
    counts = {64: 100, 256: 10, 512: 5}
    obs = {"batch_class_counts": counts, "classes": classes, "peaks": peaks,
           "window_s": 10.0}
    work = 100 * classes[64]["flops"] + 10 * classes[256]["flops"] \
        + 5 * classes[512]["flops"]
    read = _layer_metric(f"mfu_pct.{suffix}")
    assert read(obs) == pytest.approx(100.0 * work / 10.0 / 197e12)
    assert 0.0 < read(obs) < 100.0
    # device time is linear in rows: 64 rows cost an eighth of 512
    assert classes[512]["flops"] == pytest.approx(8 * classes[64]["flops"],
                                                  rel=0.01)
    assert read(dict(obs, peaks=None)) is None
    assert read(dict(obs, batch_class_counts={})) is None


# --------------------------------------------------------- the knee sweep

def _line(rate, growth, failed=0, late_ms=5.0):
    return {"offered_per_s": float(rate), "backlog_growth_per_s": growth,
            "failed": failed, "generator_late_p95_ms": late_ms}


@pytest.mark.parametrize("lines,knee", [
    ([_line(2000, 8), _line(2200, 40), _line(2400, 190)], 2200.0),
    ([_line(2000, -12), _line(2200, 90), _line(2400, 20)], 2000.0),
    ([_line(2000, 0, failed=3), _line(2200, 10)], None),
    # a process stall of seconds: the generator never offered 2,200/s
    ([_line(2000, 5), _line(2200, 850, late_ms=11643.0), _line(2400, 22),
      _line(2600, 300, late_ms=900.0)], 2400.0),
    # 2 % of the rate is the line: 56 of 2,800 holds, 57 does not
    ([_line(2600, 10), _line(2800, 56), _line(3000, 61)], 2800.0),
    ([_line(2600, 10), _line(2800, 57), _line(3000, 10)], 2600.0),
    ([_line(2400, 10), _line(2000, 10), _line(2200, 10)], 2400.0)])
def test_the_knee_is_the_highest_rate_sustained_with_all_below_it(lines, knee):
    assert find_knee.knee_of(lines) == knee


def test_the_knee_implies_both_rates_to_the_nearest_fifty():
    assert find_knee.implied(2800.0) == {
        "knee_per_s": 2800.0, "steady_rate_per_s": 2250.0,
        "saturated_rate_per_s": 3500.0}
    assert find_knee.parse_rates("1800:2400:200") == [1800.0, 2000.0, 2200.0, 2400.0]
    assert find_knee.parse_rates("1000,1500") == [1000.0, 1500.0]


@pytest.mark.parametrize("mix,factor", [("ragged-steady", 0.8),
                                        ("ragged-sat", 1.25)])
def test_the_mixes_carry_the_rates_the_cells_why_states(mix, factor):
    """`0.8 x` / `1.25 x the knee (N/s, date)` in the cell's `why`, to 50/s
    in the mix file, and nothing else apart between the two mixes."""
    from benchmark import traffic

    cell = next(w for w in _manifest()["workloads"] if w["traffic"] == mix)
    rate = traffic.load_mix(mix)["arrivals"]["rate_per_s"]
    knee = float(cell["why"].split("the knee (")[1].split("/s")[0].replace(",", ""))
    assert rate == 50.0 * round(factor * knee / 50.0)
    assert f"{int(rate):,}/s = {factor} x the knee" in cell["why"]
    other = traffic.load_mix("ragged-sat" if mix == "ragged-steady"
                             else "ragged-steady")
    mine = traffic.load_mix(mix)
    assert {k: v for k, v in mine.items() if k != "arrivals"} \
        == {k: v for k, v in other.items() if k != "arrivals"}


# ------------------------------------------------------------ the manifest

def test_every_per_layer_metric_lists_its_cells():
    manifest = _manifest()
    e2e = {m["name"]: m.get("workloads") or [w["name"] for w in manifest["workloads"]]
           for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m.get("workloads"), m["name"]
        assert set(m["workloads"]) <= set(e2e[m["moves"]]), m["name"]


@pytest.mark.parametrize("metric,cell", [
    ("mfu_pct.tput", "serve-base-sat"), ("mfu_pct.lat", "serve-base-steady"),
    ("latency_p95_typical_ms.lat", "serve-base-steady"),
    ("stalled_seconds.lat", "serve-base-steady")])
def test_the_four_metrics_of_pr_27_are_listed_with_a_reader_each(metric, cell):
    entry = next(m for m in _manifest()["per_layer"] if m["name"] == metric)
    assert cell in entry["workloads"]       # later cells may share the reader
    assert callable(_layer_metric(metric))
    assert _layer_metric(metric)({}) is None


# ------------------------------------------------ both serve cells, rehearsed

@pytest.mark.parametrize("suffix", ["tput", "lat"])
def test_a_traced_rehearsal_prints_every_metric_a_cpu_run_can_read(suffix):
    """Classes 4 / 2 / 1 at `max_batch=4`. A CPU trace has no device
    plane, the CPU no published peak and no memory counter: the metrics
    read from those are left out (never written as 0), every other
    metric the manifest lists for the cell is on the line, and no share
    reads over 105."""
    cell = SERVE[suffix]
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", "2147484011", "--seconds", "2", "--trace", "1", "--rehearse",
         *(["--held-out"] if suffix == "lat" else [])],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    listed = [m for m in _manifest()["per_layer"] if cell in m["workloads"]]
    needs_a_chip = {m["name"] for m in listed if m["source"] == "device_trace"
                    or m["name"].startswith(("mfu_pct.", "peak_hbm_gib."))}
    assert set(line["metrics"]) == {m["name"] for m in listed} - needs_a_chip
    for m in listed:
        if m["unit"] == "%" and m["name"] in line["metrics"]:
            assert 0.0 < line["metrics"][m["name"]]["value"] <= 105.0
    # the counts a reader's number can be checked against by hand
    window = next(ln for ln in done.stdout.splitlines() if ln.startswith("window:"))
    counts = ast.literal_eval(
        re.search(r"\(Server\.stats\) (\{[^}]*\})", window).group(1))
    assert set(counts) <= {1, 2, 4} and sum(counts.values()) > 0
    residues, positions = (int(x) for x in re.search(
        r"(\d+) residues in (\d+) positions", window).groups())
    assert positions == 128 * sum(cls * n for cls, n in counts.items())
    assert line["metrics"][f"batch_fill_pct.{suffix}"]["value"] \
        == pytest.approx(100.0 * residues / positions)
    # the last lines on standard error: each number beside its limit
    tail = done.stderr.strip().splitlines()[-len(line["compared"]):]
    assert all(ln.startswith("check ") and "(limit " in ln for ln in tail)
    assert list(line)[-1] == "compared"
