"""The yardstick's own arithmetic: FLOPs against the program's model,
the traffic generator's invariants, and each trace reduction."""

import json
import os

import numpy as np
import pytest

from benchmark import compare, flops, trace_reduce, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "trace_v5e.json")


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,rows,seq_len", [
    ("base", 256, 512), ("large", 32, 1024), ("tiny", 4, 64)])
def test_flops_match_the_programs_model(name, rows, seq_len):
    from benchmark.program import model_sizes, program_config
    from proteinbert_tpu.train import metrics

    conf = _config(name)
    cfg = program_config(conf, {"mesh.data": 1, "mesh.model": 1})
    m = model_sizes(conf)
    assert flops.forward_flops(m, rows, seq_len) == pytest.approx(
        metrics.forward_flops(cfg.model, rows, seq_len), rel=1e-12)
    assert flops.train_flops(m, rows, seq_len) == pytest.approx(
        metrics.train_flops(cfg.model, rows, seq_len), rel=1e-12)


@pytest.mark.parametrize("name", ["base", "large", "tiny"])
def test_param_count_matches_the_programs_tree(name):
    import jax

    from benchmark.program import model_sizes, program_config
    from proteinbert_tpu.models import proteinbert

    conf = _config(name)
    cfg = program_config(conf, {"mesh.data": 1, "mesh.model": 1})
    tree = jax.eval_shape(lambda k: proteinbert.init(k, cfg.model),
                          jax.random.PRNGKey(0))
    assert flops.param_count(model_sizes(conf)) == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def test_packed_flops_grow_with_segments_only_in_the_global_track():
    m = {k: _config("base")[k] for k in (
        "vocab_size", "num_annotations", "local_dim", "global_dim", "key_dim",
        "num_heads", "num_blocks", "narrow_kernel", "wide_kernel", "wide_dilation")}
    dense = flops.forward_flops(m, 512, 1024, 1, heads=False)
    packed = flops.forward_flops(m, 512, 1024, 8, heads=False)
    assert dense < packed < 1.35 * dense


def test_unknown_device_kind_is_an_error():
    assert flops.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(ValueError):
        flops.peaks_for("cpu")
    with pytest.raises(ValueError):
        flops.peaks_for("_source")


def test_roofline_says_which_bound():
    peaks = flops.peaks_for("TPU v5 lite")
    assert flops.roofline(197e12, 1.0, peaks) == {"min_s": 1.0, "bound": "compute"}
    assert flops.roofline(1.0, 819e9, peaks) == {"min_s": 1.0, "bound": "memory"}


@pytest.mark.parametrize("mix", ["dense-256x512", "dense-32x1024",
                                 "ragged-sat", "ragged-steady"])
def test_every_seed_gets_the_same_sizes_in_another_order(mix):
    spec = traffic.load_mix(mix)
    spec["block"] = min(spec["block"], 256)   # the test's own size
    seqs_a, len_a = traffic.sequences(spec, 2, 7)
    seqs_b, len_b = traffic.sequences(spec, 2, 3_000_000_019)
    n = spec["block"]
    assert sorted(len_a[:n]) == sorted(len_b[:n]) == sorted(len_a[n:])
    assert list(len_a) != list(len_b) and seqs_a != seqs_b
    assert [len(s) for s in seqs_a] == list(len_a)
    assert len_a.min() >= spec["lengths"]["min"]
    assert len_a.max() <= spec["lengths"]["max"]
    assert set("".join(seqs_a)) <= set("ACDEFGHIKLMNPQRSTVWY")
    if spec["arrivals"]:
        due_a = traffic.due_times(spec, 2, 7)
        due_b = traffic.due_times(spec, 2, 11)
        rate = spec["arrivals"]["rate_per_s"]
        assert due_a[0] == 0.0 and np.all(np.diff(due_a) > 0)
        assert np.allclose(sorted(np.diff(due_a[:n + 1])),
                           sorted(np.diff(due_b[:n + 1])))
        # a block lasts exactly block / rate, whatever the order
        assert due_a[n] - due_a[0] == pytest.approx(n / rate, rel=1e-9)


def test_annotation_rows_are_sparse_and_some_are_empty():
    spec = traffic.load_mix("dense-256x512")
    rows = traffic.annotation_rows(spec, 400, 512, 5)
    assert rows.shape == (400, 512) and set(np.unique(rows)) == {0.0, 1.0}
    empty = (rows.sum(1) == 0).mean()
    assert 0.1 < empty < 0.3


# ------------------------------------------------------------ trace reduction

def _synthetic():
    # one chip: two runs of a program with an idle gap between them, and
    # a host annotation that covers the gap
    ops = [["fusion.1", 1000, 4000], ["copy.2", 4000, 1000],      # run 1: 1000-5000
           ["fusion.1", 9000, 4000], ["copy.2", 13500, 500]]      # run 2: 9000-14000
    modules = [["jit_train_step(1)", 1000, 4000], ["jit_train_step(1)", 9000, 5000]]
    host = [["trainer.pretrain", 0, 20000], ["benchmark.feed.next", 5500, 3000],
            ["PjitFunction(train_step)", 8600, 300]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]}


def test_busy_is_the_union_of_op_intervals():
    plane = trace_reduce.device_planes(_synthetic())[0]
    busy = trace_reduce.busy_intervals(plane)
    assert busy.tolist() == [[1000, 5000], [9000, 13000], [13500, 14000]]


def test_summary_of_a_synthetic_trace():
    s = trace_reduce.summarize(_synthetic())
    assert s["busy_s"] == pytest.approx(8500e-9)
    assert s["window_s"] == pytest.approx(20000e-9)
    assert s["programs"] == {"train_step": [2, pytest.approx(9000e-9)]}
    assert s["top_ops"][0] == ["fusion.1", pytest.approx(8000e-9)]
    # the 4000 ns gap is named by the shortest host span over its middle
    assert s["top_gaps"][0] == ["benchmark.feed.next", pytest.approx(4000e-9)]


def test_gap_between_runs_of_a_program_leaves_out_other_busy_time():
    t = _synthetic()
    plane = trace_reduce.device_planes(t)[0]
    assert trace_reduce.program_gaps_s(plane, "train_step").tolist() == [
        pytest.approx(4000e-9)]
    # something else running in between is not idle time
    plane["lines"][1]["events"].append(["other", 6000, 1000])
    assert trace_reduce.program_gaps_s(plane, "train_step").tolist() == [
        pytest.approx(3000e-9)]


def test_program_name_strips_jit_and_the_run_id():
    assert trace_reduce.program_name("jit_train_step(12345)") == "train_step"
    assert trace_reduce.program_name("jit__packed_encode_batch(7)") == "_packed_encode_batch"


def test_no_device_plane_reads_as_no_busy_time():
    t = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["benchmark.x", 0, 10]]}]}]}
    s = trace_reduce.summarize(t)
    assert s["busy_s"] == 0.0 and s["top_ops"] == []


def test_reductions_on_the_recorded_v5e_trace():
    """A trace recorded on the chip (benchmark/record_fixture.py): four
    runs of one jitted program under a host annotation."""
    with open(FIXTURE) as f:
        t = json.load(f)
    s = trace_reduce.summarize(t)
    assert trace_reduce.device_planes(t)
    assert 0 < s["busy_s"] < s["window_s"]
    runs, seconds = s["programs"]["fixture_step"]
    assert runs == 4 and 0 < seconds <= s["busy_s"] * 1.001
    assert len(trace_reduce.program_gaps_s(s["plane"], "fixture_step")) == 3
    assert s["top_ops"] and s["top_gaps"]
    assert sum(v for _, v in s["top_gaps"]) <= s["window_s"] - s["busy_s"] + 1e-9


def test_readers_return_nothing_where_there_is_nothing_to_read():
    from benchmark import readers

    empty = {"trace": None, "peaks": None, "program": "train_step"}
    for reader in (readers.program_device_ms, readers.program_gap_ms,
                   readers.device_idle_pct, readers.peak_hbm_gib,
                   readers.mfu_pct, readers.program_roofline_pct,
                   readers.queue_wait_ms, readers.batch_fill_pct,
                   readers.latency_p95_ms, readers.generator_late_ms,
                   readers.serve_mfu_pct, readers.packed_roofline_pct,
                   readers.stalled_seconds, readers.typical_p95_ms):
        assert reader(empty) is None


def test_readers_on_a_synthetic_trace():
    from benchmark import readers

    peaks = flops.peaks_for("TPU v5 lite")
    obs = {"trace": trace_reduce.summarize(_synthetic()), "peaks": peaks,
           "program": "train_step", "steps": 2, "window_s": 20000e-9,
           "call_flops": 197e12 * 2250e-9, "call_min_bytes": 1.0,
           "memory_peak_bytes": 2 ** 31}
    assert readers.program_device_ms(obs) == pytest.approx(4500e-6)
    assert readers.program_gap_ms(obs) == pytest.approx(4000e-6)
    assert readers.device_idle_pct(obs) == pytest.approx(57.5)
    assert readers.peak_hbm_gib(obs) == 2.0
    assert readers.program_roofline_pct(obs) == pytest.approx(50.0)
    assert readers.mfu_pct(obs) == pytest.approx(22.5)


def test_worst_leaf_gap_is_held_against_the_median_leaf():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    prog = {"a": 1.1, "b": 2.0, "c": 2e-9}      # the tiny leaf doubles: no matter
    assert compare.worst_leaf_gap(prog, ref) == pytest.approx(0.1)
    assert compare.worst_leaf_gap({"a": 0.0, "b": 0.0, "c": 0.0}, ref) == 1.0


def test_leaf_dir_gaps_see_a_direction_the_norms_do_not():
    ref = {"a": np.array([3.0, 4.0]), "b": np.array([0.0, 5.0]),
           "c": np.array([1e-9, 0.0])}
    turned = {"a": np.array([4.0, 3.0]), "b": np.array([0.0, 5.0]),
              "c": np.array([0.0, 1e-9])}         # same norms, "a" and "c" turned
    norms = lambda t: {k: float(np.linalg.norm(v)) for k, v in t.items()}  # noqa: E731
    assert compare.worst_leaf_gap(norms(turned), norms(ref)) == 0.0
    gaps = compare.leaf_dir_gaps(turned, ref)
    assert gaps[0] == pytest.approx(np.sqrt(2.0) / 5.0)
    assert gaps[1] == 0.0
    assert gaps[2] < 1e-9                          # held against the median leaf


def test_batch_fill_counts_the_residues_of_the_batches_counted():
    from benchmark import readers

    obs = {"batches": 4, "batched_positions": 4000, "residues_in_batches": 2600,
           "residues_in_window": 9999}
    assert readers.batch_fill_pct(obs) == pytest.approx(65.0)


def test_memory_peak_is_in_use_plus_reserved_on_the_fullest_chip():
    from benchmark.device import memory_peak_bytes

    class Chip:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    chips = [Chip({"peak_bytes_in_use": 10, "peak_bytes_reserved": 5}),
             Chip({"peak_bytes_in_use": 12, "peak_bytes_reserved": 1}),
             Chip(None)]
    assert memory_peak_bytes(chips) == 15
