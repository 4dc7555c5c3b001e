"""The two cells PR 28 added, on the CPU at tiny widths: both drivers end to
end (the four-chip one on four of the suite's virtual devices), the
decoder's controls coming out not correct, `lm_flops` against a count by
hand, and the new readers on hand-made observations."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LM, MESH = "pretrain-glm47flash-packed8k", "pretrain-large-fsdp4"
# `benchmark.run` in a process of its own with four CPU devices, set up
# before jax starts (a four-chip cell refuses fewer).
FOUR = ("import sys; sys.path.insert(0, {root!r}); "
        "from proteinbert_tpu.utils.compat import request_cpu_devices; "
        "request_cpu_devices(4); from benchmark import run; "
        "sys.exit(run.main(sys.argv[1:]))").format(root=ROOT)


def _run(cell, trace):
    head = [sys.executable, "-c", FOUR] if cell == MESH else [
        sys.executable, "-m", "benchmark.run"]
    return subprocess.run(
        [*head, "--workload", cell, "--seed", "3000000019", "--seconds", "1",
         "--trace", trace, "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"})


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [LM, MESH])
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_the_contracts_line(cell, trace):
    done = _run(cell, trace)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["count"] == (4 if cell == MESH else 1)
    manifest = _manifest()
    if trace == "0":
        assert set(line["metrics"]) == {"train_residues_per_s", "setup_s"}
    else:
        listed = {m["name"] for m in manifest["per_layer"] if cell in m["workloads"]}
        assert set(line["metrics"]) <= listed
        if cell == LM:      # the step's counters are read on any device
            assert line["metrics"]["dropped_assignments.train"]["value"] == 0
            assert line["metrics"]["routed_here_share_pct.train"]["value"] == 100
            assert line["metrics"]["expert_load_max_over_mean.train"]["value"] >= 1
    if cell == LM:          # the line names the decoder's parameter count
        assert line["compared"]["param_count"]["value"] == 248680
        assert "route_mismatch_share" in line["compared"]


def test_the_manifest_lists_the_new_cells_and_their_metrics():
    manifest = _manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[LM]["chips"] == 1 and cells[MESH]["chips"] == 4
    # however many cells the manifest counts: a quarter of them at most, and
    # one always, may ask for four chips
    assert len(cells) == len(manifest["workloads"]) >= 5
    assert 1 <= sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 4)
    for w in cells.values():
        assert len(w["why"]) <= 200
    for m in manifest["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    train = next(m for m in manifest["end_to_end"]
                 if m["name"] == "train_residues_per_s")
    assert {LM, MESH} <= set(train["workloads"])


def test_the_driver_refuses_a_file_whose_sizes_the_program_does_not_run():
    from benchmark.drivers import lm_pretrain

    with open(os.path.join(ROOT, "benchmark/configs/glm-4.7-flash-ep8.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark/workloads", LM + ".json")) as f:
        workload = json.load(f)
    cfg = lm_pretrain.cell_config(workload, config)
    assert cfg.model.experts_held == 8 and cfg.model.n_routed_experts == 64
    for key, wrong in (("hidden_size", 1024), ("n_routed_experts", 16),
                       ("router_width", 32), ("qk_rope_head_dim", 32)):
        with pytest.raises(SystemExit, match=key):
            lm_pretrain.cell_config(workload, dict(config, **{key: wrong}))


@pytest.mark.parametrize("precision", ["int8", "bf16_params"])
def test_a_control_one_precision_down_is_not_correct(precision):
    """`benchmark.read_lm_limits`: the cell's sound run, then the
    reference in the program's place, one precision down, put through the
    cell's own comparison: the sound line is correct, the control is not,
    and the line names the numbers that caught it."""
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.read_lm_limits", "--workload", LM,
         "--seed", "3000000019", "--seconds", "1", "--controls", precision,
         "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines() if x.startswith("{")]
    sound, control = lines[-2], lines[-1]
    assert sound["correct"] is True and set(sound["compared"]) >= {
        "loss_rel_gap", "grad_norm_gap", "change_norm_gap", "route_mismatch_share"}
    assert control["kind"] == "control:" + precision
    assert control["correct"] is False and control["caught_by"], control
    assert np.isfinite(control["numbers"]["change_norm_gap"])    # three steps


def test_route_mismatch_share_counts_assignments_the_reference_did_not_choose():
    from benchmark.drivers import lm_pretrain

    # (rows, layers + module, L, k): two tokens and a pad, one layer, k = 2
    reference = np.array([[[[0, 1], [2, 3], [8, 8]]]])
    segments = np.array([[1, 1, 0]])
    assert lm_pretrain.route_mismatch_share(reference, reference, segments) == 0.0
    swapped = np.array([[[[1, 0], [2, 3], [8, 8]]]])       # order within a token
    assert lm_pretrain.route_mismatch_share(swapped, reference, segments) == 0.0
    moved = np.array([[[[0, 5], [2, 3], [0, 1]]]])  # one of four; the pad counts nowhere
    assert lm_pretrain.route_mismatch_share(moved, reference, segments) == 0.25


def _sizes():
    with open(os.path.join(ROOT, "benchmark/configs/glm-4.7-flash-ep8.json")) as f:
        config = json.load(f)
    c = dict(config)
    c["experts_held"], c["n_routed_experts"] = config["n_routed_experts"], config["router_width"]
    return c


def test_lm_flops_against_a_count_by_hand():
    from benchmark import lm_flops

    c = _sizes()
    # ISSUE 28's arithmetic, in millions of parameters
    attn = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048
    assert lm_flops.attention_params(c) == attn == 21_757_952
    assert lm_flops.expert_params(c) == 3 * 2048 * 1536
    assert lm_flops.param_count(c) == 706_516_480
    # one real token, no attention pairs, no routed assignment
    per_token = (6 * attn + 3 * 2048 * 10240 + 5 * (2048 * 64 + 3 * 2048 * 1536)
                 + 2 * 2048 * 2048 + 2 * 2048 * 19360)
    assert lm_flops.forward_flops(c, 1, 0, 0) == 2.0 * per_token
    # one (query, key) pair: 20 heads x (256 + 256) multiply-adds in 6 layers
    assert (lm_flops.forward_flops(c, 0, 1, 0)) == 2.0 * 6 * 20 * 512
    # one assignment on a held expert: its three matrices
    assert lm_flops.forward_flops(c, 0, 0, 1) == 2.0 * 3 * 2048 * 1536
    assert lm_flops.train_flops(c, 7, 5, 3) == 3 * lm_flops.forward_flops(c, 7, 5, 3)
    # a balanced router sends an eighth of 4 choices in 5 expert layers here
    assert lm_flops.expected_assignments(c, 16384) == 5 * 16384 * 4 / 8
    assert lm_flops.moe_experts_flops(c, 10) == 6.0 * 10 * 3 * 2048 * 1536
    assert lm_flops.moe_experts_min_bytes(c) == 12.0 * 5 * 8 * 3 * 2048 * 1536
    assert lm_flops.train_min_bytes(c, 16384) == 24.0 * 706_516_480 + 8 * 16384


def test_the_new_readers_on_hand_made_observations():
    from benchmark import lm_readers

    obs = {"counters": [{"routed_here_share": 0.12, "dropped_assignments": 0.0},
                        {"routed_here_share": 0.13, "dropped_assignments": 2.0}]}
    assert lm_readers.counter_mean(obs, "routed_here_share") == pytest.approx(0.125)
    assert lm_readers.counter_sum(obs, "dropped_assignments") == 2.0
    assert lm_readers.counter_mean({}, "routed_here_share") is None
    assert lm_readers.counter_mean({"counters": []}, "x") is None
    # a program without the scopes, a run without a trace: nothing to read
    assert lm_readers.moe_experts_roofline_pct({"peaks": {}}) is None
    assert lm_readers.collective_ms({}) is None
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_train_step(1)", 0, 1000],
                                           ["jit_train_step(1)", 2000, 1000]]},
        {"name": "XLA Ops", "events": [
            ["%all-gather.3 = f32[8]", 100, 200], ["%fusion.1 = f32[8]", 300, 100],
            ["%reduce-scatter.1 = f32[2]", 2100, 300],
            ["%all-reduce.9 = f32[]", 5000, 50]]}]}       # outside every run
    obs = {"trace": {"plane": plane}, "program": "train_step"}
    assert lm_readers.collective_ms(obs) == pytest.approx(1e3 * 500e-9 / 2)


def test_the_documents_of_a_block_hold_the_same_lengths_whatever_the_seed():
    from benchmark import traffic
    from benchmark.drivers import lm_pretrain

    mix = traffic.load_mix("lm-packed-2x8192")
    a = lm_pretrain.documents(mix, 2, 2900000011)
    b = lm_pretrain.documents(mix, 2, 5)
    n = mix["block"]
    assert sorted(map(len, a[:n])) == sorted(map(len, b[:n])) == sorted(map(len, a[n:]))
    assert max(map(len, a)) <= 8192 and min(map(len, a)) >= 32
    ids = np.concatenate(a)
    assert ids.min() >= 0 and ids.max() < 19360
    # Zipf at exponent 0.5: the commonest id carries ~0.4 % of the positions
    assert np.bincount(ids).max() / len(ids) < 0.01
    assert lm_pretrain.segment_pairs(np.array([[1, 1, 1, 2, 2, 0]])) == 6 + 3


def test_the_cells_documents_come_in_one_order_with_ids_of_the_seeds_own():
    """The packer turns the order of the lengths into the rows' segment
    layout, and the attention core's time follows the layout: the cell's
    mix states the order, so that every seed does the same work."""
    from benchmark import traffic
    from benchmark.drivers import lm_pretrain

    mix = traffic.load_mix("lm-packed-2x8192")
    assert "order_seed" in mix
    a = lm_pretrain.documents(mix, 2, 4100000011)
    b = lm_pretrain.documents(mix, 2, 5)
    assert list(map(len, a)) == list(map(len, b))
    assert list(map(len, a[:mix["block"]])) != list(map(len, a[mix["block"]:]))
    assert not np.array_equal(np.concatenate(a), np.concatenate(b))
    again = lm_pretrain.documents(mix, 2, 4100000011)
    assert all(np.array_equal(x, y) for x, y in zip(a, again))
    # without the key the order is the seed's, as the rehearsal's mix has it
    loose = dict(mix, order_seed=None)
    assert (list(map(len, lm_pretrain.documents(loose, 2, 4100000011)))
            != list(map(len, lm_pretrain.documents(loose, 2, 5))))
