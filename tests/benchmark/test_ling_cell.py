"""The cell PR 33 added (`serve-ling3flash-sat`), on the CPU at `ling_tiny`
widths: the driver end to end, its controls coming out not correct, an
answer altered where it is produced coming out not correct, the loader's
refusals, `hybrid_flops` against a count by hand, and the new readers on
hand-made observations."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL, CONFIG = "serve-ling3flash-sat", "ling-3.0-flash-ep4"


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _manifest():
    return _json("BENCHMARK.json")


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_the_contracts_line(trace):
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "1", "--trace", trace, "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    assert "window: " in done.stdout and "routing: " in done.stdout
    if trace == "0":
        assert set(line["metrics"]) == {"embed_residues_per_s", "setup_s"}
    else:
        listed = {m["name"] for m in _manifest()["per_layer"]
                  if CELL in m.get("workloads", ())}
        assert set(line["metrics"]) <= listed
        # the batches' own counters are read on any device; every expert
        # is held at these widths
        assert line["metrics"]["dropped_assignments.tput"]["value"] == 0
        assert line["metrics"]["routed_here_share_pct.tput"]["value"] == 100
        assert line["metrics"]["expert_load_max_over_mean.tput"]["value"] >= 1
    assert set(line["compared"]) == {
        "failed_requests", "dropped_assignments", "param_count", "global_bias",
        "global_rel_err_rms", "global_rel_err_max", "local_mean_bias",
        "local_mean_rel_err_rms", "local_mean_rel_err_max",
        "global_rel_err_q1", "local_mean_rel_err_q1"}
    assert line["compared"]["param_count"]["value"] == 835152


def test_the_manifest_lists_lings_cell_configuration_and_metrics_by_name():
    manifest = _manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1
    assert cell["config"] == CONFIG and cell["traffic"] == "lm-ragged-sat"
    assert len(cell["why"]) <= 200 and "RATE" not in cell["why"]
    entry = next(e for e in manifest["configs"] if e["name"] == CONFIG)
    assert len(entry["why"]) <= 200
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                "num_experts", "vocab_size"]
    tput = next(m for m in manifest["end_to_end"]
                if m["name"] == "embed_residues_per_s")
    assert {"serve-base-sat", CELL} <= set(tput["workloads"]) and tput["bound"] == 0.07
    new = ["kda_device_ms.tput", "kda_core_device_ms.tput", "mla_device_ms.tput",
           "moe_router_device_ms.tput", "moe_dispatch_device_ms.tput",
           "moe_experts_device_ms.tput", "shared_expert_device_ms.tput",
           "expert_load_max_over_mean.tput", "routed_here_share_pct.tput",
           "dropped_assignments.tput", "kda_core_roofline"]
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for name in new:        # a later cell of the same stack may share a reader
        assert CELL in per_layer[name]["workloads"], name
        assert per_layer[name]["moves"] == "embed_residues_per_s"
    shared = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", ()) and m["name"] not in new}
    assert {"mfu_pct.tput", "batch_device_ms.tput", "peak_hbm_gib.tput",
            "device_idle_pct.tput", "compiles_in_window.tput"} <= shared


def test_the_manifest_has_its_cells_and_one_on_four_chips():
    # the six cells PR 33 knew are among however many the manifest counts
    cells = {w["name"]: w for w in _manifest()["workloads"]}
    assert {"pretrain-base-dense", "serve-base-sat", "pretrain-large-dense",
            "pretrain-glm47flash-packed8k", "pretrain-large-fsdp4", CELL} <= set(cells)
    assert 1 <= sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 4)
    assert cells["pretrain-large-fsdp4"]["chips"] == 4
    for m in _manifest()["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))


def test_the_older_entries_stand_found_by_name():
    # what `test_mla_core_metric.py` and `test_serve_classes.py` hold,
    # found by name and not by place
    per_layer = {m["name"]: m for m in _manifest()["per_layer"]}
    assert per_layer["mla_core_device_ms.train"] == {
        "name": "mla_core_device_ms.train", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "Kernels and XLA ops",
        "moves": "train_residues_per_s",
        "workloads": ["pretrain-glm47flash-packed8k"]}
    assert {"serve-base-sat", CELL} <= set(per_layer["mfu_pct.tput"]["workloads"])


def test_no_assertion_here_reads_the_manifest_by_place_or_counts_a_whole_list():
    """What PR 41 put right in the eight assertions that were marked
    `xfail(strict=True)`: an entry is found by its NAME, a list of cells is
    held as a subset, so a cell appended to the manifest breaks nothing."""
    here = os.path.join(ROOT, "tests", "benchmark")
    assert not os.path.exists(os.path.join(here, "conftest.py")), "no pin is left"
    for name in sorted(os.listdir(here)):
        if name.startswith("test_") and name != os.path.basename(__file__):
            with open(os.path.join(here, name)) as f:
                text = f.read()
            for by_place in ('["per_layer"][-', '["workloads"][-', '["configs"][-'):
                assert by_place not in text, (name, by_place)


def test_the_configuration_file_holds_the_published_widths_and_states_the_cut():
    config = _json("benchmark", "configs", CONFIG + ".json")
    published = config["published"]
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "first_k_dense_replace",
                       "num_experts", "vocab_size"}
    for key, value in published.items():
        assert (config[key] == value) != (key in reduced), key
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"], published["first_k_dense_replace"]) == (
                42, 512, 157184, 2)
    assert (config["hidden_size"], config["num_attention_heads"],
            config["qk_head_dim"], config["v_head_dim"], config["kv_lora_rank"],
            config["short_conv_kernel_size"], config["moe_intermediate_size"],
            config["intermediate_size"], config["router_width"],
            config["num_experts_per_tok"], config["n_group"], config["topk_group"],
            config["routed_scaling_factor"], config["layer_group_size"]) == (
                2560, 32, 192, 128, 512, 4, 768, 6144, 512, 8, 8, 4, 2.5, 6)
    assert config["parameters"] == 5_068_766_144
    assert {"assumed", "not_on_this_path", "deployment", "reduced_note"} <= set(config)
    assert config["param_dtype"] == config["dtype"] == "bfloat16"


def test_the_loader_refuses_a_swiglu_limit_on_a_held_layer_and_wrong_sizes():
    from benchmark.drivers import lm_serve

    config = _json("benchmark", "configs", CONFIG + ".json")
    workload = _json("benchmark", "workloads", CELL + ".json")
    cfg = lm_serve.cell_config(workload, config)
    m = cfg.model
    assert (m.experts_held, m.n_routed_experts, m.first_layer_index) == (128, 512, 1)
    # published layers 35-41 clamp their SwiGLU; none of them is held here
    assert any(config["expert_swiglu_limit_list"][35:])
    for name in lm_serve.LIMIT_LISTS:
        for held in (1, 4, 7):
            limits = list(config[name])
            limits[held] = 4
            with pytest.raises(SystemExit, match=name + ".*limit other than 0"):
                lm_serve.cell_config(workload, dict(config, **{name: limits}))
        limits = list(config[name])
        limits[0] = limits[8] = 4       # the layers before and after the share
        lm_serve.cell_config(workload, dict(config, **{name: limits}))
    for key, wrong in (("hidden_size", 1024), ("num_experts", 64),
                       ("router_width", 256), ("head_dim", 64), ("n_group", 4),
                       ("layer_group_size", 4), ("q_lora_rank", 768)):
        with pytest.raises(SystemExit, match=key):
            lm_serve.cell_config(workload, dict(config, **{key: wrong}))


@pytest.mark.parametrize("precision", ["int8", "bf16_state"])
def test_a_control_one_precision_down_is_not_correct(precision):
    """`benchmark.read_hybrid_limits`: the cell's sound run, then the
    reference in the program's place one precision down, through the
    cell's own comparison and limits: the sound line is correct, the
    control is not, and the line names the numbers that caught it."""
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.read_hybrid_limits", "--workload", CELL,
         "--seed", "3000000019", "--seconds", "1", "--controls", precision,
         "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines() if x.startswith("{")]
    sound, control = lines[-2], lines[-1]
    assert sound["kind"] == "sound" and sound["correct"] is True
    assert control["kind"] == "control:" + precision
    assert control["correct"] is False and control["caught_by"], control
    assert all(np.isfinite(v) for v in control["numbers"].values())


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch, capsys):
    from benchmark import run as bench_run
    from proteinbert_tpu import inference

    real = inference._packed_decoder_embed_batch

    def broken(params, tokens, segment_ids, annotations, cfg):
        out = dict(real(params, tokens, segment_ids, annotations, cfg=cfg))
        out["global"] = out["global"][:, ::-1]   # documents answer each other
        return out

    monkeypatch.setattr(inference, "_packed_decoder_embed_batch", broken)
    rc = bench_run.main(["--workload", CELL, "--seed", "17", "--seconds", "1",
                         "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is False and "FAILED" in out
    assert line["compared"]["global_rel_err_rms"]["value"] > 0.1
    assert line["compared"]["local_mean_rel_err_rms"]["value"] < 1e-4


@pytest.mark.parametrize("judged", ["throughput", "latency"])
def test_the_window_opens_with_the_load_and_counts_as_serve_base_sat_does(judged, capsys):
    """Open loop from the window's first instant on an idle server; the
    rate is the tokens of the documents answered inside the window over
    the window, `drivers/serve.measure`'s own count."""
    import jax

    from benchmark import run as bench_run
    from benchmark.drivers import lm_serve

    run = bench_run.tool_run(CELL, 23, 1.0, rehearse=True)
    run.workload["judged"] = judged
    out, sample = lm_serve.measure(run, jax.devices()[:1])
    obs, said = out["obs"], capsys.readouterr().out
    rate = run.mix["arrivals"]["rate_per_s"]
    assert out["failed"] == 0 and run.setup_s is not None
    assert 0.95 < run.window_s < 1.5 and "window: " in said
    assert out["attempted"] == len(obs["due_s"]) == len(obs["latency_s"])
    assert len(obs["late_s"]) == out["attempted"]
    assert obs["due_s"].min() >= 0.0 and obs["due_s"].max() < run.seconds
    assert abs(out["attempted"] - rate * run.seconds) <= 0.25 * rate
    if judged == "throughput":
        assert out["e2e"] == {"embed_residues_per_s": pytest.approx(
            obs["residues_in_window"] / run.window_s)}
    else:       # every request waited for, the tail over all of them
        assert set(out["e2e"]) == {"embed_latency_p95_ms"}
        assert np.isfinite(obs["latency_s"]).all()
    assert 0 < obs["residues_in_batches"] <= obs["batched_positions"]
    assert obs["requests_in_window"] >= len(sample["docs"]) > 0


@pytest.mark.parametrize("rates", ["30,60", "20:40:20"])
def test_the_knee_sweep_opens_every_rate_on_one_booted_server(rates, monkeypatch, capsys):
    """`benchmark.find_lm_knee`: one set-up a seed, one line a rate with
    the lowest first, each window's counts its own (the server's totals
    go on), then the knee and `--factor` times it."""
    from benchmark import find_lm_knee
    from benchmark.drivers import lm_serve

    boots = []
    real = lm_serve.serving

    def counted(run):
        boots.append(run.seed)
        return real(run)

    monkeypatch.setattr(lm_serve, "serving", counted)
    assert find_lm_knee.main(["--workload", CELL, "--rates", rates, "--seeds", "29",
                              "--seconds", "1", "--factor", "1.5", "--rehearse"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    swept, last = lines[:-1], lines[-1]
    assert boots == [29]
    assert [ln["offered_per_s"] for ln in swept] == sorted(
        find_lm_knee.find_knee.parse_rates(rates))
    for ln in swept:
        assert ln["failed"] == 0 and ln["batches"] > 0
        # a window's own requests, not the server's running totals
        assert 0 < ln["completed_per_s"] <= 1.5 * ln["offered_per_s"]
        assert abs(ln["completed_per_s"] + ln["left_at_close"] / 1.0
                   - ln["offered_per_s"]) <= 0.5 * ln["offered_per_s"]
    assert set(last) >= {"knee_per_s", "by_seed"}
    if last["knee_per_s"] is not None:
        assert last["saturated_rate_per_s"] == round(1.5 * last["knee_per_s"], 1)


def _sizes():
    from benchmark.drivers import lm_serve

    config = _json("benchmark", "configs", CONFIG + ".json")
    workload = _json("benchmark", "workloads", CELL + ".json")
    return lm_serve.reference_sizes(config, lm_serve.cell_config(workload, config))


def test_hybrid_flops_against_a_count_by_hand():
    from benchmark import hybrid_flops

    c = _sizes()
    assert hybrid_flops.layer_counts(c) == (6, 1, 1, 6)
    # ISSUE 33's arithmetic
    kda = (5 * 2560 * 4096 + 2 * 2560 * 32 + 3 * 4 * 4096 + 32 + 4096 + 128)
    latent = (2560 * 6144 + 2560 * 576 + 512 + 512 * 32 * 256 + 4096 * 2560
              + 2560 * 32)
    assert hybrid_flops.kda_params(c) == kda == 52_646_048
    assert hybrid_flops.latent_params(c) == latent == 31_965_696
    assert hybrid_flops.expert_params(c) == 3 * 2560 * 768 == 5_898_240
    assert hybrid_flops.param_count(c) == 5_068_766_144
    # one real token, no attention pair, no routed assignment
    per_token = (6 * (5 * 2560 * 4096 + 2 * 2560 * 32 + 3 * 4 * 4096)
                 + (latent - 512) + 3 * 2560 * 6144
                 + 6 * (2560 * 512 + 3 * 2560 * 768))
    recurrence = 6 * 7 * 32 * 128 * 128
    assert hybrid_flops.forward_flops(c, 1, 0, 0) == 2.0 * per_token + recurrence
    # one (query, key) pair in the one latent layer: 32 heads x (192 + 128)
    assert (hybrid_flops.forward_flops(c, 0, 1, 0)) == 2.0 * 32 * 320
    # one assignment on a held expert: its three matrices
    assert hybrid_flops.forward_flops(c, 0, 0, 1) == 2.0 * 3 * 2560 * 768
    assert hybrid_flops.kda_core_flops(c, 10) == 7 * 10 * 32 * 128 * 128
    assert hybrid_flops.kda_core_min_bytes(c, 10) == 4.0 * 10 * 32 * (5 * 128 + 1)


def test_the_new_readers_on_hand_made_observations():
    from benchmark import hybrid_readers

    routing = {"batches": 4, "assignments_held": 600, "dropped_assignments": 0,
               "real_tokens": 100, "load_max_over_mean_sum": 7.0,
               "top_k": 8, "expert_layers": 3}
    obs = {"routing": routing}
    assert hybrid_readers.load_max_over_mean(obs) == 1.75
    assert hybrid_readers.routed_here_share_pct(obs) == 25.0
    assert hybrid_readers.dropped_assignments(obs) == 0.0
    # a program without the counters (a parent commit), a window without a
    # batch, a run without a trace: nothing to read
    for empty in ({}, {"routing": None}, {"routing": dict(routing, batches=0)}):
        assert hybrid_readers.load_max_over_mean(empty) is None
        assert hybrid_readers.routed_here_share_pct(empty) is None
        assert hybrid_readers.dropped_assignments(empty) is None
    assert hybrid_readers.kda_core_roofline_pct({"peaks": {}}) is None
    assert hybrid_readers.kda_core_roofline_pct(obs) is None


def test_the_documents_of_a_block_hold_the_same_lengths_whatever_the_seed():
    from benchmark import traffic
    from benchmark.drivers import lm_serve

    mix = traffic.load_mix("lm-ragged-sat")
    a, lengths_a = lm_serve.documents(mix, 2, 3300000011)
    b, lengths_b = lm_serve.documents(mix, 2, 5)
    n = mix["block"]
    assert (sorted(lengths_a[:n]) == sorted(lengths_b[:n]) == sorted(lengths_a[n:])
            == sorted(traffic.block_lengths(mix)))
    assert [len(d) for d in a] == list(lengths_a)
    assert max(lengths_a) == 8192 and min(lengths_a) >= 32
    assert int(np.median(lengths_a)) in range(1100, 1300)
    ids = np.concatenate(a)
    assert ids.min() >= 0 and ids.max() < 39296 and ids.dtype == np.int32
    # Zipf at exponent 0.5: the commonest id carries ~0.25 % of the positions
    assert np.bincount(ids).max() / len(ids) < 0.01
    # every document distinct, the warm-up's stream another draw
    assert len({d.tobytes() for d in a}) == len(a)
    warm, _ = lm_serve.documents(mix, 1, 3300000011, stream=4)
    assert not {d.tobytes() for d in warm} & {d.tobytes() for d in a}


@pytest.mark.parametrize("case", ["sound", "flipped", "int8", "exchanged"])
def test_the_cells_limits_on_hand_made_answers(case):
    """The cell's limits against answers with the errors the chip read
    at the published widths (PERF.md section 2; `global` / `local_mean`
    a document): the sound program's (0.001 / 0.009) is correct with or
    without an expert choice flipped at four of the twelve last tokens
    (0.005 to 0.104, the largest read); int8 products' (0.0053 / 0.007,
    half of the last tokens flipped) are caught, by the FIRST QUARTILE
    alone; two answers exchanged are caught by the maximum."""
    from benchmark.drivers import lm_serve

    rng = np.random.default_rng(7)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    want = rng.normal(size=(12, 2, 256))
    norm = np.linalg.norm(want, axis=-1, keepdims=True)
    size = np.array({"int8": [0.0053, 0.007]}.get(case, [0.001, 0.009]))[None, :, None]
    got = want + size * norm * unit(rng.normal(size=want.shape))
    flips = {"flipped": [0.005, 0.01, 0.03, 0.104],
             "int8": [0.012, 0.016, 0.017, 0.021, 0.022, 0.025]}.get(case, [])
    for i, by in enumerate(flips):
        got[i, 0] += by * norm[i, 0] * unit(rng.normal(size=256))
    if case == "exchanged":
        got[[0, 1]] = got[[1, 0]]
    answers = lambda a: [{"global": x[0], "local_mean": x[1]} for x in a]  # noqa: E731
    gaps = lm_serve.gaps(answers(got), answers(want))
    caught = [name for name, value, limit in lm_serve.limit_checks(
        gaps, _json("benchmark", "workloads", CELL + ".json")) if not value <= limit]
    if case in ("sound", "flipped"):
        assert not caught, (caught, gaps)
    elif case == "int8":
        assert caught == ["global_rel_err_q1"], (caught, gaps)
    else:
        assert "global_rel_err_max" in caught and "local_mean_rel_err_max" in caught
