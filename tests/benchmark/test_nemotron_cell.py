"""The cell PR 43 added (`serve-nemotron3super-sat`), on the CPU at
`nemotron_tiny` widths: the driver end to end, both controls coming out
not correct, an answer altered where it is produced coming out not
correct, the loader's refusals, `nemotron_flops` against a count by hand,
the new reader on hand-made observations; and what nine assertions of
`test_startup_metrics.py` and `test_zaya_cell.py` held of the manifest
(tests/conftest.py marks them, `PINNED_SINCE_PR_43`), found BY NAME and
as "contains": no assertion here reads an entry by its place or a list
whole, so the next cell appended breaks nothing."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL, CONFIG, MIX = ("serve-nemotron3super-sat", "nemotron-3-super-ep4",
                     "lm-ragged-sat-32k")
ZAYA, LING = "serve-zaya1-8b-sat", "serve-ling3flash-sat"
TRAIN = ["pretrain-base-dense", "pretrain-large-dense",
         "pretrain-glm47flash-packed8k", "pretrain-large-fsdp4"]
SERVE = ["serve-base-sat", LING, ZAYA, CELL]
NEW = {"mamba_device_ms.tput": ("ms", "lower", "Model (models/glm_moe.py)"),
       "ssd_core_device_ms.tput": ("ms", "lower", "Kernels and XLA ops"),
       "ssd_core_roofline": ("%", "higher", "Kernels and XLA ops"),
       "moe_latent_device_ms.tput": ("ms", "lower", "Model (models/glm_moe.py)"),
       "gqa_device_ms.tput": ("ms", "lower", "Model (models/glm_moe.py)")}
JOINED = {"queue_wait_ms.tput", "batch_fill_pct.tput", "batch_device_ms.tput",
          "latency_p95_ms.tput", "generator_late_ms.tput", "device_idle_pct.tput",
          "peak_hbm_gib.tput", "compiles_in_window.tput", "pack_ms.tput",
          "assemble_ms.tput", "slot_wait_ms.tput", "finalize_host_ms.tput",
          "mfu_pct.tput", "packed_scope_coverage_pct", "moe_router_device_ms.tput",
          "moe_dispatch_device_ms.tput", "moe_experts_device_ms.tput",
          "shared_expert_device_ms.tput", "expert_load_max_over_mean.tput",
          "routed_here_share_pct.tput", "dropped_assignments.tput",
          "moe_experts_roofline.tput", "startup_compile_s", "startup_compiles",
          "startup_cache_load_s", "startup_trace_lower_s", "startup_warmup_s",
          "scope_map_s.tput"}
NOT_ITS = {"kda_device_ms.tput", "kda_core_roofline", "mla_device_ms.tput",
           "cca_device_ms.tput", "cca_core_roofline", "residual_device_ms.tput",
           "packed_encode_roofline", "packed_attention_device_ms"}
# what PR 39's start-up metrics were written as: name -> (unit, layer, the
# end-to-end metric it moves, cells its list has to CONTAIN)
STARTUP = {
    "startup_compile_s": ("s", "Device", "setup_s", TRAIN + SERVE),
    "startup_compiles": ("count", "Device", "setup_s", TRAIN + SERVE),
    "startup_cache_load_s": ("s", "Device", "setup_s", TRAIN + SERVE),
    "startup_trace_lower_s": ("s", "Model", "setup_s", TRAIN + SERVE),
    "startup_warmup_s": ("s", "Dispatcher (serve/dispatch.py)", "setup_s", SERVE),
    "scope_map_s.tput": ("s", "Tracing (obs/tracing.py)", "embed_residues_per_s", SERVE),
}
COMPARED = {"failed_requests", "dropped_assignments", "param_count", "global_bias",
            "global_rel_err_rms", "global_rel_err_max", "local_mean_bias",
            "local_mean_rel_err_rms", "local_mean_rel_err_max",
            "global_rel_err_q1", "local_mean_rel_err_q1"}


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _named(entries):
    by_name = {e["name"]: e for e in entries}
    assert len(by_name) == len(entries), "two entries of one name"
    return by_name


def _manifest():
    m = _json("BENCHMARK.json")
    return {key: _named(m[key])
            for key in ("configs", "workloads", "end_to_end", "per_layer")}


# --------------------------------------------- the manifest: this PR's entries

def test_the_manifest_lists_the_cell_and_its_configuration_by_name():
    m = _manifest()
    cell = m["workloads"][CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    rate = _json("benchmark", "traffic", MIX + ".json")["arrivals"]["rate_per_s"]
    assert f"Poisson {rate:g}/s = 1.5 x the knee" in cell["why"]
    # both things the guide asks a share's cell to say
    assert "a held expert sees" in cell["why"] and "4x" in cell["why"]
    entry = m["configs"][CONFIG]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert entry["source"] == ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-"
                               "120B-A12B-BF16/blob/main/config.json")
    assert entry["source"] == _json(entry["file"])["source"]
    tput = m["end_to_end"]["embed_residues_per_s"]
    assert CELL in tput["workloads"] and tput["bound"] == 0.07
    assert "workloads" not in m["end_to_end"]["setup_s"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_manifest_lists_the_new_metric_by_name_with_a_reader(name):
    from benchmark import run as bench_run

    unit, better, layer = NEW[name]
    entry = dict(_manifest()["per_layer"][name])
    assert CELL in entry.pop("workloads")
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "device_trace", "layer": layer,
                     "moves": "embed_residues_per_s"}
    # a program without the scope, a run without a trace: nothing to read
    assert bench_run._layer_metric(name)({}) is None


@pytest.mark.parametrize("name", sorted(JOINED))
def test_a_list_the_cell_joined_holds_it_after_the_cells_it_held(name):
    cells = _manifest()["per_layer"][name]["workloads"]
    assert CELL in cells
    before = [c for c in cells[:cells.index(CELL)]]
    assert before and set(before) <= set(TRAIN + SERVE)     # appended, nothing taken


def test_the_cell_is_in_no_other_models_list_and_the_counts_stand():
    m = _manifest()
    for name in NOT_ITS:
        assert CELL not in m["per_layer"][name]["workloads"], name
    eight = set(TRAIN + SERVE)
    assert eight <= set(m["workloads"])
    assert {n for n in eight if m["workloads"][n]["chips"] == 4} == {
        "pretrain-large-fsdp4"}
    used = {cell["config"] for cell in m["workloads"].values()}
    assert used == set(m["configs"]), "a configuration that no cell runs"
    for name in (CELL,):
        assert os.path.exists(os.path.join(ROOT, "benchmark", "workloads", name + ".json"))
        assert os.path.exists(os.path.join(ROOT, "benchmark", "traffic", MIX + ".json"))


# ------------------------- what the nine marked assertions held, as "contains"

@pytest.mark.parametrize("name", sorted(STARTUP))
def test_a_startup_metric_stands_as_written_and_lists_every_cell_it_listed(name):
    """`test_startup_metrics.py::test_the_manifest_lists_the_metric_by_name_
    with_a_reader[name]`, but for `sorted(workloads) == sorted(cells)`."""
    from benchmark import run as bench_run

    unit, layer, moves, cells = STARTUP[name]
    entry = dict(_manifest()["per_layer"][name])
    assert set(cells) <= set(entry.pop("workloads"))
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_span", "layer": layer, "moves": moves}
    read = bench_run._layer_metric(name)
    assert callable(read) and read({"startup_spans": [], "spans": []}) is None


def test_the_served_experts_roofline_stands_as_written_and_lists_both_cells():
    """`test_the_second_served_decoders_own_metric_stands_as_written
    [moe_experts_roofline.tput]`: the entry as PR 35 wrote it, its list
    holding ZAYA1's cell, and after it this one (one share of one scope,
    `moe_experts`, under one name)."""
    entry = dict(_manifest()["per_layer"]["moe_experts_roofline.tput"])
    cells = entry.pop("workloads")
    assert entry == {"name": "moe_experts_roofline.tput", "unit": "%",
                     "better": "higher", "source": "device_trace",
                     "layer": "Kernels and XLA ops", "moves": "embed_residues_per_s"}
    assert cells.index(ZAYA) < cells.index(CELL) and LING not in cells


def test_zayas_cell_configuration_and_metrics_stand_by_name():
    """`test_zaya_cell.py::test_the_manifest_lists_zayas_cell_configuration_
    and_metrics_by_name`, every line of it, a list holding a cell where
    that test held the list whole."""
    from tests.benchmark import test_zaya_cell as zaya

    m = _manifest()
    cell = m["workloads"][zaya.CELL]
    assert cell == {"name": zaya.CELL, "config": zaya.CONFIG, "traffic": zaya.MIX,
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "RATE" not in cell["why"]
    rate = _json("benchmark", "traffic", zaya.MIX + ".json")["arrivals"]["rate_per_s"]
    assert f"Poisson {rate:g}/s = 2.0 x the knee" in cell["why"]
    entry = m["configs"][zaya.CONFIG]
    assert entry["file"] == f"benchmark/configs/{zaya.CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers"] and len(entry["why"]) <= 200
    assert entry["source"].startswith(
        "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json")
    assert "catalog row ZAYA1-8B" in entry["source"]
    tput = m["end_to_end"]["embed_residues_per_s"]
    assert zaya.CELL in tput["workloads"] and tput["bound"] == 0.07
    for name, (unit, better, layer) in zaya.NEW.items():
        got = dict(m["per_layer"][name])
        assert zaya.CELL in got.pop("workloads"), name
        assert got == {"name": name, "unit": unit, "better": better,
                       "source": "device_trace", "layer": layer,
                       "moves": "embed_residues_per_s"}
        if name != "moe_experts_roofline.tput":     # its own: still its alone
            assert m["per_layer"][name]["workloads"] == [zaya.CELL]
    for name in zaya.SHARED:
        assert zaya.CELL in m["per_layer"][name]["workloads"], name
    listed = {n for n, e in m["per_layer"].items()
              if zaya.CELL in e.get("workloads", ())}
    assert listed >= zaya.SHARED | set(zaya.NEW)


def test_a_scope_map_is_made_before_its_seconds_are_read_in_every_cell():
    """`test_the_scope_maps_seconds_are_read_after_every_reader_that_asks`
    held the ORDER of the whole list; what it stands for is that in every
    cell `scope_map_s.*` is read after a reader that asks for the scope
    map has run there (the map is made once a class and kept). By cell:
    a reader that asks and lists the cell stands before `scope_map_s`."""
    layers = _json("BENCHMARK.json")["per_layer"]
    names = [m["name"] for m in layers]
    by_name = _named(layers)
    asks = [n for n in names if n.endswith(("_device_ms.train", "_device_ms.tput",
                                            "_roofline", "coverage_pct"))
            or "scope_coverage_pct" in n]
    assert set(NEW) - {"ssd_core_roofline"} <= set(asks)
    for mine in ("scope_map_s.train", "scope_map_s.tput"):
        for cell in by_name[mine]["workloads"]:
            earlier = [n for n in asks if names.index(n) < names.index(mine)
                       and cell in by_name[n].get("workloads", ())]
            assert earlier, (mine, cell)
    # what stands after it asks only in cells where an earlier reader has
    for n in asks:
        if names.index(n) > names.index("scope_map_s.tput"):
            assert by_name[n]["workloads"] == [CELL], n


# ------------------------------------------------------- the configuration file

def test_the_configuration_file_holds_the_published_widths_and_states_the_cut():
    config = _json("benchmark", "configs", CONFIG + ".json")
    published = config["published"]
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
        assert published == row["config"] and config["source"] == row["source_url"]
    for key, value in published.items():
        assert (config[key] == value) != (key in config["reduced"]), key
    assert config["parameters"] == 5_382_756_608 and config["weights_gib"] == 10.03
    assert config["param_dtype"] == config["dtype"] == "bfloat16"
    assert {"assumed", "not_on_this_path", "deployment", "reduced_note",
            "not_cut"} <= set(config)
    assert set(config["reduced_note"]) == set(config["reduced"])
    assert {"layer", "mamba_in", "mamba_dt", "mamba_groups", "mamba_norm",
            "attention", "router", "router_input", "latent", "shared_expert",
            "balance_bias", "weights", "stream"} <= set(config["assumed"])
    assert "no `time_step_limit`" in config["assumed"]["mamba_dt"]
    assert "NO rotary" in config["assumed"]["attention"]
    assert "four chips" in config["deployment"] and "28 chips" in config["deployment"]


def test_the_loader_refuses_wrong_sizes_and_what_the_program_does_not_build():
    from benchmark.drivers import nemotron_serve

    config = _json("benchmark", "configs", CONFIG + ".json")
    workload = _json("benchmark", "workloads", CELL + ".json")
    m = nemotron_serve.cell_config(workload, config).model
    assert (m.experts_held, m.n_routed_experts, m.num_experts_per_tok,
            m.moe_latent_size, m.pattern_held) == (128, 512, 22, 1024, "MEMEMEM*EMEME")
    for key, wrong in (("hidden_size", 2048), ("n_routed_experts", 64),
                       ("router_width", 256), ("num_experts_per_tok", 8),
                       ("mamba_num_heads", 64), ("ssm_state_size", 64),
                       ("n_groups", 4), ("conv_kernel", 2), ("chunk_size", 256),
                       ("num_key_value_heads", 8), ("head_dim", 64),
                       ("moe_latent_size", 2048), ("moe_intermediate_size", 1344),
                       ("moe_shared_expert_intermediate_size", 2688),
                       ("mlp_hidden_act", "silu"), ("routed_scaling_factor", 2.5),
                       ("num_hidden_layers", 88), ("time_step_max", 1.0),
                       ("use_conv_bias", False), ("mamba_proj_bias", True),
                       ("attention_bias", True), ("sliding_window", 4096),
                       ("model_type", "nemotron_h_v2")):
        with pytest.raises(SystemExit, match=key):
            nemotron_serve.cell_config(workload, dict(config, **{key: wrong}))
    with pytest.raises(SystemExit, match="expand x hidden_size"):
        nemotron_serve.cell_config(workload, dict(config, expand=4))
    pattern = config["hybrid_override_pattern"]
    with pytest.raises(SystemExit, match="hybrid_override_pattern"):
        nemotron_serve.cell_config(workload, dict(
            config, hybrid_override_pattern="E" + pattern[1:]))


def test_the_documents_draw_their_ids_from_the_held_rows():
    from benchmark import traffic
    from benchmark.drivers import nemotron_serve

    mix = traffic.load_mix(MIX)
    for other in ("lm-ragged-sat", "lm-ragged-sat-262k"):    # cells 7 and 8's corpus
        theirs = traffic.load_mix(other)
        assert {k: mix[k] for k in ("lengths", "block")} == {
            k: theirs[k] for k in ("lengths", "block")}
    assert mix["ids"] == {"zipf_exponent": 0.5, "vocab_size": 32768}
    # cells 7 and 8's server but for ONE row a batch: at two rows a batch
    # is 5 % of a 10 s window and six seeds spread by 6.2 % (PERF.md
    # section 4; ISSUE 43 names this fallback)
    server = _json("benchmark", "workloads", CELL + ".json")["server"]
    assert dict(server, max_batch=2) == _json(
        "benchmark", "workloads", ZAYA + ".json")["server"]
    assert server == {
        "serve_mode": "ragged", "max_batch": 1, "pack_max_segments": 16,
        "pipeline_depth": 2, "cache_size": 0, "queue_depth": 4096, "max_wait_s": 0.05}
    docs, lengths = nemotron_serve.documents(mix, 2, 3543000011)
    assert sorted(lengths[:64]) == sorted(traffic.block_lengths(mix))
    assert max(lengths) == 8192 and min(lengths) >= 32
    ids = np.concatenate(docs)
    assert ids.min() >= 0 and 30_000 < ids.max() < 32768 and ids.dtype == np.int32
    assert len({d.tobytes() for d in docs}) == len(docs)


# ------------------------------------------------------------ the driver

@pytest.mark.parametrize("trace", ["0", "1"])
def test_a_rehearsal_prints_the_contracts_line(trace):
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed",
         "3000000043", "--seconds", "1", "--trace", trace, "--rehearse"],
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
        listed = {n for n, e in _manifest()["per_layer"].items()
                  if CELL in e.get("workloads", ())}
        assert JOINED & set(line["metrics"]) and set(line["metrics"]) <= listed
        assert line["metrics"]["dropped_assignments.tput"]["value"] == 0
        assert line["metrics"]["routed_here_share_pct.tput"]["value"] == 100
        assert line["metrics"]["expert_load_max_over_mean.tput"]["value"] >= 1
        # no device plane on the CPU: the scopes' readers find nothing
        assert not set(NEW) & set(line["metrics"])
    assert set(line["compared"]) == COMPARED
    assert line["compared"]["param_count"]["value"] == 551056


@pytest.mark.parametrize("control", ["int8", "state_bf16"])
def test_a_control_one_precision_down_is_not_correct(control):
    """`benchmark.read_nemotron_limits`: the cell's sound run, then the
    reference in the program's place with int8 products, or with the
    recurrence's state rounded to bfloat16 after every token, through the
    cell's own comparison and limits: the sound line is correct, the
    control is not, and the line names the numbers that caught it."""
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.read_nemotron_limits", "--workload", CELL,
         "--seed", "3000000043", "--seconds", "1", "--controls", control,
         "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines() if x.startswith("{")]
    sound, read = lines[-2], lines[-1]
    assert sound["kind"] == "sound" and sound["correct"] is True
    assert read["kind"] == "control:" + control
    assert read["correct"] is False and read["caught_by"], read
    assert all(np.isfinite(v) for v in read["numbers"].values())


def test_every_token_of_a_packed_batch_against_the_reference(capsys):
    """`benchmark.read_nemotron_limits --flips`: the program's trunk over
    one packed batch of the cell's documents against the reference on
    each document alone, EVERY token; in float32 at the rehearsal's
    widths no choice of 4 flips and every token reads float32's rounding."""
    from benchmark import read_nemotron_limits

    assert read_nemotron_limits.main(["--workload", CELL, "--seed", "3000000043",
                                      "--flips", "--rehearse"]) == 0
    line = json.loads([x for x in capsys.readouterr().out.splitlines()
                       if x.startswith("{")][-1])
    assert line["documents"] >= 4 and 64 < line["tokens"] <= 128
    assert 0 < line["err_quantiles"]["1.0"] < 1e-5
    assert set(line["tokens_over"].values()) == {0}


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


def test_the_knee_sweep_runs_this_cells_own_driver(monkeypatch, capsys):
    """`benchmark.find_lm_knee` finds the driver by the workload's file:
    one boot, one line a rate, the knee times `--factor` 1.5."""
    from benchmark import find_lm_knee
    from benchmark.drivers import nemotron_serve

    boots, real = [], nemotron_serve.serving

    def counted(run):
        boots.append(run.seed)
        return real(run)

    monkeypatch.setattr(nemotron_serve, "serving", counted)
    assert find_lm_knee.main(["--workload", CELL, "--rates", "20,40", "--seeds",
                              "29", "--seconds", "1", "--factor", "1.5",
                              "--rehearse"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert boots == [29] and [ln["offered_per_s"] for ln in lines[:-1]] == [20, 40]
    assert all(ln["failed"] == 0 and ln["batches"] > 0 for ln in lines[:-1])
    if lines[-1]["knee_per_s"] is not None:
        assert lines[-1]["saturated_rate_per_s"] == round(1.5 * lines[-1]["knee_per_s"], 1)


# ------------------------------------------------- the yardstick's functions

def _sizes():
    from benchmark.drivers import nemotron_serve

    config = _json("benchmark", "configs", CONFIG + ".json")
    workload = _json("benchmark", "workloads", CELL + ".json")
    return nemotron_serve.reference_sizes(
        config, nemotron_serve.cell_config(workload, config))


def test_nemotron_flops_against_a_count_by_hand():
    from benchmark import nemotron_flops as nf

    c = _sizes()
    # ISSUE 43's arithmetic: the norm, W_in, the taps and their bias, A_log,
    # dt_bias, D, the gated norm's scale, W_out
    mamba = (4096 + 4096 * (8192 + 10240 + 128) + 4 * 10240 + 10240 + 3 * 128
             + 8192 + 8192 * 4096)
    gqa = 4096 + 4096 * 4096 + 2 * 4096 * 256 + 4096 * 4096
    expert = 2 * 1024 * 2688
    moe = (4096 + 4096 * 512 + 2 * 4096 * 1024 + 128 * expert + 2 * 4096 * 5376)
    assert nf.mamba_params(c) == mamba == 109_640_064
    assert nf.gqa_params(c) == gqa == 35_655_680
    assert nf.expert_params(c) == expert == 5_505_024
    assert nf.latent_moe_params(c) == moe == 759_173_120
    assert nf.layer_counts(c) == {"mamba": 6, "gqa": 1, "latent_moe": 6}
    assert nf.param_count(c) == 6 * mamba + gqa + 6 * moe + 32768 * 4096 + 4096
    assert nf.param_count(c) == 5_382_756_608
    # one real token, no pair, no assignment: every weight product and tap,
    # and the recurrence's 5 x 64 x 128 a head
    per_token = (6 * 2 * (4096 * 18560 + 4 * 10240 + 8192 * 4096)
                 + 6 * 5 * 128 * 64 * 128
                 + 2 * (4096 * 4096 + 2 * 4096 * 256 + 4096 * 4096)
                 + 6 * 2 * (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376))
    assert nf.forward_flops(c, 1, 0, 0) == per_token
    # one (query, key) pair: scores and values, 32 query heads of 128, one layer
    assert nf.forward_flops(c, 0, 1, 0) == 2.0 * 2 * 32 * 128
    # one assignment: its expert's two matrices
    assert nf.forward_flops(c, 0, 0, 1) == 2.0 * expert
    assert nf.experts_flops(c, 5) == 5 * 2.0 * expert
    assert nf.ssd_core_flops(c, 10) == 5.0 * 10 * 128 * 64 * 128
    # x, B, C in and y out in bfloat16, dt in float32
    assert nf.ssd_core_min_bytes(c, 10) == 10 * (2.0 * (10240 + 8192) + 4.0 * 128)
    assert nf.experts_min_bytes(c, 6) == 2.0 * 6 * 128 * expert
    # ISSUE 43's token: ~2.47 GFLOP with 5.5 assignments a layer, Mamba ~55 %
    token = nf.forward_flops(c, 1, 0, 6 * 5.5)
    assert 2.40e9 < token < 2.50e9
    assert 0.53 < 6 * 2 * (4096 * 18560 + 4 * 10240 + 8192 * 4096 + 2.5 * 128 * 64 * 128) / token < 0.57
    # a full batch of ISSUE 43's documents: ~40 TFLOP
    full = nf.forward_flops(c, 16384, 16384 * 1900, 16384 * 5.5 * 6)
    assert 39e12 < full < 42e12


def test_the_new_reader_on_hand_made_observations(monkeypatch):
    from benchmark import nemotron_readers, span_readers

    peaks = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}
    obs = {"peaks": peaks, "ssd_core_flops": 1e12, "ssd_core_min_bytes": 8e9}
    ms = {"ssd_core": 40.0}
    monkeypatch.setattr(span_readers, "scope_ms", lambda o, scope: ms.get(scope))
    # compute: 1e12 / 200e12 = 5 ms; memory: 8e9 / 800e9 = 10 ms, the longer, of 40
    assert nemotron_readers.ssd_core_roofline_pct(obs) == pytest.approx(25.0)
    ms.clear()      # a program without the scope (a parent commit)
    assert nemotron_readers.ssd_core_roofline_pct(obs) is None
    ms.update(ssd_core=40.0)
    assert nemotron_readers.ssd_core_roofline_pct(dict(obs, peaks=None)) is None
    assert nemotron_readers.ssd_core_roofline_pct({"peaks": peaks}) is None
    monkeypatch.undo()
    assert nemotron_readers.ssd_core_roofline_pct({}) is None
