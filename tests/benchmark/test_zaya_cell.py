"""The cell PR 35 added (`serve-zaya1-8b-sat`), on the CPU at `zaya_tiny`
widths: the driver end to end, its control coming out not correct, an
answer altered where it is produced coming out not correct, the loader's
refusals, `cca_flops` against a count by hand, the new readers on
hand-made observations; and what three assertions of `test_ling_cell.py`
held of the manifest (tests/conftest.py marks them), found BY NAME: no
assertion here reads an entry by its place or counts a whole list, so the
next cell appended breaks nothing."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL, CONFIG, MIX = "serve-zaya1-8b-sat", "zaya1-8b-pp2", "lm-ragged-sat-262k"
LING = "serve-ling3flash-sat"
NEW = {"cca_device_ms.tput": ("ms", "lower", "Model (models/glm_moe.py)"),
       "cca_mix_device_ms.tput": ("ms", "lower", "Model (models/glm_moe.py)"),
       "cca_core_device_ms.tput": ("ms", "lower", "Kernels and XLA ops"),
       "cca_core_roofline": ("%", "higher", "Kernels and XLA ops"),
       "moe_experts_roofline.tput": ("%", "higher", "Kernels and XLA ops"),
       "residual_device_ms.tput": ("ms", "lower", "Model (models/glm_moe.py)")}
SHARED = {"queue_wait_ms.tput", "batch_fill_pct.tput", "batch_device_ms.tput",
          "latency_p95_ms.tput", "generator_late_ms.tput", "device_idle_pct.tput",
          "peak_hbm_gib.tput", "compiles_in_window.tput", "pack_ms.tput",
          "assemble_ms.tput", "slot_wait_ms.tput", "finalize_host_ms.tput",
          "mfu_pct.tput", "packed_scope_coverage_pct", "moe_router_device_ms.tput",
          "moe_dispatch_device_ms.tput", "moe_experts_device_ms.tput",
          "expert_load_max_over_mean.tput", "routed_here_share_pct.tput",
          "dropped_assignments.tput"}
LINGS_OWN = {"kda_device_ms.tput", "kda_core_device_ms.tput", "mla_device_ms.tput",
             "shared_expert_device_ms.tput", "kda_core_roofline"}
COMPARED = {"failed_requests", "dropped_assignments", "param_count", "global_bias",
            "global_rel_err_rms", "global_rel_err_max", "local_mean_bias",
            "local_mean_rel_err_rms", "local_mean_rel_err_max",
            "global_rel_err_q1", "local_mean_rel_err_q1", "global_bias_but_one",
            "global_rel_err_rms_but_one", "global_rel_err_max_but_one"}


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


# ------------------------------------------------------------ the manifest

def test_the_manifest_lists_zayas_cell_configuration_and_metrics_by_name():
    m = _manifest()
    cell = m["workloads"][CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "RATE" not in cell["why"]
    rate = _json("benchmark", "traffic", MIX + ".json")["arrivals"]["rate_per_s"]
    assert f"Poisson {rate:g}/s = 2.0 x the knee" in cell["why"]
    entry = m["configs"][CONFIG]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers"] and len(entry["why"]) <= 200
    assert entry["source"].startswith(
        "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json")
    assert "catalog row ZAYA1-8B" in entry["source"]
    tput = m["end_to_end"]["embed_residues_per_s"]
    assert CELL in tput["workloads"] and tput["bound"] == 0.07
    for name, (unit, better, layer) in NEW.items():
        assert m["per_layer"][name] == {
            "name": name, "unit": unit, "better": better, "source": "device_trace",
            "layer": layer, "moves": "embed_residues_per_s", "workloads": [CELL]}
    for name in SHARED:
        assert CELL in m["per_layer"][name]["workloads"], name
    listed = {n for n, e in m["per_layer"].items() if CELL in e.get("workloads", ())}
    assert listed >= SHARED | set(NEW)      # later PRs append metrics of every cell


def test_every_cell_has_its_files_and_one_cell_is_on_four_chips():
    """What `test_ling_cell.py` held of six cells, of however many the
    manifest counts: the seven this PR knows are among them, one of the
    seven is on four chips, and every entry has the files the harness
    finds by its name."""
    m = _manifest()
    seven = {"pretrain-base-dense", "serve-base-sat", "pretrain-large-dense",
             "pretrain-glm47flash-packed8k", "pretrain-large-fsdp4", LING, CELL}
    assert seven <= set(m["workloads"])
    assert {n for n in seven if m["workloads"][n]["chips"] == 4} == {
        "pretrain-large-fsdp4"}
    for name, cell in m["workloads"].items():
        assert cell["chips"] in (1, 4)
        assert os.path.exists(os.path.join(ROOT, "benchmark", "workloads", name + ".json"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", cell["traffic"] + ".json"))
        assert os.path.exists(os.path.join(ROOT, m["configs"][cell["config"]]["file"]))
    for name in m["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py")), name
    used = {cell["config"] for cell in m["workloads"].values()}
    assert used == set(m["configs"]), "a configuration that no cell runs"


def test_lings_entries_and_the_older_ones_stand_whole():
    """Found by name: Ling's cell, configuration and eleven metrics as PR
    33 wrote them (its own five still its alone, the six it shares now
    with this cell after it), `mla_core_device_ms.train` as PR 31 wrote
    it, and each list that held a cell still holding it."""
    m = _manifest()
    assert m["workloads"][LING] == {
        "name": LING, "config": "ling-3.0-flash-ep4", "traffic": "lm-ragged-sat",
        "chips": 1,
        "why": "ragged server, 2 rows x 8,192 x 16 docs, embed of token documents "
               "(log-normal, median 1,200, to 8,192), Poisson 21/s = 1.5 x the knee "
               "(14/s): one- and two-row batches; a held expert sees ~256 tokens"}
    ling = m["configs"]["ling-3.0-flash-ep4"]
    assert ling["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                               "num_experts", "vocab_size"]
    assert ling["file"] == "benchmark/configs/ling-3.0-flash-ep4.json"
    tput = m["end_to_end"]["embed_residues_per_s"]["workloads"]
    assert tput.index("serve-base-sat") < tput.index(LING) < tput.index(CELL)
    for name in LINGS_OWN:
        assert LING in m["per_layer"][name]["workloads"], name
        assert CELL not in m["per_layer"][name]["workloads"], name
        assert m["per_layer"][name]["moves"] == "embed_residues_per_s"
    for name in SHARED:
        cells = m["per_layer"][name]["workloads"]
        assert LING in cells and cells.index(CELL) > cells.index(LING), name
    assert m["per_layer"]["mla_core_device_ms.train"] == {
        "name": "mla_core_device_ms.train", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "Kernels and XLA ops",
        "moves": "train_residues_per_s",
        "workloads": ["pretrain-glm47flash-packed8k"]}
    assert {"serve-base-sat", LING, CELL} <= set(m["per_layer"]["mfu_pct.tput"]["workloads"])
    assert CELL not in m["per_layer"]["packed_encode_roofline"]["workloads"]


def test_no_test_of_the_benchmark_is_pinned_by_the_suites_conftest_any_more():
    """`tests/conftest.py` (not a file of the benchmark: PR 41 could not
    edit it) still names five assertions `xfail(strict=True)`. PR 41 put
    them right under new names, so its two dictionaries, while they stay,
    match no test."""
    from tests import conftest

    pinned = {**getattr(conftest, "PINNED_TO_AN_OLDER_MANIFEST", {}),
              **getattr(conftest, "PINNED_SINCE_PR_39", {})}
    for tail in pinned:
        path, name = tail.split("::")
        with open(os.path.join(ROOT, "tests", path)) as f:
            assert "def " + name.split("[")[0] + "(" not in f.read(), tail


def test_the_configuration_file_holds_the_published_widths_and_states_the_cut():
    config = _json("benchmark", "configs", CONFIG + ".json")
    published = config["published"]
    assert config["reduced"] == ["num_hidden_layers"]
    for key, value in published.items():
        assert (config[key] == value) != (key in config["reduced"]), key
    assert published["num_hidden_layers"] == 40 and config["num_hidden_layers"] == 24
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["num_experts"], config["num_experts_per_tok"],
            config["moe_intermediate_size"], config["router_hidden_size"],
            config["vocab_size"], config["cca_time0"], config["cca_time1"],
            config["partial_rotary_factor"], config["rope_theta"]) == (
                2048, 8, 2, 128, 16, 1, 2048, 256, 262272, 2, 2, 0.5, 5_000_000)
    assert config["rope_theta"] == published["rope_parameters"]["hybrid"]["rope_theta"]
    assert config["parameters"] == 5_519_138_864
    assert config["param_dtype"] == config["dtype"] == "bfloat16"
    assert {"assumed", "not_on_this_path", "deployment", "reduced_note",
            "not_cut"} <= set(config)
    # every (a) of ISSUE 35, and the recipe
    assert {"conv0", "conv1", "qk_mean", "qk_norm", "rotary", "value_shift",
            "router_state", "router_mlp", "residual_scaling", "weights",
            "stream"} <= set(config["assumed"])
    assert "shares-add-up" in config["not_cut"]


# ------------------------------------------------------------ the driver

@pytest.mark.parametrize("trace", ["0", "1"])
def test_a_rehearsal_prints_the_contracts_line(trace):
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
    assert "kernel paths: cca_core {'reference/tiles_do_not_fit'" in done.stdout
    if trace == "0":
        assert set(line["metrics"]) == {"embed_residues_per_s", "setup_s"}
    else:
        listed = {n for n, e in _manifest()["per_layer"].items()
                  if CELL in e.get("workloads", ())}
        assert SHARED & set(line["metrics"]) and set(line["metrics"]) <= listed
        assert line["metrics"]["dropped_assignments.tput"]["value"] == 0
        assert line["metrics"]["routed_here_share_pct.tput"]["value"] == 100
        assert line["metrics"]["expert_load_max_over_mean.tput"]["value"] >= 1
        # no device plane on the CPU: the scopes' readers find nothing
        assert not set(NEW) & set(line["metrics"])
    assert set(line["compared"]) == COMPARED
    assert line["compared"]["param_count"]["value"] == 546344


def test_the_loader_refuses_another_layer_kind_and_wrong_sizes():
    from benchmark.drivers import cca_serve

    config = _json("benchmark", "configs", CONFIG + ".json")
    workload = _json("benchmark", "workloads", CELL + ".json")
    m = cca_serve.cell_config(workload, config).model
    assert (m.experts_held, m.n_routed_experts, m.num_key_value_heads,
            m.first_layer_index) == (16, 16, 2, 0)
    for key, wrong in (("hidden_size", 1024), ("num_experts", 8),
                       ("num_key_value_heads", 8), ("head_dim", 64),
                       ("cca_time1", 4), ("partial_rotary_factor", 1.0),
                       ("router_hidden_size", 128), ("num_experts_per_tok", 2),
                       ("rope_theta", 10000), ("sliding_window", 4096),
                       ("attention_bias", True), ("num_hidden_layers", 40)):
        with pytest.raises(SystemExit, match=key):
            cca_serve.cell_config(workload, dict(config, **{key: wrong}))
    kinds = list(config["layer_types"])
    kinds[3] = "hybrid_sliding"         # a held layer of the 74B sibling's kind
    with pytest.raises(SystemExit, match="have to be `hybrid`"):
        cca_serve.cell_config(workload, dict(config, layer_types=kinds))
    kinds = list(config["layer_types"])
    kinds[30] = "hybrid_sliding"        # past the stage: not this chip's
    cca_serve.cell_config(workload, dict(config, layer_types=kinds))


@pytest.mark.parametrize("control", ["int8", "flip"])
def test_a_control_one_precision_down_is_not_correct(control):
    """`benchmark.read_cca_limits`: the cell's sound run, then the
    reference in the program's place with int8 products, through the
    cell's own comparison and limits: the sound line is correct, the
    control is not, and the line names the numbers that caught it.
    "flip" is no precision but a reading: one layer's balance bias left
    at zero, so that some last tokens take another expert."""
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.read_cca_limits", "--workload", CELL,
         "--seed", "3000000019", "--seconds", "1", "--controls", control,
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
    if control == "flip":   # some documents' last token flipped, not all
        errs = sorted(read["by_document"]["global"])
        assert errs[-1] > 10 * errs[0] > 0


def test_every_token_of_a_packed_batch_against_the_reference(capsys):
    """`benchmark.read_cca_flips`: the program's trunk over one packed
    batch of the cell's documents against the reference on each document
    alone, EVERY token (what the whole sample's limits stand on); in
    float32 at the rehearsal's widths no top-1 choice flips and every
    token reads the rounding of float32."""
    from benchmark import read_cca_flips

    assert read_cca_flips.main(["--workload", CELL, "--seeds", "3000000019",
                                "--rehearse"]) == 0
    line = json.loads([x for x in capsys.readouterr().out.splitlines()
                       if x.startswith("{")][-1])
    assert line["documents"] >= 4 and 64 < line["tokens"] <= 128
    assert 0 < line["err_quantiles"]["1.0"] < 1e-5
    assert set(line["tokens_over"].values()) == {0}
    tokens, seg, taken = read_cca_flips.packed_batch(
        [np.arange(5), np.arange(7), np.arange(4), np.arange(3)], 2, 8, 2)
    assert [[len(d) for d in row] for row in taken] == [[5, 3], [7]]
    assert seg.tolist() == [[1] * 5 + [2] * 3, [1] * 7 + [0]]
    assert tokens[1, 7] < 0 and tokens[0, 5:].tolist() == [0, 1, 2]


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
    one boot, one line a rate, the knee times `--factor` 2.0."""
    from benchmark import find_lm_knee
    from benchmark.drivers import cca_serve

    boots, real = [], cca_serve.serving

    def counted(run):
        boots.append(run.seed)
        return real(run)

    monkeypatch.setattr(cca_serve, "serving", counted)
    assert find_lm_knee.main(["--workload", CELL, "--rates", "20,40", "--seeds",
                              "29", "--seconds", "1", "--factor", "2.0",
                              "--rehearse"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert boots == [29] and [ln["offered_per_s"] for ln in lines[:-1]] == [20, 40]
    assert all(ln["failed"] == 0 and ln["batches"] > 0 for ln in lines[:-1])
    if lines[-1]["knee_per_s"] is not None:
        assert lines[-1]["saturated_rate_per_s"] == round(2.0 * lines[-1]["knee_per_s"], 1)


# ------------------------------------------------- the yardstick's functions

def _sizes():
    from benchmark.drivers import cca_serve

    config = _json("benchmark", "configs", CONFIG + ".json")
    workload = _json("benchmark", "workloads", CELL + ".json")
    return cca_serve.reference_sizes(config, cca_serve.cell_config(workload, config))


def test_cca_flops_against_a_count_by_hand():
    from benchmark import cca_flops

    c = _sizes()
    # ISSUE 35's arithmetic: W_q, W_k, two value matrices, the two
    # convolutions with their biases, W_o, tau
    mixer = (2048 * 1024 + 2048 * 256 + 2 * 2048 * 128 + 1024 * 2048
             + (2 * 1280 + 1280) + (2 * 10 * 128 * 128 + 1280) + 2)
    router = 2048 * 256 + 256 + 256 + 256 + 2 * (256 * 256 + 256) + 256 * 16
    assert cca_flops.mixer_params(c) == mixer == 5_575_682
    assert cca_flops.router_params(c) == router == 660_736
    assert cca_flops.expert_params(c) == 3 * 2048 * 2048 == 12_582_912
    assert cca_flops.layer_params(c) == 10 * 2048 + mixer + router + 16 * 12_582_912
    assert cca_flops.param_count(c) == 5_519_138_864
    # one real token, no pair, no assignment: every weight product and tap
    per_token = (2048 * 1024 + 2 * 2048 * 256 + 1024 * 2048 + 2 * 1280
                 + 2 * 10 * 128 * 128 + 2048 * 256 + 2 * 256 * 256 + 256 * 16)
    assert cca_flops.forward_flops(c, 1, 0, 0) == 2.0 * 24 * per_token
    # one (query, key) pair: scores and values, 8 query heads of 128, 24 layers
    assert cca_flops.forward_flops(c, 0, 1, 0) == 24 * 2.0 * 2 * 8 * 128
    assert cca_flops.core_flops(c, 10) == 2.0 * 2 * 10 * 8 * 128
    # one assignment: its expert's three matrices
    assert cca_flops.forward_flops(c, 0, 0, 1) == 2.0 * 3 * 2048 * 2048
    assert cca_flops.experts_flops(c, 5) == 5 * 2.0 * 3 * 2048 * 2048
    # q and o at 8 heads, k and v at 2, bfloat16; 16 experts' matrices a layer
    assert cca_flops.core_min_bytes(c, 10) == 2.0 * 10 * (2 * 8 + 2 * 2) * 128
    assert cca_flops.experts_min_bytes(c, 24) == 2.0 * 24 * 16 * 3 * 2048 * 2048
    # a full batch of ISSUE 35's documents: 17.9 TFLOP
    full = cca_flops.forward_flops(c, 16384, 16384 * 1900, 16384 * 24)
    assert 17.5e12 < full < 18.2e12


def test_the_new_readers_on_hand_made_observations(monkeypatch):
    from benchmark import cca_readers, span_readers

    peaks = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}
    obs = {"peaks": peaks, "cca_core_flops": 2e12, "cca_core_min_bytes": 1e9,
           "served_experts_flops": 1e12, "served_experts_min_bytes": 8e9}
    ms = {"cca_core": 40.0, "moe_experts": 20.0}
    monkeypatch.setattr(span_readers, "scope_ms", lambda o, scope: ms.get(scope))
    # compute-bound: 2e12 / 200e12 = 10 ms of 40; memory-bound: 8e9 / 800e9 = 10 of 20
    assert cca_readers.cca_core_roofline_pct(obs) == pytest.approx(25.0)
    assert cca_readers.moe_experts_roofline_pct(obs) == pytest.approx(50.0)
    # a program without the scope (a parent commit), a run without peaks
    # or without the driver's counts: nothing to read
    ms.clear()
    assert cca_readers.cca_core_roofline_pct(obs) is None
    assert cca_readers.moe_experts_roofline_pct(obs) is None
    ms.update(cca_core=40.0, moe_experts=20.0)
    assert cca_readers.cca_core_roofline_pct(dict(obs, peaks=None)) is None
    assert cca_readers.moe_experts_roofline_pct({"peaks": peaks}) is None
    monkeypatch.undo()
    assert cca_readers.cca_core_roofline_pct({}) is None
    for name in NEW:    # each has a reader file that loads and reads nothing here
        from benchmark import run as bench_run

        assert bench_run._layer_metric(name)({}) is None


def test_the_documents_draw_their_ids_from_the_whole_vocabulary():
    from benchmark import traffic
    from benchmark.drivers import cca_serve

    mix = traffic.load_mix(MIX)
    ling = traffic.load_mix("lm-ragged-sat")
    assert {k: mix[k] for k in ("lengths", "block")} == {
        k: ling[k] for k in ("lengths", "block")}      # cells 5 and 7's corpus
    assert mix["ids"] == {"zipf_exponent": 0.5, "vocab_size": 262272}
    docs, lengths = cca_serve.documents(mix, 2, 3500000011)
    assert sorted(lengths[:64]) == sorted(traffic.block_lengths(mix))
    assert max(lengths) == 8192 and min(lengths) >= 32
    ids = np.concatenate(docs)
    assert ids.min() >= 0 and 200_000 < ids.max() < 262272 and ids.dtype == np.int32
    assert len(np.unique(ids)) > 100_000
    assert len({d.tobytes() for d in docs}) == len(docs)


@pytest.mark.parametrize("case", ["sound", "flipped", "two_flipped", "a_quarter_moved",
                                  "int8", "int8_last_token_only", "exchanged"])
def test_the_cells_limits_on_hand_made_answers(case):
    """The cell's limits against answers with the errors the chip read at
    the published widths (PERF.md section 2; `global` / `local_mean` a
    document): the sound program's (0.00011 / 0.00105) is correct, with or
    without ONE last token whose top-1 choice flipped (0.01005, the
    largest of the 115 read among 65,370); two flipped in one sample, or a
    quarter of the last-token vectors moved by less than a flip, are
    caught by the numbers that leave the worst answer out; int8 products'
    (0.0009 / 0.0062) by every number that can tell a precision, and by
    the last-token vectors' own where only they carry them; two answers
    exchanged by the maximum."""
    from benchmark.drivers import cca_serve

    rng = np.random.default_rng(7)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    want = rng.normal(size=(12, 2, 256))
    norm = np.linalg.norm(want, axis=-1, keepdims=True)
    size = {"int8": [0.0009, 0.0062], "int8_last_token_only": [0.0009, 0.00105]}.get(
        case, [0.00011, 0.00105])
    got = want + np.array(size)[None, :, None] * norm * unit(rng.normal(size=want.shape))
    moved = {"flipped": [0.01005], "two_flipped": [0.01005, 0.0045],
             "a_quarter_moved": [0.004] * 3}.get(case, [])
    for i, by in enumerate(moved):
        got[i, 0] += by * norm[i, 0] * unit(rng.normal(size=256))
    if case == "exchanged":
        got[[0, 1]] = got[[1, 0]]
    answers = lambda a: [{"global": x[0], "local_mean": x[1]} for x in a]  # noqa: E731
    gaps = cca_serve.gaps(answers(got), answers(want))
    limits = _json("benchmark", "workloads", CELL + ".json")
    caught = {name for name, value, limit in cca_serve.limit_checks(gaps, limits)
              if not value <= limit}
    but_one = {"global_rel_err_rms_but_one", "global_rel_err_max_but_one",
               "global_bias_but_one"}
    tells_a_precision = set(limits["limits"]) - {
        "global_rel_err_rms", "global_rel_err_max", "global_bias"}
    if case in ("sound", "flipped"):
        assert not caught, (caught, gaps)
    elif case in ("two_flipped", "a_quarter_moved"):
        assert but_one <= caught <= but_one | {"global_rel_err_q1"}, (caught, gaps)
    elif case == "int8":
        assert caught == tells_a_precision, (caught, gaps)
    elif case == "int8_last_token_only":
        assert caught == but_one | {"global_rel_err_q1"}, (caught, gaps)
    else:
        assert "global_rel_err_max" in caught and "local_mean_rel_err_max" in caught


def test_every_limit_that_tells_a_precision_lies_between_its_two_readings():
    """PERF.md section 2's two readings a number (my chip runs, PR 35: the
    largest of the sound runs, the int8 control's smallest), the limit
    between them with room on both sides; and the three numbers a flipped
    top-1 choice enters, whose limits are what ONE flip of twice the
    largest read (0.01005) makes of a sample of 12."""
    read = {"global_rel_err_q1": (0.000109, 0.000842),
            "global_rel_err_rms_but_one": (0.000143, 0.000904),
            "global_rel_err_max_but_one": (0.00023, 0.001049),
            "global_bias_but_one": (0.0000381, 0.000272),
            "local_mean_rel_err_q1": (0.001054, 0.00617),
            "local_mean_rel_err_rms": (0.00111, 0.00628),
            "local_mean_rel_err_max": (0.00138, 0.00650),
            "local_mean_bias": (0.000348, 0.00180)}
    limits = _json("benchmark", "workloads", CELL + ".json")["limits"]
    for name, (sound, control) in read.items():
        assert 2 * sound <= limits[name] <= control / 1.7, name
    flip = limits["global_rel_err_max"]
    assert 0.01005 * 1.9 <= flip <= 0.01005 * 2.1
    assert flip / 12 ** 0.5 <= limits["global_rel_err_rms"] <= 1.1 * flip / 12 ** 0.5
    assert flip / 12 <= limits["global_bias"] <= 1.1 * flip / 12
    assert set(limits) == set(read) | {"global_rel_err_max", "global_rel_err_rms",
                                       "global_bias"}


def test_a_core_off_the_kernel_is_counted():
    from benchmark.drivers.cca_serve import cores_off_the_kernel

    assert cores_off_the_kernel({"pallas/grouped_keys": 3}) == 0
    assert cores_off_the_kernel({"pallas/grouped_keys": 2,
                                 "reference/tiles_do_not_fit": 1}) == 1
    assert cores_off_the_kernel({"reference/not_tpu": 2}) == 1
    assert cores_off_the_kernel({}) == cores_off_the_kernel(None) == 1
