"""CLI tests (reference C15/C16 parity — and unlike the reference's
create_uniref_db.py, these parsers must actually construct)."""

import gzip
import json
import os

import numpy as np
import pytest

from proteinbert_tpu.cli.main import apply_overrides, build_parser, main
from proteinbert_tpu.configs import get_preset

from tests.test_etl import GO_TXT, RECORDS, SEQS, _make_xml


@pytest.fixture(scope="module")
def etl_inputs(tmp_path_factory):
    d = tmp_path_factory.mktemp("cli")
    (d / "go.txt").write_text(GO_TXT)
    with gzip.open(d / "uniref.xml.gz", "wt") as f:
        f.write(_make_xml(RECORDS))
    (d / "uniref.fasta").write_text(
        "".join(f">{k} desc\n{v}\n" for k, v in SEQS.items()))
    return d


def test_parser_constructs():
    p = build_parser()
    for cmd in ("create-uniref-db", "merge-uniref-dbs", "create-h5",
                "pretrain", "smoke"):
        assert cmd in p.format_help()


def test_apply_overrides():
    cfg = get_preset("tiny")
    cfg2 = apply_overrides(cfg, ["model.local_dim=64", "train.max_steps=7",
                                 "model.remat=true"])
    assert cfg2.model.local_dim == 64
    assert cfg2.train.max_steps == 7
    assert cfg2.model.remat is True
    assert cfg.model.local_dim == 32  # original untouched (frozen tree)
    with pytest.raises(SystemExit):
        apply_overrides(cfg, ["model.nope=1"])


def test_etl_commands_end_to_end(etl_inputs, tmp_path):
    db = tmp_path / "ann.db"
    csv = tmp_path / "meta.csv"
    h5 = tmp_path / "data.h5"
    assert main([
        "create-uniref-db",
        "--uniref-xml", str(etl_inputs / "uniref.xml.gz"),
        "--go-meta", str(etl_inputs / "go.txt"),
        "--output-db", str(db),
        "--go-meta-csv", str(csv),
    ]) == 0
    assert db.exists() and csv.exists()
    assert main([
        "create-h5",
        "--db", str(db),
        "--fasta", str(etl_inputs / "uniref.fasta"),
        "--go-meta-csv", str(csv),
        "--output", str(h5),
        "--min-records", "2",
    ]) == 0
    assert h5.exists()

    import h5py

    with h5py.File(h5, "r") as f:
        assert f["seqs"].shape[0] == 3  # one record has no FASTA entry


def test_sharded_etl_commands(etl_inputs, tmp_path):
    merged = tmp_path / "merged.db"
    csv = tmp_path / "meta.csv"
    for k in range(2):
        assert main([
            "create-uniref-db",
            "--uniref-xml", str(etl_inputs / "uniref.xml.gz"),
            "--go-meta", str(etl_inputs / "go.txt"),
            "--output-db", str(merged),
            "--task-index", str(k), "--task-count", "2",
        ]) == 0
    assert main([
        "merge-uniref-dbs",
        "--output-db", str(merged), "--num-shards", "2",
        "--go-meta", str(etl_inputs / "go.txt"),
        "--go-meta-csv", str(csv),
    ]) == 0
    from proteinbert_tpu.etl import read_aggregates

    counts, n_any = read_aggregates(str(merged))
    assert n_any == 3 and counts["GO:0000001"] == 3


def test_pretrain_cli_on_h5(etl_inputs, tmp_path):
    """Full user journey: ETL → pretrain CLI on the built file."""
    db, csv, h5 = tmp_path / "a.db", tmp_path / "m.csv", tmp_path / "d.h5"
    main(["create-uniref-db", "--uniref-xml", str(etl_inputs / "uniref.xml.gz"),
          "--go-meta", str(etl_inputs / "go.txt"), "--output-db", str(db),
          "--go-meta-csv", str(csv)])
    main(["create-h5", "--db", str(db), "--fasta", str(etl_inputs / "uniref.fasta"),
          "--go-meta-csv", str(csv), "--output", str(h5), "--min-records", "2"])
    hist = tmp_path / "hist.json"
    assert main([
        "pretrain", "--preset", "tiny", "--data", str(h5),
        "--max-steps", "4", "--checkpoint-dir", str(tmp_path / "ck"),
        "--history-json", str(hist),
        "--set", "data.batch_size=2", "--set", "train.log_every=2",
        "--set", "checkpoint.every_steps=0", "--set", "optimizer.warmup_steps=2",
        "--set", "model.num_blocks=1", "--set", "model.local_dim=8",
        "--set", "model.global_dim=16", "--set", "model.key_dim=4",
        "--set", "data.seq_len=32",
    ]) == 0
    h = json.loads(hist.read_text())
    assert len(h) == 2 and np.isfinite(h[-1]["loss"])


TINY_SETS = [
    "--set", "data.batch_size=4", "--set", "model.num_blocks=1",
    "--set", "model.local_dim=8", "--set", "model.global_dim=16",
    "--set", "model.key_dim=4", "--set", "model.num_annotations=32",
    "--set", "data.seq_len=32",
]


def test_finetune_cli_from_pretrained(tmp_path):
    """pretrain → checkpoint → finetune --pretrained loads the trunk."""
    ck = tmp_path / "ck"
    assert main([
        "pretrain", "--preset", "tiny", "--max-steps", "2",
        "--checkpoint-dir", str(ck), *TINY_SETS,
        "--set", "train.log_every=0", "--set", "checkpoint.every_steps=2",
        "--set", "checkpoint.async_save=false",
        "--set", "optimizer.warmup_steps=2",
    ]) == 0
    hist = tmp_path / "ft.json"
    ft_ck = tmp_path / "ft_ck"
    assert main([
        "finetune", "--preset", "tiny", "--task", "sequence_classification",
        "--num-outputs", "3", "--epochs", "1",
        "--pretrained", str(ck), "--history-json", str(hist),
        "--checkpoint-dir", str(ft_ck), *TINY_SETS,
    ]) == 0
    h = json.loads(hist.read_text())
    assert len(h) == 1 and np.isfinite(h[0]["train_loss"])
    assert "eval_accuracy" in h[0]
    # The fine-tuned weights were actually persisted (per-epoch ckpt).
    assert any(ft_ck.iterdir())


def test_finetune_cli_fresh_trunk(tmp_path):
    assert main([
        "finetune", "--preset", "tiny", "--task", "sequence_regression",
        "--num-outputs", "1", "--epochs", "1", "--freeze-trunk",
        "--checkpoint-dir", str(tmp_path / "ck"), *TINY_SETS,
    ]) == 0


def test_finetune_cli_tsv_data(tmp_path):
    """Real-data path: TSV → load → train → eval (secondary-structure
    shape: per-residue labels as a digit string)."""
    rng = np.random.default_rng(3)
    lines = []
    for _ in range(24):
        L = int(rng.integers(10, 30))
        seq = "".join(rng.choice(list("ACDEFGHIKLMNPQRSTVWY"), size=L))
        labels = "".join(str((ord(c) + 1) % 3) for c in seq)
        lines.append(f"{seq}\t{labels}")
    tsv = tmp_path / "ss.tsv"
    tsv.write_text("# seq<TAB>labels\n" + "\n".join(lines) + "\n")
    hist = tmp_path / "h.json"
    assert main([
        "finetune", "--preset", "tiny", "--task", "token_classification",
        "--num-outputs", "3", "--epochs", "3",
        "--data", str(tsv), "--eval-data", str(tsv),
        "--checkpoint-dir", str(tmp_path / "ck"),
        "--history-json", str(hist), *TINY_SETS,
        "--set", "optimizer.warmup_steps=2",
        "--set", "optimizer.learning_rate=3e-3",
    ]) == 0
    h = json.loads(hist.read_text())
    assert h[-1]["train_loss"] < h[0]["train_loss"]  # label fn is learnable


def test_merge_requires_shard_spec(tmp_path):
    with pytest.raises(SystemExit, match="--shards or --num-shards"):
        main(["merge-uniref-dbs", "--output-db", str(tmp_path / "m.db")])


def test_smoke_honors_preset_flag():
    # smoke defaults to tiny but must not silently override a user choice.
    p = build_parser()
    assert p.parse_args(["smoke"]).preset == "tiny"
    assert p.parse_args(["smoke", "--preset", "base"]).preset == "base"


def test_platform_flag(tmp_path):
    """--platform forces the backend before first device use."""
    import subprocess
    import sys

    p = build_parser()
    assert p.parse_args(["--platform", "cpu", "smoke"]).platform == "cpu"
    assert p.parse_args(["smoke"]).platform is None
    # PB_PLATFORM env (the examples' knob) is the flag's default, so any
    # CLI invocation — not just full_workflow.sh — honors it.
    import unittest.mock as mock

    with mock.patch.dict("os.environ", {"PB_PLATFORM": "cpu"}):
        assert build_parser().parse_args(["smoke"]).platform == "cpu"
    with mock.patch.dict("os.environ", {"PB_PLATFORM": ""}):
        assert build_parser().parse_args(["smoke"]).platform is None
    # End-to-end in a SUBPROCESS: forcing the platform initializes and
    # caches that backend set process-wide (restoring the config value
    # would not undo it), so the mutation must not happen in the pytest
    # process.
    code = (
        "import sys; from proteinbert_tpu.cli.main import main; "
        "sys.exit(main(["
        "'--platform', 'cpu', 'smoke', '--max-steps', '2', "
        "'--set', 'data.batch_size=4', '--set', 'train.log_every=1', "
        "'--set', 'model.num_blocks=1', '--set', 'model.local_dim=8', "
        "'--set', 'model.global_dim=16', '--set', 'model.key_dim=4', "
        "'--set', 'model.num_annotations=32', '--set', 'data.seq_len=32', "
        "'--set', 'optimizer.warmup_steps=2']))"
    )
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]


def test_smoke_cli(tmp_path):
    assert main([
        "smoke", "--max-steps", "4",
        "--set", "data.batch_size=4", "--set", "train.log_every=2",
        "--set", "model.num_blocks=1", "--set", "model.local_dim=8",
        "--set", "model.global_dim=16", "--set", "model.key_dim=4",
        "--set", "model.num_annotations=32", "--set", "data.seq_len=32",
        "--set", "checkpoint.every_steps=0",
    ]) == 0


def test_metrics_jsonl_flag(tmp_path):
    mj = tmp_path / "metrics.jsonl"
    assert main([
        "smoke", "--max-steps", "4", "--metrics-jsonl", str(mj),
        "--checkpoint-dir", str(tmp_path / "ck"), *TINY_SETS,
        "--set", "train.log_every=2", "--set", "checkpoint.every_steps=0",
    ]) == 0
    lines = [json.loads(x) for x in mj.read_text().splitlines()]
    assert [r["step"] for r in lines] == [2, 4]
    assert all(np.isfinite(r["loss"]) for r in lines)


def test_bucketed_pretrain_on_h5_with_resume(etl_inputs, tmp_path):
    """ETL → bucketed pretrain on the real HDF5 file → preempt-free
    checkpoint resume continues the bucketed stream (index-only skip)."""
    db, csv, h5 = tmp_path / "a.db", tmp_path / "m.csv", tmp_path / "d.h5"
    main(["create-uniref-db", "--uniref-xml", str(etl_inputs / "uniref.xml.gz"),
          "--go-meta", str(etl_inputs / "go.txt"), "--output-db", str(db),
          "--go-meta-csv", str(csv)])
    main(["create-h5", "--db", str(db), "--fasta", str(etl_inputs / "uniref.fasta"),
          "--go-meta-csv", str(csv), "--output", str(h5), "--min-records", "2"])
    ck = tmp_path / "ck"
    sets = ["--set", "data.batch_size=2", "--set", "model.num_blocks=1",
            "--set", "model.local_dim=8", "--set", "model.global_dim=16",
            "--set", "model.key_dim=4", "--set", "data.seq_len=32",
            "--set", "data.buckets=[16,32]", "--set", "train.log_every=0",
            "--set", "checkpoint.every_steps=2",
            "--set", "checkpoint.async_save=false",
            "--set", "optimizer.warmup_steps=2"]
    assert main(["pretrain", "--preset", "tiny", "--data", str(h5),
                 "--max-steps", "2", "--checkpoint-dir", str(ck), *sets]) == 0
    # Resume extends the same run two more steps.
    assert main(["pretrain", "--preset", "tiny", "--data", str(h5),
                 "--max-steps", "4", "--checkpoint-dir", str(ck), *sets]) == 0
    from proteinbert_tpu.train import Checkpointer

    c = Checkpointer(str(ck), async_save=False)
    assert c.latest_step() == 4
    c.close()


def test_config_json_roundtrip_all_presets():
    from proteinbert_tpu.configs import config_from_dict, config_to_dict

    for name in ("tiny", "base", "long", "large"):
        cfg = get_preset(name)
        assert config_from_dict(config_to_dict(cfg)) == cfg


def test_pretrain_writes_config_json_and_inference_needs_no_overrides(tmp_path):
    """The killer usability path: pretrain with custom geometry → every
    downstream command reconstructs the run config from config.json with
    NO --pretrained-set flags."""
    import json

    from proteinbert_tpu.cli.main import main
    from proteinbert_tpu.configs import load_config

    ck = str(tmp_path / "run")
    overrides = ["--set", "model.local_dim=32", "--set", "model.global_dim=64",
                 "--set", "model.key_dim=16", "--set", "model.num_blocks=2",
                 "--set", "model.num_annotations=64",
                 "--set", "model.dtype=float32", "--set", "data.seq_len=48",
                 "--set", "data.batch_size=4"]
    assert main(["pretrain", "--preset", "tiny", *overrides,
                 "--max-steps", "3", "--checkpoint-dir", ck]) == 0
    saved = load_config(str(tmp_path / "run" / "config.json"))
    assert saved.model.local_dim == 32 and saved.data.seq_len == 48

    emb = str(tmp_path / "e.npz")
    assert main(["embed", "--pretrained", ck, "--output", emb,
                 "MKTAYIAKQR"]) == 0
    import numpy as np
    assert np.load(emb)["global"].shape == (1, 64)

    out = str(tmp_path / "ev.json")
    assert main(["evaluate", "--pretrained", ck, "--max-batches", "2",
                 "--output", out]) == 0
    assert json.load(open(out))["step"] == 3

    npz = str(tmp_path / "w.npz")
    assert main(["export-weights", "--pretrained", ck,
                 "--output", npz]) == 0

    # finetune restores the trunk through config.json too
    assert main(["finetune", "--preset", "tiny", "--pretrained", ck,
                 "--task", "sequence_classification", "--num-outputs", "3",
                 "--epochs", "1",
                 "--set", "data.seq_len=48", "--set", "data.batch_size=4",
                 "--checkpoint-dir", str(tmp_path / "ft")]) == 0


def test_pretrained_set_overrides_config_json(tmp_path):
    """Explicit --pretrained-set still wins over the saved config."""
    from proteinbert_tpu.cli.main import _pretrain_run_config
    from proteinbert_tpu.configs import save_config

    cfg = get_preset("tiny")
    (tmp_path / "run").mkdir()
    save_config(cfg, str(tmp_path / "run" / "config.json"))
    got = _pretrain_run_config(str(tmp_path / "run"), "base",
                               ["data.seq_len=99"])
    assert got.data.seq_len == 99
    assert got.model.local_dim == cfg.model.local_dim  # from json, not preset


def test_corrupt_config_json_gives_clear_error(tmp_path):
    from proteinbert_tpu.cli.main import _pretrain_run_config

    (tmp_path / "run").mkdir()
    (tmp_path / "run" / "config.json").write_text('{"model": {"local_')
    with pytest.raises(SystemExit, match="corrupt config.json"):
        _pretrain_run_config(str(tmp_path / "run"), "tiny", [])


def test_save_config_leaves_no_tmp_and_is_readable(tmp_path):
    from proteinbert_tpu.configs import load_config, save_config

    cfg = get_preset("long")  # exercises the bucket tuple
    path = tmp_path / "config.json"
    save_config(cfg, str(path))
    assert load_config(str(path)) == cfg
    assert [p.name for p in tmp_path.iterdir()] == ["config.json"]


def test_data_bench_cli(tmp_path, capsys):
    import json as _json

    from proteinbert_tpu.cli.main import main

    assert main(["data-bench", "--preset", "tiny", "--batches", "5",
                 "--set", "model.num_annotations=64",
                 "--set", "data.batch_size=4",
                 "--set", "data.seq_len=48"]) == 0
    lines = [ln for ln in capsys.readouterr().out.strip().split("\n")
             if ln.startswith("{")]
    assert len(lines) == 2
    for ln in lines:
        r = _json.loads(ln)
        assert r["variant"] in ("direct", "prefetch")
        assert r["batches_per_sec"] > 0 and r["batches"] == 5


def test_finetune_writes_config_json(tmp_path):
    from proteinbert_tpu.cli.main import main
    from proteinbert_tpu.configs import FinetuneConfig, load_config

    ft = str(tmp_path / "ft")
    assert main(["finetune", "--preset", "tiny",
                 "--task", "sequence_classification", "--num-outputs", "3",
                 "--epochs", "1", "--set", "data.seq_len=48",
                 "--set", "data.batch_size=4", "--set", "model.local_dim=32",
                 "--set", "model.num_annotations=64",
                 "--checkpoint-dir", ft]) == 0
    saved = load_config(str(tmp_path / "ft" / "config.json"),
                        FinetuneConfig)
    assert saved.task.kind == "sequence_classification"
    assert saved.model.local_dim == 32


def test_finetune_rejects_shared_run_dir(tmp_path):
    from proteinbert_tpu.cli.main import main

    d = str(tmp_path / "run")
    with pytest.raises(SystemExit, match="must differ"):
        main(["finetune", "--preset", "tiny", "--pretrained", d,
              "--checkpoint-dir", d])
