"""Multi-tenant head registry + split-apply + shared-trunk serving
(ISSUE 8): registry round-trip/corruption/trunk-compat, split-apply
parity with the monolithic finetune forward, mixed-head micro-batch
parity vs per-head sequential serving, hot add/remove under concurrent
traffic with drain semantics, the downstream eval harness, and the
per-head diagnose section."""

import json
import threading

import jax
import numpy as np
import pytest

from proteinbert_tpu.configs import (
    DataConfig, FinetuneConfig, ModelConfig, OptimizerConfig,
    PretrainConfig, TaskConfig, TrainConfig,
)
from proteinbert_tpu.data.synthetic import make_task_batches
from proteinbert_tpu.data.vocab import ALPHABET
from proteinbert_tpu.heads import (
    CorruptHeadError, HeadRegistry, TrunkMismatchError, UnknownHeadError,
    trunk_fingerprint,
)
from proteinbert_tpu.heads import apply as heads_apply
from proteinbert_tpu.heads.registry import LoadedHead
from proteinbert_tpu.models import finetune as ft_model
from proteinbert_tpu.models import proteinbert
from proteinbert_tpu.serve import TASK_KIND, Server

MODEL = ModelConfig(local_dim=32, global_dim=64, key_dim=16, num_heads=4,
                    num_blocks=2, num_annotations=64, dtype="float32")
CFG = PretrainConfig(
    model=MODEL,
    data=DataConfig(seq_len=64, batch_size=4, buckets=(32, 64)),
    optimizer=OptimizerConfig(warmup_steps=5),
    train=TrainConfig(max_steps=1))

TASKS = [TaskConfig(kind="token_classification", num_outputs=4),
         TaskConfig(kind="sequence_classification", num_outputs=3),
         TaskConfig(kind="sequence_regression", num_outputs=1)]


@pytest.fixture(scope="module")
def params():
    return proteinbert.init(jax.random.PRNGKey(0), MODEL)


@pytest.fixture(scope="module")
def fp(params):
    return trunk_fingerprint(params)


@pytest.fixture(scope="module")
def registry(tmp_path_factory, params, fp):
    """A registry holding one head per task kind; yields
    (HeadRegistry, [head_id], [LoadedHead])."""
    reg = HeadRegistry(str(tmp_path_factory.mktemp("heads")))
    hids = []
    for i, task in enumerate(TASKS):
        hp = ft_model.head_init(jax.random.PRNGKey(i + 1), MODEL, task)
        hids.append(reg.save(jax.tree.map(np.asarray, hp), task, fp,
                             name=f"t{i}"))
    return reg, hids, [reg.load(h, trunk_fp=fp) for h in hids]


def _seqs(n, rng=None, lo=8, hi=28):
    rng = rng or np.random.default_rng(0)
    return ["".join(rng.choice(list(ALPHABET), size=int(L)))
            for L in rng.integers(lo, hi, size=n)]


# ---------------------------------------------------------------- registry

def test_registry_roundtrip_and_verify(registry, fp):
    reg, hids, heads = registry
    assert len(set(hids)) == 3
    metas = reg.list_heads()
    assert {m["head_id"] for m in metas} == set(hids)
    assert all(m["trunk_fingerprint"] == fp for m in metas)
    loaded = reg.load(hids[0])
    assert loaded.task.kind == "token_classification"
    assert loaded.meta["trunk_fingerprint"] == fp
    reg.verify(hids[0])  # digest matches
    # Round-trip preserves every leaf bit-exactly.
    original = ft_model.head_init(jax.random.PRNGKey(1), MODEL, TASKS[0])
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), b), original, loaded.params)
    assert hids[0] in reg and "nope" not in reg


def test_registry_idempotent_resave(registry, fp):
    reg, hids, _ = registry
    hp = ft_model.head_init(jax.random.PRNGKey(1), MODEL, TASKS[0])
    again = reg.save(jax.tree.map(np.asarray, hp), TASKS[0], fp, name="t0")
    assert again == hids[0]  # content-addressed: same content, same id
    reg.verify(again)


def test_registry_corruption_rejected(tmp_path, params, fp):
    reg = HeadRegistry(str(tmp_path))
    hp = ft_model.head_init(jax.random.PRNGKey(9), MODEL, TASKS[1])
    hid = reg.save(jax.tree.map(np.asarray, hp), TASKS[1], fp)
    npz = tmp_path / hid / "head.npz"
    blob = bytearray(npz.read_bytes())
    blob[len(blob) // 2] ^= 0xFF  # flip one byte mid-archive
    npz.write_bytes(bytes(blob))
    with pytest.raises(CorruptHeadError):
        reg.load(hid)
    # meta tampering is caught too
    meta_path = tmp_path / hid / "meta.json"
    meta = json.loads(meta_path.read_text())
    meta["head_digest"] = "0" * 64
    npz.write_bytes(blob)  # even with a "readable" npz
    meta_path.write_text(json.dumps(meta))
    with pytest.raises(CorruptHeadError):
        reg.verify(hid)


def test_registry_unknown_head(registry):
    reg, _, _ = registry
    with pytest.raises(UnknownHeadError):
        reg.load("deadbeef00000000")
    with pytest.raises(UnknownHeadError):
        reg.load("../escape")


def test_trunk_mismatch_is_typed(registry, params):
    reg, hids, _ = registry
    other = proteinbert.init(jax.random.PRNGKey(123), MODEL)
    with pytest.raises(TrunkMismatchError, match="trained against"):
        reg.load(hids[0], trunk_fp=trunk_fingerprint(other))
    # and without a fingerprint the load is allowed (caller's choice)
    assert reg.load(hids[0]).head_id == hids[0]


def test_fingerprint_strips_pretrain_heads(params):
    trunk_only = {k: v for k, v in params.items()
                  if k not in ("local_head", "global_head")}
    assert trunk_fingerprint(params) == trunk_fingerprint(trunk_only)
    # ... and actually depends on the weights
    other = proteinbert.init(jax.random.PRNGKey(5), MODEL)
    assert trunk_fingerprint(params) != trunk_fingerprint(other)


# ------------------------------------------------------------- split-apply

@pytest.mark.parametrize("task", TASKS, ids=lambda t: t.kind)
def test_split_apply_bit_parity_eager(params, task):
    """encode_trunk + apply_head IS the monolithic finetune.apply
    decomposition — eager-vs-eager they must agree bit for bit."""
    head = ft_model.head_init(jax.random.PRNGKey(7), MODEL, task)
    trunk = {k: v for k, v in params.items()
             if k not in ("local_head", "global_head")}
    tokens = jax.numpy.asarray(
        np.array([[2] + [5, 6, 7, 8] * 3 + [3] + [0] * 50,
                  [2, 9, 10, 3] + [0] * 60], np.int32))
    mono = np.asarray(ft_model.apply({"trunk": trunk, "head": head},
                                     tokens, MODEL, task))
    out = proteinbert.encode_trunk(trunk, tokens, MODEL)
    split = np.asarray(ft_model.apply_head(
        head, out["local"], out["global"], out["pad_mask"], task.kind))
    np.testing.assert_array_equal(mono, split)


@pytest.mark.parametrize("task", [TASKS[0], TASKS[2]],
                         ids=lambda t: t.kind)
def test_split_apply_jitted_tolerance(params, task):
    """The serving executables (jitted trunk_batch + head_batch) vs the
    eager monolithic forward: same math, different XLA fusion —
    documented fp32 tolerance (docs/serving.md)."""
    head = LoadedHead("hx", "hx", task,
                      ft_model.head_init(jax.random.PRNGKey(7), MODEL,
                                         task), {})
    trunk = {k: v for k, v in params.items()
             if k not in ("local_head", "global_head")}
    from proteinbert_tpu.data.transforms import tokenize_batch

    tokens = tokenize_batch(_seqs(4), 64)
    mono = np.asarray(ft_model.apply(
        {"trunk": trunk, "head": head.params},
        jax.numpy.asarray(tokens), MODEL, task))
    split = heads_apply.predict_task_rows(params, MODEL, head, tokens)
    np.testing.assert_allclose(split, mono, rtol=0, atol=1e-5)


# ------------------------------------------------------- shared-trunk serve

def test_mixed_batch_parity_vs_sequential(params, registry):
    """One micro-batch mixing all three heads through ONE shared trunk
    executable is bit-identical, row for row, to per-head sequential
    serving at the same compiled shape."""
    reg, hids, heads = registry
    seqs = _seqs(6)
    assign = [hids[i % 3] for i in range(6)]

    mixed = Server(params, CFG, max_batch=6, max_wait_s=60.0,
                   cache_size=0, warm_kinds=(), batch_classes=(6,),
                   registry=reg, heads=hids)
    futs = [mixed.submit(TASK_KIND, s, head_id=h)
            for s, h in zip(seqs, assign)]
    mixed.scheduler.poll()
    mixed_out = [f.result(timeout=30) for f in futs]
    assert mixed.scheduler.batches_total == 1  # ONE batch, 3 heads
    assert mixed.dispatcher.trunk_executable_count == 1
    mixed.abort()

    seq_srv = Server(params, CFG, max_batch=2, max_wait_s=60.0,
                     cache_size=0, warm_kinds=(), batch_classes=(6,),
                     registry=reg, heads=hids, partition_heads=True)
    futs = [seq_srv.submit(TASK_KIND, s, head_id=h)
            for s, h in zip(seqs, assign)]
    for _ in range(3):
        seq_srv.scheduler.poll()
    seq_out = [f.result(timeout=30) for f in futs]
    assert seq_srv.scheduler.batches_total == 3  # per-head batches
    seq_srv.abort()

    for m, s in zip(mixed_out, seq_out):
        np.testing.assert_array_equal(m, s)
    # Output shapes follow each row's task kind.
    assert mixed_out[0].shape == (32, 4)     # token head @ bucket 32
    assert mixed_out[1].shape == (3,)        # sequence classifier
    assert mixed_out[2].shape == (1,)        # regressor


def test_mixed_batch_matches_offline_split_apply(params, registry):
    """Served outputs vs offline predict_task_rows at the same padded
    shape: identical executables → bit-identical."""
    from proteinbert_tpu import inference

    reg, hids, heads = registry
    seqs = _seqs(6, np.random.default_rng(3))
    assign = [hids[i % 3] for i in range(6)]
    srv = Server(params, CFG, max_batch=6, max_wait_s=60.0,
                 cache_size=0, warm_kinds=(), batch_classes=(6,),
                 registry=reg, heads=hids)
    futs = [srv.submit(TASK_KIND, s, head_id=h)
            for s, h in zip(seqs, assign)]
    srv.scheduler.poll()
    tokens = inference._tokenize_masked(seqs, 64)[:, :32]
    by_id = {h.head_id: h for h in heads}
    for i, (f, hid) in enumerate(zip(futs, assign)):
        offline = heads_apply.predict_task_rows(
            params, MODEL, by_id[hid], tokens)[i]
        np.testing.assert_array_equal(f.result(timeout=30), offline)
    srv.abort()


def test_hot_add_never_recompiles_trunk(params, registry):
    """Warmup compiles the shared trunk once per shape and reports
    per-head incremental cost; adding a head to the LIVE server pays
    only the cheap tail — the trunk executable count stays flat."""
    reg, hids, heads = registry
    srv = Server(params, CFG, max_batch=4, max_wait_s=0.002,
                 cache_size=0, warm_kinds=(), batch_classes=(4,),
                 registry=reg, heads=hids[:2])
    srv.start()
    report = srv.dispatcher.warmup_report
    n_trunk = srv.dispatcher.trunk_executable_count
    assert n_trunk == report["trunk_executables"] == 2  # 2 buckets x 1 cls
    assert set(report["heads"]) == set(hids[:2])
    assert all(v >= 0.0 for v in report["heads"].values())

    # Hot add under a live scheduler; serve through it immediately.
    srv.add_head(hids[2])
    out = srv.predict_task(hids[2], "ACDEFGHIKL", timeout=30)
    assert out.shape == (1,)
    assert srv.dispatcher.trunk_executable_count == n_trunk  # FLAT
    assert hids[2] in srv.dispatcher.warmup_report["heads"]
    assert {h["head_id"] for h in srv.list_heads()} == set(hids)
    srv.drain(timeout=30)


def test_hot_remove_drains_under_concurrent_traffic(params, registry):
    """remove_head mid-traffic: already-admitted requests complete
    (they carry their own head reference), new submits get the typed
    UnknownHeadError, and nothing is lost."""
    reg, hids, heads = registry
    srv = Server(params, CFG, max_batch=4, max_wait_s=0.002,
                 cache_size=0, warm_kinds=(), batch_classes=(4,),
                 registry=reg, heads=hids)
    srv.start()
    seqs = _seqs(24, np.random.default_rng(7))
    results, errors = {}, []

    def client(w):
        for i in range(w, 24, 6):
            try:
                results[i] = srv.predict_task(hids[i % 3], seqs[i],
                                              timeout=60)
            except Exception as e:  # noqa: BLE001
                errors.append((i, e))

    threads = [threading.Thread(target=client, args=(w,))
               for w in range(6)]
    for t in threads:
        t.start()
    srv.remove_head(hids[0])  # mid-traffic
    for t in threads:
        t.join(120)
    # In-flight/queued head-0 requests admitted BEFORE the removal must
    # have completed; any head-0 submit AFTER it sees UnknownHeadError.
    assert all(isinstance(e, UnknownHeadError) for _, e in errors)
    assert len(results) + len(errors) == 24  # nothing lost
    assert all(i % 3 == 0 for i, _ in errors)
    with pytest.raises(UnknownHeadError):
        srv.predict_task(hids[0], "ACDEF", timeout=10)
    assert srv.stats()["rejected"]["unknown_head"] >= 1
    # The other tenants are untouched.
    assert srv.predict_task(hids[1], "ACDEFGH", timeout=30).shape == (3,)
    srv.drain(timeout=30)


def test_unknown_head_submit_and_validation(params, registry):
    reg, hids, _ = registry
    srv = Server(params, CFG, max_batch=2, max_wait_s=60.0,
                 cache_size=0, warm_kinds=(), registry=reg,
                 heads=hids[:1])
    with pytest.raises(UnknownHeadError):
        srv.submit(TASK_KIND, "ACDEF", head_id="not-registered")
    with pytest.raises(ValueError, match="head_id is required"):
        srv.submit(TASK_KIND, "ACDEF")
    with pytest.raises(ValueError, match="head_id is required"):
        srv.submit("embed", "ACDEF", head_id=hids[0])
    assert srv.stats()["rejected"]["unknown_head"] == 1
    srv.abort()


def test_server_registry_trunk_check(tmp_path, params):
    """Server head loading enforces trunk compatibility: a head trained
    against a different trunk raises TrunkMismatchError at add time."""
    reg = HeadRegistry(str(tmp_path))
    other = proteinbert.init(jax.random.PRNGKey(99), MODEL)
    hid = reg.save(
        jax.tree.map(np.asarray,
                     ft_model.head_init(jax.random.PRNGKey(1), MODEL,
                                        TASKS[1])),
        TASKS[1], trunk_fingerprint(other))
    with pytest.raises(TrunkMismatchError):
        Server(params, CFG, warm_kinds=(), registry=reg, heads=[hid])


def test_http_predict_task_and_head_lifecycle(params, registry):
    import urllib.error
    import urllib.request

    from proteinbert_tpu.serve.http import make_http_server

    reg, hids, heads = registry
    srv = Server(params, CFG, max_batch=2, max_wait_s=0.002,
                 cache_size=0, warm_kinds=(), batch_classes=(2,),
                 registry=reg, heads=hids[:2])
    srv.start()
    httpd = make_http_server(srv, port=0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    base = f"http://127.0.0.1:{port}"

    def post(path, payload):
        req = urllib.request.Request(
            base + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    try:
        status, body = post("/v1/predict_task",
                            {"head_id": hids[1], "seq": "ACDEFGHIKL"})
        assert status == 200 and body["head_id"] == hids[1]
        assert len(body["outputs"]) == 3
        # typed 404 for an unknown head — distinct from a route 404
        status, body = post("/v1/predict_task",
                            {"head_id": "nope", "seq": "ACDEF"})
        assert status == 404 and body["type"] == "unknown_head"
        # list / add / remove lifecycle
        with urllib.request.urlopen(base + "/v1/heads", timeout=30) as r:
            listed = json.loads(r.read())["heads"]
        assert {h["head_id"] for h in listed} == set(hids[:2])
        status, body = post("/v1/heads/add", {"head_id": hids[2]})
        assert status == 200 and len(body["heads"]) == 3
        status, body = post("/v1/predict_task",
                            {"head_id": hids[2], "seq": "ACDEFGHIKL"})
        assert status == 200 and len(body["outputs"]) == 1
        status, body = post("/v1/heads/remove", {"head_id": hids[2]})
        assert status == 200
        status, body = post("/v1/predict_task",
                            {"head_id": hids[2], "seq": "ACDEF"})
        assert status == 404 and body["type"] == "unknown_head"
        status, body = post("/v1/heads/remove", {"head_id": "nope"})
        assert status == 404
    finally:
        httpd.shutdown()
        httpd.server_close()
        srv.drain(timeout=30)


# ------------------------------------------------- finetune → register

def test_finetune_registers_head(tmp_path, params, fp):
    from proteinbert_tpu.obs import Telemetry, read_events
    from proteinbert_tpu.train.finetune import finetune

    reg = HeadRegistry(str(tmp_path / "reg"))
    events = str(tmp_path / "events.jsonl")
    cfg = FinetuneConfig(
        model=MODEL,
        task=TaskConfig(kind="sequence_classification", num_outputs=3,
                        epochs=1, freeze_trunk=True),
        data=DataConfig(seq_len=64, batch_size=8),
        optimizer=OptimizerConfig(learning_rate=3e-3, warmup_steps=5,
                                  schedule="warmup_cosine",
                                  total_steps=100),
        train=TrainConfig(seed=0))
    batches = make_task_batches(16, np.random.default_rng(0),
                                "sequence_classification", 3, 64, 8)
    tele = Telemetry(events_path=events)
    # finetune_step donates its state, which aliases pretrained_trunk —
    # hand it a host copy so the module-scoped params stay alive.
    out = finetune(cfg, lambda epoch: iter(batches),
                   eval_batches=lambda: iter(batches),
                   pretrained_trunk=jax.tree.map(np.asarray, params),
                   telemetry=tele, registry=reg, register_name="ft-test")
    tele.close()
    hid = out["head_id"]
    assert hid is not None
    meta = reg.verify(hid)
    assert meta["name"] == "ft-test"
    assert "eval_accuracy" in meta["metrics"]
    # freeze_trunk ⇒ the registered fingerprint IS the pretrain trunk's:
    # the head loads against the resident trunk with the check ON.
    loaded = reg.load(hid, trunk_fp=fp)
    assert loaded.task.num_outputs == 3
    recs = read_events(events, strict=True)
    reg_events = [r for r in recs if r["event"] == "head_registered"]
    assert len(reg_events) == 1
    assert reg_events[0]["head_id"] == hid
    assert reg_events[0]["trunk_fingerprint"] == fp


def test_finetune_unfrozen_trunk_mismatches(tmp_path, params, fp):
    """Without freeze_trunk the head is trained against a DRIFTED
    trunk; loading it against the pretrained trunk must raise the
    typed TrunkMismatchError instead of silently serving garbage."""
    from proteinbert_tpu.train.finetune import finetune

    reg = HeadRegistry(str(tmp_path / "reg"))
    cfg = FinetuneConfig(
        model=MODEL,
        task=TaskConfig(kind="sequence_regression", num_outputs=1,
                        epochs=1, freeze_trunk=False),
        data=DataConfig(seq_len=64, batch_size=8),
        optimizer=OptimizerConfig(learning_rate=3e-3, warmup_steps=2,
                                  schedule="warmup_cosine",
                                  total_steps=100),
        train=TrainConfig(seed=0))
    batches = make_task_batches(16, np.random.default_rng(1),
                                "sequence_regression", 1, 64, 8)
    out = finetune(cfg, lambda epoch: iter(batches),
                   pretrained_trunk=jax.tree.map(np.asarray, params),
                   registry=reg)
    with pytest.raises(TrunkMismatchError):
        reg.load(out["head_id"], trunk_fp=fp)
    # ... but loads fine unchecked (e.g. to serve its own trunk).
    assert reg.load(out["head_id"]).head_id == out["head_id"]


# ------------------------------------------------------- eval harness

def test_eval_metric_primitives():
    from proteinbert_tpu.heads.eval import auc_proxy, spearman

    assert spearman([1, 2, 3, 4], [10, 20, 30, 40]) == pytest.approx(1.0)
    assert spearman([1, 2, 3, 4], [4, 3, 2, 1]) == pytest.approx(-1.0)
    assert spearman([1, 1, 1], [1, 2, 3]) == 0.0  # degenerate → 0, not NaN
    scores = np.array([[0.9, 0.1], [0.8, 0.2], [0.1, 0.9], [0.2, 0.8]])
    labels = np.array([0, 0, 1, 1])
    assert auc_proxy(scores, labels) == pytest.approx(1.0)  # perfect
    assert auc_proxy(scores, 1 - labels) == pytest.approx(0.0)
    assert auc_proxy(scores[:2], np.array([0, 0])) is None  # one class


@pytest.mark.parametrize("trunk_arm", ["fp32", "int8"])
def test_evaluate_head_and_events(tmp_path, params, registry, trunk_arm):
    """`int8`: the same harness over dequantize(quantize(trunk)), which
    is exactly what the quantized serving executables compute from
    their int8 weights: the worst head's score stays within 0.1 of the
    fp32 trunk's worst (docs/serving.md, the quantized arm)."""
    from proteinbert_tpu.heads.eval import evaluate_heads
    from proteinbert_tpu.obs import Telemetry, read_events
    from proteinbert_tpu.parallel.quant import (
        dequantize_params, quantize_params,
    )

    reg, hids, heads = registry

    def evaluate(trunk, telemetry=None):
        return evaluate_heads(
            trunk, MODEL, heads,
            lambda head: make_task_batches(
                16, np.random.default_rng(2), head.task.kind,
                head.task.num_outputs, 64, 8),
            telemetry=telemetry)

    events = str(tmp_path / "ev.jsonl")
    tele = Telemetry(events_path=events)
    if trunk_arm == "int8":
        results = evaluate(dequantize_params(quantize_params(params)), tele)
        floor = min(m["score"] for m in evaluate(params).values()) - 0.1
        assert min(m["score"] for m in results.values()) >= floor
    else:
        results = evaluate(params, tele)
    tele.close()
    assert set(results) == set(hids)
    for hid, m in results.items():
        assert "score" in m and np.isfinite(m["score"])
    assert "per_residue_accuracy" in results[hids[0]]
    assert "auc_proxy" in results[hids[1]]
    assert "spearman" in results[hids[2]] and "mse" in results[hids[2]]
    recs = read_events(events, strict=True)
    evals = [r for r in recs if r["event"] == "head_eval"]
    assert {r["head_id"] for r in evals} == set(hids)
    assert all("score" in r["metrics"] for r in evals)


# --------------------------------------------------- diagnose per head

def test_diagnose_per_head_breakdown():
    from proteinbert_tpu.obs.diagnose import render_serve, summarize_serve
    from proteinbert_tpu.obs.events import make_record, validate_record

    recs = [make_record("serve_start", seq=0, t=0.0,
                        config={"max_batch": 4}, pid=1)]
    seq = 1
    for hid, lat, outcome in [("aaa", 0.010, "ok"), ("aaa", 0.014, "ok"),
                              ("bbb", 0.200, "ok"),
                              ("bbb", 0.250, "error"),
                              (None, 0.005, "ok")]:
        fields = {"kind": TASK_KIND if hid else "embed",
                  "outcome": outcome, "request_id": f"r{seq}",
                  "stages": {"queue": lat / 2, "execute": lat / 2},
                  "e2e_s": lat}
        if hid:
            fields["head_id"] = hid
        recs.append(make_record("serve_request", seq=seq, t=float(seq),
                                **fields))
        seq += 1
    recs.append(make_record("serve_reject", seq=seq, t=float(seq),
                            reason="unknown_head", head_id="ccc"))
    for r in recs:
        validate_record(r)
    summary = summarize_serve(recs)
    per = summary["per_head"]
    assert set(per) == {"aaa", "bbb"}  # the untagged embed is excluded
    assert per["aaa"]["n"] == 2 and per["aaa"]["errors"] == 0
    assert per["bbb"]["errors"] == 1
    assert per["bbb"]["p99_s"] >= per["bbb"]["p50_s"] >= 0.2
    assert summary["unknown_head_rejects"] == {"ccc": 1}
    text = render_serve(summary)
    assert "head aaa" in text and "head bbb" in text
    assert "unknown-head rejects: ccc x1" in text
