"""The int8 ANN index + /v1/neighbors subsystem (proteinbert_tpu/index/,
ISSUE 17).

Three tiers:

- **builder durability** (jax-free, synthetic stores written through
  the mapper's own commit_block protocol): build determinism,
  torn-tail resume to byte identity, typed manifest-drift refusals
  BEFORE any write, `verify_index` corruption detection;
- **scorer quality**: the quantized index's recall@k vs exact fp32
  brute force at full probe — the int8-residual representation must
  not change what the index answers;
- **served integration** (one tiny trunk): `/v1/neighbors` through a
  ragged Server returns exactly the offline scorer's answer over the
  same embedding, per-outcome accounting + cache scoping behave, and a
  trunk-fingerprint mismatch is a typed refusal at attach time.
"""

import json
import os

import numpy as np
import pytest

from proteinbert_tpu.index import (
    IndexBuildError, build_index, index_digests, index_identity,
    verify_index,
)
from proteinbert_tpu.index.scorer import (
    NeighborIndex, evaluate_recall, exact_topk,
    store_vectors_in_index_order,
)
from proteinbert_tpu.mapper import StoreConfigError, StoreError
from proteinbert_tpu.mapper.store import (
    EmbeddingStore, ShardCursor, block_digest, commit_block,
    corpus_digest, serialize_block, shard_ranges,
)

DIM = 16
NUM_SHARDS = 2
STORE_BLOCK = 8
ANCHORS = 4


def make_store(store_dir, n=40, seed=7, dim=DIM, fingerprint=None,
               num_shards=NUM_SHARDS):
    """A complete embedding store with clustered synthetic vectors,
    written through the real durability protocol — the builder's input
    contract without a trunk forward. Returns the fp32 vectors in
    index row order (shard-major, corpus order within a shard — which
    for contiguous shard_ranges is just corpus order)."""
    rng = np.random.default_rng(seed)
    ids = [f"syn{i:05d}" for i in range(n)]
    seqs = ["A" * (10 + i % 7) for i in range(n)]
    anchors = rng.standard_normal((ANCHORS, dim)).astype(np.float32)
    vecs = (anchors[rng.integers(0, ANCHORS, size=n)]
            + 0.15 * rng.standard_normal((n, dim))).astype(np.float32)
    store = EmbeddingStore(store_dir)
    fingerprint = fingerprint or "deadbeef" * 8
    store.ensure_manifest({
        "kind": "embedding_store", "corpus_n": n,
        "corpus_digest": corpus_digest(ids, seqs),
        "model_fingerprint": fingerprint,
        "num_shards": num_shards, "block_size": STORE_BLOCK,
        "rows_per_batch": 2, "max_segments": 4, "seq_len": 48,
        "buckets": [16, 32, 48],
    })
    for shard, (lo, hi) in enumerate(shard_ranges(n, num_shards)):
        cursor = ShardCursor(store_dir, shard)
        state = cursor.write_state(cursor.fresh_state())
        for start in range(0, hi - lo, STORE_BLOCK):
            end = min(start + STORE_BLOCK, hi - lo)
            rows = slice(lo + start, lo + end)
            arrays = {
                "ids": np.array(ids[rows], dtype="S"),
                "lengths": np.array([len(s) for s in seqs[rows]],
                                    np.int32),
                "global": vecs[rows],
                "local_mean": np.zeros((end - start, dim), np.float32),
            }
            payload = serialize_block(
                {"shard": shard, "block": start // STORE_BLOCK,
                 "start": start, "end": end,
                 "model_fingerprint": fingerprint}, arrays)
            entry = {"block": start // STORE_BLOCK,
                     "digest": block_digest(payload), "start": start,
                     "end": end, "n": end - start, "quarantined": []}
            state = commit_block(store, cursor, state, payload, entry)
        cursor.write_state(dict(state, done=True))
    return vecs


BUILD_KW = dict(num_centroids=4, block_size=8, kmeans_iters=4)


class TestBuilderDurability:

    def test_build_deterministic_byte_identical(self, tmp_path):
        make_store(str(tmp_path / "store"))
        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        sa = build_index(str(tmp_path / "store"), a, **BUILD_KW)
        sb = build_index(str(tmp_path / "store"), b, **BUILD_KW)
        assert sa["outcome"] == sb["outcome"] == "completed"
        assert index_digests(a) == index_digests(b)
        assert index_identity(a) == index_identity(b)
        for dg in index_digests(a).values():
            with open(EmbeddingStore(a).object_path(dg), "rb") as fa, \
                    open(EmbeddingStore(b).object_path(dg), "rb") as fb:
                assert fa.read() == fb.read()

    def test_torn_tail_resume_byte_identical(self, tmp_path):
        store = str(tmp_path / "store")
        make_store(store)
        control = str(tmp_path / "control")
        build_index(store, control, **BUILD_KW)
        chaos = str(tmp_path / "chaos")
        # Preempt mid-build, then tear the tail block object the way a
        # crash mid-write would — resume must drop + re-work that one
        # block and still converge on the control's bytes.
        pre = build_index(store, chaos, max_blocks=3, **BUILD_KW)
        assert pre["outcome"] == "preempted"
        state, _ = ShardCursor(chaos, 0).load()
        tail = state["blocks"][-1]["digest"]
        path = EmbeddingStore(chaos).object_path(tail)
        with open(path, "rb") as f:
            data = f.read()
        with open(path, "wb") as f:
            f.write(data[: len(data) // 2])
        stats = build_index(store, chaos, **BUILD_KW)
        assert stats["outcome"] == "completed"
        assert stats["reworked_blocks"] <= NUM_SHARDS
        assert index_digests(chaos) == index_digests(control)
        assert index_identity(chaos) == index_identity(control)

    def test_stale_store_pin_refused_before_any_write(self, tmp_path):
        store = str(tmp_path / "store")
        make_store(store)
        index = str(tmp_path / "index")
        build_index(store, index, **BUILD_KW)
        before = index_digests(index)
        # Different corpus AND different trunk: both pins must refuse.
        other = str(tmp_path / "other")
        make_store(other, seed=8, fingerprint="feedface" * 8)
        with pytest.raises(StoreConfigError) as ei:
            build_index(other, index, **BUILD_KW)
        msg = str(ei.value)
        assert "corpus_digest" in msg or "model_fingerprint" in msg
        assert index_digests(index) == before  # refusal preceded writes

    def test_unfinished_store_refused(self, tmp_path):
        store = str(tmp_path / "store")
        make_store(store)
        state, _ = ShardCursor(store, 1).load()
        ShardCursor(store, 1).write_state(dict(state, done=False))
        with pytest.raises(IndexBuildError, match="not done"):
            build_index(store, str(tmp_path / "index"), **BUILD_KW)

    def test_verify_catches_flip_and_hole_typed(self, tmp_path):
        store = str(tmp_path / "store")
        make_store(store)
        index = str(tmp_path / "index")
        build_index(store, index, **BUILD_KW)
        rep = verify_index(index)
        assert rep["ok"] and rep["complete"]
        victim = sorted(v for k, v in index_digests(index).items()
                        if k != "centroids")[0]
        path = EmbeddingStore(index).object_path(victim)
        with open(path, "rb") as f:
            good = f.read()
        with open(path, "wb") as f:
            f.write(good[:-1] + bytes([good[-1] ^ 0xFF]))
        rep = verify_index(index)
        assert not rep["ok"]
        assert any(c.get("reason") == "digest_mismatch"
                   for c in rep["corrupt"])
        os.remove(path)
        rep = verify_index(index)
        assert not rep["ok"]
        assert any(h["digest"] == victim for h in rep["holes"])
        with open(path, "wb") as f:
            f.write(good)
        assert verify_index(index)["ok"]

    def test_load_refuses_foreign_directory(self, tmp_path):
        with pytest.raises(StoreError):
            NeighborIndex.load(str(tmp_path / "nothing_here"))
        # An embedding STORE is not an INDEX — typed, not garbage.
        store = str(tmp_path / "store")
        make_store(store)
        with pytest.raises(StoreConfigError):
            NeighborIndex.load(store)


class TestScorerQuality:

    @pytest.fixture(scope="class")
    def built(self, tmp_path_factory):
        tmp = tmp_path_factory.mktemp("scorer")
        store = str(tmp / "store")
        make_store(store, n=96)
        index_dir = str(tmp / "index")
        stats = build_index(store, index_dir, **BUILD_KW)
        return (NeighborIndex.load(index_dir),
                store_vectors_in_index_order(store), stats)

    @pytest.mark.parametrize("probe_share", [1.0, 0.5])
    def test_quantized_recall_bound(self, built, probe_share):
        """The int8-residual representation must preserve the answers:
        at nprobe == num_centroids the shortlist is the whole corpus,
        so any recall loss is PURELY quantization error; at half the
        lists (the serving default's share, 8 of 16) the shortlist
        loses some too. recall@10 >= 0.95 against exact brute force
        either way."""
        index, vectors, _stats = built
        queries = vectors[::5]
        nprobe = int(index.centroids.shape[0] * probe_share)
        recall = evaluate_recall(index, vectors, queries, k=10,
                                 nprobe=nprobe)
        assert recall >= 0.95

    def test_lookup_rows_matches_lookup_one(self, built):
        index, vectors, _stats = built
        q = vectors[3]
        scores, rows = index.lookup_rows(q[None, :], k=5,
                                         nprobe=index.centroids.shape[0])
        pairs = index.lookup_one(q, k=5,
                                 nprobe=index.centroids.shape[0])
        assert [p[0] for p in pairs] == [
            index.ids[r].decode() for r in rows[0]]
        np.testing.assert_allclose([p[1] for p in pairs], scores[0],
                                   rtol=1e-6)

    def test_self_is_top1_and_exact_topk_sane(self, built):
        index, vectors, _stats = built
        got = exact_topk(vectors, vectors[:8], k=1)[:, 0]
        np.testing.assert_array_equal(got, np.arange(8))
        for row in (0, 17, 41):
            pairs = index.lookup_one(vectors[row], k=1,
                                     nprobe=index.centroids.shape[0])
            assert pairs[0][0] == index.ids[row].decode()

    @pytest.mark.parametrize("dim,block,bound", [(DIM, 8, 0.45),
                                                 (64, 64, 0.30)])
    def test_bytes_ratio_accounting(self, tmp_path, dim, block, bound):
        """int8 codes + an int32 list assignment + one fp32 scale row a
        block against 4 bytes a channel: 1/4 + 1/dim + 1/block. At a
        trunk's width (global_dim >= 64, blocks of 64) the index holds
        <= 0.30x the fp32 vector bytes (docs/neighbors.md, sizing); the
        16-wide fixture pays its assignment and scales dearly."""
        store = str(tmp_path / "store")
        make_store(store, n=128, dim=dim)
        stats = build_index(store, str(tmp_path / "index"),
                            **dict(BUILD_KW, block_size=block))
        assert stats["outcome"] == "completed"
        assert stats["bytes_ratio"] == pytest.approx(
            stats["index_vector_bytes"] / stats["fp32_vector_bytes"],
            abs=1e-4)
        assert stats["bytes_ratio"] <= bound

    def test_clamp_validation(self, built):
        index, _vectors, _stats = built
        q = np.zeros(index.dim, np.float32)
        with pytest.raises(ValueError, match="k"):
            index.lookup_one(q, k=0)
        with pytest.raises(ValueError, match="nprobe"):
            index.lookup_one(q, k=1, nprobe=0)


# ------------------------------------------------------- served tier

import jax  # noqa: E402

from proteinbert_tpu.configs import (  # noqa: E402
    DataConfig, ModelConfig, OptimizerConfig, PretrainConfig,
    TrainConfig,
)
from proteinbert_tpu.heads import TrunkMismatchError, trunk_fingerprint  # noqa: E402
from proteinbert_tpu.serve import Server  # noqa: E402
from proteinbert_tpu.serve.server import DEFAULT_NEIGHBORS_K  # noqa: E402
from proteinbert_tpu.train import create_train_state  # noqa: E402

SEQ_LEN = 48


@pytest.fixture(scope="module")
def trunk():
    cfg = PretrainConfig(
        model=ModelConfig(local_dim=16, global_dim=32, key_dim=8,
                          num_heads=2, num_blocks=2, num_annotations=32,
                          dtype="float32"),
        data=DataConfig(seq_len=SEQ_LEN, batch_size=4,
                        buckets=(16, 32, 48)),
        optimizer=OptimizerConfig(warmup_steps=5),
        train=TrainConfig(seed=0, max_steps=1))
    state = create_train_state(jax.random.PRNGKey(0), cfg)
    return state.params, cfg


@pytest.fixture(scope="module")
def trunk_index(trunk, tmp_path_factory):
    """An index pinned to the REAL trunk fingerprint (vectors are
    synthetic at the trunk's global_dim — attach-time compatibility is
    a fingerprint contract, not a geometry one)."""
    params, cfg = trunk
    tmp = tmp_path_factory.mktemp("served")
    store = str(tmp / "store")
    make_store(store, n=48, dim=cfg.model.global_dim,
               fingerprint=trunk_fingerprint(params))
    index_dir = str(tmp / "index")
    build_index(store, index_dir, **BUILD_KW)
    return NeighborIndex.load(index_dir)


def _drain(srv, futs):
    srv.queue.close()
    while srv.scheduler.poll():
        pass
    return [f.result(timeout=5) for f in futs]


class TestServedNeighbors:

    def test_served_equals_offline_over_same_embedding(
            self, trunk, trunk_index):
        params, cfg = trunk
        srv = Server(params, cfg, max_batch=4, max_wait_s=60.0,
                     cache_size=0, warm_kinds=(), serve_mode="ragged",
                     index=trunk_index, nprobe=4)
        seqs = ["MKTAYIAKQR", "GDSLAVVL", "MNNQRKKT"]
        nf = [srv.submit("neighbors", s, top_k=5) for s in seqs]
        ef = [srv.submit("embed", s) for s in seqs]
        out = _drain(srv, nf + ef)
        served, embeds = out[:3], out[3:]
        for got, emb in zip(served, embeds):
            offline = trunk_index.lookup_one(emb["global"], k=5,
                                             nprobe=4)
            assert got["neighbors"] == offline
        by = srv.stats()["neighbors"]["by_outcome"]
        assert by["ok"] == 3
        srv.drain(timeout=10)

    def test_default_k_and_outcome_accounting(self, trunk, trunk_index):
        params, cfg = trunk
        srv = Server(params, cfg, max_batch=2, max_wait_s=60.0,
                     cache_size=8, warm_kinds=(), serve_mode="ragged",
                     index=trunk_index, nprobe=2)
        f1 = srv.submit("neighbors", "MKTAYIAKQR")
        _drain(srv, [f1])
        assert len(f1.result()["neighbors"]) == DEFAULT_NEIGHBORS_K
        f2 = srv.submit("neighbors", "MKTAYIAKQR")  # cache hit
        assert f2.done()
        assert f2.result() == f1.result()
        stats = srv.stats()["neighbors"]
        assert stats["by_outcome"]["ok"] == 1
        assert stats["by_outcome"]["cache_hit"] == 1
        assert stats["index_digest"] == trunk_index.digest
        assert stats["num_vectors"] == trunk_index.num_vectors
        srv.drain(timeout=10)

    def test_no_index_is_typed_submit_error(self, trunk):
        params, cfg = trunk
        srv = Server(params, cfg, max_batch=2, max_wait_s=60.0,
                     cache_size=0, warm_kinds=(), serve_mode="ragged")
        with pytest.raises(ValueError, match="no neighbor index"):
            srv.submit("neighbors", "MKTAYIAKQR")
        assert srv.stats()["neighbors"] is None
        srv.drain(timeout=10)

    def test_trunk_mismatch_refused_at_attach(self, trunk, tmp_path):
        params, cfg = trunk
        store = str(tmp_path / "store")
        make_store(store, n=32, dim=cfg.model.global_dim,
                   fingerprint="feedface" * 8)  # some OTHER trunk
        index_dir = str(tmp_path / "index")
        build_index(store, index_dir, **BUILD_KW)
        with pytest.raises(TrunkMismatchError, match="rebuild"):
            Server(params, cfg, max_batch=2, warm_kinds=(),
                   serve_mode="ragged",
                   index=NeighborIndex.load(index_dir))


class TestFleetCacheScoping:

    def test_neighbors_cache_key_requires_index_digest(self):
        from proteinbert_tpu.serve.fleet import FleetRouter

        body = {"seq": "MKTAYIAK", "k": 5}
        url = ["http://localhost:1"]  # never contacted: key tests only
        blind = FleetRouter(url, cache_size=16)
        assert blind._cache_key("neighbors", body) is None
        digest = "ab" * 32
        scoped = FleetRouter(url, cache_size=16, index_digest=digest)
        key = scoped._cache_key("neighbors", body)
        assert key is not None
        # Same body, different fleet index → different key (two fleets
        # serving different corpora must never share answers).
        other = FleetRouter(url, cache_size=16, index_digest="cd" * 32)
        assert other._cache_key("neighbors", body) != key
        # k changes the answer → changes the key.
        assert scoped._cache_key("neighbors",
                                 {"seq": "MKTAYIAK", "k": 3}) != key
        # Non-neighbors kinds are unaffected by the digest.
        assert blind._cache_key("embed", {"seq": "MKTAYIAK"}) == \
            scoped._cache_key("embed", {"seq": "MKTAYIAK"})


class TestEventsAndCli:

    def test_build_events_schema_valid(self, tmp_path):
        from proteinbert_tpu.obs import Telemetry, read_events

        store = str(tmp_path / "store")
        make_store(store)
        path = tmp_path / "events.jsonl"
        tele = Telemetry(events_path=str(path))
        build_index(store, str(tmp_path / "index"), telemetry=tele,
                    **BUILD_KW)
        tele.close()
        recs = read_events(str(path), strict=True)
        builds = [r for r in recs if r["event"] == "index_build"]
        assert [b["state"] for b in builds] == ["start", "completed"]
        shard_done = [r for r in recs if r["event"] == "index_shard"
                      and r["state"] == "done"]
        assert len(shard_done) == NUM_SHARDS

    def test_cli_verify_report_shape(self, tmp_path, capsys):
        from proteinbert_tpu.cli.main import main as cli_main

        store = str(tmp_path / "store")
        make_store(store)
        index = str(tmp_path / "index")
        build_index(store, index, **BUILD_KW)
        assert cli_main(["index", "--index", index, "--verify"]) in (0,
                                                                     None)
        out = capsys.readouterr().out
        rep = json.loads(next(ln for ln in out.splitlines()
                              if ln.startswith("{")))
        assert rep["ok"] and rep["complete"]
        assert rep["vectors"] == 40
