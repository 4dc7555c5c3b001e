"""Quantized collectives + int8 serving (parallel/quant.py, ISSUE 12):
stochastic-rounding determinism and lockstep, quantize→dequantize
error bounds per dtype, the int8/bf16 reduce-scatter parity grid
(plain/ZeRO-1 x fp32/bf16/int8) on the virtual 8-device mesh,
checkpoint interchangeability across reduce dtypes, the typed config
rejections, and the quantized serving arm (weights, parity sampling,
event fields)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from proteinbert_tpu.configs import (
    CheckpointConfig, DataConfig, MeshConfig, ModelConfig,
    OptimizerConfig, ParallelConfig, PretrainConfig, TrainConfig,
)
from proteinbert_tpu.data import (
    InMemoryPretrainingDataset, make_pretrain_iterator,
)
from proteinbert_tpu.parallel import (
    batch_sharding, make_mesh, make_zero_train_step, shard_train_state,
)
from proteinbert_tpu.parallel import quant as q
from proteinbert_tpu.train import (
    Checkpointer, create_train_state, train_step,
)
from tests.conftest import make_random_proteins

requires_8 = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 (virtual) devices"
)

# Documented parity bounds (docs/distributed.md, quantized reduction):
# max param deviation from the exact fp32 reference after two steps at
# lr 1e-3 on the tiny grid model. The fp32-PAYLOAD explicit control
# bounds the harness itself.
INT8_BOUND = 1e-3
BF16_BOUND = 5e-4
CONTROL_BOUND = 1e-6


def cfg_for(mesh_cfg, parallel=None, **kw):
    model = dict(
        local_dim=16, global_dim=32, key_dim=8, num_heads=4, num_blocks=2,
        num_annotations=64, dtype="float32",
    )
    return PretrainConfig(
        model=ModelConfig(**model),
        data=DataConfig(seq_len=32, batch_size=16, **kw.pop("data_kw", {})),
        optimizer=OptimizerConfig(learning_rate=1e-3, warmup_steps=10),
        mesh=mesh_cfg,
        parallel=parallel or ParallelConfig(zero_update=True,
                                            grad_reduce_dtype="int8"),
        train=TrainConfig(max_steps=4, **kw.pop("train_kw", {})),
    )


MESH_CFG = MeshConfig(data=4, fsdp=2)
REF_CFG = cfg_for(MeshConfig(), parallel=ParallelConfig())


def make_batch(cfg, seed=0):
    rng = np.random.default_rng(seed)
    seqs, ann = make_random_proteins(
        cfg.data.batch_size, rng, num_annotations=cfg.model.num_annotations,
        max_len=40,
    )
    ds = InMemoryPretrainingDataset(seqs, ann, cfg.data.seq_len)
    return next(make_pretrain_iterator(ds, cfg.data.batch_size, seed=seed))


def _two_steps_quant(cfg, batch, payload=None):
    mesh = make_mesh(cfg.mesh)
    state = shard_train_state(
        create_train_state(jax.random.PRNGKey(0), cfg), mesh,
        zero_update=True)
    if payload is not None:
        step = q.make_quant_zero_train_step(mesh, cfg, payload=payload)
    else:
        step = make_zero_train_step(mesh, cfg)
    bsh = batch_sharding(mesh)
    dbatch = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
    state, m1 = step(state, dbatch)
    state, m2 = step(state, dbatch)
    return state, m1, m2


def _max_param_err(ref_state, state):
    err = 0.0
    for r, g in zip(jax.tree.leaves(ref_state.params),
                    jax.tree.leaves(state.params)):
        err = max(err, float(np.max(np.abs(
            np.asarray(r, np.float64)
            - np.asarray(jax.device_get(g), np.float64)))))
    return err


# ------------------------------------------------------------ primitives


class TestPrimitives:
    def test_bf16_stochastic_rounding_deterministic_and_bounded(self):
        x = jnp.asarray(
            np.random.default_rng(0).normal(size=(4096,)), jnp.float32)
        key = jax.random.PRNGKey(7)
        a = q.stochastic_round_bf16(x, key)
        b = q.stochastic_round_bf16(x, key)
        assert a.dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
        # Different key → different noise → different rounding pattern.
        c = q.stochastic_round_bf16(x, jax.random.PRNGKey(8))
        assert not np.array_equal(np.asarray(a, np.float32),
                                  np.asarray(c, np.float32))
        # Per-element error bounded by one bf16 ulp (2^-8 relative).
        err = np.abs(np.asarray(a, np.float32) - np.asarray(x))
        assert float(np.max(err / np.abs(np.asarray(x)))) <= 2 ** -7
        # Unbiased-ish: the mean residual is far below one ulp.
        assert abs(float(np.mean(np.asarray(a, np.float32)
                                 - np.asarray(x)))) < 1e-4

    def test_int8_chunks_roundtrip_error_bounded(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(3, 1300)) * 10.0, jnp.float32)
        key = jax.random.PRNGKey(3)
        qq, scale, m = q.quantize_int8_chunks(x, key)
        assert qq.dtype == jnp.int8 and m == 1300
        back = q.dequantize_int8_chunks(qq, scale, m)
        assert back.shape == x.shape
        # Stochastic floor(y + u) lands within one quantum of y.
        err = np.abs(np.asarray(back) - np.asarray(x))
        bound = np.repeat(np.asarray(scale), qq.shape[-1],
                          axis=-1)[..., :m]
        assert np.all(err <= bound + 1e-6)
        # Deterministic under a fixed key; rounds-to-nearest without.
        q2, s2, _ = q.quantize_int8_chunks(x, key)
        assert np.array_equal(np.asarray(qq), np.asarray(q2))
        qd, sd, _ = q.quantize_int8_chunks(x, None)
        errd = np.abs(q.dequantize_int8_chunks(qd, sd, m) - x)
        assert np.all(np.asarray(errd)
                      <= np.asarray(bound) / 2 + 1e-6)

    def test_int8_chunks_zero_and_tiny_slices(self):
        # All-zero chunks must not divide by zero, and a slice smaller
        # than the chunk must not be padded up to it (the wire-bytes
        # property the comm gate measures).
        z = jnp.zeros((2, 16), jnp.float32)
        qq, scale, m = q.quantize_int8_chunks(z, None)
        assert qq.shape[-1] <= 16 and m == 16
        assert np.all(np.asarray(qq) == 0)
        assert np.all(np.asarray(scale) == 1.0)

    def test_lockstep_noise_is_replica_indexed(self):
        # The multi-host lockstep property reduced to its mechanism:
        # the rounding noise is a pure function of (key, replica index)
        # — same inputs, same noise on every host; different replicas,
        # different noise (partials must not round identically).
        key = jax.random.PRNGKey(0)
        k0 = jax.random.fold_in(key, 0)
        k1 = jax.random.fold_in(key, 1)
        x = jnp.asarray(np.random.default_rng(2).normal(size=(512,)),
                        jnp.float32)
        a0, _, _ = q.quantize_int8_chunks(x[None], k0)
        a0b, _, _ = q.quantize_int8_chunks(x[None], k0)
        a1, _, _ = q.quantize_int8_chunks(x[None], k1)
        assert np.array_equal(np.asarray(a0), np.asarray(a0b))
        assert not np.array_equal(np.asarray(a0), np.asarray(a1))


# ------------------------------------------------------- the parity grid


@requires_8
@pytest.mark.parametrize(
    "payload,bound",
    [("fp32", CONTROL_BOUND), ("bf16", BF16_BOUND), ("int8", INT8_BOUND)],
    ids=["fp32-control", "bf16", "int8"],
)
def test_quant_reduce_scatter_parity_grid(payload, bound):
    """The ZeRO-1 quantized reduce-scatter vs the PLAIN replicated fp32
    step (the full plain/ZeRO x payload grid): step-1 loss identical
    (same corruption ops on the same key — deviation is quantization
    alone), two-step param deviation within the documented bound per
    payload, and the fp32-payload explicit control within 1e-6 (the
    harness itself adds nothing)."""
    cfg = cfg_for(MESH_CFG, parallel=ParallelConfig(
        zero_update=True,
        grad_reduce_dtype=payload if payload != "fp32" else "int8"))
    batch = make_batch(cfg)

    ref_state = create_train_state(jax.random.PRNGKey(0), REF_CFG)
    ref_state, rm1 = train_step(ref_state, dict(batch), REF_CFG)
    ref_state, _ = train_step(ref_state, dict(batch), REF_CFG)

    state, m1, m2 = _two_steps_quant(
        cfg, batch, payload="fp32" if payload == "fp32" else None)
    assert abs(float(m1["loss"]) - float(rm1["loss"])) \
        <= 2e-5 * max(1.0, abs(float(rm1["loss"])))
    err = _max_param_err(ref_state, state)
    assert err <= bound, (payload, err)
    if payload != "fp32":
        assert err > 0.0, "quantization did not round anything"


@requires_8
def test_quant_step_deterministic():
    """Bit-determinism across runs from the same state — the noise is
    seeded from the (replicated, checkpointed) step key, so re-runs and
    every host of a multi-host mesh draw identical noise."""
    cfg = cfg_for(MESH_CFG)
    batch = make_batch(cfg)
    a, _, _ = _two_steps_quant(cfg, batch)
    b, _, _ = _two_steps_quant(cfg, batch)
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(x)), np.asarray(jax.device_get(y)))


@requires_8
def test_quant_packed_batch_parity():
    """A PACKED batch through the int8 quantized step vs the replicated
    fp32 packed step: the per-segment loss decomposition inside the
    quantized shard_map must reproduce packed_pretrain_loss."""
    parallel = ParallelConfig(zero_update=True, grad_reduce_dtype="int8")
    cfg = cfg_for(MESH_CFG, parallel=parallel,
                  data_kw=dict(packing=True, pack_max_segments=4))
    ref_cfg = cfg_for(MeshConfig(), parallel=ParallelConfig(),
                      data_kw=dict(packing=True, pack_max_segments=4))
    rng = np.random.default_rng(3)
    seqs, ann = make_random_proteins(48, rng, num_annotations=64,
                                     max_len=14)
    from proteinbert_tpu.data.packing import make_packed_iterator

    ds = InMemoryPretrainingDataset(seqs, ann, cfg.data.seq_len)
    batch = next(make_packed_iterator(
        ds, cfg.data.batch_size, seed=0, max_segments=4))

    ref_state = create_train_state(jax.random.PRNGKey(0), ref_cfg)
    ref_state, rm1 = train_step(ref_state, dict(batch), ref_cfg)
    ref_state, _ = train_step(ref_state, dict(batch), ref_cfg)

    state, m1, _ = _two_steps_quant(cfg, batch)
    assert abs(float(m1["loss"]) - float(rm1["loss"])) \
        <= 2e-5 * max(1.0, abs(float(rm1["loss"])))
    err = _max_param_err(ref_state, state)
    assert 0.0 < err <= INT8_BOUND, err


@requires_8
def test_sustained_loss_trajectory_tracks_fp32():
    """The short sustained-pretrain check of the documented
    methodology (docs/distributed.md): 12 steps over a real batch
    stream, int8 and bf16 reductions must track the fp32 ZeRO loss
    curve within 1% relative at every step — quantization noise may
    perturb, it must not bend the trajectory."""
    mesh = make_mesh(MESH_CFG)
    bsh = batch_sharding(mesh)
    rng = np.random.default_rng(11)
    seqs, ann = make_random_proteins(64, rng, num_annotations=64,
                                     max_len=40)
    base = cfg_for(MESH_CFG, parallel=ParallelConfig(zero_update=True))
    ds = InMemoryPretrainingDataset(seqs, ann, base.data.seq_len)

    def run(grd):
        cfg = cfg_for(MESH_CFG, parallel=ParallelConfig(
            zero_update=True, grad_reduce_dtype=grd))
        it = make_pretrain_iterator(ds, cfg.data.batch_size, seed=0)
        state = shard_train_state(
            create_train_state(jax.random.PRNGKey(0), cfg), mesh,
            zero_update=True)
        step = make_zero_train_step(mesh, cfg)
        losses = []
        for _ in range(12):
            batch = {k: jax.device_put(v, bsh[k])
                     for k, v in next(it).items()}
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        return losses

    ref = run("fp32")
    assert all(np.isfinite(ref))
    for grd in ("bf16", "int8"):
        got = run(grd)
        for i, (a, b) in enumerate(zip(ref, got)):
            assert abs(a - b) <= 0.01 * max(1.0, abs(a)), (grd, i, a, b)


@requires_8
def test_checkpoints_interchangeable_across_reduce_dtypes(tmp_path):
    """Leaf shapes and shardings are payload-independent, so a
    checkpoint written under int8 reduction restores into an fp32 run
    (and vice versa) byte-for-byte — the reduce dtype is a per-run
    execution knob, not a format."""
    int8_cfg = cfg_for(MESH_CFG)
    batch = make_batch(int8_cfg)
    mesh = make_mesh(MESH_CFG)
    state = shard_train_state(
        create_train_state(jax.random.PRNGKey(0), int8_cfg), mesh,
        zero_update=True)
    step8 = make_zero_train_step(mesh, int8_cfg)
    bsh = batch_sharding(mesh)
    dbatch = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
    state, _ = step8(state, dbatch)

    ck = Checkpointer(str(tmp_path / "ck"), async_save=False)
    ck.save(1, jax.device_get(state))
    template = shard_train_state(
        create_train_state(jax.random.PRNGKey(0), int8_cfg), mesh,
        zero_update=True)
    restored, _ = ck.restore(template)
    ck.close()
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b))),
        restored, state)
    # ...and the restored state steps under the FP32 zero rule.
    fp32_cfg = cfg_for(MESH_CFG, parallel=ParallelConfig(
        zero_update=True, grad_reduce_dtype="fp32"))
    step32 = make_zero_train_step(mesh, fp32_cfg)
    nxt, m = step32(restored, dbatch)
    assert int(jax.device_get(nxt.step)) == 2
    assert np.isfinite(float(m["loss"]))


# ------------------------------------------------------- typed rejections


class TestConfigRejections:
    def test_unknown_payload_rejected(self):
        mesh = make_mesh(MESH_CFG) if jax.device_count() >= 8 else None
        if mesh is None:
            pytest.skip("needs 8 virtual devices")
        with pytest.raises(q.QuantConfigError, match="payload"):
            q.check_quant_mesh(mesh, "fp8")

    @requires_8
    def test_model_axis_rejected(self):
        mesh_cfg = MeshConfig(data=2, fsdp=2, model=2)
        mesh = make_mesh(mesh_cfg)
        with pytest.raises(q.QuantConfigError, match="model"):
            q.check_quant_mesh(mesh, "int8")

    @requires_8
    def test_indivisible_batch_rejected(self):
        mesh = make_mesh(MESH_CFG)
        with pytest.raises(q.QuantConfigError, match="batch"):
            q.check_quant_mesh(mesh, "int8", batch_size=12)

    @requires_8
    def test_seq_parallel_pallas_step_rejects_int8(self):
        """The ISSUE 12 satellite: grad_reduce_dtype='int8' + the
        explicit seq-parallel Pallas step is a typed QuantConfigError
        (mirroring that step's packing rejection); bf16 keeps its
        documented cast-only legacy path there."""
        from proteinbert_tpu.parallel.seq_parallel import (
            make_seq_parallel_train_step,
        )

        mesh_cfg = MeshConfig(data=2, fsdp=2, seq=2)
        cfg = cfg_for(mesh_cfg, parallel=ParallelConfig(
            zero_update=True, grad_reduce_dtype="int8"))
        cfg = cfg.replace(model=dataclasses.replace(
            cfg.model, use_pallas=True))
        mesh = make_mesh(mesh_cfg)
        with pytest.raises(q.QuantConfigError,
                           match="sequence-parallel"):
            make_seq_parallel_train_step(mesh, cfg)

    @requires_8
    def test_seq_axis_rejected_for_quant_zero(self):
        mesh_cfg = MeshConfig(data=2, fsdp=2, seq=2)
        cfg = cfg_for(mesh_cfg)
        mesh = make_mesh(mesh_cfg)
        with pytest.raises(q.QuantConfigError, match="seq"):
            q.make_quant_zero_train_step(mesh, cfg)


# --------------------------------------------------------- serving arm


@pytest.fixture(scope="module")
def serve_setup():
    cfg = PretrainConfig(
        model=ModelConfig(local_dim=16, global_dim=32, key_dim=8,
                          num_heads=4, num_blocks=2, num_annotations=64,
                          dtype="float32"),
        data=DataConfig(seq_len=64, batch_size=4))
    from proteinbert_tpu.models import proteinbert

    params = proteinbert.init(jax.random.PRNGKey(0), cfg.model)
    rng = np.random.default_rng(5)
    from proteinbert_tpu.data.vocab import ALPHABET

    alphabet = np.array(list(ALPHABET))
    seqs = ["".join(rng.choice(alphabet, size=int(n)))
            for n in rng.integers(8, 50, size=8)]
    return params, cfg, seqs


class TestServeQuant:
    # Documented weight-quantization serving bound at these tiny dims
    # (docs/serving.md): per-channel int8 weights on an UNTRAINED
    # d=16 trunk.
    PARITY_BOUND = 0.15

    def test_quantize_params_roundtrip_and_bytes(self, serve_setup):
        params, cfg, _ = serve_setup
        qp = q.quantize_params(params)
        back = q.dequantize_params(qp)
        # Structure preserved; >=2-D leaves quantized within one scale
        # quantum, 1-D leaves untouched.
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
            assert a.shape == b.shape
        flat_q = jax.tree.leaves(qp, is_leaf=q._is_quant_leaf)
        assert any(q._is_quant_leaf(x) for x in flat_q)
        ratio = q.param_bytes(qp) / q.param_bytes(params)
        assert ratio <= 0.40, ratio
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
            if a.ndim >= 2:
                scale = np.max(np.abs(np.asarray(a)), axis=-2,
                               keepdims=True) / 127.0
                assert np.all(np.abs(np.asarray(a) - np.asarray(b))
                              <= scale / 2 + 1e-7)
            else:
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))

    def test_bucketed_quant_arm_parity_and_sampling(self, serve_setup,
                                                    tmp_path):
        from proteinbert_tpu.obs import Telemetry, read_events
        from proteinbert_tpu.serve import Server

        params, cfg, seqs = serve_setup
        ev = str(tmp_path / "events.jsonl")
        tele = Telemetry(events_path=ev)
        fp = Server(params, cfg, max_batch=4, max_wait_s=0.005)
        qs = Server(params, cfg, max_batch=4, max_wait_s=0.005,
                    quant="int8", quant_parity_every=1, telemetry=tele)
        with fp, qs:
            # Warmup dummy batches must not consume the parity cadence
            # or count as LIVE parity samples (review fix): before any
            # real request the sample count is zero.
            assert qs.dispatcher.quant_report.get("parity_samples",
                                                  0) == 0
            assert qs.dispatcher._quant_batches == 0
            worst = 0.0
            for s in seqs:
                a = fp.embed(s, timeout=120)
                b = qs.embed(s, timeout=120)
                for k in a:
                    worst = max(worst,
                                float(np.max(np.abs(a[k] - b[k]))))
            sampled = qs.stats()["quant"]["parity_max"]
            go_a = fp.predict_go(seqs[0], timeout=120)
            go_b = qs.predict_go(seqs[0], timeout=120)
            stats = qs.stats()
        tele.close()
        assert 0.0 < worst <= self.PARITY_BOUND, worst
        # Every live batch is shadowed (parity_every=1), so the
        # dispatcher's own maximum must TRACK the one measured across
        # the two servers: a shadow that measures nothing (an arm
        # against itself) passes both bounds and fails here.
        assert abs(sampled - worst) <= 0.25 * worst + 1e-4, (sampled,
                                                              worst)
        assert float(np.max(np.abs(go_a - go_b))) <= self.PARITY_BOUND
        assert stats["quant"]["mode"] == "int8"
        assert stats["quant"]["parity_samples"] >= 1
        assert 0.0 < stats["quant"]["parity_max"] <= self.PARITY_BOUND
        assert stats["quant"]["weight_bytes_ratio"] <= 0.40
        recs = read_events(ev, strict=True)
        srs = [r for r in recs if r["event"] == "serve_request"]
        assert srs and all(r.get("quant") == "int8" for r in srs)
        sbs = [r for r in recs if r["event"] == "serve_batch"]
        assert sbs and all(r.get("quant") == "int8" for r in sbs)
        assert any(r.get("quant_parity_max") is not None for r in sbs)

    def test_ragged_quant_arm_parity(self, serve_setup):
        from proteinbert_tpu.serve import Server

        params, cfg, seqs = serve_setup
        fp = Server(params, cfg, max_batch=2, max_wait_s=0.005,
                    serve_mode="ragged")
        qs = Server(params, cfg, max_batch=2, max_wait_s=0.005,
                    serve_mode="ragged", quant="int8",
                    quant_parity_every=1)
        with fp, qs:
            worst = 0.0
            for s in seqs[:4]:
                a = fp.embed(s, timeout=120)
                b = qs.embed(s, timeout=120)
                for k in a:
                    worst = max(worst,
                                float(np.max(np.abs(a[k] - b[k]))))
            stats = qs.stats()
        assert 0.0 < worst <= self.PARITY_BOUND, worst
        assert stats["quant"]["parity_samples"] >= 1

    def test_ragged_rejects_act_quant(self, serve_setup):
        from proteinbert_tpu.serve import Server

        params, cfg, _ = serve_setup
        with pytest.raises(ValueError, match="int8_act"):
            Server(params, cfg, max_batch=2, serve_mode="ragged",
                   quant="int8_act")

    def test_act_arm_runs_and_stays_bounded(self, serve_setup):
        from proteinbert_tpu.serve import Server

        params, cfg, seqs = serve_setup
        fp = Server(params, cfg, max_batch=4, max_wait_s=0.005)
        qa = Server(params, cfg, max_batch=4, max_wait_s=0.005,
                    quant="int8_act")
        with fp, qa:
            a = fp.embed(seqs[0], timeout=120)
            b = qa.embed(seqs[0], timeout=120)
        worst = max(float(np.max(np.abs(a[k] - b[k]))) for k in a)
        # Activation fake-quant adds error on top of the weight arm;
        # documented looser bound.
        assert 0.0 < worst <= 2 * self.PARITY_BOUND, worst

    def test_fp32_trunk_parked_on_host_without_parity_shadow(
            self, serve_setup):
        """With no parity shadow the fp32 trunk has no device consumer,
        so resident HBM must hold ONLY the int8 weights (the footprint
        claim) — and the server still serves."""
        from proteinbert_tpu.serve import Server

        params, cfg, seqs = serve_setup
        srv = Server(params, cfg, max_batch=4, max_wait_s=0.005,
                     quant="int8")  # quant_parity_every defaults to 0
        assert srv.dispatcher.quant_report["fp32_resident"] == "host"
        assert all(isinstance(x, np.ndarray)
                   for x in jax.tree.leaves(srv.dispatcher.params))
        with srv:
            out = srv.embed(seqs[0], timeout=120)
        assert np.isfinite(out["global"]).all()
        # With the shadow on, both trunks stay resident by design.
        srv2 = Server(params, cfg, max_batch=4, quant="int8",
                      quant_parity_every=2)
        assert srv2.dispatcher.quant_report["fp32_resident"] == "device"
        srv2.abort()

    def test_fp32_arm_events_have_no_quant_fields(self, serve_setup,
                                                  tmp_path):
        """The documented contract is absent-means-fp32: a plain fp32
        server's serve_batch/serve_request events must not carry
        quant/quant_parity_max keys at all (not even as null)."""
        from proteinbert_tpu.obs import Telemetry, read_events
        from proteinbert_tpu.serve import Server

        params, cfg, seqs = serve_setup
        ev = str(tmp_path / "fp32_events.jsonl")
        tele = Telemetry(events_path=ev)
        with Server(params, cfg, max_batch=4, max_wait_s=0.005,
                    telemetry=tele) as srv:
            srv.embed(seqs[0], timeout=120)
        tele.close()
        recs = read_events(ev, strict=True)
        for r in recs:
            if r["event"] in ("serve_batch", "serve_request"):
                assert "quant" not in r, r
                assert "quant_parity_max" not in r, r

    def test_unknown_quant_mode_rejected(self, serve_setup):
        from proteinbert_tpu.serve import Server

        params, cfg, _ = serve_setup
        with pytest.raises(ValueError, match="quant"):
            Server(params, cfg, quant="int4")

    def test_serve_config_default_rides_run_config(self, serve_setup):
        from proteinbert_tpu.configs import ServeConfig
        from proteinbert_tpu.serve import Server

        params, cfg, _ = serve_setup
        qcfg = cfg.replace(serve=ServeConfig(quant="int8",
                                             quant_parity_every=3))
        srv = Server(params, qcfg, max_batch=4)
        assert srv.quant == "int8"
        assert srv.dispatcher.quant_parity_every == 3
        srv.abort()
        # Explicit ctor args override the config default.
        srv2 = Server(params, qcfg, max_batch=4, quant="fp32")
        assert srv2.quant == "fp32"
        assert srv2.dispatcher.qparams is None
        srv2.abort()
