"""The causal expert decoder (models/glm_moe.py, ops/moe.py) at a tiny
preset: against the plain reference on seeded weights, the shares of an
expert-parallel group adding up to the uncut layer, segment isolation,
no dropped assignment under total imbalance, checkpoint and config."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare
from benchmark.reference import glm4_moe_lite_f32 as ref
from proteinbert_tpu.configs import (
    DecoderConfig, config_from_dict, config_to_dict, get_preset,
)
from proteinbert_tpu.models import glm_moe
from proteinbert_tpu.ops import moe
from proteinbert_tpu.train import train_state as ts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2_500_000_123        # seeds pass 2**31


def tiny(**model):
    cfg = get_preset("glm_tiny")
    return cfg.replace(model=dataclasses.replace(cfg.model, **model))


def sizes(cfg):
    return dataclasses.asdict(cfg.model)


def batches(n, rows=2, L=64, vocab=512, seed=0):
    rng = np.random.default_rng(seed)
    seg = np.stack([np.concatenate([np.repeat([1, 2, 3], [20, 30, 10]), np.zeros(4)]),
                    np.repeat([1, 2], [40, 24])])[:rows].astype(np.int32)
    return [{"tokens": rng.integers(0, vocab, (rows, L)).astype(np.int32),
             "segment_ids": seg} for _ in range(n)]


def _strip(tree):
    return {k: v for k, v in tree.items() if k != "balance_bias"}


def _mu(opt_state):
    return [s for s in jax.tree.leaves(opt_state, is_leaf=lambda s: hasattr(s, "mu"))
            if hasattr(s, "mu")][0].mu


@pytest.mark.parametrize("held,offset", [(8, 0), (4, 2)])
def test_three_steps_follow_the_reference(held, offset):
    """Weights from the seed, loss, first gradient, three clip + Adam
    steps and the balance bias: the program through `train_step`, the
    reference through its own code, on a whole and on a part share."""
    cfg = tiny(experts_held=held, expert_offset=offset)
    state = ts.create_train_state(ref.seed_key(SEED), cfg)
    theirs, their_bias = ref.init_params(
        jax.random.split(ref.seed_key(SEED))[0], sizes(cfg))
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b)), _strip(state.params), theirs))
    assert glm_moe.param_count(cfg.model) == sum(
        x.size for x in jax.tree.leaves(theirs))
    start = jax.device_get(state.params)
    losses, first = [], None
    fed = batches(3)
    for batch in fed:
        state, metrics = ts.train_step(state, batch, cfg)
        losses.append(float(metrics["loss"]))
        assert float(metrics["dropped_assignments"]) == 0
        if first is None:
            first = _strip(jax.tree.map(
                lambda x: np.asarray(x) / np.float32(1 - cfg.optimizer.b1),
                jax.device_get(_mu(state.opt_state))))
    end = jax.device_get(state.params)
    o = {k: getattr(cfg.optimizer, k) for k in (
        "learning_rate", "warmup_steps", "grad_clip_norm", "b1", "b2")}
    reference = ref.follow_steps(SEED, fed, sizes(cfg), o)
    program = {"losses": losses, "first_grad": first,
               "first_grad_norms": ref.host_norms(first),
               "change_norms": ref.host_norms(jax.tree.map(
                   lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
                   _strip(end), _strip(start)))}
    gaps = compare.training_checks(program, reference)
    assert gaps["loss_rel_gap"] < 1e-6 and gaps["grad_norm_gap"] < 1e-5
    assert gaps["grad_dir_gap"] < 1e-5 and gaps["change_norm_gap"] < 1e-3
    for k, b in end["balance_bias"].items():
        np.testing.assert_allclose(np.asarray(b), reference["bias"][k], atol=1e-7)
        assert np.abs(np.asarray(b)).max() > 0      # the bias did move


def test_the_shares_add_up():
    """The eight 8-expert shares' routed parts, with the shared expert
    counted once, sum to the uncut 64-expert reference layer."""
    cfg = tiny(n_routed_experts=64, experts_held=64, num_experts_per_tok=4).model
    c = dataclasses.asdict(cfg)
    params, bias = ref.init_params(jax.random.PRNGKey(3), c)
    layer = jax.tree.map(lambda a: a[0], params["layers"])
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((48, cfg.hidden_size)), jnp.float32)
    real = jnp.ones((48,), bool)
    whole, _, _ = ref._routed(layer["moe"], bias["layers"][0], x, real, c, "f32")
    shared = ref._ffn(layer["shared"], x, "f32")
    total, held_total = 0.0, 0
    for share in range(8):
        part = dataclasses.replace(cfg, experts_held=8, expert_offset=8 * share)
        mine = dict(layer["moe"], experts=jax.tree.map(
            lambda a: a[8 * share:8 * share + 8], layer["moe"]["experts"]))
        y, stats = moe.moe_apply(mine, bias["layers"][0], x, real, part)
        total = total + y
        held_total += int(stats["held_counts"].sum())
        assert int(stats["dropped"]) == 0
    assert held_total == 48 * 4         # every assignment on exactly one share
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(whole + shared), atol=2e-6)
    assert float(jnp.abs(whole).max()) > 1e-3


def _losses(cfg, tokens, seg):
    params = glm_moe.init(jax.random.PRNGKey(1), cfg.model)
    _, (out, _) = glm_moe.loss_and_stats(
        params, jnp.asarray(tokens), jnp.asarray(seg), cfg.model)
    real = seg > 0
    n_main = (real & (np.pad(seg[:, 1:], ((0, 0), (0, 1))) == seg)).sum()
    n_mtp = (real & (np.pad(seg[:, 2:], ((0, 0), (0, 2))) == seg)).sum()
    return float(out["main_loss"]) * n_main, float(out["mtp_loss"]) * n_mtp


def test_a_packed_row_equals_its_documents_alone():
    """Main and prediction-module losses of a packed row are the sums of
    its documents' losses, each alone in a row: attention, positions, the
    module's shifted inputs and both targets stay inside the segment."""
    cfg = tiny()
    batch = batches(1, rows=1)[0]
    packed = _losses(cfg, batch["tokens"], batch["segment_ids"])
    alone = np.zeros(2)
    for s in (1, 2, 3):
        at = batch["segment_ids"][0] == s
        tokens = np.zeros((1, 64), np.int32)
        seg = np.zeros((1, 64), np.int32)
        tokens[0, :at.sum()] = batch["tokens"][0, at]
        seg[0, :at.sum()] = 1
        alone += _losses(cfg, tokens, seg)
    np.testing.assert_allclose(packed, alone, rtol=2e-5)


def test_no_assignment_is_dropped_when_every_token_takes_one_expert():
    """Total imbalance: every token's first choice is expert 0 and its
    second expert 1 (a bias no score can beat): 2 x T assignments on two
    held experts, all taken, and the result is the dense sum."""
    cfg = tiny().model
    c = dataclasses.asdict(cfg)
    params, _ = ref.init_params(jax.random.PRNGKey(5), c)
    layer = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
    bias = jnp.zeros((8,)).at[0].set(100.0).at[1].set(50.0)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((128, 64)), jnp.float32)
    real = jnp.ones((128,), bool)
    y, stats = moe.moe_apply(layer, bias, x, real, cfg)
    assert stats["held_counts"].tolist() == [128, 128, 0, 0, 0, 0, 0, 0]
    assert int(stats["dropped"]) == 0
    want, _, _ = ref._routed(layer, bias, x, real, c, "f32")
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-6)
    # and through the custom backward pass: the gradient of the dense sum
    def dense(p):
        return (ref._routed(p, bias, x, real, c, "f32")[0] ** 2).sum()

    def grouped(p):
        return (moe.moe_apply(p, bias, x, real, cfg)[0] ** 2).sum()

    for a, b in zip(jax.tree.leaves(jax.grad(grouped)(layer)),
                    jax.tree.leaves(jax.grad(dense)(layer))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4)


def test_pad_tokens_are_routed_nowhere():
    cfg = tiny().model
    params = glm_moe.init(jax.random.PRNGKey(0), cfg)
    batch = batches(1)[0]
    _, (_, counters) = glm_moe.loss_and_stats(
        params, jnp.asarray(batch["tokens"]), jnp.asarray(batch["segment_ids"]), cfg)
    real = int((batch["segment_ids"] > 0).sum())
    assert counters["load"].sum(-1).tolist() == [real * cfg.num_experts_per_tok]
    assert int(counters["held_counts"].sum()) == 2 * real * cfg.num_experts_per_tok


def test_checkpoint_round_trip_of_the_decoder_state(tmp_path):
    from proteinbert_tpu.train.checkpoint import Checkpointer

    cfg = tiny()
    state = ts.create_train_state(jax.random.PRNGKey(0), cfg)
    state, _ = ts.train_step(state, batches(1)[0], cfg)
    ck = Checkpointer(str(tmp_path / "run"), async_save=False)
    assert ck.save(1, state, {"batches_consumed": 1})
    ck.wait()
    fresh = ts.create_train_state(jax.random.PRNGKey(9), cfg)
    back, data = ck.restore(fresh)
    ck.close()
    assert data["batches_consumed"] == 1 and int(back.step) == 1
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.abs(np.asarray(back.params["balance_bias"]["layers"])).max() > 0


def test_experts_go_over_the_model_axis():
    from jax.sharding import Mesh, PartitionSpec as P
    from proteinbert_tpu.parallel.sharding import shard_train_state, state_sharding

    cfg = tiny()
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 2, 2, 1),
                ("data", "fsdp", "model", "seq"))
    state = ts.create_train_state(jax.random.PRNGKey(0), cfg)
    layout = state_sharding(mesh, jax.eval_shape(lambda: state))
    assert layout.params["layers"]["moe"]["experts"]["gate"].spec == P(
        None, "model", None, None)
    assert layout.params["mtp"]["layer"]["moe"]["experts"]["down"].spec == P(
        "model", None, None)
    assert "model" not in str(layout.params["layers"]["moe"]["router"].spec)
    from proteinbert_tpu.parallel.sharding import batch_sharding

    where = batch_sharding(mesh)
    batch = batches(1)[0]
    _, want = ts.train_step(ts.create_train_state(jax.random.PRNGKey(0), cfg),
                            batch, cfg)
    new, metrics = ts.train_step(
        shard_train_state(state, mesh),
        jax.device_put(batch, {k: where[k] for k in batch}), cfg)
    np.testing.assert_allclose(float(metrics["loss"]), float(want["loss"]), rtol=1e-5)
    assert int(new.step) == 1


def test_config_round_trip_and_presets():
    cfg = get_preset("glm47flash_ep8")
    assert isinstance(cfg.model, DecoderConfig)
    assert config_from_dict(config_to_dict(cfg)) == cfg
    assert glm_moe.param_count(cfg.model) == 706_516_480
    assert config_from_dict(config_to_dict(get_preset("base"))) == get_preset("base")


def test_published_equals_the_files_keys_except_the_reduced():
    with open(os.path.join(ROOT, "benchmark/configs/glm-4.7-flash-ep8.json")) as f:
        config = json.load(f)
    assert sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    for key, value in config["published"].items():
        if key in config["reduced"]:
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert config["router_width"] == config["published"]["n_routed_experts"]
    assert config["parameters"] == 706_516_480
    assert abs(16 * config["parameters"] / 2 ** 30 - config["state_gib"]) < 0.005


def test_token_documents_pack_through_the_planner():
    from proteinbert_tpu.data.dataset import TokenDocumentDataset
    from proteinbert_tpu.data.packing import make_packed_iterator

    rng = np.random.default_rng(0)
    docs = [rng.integers(0, 512, n).astype(np.int32)
            for n in rng.integers(3, 40, 64)]
    it = make_packed_iterator(TokenDocumentDataset(docs, 64), 2, shuffle=False,
                              max_segments=4, max_open=4)
    batch = next(it)
    assert set(batch) == {"tokens", "segment_ids"}
    seen = []
    for row_t, row_s in zip(batch["tokens"], batch["segment_ids"]):
        for s in range(1, row_s.max() + 1):
            seen.append(row_t[row_s == s])
    for got in seen:    # every packed segment is one whole document, id 0 and all
        assert any(len(d) == len(got) and (d == got).all() for d in docs)


def test_a_step_reports_the_experts_each_token_chose_and_the_trainer_logs_scalars():
    """`metrics["route_ids"]` (expert layers + module, tokens, k) is the
    one metric that is no scalar: k distinct experts a real token, the id
    past the last at a pad; the trainer's log fetch leaves it on the
    device and names the decoder's two losses as the model does."""
    import logging

    from proteinbert_tpu.train.trainer import pretrain

    cfg = tiny()
    cfg = cfg.replace(train=dataclasses.replace(cfg.train, max_steps=4, log_every=2))
    fed = batches(4)
    state, metrics = ts.train_step(
        ts.create_train_state(jax.random.PRNGKey(0), cfg), fed[0], cfg)
    ids = np.asarray(metrics["route_ids"])
    real = (fed[0]["segment_ids"] > 0).reshape(-1)
    m = cfg.model
    assert ids.shape == (m.num_moe_layers + 1, real.size, m.num_experts_per_tok)
    assert (ids[:, ~real] == m.n_routed_experts).all()
    assert ((ids[:, real] >= 0) & (ids[:, real] < m.n_routed_experts)).all()
    assert (np.diff(np.sort(ids[:, real], axis=-1), axis=-1) > 0).all()
    assert all(v.ndim == 0 for k, v in metrics.items() if k != "route_ids")
    # a handler of the test's own on the trainer's logger: another test's
    # logging set-up may have turned propagation to the root off
    said, log = [], logging.getLogger("proteinbert_tpu.train.trainer")
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    level = log.level
    log.addHandler(handler)
    log.setLevel(logging.INFO)
    try:
        out = pretrain(cfg, iter(fed))
    finally:
        log.removeHandler(handler)
        log.setLevel(level)
    assert [h["step"] for h in out["history"]] == [2, 4]
    for h in out["history"]:
        assert "route_ids" not in h and "mfu" not in h
        assert {"main_loss", "mtp_loss", "main_acc", "dropped_assignments"} <= set(h)
        assert all(isinstance(v, (int, float)) for v in h.values())
    assert any("(main " in line and " mtp " in line for line in said)


def test_the_flash_kernel_names_the_sizes_it_does_not_take():
    from proteinbert_tpu.ops.attention import (
        flash_segment_attention, flash_tiles_fit,
    )

    assert flash_tiles_fit(8192, 512, 256, 256)
    assert not flash_tiles_fit(64, 16, 16, 16)
    assert not flash_tiles_fit(8192, 512, 192, 256)
    q = jnp.zeros((1, 64, 2, 16))
    with pytest.raises(ValueError, match="rows of 64, block 16, heads of 16 / 16"):
        flash_segment_attention(q, q, q, jnp.ones((1, 64), jnp.int32),
                                scale=1.0, block=16)
