"""The decoder's plain reference and the comparison after it do their host
arithmetic block by block over a few threads (PR 41). What holds them to
the numbers they gave before: `follow_steps` against a fixture recorded
with the tree BEFORE the rewrite (`benchmark/record_reference_fixture.py`),
and the blocked helpers against the whole-leaf numpy expressions they
took the place of, on hand-made trees with odd sizes."""

import json

import numpy as np
import pytest

from benchmark import blocked, compare, record_reference_fixture
from benchmark.reference import glm4_moe_lite_f32 as ref


@pytest.fixture(scope="module")
def recorded():
    with open(record_reference_fixture.FIXTURE) as f:
        return json.load(f)


@pytest.mark.parametrize("case", sorted(record_reference_fixture.CASES))
def test_follow_steps_gives_what_the_tree_before_the_rewrite_gave(recorded, case):
    fed = [{k: np.asarray(v, np.int32) for k, v in b.items()}
           for b in recorded["batches"]]
    assert [b["tokens"].tolist() for b in fed] == [
        b["tokens"].tolist() for b in record_reference_fixture.batches(
            recorded["c"]["vocab_size"])]
    was = recorded["cases"][case]
    out = record_reference_fixture.reading(ref.follow_steps(
        recorded["seed"], fed, recorded["c"], recorded["o"],
        precision=was["precision"], operands=was["operands"]))
    np.testing.assert_allclose(out["losses"], was["losses"], rtol=1e-6)
    for key in ("first_grad_norms", "change_norms"):
        floor = 1e-6 * float(np.median(was[key]))       # some leaves are all but zero
        np.testing.assert_allclose(out[key], was[key], rtol=1e-6, atol=floor,
                                   err_msg=key)
    assert out["first_ids"] == was["first_ids"]
    assert out["bias"] == was["bias"]
    if case == "bf16_params":     # the warm-up's steps are lost whole
        f32 = recorded["cases"]["f32"]["change_norms"]
        assert np.median(was["change_norms"]) < 0.5 * np.median(f32)


def _tree(rng, scale=1.0):
    """Leaves under a block, at one, over one and not a multiple of it, a
    scalar-sized, an empty and an all-zero one."""
    sizes = {"small": (7, 5), "block": (blocked.BLOCK,),
             "odd": (3, blocked.BLOCK // 2 + 11), "pieces": (2 * blocked.PIECE + 12345,),
             "one": (1,), "none": (0, 4)}
    tree = {k: (scale * rng.standard_normal(s)).astype(np.float32)
            for k, s in sizes.items()}
    tree["nested"] = {"zero": np.zeros((1000, 3), np.float32)}
    return tree


def test_blocked_norms_are_numpys_in_float64():
    import jax

    rng = np.random.default_rng(5)
    a, b = _tree(rng), _tree(rng, 1e-3)
    got = blocked.norms(a)
    assert jax.tree.structure(got) == jax.tree.structure(a)
    for g, x in zip(jax.tree.leaves(got), jax.tree.leaves(a)):
        assert g == pytest.approx(np.linalg.norm(x.astype(np.float64).ravel()),
                                  rel=1e-12, abs=0)
    assert got["nested"]["zero"] == 0.0 and got["none"] == 0.0
    for diff_dtype in (np.float64, np.float32):
        got = jax.tree.leaves(blocked.norms(a, minus=b, diff_dtype=diff_dtype))
        for g, x, y in zip(got, jax.tree.leaves(a), jax.tree.leaves(b)):
            want = np.linalg.norm((x.astype(diff_dtype) - y.astype(diff_dtype))
                                  .astype(np.float64).ravel())
            assert g == pytest.approx(want, rel=1e-12, abs=0)
    # the same numbers whichever thread takes which piece
    assert blocked.sq_sums(a) == blocked.sq_sums(a)
    # a leaf whose memory is not in C order (the TPU hands some back so) is
    # read through a copy: the same norm
    turned = {"t": np.asfortranarray(a["odd"]), "s": a["small"][:, ::2]}
    assert not turned["t"].flags.c_contiguous
    assert blocked.norms(turned)["t"] == blocked.norms(a)["odd"]
    assert blocked.norms(turned)["s"] == pytest.approx(
        np.linalg.norm(a["small"][:, ::2].astype(np.float64)), rel=1e-12)


def _adam_as_it_was(params, grads, mu, nu, count, o):
    """`adam_step` before PR 41: whole-leaf numpy expressions."""
    import jax

    leaves = jax.tree.leaves(grads)
    gnorm = float(np.sqrt(sum(float(np.sum(np.square(g, dtype=np.float64)))
                              for g in leaves)))
    clip = np.float32(1.0 if gnorm < o["grad_clip_norm"]
                      else o["grad_clip_norm"] / gnorm)
    b1, b2 = np.float32(o["b1"]), np.float32(o["b2"])
    t = count + 1
    lr = np.float32(ref.learning_rate(count, o))
    c1, c2 = np.float32(1 - o["b1"] ** t), np.float32(1 - o["b2"] ** t)
    for p, g, a, b in zip(*map(jax.tree.leaves, (params, grads, mu, nu))):
        g *= clip
        a *= b1
        a += (1 - b1) * g
        b *= b2
        b += (1 - b2) * g * g
        p -= lr * (a / c1) / (np.sqrt(b / c2) + np.float32(1e-8))


@pytest.mark.parametrize("scale", [1e-4, 3.0], ids=["unclipped", "clipped"])
def test_blocked_adam_step_is_the_whole_leaf_formulas_to_the_last_bit(scale):
    import jax

    o = {"learning_rate": 2e-4, "warmup_steps": 4, "grad_clip_norm": 1.0,
         "b1": 0.9, "b2": 0.999}
    rng = np.random.default_rng(11)
    copy = lambda t: jax.tree.map(np.copy, t)  # noqa: E731
    params = _tree(rng, 0.02)
    mu, nu = jax.tree.map(np.zeros_like, params), jax.tree.map(np.zeros_like, params)
    old = [copy(params), None, copy(mu), copy(nu)]
    for count in range(3):          # rates 0, 5e-5, 1e-4
        grads = _tree(rng, scale)
        old[1] = copy(grads)
        ref.adam_step(params, grads, mu, nu, count, o)
        _adam_as_it_was(*old, count, o)
        for mine, theirs in zip((params, grads, mu, nu), old):
            for x, y in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
                np.testing.assert_array_equal(x, y)
    first = _tree(np.random.default_rng(11), 0.02)
    assert np.abs(params["odd"] - first["odd"]).max() > 0        # it moved


def test_an_update_in_place_refuses_a_leaf_it_could_only_copy():
    """The first chip run of PR 41 lost Adam's update of the stacked
    experts' leaves: the TPU handed them back with their dimensions in
    another order in memory, `reshape(-1)` of such a leaf is a copy, and
    the copy took the update. `fetch` asks for C order; `blocked` refuses."""
    import jax

    o = {"learning_rate": 2e-4, "warmup_steps": 4, "grad_clip_norm": 1.0,
         "b1": 0.9, "b2": 0.999}
    rng = np.random.default_rng(2)
    params = {"w": np.asfortranarray(rng.standard_normal((6, 5)).astype(np.float32))}
    zeros = jax.tree.map(np.zeros_like, params)
    with pytest.raises(ValueError, match="contiguous"):
        ref.adam_step(params, jax.tree.map(np.ones_like, params), zeros,
                      jax.tree.map(np.zeros_like, params), 1, o)
    fetched = ref.fetch({"w": jax.numpy.asarray(params["w"])})["w"]
    assert fetched.flags.c_contiguous and fetched.flags.writeable
    np.testing.assert_array_equal(fetched, params["w"])


def test_leaf_dir_gaps_once_serve_the_check_and_the_spread():
    """`lm_pretrain.run` computes the gaps once and hands them to both
    readers; before PR 41 each made its own pass over the two trees."""
    import jax

    rng = np.random.default_rng(3)
    reference = _tree(rng)
    program = jax.tree.map(
        lambda x: (x + 1e-3 * rng.standard_normal(x.shape)).astype(np.float32), reference)
    was = []
    for p, r in zip(jax.tree.leaves(program), jax.tree.leaves(reference)):
        d = p - r
        was.append((np.sqrt(np.sum(np.square(d, dtype=np.float64))),
                    np.sqrt(np.sum(np.square(r, dtype=np.float64)))))
    diff, norm = map(np.array, zip(*was))
    was = diff / np.maximum(norm, np.median(norm))
    gaps = compare.leaf_dir_gaps(program, reference)
    np.testing.assert_allclose(gaps, was, rtol=1e-12)
    side = {"losses": [1.0], "first_grad_norms": blocked.norms(reference),
            "change_norms": blocked.norms(reference)}
    twice = compare.training_checks(dict(side, first_grad=program),
                                    dict(side, first_grad=reference))
    once = compare.training_checks(dict(side, first_grad=None),
                                   dict(side, first_grad=None), dir_gaps=gaps)
    assert once == twice
    assert once["grad_dir_gap"] == pytest.approx(float(np.median(was)), rel=1e-12)
    assert compare.spread_of(gaps) == compare.leaf_dir_spread(program, reference)
    assert compare.spread_of(gaps)[-1] == pytest.approx(float(was.max()), rel=1e-12)
