"""The KDA op (`ops/kda.py`), its Pallas state walk (`kernels/kda.py`, in
the interpreter) and the segment-bounded convolution, against the token
recurrence and the shifted adds of the plain reference
(`benchmark/reference/bailing_hybrid_f32.py`), each document ALONE."""

import numpy as np
import pytest

from benchmark.reference import bailing_hybrid_f32 as ref
from proteinbert_tpu.ops import kda

B, L, H, D = 2, 128, 3, 16
# lengths that are no multiple of either chunk; padding ends each row
DOCS = [[37, 50, 30], [100, 5]]


def _inputs(seed=0, H=H):
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    f32 = lambda a: a.astype(np.float32)  # noqa: E731
    q = f32(unit(rng.normal(size=(B, L, H, D))) * D ** -0.5)
    k = f32(unit(rng.normal(size=(B, L, H, D))))
    v = f32(rng.normal(size=(B, L, H, D)))
    g = f32(-5.0 * rng.uniform(size=(B, L, H, D)) ** 3)     # in (-5, 0], many near 0
    beta = f32(rng.uniform(size=(B, L, H)))
    seg = np.zeros((B, L), np.int32)
    spans = []
    for b, lengths in enumerate(DOCS):
        at = 0
        for i, n in enumerate(lengths):
            seg[b, at:at + n] = i + 1
            spans.append((b, slice(at, at + n)))
            at += n
    return (q, k, v, g, beta, seg), spans


def _alone(args, spans):
    q, k, v, g, beta, _ = args
    return [np.asarray(ref.kda_recurrence(q[b, s], k[b, s], v[b, s], g[b, s], beta[b, s]))
            for b, s in spans]


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_op_equals_the_token_recurrence_of_each_document_alone(chunk):
    args, spans = _inputs()
    got = np.asarray(kda.kda_chunked(*args, chunk))
    for (b, s), want in zip(spans, _alone(args, spans)):
        np.testing.assert_allclose(got[b, s], want, atol=1e-5)


@pytest.mark.parametrize("chunk, heads", [(16, 3), (64, 3), (64, 4), (64, 6)])
def test_pallas_kernels_in_the_interpreter_equal_the_op(chunk, heads):
    """The TPU path (`kernels/kda.py`: A and B, the triangular inverse in
    XLA, the walk of the state) against the op in plain jax, on packed
    rows, and so against each document alone; one, four and two heads a
    grid step."""
    args, spans = _inputs(1, heads)
    got = np.asarray(kda._kda_tpu(*args, chunk, interpret=True))
    np.testing.assert_allclose(got, np.asarray(kda._kda_plain(*args, chunk)),
                               atol=2e-6)
    for (b, s), want in zip(spans, _alone(args, spans)):
        np.testing.assert_allclose(got[b, s], want, atol=1e-5)


def test_pallas_pairs_kernel_equals_the_ops_a_and_b():
    """`kda_pairs` alone, with every channel near the lower bound so that
    exp(G) underflows inside a chunk: its B is the op's, finite."""
    from proteinbert_tpu.kernels.kda import kda_pairs

    (q, k, v, g, beta, seg), _ = _inputs(3)
    g = np.full_like(g, -4.999)
    flat = lambda a: a.reshape(B, L, H * D)  # noqa: E731
    ids, reached, reaches, keeps = kda._chunk_masks(seg, 64)
    rows = np.zeros((B, L // 64, 8, 64), np.float32)
    rows[:, :, 0], rows[:, :, 1], rows[:, :, 2] = ids, reached, reaches
    rows[:, :, 3] = np.asarray(keeps)[..., None]
    a, b = kda_pairs(flat(q), flat(k), flat(g), rows, H, kda.SUB, interpret=True)
    want = kda.kda_prepare(q, k, v, g, beta, seg, 64)
    assert np.isfinite(np.asarray(a)).all()
    np.testing.assert_allclose(np.asarray(b), np.asarray(want.b), atol=1e-6)
    assert not np.triu(np.asarray(a)).any()       # strictly lower


def test_a_document_does_not_read_what_was_packed_before_it():
    """The same document after two different neighbours, at an offset
    inside a chunk: its answer does not move (to the rounding of the
    chunk's running sum of log decays, which the neighbour shares)."""
    args, spans = _inputs(2)
    q, k, v, g, beta, seg = args
    first = np.asarray(kda.kda_chunked(*args, 64))
    other = [a.copy() for a in (q, k, v, g, beta)]
    for a in other:                      # another first document in row 0
        a[0, :37] = a[1, 20:57]
    second = np.asarray(kda.kda_chunked(*other, seg, 64))
    assert np.abs(first[0, :37] - second[0, :37]).max() > 1e-3
    np.testing.assert_allclose(first[0, 37:87], second[0, 37:87], atol=2e-6)


def test_strong_decay_over_a_block_stays_finite_and_right():
    """Every channel at the lower bound for a whole row: exp(G) underflows
    inside a chunk, the factors around a block's first position do not
    overflow."""
    args, spans = _inputs(3)
    q, k, v, g, beta, seg = args
    g = np.full_like(g, -4.999)
    got = np.asarray(kda.kda_chunked(q, k, v, g, beta, seg, 64))
    assert np.isfinite(got).all()
    for (b, s), want in zip(spans, _alone((q, k, v, g, beta, seg), spans)):
        np.testing.assert_allclose(got[b, s], want, atol=1e-5)


def test_unit_lower_inverse():
    rng = np.random.default_rng(4)
    m = np.tril(rng.normal(size=(2, 3, 64, 64)).astype(np.float32) * 0.3, -1)
    inv = np.asarray(kda._unit_lower_inverse(m))
    np.testing.assert_allclose(inv @ (np.eye(64) + m), np.broadcast_to(
        np.eye(64), m.shape), atol=2e-4)


def test_rows_that_are_no_multiple_of_the_chunk_are_refused():
    args, _ = _inputs()
    with pytest.raises(ValueError, match="multiple of the chunk"):
        kda.kda_chunked(*args, 48)


def test_segment_conv_reads_nothing_across_a_boundary():
    rng = np.random.default_rng(5)
    (_, _, _, _, _, seg), spans = _inputs()
    x = rng.normal(size=(B, L, 8)).astype(np.float32)
    taps = rng.normal(size=(4, 8)).astype(np.float32)
    got = np.asarray(kda.segment_conv(x, taps, seg))
    for b, s in spans:
        np.testing.assert_allclose(got[b, s], np.asarray(ref.conv_taps(x[b, s], taps)),
                                   atol=1e-6)


def test_the_plain_op_is_differentiable_and_its_gradient_is_the_recurrences():
    """Forward only on the serving path, but the op in plain `jax.numpy`
    carries a gradient for the training path to take later: that of the
    token recurrence, one document a row."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(6)
    n = 48
    q, k, v, g = (jnp.asarray(rng.normal(size=(1, n, 2, 8)), jnp.float32)
                  for _ in range(4))
    g = -jnp.abs(g)
    beta = jnp.asarray(rng.uniform(size=(1, n, 2)), jnp.float32)
    seg = jnp.ones((1, n), jnp.int32)
    w = jnp.asarray(rng.normal(size=(1, n, 2, 8)), jnp.float32)

    def chunked(q, k, v, g, beta):
        return jnp.sum(w * kda.kda_chunked(q, k, v, g, beta, seg, 16))

    def token_by_token(q, k, v, g, beta):
        return jnp.sum(w[0] * ref.kda_recurrence(q[0], k[0], v[0], g[0], beta[0]))

    got = jax.grad(chunked, argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    want = jax.grad(token_by_token, argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=2e-4)
