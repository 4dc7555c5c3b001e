"""The Pallas kernel of the chunked Mamba-2 recurrence (`kernels/ssd.py`)
in the interpreter at small lane-aligned shapes, against the token
recurrence and the plain scan over chunks (`ops/ssd.py`): boundaries
inside chunks, a pad tail, rows whose boundaries differ, bfloat16
operands on a float32 state, document isolation, the gradient through
the dispatching `ssd_chunked`, and which sizes the tiles take with what
the path counter reads."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proteinbert_tpu.configs import get_preset
from proteinbert_tpu.kernels import ssd as kernel
from proteinbert_tpu.models import glm_moe
from proteinbert_tpu.ops import ssd

Q = 128
# row 0: a document that ends inside the first chunk (70), one over more
# than three chunks (400), one that starts and ends inside the last (20),
# a pad tail; row 1: other boundaries, no pad
ROWS = ([70, 400, 20], [300, 212])


def _operands(seed=0, L=512, H=4, P=64, G=2, N=128, rows=ROWS):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    B = len(rows)
    x = jax.random.normal(k[0], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, L, H)) - 2.0)
    a = -jnp.exp(jax.random.normal(k[2], (H,)))
    b = jax.random.normal(k[3], (B, L, G, N))
    c = jax.random.normal(k[4], (B, L, G, N))
    seg = np.zeros((B, L), np.int32)
    for r, row in enumerate(rows):
        ends = np.cumsum(row)
        for i, (lo, hi) in enumerate(zip(np.r_[0, ends[:-1]], ends), 1):
            seg[r, lo:hi] = i
    return x, dt, a, b, c, jnp.asarray(seg)


@pytest.fixture
def tpu_branches(monkeypatch):
    """Every `lax.platform_dependent` on its TPU branch and the Pallas
    kernels in the interpreter: -> the names of the kernels called."""
    from jax import lax
    from jax.experimental import pallas as pl

    called, pallas_call = [], pl.pallas_call

    def interpreted(*args, **kwargs):
        called.append(kwargs["name"])
        return pallas_call(*args, **{**kwargs, "interpret": True})

    monkeypatch.setattr(pl, "pallas_call", interpreted)
    monkeypatch.setattr(lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    return called


def _in_the_interpreter(*operands, dtype=jnp.float32):
    return ssd._ssd_tpu(*operands, Q, dtype, interpret=True)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("P", [64, 128, 256])
def test_the_kernel_is_the_token_recurrence(P):
    """Heads of 64 two a lane tile, heads of 128 one a tile, heads of 256
    two tiles each."""
    x, dt, a, b, c, seg = _operands(P=P)
    want = ssd.ssd_recurrent(x, dt, a, b, c, seg)
    got = _in_the_interpreter(x, dt, a, b, c, seg)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    real = np.asarray(seg > 0)
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real],
                               atol=2e-5 * float(jnp.abs(want).max()))
    # and the long document of row 0 is what it is ALONE, from a zero state
    alone = ssd.ssd_recurrent(x[:1, 70:470], dt[:1, 70:470], a, b[:1, 70:470],
                              c[:1, 70:470], jnp.ones((1, 400), jnp.int32))
    np.testing.assert_allclose(got[0, 70:470], alone[0],
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_bfloat16_operands_give_the_plain_scans_numbers_on_a_float32_state(monkeypatch):
    x, dt, a, b, c, seg = _operands(seed=2)
    bf = jnp.bfloat16
    # decays slow enough that a chunk's state is mostly the chunks' before it
    x, b, c, a = x.astype(bf), b.astype(bf), c.astype(bf), 0.02 * a
    plain = ssd._ssd_plain(x, dt, a, b, c, seg, Q, bf)
    real = np.asarray(seg > 0)
    apart = lambda got: _rel(np.asarray(got)[real], np.asarray(plain)[real])  # noqa: E731
    got = _in_the_interpreter(x, dt, a, b, c, seg, dtype=bf)
    assert got.dtype == jnp.float32
    assert apart(got) < 1e-4            # bfloat16's own rounding is 4e-3
    assert 1e-4 < _rel(got, ssd.ssd_recurrent(x, dt, a, b, c, seg)) < 2e-2
    # the cell's control: a state ROUNDED to bfloat16 from chunk to chunk
    # is another result, and this test tells it from the kernel's
    sound = kernel._chunk_kernel

    def rounded(*refs, **sizes):
        sound(*refs, **sizes)
        refs[-1][...] = refs[-1][...].astype(bf).astype(jnp.float32)

    monkeypatch.setattr(kernel, "_chunk_kernel", rounded)
    assert apart(_in_the_interpreter(x, dt, a, b, c, seg, dtype=bf)) > 1e-3


def test_a_document_packed_after_others_answers_as_it_does_alone():
    x, dt, a, b, c, seg = _operands(seed=3)
    packed = _in_the_interpreter(x, dt, a, b, c, seg)
    # row 0's second document (70:470) moved to a row's start, padded to chunks
    at = lambda m: jnp.pad(m[:1, 70:470], [(0, 0), (0, 112)] + [(0, 0)] * (m.ndim - 2))  # noqa: E731
    alone = _in_the_interpreter(at(x), at(dt), a, at(b), at(c),
                                jnp.pad(jnp.ones((1, 400), jnp.int32), [(0, 0), (0, 112)]))
    np.testing.assert_allclose(packed[0, 70:470], alone[0, :400],
                               atol=2e-5 * float(jnp.abs(packed).max()))
    # and blind to the boundaries it must NOT be
    blind = _in_the_interpreter(x, dt, a, b, c, jnp.ones_like(seg))
    assert float(jnp.abs(blind[0, 70:470] - alone[0, :400]).max()) > 1e-2


def test_the_dispatching_form_differentiates_to_the_token_recurrences(tpu_branches):
    """`ssd_chunked` at sizes the tiles take, its TPU branch forced into
    the interpreter: the forward is the kernel's, the backward the plain
    scan's, and the gradient the token recurrence's."""
    x, dt, a, b, c, seg = _operands(seed=1, L=256, rows=([70, 150], [256]))
    real = (seg > 0)[..., None, None]

    def loss(fn, x, dt, a, b, c):
        return jnp.sum(jnp.where(real, fn(x, dt, a, b, c), 0.0) ** 2)

    chunked = lambda *o: ssd.ssd_chunked(*o, seg, Q)  # noqa: E731
    token = lambda *o: ssd.ssd_recurrent(*o, seg)  # noqa: E731
    got = jax.grad(loss, argnums=(1, 2, 3, 4, 5))(chunked, x, dt, a, b, c)
    assert tpu_branches == ["ssd_chunks"]
    want = jax.grad(loss, argnums=(1, 2, 3, 4, 5))(token, x, dt, a, b, c)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-4 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("sizes, fits", [
    (dict(L=8192, H=128, P=64, G=8, N=128, Q=128), True),       # the published widths
    (dict(L=512, H=4, P=64, G=2, N=128, Q=128), True),
    (dict(L=512, H=4, P=128, G=2, N=128, Q=128), True),
    (dict(L=512, H=2, P=256, G=2, N=128, Q=128), True),         # a head over two lane tiles
    (dict(L=64, H=8, P=8, G=2, N=16, Q=16), False),             # `nemotron_tiny`
    (dict(L=512, H=4, P=64, G=2, N=64, Q=128), False),          # the state no lane tile
    (dict(L=512, H=2, P=64, G=2, N=128, Q=128), False),         # a group half a tile
    (dict(L=512, H=6, P=96, G=2, N=128, Q=128), False),         # heads that straddle tiles
    (dict(L=512, H=4, P=64, G=2, N=128, Q=64), False),          # the chunk no lane tile
    (dict(L=8192, H=128, P=64, G=1, N=128, Q=128), False),      # 128 heads' step over the budget
])
def test_which_sizes_the_tiles_take(sizes, fits):
    assert kernel.tiles_fit(**sizes) is fits


def test_the_mixer_counts_which_recurrence_it_traced(request, monkeypatch):
    """Sizes the tiles do not take fall to the plain scan whatever the
    platform and are counted `reference/tiles_do_not_fit`; fitting ones
    are `pallas/chunked` where the program is lowered for a TPU and
    `reference/not_tpu` here."""
    tiny = get_preset("nemotron_tiny").model
    wide = dataclasses.replace(tiny, mamba_num_heads=4, mamba_head_dim=64,
                               ssm_state_size=128, chunk_size=128)

    def traced(m, L):
        p = jax.tree.map(lambda a: a[0], glm_moe.init_served(jax.random.PRNGKey(0), m)["mamba"])
        before = dict(kernel.SSD_CORE_PATH_TOTAL)
        x = jnp.ones((1, L, m.hidden_size), jnp.float32)
        jax.eval_shape(lambda p: glm_moe.mamba_mixer(p["mixer"], x, jnp.ones((1, L), jnp.int32), m), p)
        return {k: n - before.get(k, 0) for k, n in kernel.SSD_CORE_PATH_TOTAL.items()
                if n != before.get(k, 0)}

    assert traced(tiny, 64) == {("reference", "tiles_do_not_fit"): 1}
    assert traced(wide, 256) == {("reference", "not_tpu"): 1}
    called = request.getfixturevalue("tpu_branches")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert traced(wide, 256) == {("pallas", "chunked"): 1}
    assert called == ["ssd_chunks"]
    called.clear()
    assert traced(tiny, 64) == {("reference", "tiles_do_not_fit"): 1}
    assert called == []


def test_a_server_mirrors_the_counter_into_its_registry_and_stats():
    from proteinbert_tpu.obs import Telemetry
    from proteinbert_tpu.serve.server import Server

    cfg, tele = get_preset("nemotron_tiny"), Telemetry()
    params = glm_moe.init_served(jax.random.PRNGKey(0), cfg.model)
    server = Server(params, cfg, serve_mode="ragged", max_batch=2, pack_max_segments=4,
                    cache_size=0, warm_kinds=(), telemetry=tele)
    # a traced mixer notes once an executable; a program this process has
    # traced before notes nothing, so the test notes for itself
    kernel.note_ssd_core_path("pallas", "chunked")
    kernel.note_ssd_core_path("reference", "tiles_do_not_fit", ("test-shape",))
    kernel.note_ssd_core_path("reference", "tiles_do_not_fit", ("test-shape",))
    total = kernel.SSD_CORE_PATH_TOTAL
    assert server.stats()["ssd_core_path"] == {
        f"{path}/{reason}": n for (path, reason), n in sorted(total.items())}
    mirrored = lambda **labels: tele.metrics.counter(  # noqa: E731
        "ssd_core_kernel_path_total", **labels).value
    assert mirrored(path="pallas", reason="chunked") == 1
    assert mirrored(path="reference", reason="tiles_do_not_fit") == 2
    server.drain(timeout=10)
    kernel.note_ssd_core_path("pallas", "chunked")      # a closed server hears nothing
    assert mirrored(path="pallas", reason="chunked") == 1
