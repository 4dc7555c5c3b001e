"""The decoder's segment-bounded flash kernels (kernels/segment_flash.py)
in Pallas's interpreter on the CPU, against `causal_segment_attention`:
outputs and the gradients of q, k and v over rows of one document, many
short ones, a pad tail, an all-pad row and the packer's own rows; the
tile bounds against a brute-force count; the contract they rest on; the
step's `attn_tiles_walked_share`."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proteinbert_tpu.configs import get_preset
from proteinbert_tpu.data.dataset import TokenDocumentDataset
from proteinbert_tpu.data.packing import make_packed_iterator
from proteinbert_tpu.kernels.segment_flash import segment_flash_attention
from proteinbert_tpu.models import glm_moe
from proteinbert_tpu.ops.attention import (
    causal_segment_attention, segment_tile_bounds, tiles_walked_share,
)

TILE, HEADS, HEAD = 128, 2, 128


def runs(*spans):
    """One row of segment ids from (id, length) spans."""
    return np.concatenate([np.full(n, s, np.int32) for s, n in spans])


def packed_rows(rows, seq_len, lengths, max_segments=8, seed=0):
    """`rows` rows as the program's own packer lays documents of these
    lengths out (first fit, the PackPlanner)."""
    rng = np.random.default_rng(seed)
    docs = [rng.integers(0, 100, n).astype(np.int32) for n in lengths]
    feed = make_packed_iterator(TokenDocumentDataset(docs, seq_len), rows, seed=0,
                                shuffle=False, max_segments=max_segments)
    return next(feed)["segment_ids"]


ROWS = {
    "one_document": np.stack([runs((1, 512)), runs((7, 512))]),
    # boundaries on tile edges (128, 384) and off them (100, 300, 301)
    "short_documents": np.stack([
        runs((1, 100), (2, 28), (3, 172), (4, 1), (5, 83), (6, 128)),
        runs((1, 128), (2, 256), (3, 128))]),
    "longer_rows": np.stack([
        runs((1, 700), (2, 20), (3, 304)), runs((1, 130), (2, 894))]),
    "pad_tail": np.stack([runs((1, 200), (2, 250), (0, 62)),
                          runs((1, 384), (0, 128))]),
    "all_pad_row": np.stack([runs((0, 512)), runs((1, 300), (2, 212))]),
    "from_the_packer": packed_rows(
        2, 1024, [300, 500, 150, 90, 260, 410, 64, 700, 33, 128, 256, 130]),
}


@functools.lru_cache(maxsize=None)
def _both(rows, seed=0):
    """(out, dq, dk, dv) of the kernels and of plain attention on one of
    ROWS: one pass for the four cases that read it."""
    seg = jnp.asarray(ROWS[rows], jnp.int32)
    B, L = seg.shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, g = (jax.random.normal(kk, (B, L, HEADS, HEAD), jnp.float32)
                  for kk in keys)
    scale = HEAD ** -0.5
    lo, hi = segment_tile_bounds(seg, TILE)
    heads_first = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731

    def kernel(q, k, v):
        return heads_first(segment_flash_attention(
            heads_first(q), heads_first(k), heads_first(v), seg, lo, hi,
            scale, TILE, True))

    def plain(q, k, v):
        return causal_segment_attention(q, k, v, seg, scale, TILE)

    out_k, vjp_k = jax.vjp(kernel, q, k, v)
    out_p, vjp_p = jax.vjp(plain, q, k, v)
    return (out_k, *vjp_k(g)), (out_p, *vjp_p(g))


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("rows", sorted(ROWS))
def test_kernels_give_plain_attention(rows, what):
    got, want = (side[["out", "dq", "dk", "dv"].index(what)]
                 for side in _both(rows))
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_kernels_take_bfloat16_and_two_head_sizes():
    """The configuration's precision: bfloat16 operands, float32 softmax;
    the keys' head (256) need not be the values' (128)."""
    seg = jnp.asarray(ROWS["short_documents"])
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k = (jax.random.normal(kk, (2, 1, 512, 256), jnp.float32) for kk in keys[:2])
    v = jax.random.normal(keys[2], (2, 1, 512, 128), jnp.float32)
    lo, hi = segment_tile_bounds(seg, TILE)
    low = [a.astype(jnp.bfloat16) for a in (q, k, v)]
    out = segment_flash_attention(*low, seg, lo, hi, 1 / 16, TILE, True)
    assert out.dtype == jnp.bfloat16 and out.shape == v.shape
    want = causal_segment_attention(
        *(a.astype(jnp.float32).transpose(0, 2, 1, 3) for a in low), seg, 1 / 16, TILE)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want.transpose(0, 2, 1, 3)), atol=0.03)


def _tiles_that_meet(row, block):
    """Brute force: tiles (query i, key j) holding a causal pair in one
    segment."""
    L = len(row)
    meet = (row[:, None] == row[None, :]) & np.tri(L, dtype=bool)
    n = -(-L // block)
    pad = n * block - L
    meet = np.pad(meet, ((0, pad), (0, pad)))
    return meet.reshape(n, block, n, block).any(axis=(1, 3))


@pytest.mark.parametrize("block", [128, 64, 48])
@pytest.mark.parametrize("rows", sorted(ROWS))
def test_bounds_cover_exactly_the_tiles_that_meet(rows, block):
    seg = ROWS[rows]
    lo, hi = (np.asarray(a) for a in segment_tile_bounds(jnp.asarray(seg), block))
    n = lo.shape[1]
    assert lo.shape == hi.shape == (len(seg), -(-seg.shape[1] // block))
    for b, row in enumerate(seg):
        meet = _tiles_that_meet(row, block)
        i, j = np.arange(n)[:, None], np.arange(n)[None, :]
        # Every tile that holds a pair is walked, by rows and by columns ...
        assert not (meet & ~((j >= lo[b][:, None]) & (j <= i))).any()
        assert not (meet & ~((i >= j) & (i <= hi[b][None, :]))).any()
        # ... and each walk starts and ends on a tile that holds one.
        assert meet[np.arange(n), lo[b]].all() and meet[hi[b], np.arange(n)].all()


def test_the_packer_keeps_each_segment_in_one_run():
    """The contract the bounds rest on (data/packing.py): an id never
    comes back after another id followed it; padding is the last run."""
    seg = packed_rows(8, 512, np.random.default_rng(1).integers(10, 300, 80))
    assert (seg > 0).any() and (seg == 0).any()
    for row in seg:
        starts = np.flatnonzero(np.diff(row, prepend=-1))
        ids = row[starts]
        assert len(set(ids)) == len(ids), ids
        assert (ids == 0).sum() <= 1 and (0 not in ids or ids[-1] == 0)


def test_a_row_that_breaks_the_contract_loses_the_pairs_across_the_gap():
    """Outside the contract, documented: id 1 comes back after id 2, the
    element mask would let its second run see its first, the bounds are
    those of the RUNS and leave that tile out."""
    row = runs((1, 128), (2, 128), (1, 128))
    lo, hi = (np.asarray(a)[0] for a in segment_tile_bounds(jnp.asarray(row[None]), 128))
    assert _tiles_that_meet(row, 128)[2, 0]
    assert lo.tolist() == [0, 1, 2] and hi.tolist() == [0, 1, 2]


def _numpy_share(seg, block):
    n = seg.shape[1] // block
    walked = sum(int(np.tril(_tiles_that_meet(row, block)).sum()) for row in seg)
    return walked / (len(seg) * n * (n + 1) // 2)


def test_walked_share_is_one_for_one_document_rows_and_counts_a_packed_batch():
    assert float(tiles_walked_share(jnp.asarray(ROWS["one_document"]), TILE)) == 1.0
    for name in ("short_documents", "pad_tail", "from_the_packer"):
        seg = ROWS[name]
        # contiguous runs: the walk between the bounds has no hole
        assert float(tiles_walked_share(jnp.asarray(seg), TILE)) == pytest.approx(
            _numpy_share(seg, TILE))


def test_walked_share_of_the_cells_own_mix_at_its_own_tile():
    """Sixty batches of the decoder cell's feed (2 x 8,192, documents of
    median 1,200 tokens, first fit) at the tile of 512: ISSUE 31 reckoned
    0.584 over these 120 rows. A single batch reads 0.3 to 1.0."""
    from benchmark import run as bench_run
    from benchmark.drivers import lm_pretrain

    run = bench_run.tool_run("pretrain-glm47flash-packed8k", 2_900_000_011, 10)
    feed = lm_pretrain.make_feed(run, lm_pretrain.cell_config(run.workload, run.config))
    seg = np.concatenate([next(feed)["segment_ids"] for _ in range(60)])
    share = float(tiles_walked_share(jnp.asarray(seg), 512))
    assert share == pytest.approx(_numpy_share(seg, 512))
    assert 0.55 <= share <= 0.62, share


def test_the_step_reports_the_share_the_kernels_walk():
    cfg = get_preset("glm_tiny")
    model = dataclasses.replace(cfg.model, attention_block=16)
    seg = jnp.asarray(np.stack([runs((1, 20), (2, 30), (3, 10), (0, 4)),
                                runs((1, 64))]))
    out = {"loss": jnp.zeros(()), "main_loss": jnp.zeros(()), "main_acc": jnp.zeros(())}
    counters = {"held_counts": jnp.ones((2, 8)), "dropped": jnp.zeros(()),
                "block_rows": jnp.full((), 128.0)}
    got = glm_moe.step_metrics(out, counters, seg, model)
    # tiles of 16: row 0 walks 1 + 2 + 2 + 3 (id 2 starts in tile 1 and opens
    # tiles 2 and 3), row 1 all 10
    assert float(got["attn_tiles_walked_share"]) == pytest.approx((8 + 10) / 20)
    assert got["attn_tiles_walked_share"].dtype == jnp.float32
