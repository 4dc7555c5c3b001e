"""Whole-tree arithmetic on the host, block by block over a few threads.

The decoder's training cell compares trees of 706.5 M float32 parameters
(2.83 GB a tree). A numpy expression over a whole leaf makes whole-leaf
temporaries (a float64 copy of the largest leaf is 0.8 GB) on one thread;
here a leaf is walked in blocks that fit a core's cache, through scratch
made once a thread, and the pieces of all leaves go over one small pool
(numpy releases the interpreter's lock inside its loops). A leaf's sum is
the sum of its pieces' float64 sums in the order of the pieces, whatever
thread took which: the same numbers run to run.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import numpy as np

BLOCK = 1 << 18         # elements a block: 1 MiB of float32, 2 MiB of float64
PIECE = 16 * BLOCK      # elements a task of the pool
THREADS = max(1, min(8, os.cpu_count() or 1))

_pool = None
_local = threading.local()


def scratch(dtype, slot: int = 0) -> np.ndarray:
    """This thread's own BLOCK elements of `dtype` (`slot`: a second one)."""
    key = (np.dtype(dtype).str, slot)
    held = _local.__dict__.setdefault("scratch", {})
    if key not in held:
        held[key] = np.empty(BLOCK, dtype)
    return held[key]


def flat(leaf, in_place: bool) -> np.ndarray:
    """A leaf as one dimension, in C order: a view of its memory where
    that is contiguous in C order, else a copy, which an update in place
    would be lost in: refused."""
    leaf = np.asarray(leaf)
    if in_place and not (leaf.flags.c_contiguous and leaf.flags.writeable):
        raise ValueError("an update in place needs a writable leaf, contiguous "
                         f"in C order; got strides {leaf.strides} for {leaf.shape}")
    return leaf.reshape(-1)


def over_pieces(fn, *trees, in_place: bool = False) -> list:
    """fn(leaf of each tree, flat..., lo, hi) over every piece of every
    leaf, on the pool. -> for each leaf the list of its pieces' results,
    in the order of the pieces. `in_place`: fn writes into the leaves."""
    global _pool
    import jax

    columns = [[flat(x, in_place) for x in jax.tree.leaves(t)] for t in trees]
    tasks = [(i, lo, min(lo + PIECE, first.size))
             for i, first in enumerate(columns[0])
             for lo in range(0, max(first.size, 1), PIECE)]
    if _pool is None:
        _pool = ThreadPoolExecutor(THREADS, thread_name_prefix="blocked")
    done = _pool.map(lambda t: fn(*(col[t[0]] for col in columns), t[1], t[2]), tasks)
    out = [[] for _ in columns[0]]
    for (i, _, _), value in zip(tasks, done):
        out[i].append(value)
    return out


def _sq_sum(*leaves_lo_hi, diff_dtype):
    """Sum of x ** 2 or, given (x, y), of (x - y) ** 2 over [lo, hi): the
    difference taken in `diff_dtype`, squares and the sum in float64."""
    *leaves, lo, hi = leaves_lo_hi
    total, wide = 0.0, scratch(np.float64)
    for at in range(lo, hi, BLOCK):
        b = wide[:min(BLOCK, hi - at)]
        if len(leaves) == 1:
            np.copyto(b, leaves[0][at:at + b.size])
        else:
            d = scratch(diff_dtype, 1)[:b.size]
            np.subtract(leaves[0][at:at + b.size], leaves[1][at:at + b.size],
                        out=d, dtype=diff_dtype, casting="same_kind")
            np.copyto(b, d)
        np.multiply(b, b, out=b)
        total += float(b.sum())
    return total


def sq_sums(tree, minus=None, diff_dtype=np.float64) -> list:
    """For every leaf the float64 sum of its squares or, with `minus`, of
    the squares of its difference from that tree's leaf."""
    trees = (tree,) if minus is None else (tree, minus)
    parts = over_pieces(partial(_sq_sum, diff_dtype=diff_dtype), *trees)
    return [float(sum(p)) for p in parts]


def norms(tree, minus=None, diff_dtype=np.float64):
    """`tree`'s shape with every leaf's norm (of its difference from
    `minus`'s leaf, where given) as a float."""
    import jax

    return jax.tree.unflatten(jax.tree.structure(tree), [
        float(np.sqrt(s)) for s in sq_sums(tree, minus, diff_dtype)])
