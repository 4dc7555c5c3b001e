"""The one traffic generator: a mix is a data file, this reads it.

A mix (`benchmark/traffic/<name>.json`) fixes a set of sequence lengths and,
for serving, a set of gaps between arrivals. Every seed gets the SAME sets
in another order, block by block, so that the work of a run does not
depend on the seed: lengths are the quantiles of the mix's distribution
(log-normal, cropped), gaps the quantiles of the exponential distribution
at the mix's rate. Residues are uniform over the twenty standard amino
acids; annotations are sparse 0/1 rows.

Keys of a mix:
  lengths      {"median", "sigma", "min", "max"}: log-normal, in residues
  block        how many sequences make one block (a training batch, or
               one cycle of arrivals)
  arrivals     null (training), or {"rate_per_s"}: open loop, Poisson
  annotations  {"positives", "share_without"}: mean number of 1s in a
               protein's annotation row, and the share of proteins with none
The lengths follow `bench.py:468-473` of this repository (UniRef-like:
median 350, sigma 0.6), which is read by nothing here.
"""

from __future__ import annotations

import json
import math
import os
from statistics import NormalDist

import numpy as np

AMINO = np.frombuffer(b"ACDEFGHIKLMNPQRSTVWY", np.uint8)
_HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(_HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def block_lengths(mix: dict) -> np.ndarray:
    """The block's fixed multiset of lengths: quantiles, cropped."""
    spec, n = mix["lengths"], int(mix["block"])
    nd = NormalDist()
    q = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    lengths = np.rint(spec["median"] * np.exp(spec["sigma"] * q)).astype(np.int64)
    return np.clip(lengths, spec["min"], spec["max"])


def block_gaps(mix: dict) -> np.ndarray:
    """The block's fixed multiset of gaps (seconds): exponential
    quantiles, rescaled so a block lasts exactly block / rate."""
    n, rate = int(mix["block"]), float(mix["arrivals"]["rate_per_s"])
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    return gaps * (n / rate) / gaps.sum()


def sequences(mix: dict, n_blocks: int, seed: int, stream: int = 1):
    """n_blocks x block sequences as strings, and their lengths: each
    block the same lengths in an order of its own, residues from the seed
    (`stream` tells apart draws that must differ, as warm-up and window)."""
    rng = np.random.default_rng([seed, stream])
    base = block_lengths(mix)
    lengths = np.concatenate([rng.permutation(base) for _ in range(n_blocks)])
    flat = AMINO[rng.integers(0, len(AMINO), int(lengths.sum()))].tobytes()
    ends = np.cumsum(lengths)
    seqs = [flat[e - n:e].decode("ascii") for e, n in zip(ends, lengths)]
    return seqs, lengths


def annotation_rows(mix: dict, n: int, width: int, seed: int) -> np.ndarray:
    """(n, width) float32 0/1 rows, sparse, some all zero."""
    spec = mix["annotations"]
    rng = np.random.default_rng([seed, 2])
    rows = np.zeros((n, width), np.float32)
    counts = rng.poisson(spec["positives"], n) + 1
    counts[rng.random(n) < spec["share_without"]] = 0
    for i, c in enumerate(counts):
        rows[i, rng.integers(0, width, c)] = 1.0
    return rows


def due_times(mix: dict, n_blocks: int, seed: int) -> np.ndarray:
    """When each request is due, in seconds from the window's start."""
    rng = np.random.default_rng([seed, 3])
    base = block_gaps(mix)
    gaps = np.concatenate([rng.permutation(base) for _ in range(n_blocks)])
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def blocks_for(mix: dict, seconds: float) -> int:
    """Blocks of arrivals that cover a window of this length."""
    per_block = mix["block"] / float(mix["arrivals"]["rate_per_s"])
    return max(1, math.ceil(seconds / per_block))
