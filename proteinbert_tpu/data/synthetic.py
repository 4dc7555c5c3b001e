"""Synthetic UniRef-like data (reference C15 fixture,
dummy_tests.py:23-38 parity): random AA strings + sparse annotations.

Used by the test suite, the `smoke` CLI command, and `pretrain` when no
--data file is given — the same role the reference's
`create_random_samples` plays for its smoke driver.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np


def make_random_proteins(
    n: int,
    rng: np.random.Generator,
    num_annotations: int = 512,
    max_len: int = 250,
    density: float = 0.005,
) -> Tuple[List[str], np.ndarray]:
    """n random AA strings of length 0..max_len and (n, A) sparse 0/1
    annotation rows (~`density` positive rate)."""
    from proteinbert_tpu.data.vocab import ALPHABET

    seqs = []
    for _ in range(n):
        L = int(rng.integers(0, max_len + 1))
        seqs.append("".join(rng.choice(list(ALPHABET), size=L)))
    ann = (rng.random((n, num_annotations)) < density).astype(np.float32)
    return seqs, ann


# Hydrophobic residues, used to derive LEARNABLE synthetic labels below.
_HYDROPHOBIC = set("AVILMFWC")

# Two-state residue preferences for the STRUCTURED generator: state 0 is
# hydrophobic-core-like, state 1 polar/loop-like — a miniature of the
# secondary-structure signal ProteinBERT's real transfer tasks carry.
_STATE_RESIDUES = ("AVILMFWC", "DEKRHNQSTGP")


def make_structured_proteins(
    n: int,
    rng: np.random.Generator,
    num_annotations: int = 512,
    min_len: int = 40,
    max_len: int = 250,
    switch_prob: float = 0.05,
    fidelity: float = 0.70,
):
    """Synthetic proteins with LATENT STRUCTURE, for transfer experiments.

    Each sequence is emitted by a two-state Markov chain (persistence
    1 - `switch_prob`); a residue is drawn from its state's preferred
    set with prob `fidelity`, else uniformly. The defaults make a
    single residue a WEAK predictor of its own state (~75% decodable)
    while the surrounding segment is a strong one — so a frozen-trunk
    linear probe separates context-integrating features (what denoising
    pretraining learns) from random features (which can only surface
    per-token identity). Annotations
    are 3-mer occurrence bits (annotation j fires iff the j-th of
    `num_annotations` fixed 3-mers occurs), giving the global track a
    content-derived target. A denoising-pretrained trunk therefore
    learns exactly the local statistics that the downstream "predict
    the hidden state" task (see examples/transfer_experiment.py) needs —
    the synthetic miniature of the paper's secondary-structure
    transfer, which the reference only sketched in commented-out code
    (reference utils.py:348-493).

    Returns (seqs, annotations (n, A) float32, states: list of (L,)
    int8 arrays — the per-residue hidden state, usable as few-shot
    labels).
    """
    from proteinbert_tpu.data.vocab import ALPHABET

    alphabet = list(ALPHABET)
    # Fixed motif list drawn from the SAME rng: deterministic for a
    # seeded caller, shared between corpus and task splits.
    motifs = ["".join(rng.choice(alphabet, size=3))
              for _ in range(num_annotations)]
    motif_cols: dict = {}
    for j, m in enumerate(motifs):  # random 3-mers can collide
        motif_cols.setdefault(m, []).append(j)
    pools = [np.frombuffer(s.encode(), np.uint8) for s in _STATE_RESIDUES]
    alpha_arr = np.frombuffer("".join(alphabet).encode(), np.uint8)
    seqs = []
    states_out = []
    ann = np.zeros((n, num_annotations), np.float32)
    for i in range(n):
        L = int(rng.integers(min_len, max_len + 1))
        flips = rng.random(L) < switch_prob
        states = (np.cumsum(flips) + rng.integers(0, 2)) % 2
        faithful = rng.random(L) < fidelity
        # Vectorized residue draw (a per-char Python loop costs minutes
        # at the 16k-row rehearsal-corpus scale on a 1-core host).
        draw = np.where(states == 0,
                        pools[0][rng.integers(0, len(pools[0]), L)],
                        pools[1][rng.integers(0, len(pools[1]), L)])
        chars = np.where(faithful, draw,
                         alpha_arr[rng.integers(0, len(alpha_arr), L)])
        seq = chars.astype(np.uint8).tobytes().decode("ascii")
        seqs.append(seq)
        states_out.append(states.astype(np.int8))
        # O(L) motif membership via the sequence's own 3-mer set,
        # instead of O(L * num_annotations) substring scans.
        for m in {seq[k:k + 3] for k in range(L - 2)}:
            for j in motif_cols.get(m, ()):
                ann[i, j] = 1.0
    return seqs, ann, states_out


def make_task_batches(
    n: int,
    rng: np.random.Generator,
    kind: str,
    num_outputs: int,
    seq_len: int,
    batch_size: int,
):
    """Synthetic supervised batches whose labels are deterministic
    functions of the sequence — so a working fine-tune loop must drive the
    loss down (the role the reference's random-label smoke data cannot
    play). Labels:
      token_classification    — residue's token id mod num_outputs;
      sequence_classification — dominant-class of the per-residue labels;
      sequence_regression     — hydrophobic fraction of the sequence.
    Returns a list of {"tokens", "labels"} numpy batches.
    """
    from proteinbert_tpu.data.vocab import ALPHABET, PAD_ID
    from proteinbert_tpu.data.transforms import tokenize_batch

    seqs = []
    for _ in range(n):
        L = int(rng.integers(seq_len // 4, seq_len - 2))
        seqs.append("".join(rng.choice(list(ALPHABET), size=L)))
    tokens = tokenize_batch(seqs, seq_len)

    if kind == "token_classification":
        labels = (tokens % num_outputs).astype(np.int32)
    elif kind == "sequence_classification":
        per_tok = tokens % num_outputs
        labels = np.zeros(n, np.int32)
        for i in range(n):
            real = tokens[i] != PAD_ID
            labels[i] = np.bincount(per_tok[i][real],
                                    minlength=num_outputs).argmax()
    elif kind == "sequence_regression":
        labels = np.array(
            [sum(c in _HYDROPHOBIC for c in s) / max(len(s), 1) for s in seqs],
            np.float32,
        )
    else:
        raise ValueError(f"unknown task kind {kind!r}")

    from proteinbert_tpu.data.finetune_data import batch_task_data

    return batch_task_data(tokens, labels, batch_size)


def make_random_documents(n: int, rng: np.random.Generator, vocab_size: int,
                          median: float = 1200.0, sigma: float = 1.0,
                          min_len: int = 32, max_len: int = 8192):
    """`n` documents of uniform random token ids with log-normal lengths:
    what `pbt pretrain` trains the causal decoder on when no data is given."""
    lengths = np.clip(np.rint(median * np.exp(sigma * rng.standard_normal(n))),
                      min_len, max_len).astype(np.int64)
    return [rng.integers(0, vocab_size, k).astype(np.int32) for k in lengths]
