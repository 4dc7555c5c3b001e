"""Inference on a pretrained trunk: embeddings, GO prediction, residue filling.

The reference repo's end goal — per the ProteinBERT paper it replicates
(reference README.md:9) — is a pretrained encoder whose representations
feed downstream protein tasks, but it ships no inference path at all (the
README defers even the pretrained model to "Soon(TM)", reference
README.md:5-6; the only forward passes live inside the training loop,
reference utils.py:291). This module supplies that missing surface,
TPU-style: one jitted batched forward reused across every entry point,
static shapes (pad to the config seq_len, fixed batch), host code doing
only string work.

Entry points:
- `load_trunk`       — restore pretrained params from an orbax run dir.
- `embed`            — (N, G) global + length-masked mean (N, C) local
                       representations (the fine-tune features of
                       models/finetune.py, exposed for external use).
- `predict_go`       — sigmoid GO-annotation probabilities / top-k.
- `predict_residues` — per-position amino-acid distributions; fills
                       '?'-masked positions with the argmax residue.

Annotations default to the all-zero vector: the corruption pipeline
explicitly trains this "no annotations known" input via its p=0.5
hide-all branch (reference data_processing.py:127-128, kept as a feature
— SURVEY ledger #5), so it is the principled query input for a sequence
whose GO terms are unknown.
"""

from __future__ import annotations

import logging
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from proteinbert_tpu.configs import DecoderConfig, ModelConfig, PretrainConfig
from proteinbert_tpu.data.vocab import EOS_ID, PAD_ID, SOS_ID, UNK_ID, get_vocab
from proteinbert_tpu.models import glm_moe, proteinbert
from proteinbert_tpu.obs import tracing

logger = logging.getLogger(__name__)

MASK_CHAR = "?"  # maps to <unk>: the "residue unknown, predict it" input

# Process-wide count of sequences whose tail was truncated to fit the
# model window (the serving layer additionally counts its own
# serve_truncated_total metric). Mutable one-slot list so callers can
# read a stable reference.
TRUNCATED_TOTAL = [0]


class SequenceTooLongError(ValueError):
    """A sequence exceeds the model window (seq_len - 2 residues) and the
    caller asked for rejection instead of truncate-and-count
    (`on_overflow="error"`, or the serving layer's `on_long="reject"`)."""


def load_state(checkpoint_dir: str, cfg: PretrainConfig):
    """Restore the full TrainState (and step) from a pretrain run dir.

    `cfg` must describe the pretrain run (preset + overrides) so the
    restore template matches the saved pytree — same contract as the
    finetune CLI's --pretrained flag (cli/main.py).
    """
    from proteinbert_tpu.train import Checkpointer, create_train_state

    tracing.backend()
    with tracing.startup_span("startup.init_state"):
        template = create_train_state(jax.random.PRNGKey(cfg.train.seed), cfg)
    ck = Checkpointer(checkpoint_dir, async_save=False)
    try:
        with tracing.startup_span("startup.restore"):
            state, _ = ck.restore(template)
    finally:
        ck.close()
    if state is None:
        raise FileNotFoundError(f"no checkpoint found in {checkpoint_dir}")
    return state, int(state.step)


def load_trunk(checkpoint_dir: str, cfg: PretrainConfig):
    """Restore pretrained params (and step) — load_state for callers that
    only need the model weights."""
    state, step = load_state(checkpoint_dir, cfg)
    return state.params, step


@partial(jax.jit, static_argnames=("cfg", "per_residue"))
def _encode_batch(params, tokens, annotations, cfg: ModelConfig,
                  per_residue: bool = False):
    local, global_ = proteinbert.encode(params, tokens, annotations, cfg)
    mask = (tokens != PAD_ID).astype(jnp.float32)[:, :, None]
    local = local.astype(jnp.float32)
    out = {
        "local_mean": (local * mask).sum(1) / jnp.maximum(mask.sum(1), 1.0),
        "global": global_.astype(jnp.float32),
    }
    if per_residue:  # only ship the big (B, L, C) track when asked
        out["local"] = local
    return out


def _segment_real_mask(tokens, segment_ids, num_segments: int):
    """(B, S, L) bool: True where position l belongs to segment s AND
    holds a real (non-<pad>) token. A ragged serving span is bucket-
    quantized (serve/dispatch.RaggedDispatcher), so its tail holds
    <pad> tokens that must be excluded from pooling/attention exactly
    as the bucketed path's pad_mask excludes them."""
    seg = (segment_ids[:, None, :]
           == jnp.arange(1, num_segments + 1,
                         dtype=segment_ids.dtype)[None, :, None])
    return seg & (tokens != PAD_ID)[:, None, :]


@partial(jax.jit, static_argnames="cfg")
def _packed_encode_batch(params, tokens, segment_ids, annotations,
                         cfg: ModelConfig):
    """The ragged serving form of `_encode_batch`: one fixed-shape
    (rows, seq_len) packed batch of up to S segments per row →
    {"local_mean": (B, S, C), "global": (B, S, G)} float32 per-SEGMENT
    representations. Per-segment math mirrors the bucketed entry
    row-for-row (mask-weighted mean over real positions), so a span's
    outputs match the bucketed dispatcher's within jitted tolerance
    (docs/serving.md, ragged batching). A forward-only entry (as its
    `predict_go` / `predict_residues` siblings below): on a TPU and
    at C <= 512 the local track runs the segment-aware fused Pallas
    kernel on every shape its guard has a plan for
    (kernels/fused_block.packed_local_track_forward, ISSUE 42), with or
    without cfg.use_pallas — the packed executables this builds are
    fast-path executables there, counted in
    fused_kernel_path_total{path=pallas,reason=packed}."""
    pad_mask = tokens != PAD_ID
    with jax.named_scope("encode"):
        local, global_ = proteinbert.encode(params, tokens, annotations, cfg,
                                            pad_mask=pad_mask,
                                            segment_ids=segment_ids,
                                            forward_only=True)
    with jax.named_scope("pool"):
        m = _segment_real_mask(tokens, segment_ids,
                               annotations.shape[1]).astype(jnp.float32)
        local = local.astype(jnp.float32)
        local_mean = (jnp.einsum("bsl,blc->bsc", m, local)
                      / jnp.maximum(m.sum(-1)[..., None], 1.0))
        return {"local_mean": local_mean,
                "global": global_.astype(jnp.float32)}


@partial(jax.jit, static_argnames="cfg")
def _packed_decoder_embed_batch(params, tokens, segment_ids, annotations,
                                cfg: DecoderConfig):
    """`_packed_encode_batch` of the causal decoder (models/glm_moe.py,
    the hybrid stack): one (rows, seq_len) packed batch of token
    documents -> {"global": (B, S, D) the final-norm hidden state at
    each document's last token, "local_mean": (B, S, D) its mean over
    the document, "routing": the batch's expert counters}. Same
    signature as the ProteinBERT entry so the dispatcher calls either;
    `annotations` is (B, S, 0) and gives S. A negative token id marks a
    position of a span past its document's end."""
    return glm_moe.served_embed(params, tokens, segment_ids,
                                annotations.shape[1], cfg)


@partial(jax.jit, static_argnames="cfg")
def _packed_go_probs_batch(params, tokens, segment_ids, annotations,
                           cfg: ModelConfig):
    """(B, S, A) sigmoid GO probabilities per packed segment."""
    _, global_logits = proteinbert.apply(
        params, tokens, annotations, cfg, pad_mask=(tokens != PAD_ID),
        segment_ids=segment_ids, forward_only=True)
    return jax.nn.sigmoid(global_logits)


@partial(jax.jit, static_argnames="cfg")
def _packed_residue_probs_batch(params, tokens, segment_ids, annotations,
                                cfg: ModelConfig):
    """(B, L, V) per-position softmax over a packed batch; callers
    slice each segment's span back out (the span's rows line up with
    the bucketed entry's (bucket_len, V) output)."""
    local_logits, _ = proteinbert.apply(
        params, tokens, annotations, cfg, pad_mask=(tokens != PAD_ID),
        segment_ids=segment_ids, forward_only=True)
    return jax.nn.softmax(local_logits, -1)


@partial(jax.jit, static_argnames="cfg")
def _go_probs_batch(params, tokens, annotations, cfg: ModelConfig):
    _, global_logits = proteinbert.apply(params, tokens, annotations, cfg)
    return jax.nn.sigmoid(global_logits)


@partial(jax.jit, static_argnames="cfg")
def _residue_probs_batch(params, tokens, annotations, cfg: ModelConfig):
    local_logits, _ = proteinbert.apply(params, tokens, annotations, cfg)
    return jax.nn.softmax(local_logits, -1)


def _tokenize_masked(seqs: Sequence[str], seq_len: int,
                     on_overflow: str = "warn") -> np.ndarray:
    """Tokenize with MASK_CHAR → <unk> (no random crop: inference is
    deterministic).

    Over-length handling is never silent (the seed behavior clipped
    quietly): sequences longer than seq_len-2 residues are either
    rejected with SequenceTooLongError (`on_overflow="error"`) or
    truncated AND counted in TRUNCATED_TOTAL, with one warning per call
    (`on_overflow="warn"`, the default; "count" skips the log line for
    callers that surface the count themselves — the serving layer does,
    via its own serve_truncated_total metric in Server.submit).
    """
    if on_overflow not in ("warn", "error", "count"):
        raise ValueError(f"on_overflow must be 'warn', 'error', or "
                         f"'count', got {on_overflow!r}")
    window = seq_len - 2
    too_long = [i for i, s in enumerate(seqs) if len(s) > window]
    if too_long:
        if on_overflow == "error":
            raise SequenceTooLongError(
                f"{len(too_long)} sequence(s) exceed the model window of "
                f"{window} residues (first: index {too_long[0]}, length "
                f"{len(seqs[too_long[0]])}); raise data.seq_len, split "
                "the sequence, or allow truncation")
        TRUNCATED_TOTAL[0] += len(too_long)
        if on_overflow == "warn":
            logger.warning(
                "truncating %d sequence(s) longer than the %d-residue "
                "model window to their first %d residues (counted in "
                "inference.TRUNCATED_TOTAL)", len(too_long), window,
                window)
    vocab = get_vocab()
    out = np.full((len(seqs), seq_len), PAD_ID, dtype=np.int32)
    for i, seq in enumerate(seqs):
        seq = seq[:window]
        ids = vocab.encode(seq)  # MASK_CHAR is outside the alphabet → <unk>
        out[i, 0] = SOS_ID
        out[i, 1 : 1 + len(ids)] = ids
        out[i, 1 + len(ids)] = EOS_ID
    return out


def check_annotations(annotations: Optional[np.ndarray], n: int,
                      cfg: PretrainConfig) -> np.ndarray:
    """Default-and-validate a query annotation matrix to (n, A) float32
    (None → the trained "no annotations known" all-zero input). Shared
    by the offline batch path, the bucketed path, and the serving
    layer's submit-time validation."""
    if annotations is None:
        annotations = np.zeros((n, cfg.model.num_annotations), np.float32)
    annotations = np.asarray(annotations, np.float32)
    if annotations.shape != (n, cfg.model.num_annotations):
        raise ValueError(
            f"annotations shape {annotations.shape} != "
            f"({n}, {cfg.model.num_annotations})"
        )
    return annotations


def fill_masked_residues(seq: str, probs: np.ndarray, window: int) -> str:
    """Fill each MASK_CHAR in seq[:window] with the argmax amino acid
    from `probs` — one (L, V) softmax row, position 0 = <sos> — never
    choosing pad/sos/eos/unk; the un-modeled tail beyond `window`
    passes through unchanged. Shared by offline `predict_residues` and
    the serving finalizer (serve/server.py) so the fill rule cannot
    drift between the two surfaces."""
    aa = np.asarray(probs).copy()
    aa[:, : UNK_ID + 1] = 0.0  # only amino-acid tokens are valid fills
    vocab = get_vocab()
    chars = list(seq[:window])
    for pos, ch in enumerate(chars):
        if ch == MASK_CHAR:
            chars[pos] = vocab.itos[int(aa[pos + 1].argmax())]
    return "".join(chars) + seq[window:]


def _batched(
    params, cfg: PretrainConfig, tokens: np.ndarray,
    annotations: Optional[np.ndarray], batch_size: int, fn,
) -> List:
    """Run `fn(params, tokens, annotations, model_cfg)` over fixed-size
    batches (last one padded so every call hits the same compiled shape);
    returns the per-batch outputs trimmed back to the true row count.
    `fn` must return only what the caller keeps — every leaf is copied to
    host and retained across the whole run."""
    n = tokens.shape[0]
    if n == 0:
        raise ValueError("no sequences given")
    annotations = check_annotations(annotations, n, cfg)
    outs = []
    for start in range(0, n, batch_size):
        tb = tokens[start : start + batch_size]
        ab = annotations[start : start + batch_size]
        rows = tb.shape[0]
        if rows < batch_size:  # pad the tail batch to the compiled shape
            tb = np.pad(tb, ((0, batch_size - rows), (0, 0)))
            ab = np.pad(ab, ((0, batch_size - rows), (0, 0)))
        res = fn(params, jnp.asarray(tb), jnp.asarray(ab), cfg.model)
        outs.append(jax.tree.map(lambda a: np.asarray(a)[:rows], res))
    return outs


def embed_batches(
    params, cfg: PretrainConfig, seqs: Sequence[str],
    annotations: Optional[np.ndarray] = None, batch_size: int = 32,
    per_residue: bool = False, on_overflow: str = "warn",
):
    """Yield per-batch representation dicts — the streaming form of
    `embed` (host memory stays O(batch), so million-sequence FASTA runs
    can write each batch straight to disk; the embed CLI does exactly
    that for HDF5 output).

    Each yielded dict holds float32 "global" (b, G) and "local_mean"
    (b, C) — plus "local" (b, seq_len, C) and int32 "tokens"
    (b, seq_len) with `per_residue=True` — where b ≤ batch_size is the
    batch's true row count.
    """
    n = len(seqs)
    if n == 0:
        raise ValueError("no sequences given")
    for start in range(0, n, batch_size):
        # Tokenize per chunk — this is what keeps host memory O(batch).
        chunk_tokens = _tokenize_masked(seqs[start : start + batch_size],
                                        cfg.data.seq_len, on_overflow)
        chunk_ann = (annotations[start : start + batch_size]
                     if annotations is not None else None)
        out = _batched(
            params, cfg, chunk_tokens, chunk_ann, batch_size,
            partial(_encode_batch, per_residue=per_residue))[0]
        if per_residue:
            out["tokens"] = chunk_tokens
        yield out


def _bucketed_rows(params, cfg: PretrainConfig, kind: str,
                   tokens: np.ndarray, annotations: Optional[np.ndarray],
                   batch_size: int, buckets):
    """Route an offline batch job through the serving layer's bucket
    dispatcher (serve/dispatch.py): rows grouped by length bucket, each
    group run at its bucket length instead of the full seq_len, results
    reassembled in input order. Shares the jitted kernels with the
    unbucketed path, so with buckets=(seq_len,) the output is
    bit-identical to it (tests/test_serve.py proves this)."""
    from proteinbert_tpu.serve.dispatch import BucketDispatcher

    if tokens.shape[0] == 0:
        raise ValueError("no sequences given")

    dispatcher = BucketDispatcher(
        params, cfg, buckets=buckets, max_batch=batch_size,
        batch_classes=(batch_size,))
    return dispatcher.run_rows(kind, tokens, annotations, batch_size)


def embed(
    params, cfg: PretrainConfig, seqs: Sequence[str],
    annotations: Optional[np.ndarray] = None, batch_size: int = 32,
    per_residue: bool = False, bucketed: bool = False, buckets=None,
    on_overflow: str = "warn",
) -> Dict[str, np.ndarray]:
    """Trunk representations for downstream use.

    Returns {"global": (N, G), "local_mean": (N, C)} float32 — and, with
    `per_residue=True`, "local": (N, seq_len, C) plus "tokens":
    (N, seq_len) int32 so callers can mask pad positions themselves.
    Holds all N rows in memory; for large N use `embed_batches`.

    `bucketed=True` routes through the serving bucket dispatcher: rows
    run at their length bucket (`buckets` ascending, last == seq_len;
    default cfg.data.buckets, else the single full-length bucket)
    instead of all padding to seq_len — same numbers, fewer FLOPs for
    short sequences. Incompatible with `per_residue` (whose output is
    full-seq_len shaped by contract).
    """
    if bucketed:
        if per_residue:
            raise ValueError(
                "per_residue output is (N, seq_len, C) by contract; "
                "bucketed execution would change its shape — use "
                "bucketed=False for per-residue embeddings")
        n = len(seqs)
        if n == 0:
            raise ValueError("no sequences given")
        tokens = _tokenize_masked(seqs, cfg.data.seq_len, on_overflow)
        annotations = check_annotations(annotations, n, cfg)
        return _bucketed_rows(params, cfg, "embed", tokens, annotations,
                              batch_size, buckets)
    outs = list(embed_batches(params, cfg, seqs, annotations, batch_size,
                              per_residue, on_overflow))
    return {k: np.concatenate([o[k] for o in outs]) for k in outs[0]}


def predict_go(
    params, cfg: PretrainConfig, seqs: Sequence[str],
    batch_size: int = 32, top_k: Optional[int] = None,
    bucketed: bool = False, buckets=None, on_overflow: str = "warn",
):
    """GO-annotation probabilities from sequence alone.

    Returns (N, A) sigmoid probabilities; with `top_k`, instead a list of
    N descending [(annotation_index, prob), ...] lists. The indices are
    rows of the HDF5 builder's `included_annotations` mapping
    (etl/h5_builder.py) — join against the GO-meta CSV for names.
    `bucketed=True` runs each row at its length bucket (see `embed`).
    """
    tokens = _tokenize_masked(seqs, cfg.data.seq_len, on_overflow)
    if bucketed:
        probs = _bucketed_rows(params, cfg, "predict_go", tokens, None,
                               batch_size, buckets)
    else:
        outs = _batched(params, cfg, tokens, None, batch_size,
                        _go_probs_batch)
        probs = np.concatenate(outs)
    if top_k is None:
        return probs
    k = min(top_k, probs.shape[1])
    idx = np.argsort(-probs, axis=1)[:, :k]
    return [
        [(int(j), float(p)) for j, p in zip(row, prob_row[row])]
        for row, prob_row in zip(idx, probs)
    ]


def predict_residues(
    params, cfg: PretrainConfig, seqs: Sequence[str], batch_size: int = 32,
    bucketed: bool = False, buckets=None, on_overflow: str = "warn",
) -> Tuple[List[str], np.ndarray]:
    """Per-position amino-acid prediction; '?' marks residues to fill.

    '?' positions enter the model as <unk> — the same "identity lost"
    condition the denoising pretraining's token randomization teaches the
    model to repair (reference data_processing.py:86-105). Returns
    (filled_seqs, probs (N, seq_len, V) softmax over the full vocab).

    Sequences longer than cfg.data.seq_len - 2 with a '?' in the
    truncated tail are rejected (the model never sees those positions,
    so "filling" them would silently return the mask unchanged).

    `bucketed=True` runs each row at its length bucket (see `embed`);
    probability rows beyond a row's bucket length come back zero-filled
    (those positions are pad by construction).
    """
    window = cfg.data.seq_len - 2
    for i, seq in enumerate(seqs):
        if MASK_CHAR in seq[window:]:
            raise ValueError(
                f"sequence {i} has a {MASK_CHAR!r} beyond position "
                f"{window} — outside the model's seq_len window; raise "
                "data.seq_len (--pretrained-set data.seq_len=...) or "
                "split the sequence")
    tokens = _tokenize_masked(seqs, cfg.data.seq_len, on_overflow)
    if bucketed:
        probs = _bucketed_rows(params, cfg, "predict_residues", tokens,
                               None, batch_size, buckets)
    else:
        outs = _batched(params, cfg, tokens, None, batch_size,
                        _residue_probs_batch)
        probs = np.concatenate(outs)
    filled = [fill_masked_residues(seq, probs[i], window)
              for i, seq in enumerate(seqs)]
    return filled, probs
