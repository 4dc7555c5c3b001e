"""Split-apply execution: one shared trunk pass, many cheap head tails.

The multi-tenant serving shape (ISSUE 8): a micro-batch of requests for
DIFFERENT finetuned tasks runs the expensive trunk forward ONCE —
`trunk_batch` is one jitted executable per (batch_class, bucket_len)
shape, independent of which heads ride the batch — and each distinct
head then runs as a cheap jitted matmul tail over the full batch
(`head_batch`), with each request keeping its own head's row. Head
parameters are traced arguments, so every head of the same structure
(linear vs one-hidden-layer MLP, same dims, same task kind) shares ONE
compiled head executable: adding a tenant never adds a trunk compile
and usually adds no compile at all.

Numerics contract: `head_batch` composes `models/finetune.apply_head`
over `models/proteinbert.encode_trunk` — the exact decomposition the
monolithic `models/finetune.apply` is built from — so split-apply
output is the same computation, and a row's result is independent of
which other rows (other tenants' requests) share its batch (per-row
independence of the trunk forward; tests/test_heads.py asserts bit
identity of mixed-batch vs per-head serving).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from proteinbert_tpu.configs import ModelConfig
from proteinbert_tpu.models import finetune as ft_model
from proteinbert_tpu.models import proteinbert


@partial(jax.jit, static_argnames="cfg")
def trunk_batch(params, tokens, annotations, cfg: ModelConfig):
    """The shared executable: (B, L) tokens + (B, A) annotations →
    {"local", "global", "pad_mask"} trunk representation. One compile
    per (B, L) shape regardless of which heads consume it."""
    return proteinbert.encode_trunk(params, tokens, cfg, annotations)


@partial(jax.jit, static_argnames="kind")
def head_batch(head, local, global_, pad_mask, kind: str):
    """One head's tail over a whole trunk-encoded batch: float32
    logits/predictions shaped by `kind` (models/finetune module doc).
    `head` is a traced pytree — all heads with one structure share one
    executable."""
    return ft_model.apply_head(head, local, global_, pad_mask, kind)


@partial(jax.jit, static_argnames="cfg")
def packed_trunk_batch(params, tokens, segment_ids, annotations,
                       cfg: ModelConfig):
    """The ragged-serving shared executable (ISSUE 9): one fixed-shape
    (rows, seq_len) PACKED batch → {"local" (B, L, C), "global"
    (B, S, G), "seg_mask" (B, S, L) bool} per-segment trunk
    representation. One compile per request-kind shape regardless of
    which heads consume it — the packed sibling of `trunk_batch`.
    `seg_mask` is True only at a segment's REAL token positions (a
    bucket-quantized span's <pad> tail is excluded), so the head tails
    pool exactly the positions the bucketed path's pad_mask keeps.
    A forward-only entry: on a TPU and at C <= 512 the trunk's
    local track runs the segment-aware fused Pallas kernel on supported
    shapes (kernels/fused_block.packed_local_track_forward, ISSUE 42) —
    the shared packed trunk executable is a fast-path executable."""
    from proteinbert_tpu import inference
    from proteinbert_tpu.data.vocab import PAD_ID

    local, global_ = proteinbert.encode(params, tokens, annotations, cfg,
                                        pad_mask=(tokens != PAD_ID),
                                        segment_ids=segment_ids,
                                        forward_only=True)
    return {"local": local, "global": global_,
            "seg_mask": inference._segment_real_mask(
                tokens, segment_ids, annotations.shape[1])}


def packed_head_features(local: jax.Array, global_: jax.Array,
                         seg_mask: jax.Array, kind: str) -> jax.Array:
    """Per-SEGMENT feature tensor for a `kind` head over a packed trunk
    representation — the segment-aware sibling of
    `models/finetune.head_features` (same pooling math per segment:
    mask-weighted mean over real positions, concatenated with the
    segment's own global vector), so a span's head input matches the
    bucketed path's within jitted tolerance. token_classification heads
    read the local track directly; callers slice each segment's span
    from the (B, L, out) result."""
    if kind == "token_classification":
        return local
    m = seg_mask.astype(local.dtype)  # (B, S, L)
    pooled = (jnp.einsum("bsl,blc->bsc", m, local)
              / jnp.maximum(m.sum(-1)[..., None], 1.0))
    return jnp.concatenate([global_, pooled], axis=-1)


@partial(jax.jit, static_argnames="kind")
def packed_head_batch(head, local, global_, seg_mask, kind: str):
    """One head's tail over a packed trunk batch: float32 outputs shaped
    (B, L, out) for token_classification (slice spans out) or (B, S,
    out) per segment otherwise. `head` is traced — all heads of one
    structure share one executable, same as `head_batch`."""
    return ft_model._head_apply(
        head, packed_head_features(local, global_, seg_mask, kind)
    ).astype(jnp.float32)


def apply_heads_packed(
    trunk_out: Dict[str, jax.Array],
    riders: Sequence[Tuple[Any, int, int, int, int]],
) -> List[np.ndarray]:
    """Mixed-head tail for a PACKED batch: `riders` is one (head, row,
    segment_index, start, span) tuple per request, row-major. Each
    DISTINCT head runs once over the full packed batch, then every
    rider keeps its own segment's slice — (span, out) for
    token_classification (aligned with the bucketed (bucket_len, out)
    output), (out,) / (1,) otherwise. Returns host arrays aligned to
    `riders` order."""
    out: List[Optional[np.ndarray]] = [None] * len(riders)
    by_head: Dict[str, List[int]] = {}
    head_of: Dict[str, Any] = {}
    for i, (head, _, _, _, _) in enumerate(riders):
        by_head.setdefault(head.head_id, []).append(i)
        head_of[head.head_id] = head
    for head_id, idxs in by_head.items():
        head = head_of[head_id]
        res = np.asarray(packed_head_batch(
            head.params, trunk_out["local"], trunk_out["global"],
            trunk_out["seg_mask"], head.task.kind))
        for i in idxs:
            _, row, seg, start, span = riders[i]
            if head.task.kind == "token_classification":
                out[i] = res[row, start:start + span]
            else:
                out[i] = res[row, seg]
    return out  # type: ignore[return-value]


def apply_heads(
    trunk_out: Dict[str, jax.Array],
    heads: Sequence[Any],
) -> List[np.ndarray]:
    """Mixed-head tail: per-row head objects (each with `.params`,
    `.task.kind`, `.head_id` — heads/registry.LoadedHead) over one
    shared trunk representation. Each DISTINCT head runs once over the
    full batch (shape-stable: no per-group-size executables), then
    every row keeps its own head's output. Returns host arrays aligned
    to the input rows."""
    rows_out: List[Optional[np.ndarray]] = [None] * len(heads)
    by_head: Dict[str, List[int]] = {}
    head_of: Dict[str, Any] = {}
    for i, head in enumerate(heads):
        by_head.setdefault(head.head_id, []).append(i)
        head_of[head.head_id] = head
    for head_id, idxs in by_head.items():
        head = head_of[head_id]
        out = np.asarray(head_batch(head.params, trunk_out["local"],
                                    trunk_out["global"],
                                    trunk_out["pad_mask"],
                                    head.task.kind))
        for i in idxs:
            rows_out[i] = out[i]
    return rows_out  # type: ignore[return-value]


def predict_task_rows(
    trunk_params,
    cfg: ModelConfig,
    head,
    tokens: np.ndarray,
    annotations: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Offline single-head entry: (N, L) tokens → (N, ...) float32 head
    outputs through the SAME jitted trunk+head executables serving
    uses — the sequential-per-head reference mixed-batch parity is
    measured against, and the eval harness's forward."""
    if annotations is None:
        annotations = np.zeros((tokens.shape[0], cfg.num_annotations),
                               np.float32)
    trunk_out = trunk_batch(trunk_params, tokens, annotations, cfg)
    return np.asarray(head_batch(head.params, trunk_out["local"],
                                 trunk_out["global"],
                                 trunk_out["pad_mask"], head.task.kind))
