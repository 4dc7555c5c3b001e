"""Multi-tenant head registry + split-apply + downstream eval (ISSUE 8).

The subsystem that turns "a pretraining repro with a server" into "a
task platform" (ROADMAP item 5): many small finetuned task heads —
secondary structure, GO prediction, stability, arbitrary user tasks —
share ONE resident pretrained trunk's worth of HBM, and the serving
layer batches requests for *different* heads through the shared trunk
in one micro-batch, swapping only the cheap head matmuls.

- **registry** (`heads/registry.py`) — content-addressed, self-
  verifying on-disk head artifacts: head params + TaskConfig + trunk
  fingerprint + eval metrics. Typed failures: `UnknownHeadError`
  (serving 404), `CorruptHeadError` (digest mismatch),
  `TrunkMismatchError` (trained against a different trunk — the
  silent-garbage case, refused).
- **apply** (`heads/apply.py`) — split-apply execution: one jitted
  trunk executable per batch shape shared by ALL heads
  (`proteinbert.encode_trunk` under the hood), plus a cheap jitted
  per-head tail reusing `models/finetune.apply_head`.
- **eval** (`heads/eval.py`) — downstream-task metrics (per-residue
  accuracy, multilabel AUC proxy, regression Spearman) recorded as
  schema-versioned `head_eval` events so finetune-quality regressions
  gate via the bench-trajectory sentinel like perf does.

Producers: `train/finetune.finetune(..., registry=)` and the
`pbt finetune --register-head` CLI. Consumers: the serving layer
(`serve/dispatch.py` dynamic head kinds, `Server.predict_task`),
and `pbt eval-heads`. docs/finetuning.md walks the
train → register → serve → eval loop end to end.
"""

from proteinbert_tpu.heads.registry import (
    CorruptHeadError,
    HeadRegistry,
    HeadRegistryError,
    LoadedHead,
    TrunkMismatchError,
    UnknownHeadError,
    trunk_fingerprint,
)

__all__ = [
    "HeadRegistry",
    "LoadedHead",
    "HeadRegistryError",
    "UnknownHeadError",
    "CorruptHeadError",
    "TrunkMismatchError",
    "trunk_fingerprint",
]
