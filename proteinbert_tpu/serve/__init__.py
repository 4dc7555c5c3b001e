"""Online serving subsystem (ISSUE 5 tentpole).

The offline inference surface (proteinbert_tpu/inference.py) is a
blocking batch API: every request pads to the full `cfg.data.seq_len`,
compiles one static shape, and concurrent callers serialize. This
package is the TPU-native online answer — the shape-bucketed,
continuously batched execution model of ragged-paged-attention-style
serving (PAPERS.md), built from five cooperating pieces:

- **queue** (`serve/queue.py`) — thread-safe bounded request queue with
  admission control: bounded depth with OLDEST-FIRST eviction (the
  evicted request's future fails with `QueueFullError` — rejected,
  never silently dropped), per-request deadlines, and a closed state
  that rejects new work during drain;
- **dispatch** (`serve/dispatch.py`) — one pre-warmed jitted executable
  per (bucket_len, batch_class) shape class, reusing the bucket-
  boundary semantics of `data/dataset.make_bucketed_iterator` (buckets
  ascending, last == seq_len) so a 40-residue query pays 64-length
  FLOPs, not 512; served batches shard over the mesh batch dim
  (`parallel/sharding.serve_batch_sharding`);
- **scheduler** (`serve/scheduler.py`) — continuous micro-batching:
  drains the queue under a max-batch/max-wait policy, groups requests
  by (kind, bucket), dispatches the fullest/oldest group. The clock is
  injected, so batch formation is deterministic under a fake clock
  (tests/test_serve.py);
- **cache** (`serve/cache.py`) — content-addressed (sequence-hash
  keyed) LRU result cache with hit/miss/eviction counters,
  short-circuiting repeat queries before they ever enqueue;
- **server** (`serve/server.py`) — the `Server` facade: `embed` /
  `predict_go` / `predict_residues` as sync calls or `submit()`
  futures, graceful `drain()` (in-flight batches finish, queue rejects
  new work) vs `abort()` (pending futures fail, flight-recorder note),
  `serve_*` telemetry on the same obs stream as training runs;
- **http** (`serve/http.py`) — a thin stdlib `http.server` JSON
  endpoint over the same facade (`pbt serve`).

Two dispatch modes (ISSUE 9): the default **bucketed** ladder above,
and **ragged** (`serve_mode="ragged"` / `pbt serve --serve-mode
ragged`) — heterogeneous requests PACK into fixed-shape
(rows, seq_len) rows at bucket-quantized spans via the training-side
packing representation (`data/packing.py`, tokens + segment_ids), so
ONE warm executable per request kind serves every length mix
(`RaggedDispatcher` + `PackedBatchScheduler`), with per-request
outputs matching the bucketed dispatcher's within the documented
jitted ≤1e-5 tolerance (docs/serving.md, "Ragged batching").

Measured on the chip by the benchmark's serve cells (`python -m
benchmark.run --workload serve-base-sat`); documented in
docs/serving.md.

Above single servers sits the FLEET layer (ISSUE 11, `serve/fleet.py`
/ `pbt fleet`): N replicas behind a `FleetRouter` — /healthz +
SLO-burn health states, idempotent retries with capped backoff and a
fleet-wide retry budget, typed load shedding on top of the 429/503
contract, operator drain/re-admit, a shared content-addressed result
cache, and exactly-once request sealing audited by the fault-injection
drill (`tools/fleet_drill.py`).
"""

from proteinbert_tpu.serve.cache import EmbeddingCache, content_key
from proteinbert_tpu.serve.dispatch import (
    TASK_KIND, BucketDispatcher, RaggedDispatcher,
)
from proteinbert_tpu.serve.errors import (
    DeadlineExceededError,
    QueueFullError,
    SequenceTooLongError,
    ServeError,
    ServerClosedError,
    TrunkMismatchError,
    UnknownHeadError,
)
from proteinbert_tpu.serve.fleet import (
    FaultInjector, FleetRouter, make_fleet_http_server,
)
from proteinbert_tpu.serve.queue import Request, RequestQueue
from proteinbert_tpu.serve.scheduler import (
    MicroBatchScheduler, PackedBatchScheduler,
)
from proteinbert_tpu.serve.server import SERVE_MODES, Server

from proteinbert_tpu.serve.trace import RequestTrace

__all__ = [
    "Server",
    "FleetRouter",
    "FaultInjector",
    "make_fleet_http_server",
    "SERVE_MODES",
    "BucketDispatcher",
    "RaggedDispatcher",
    "MicroBatchScheduler",
    "PackedBatchScheduler",
    "RequestQueue",
    "Request",
    "RequestTrace",
    "EmbeddingCache",
    "content_key",
    "TASK_KIND",
    "ServeError",
    "QueueFullError",
    "DeadlineExceededError",
    "ServerClosedError",
    "SequenceTooLongError",
    "UnknownHeadError",
    "TrunkMismatchError",
]
