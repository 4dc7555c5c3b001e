"""`Server` — the online-inference facade.

Ties the queue, scheduler, dispatcher, and cache together behind the
same three capabilities the offline surface exposes (inference.py):
`embed`, `predict_go`, `predict_residues` — each available as a
blocking call or a `submit()` future for in-process callers (the HTTP
layer in serve/http.py is a thin JSON shim over exactly this facade).

Request life cycle:

  submit() [client thread]                    scheduler thread
  ├─ over-length policy (reject/truncate+count)
  ├─ tokenize + bucket-route (serve/dispatch)
  ├─ cache lookup — hit returns a resolved future, nothing enqueues
  └─ queue.push (may evict the oldest    ──►  poll(): group by
     request with QueueFullError)             (kind, bucket), dispatch
                                              at max_batch/max_wait —
                                              submit only; a completer
                                              thread fetches results,
                                              finalizes per row: cache
                                              put + future.set_result

Pipelined dispatch (ISSUE 19): dispatch is split into submit (enqueue
the jitted call — JAX dispatch is async, so this returns immediately)
and finalize (blocking host fetch + per-request fan-out), joined by a
bounded in-flight window (`pipeline_depth`, default 2). Batch N+1
forms and submits while batch N computes; the completer thread drains
the window in FIFO order. Depth 1 disables the completer and restores
the serial path bit-for-bit (docs/serving.md "Pipelined dispatch").

Shutdown is two-mode, per the resilience conventions of
train/resilience.GracefulShutdown:

- `drain()` — the queue closes (new submits raise ServerClosedError),
  every queued and in-flight request completes, then the scheduler
  thread exits; emits `serve_end{outcome=drained}`.
- `abort()` — queued + pending futures fail with ServerClosedError,
  the loop stops after the in-flight batch, a `note` lands on the
  telemetry stream and the flight recorder dumps (forensics for the
  requests that were killed); emits `serve_end{outcome=aborted}`.

Request tracing + SLOs (ISSUE 6): with telemetry enabled, every
request carries a `serve/trace.RequestTrace` that collects one clock
mark per stage boundary (submit → queue → batch_form → dispatch →
execute → finalize). Traces SAMPLED at `trace_sample_rate` — plus ALL
requests that end in an error or rejection, regardless of sampling —
emit a `serve_request` event and, when the telemetry carries a span
collector, Perfetto spans on a per-request lane. Every request's
outcome also feeds the optional `obs/slo.SLOEvaluator` (declarative
latency/error-rate objectives; burn rates on `/metrics`,
`stats()["slo"]`, and `pbt diagnose --serve`; breach → optional
on-demand device profile). With the NULL facade no trace objects are
created and every touchpoint is a None check — the served path costs
what it did before tracing existed.

Telemetry (all optional, NULL-facade free when absent —
docs/observability.md): `serve_start`/`serve_batch`/`serve_reject`/
`serve_request`/`slo_breach`/`serve_end` events; `serve_queue_depth`,
`serve_batch_occupancy`, `serve_cache_hit_rate`,
`slo_burn_rate{objective=}` gauges; the `serve_latency` quantile
window (`serve_latency_p50_s`/`p99_s` at scrape time);
`serve_requests_total{kind=}`, `serve_rejected_total{reason=}`,
`serve_truncated_total`, `serve_cache_*_total` counters;
`serve_latency_seconds`, `serve_queue_wait_seconds`,
`serve_batch_seconds`, `serve_batch_rows` histograms. With a neighbor
index attached (ISSUE 17): `neighbor_query` events (sampled) and the
`neighbors_requests_total{outcome=}` per-outcome funnel.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, Optional

import numpy as np

from proteinbert_tpu import inference
from proteinbert_tpu.configs import DecoderConfig, PretrainConfig
from proteinbert_tpu.heads.registry import (
    HeadRegistry, LoadedHead, TrunkMismatchError, UnknownHeadError,
    trunk_fingerprint,
)
from proteinbert_tpu.obs import tracing
from proteinbert_tpu.serve.cache import EmbeddingCache, content_key
from proteinbert_tpu.serve.dispatch import (
    DECODER_PAD, KINDS, NEIGHBORS_KIND, TASK_KIND, BucketDispatcher,
    RaggedDispatcher,
)
from proteinbert_tpu.serve.errors import (
    SequenceTooLongError, ServerClosedError,
)
from proteinbert_tpu.serve.queue import Request, RequestQueue
from proteinbert_tpu.serve.scheduler import (
    MicroBatchScheduler, PackedBatchScheduler,
)
from proteinbert_tpu.serve.trace import RequestTrace, stride_sampled

SERVE_MODES = ("bucketed", "ragged")

# Default result size for `/v1/neighbors` when the request carries no
# `k` — matches the recall gate's k (tests/test_index.py, recall@10).
DEFAULT_NEIGHBORS_K = 10


class Server:
    """Online serving facade over a pretrained trunk (see module doc)."""

    def __init__(
        self,
        params,
        cfg: PretrainConfig,
        *,
        buckets=None,
        max_batch: int = 8,
        max_wait_s: float = 0.01,
        queue_depth: int = 64,
        cache_size: int = 1024,
        default_deadline_s: Optional[float] = None,
        on_long: str = "truncate",
        mesh=None,
        telemetry=None,
        clock=time.monotonic,
        warm_kinds=("embed",),
        batch_classes=None,
        trace_sample_rate: Optional[float] = 1.0,
        slos=None,
        slo_profile_dir: Optional[str] = None,
        slo_breach_cooldown_s: float = 60.0,
        registry=None,
        heads=None,
        partition_heads: bool = False,
        serve_mode: str = "bucketed",
        pack_max_segments: int = 8,
        quant: Optional[str] = None,
        quant_parity_every: Optional[int] = None,
        index=None,
        nprobe: int = 8,
        replica_id: Optional[str] = None,
        pipeline_depth: Optional[int] = None,
        candidate_loader=None,
    ):
        from proteinbert_tpu.obs import as_telemetry

        if on_long not in ("truncate", "reject"):
            raise ValueError(f"on_long must be 'truncate' or 'reject', "
                             f"got {on_long!r}")
        if serve_mode not in SERVE_MODES:
            raise ValueError(f"serve_mode must be one of {SERVE_MODES}, "
                             f"got {serve_mode!r}")
        self.cfg = cfg
        # The model is picked by the type of `cfg.model` (ISSUE 33): the
        # causal decoder rides the same queue, packer, row classes and
        # dispatcher, ragged and `embed` only; what is not built for it
        # is refused here, by name.
        self.decoder = isinstance(cfg.model, DecoderConfig)
        if self.decoder:
            unbuilt = {
                "bucketed serving (serve_mode='bucketed')": serve_mode != "ragged",
                "the result cache (cache_size > 0)": bool(cache_size),
                "heads (heads= / registry=)": bool(heads) or registry is not None,
                "the neighbor index (index=)": index is not None,
            }
            named = [what for what, asked in unbuilt.items() if asked]
            if named:
                raise ValueError(
                    "not built for the decoder: " + "; ".join(named)
                    + " (it is served ragged, `embed` only, cache_size=0)")
        self.on_long = on_long
        self.default_deadline_s = default_deadline_s
        self.clock = clock
        self.serve_mode = serve_mode
        # Quantized executable arm (ISSUE 12): defaults ride the run
        # config (configs.ServeConfig) so `pbt serve --pretrained DIR`
        # inherits the trained-against quantization decision; explicit
        # ctor args override per server.
        serve_cfg = getattr(cfg, "serve", None)
        if quant is None:
            quant = getattr(serve_cfg, "quant", "fp32")
        if quant_parity_every is None:
            quant_parity_every = getattr(serve_cfg,
                                         "quant_parity_every", 0)
        # Pipelined dispatch (ISSUE 19): bounded in-flight window for
        # the scheduler. Depth 1 restores the serial pre-pipeline path
        # (submit + finalize inline on the scheduler thread); depth >= 2
        # starts a completer thread so batch N+1 forms while batch N
        # computes. Same config-then-ctor precedence as quant.
        if pipeline_depth is None:
            pipeline_depth = getattr(serve_cfg, "pipeline_depth", 2)
        self.pipeline_depth = max(1, int(pipeline_depth))
        self.quant = quant
        # Fleet identity (ISSUE 18): a stable name the fleet assigns at
        # spawn (`pbt serve --replica-id r0`). Stamped onto every
        # serve_request/serve_batch event so fleet joins key on an
        # explicit identity, never an inferred port.
        self.replica_id = replica_id
        self.tele = as_telemetry(telemetry)
        metrics = self.tele.metrics
        self.cache = EmbeddingCache(cache_size, metrics=metrics)
        self.queue = RequestQueue(queue_depth)
        if serve_mode == "ragged":
            # Ragged packed serving (ISSUE 9): heterogeneous requests
            # PACK into fixed-shape (max_batch, seq_len) rows at their
            # bucket-quantized spans — one warm executable per request
            # kind and row class, outputs matching the bucketed
            # dispatcher's within the documented jitted tolerance
            # (docs/serving.md). `max_batch` means the packed ROWS of
            # the LARGEST batch here (it carries up to max_batch *
            # pack_max_segments requests); an under-full batch runs at
            # the row class that fits it: max_batch, /2, /4, /8.
            if partition_heads:
                raise ValueError(
                    "partition_heads is a bucketed-mode baseline knob; "
                    "ragged packing mixes heads through the shared "
                    "trunk by construction")
            if batch_classes is not None:
                raise ValueError(
                    "batch_classes is a bucketed-mode knob — the ragged "
                    "row classes are derived from max_batch "
                    "(dispatch.default_row_classes)")
            self.dispatcher = RaggedDispatcher(
                params, cfg, buckets=buckets, rows_per_batch=max_batch,
                max_segments=pack_max_segments, mesh=mesh,
                metrics=metrics, quant=quant,
                quant_parity_every=quant_parity_every)
            self.scheduler = PackedBatchScheduler(
                self.queue, self.dispatcher, self._finalize,
                rows_per_batch=max_batch, max_wait_s=max_wait_s,
                clock=clock, max_segments=pack_max_segments,
                telemetry=telemetry, replica_id=replica_id,
                latency_observer=self._observe_latency,
                expire_observer=self._count_expiry,
                complete_observer=self._on_complete,
                pipeline_depth=self.pipeline_depth)
        else:
            self.dispatcher = BucketDispatcher(
                params, cfg, buckets=buckets, max_batch=max_batch,
                batch_classes=batch_classes, mesh=mesh, metrics=metrics,
                quant=quant, quant_parity_every=quant_parity_every)
            self.scheduler = MicroBatchScheduler(
                self.queue, self.dispatcher, self._finalize,
                max_batch=max_batch, max_wait_s=max_wait_s, clock=clock,
                partition_heads=partition_heads,
                telemetry=telemetry, replica_id=replica_id,
                latency_observer=self._observe_latency,
                expire_observer=self._count_expiry,
                complete_observer=self._on_complete,
                pipeline_depth=self.pipeline_depth)
        # Multi-tenant heads (ISSUE 8): an optional registry to resolve
        # head ids from, plus the resident trunk's fingerprint computed
        # LAZILY (one device→host fetch of the whole trunk — only paid
        # when a head is actually loaded). Every registry load checks
        # the artifact's trunk_fingerprint against the resident trunk:
        # a head trained against a different trunk raises the typed
        # TrunkMismatchError instead of silently serving garbage.
        if isinstance(registry, str):
            registry = HeadRegistry(registry)
        self.registry = registry
        self._trunk_fp: Optional[str] = None
        for h in (heads or ()):
            self.add_head(h)
        # Neighbor index (ISSUE 17): an optional scorer.NeighborIndex.
        # `/v1/neighbors` requests ride the embed executable (dispatch
        # normalizes the kind — zero new trunk compiles), then probe
        # this index on the scheduler thread. The index pins the trunk
        # it was built from; a fingerprint mismatch is the same class
        # of error as a mis-trunked head, and gets the same typed
        # refusal before the server can serve garbage neighbors.
        self.index = index
        self.nprobe = int(nprobe)
        if index is not None:
            if self.nprobe < 1:
                raise ValueError(f"nprobe must be >= 1, got {nprobe}")
            fp = self.trunk_fp()
            if index.model_fingerprint != fp:
                raise TrunkMismatchError(
                    "neighbor index was built from embeddings of trunk "
                    f"{index.model_fingerprint[:12]}…, but this server "
                    f"holds trunk {fp[:12]}… — rebuild it with "
                    "`pbt index` over this model's embedding store")
        # Blue-green rollout (ISSUE 20): the candidate/parked arm
        # identities this facade tracks beside the dispatcher's trees,
        # and the loader that resolves a rollout `source` string to a
        # trunk params tree (cli/main.py wires run-dir loading here;
        # drills pass a closure). shadow_total mirrors how many shadow
        # requests ran — the ONLY live counter shadow traffic touches.
        self.candidate_loader = candidate_loader
        self._candidate_fp: Optional[str] = None
        self._parked_fp: Optional[str] = None
        self.shadow_total = 0
        # The p50/p99 ring lives in the obs registry (QuantileWindow):
        # /metrics scrapes, stats(), and serve_request events all read
        # the same ring. A disabled registry (NULL telemetry) returns a
        # live unregistered window so stats() still reports real numbers.
        self.latencies = metrics.quantile_window("serve_latency")
        # Request tracing: None disables trace objects entirely; a rate
        # in [0, 1] traces every request cheaply and EMITS the sampled
        # fraction (errors/rejections always emit). NULL telemetry also
        # disables: there is nowhere to emit to.
        if trace_sample_rate is not None and not self.tele.enabled:
            trace_sample_rate = None
        self.trace_sample_rate = trace_sample_rate
        self._req_ids = itertools.count(1)
        self._id_prefix = f"{os.getpid():x}-"
        self.slo = None
        self.profile_trigger = None
        if slos:
            from proteinbert_tpu.obs.slo import ProfileTrigger, SLOEvaluator

            on_breach = None
            if slo_profile_dir:
                self.profile_trigger = ProfileTrigger(slo_profile_dir,
                                                      clock=clock)
                on_breach = self.profile_trigger
            self.slo = SLOEvaluator(
                slos, metrics=metrics, telemetry=self.tele, clock=clock,
                on_breach=on_breach,
                breach_cooldown_s=slo_breach_cooldown_s)
            stage_objs = [o.name for o in self.slo.objectives
                          if o.kind == "latency" and o.stage != "e2e"]
            if stage_objs and self.trace_sample_rate is None:
                raise ValueError(
                    f"stage-scoped slo objective(s) {stage_objs} need "
                    "request tracing for per-stage durations, but "
                    "tracing is off (telemetry disabled or "
                    "trace_sample_rate=None) — they would never "
                    "observe anything")
            # SLO violation attribution consumes pad/prep/device per
            # request, so every batch must be timed, not just sampled
            # riders' batches.
            self.scheduler.time_batches = True
        self._warm_kinds = tuple(warm_kinds)
        self._started = False
        self._ended = False
        self._depth_g = metrics.gauge("serve_queue_depth")
        self._latency_h = metrics.histogram("serve_latency_seconds")
        self._truncated_c = metrics.counter("serve_truncated_total")
        self._req_c = {k: metrics.counter("serve_requests_total", kind=k)
                       for k in KINDS + (TASK_KIND, NEIGHBORS_KIND)}
        from proteinbert_tpu.obs.events import (
            SERVE_REJECT_REASONS, SERVE_REQUEST_OUTCOMES,
        )

        self._rej_c = {r: metrics.counter("serve_rejected_total", reason=r)
                       for r in SERVE_REJECT_REASONS}
        # Per-outcome `/v1/neighbors` funnel (ISSUE 17): every neighbors
        # request lands in exactly one bucket via the _seal funnel.
        self._nbr_c = {o: metrics.counter("neighbors_requests_total",
                                          outcome=o)
                       for o in SERVE_REQUEST_OUTCOMES}
        self.completed_total = 0
        self.cache_hit_returns = 0
        # Local mirrors of the labeled counters: stats() must report
        # real numbers even under the NULL telemetry facade (whose
        # metric instruments are shared no-ops). Bumped from concurrent
        # client/HTTP threads, so the read-modify-write needs a lock.
        # (completed_total needs none: finalize has exactly one writer
        # — the completer thread when pipeline_depth > 1, else the
        # scheduler thread — never both; see scheduler._finalize_batch.)
        self._mirror_lock = threading.Lock()
        self.truncated_total = 0
        self.rejected_total = {r: 0 for r in self._rej_c}
        self.neighbors_total = {o: 0 for o in self._nbr_c}
        # Kernel fast-path COVERAGE (ISSUEs 10/13): mirror the
        # kernels/fused_block AND kernels/attention dispatch bumps —
        # both the Pallas fast path and the XLA reference path — into
        # the registry as fused_kernel_path_total{path=,reason=} /
        # attention_kernel_path_total{path=,reason=}, so /metrics,
        # stats() and `pbt diagnose --serve` show how many compiled
        # shapes run the fast path, not just the misses. (The
        # one-release deprecated fused_kernel_fallback_total mirror was
        # removed in ISSUE 12, as PR 9 scheduled.) Registered LAST —
        # after every raising statement above — so a failed
        # construction (bad SLO spec, trunk-mismatched head) cannot
        # leak a process-global observer; drain()/abort() unregister
        # them.
        from proteinbert_tpu.kernels.attention import (
            register_attention_path_observer,
        )
        from proteinbert_tpu.kernels.fused_block import (
            register_path_observer,
        )
        from proteinbert_tpu.kernels.moe_rows import (
            register_moe_rows_path_observer,
        )
        from proteinbert_tpu.kernels.one_pass import (
            register_onepass_path_observer,
        )
        from proteinbert_tpu.kernels.segment_flash import (
            register_cca_core_path_observer,
        )
        from proteinbert_tpu.kernels.ssd import (
            register_ssd_core_path_observer,
        )

        self._path_c: Dict[Any, Any] = {}

        # Bind metrics + the counter dict via default args, NOT self: a
        # Server abandoned without drain()/abort() must leak only this
        # small dict through the process-global observer lists, never
        # the params/dispatcher it would pin via a bound method.
        def _mirror(name: str, path: str, reason: str,
                    _metrics=metrics, _c=self._path_c) -> None:
            c = _c.get((name, path, reason))
            if c is None:
                c = _c[(name, path, reason)] = _metrics.counter(
                    name, path=path, reason=reason)
            c.inc()

        def _mirror_path(path: str, reason: str) -> None:
            _mirror("fused_kernel_path_total", path, reason)

        def _mirror_attn_path(path: str, reason: str) -> None:
            _mirror("attention_kernel_path_total", path, reason)

        def _mirror_onepass_path(path: str, reason: str) -> None:
            _mirror("onepass_kernel_path_total", path, reason)

        def _mirror_moe_rows_path(path: str, reason: str) -> None:
            _mirror("moe_rows_kernel_path_total", path, reason)

        def _mirror_cca_core_path(path: str, reason: str) -> None:
            _mirror("cca_core_kernel_path_total", path, reason)

        def _mirror_ssd_core_path(path: str, reason: str) -> None:
            _mirror("ssd_core_kernel_path_total", path, reason)

        self._cca_core_path_cb = _mirror_cca_core_path
        register_cca_core_path_observer(self._cca_core_path_cb)
        self._ssd_core_path_cb = _mirror_ssd_core_path
        register_ssd_core_path_observer(self._ssd_core_path_cb)
        self._path_cb = _mirror_path
        self._attn_path_cb = _mirror_attn_path
        self._onepass_path_cb = _mirror_onepass_path
        self._moe_rows_path_cb = _mirror_moe_rows_path
        register_path_observer(self._path_cb)
        register_attention_path_observer(self._attn_path_cb)
        register_onepass_path_observer(self._onepass_path_cb)
        register_moe_rows_path_observer(self._moe_rows_path_cb)

    def _bump(self, mirror: str, reason: Optional[str] = None) -> None:
        with self._mirror_lock:
            if reason is None:
                setattr(self, mirror, getattr(self, mirror) + 1)
            else:
                self.rejected_total[reason] += 1

    # ---------------------------------------------------------- lifecycle

    def start(self) -> "Server":
        """Warm the compiled shape classes and start the scheduler."""
        if self._started:
            raise RuntimeError("server already started")
        tracing.backend()
        warmed = self.dispatcher.warmup(self._warm_kinds)
        if self.index is not None:
            # Warm the one lookup executable every single-request probe
            # uses — (Q=1, nprobe, k=DEFAULT_NEIGHBORS_K) — so the first
            # /v1/neighbors request pays lookup time, not compile time.
            self.index.lookup_rows(
                np.zeros((1, self.index.dim), np.float32),
                k=DEFAULT_NEIGHBORS_K, nprobe=self.nprobe)
        self.tele.emit("serve_start", pid=os.getpid(), config={
            "serve_mode": self.serve_mode,
            "buckets": list(self.dispatcher.buckets),
            "batch_classes": list(self.dispatcher.batch_classes),
            "pack_max_segments": getattr(self.dispatcher,
                                         "max_segments", None),
            "max_batch": self.scheduler.max_batch,
            "max_wait_s": self.scheduler.max_wait_s,
            "queue_depth": self.queue.max_depth,
            "cache_size": self.cache.capacity,
            "on_long": self.on_long,
            "warmed_executables": warmed,
            "trace_sample_rate": self.trace_sample_rate,
            "slos": ([o.name for o in self.slo.objectives]
                     if self.slo else []),
            "mesh": (dict(self.dispatcher.mesh.shape)
                     if self.dispatcher.mesh is not None else None),
            "heads": sorted(self.dispatcher.heads),
            "warmup": self.dispatcher.warmup_report,
            "quant": self.quant,
            "quant_report": self.dispatcher.quant_report or None,
            "pipeline_depth": self.pipeline_depth,
            "neighbor_index": (self.index.digest
                               if self.index is not None else None),
            "nprobe": self.nprobe if self.index is not None else None,
            "replica_id": self.replica_id,
        })
        self.scheduler.start()
        self._started = True
        return self

    # -------------------------------------------------- multi-tenant heads

    def trunk_fp(self) -> str:
        """The resident trunk's fingerprint (computed once); the value
        every registry load is checked against."""
        if self._trunk_fp is None:
            self._trunk_fp = trunk_fingerprint(self.dispatcher.params)
        return self._trunk_fp

    def add_head(self, head) -> str:
        """Hot-add a head to a (possibly live) server: a head id
        resolved through the registry (trunk-compatibility ENFORCED —
        TrunkMismatchError if it was trained against a different
        trunk), or an already-LoadedHead (trusted: in-process producers
        like tests/bench build these directly). On a live server the
        head's tail is warmed incrementally; the trunk is never
        recompiled. Returns the head id."""
        if isinstance(head, str):
            if self.registry is None:
                raise UnknownHeadError(
                    f"cannot resolve head id {head!r}: this server has "
                    "no registry (pass registry= or a LoadedHead)")
            head = self.registry.load(head, trunk_fp=self.trunk_fp())
        assert isinstance(head, LoadedHead)
        warm_s = self.dispatcher.add_head(
            head, warm=getattr(self, "_started", False))
        self.tele.emit("note", source="serve", kind="head_added",
                       head_id=head.head_id, name=head.name,
                       task=head.task.kind,
                       incremental_warmup_s=round(warm_s, 6))
        return head.head_id

    def remove_head(self, head_id: str) -> None:
        """Hot-remove a head: new submits for it get the typed
        UnknownHeadError (HTTP 404) immediately; already-admitted
        requests carry their own head reference and complete normally
        (drain semantics — tests/test_heads.py exercises this under
        concurrent traffic)."""
        head = self.dispatcher.remove_head(head_id)
        self.tele.emit("note", source="serve", kind="head_removed",
                       head_id=head.head_id, name=head.name)

    def list_heads(self):
        """[{head_id, name, kind, num_outputs}] of the currently
        servable heads."""
        return self.dispatcher.list_heads()

    # ------------------------------------------------ blue-green rollout

    def load_candidate(self, params=None, source: Optional[str] = None,
                       hbm_budget_bytes: Optional[int] = None
                       ) -> Dict[str, Any]:
        """Load a candidate trunk beside the resident one and warm-boot
        it through the compile cache (ISSUE 20). Pass the params tree
        directly or a `source` string for the server's
        `candidate_loader` to resolve. HBM-priced with the typed
        `CandidateUnfitError` refusal when both arms don't fit (see
        dispatch.load_candidate). Returns the candidate report
        {fingerprint, warm_seconds, weight bytes...}."""
        if self.decoder:
            raise ValueError("a rollout candidate is not built for the "
                             "decoder (two trees do not fit one chip)")
        if (params is None) == (source is None):
            raise ValueError("pass exactly one of params= / source=")
        if params is None:
            if self.candidate_loader is None:
                raise ValueError(
                    "this server has no candidate_loader — pass the "
                    "params tree directly, or construct the server "
                    "with candidate_loader=")
            params = self.candidate_loader(source)
        # Fingerprint BEFORE the dispatcher takes ownership (it may
        # host-park or re-place the tree under quant/mesh serving).
        fp = trunk_fingerprint(params)
        report = self.dispatcher.load_candidate(
            params, hbm_budget_bytes=hbm_budget_bytes)
        warm_s = self.dispatcher.warm_candidate()
        self._candidate_fp = fp
        report = dict(report, fingerprint=fp,
                      warm_seconds=round(warm_s, 6))
        self.tele.emit("rollout_state", state="candidate_loaded",
                       fingerprint=fp, source=source or "params")
        return report

    def unload_candidate(self) -> bool:
        """Drop the candidate arm (abort / gate refusal); returns
        whether one was loaded. The resident arm is untouched."""
        had = self.dispatcher.unload_candidate()
        if had:
            fp = self._candidate_fp
            self._candidate_fp = None
            self.tele.emit("rollout_state", state="candidate_unloaded",
                           fingerprint=fp or "")
        return had

    def flip(self) -> Dict[str, Any]:
        """Atomic promotion: the candidate becomes the resident trunk
        (dispatch.flip — zero dropped or torn in-flight requests), the
        outgoing trunk parks on host for instant rollback, and the
        result cache FLUSHES: results the old trunk computed must not
        outlive it, or a cached pre-flip embedding would answer a
        post-flip query with the wrong model."""
        old_fp = self.trunk_fp()
        seconds = self.dispatcher.flip()
        self._parked_fp = old_fp
        self._trunk_fp = self._candidate_fp
        self._candidate_fp = None
        dropped = self.cache.clear()
        self.tele.emit("rollout_flip",
                       replica=self.replica_id or "local", phase="flip",
                       seconds=round(seconds, 6),
                       fingerprint=self._trunk_fp or "", ok=True)
        return {"seconds": round(seconds, 6),
                "fingerprint": self._trunk_fp,
                "parked_fingerprint": self._parked_fp,
                "cache_dropped": dropped}

    def rollback_trunk(self) -> Dict[str, Any]:
        """Instant rollback to the parked trunk — bit-identical
        resident numerics (dispatch.rollback); the demoted trunk moves
        to the candidate slot. Flushes the cache for the same reason
        flip() does."""
        demoted_fp = self.trunk_fp()
        seconds = self.dispatcher.rollback()
        self._trunk_fp = self._parked_fp
        self._candidate_fp = demoted_fp
        self._parked_fp = None
        dropped = self.cache.clear()
        self.tele.emit("rollout_flip",
                       replica=self.replica_id or "local",
                       phase="rollback", seconds=round(seconds, 6),
                       fingerprint=self._trunk_fp or "", ok=True)
        return {"seconds": round(seconds, 6),
                "fingerprint": self._trunk_fp,
                "cache_dropped": dropped}

    def shadow_submit(self, kind: str, seq: str, annotations=None,
                      head_id: Optional[str] = None,
                      top_k: Optional[int] = None):
        """Run ONE request through the CANDIDATE arm, synchronously and
        invisibly (ISSUE 20): same tokenization/bucketing/result
        shaping as the live path, but it never touches the queue, the
        result cache, the SLO evaluator, or any live counter — the only
        bookkeeping is the `shadow_total` mirror. Raises
        NoCandidateError when no candidate is loaded. `neighbors` is
        refused: the ANN index pins the RESIDENT trunk's embedding
        space, so a candidate-arm probe would score garbage."""
        if kind == NEIGHBORS_KIND:
            raise ValueError(
                "neighbors cannot shadow: the ANN index pins the "
                "resident trunk's embedding space")
        if kind not in KINDS and kind != TASK_KIND:
            raise ValueError(f"unknown request kind {kind!r}; have "
                             f"{KINDS + (TASK_KIND,)}")
        if (kind == TASK_KIND) != (head_id is not None):
            raise ValueError(
                f"head_id is required for kind {TASK_KIND!r} and "
                "invalid for every other kind")
        if not seq:
            raise ValueError("empty sequence")
        head = (self.dispatcher.get_head(head_id)
                if kind == TASK_KIND else None)
        if annotations is not None:
            annotations = inference.check_annotations(
                np.asarray(annotations, np.float32)[None], 1, self.cfg)[0]
        bucket_len = self.dispatcher.bucket_len(len(seq))
        tokens = inference._tokenize_masked(
            [seq], self.cfg.data.seq_len, on_overflow="count")[0]
        if self.serve_mode == "ragged":
            # One real rider in row 0 of an otherwise-dummy packed
            # grid of the smallest row class; the other rows compute
            # but fan out to nobody.
            from proteinbert_tpu.data.vocab import PAD_ID

            tok, seg, ann, _ = self.dispatcher._dummy_packed(
                self.dispatcher.batch_classes[0])
            tok[0, :] = PAD_ID
            tok[0, :bucket_len] = tokens[:bucket_len]
            seg[0, :] = 0
            seg[0, :bucket_len] = 1
            if annotations is not None:
                ann[0, 0] = annotations
            row = self.dispatcher.run_packed_candidate(
                kind, tok, seg, ann, [(0, 0, 0, bucket_len)],
                heads=[head] if head is not None else None)[0]
        else:
            out = self.dispatcher.run_candidate(
                kind, tokens[None, :bucket_len],
                annotations[None] if annotations is not None else None,
                heads=[head] if head is not None else None)
            if kind == "embed":
                row = {k: v[0] for k, v in out.items()}
            else:
                row = out[0]
        if kind == "embed":
            value = {"global": np.asarray(row["global"]),
                     "local_mean": np.asarray(row["local_mean"])}
        elif kind in ("predict_go", TASK_KIND):
            value = np.asarray(row)
        else:  # predict_residues
            probs = np.asarray(row)
            value = (inference.fill_masked_residues(
                seq, probs, self.cfg.data.seq_len - 2), probs)
        self._bump("shadow_total")
        return self._present(kind, value, top_k)

    def rollout_status(self) -> Dict[str, Any]:
        """The replica's rollout arm state — surfaced on /healthz (via
        stats) so the fleet health sweep sees fingerprints per arm."""
        with self._mirror_lock:
            shadow = self.shadow_total
        return {
            "resident_fingerprint": self.trunk_fp(),
            "candidate_fingerprint": self._candidate_fp,
            "parked_fingerprint": self._parked_fp,
            "shadow_requests": shadow,
            "candidate": self.dispatcher.candidate_status(),
        }

    def __enter__(self) -> "Server":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close(drain=exc_type is None)
        return False

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful shutdown: stop admitting, finish everything queued
        and in flight, then emit `serve_end{drained}`. Returns False if
        the scheduler did not exit within `timeout`."""
        self.queue.close()
        done = self.scheduler.join(timeout)
        if not self._ended:
            self._ended = True
            self._release_path_observer()
            self.tele.emit("serve_end", outcome="drained",
                           stats=self.stats())
        return done

    def _release_path_observer(self) -> None:
        from proteinbert_tpu.kernels.attention import (
            unregister_attention_path_observer,
        )
        from proteinbert_tpu.kernels.fused_block import (
            unregister_path_observer,
        )
        from proteinbert_tpu.kernels.moe_rows import (
            unregister_moe_rows_path_observer,
        )
        from proteinbert_tpu.kernels.one_pass import (
            unregister_onepass_path_observer,
        )
        from proteinbert_tpu.kernels.segment_flash import (
            unregister_cca_core_path_observer,
        )
        from proteinbert_tpu.kernels.ssd import (
            unregister_ssd_core_path_observer,
        )

        unregister_path_observer(self._path_cb)
        unregister_attention_path_observer(self._attn_path_cb)
        unregister_onepass_path_observer(self._onepass_path_cb)
        unregister_moe_rows_path_observer(self._moe_rows_path_cb)
        unregister_cca_core_path_observer(self._cca_core_path_cb)
        unregister_ssd_core_path_observer(self._ssd_core_path_cb)

    def abort(self) -> None:
        """Hard shutdown: fail all queued + pending work with
        ServerClosedError, leave a flight-recorder trail, emit
        `serve_end{aborted}`. In-flight batches still finish (a jitted
        call cannot be interrupted); their futures resolve normally."""
        self.scheduler.stop()
        exc = ServerClosedError("server aborted before this request ran")
        failed = self.queue.fail_all(exc)
        self.scheduler.join(timeout=30.0)
        failed += self.scheduler.fail_pending(exc)
        now = self.clock()
        for req in failed:
            # Killed requests close their traces too — an abort must
            # not orphan spans (tests/test_serve_trace.py).
            self._seal(req.trace, "aborted", now, error=exc,
                       e2e_fallback=max(0.0, now - req.enqueued_at),
                       kind=req.kind)
        n = len(failed)
        if not self._ended:
            self._ended = True
            self._release_path_observer()
            self.tele.emit("note", source="serve", kind="abort",
                           failed_requests=n)
            self.tele.emit("serve_end", outcome="aborted",
                           stats=self.stats())
            self.tele.dump_flight("serve_abort")

    def close(self, drain: bool = True,
              timeout: Optional[float] = None) -> None:
        if drain:
            self.drain(timeout)
        else:
            self.abort()

    # ------------------------------------------------------------- submit

    def submit(self, kind: str, seq: str, annotations=None,
               deadline_s: Optional[float] = None,
               top_k: Optional[int] = None,
               head_id: Optional[str] = None,
               trace_id: Optional[str] = None) -> Future:
        """Enqueue one request; returns its future (which carries the
        trace id as `.pbt_request_id` when tracing is on — the FLEET
        id when a router propagated one via `trace_id`, so one id
        names the request end-to-end across processes). Raises
        SequenceTooLongError (on_long="reject", or a '?' beyond the
        window for predict_residues), UnknownHeadError (predict_task
        for an unregistered/removed head — the typed 404), and
        ServerClosedError synchronously; QueueFullError /
        DeadlineExceededError land on futures (the evicted/expired
        request's, which may be an earlier caller's — never silently
        dropped)."""
        if kind not in KINDS and kind not in (TASK_KIND, NEIGHBORS_KIND):
            raise ValueError(f"unknown request kind {kind!r}; have "
                             f"{KINDS + (TASK_KIND, NEIGHBORS_KIND)}")
        if kind == NEIGHBORS_KIND and self.index is None:
            raise ValueError(
                "this server has no neighbor index attached — start it "
                "with index= (pbt serve --index DIR) to serve "
                "/v1/neighbors")
        if self.decoder:
            if kind != "embed" or annotations is not None:
                raise ValueError(
                    f"kind {kind!r} / annotations are not built for the "
                    "decoder: its server answers `embed` of a document "
                    "of token ids")
            seq = self._document(seq)
        if not len(seq):
            raise ValueError("empty sequence")
        if (kind == TASK_KIND) != (head_id is not None):
            raise ValueError(
                f"head_id is required for kind {TASK_KIND!r} and invalid "
                "for every other kind")
        now0 = self.clock()
        trace = None
        if self.trace_sample_rate is not None:
            n = next(self._req_ids)
            trace = RequestTrace(
                f"{self._id_prefix}{n:x}", kind, now0,
                sampled=stride_sampled(n, self.trace_sample_rate))
            # Join the propagated fleet context (ISSUE 18): the
            # router-minted id becomes this trace's trace_id/parent,
            # and the replica identity rides every emitted event.
            trace.join(trace_id, self.replica_id)
            trace.head_id = head_id
            # Which executable arm will serve this request (`quant` on
            # serve_request events — the per-request A/B attribution
            # field; absent on the fp32 arm).
            if self.quant != "fp32":
                trace.quant = self.quant
        head = None
        if kind == TASK_KIND:
            try:
                head = self.dispatcher.get_head(head_id)
            except UnknownHeadError as exc:
                # Typed 404: the head was never added or was hot-
                # removed. Counted + traced like every other rejection.
                self._rej_c["unknown_head"].inc()
                self._bump("rejected_total", "unknown_head")
                self.tele.emit("serve_reject", reason="unknown_head",
                               kind=kind, queue_depth=len(self.queue),
                               head_id=head_id)
                self._seal(trace, "rejected", self.clock(),
                           kind=kind)
                if trace is not None:
                    exc.pbt_request_id = trace.public_id()
                raise
        window = self.cfg.data.seq_len - (0 if self.decoder else 2)
        if len(seq) > window:
            if (self.on_long == "reject"
                    or (kind == "predict_residues"
                        and inference.MASK_CHAR in seq[window:])):
                self._rej_c["too_long"].inc()
                self._bump("rejected_total", "too_long")
                self.tele.emit("serve_reject", reason="too_long",
                               kind=kind, queue_depth=len(self.queue))
                self._seal(trace, "rejected", self.clock(),
                           kind=kind)
                exc = SequenceTooLongError(
                    f"sequence of {len(seq)} residues exceeds the model "
                    f"window of {window}"
                    + (" (and masks a position the model would never "
                       "see)" if kind == "predict_residues" else
                       "; the server is configured to reject rather "
                       "than truncate"))
                if trace is not None:
                    # Synchronous rejections carry the trace id on the
                    # exception: the HTTP layer still answers with an
                    # X-PBT-Request-Id pinning the rejection's trace.
                    exc.pbt_request_id = trace.public_id()
                raise exc
            # The process-wide inference.TRUNCATED_TOTAL is bumped by
            # _tokenize_masked below (cache hits skip tokenization and
            # so don't count there); these are the serving-side counts.
            self._truncated_c.inc()
            self._bump("truncated_total")
        if annotations is not None:
            annotations = inference.check_annotations(
                np.asarray(annotations, np.float32)[None], 1, self.cfg)[0]
        self._req_c[kind].inc()
        future: Future = Future()
        if trace is not None:
            future.pbt_request_id = trace.public_id()
        key = None
        if self.cache.capacity:
            if trace is not None:
                trace.cache = "miss"
            # A head id is content-addressed over its weights + task +
            # trunk, so including it keys cached task results to the
            # exact model that produced them.
            if kind == NEIGHBORS_KIND:
                # Neighbor results depend on the exact index contents
                # (identity digest), the requested k, and the probe
                # breadth — all three scope the key, so a rebuilt index
                # or a different k can never alias a stale answer.
                scope = (f"{kind}:{self.index.digest[:16]}"
                         f":k{top_k or DEFAULT_NEIGHBORS_K}"
                         f":p{self.nprobe}")
            elif head is None:
                scope = kind
            else:
                scope = f"{kind}:{head.head_id}"
            key = content_key(scope, seq, annotations)
            hit = self.cache.get(key)
            if hit is not None:
                self._bump("cache_hit_returns")
                if trace is not None:
                    trace.cache = "hit"
                future.set_result(self._present(kind, hit, top_k))
                self._seal(trace, "cache_hit", self.clock(),
                           kind=kind)
                return future
        bucket_len = self.dispatcher.bucket_len(len(seq))
        if self.decoder:    # a document longer than the window keeps its head
            tokens = np.full(bucket_len, DECODER_PAD, np.int32)
            tokens[:min(len(seq), bucket_len)] = seq[:bucket_len]
        else:
            tokens = inference._tokenize_masked(
                [seq], self.cfg.data.seq_len,
                on_overflow="count")[0, :bucket_len]
        now = self.clock()
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        if trace is not None:
            trace.mark_enqueued(now)
        req = Request(
            kind=kind, seq=seq, tokens=tokens, bucket_len=bucket_len,
            future=future, enqueued_at=now, annotations=annotations,
            deadline=(now + deadline_s if deadline_s is not None else None),
            top_k=top_k, cache_key=key, trace=trace, head=head)
        try:
            evicted = self.queue.push(req)
        except ServerClosedError as exc:
            self._rej_c["closed"].inc()
            self._bump("rejected_total", "closed")
            self.tele.emit("serve_reject", reason="closed", kind=kind,
                           queue_depth=len(self.queue))
            self._seal(trace, "rejected", self.clock(), kind=kind)
            if trace is not None:
                exc.pbt_request_id = trace.public_id()
            raise
        if evicted:
            now2 = self.clock()
            for old in evicted:
                self._rej_c["queue_full"].inc()
                self._bump("rejected_total", "queue_full")
                self.tele.emit("serve_reject", reason="queue_full",
                               kind=old.kind,
                               queue_depth=self.queue.max_depth)
                self._seal(old.trace, "evicted", now2,
                           e2e_fallback=max(0.0, now2 - old.enqueued_at),
                           kind=old.kind)
        self._depth_g.set(len(self.queue))
        return future

    def _document(self, seq) -> np.ndarray:
        """A decoder request's document as int32 token ids of the
        vocabulary slice held here; anything else is the request's
        error."""
        try:
            ids = np.asarray(seq)
            if ids.ndim != 1 or ids.dtype.kind not in "iu":
                raise TypeError
        except (TypeError, ValueError):
            raise ValueError("a decoder request is a 1-D sequence of whole "
                             "token ids") from None
        vocab = self.cfg.model.vocab_size
        if ids.size and (ids.min() < 0 or ids.max() >= vocab):
            raise ValueError(f"token id outside the {vocab} rows of the "
                             "vocabulary this server holds")
        return ids.astype(np.int32)

    # -------------------------------------------------------- sync facade

    def embed(self, seq: str, annotations=None,
              timeout: Optional[float] = None,
              deadline_s: Optional[float] = None) -> Dict[str, np.ndarray]:
        """{"global": (G,), "local_mean": (C,)} float32 for one
        sequence — the serving form of inference.embed."""
        return self.submit("embed", seq, annotations,
                           deadline_s=deadline_s).result(timeout)

    def predict_go(self, seq: str, top_k: Optional[int] = None,
                   timeout: Optional[float] = None,
                   deadline_s: Optional[float] = None):
        """(A,) sigmoid probabilities, or the top-k
        [(annotation_index, prob), ...] list."""
        return self.submit("predict_go", seq, top_k=top_k,
                           deadline_s=deadline_s).result(timeout)

    def predict_residues(self, seq: str, timeout: Optional[float] = None,
                         deadline_s: Optional[float] = None):
        """(filled_seq, probs (bucket_len, V)) — '?' positions filled
        with the argmax amino acid, like inference.predict_residues."""
        return self.submit("predict_residues", seq,
                           deadline_s=deadline_s).result(timeout)

    def neighbors(self, seq: str, k: Optional[int] = None,
                  timeout: Optional[float] = None,
                  deadline_s: Optional[float] = None):
        """{"neighbors": [(corpus_id, cosine_score), ...]} best-first
        for one query sequence: the sequence embeds through the trunk
        (riding whatever micro-batch is forming), then its global
        vector probes the attached int8 IVF index. Requires a server
        started with `index=`."""
        return self.submit(NEIGHBORS_KIND, seq, top_k=k,
                           deadline_s=deadline_s).result(timeout)

    def predict_task(self, head_id: str, seq: str, annotations=None,
                     timeout: Optional[float] = None,
                     deadline_s: Optional[float] = None) -> np.ndarray:
        """One registered head's float32 output for one sequence:
        (L, num_outputs) logits for token_classification,
        (num_outputs,) logits for sequence_classification, (1,) value
        for sequence_regression — the serving form of
        heads/apply.predict_task_rows. The request rides whatever
        micro-batch is forming for its bucket, alongside requests for
        OTHER heads (one shared trunk pass, per-head tails)."""
        return self.submit(TASK_KIND, seq, annotations,
                           deadline_s=deadline_s,
                           head_id=head_id).result(timeout)

    # ------------------------------------------------------- finalization

    def _present(self, kind: str, value, top_k: Optional[int]):
        """Shape a cached/computed value for one caller (top_k is a
        per-request view over the cached full probability row)."""
        if kind == "predict_go" and top_k is not None:
            probs = value
            k = min(top_k, probs.shape[0])
            idx = np.argsort(-probs)[:k]
            return [(int(j), float(probs[j])) for j in idx]
        return value

    def _finalize(self, req: Request, row) -> None:
        """Scheduler callback: one request's raw model row → its result
        (+ cache insert). Runs on the finalize thread — the completer
        when pipeline_depth > 1, else the scheduler thread; exactly one
        of the two ever calls this (ISSUE 19)."""
        if req.kind == NEIGHBORS_KIND:
            # The embed leg already ran (dispatch served this request
            # as an embed row); the lookup leg probes the resident
            # index here, on the scheduler thread, and is timed into
            # its own `lookup` trace stage.
            g = np.asarray(row["global"])
            k = req.top_k if req.top_k else DEFAULT_NEIGHBORS_K
            t0 = self.clock()
            pairs = self.index.lookup_one(g, k=k, nprobe=self.nprobe)
            t1 = self.clock()
            if req.trace is not None:
                req.trace.mark_lookup(t1)
            if req.trace is not None and req.trace.sampled:
                self.tele.emit(
                    "neighbor_query", k=int(k), nprobe=self.nprobe,
                    candidates=min(
                        self.index.num_vectors,
                        self.nprobe * int(self.index.members.shape[1])),
                    lookup_s=round(max(0.0, t1 - t0), 9),
                    outcome="ok", request_id=req.trace.request_id)
            value = {"neighbors": pairs}
        elif req.kind == "embed":
            value = {"global": np.asarray(row["global"]),
                     "local_mean": np.asarray(row["local_mean"])}
        elif req.kind in ("predict_go", TASK_KIND):
            value = np.asarray(row)
        else:  # predict_residues: fill '?' via the argmax amino acid
            probs = np.asarray(row)
            value = (inference.fill_masked_residues(
                req.seq, probs, self.cfg.data.seq_len - 2), probs)
        if req.cache_key is not None:
            self.cache.put(req.cache_key, value)
        self.completed_total += 1
        if not req.future.done():
            req.future.set_result(self._present(req.kind, value, req.top_k))
        self._depth_g.set(len(self.queue))

    def _count_expiry(self, req: Request) -> None:
        """Scheduler callback per deadline-expired request: the expiry
        IS a rejection, so it must show in serve_rejected_total, stats,
        and the CLI's --max-requests accounting (the serve_reject event
        is emitted scheduler-side already)."""
        self._rej_c["deadline"].inc()
        self._bump("rejected_total", "deadline")

    def _observe_latency(self, seconds: float) -> None:
        """Scheduler callback per successfully batched row: one ring
        (the registry QuantileWindow) serves stats(), /metrics, and the
        percentile gauges — computed at read time, no refresh cadence
        to drift."""
        self.latencies.observe(seconds)
        self._latency_h.observe(seconds)

    def _on_complete(self, req: Request, outcome: str, now: float,
                     error: Optional[BaseException],
                     ctx: Optional[dict]) -> None:
        """Scheduler callback per terminal request (ok/error/expired):
        seal the trace, emit, feed the SLO evaluator."""
        self._seal(req.trace, outcome, now, error=error,
                   e2e_fallback=max(0.0, now - req.enqueued_at),
                   kind=req.kind)

    def _seal(self, trace: Optional[RequestTrace], outcome: str,
              now: float, error: Optional[BaseException] = None,
              e2e_fallback: float = 0.0,
              kind: Optional[str] = None) -> None:
        """The single terminal funnel: every request reaches this
        exactly once per outcome path. Emits the serve_request event +
        spans for sampled or failed requests; feeds every completion
        (traced or not) to the SLO evaluator. `kind` lets untraced
        requests still feed the per-kind outcome funnels (neighbors);
        traced requests fall back to the trace's own kind."""
        stages = None
        e2e = e2e_fallback
        rid = None
        if kind is None and trace is not None:
            kind = trace.kind
        if trace is not None:
            if not trace.finish(outcome, now, error):
                return  # already sealed by an earlier outcome path
            e2e = trace.e2e_s()
            rid = trace.request_id
            emit = trace.sampled or outcome not in ("ok", "cache_hit")
            if emit or self.slo:
                # Stage decomposition only when something consumes it:
                # a sampled-out request with no SLOs pays marks, not
                # dict-building (the <1%-of-latency contract).
                stages = trace.stages()
            if emit:
                self.tele.emit("serve_request",
                               **trace.event_fields(stages=stages))
                if self.tele.spans is not None:
                    trace.export_spans(self.tele.spans)
        if kind == NEIGHBORS_KIND:
            c = self._nbr_c.get(outcome)
            if c is not None:
                c.inc()
            with self._mirror_lock:
                self.neighbors_total[outcome] = \
                    self.neighbors_total.get(outcome, 0) + 1
        if self.slo:
            if stages is not None and trace.pad_fraction \
                    and "execute" in stages:
                # Synthetic attribution stage: the share of device time
                # spent computing padding — the ragged-serving lever.
                stages = dict(stages)
                stages["pad_wasted"] = round(
                    stages["execute"] * trace.pad_fraction, 9)
            self.slo.observe(outcome, e2e, stages=stages,
                             request_id=rid, now=now)

    # ------------------------------------------------------------- stats

    def stats(self) -> Dict[str, Any]:
        with self._mirror_lock:
            mirrors = {
                "cache_hit_returns": self.cache_hit_returns,
                "truncated": self.truncated_total,
                "rejected": dict(self.rejected_total),
            }
            neighbors_by_outcome = dict(self.neighbors_total)
        from proteinbert_tpu.kernels.attention import ATTN_PATH_TOTAL
        from proteinbert_tpu.kernels.fused_block import PATH_TOTAL
        from proteinbert_tpu.kernels.moe_rows import MOE_ROWS_PATH_TOTAL
        from proteinbert_tpu.kernels.one_pass import ONEPASS_PATH_TOTAL
        from proteinbert_tpu.kernels.segment_flash import CCA_CORE_PATH_TOTAL
        from proteinbert_tpu.kernels.ssd import SSD_CORE_PATH_TOTAL

        qw = self.scheduler.queue_wait
        # One coherent locked read of the dispatch counters: the
        # scheduler thread updates them under its lock (ISSUE 15
        # lock-discipline rule), so an unlocked field read here could
        # see a torn batches/rows pair mid-dispatch.
        batches, rows, expired = self.scheduler.stats_counts()
        class_counts, positions = self.scheduler.class_counts()
        out = {
            "completed": self.completed_total,
            **mirrors,
            "serve_mode": self.serve_mode,
            # Executable-zoo accounting (ISSUE 9): warm trunk-level
            # executables + cumulative warmup seconds — the numbers the
            # ragged mode's O(kinds) collapse is measured by.
            "executables": self.dispatcher.executable_count,
            "warmup_seconds": round(self.dispatcher.warmup_seconds_total,
                                    6),
            # Process-wide fused-kernel path coverage (trace-time, one
            # bump per executable): "path/reason" → executables built
            # on that path. "pallas/*" is the fast path; "reference/*"
            # the XLA composition (ISSUE 10 two-sided counter).
            "fused_path": {f"{p}/{r}": n
                           for (p, r), n in sorted(PATH_TOTAL.items())},
            # Same two-sided coverage for the ragged attention kernel
            # (kernels/attention.py, ISSUE 13).
            "attention_path": {f"{p}/{r}": n
                               for (p, r), n
                               in sorted(ATTN_PATH_TOTAL.items())},
            # One-pass trunk coverage (kernels/one_pass.py, ISSUE 16):
            # "pallas/*" means the whole block — local track AND
            # attention — ran as a single VMEM-resident kernel;
            # "reference/*" is the two-kernel composition fallback.
            "onepass_path": {f"{p}/{r}": n
                             for (p, r), n
                             in sorted(ONEPASS_PATH_TOTAL.items())},
            # The experts' loop's row movers (kernels/moe_rows.py):
            # "pallas/slabs" where a block's rows move as slabs by DMA,
            # "reference/*" where XLA's gather and scatter move them.
            "moe_rows_path": {f"{p}/{r}": n
                              for (p, r), n
                              in sorted(MOE_ROWS_PATH_TOTAL.items())},
            # The CCA mixer's attention core (ISSUE 35): "pallas/
            # grouped_keys" where it is the flash forward kernel reading
            # each key head for its group of query heads, "reference/*"
            # where plain jax over repeated keys.
            "cca_core_path": {f"{p}/{r}": n
                              for (p, r), n
                              in sorted(CCA_CORE_PATH_TOTAL.items())},
            # The Mamba-2 mixer's recurrence (ISSUE 44): "pallas/chunked"
            # where it is the kernel with a group's state in VMEM,
            # "reference/*" where the plain scan over chunks.
            "ssd_core_path": {f"{p}/{r}": n
                              for (p, r), n
                              in sorted(SSD_CORE_PATH_TOTAL.items())},
            # Quantized executable arm (ISSUE 12): which arm serves,
            # the measured weight-HBM footprint, and the worst sampled
            # parity deviation vs the fp32 shadow (None = fp32 arm).
            "quant": ({"mode": self.quant, **self.dispatcher.quant_report}
                      if self.quant != "fp32" else None),
            "heads": len(self.dispatcher.heads),
            "batches": batches,
            "batched_rows": rows,
            # Batches run by batch class (ragged: row class) and the
            # positions they computed, class x length each: the share
            # of batches under the largest class is how often a smaller
            # executable was enough, and real residues over
            # batched_positions is the fill the device really ran at.
            "batch_class_counts": class_counts,
            "batched_positions": positions,
            "queue_depth": len(self.queue),
            "evicted": self.queue.evicted_total,
            "expired": expired,
            "cache": self.cache.stats(),
            "latency": self.latencies.summary(),
            "queue_wait": {
                "count": qw.count,
                "mean_s": (round(qw.total / qw.count, 6)
                           if qw.count else None),
                "max_s": (round(qw.max, 6) if qw.count else None),
            },
            # Pipelined dispatch (ISSUE 19): window depth, the deepest
            # the window actually got (overlap observed ⇔ >= 2), and
            # the share of finalize seconds that overlapped device
            # compute of a later batch.
            "pipeline": self.scheduler.pipeline_stats(),
            # Blue-green rollout arms (ISSUE 20): per-arm fingerprints
            # + shadow-request count — the fields the fleet health
            # sweep joins on to flag a mixed-fingerprint fleet.
            "rollout": self.rollout_status(),
        }
        # Neighbor-index arm (ISSUE 17): which index serves, its size,
        # and how many distinct lookup shapes have compiled — the
        # "one warm executable per (nprobe, k)" evidence.
        out["neighbors"] = (None if self.index is None else {
            "index_digest": self.index.digest,
            "corpus_digest": self.index.corpus_digest,
            "num_vectors": self.index.num_vectors,
            "nprobe": self.nprobe,
            "lookup_executables": self.index.executables(),
            "by_outcome": neighbors_by_outcome,
        })
        # The decoder's expert counters over the batches run (ISSUE 33);
        # None for a model without experts.
        out["routing"] = (self.dispatcher.routing_stats() if self.decoder
                          else None)
        if self.slo:
            out["slo"] = self.slo.status()
        return out
