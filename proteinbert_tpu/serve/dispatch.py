"""Bucketed shape-class dispatch: one warm executable per shape.

The offline inference path compiles ONE static shape — (batch_size,
seq_len) — so a 40-residue query pays full-seq_len FLOPs. Online
traffic is ragged; the TPU-native answer (the Operator-Fusion inference
and Ragged Paged Attention papers, PAPERS.md) is a small, fixed family
of compiled shapes kept warm, with every request routed to the
cheapest one that fits:

- **length buckets** reuse the semantics of
  `data/dataset.make_bucketed_iterator` (ascending, last == seq_len;
  a row goes to the smallest bucket that fits its tokenized length) —
  the model is shape-parametric in L, so each bucket is just one more
  executable of the same jitted function;
- **batch classes** are a short ladder (powers of two up to
  `max_batch` by default): a micro-batch of r rows is padded up to the
  smallest class ≥ r, bounding both the executable count
  (|buckets| x |classes| per request kind) and the pad waste (< 2x).

`warmup()` compiles every (bucket_len, batch_class) pair up front so
no request ever pays a compile. With a `mesh`, batches are placed
batch-dim-sharded (`parallel/sharding.serve_batch_sharding`) before
dispatch, so a multi-chip server data-parallelizes each micro-batch.

`run_rows` is the OFFLINE entry (`inference.embed(..., bucketed=True)`):
group a whole token matrix by bucket, run each group at its bucket
length, reassemble in input order — with buckets=(seq_len,) the result
is bit-identical to the unbucketed `_batched` path because both feed
the same jitted kernels the same padded shapes.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from proteinbert_tpu.configs import DecoderConfig, PretrainConfig
from proteinbert_tpu.data.vocab import EOS_ID, PAD_ID, SOS_ID
from proteinbert_tpu import inference
from proteinbert_tpu.heads import apply as heads_apply
from proteinbert_tpu.heads.registry import LoadedHead, UnknownHeadError
from proteinbert_tpu.obs import tracing
from proteinbert_tpu.serve.errors import CandidateUnfitError, NoCandidateError

KINDS = ("embed", "predict_go", "predict_residues")


def _device_hbm_bytes() -> Optional[int]:
    """The accelerator's per-device memory budget in bytes
    (memory_stats()["bytes_limit"]). The CPU backend reports no stats
    (None) — candidate HBM pricing then only refuses against an
    explicit budget; an accelerator that fails to report raises."""
    stats = jax.local_devices()[0].memory_stats()
    if isinstance(stats, dict):
        limit = stats.get("bytes_limit")
        if isinstance(limit, int) and limit > 0:
            return limit
    return None

# The dynamic request kind (ISSUE 8): a predict_task request names a
# REGISTERED HEAD instead of a pretraining output. All predict_task
# requests — whatever head they carry — share one warm TRUNK executable
# per (bucket_len, batch_class) ("trunk" entries in `_warm`), plus a
# cheap per-head tail (heads/apply.head_batch) whose executable is
# shared by every head of the same structure. Adding a head NEVER adds
# a trunk compile (the executable-count-stays-flat contract,
# tests/test_heads.py).
TASK_KIND = "predict_task"

# What fills a decoder request's span past its document's end: every id
# of its vocabulary is a real token, 0 included.
DECODER_PAD = -1

# The ANN request kind (ISSUE 17): a `neighbors` request's DEVICE work
# is exactly an embed — the query rides the same warm embed executables
# (bucketed and packed) and only differs after host fetch, when the
# server probes the neighbor index with the returned global embedding.
# Both dispatchers therefore NORMALIZE it to "embed" on entry: same
# jitted fn, same `_warm` key, so serving neighbors adds zero compiles.
NEIGHBORS_KIND = "neighbors"


def resolve_buckets(cfg: PretrainConfig, buckets=None) -> Tuple[int, ...]:
    """Serving bucket boundaries: the explicit argument, else the
    config's training buckets (cfg.data.buckets), else the single
    full-length bucket. Same validity rules as the bucketed iterator:
    ints, strictly ascending, last == seq_len."""
    if buckets is None:
        buckets = cfg.data.buckets or (cfg.data.seq_len,)
    try:
        buckets = tuple(int(b) for b in buckets)
    except (TypeError, ValueError):
        raise ValueError(f"buckets must be ints, got {buckets!r}") from None
    if not buckets or sorted(set(buckets)) != list(buckets):
        raise ValueError(f"buckets must be strictly ascending, got {buckets}")
    if buckets[-1] != cfg.data.seq_len:
        raise ValueError(f"last bucket {buckets[-1]} must equal "
                         f"data.seq_len {cfg.data.seq_len}")
    if buckets[0] < 3:
        raise ValueError(f"smallest bucket {buckets[0]} cannot hold "
                         "<sos> + one residue + <eos>")
    return buckets


def default_batch_classes(max_batch: int, multiple: int = 1) -> Tuple[int, ...]:
    """Ascending power-of-two ladder capped by (and always containing)
    max_batch: 8 → (1, 2, 4, 8); 12 → (1, 2, 4, 8, 12). With
    `multiple` — a mesh's data*fsdp extent — every rung is a multiple
    of it so a served batch splits evenly across the replicas:
    (16, multiple=4) → (4, 8, 16)."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    if multiple < 1:
        raise ValueError(f"multiple must be >= 1, got {multiple}")
    if max_batch % multiple:
        raise ValueError(
            f"max_batch {max_batch} is not divisible by the mesh's "
            f"data*fsdp extent {multiple} — pick a max_batch the mesh "
            "can split evenly over the batch dim")
    classes = []
    c = multiple
    while c < max_batch:
        classes.append(c)
        c *= 2
    classes.append(max_batch)
    return tuple(classes)


MAX_ROW_CLASSES = 4


def default_row_classes(rows_per_batch: int, multiple: int = 1) -> Tuple[int, ...]:
    """The packed executable's row classes, ascending: R, R/2, R/4, R/8,
    each kept only while it is a whole number of rows that the mesh's
    data*fsdp extent `multiple` divides: 512 → (64, 128, 256, 512);
    6 → (3, 6); (8, multiple=2) → (2, 4, 8). Never more than
    MAX_ROW_CLASSES: every class is one more executable per request
    kind to warm and to hold. `rows_per_batch` itself has to split over
    the replicas, as in `default_batch_classes`."""
    default_batch_classes(rows_per_batch, multiple)  # the same refusals
    classes = [rows_per_batch]
    while (len(classes) < MAX_ROW_CLASSES and classes[-1] % 2 == 0
           and (classes[-1] // 2) % multiple == 0):
        classes.append(classes[-1] // 2)
    return tuple(reversed(classes))


class InFlightBatch:
    """Handle for one asynchronously dispatched micro-batch (ISSUE 19).

    `run_*_async` returns one of these immediately after the jitted
    call is ENQUEUED — JAX dispatch is async, so the device computes
    while the host moves on to form the next batch. Everything that
    blocks (the `np.asarray` host fetch, per-request fan-out, the quant
    parity shadow) lives in `finalize()`, which the scheduler's
    completer thread calls when it is ready to resolve the batch. The
    sync entries (`run_timed`/`run_packed_timed`) are literally
    submit + immediate finalize, so async and sync outputs are
    bit-identical by construction (gated by tools/pipeline_smoke.py
    and the bench `pipeline` phase).
    """

    __slots__ = ("rows", "timings", "_fetch", "_result")

    def __init__(self, rows: int, timings: Dict, fetch):
        self.rows = rows
        self.timings = timings
        self._fetch = fetch
        self._result = None

    def finalize(self):
        """Block for the device result (host fetch + fan-out + parity
        shadow) and return (outputs, timings) — the exact pair the sync
        entry returns. Idempotent: a second call returns the first
        call's result."""
        if self._fetch is not None:
            out = self._fetch()
            self._result = (out, self.timings)
            self._fetch = None
        return self._result


class BucketDispatcher:
    """Routes (kind, tokens, annotations) micro-batches to the warm
    executable of their shape class and returns trimmed host outputs."""

    def __init__(
        self,
        params,
        cfg: PretrainConfig,
        buckets: Optional[Sequence[int]] = None,
        max_batch: int = 8,
        batch_classes: Optional[Sequence[int]] = None,
        mesh=None,
        metrics=None,
        quant: str = "fp32",
        quant_parity_every: int = 0,
    ):
        from proteinbert_tpu.parallel.quant import SERVE_QUANT_MODES

        if quant not in SERVE_QUANT_MODES:
            raise ValueError(f"quant must be one of {SERVE_QUANT_MODES}, "
                             f"got {quant!r}")
        self.params = params
        self.cfg = cfg
        self.buckets = resolve_buckets(cfg, buckets)
        self.max_batch = int(max_batch)
        divisor = 1
        if mesh is not None:
            divisor = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
        if batch_classes is None:
            # Mesh-aware default: every rung divisible by the replica
            # count, so `pbt serve --mesh` works out of the box.
            batch_classes = default_batch_classes(self.max_batch, divisor)
        self.batch_classes = tuple(sorted(int(c) for c in set(batch_classes)))
        if self.batch_classes[-1] < self.max_batch:
            raise ValueError(
                f"largest batch class {self.batch_classes[-1]} cannot hold "
                f"a full micro-batch of {self.max_batch}")
        self.mesh = mesh
        self._shardings = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from proteinbert_tpu.parallel.sharding import serve_batch_sharding

            bad = [c for c in self.batch_classes if c % divisor]
            if bad:
                raise ValueError(
                    f"batch classes {bad} are not divisible by the mesh's "
                    f"data*fsdp extent {divisor} — a served batch shards "
                    "over the batch dim, so every compiled class must "
                    "split evenly across the replicas")
            self._shardings = serve_batch_sharding(mesh)
            # Replicate the trunk over the mesh devices. Orbax-restored
            # params arrive COMMITTED to one device, and a jitted call
            # mixing them with batch-dim-sharded inputs is an
            # "incompatible devices" error — so `pbt serve --mesh` from
            # any real run dir needs the explicit replicated placement
            # (batch-dim data parallelism is the serving layout; fresh
            # uncommitted params, as tests build, were merely lucky).
            self.params = jax.device_put(
                self.params, NamedSharding(mesh, PartitionSpec()))
        # Quantized executable arm (ISSUE 12): with quant != "fp32" the
        # dispatcher quantizes the trunk's weights ONCE at load time
        # (symmetric per-channel int8, parallel/quant.py) and every
        # request runs the quantized executables, which hold int8
        # weights in HBM and dequantize in-executable. The fp32 params
        # are kept resident too — they are the parity-shadow arm
        # (quant_parity_every) and the source of truth for head trunk
        # fingerprints. quant_report records the measured HBM-footprint
        # evidence; parity samples land in quant_parity_max /
        # `serve_quant_parity_max`.
        self.quant = quant
        self.quant_parity_every = int(quant_parity_every)
        # True while warmup() runs its dummy batches: quant parity
        # bookkeeping skips them (see _quant_batch_tick).
        self._warming = False
        self.qparams = None
        self.quant_report: Dict = {}
        self.quant_parity_max: Optional[float] = None
        self._quant_parity_g = (
            metrics.gauge("serve_quant_parity_max")
            if metrics is not None and quant != "fp32" else None)
        self._quant_batches = 0
        if quant != "fp32":
            from proteinbert_tpu.parallel.quant import (
                param_bytes, quantize_params,
            )

            fp32_bytes = param_bytes(self.params)
            qp = quantize_params(self.params)
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                qp = jax.device_put(
                    qp, NamedSharding(mesh, PartitionSpec()))
            self.qparams = qp
            q_bytes = param_bytes(self.qparams)
            if self.quant_parity_every <= 0:
                # No parity shadow → the fp32 trunk has no device-side
                # consumer (head fingerprints hash host values), so
                # PARK IT ON HOST: resident HBM holds only the int8
                # weights — the footprint claim, honored, and the
                # headroom a second resident trunk needs. With the
                # shadow on, both trunks stay resident by design
                # (docs/serving.md documents the cost).
                self.params = jax.tree.map(np.asarray, self.params)
            self.quant_report = {
                "mode": quant,
                "weight_bytes_fp32": fp32_bytes,
                "weight_bytes_quant": q_bytes,
                "weight_bytes_ratio": round(q_bytes / max(fp32_bytes, 1),
                                            4),
                "parity_every": self.quant_parity_every,
                "fp32_resident": ("device" if self.quant_parity_every > 0
                                  else "host"),
            }
        # Blue-green candidate arm (ISSUE 20): a SECOND trunk loaded
        # beside the resident one. `cand_*` serve shadow traffic until
        # flip() atomically swaps them in as the resident arm; the
        # outgoing trunk parks on HOST (`parked_*`) for instant
        # rollback. Every batch reads its arm through _arm_snapshot()
        # under this lock, so a flip can never tear a batch across two
        # trunks.
        self._arm_lock = threading.Lock()
        self.cand_params = None  # guarded-by: _arm_lock
        self.cand_qparams = None  # guarded-by: _arm_lock
        self.parked_params = None  # guarded-by: _arm_lock
        self.parked_qparams = None  # guarded-by: _arm_lock
        self.candidate_report: Dict = {}  # guarded-by: _arm_lock
        self._compile_hist = (metrics.histogram("serve_compile_seconds")
                              if metrics is not None else None)
        # Executable-zoo accounting (ISSUE 9 satellite): how many warm
        # executables this dispatcher holds and the cumulative seconds
        # warmup() spent building them — registry gauges so the ragged
        # path's compile-count/HBM reduction is a measured, trajectory-
        # tracked claim. Mirrored in plain attributes for callers with
        # no registry (bench, tests).
        self._exec_g = (metrics.gauge("serve_executable_count")
                        if metrics is not None else None)
        self._warmup_g = (metrics.gauge("serve_warmup_seconds_total")
                          if metrics is not None else None)
        self.warmup_seconds_total = 0.0
        # Warm-shape bookkeeping. Mutated by the scheduler thread per
        # batch and READ (iterated) from client/HTTP threads
        # (warm_head, trunk_executable_count) — iteration during a
        # concurrent add is a RuntimeError in CPython, so both sides
        # take the lock (negligible next to a model call).
        self._warm: set = set()
        self._warm_lock = threading.Lock()
        # Registered heads (ISSUE 8): head_id → LoadedHead with params
        # already on device. Mutated by hot add/remove from client
        # threads while the scheduler serves — guarded; requests carry
        # their OWN head reference from admission time, so a removal
        # only affects new submits (drain semantics, serve/server.py).
        self.heads: Dict[str, LoadedHead] = {}
        self._heads_lock = threading.Lock()
        self.warmup_report: Dict = {"trunk_executables": 0,
                                    "trunk_s": 0.0, "heads": {}}

    # ------------------------------------------------------------ routing

    def bucket_len(self, seq_len_residues: int) -> int:
        """Smallest bucket holding a sequence of this many residues
        (tokenized length = residues + <sos> + <eos>, capped at the
        model window like tokenization caps it)."""
        tok_len = min(seq_len_residues + 2, self.cfg.data.seq_len)
        i = int(np.searchsorted(self.buckets, tok_len))
        return self.buckets[i]

    def batch_class(self, rows: int) -> int:
        """Smallest compiled batch class that fits `rows`."""
        for c in self.batch_classes:
            if c >= rows:
                return c
        raise ValueError(f"{rows} rows exceed the largest batch class "
                         f"{self.batch_classes[-1]}")

    # ------------------------------------------------------ head registry

    @property
    def trunk_executable_count(self) -> int:
        """Warm shared-trunk executables — the number the multi-tenant
        contract says stays FLAT across head add/remove."""
        with self._warm_lock:
            return sum(1 for k in self._warm if k[0] == "trunk")

    @property
    def executable_count(self) -> int:
        """ALL warm trunk-level executables (every kind + the shared
        trunk) — the zoo the ragged dispatcher collapses to O(kinds)."""
        with self._warm_lock:
            return len(self._warm)

    def _note_warm(self, key) -> None:
        """Record one warm executable and keep the registry gauge (and
        therefore /metrics and the bench capture) in step."""
        with self._warm_lock:
            self._warm.add(key)
            n = len(self._warm)
        if self._exec_g is not None:
            self._exec_g.set(n)

    def _note_warmup_seconds(self, seconds: float) -> None:
        self.warmup_seconds_total += seconds
        if self._warmup_g is not None:
            self._warmup_g.set(round(self.warmup_seconds_total, 6))

    def add_head(self, head: LoadedHead, warm: bool = False) -> float:
        """Register a head for predict_task serving: parameters go to
        device once, and with `warm=True` (a live server) the head's
        tail is pre-run against every already-warm trunk shape — the
        PER-HEAD INCREMENTAL warmup cost, returned in seconds and
        recorded in `warmup_report["heads"]`. The trunk is never
        recompiled (asserted by tests/test_heads.py)."""
        if self.mesh is not None:
            # Same committed-params hazard as the trunk (see __init__):
            # registry-loaded head params arrive committed to one
            # device and must be replicated to join mesh-sharded
            # trunk outputs in the jitted tail.
            from jax.sharding import NamedSharding, PartitionSpec

            placed = jax.device_put(
                head.params, NamedSharding(self.mesh, PartitionSpec()))
        else:
            placed = jax.device_put(head.params)
        head = LoadedHead(head_id=head.head_id, name=head.name,
                          task=head.task, params=placed, meta=head.meta)
        with self._heads_lock:
            self.heads[head.head_id] = head
        return self.warm_head(head) if warm else 0.0

    def remove_head(self, head_id: str) -> LoadedHead:
        """Unregister a head; raises UnknownHeadError if absent. New
        submits for it 404 immediately; already-admitted requests hold
        their own reference and complete normally (drain semantics)."""
        with self._heads_lock:
            try:
                return self.heads.pop(head_id)
            except KeyError:
                raise UnknownHeadError(
                    f"no head {head_id!r} is registered on this "
                    "server") from None

    def get_head(self, head_id: str) -> LoadedHead:
        with self._heads_lock:
            try:
                return self.heads[head_id]
            except KeyError:
                raise UnknownHeadError(
                    f"no head {head_id!r} is registered on this server; "
                    f"have {sorted(self.heads)}") from None

    def list_heads(self) -> List[Dict]:
        with self._heads_lock:
            return [{"head_id": h.head_id, "name": h.name,
                     "kind": h.task.kind,
                     "num_outputs": h.task.num_outputs}
                    for h in self.heads.values()]

    def _dummy_batch(self, L: int, cls: int):
        tokens = np.full((cls, L), PAD_ID, np.int32)
        tokens[:, 0] = SOS_ID
        tokens[:, 1] = EOS_ID
        ann = np.zeros((cls, self.cfg.model.num_annotations), np.float32)
        return tokens, ann

    def warm_head(self, head: LoadedHead) -> float:
        """Compile one head's tail for every already-warm trunk shape;
        returns the incremental seconds. The tail is warmed on ZERO
        dummies of the trunk-output shapes (local (cls, L, C) / global
        (cls, G) in the compute dtype, pad_mask (cls, L) bool) — the
        identical tail executable, with NO trunk execution at all, so a
        control-plane hot add cannot spike the data plane's tail
        latency. The trunk never compiles here:
        `trunk_executable_count` is flat across this call."""
        with self._warm_lock:
            shapes = sorted({(k[1], k[2]) for k in self._warm
                             if k[0] == "trunk"})
        dtype = jnp.dtype(self.cfg.model.dtype)
        total = 0.0
        for L, cls in shapes:
            local = jnp.zeros((cls, L, self.cfg.model.local_dim), dtype)
            global_ = jnp.zeros((cls, self.cfg.model.global_dim), dtype)
            pad_mask = jnp.zeros((cls, L), bool)
            t0 = time.perf_counter()
            jax.block_until_ready(heads_apply.head_batch(
                head.params, local, global_, pad_mask, head.task.kind))
            total += time.perf_counter() - t0
        self.warmup_report["heads"][head.head_id] = round(total, 6)
        return total

    # ----------------------------------------------------------- execution

    def _fn(self, kind: str, quantized: Optional[bool] = None):
        """The jitted entry for one request kind — the quantized arm's
        (parallel/quant.py) when this dispatcher serves quantized,
        unless `quantized=False` asks for the fp32 shadow (parity
        sampling)."""
        if quantized is None:
            quantized = self.quant != "fp32"
        if quantized:
            from proteinbert_tpu.parallel.quant import quant_entry

            return quant_entry(kind, act=self.quant == "int8_act")
        if kind == "embed":
            return inference._encode_batch
        if kind == "predict_go":
            return inference._go_probs_batch
        if kind == "predict_residues":
            return inference._residue_probs_batch
        raise ValueError(f"unknown request kind {kind!r}; have {KINDS}")

    def _run_params(self, quantized: Optional[bool] = None):
        if quantized is None:
            quantized = self.quant != "fp32"
        return self.qparams if quantized else self.params

    def _trunk_fn(self, quantized: Optional[bool] = None):
        """The shared predict_task trunk entry — quantized arm when
        configured (head TAILS always run fp32 on the trunk's outputs:
        they are tiny, and per-head quantization would multiply
        artifacts; docs/serving.md)."""
        if quantized is None:
            quantized = self.quant != "fp32"
        if quantized:
            from proteinbert_tpu.parallel.quant import _q_trunk_batch

            return _q_trunk_batch
        return heads_apply.trunk_batch

    # ------------------------------------------------ blue-green arms

    def _replicate(self, tree):
        """Device placement for a trunk-sized tree: replicated over the
        mesh when one exists (the same committed-params hazard as
        __init__), handed to jit as-is otherwise."""
        if self.mesh is None:
            return tree
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(tree, NamedSharding(self.mesh,
                                                  PartitionSpec()))

    def _arm_snapshot(self, arm: str = "resident"):
        """One atomic read of (serving params, fp32 reference params)
        for an executable arm — THE flip-atomicity point (ISSUE 20).
        Every batch takes both trees in a single lock hold, so a
        concurrent flip() can never hand a batch the old serving arm
        with the new parity reference (or vice versa); batches already
        submitted keep the references they captured and finish on the
        trunk they started on."""
        with self._arm_lock:
            if arm == "resident":
                params, qp = self.params, self.qparams
            elif arm == "candidate":
                params, qp = self.cand_params, self.cand_qparams
                if params is None:
                    raise NoCandidateError(
                        "no candidate trunk is loaded on this replica "
                        "(load one with Server.load_candidate / "
                        "POST /v1/rollout/load)")
            else:
                raise ValueError(f"unknown executable arm {arm!r}; "
                                 "have ('resident', 'candidate')")
        return (qp if self.quant != "fp32" else params), params

    def load_candidate(self, params,
                       hbm_budget_bytes: Optional[int] = None) -> Dict:
        """Load a candidate trunk beside the resident one (ISSUE 20).

        The candidate must be STRUCTURALLY IDENTICAL to the resident
        trunk (same tree, shapes, dtypes) — that is what lets it ride
        the resident arm's compiled executables, which are keyed on
        shapes, not on which params they run. Under quant serving the
        candidate is quantized exactly like the resident arm (and its
        fp32 source parks on host when the resident fp32 does).

        HBM pricing: the device-resident bytes of BOTH arms are summed
        and checked against `hbm_budget_bytes` (explicit argument, else
        the backend's reported per-device limit, else unenforced) —
        `CandidateUnfitError` is the typed refusal when two trunks
        don't fit; the int8 arm's ~0.27x resident bytes are the
        headroom the second trunk rides in. Returns the candidate
        report (also kept for candidate_status())."""
        from proteinbert_tpu.parallel.quant import (
            param_bytes, quantize_params,
        )

        res_leaves = jax.tree.leaves(self.params)
        cand_leaves = jax.tree.leaves(params)
        if (jax.tree.structure(params) != jax.tree.structure(self.params)
                or any(a.shape != b.shape or a.dtype != b.dtype
                       for a, b in zip(res_leaves, cand_leaves))):
            raise ValueError(
                "candidate trunk does not match the resident trunk's "
                "parameter structure/shapes/dtypes — only a "
                "structurally identical trunk can ride the warm "
                "executables (shape-keyed compile cache)")
        cand_q = None
        if self.quant != "fp32":
            cand_q = self._replicate(quantize_params(params))
            if self.quant_parity_every <= 0:
                # Mirror the resident arm: no parity shadow → the
                # fp32 source parks on host, HBM holds int8 only.
                cand_store = jax.tree.map(np.asarray, params)
            else:
                cand_store = self._replicate(params)
            cand_dev = param_bytes(cand_q)
            if self.quant_parity_every > 0:
                cand_dev += param_bytes(cand_store)
            res_dev = param_bytes(self.qparams)
            if self.quant_parity_every > 0:
                res_dev += param_bytes(self.params)
        else:
            cand_store = self._replicate(params)
            cand_dev = param_bytes(cand_store)
            res_dev = param_bytes(self.params)
        budget = (hbm_budget_bytes if hbm_budget_bytes is not None
                  else _device_hbm_bytes())
        if budget is not None and res_dev + cand_dev > budget:
            raise CandidateUnfitError(
                f"candidate trunk needs {cand_dev} device bytes beside "
                f"the resident arm's {res_dev} ({res_dev + cand_dev} "
                f"total > HBM budget {budget}) — two fp32 trunks don't "
                "fit; serve --quant int8 (~0.27x resident bytes) to "
                "buy the headroom, or raise the budget")
        report = {
            "quant": self.quant,
            "weight_bytes_resident": int(res_dev),
            "weight_bytes_candidate": int(cand_dev),
            "hbm_budget_bytes": budget,
        }
        with self._arm_lock:
            self.cand_params = cand_store
            self.cand_qparams = cand_q
            self.candidate_report = dict(report)
        return report

    def warm_candidate(self) -> float:
        """Pre-run the candidate arm over every already-warm trunk-level
        shape. The executables are keyed on shapes/dtypes, not on the
        params they run, so the candidate boots THROUGH the compile
        cache — this pass proves that (zero new compiles; `_warm` and
        the executable gauge stay flat) and faults in the candidate's
        device placement before any shadow traffic arrives. Returns
        wall seconds."""
        with self._warm_lock:
            keys = sorted(self._warm)
        run_params, _ = self._arm_snapshot("candidate")
        t0 = time.perf_counter()
        self._warming = True
        try:
            for kind, L, cls in keys:
                tokens, ann = self._dummy_batch(L, cls)
                tb, ab = self._place(tokens, ann)
                fn = (self._trunk_fn() if kind == "trunk"
                      else self._fn(kind))
                jax.block_until_ready(
                    fn(run_params, tb, ab, self.cfg.model))
        finally:
            self._warming = False
        return time.perf_counter() - t0

    def flip(self) -> float:
        """Atomic promotion: the candidate becomes the resident arm in
        one lock hold — batches already submitted keep the params they
        captured (zero dropped, zero torn), batches submitted after
        this return see only the new trunk. The outgoing trunk parks on
        HOST (so HBM never holds three trunks) for instant rollback().
        Returns wall seconds (dominated by the device→host park
        fetch, which runs before the swap, outside the lock)."""
        t0 = time.perf_counter()
        with self._arm_lock:
            if self.cand_params is None:
                raise NoCandidateError(
                    "flip asked with no candidate trunk loaded")
            old_p, old_q = self.params, self.qparams
        # Park the outgoing arm on host BEFORE taking the swap lock:
        # in-flight batches read it concurrently (read-only), and the
        # swap itself stays O(pointer).
        parked = jax.tree.map(np.asarray, old_p)
        parked_q = (jax.tree.map(np.asarray, old_q)
                    if old_q is not None else None)
        with self._arm_lock:
            if self.cand_params is None:
                raise NoCandidateError(
                    "candidate trunk vanished mid-flip (concurrent "
                    "flip/unload)")
            self.params = self.cand_params
            self.qparams = self.cand_qparams
            self.cand_params = None
            self.cand_qparams = None
            self.parked_params = parked
            self.parked_qparams = parked_q
        return time.perf_counter() - t0

    def rollback(self) -> float:
        """Instant rollback: the parked trunk returns as the resident
        arm — bit-identical numerics, because the parked arrays are
        exact host copies of the pre-flip weights feeding the exact
        same executables — and the demoted trunk moves back to the
        candidate slot (still warm, so a fixed re-promotion does not
        reload). Raises NoCandidateError when nothing is parked."""
        t0 = time.perf_counter()
        with self._arm_lock:
            if self.parked_params is None:
                raise NoCandidateError(
                    "rollback asked with no parked trunk")
            demoted_p, demoted_q = self.params, self.qparams
            self.params = self._replicate(self.parked_params)
            self.qparams = (self._replicate(self.parked_qparams)
                            if self.parked_qparams is not None else None)
            self.cand_params = demoted_p
            self.cand_qparams = demoted_q
            self.parked_params = None
            self.parked_qparams = None
        return time.perf_counter() - t0

    def unload_candidate(self) -> bool:
        """Drop the candidate arm (rollout abort / gate refusal); the
        resident arm is untouched. Returns whether one was loaded."""
        with self._arm_lock:
            had = self.cand_params is not None
            self.cand_params = None
            self.cand_qparams = None
            self.candidate_report = {}
        return had

    def candidate_status(self) -> Dict:
        """Arm occupancy + the candidate report, one atomic read."""
        with self._arm_lock:
            return {"loaded": self.cand_params is not None,
                    "parked": self.parked_params is not None,
                    **self.candidate_report}

    def run_candidate(self, kind: str, tokens: np.ndarray,
                      annotations: Optional[np.ndarray] = None,
                      heads: Optional[Sequence[LoadedHead]] = None):
        """Run one micro-batch on the CANDIDATE arm, synchronously —
        the shadow-mirror entry (ISSUE 20). Identical prep/padding to
        `run` on the same warm executables (shape-keyed, so the
        candidate rides the resident arm's compiles), but nothing here
        touches the quant parity cadence or any live-path accounting."""
        result, _ = self.run_timed_async(
            kind, tokens, annotations, timed=False, heads=heads,
            arm="candidate").finalize()
        return result

    @staticmethod
    def _parity_max(a, b) -> float:
        """Max abs elementwise deviation between two same-structure
        outputs (dicts/arrays/lists of arrays) on host; boolean leaves
        (masks) are excluded — identical by construction, and their
        arithmetic difference is meaningless."""
        worst = 0.0
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            xa, ya = np.asarray(x), np.asarray(y)
            if xa.dtype == np.bool_ or ya.dtype == np.bool_:
                continue
            if xa.size:
                worst = max(worst, float(np.max(np.abs(
                    xa.astype(np.float32) - ya.astype(np.float32)))))
        return worst

    def _quant_batch_tick(self, timings: Dict) -> bool:
        """Per-batch quant bookkeeping shared by every dispatch path:
        stamp the arm onto the timings (UNCONDITIONALLY — the
        absent-means-fp32 event contract must hold on untimed batches
        too; the schedulers merge these fields from a timed=False
        run), advance the batch counter, and decide whether THIS batch
        runs the fp32 parity shadow. Warmup dummy batches are excluded
        entirely: they must neither consume the parity cadence nor
        count all-PAD compiles as LIVE parity samples."""
        if self.quant == "fp32" or self._warming:
            return False
        timings["quant"] = self.quant
        self._quant_batches += 1
        return (self.quant_parity_every > 0
                and (self._quant_batches - 1)
                % self.quant_parity_every == 0)

    def _shadow_parity(self, out, ref_thunk,
                       timings: Dict) -> None:
        """Run the fp32 shadow (`ref_thunk`), record the worst
        per-request deviation against `out` — the one implementation
        every (bucketed|ragged) x (kind|heads) path shares."""
        worst = self._parity_max(out, ref_thunk())
        self.quant_parity_max = max(self.quant_parity_max or 0.0, worst)
        self.quant_report["parity_max"] = round(self.quant_parity_max, 9)
        self.quant_report["parity_samples"] = (
            self.quant_report.get("parity_samples", 0) + 1)
        if self._quant_parity_g is not None:
            self._quant_parity_g.set(round(self.quant_parity_max, 9))
        timings["quant_parity_max"] = round(worst, 9)

    def _place(self, tokens: np.ndarray, annotations: np.ndarray):
        if self._shardings is None:
            return jnp.asarray(tokens), jnp.asarray(annotations)
        return (jax.device_put(tokens, self._shardings["tokens"]),
                jax.device_put(annotations, self._shardings["annotations"]))

    def run(self, kind: str, tokens: np.ndarray,
            annotations: Optional[np.ndarray] = None,
            heads: Optional[Sequence[LoadedHead]] = None):
        """Run one micro-batch: tokens (r, L) with L a bucket length,
        annotations (r, A) or None. Rows are padded up to the batch
        class, outputs come back trimmed to r on host.

        Returns {"global", "local_mean"} for "embed", (r, A) probs for
        "predict_go", (r, L, V) probs for "predict_residues". For
        "predict_task", `heads` carries row i's LoadedHead and the
        return is a list of r per-row float32 head outputs (shapes
        differ between heads of different task kinds).
        """
        result, _ = self.run_timed(kind, tokens, annotations,
                                   timed=False, heads=heads)
        return result

    def run_timed(self, kind: str, tokens: np.ndarray,
                  annotations: Optional[np.ndarray] = None,
                  timed: bool = True,
                  heads: Optional[Sequence[LoadedHead]] = None):
        """`run()` that also returns stage attribution for request
        traces: {"prep_s": pad + device placement, "device_s": model
        call through host fetch (the compile lands here on a cold
        shape), "finalize_s": the host-fetch share of device_s,
        "pad_fraction": padding share of the (batch_class, L) grid the
        executable actually ran — row padding up to the class plus
        token padding within rows}. Implemented as submit + immediate
        finalize of the async entry, so sync and pipelined dispatch
        share one code path (and therefore bit-identical outputs)."""
        return self.run_timed_async(kind, tokens, annotations,
                                    timed=timed, heads=heads).finalize()

    def run_timed_async(self, kind: str, tokens: np.ndarray,
                        annotations: Optional[np.ndarray] = None,
                        timed: bool = True,
                        heads: Optional[Sequence[LoadedHead]] = None,
                        arm: str = "resident",
                        batch: Optional[int] = None) -> InFlightBatch:
        """Submit one micro-batch and return an `InFlightBatch` as soon
        as the jitted call is enqueued (ISSUE 19). Validation, padding,
        device placement and the model call happen here on the calling
        (scheduler) thread; the blocking host fetch, head tails and the
        parity shadow run in the handle's `finalize()`. `arm` selects
        the trunk (ISSUE 20): "resident" is the live arm, "candidate"
        the blue-green shadow arm — both trees are read atomically via
        `_arm_snapshot`, so a concurrent flip never tears a batch.
        `batch` is the scheduler's sequence number, stamped on this
        batch's `serve.place` / `serve.launch` / `serve.fetch` spans
        (obs/tracing), whose durations are also what `timings` reports."""
        if kind == NEIGHBORS_KIND:
            kind = "embed"  # identical device work, shared executable
        rows, L = tokens.shape
        if L not in self.buckets:
            raise ValueError(f"tokens length {L} is not one of the "
                             f"buckets {self.buckets}")
        if (kind == TASK_KIND) != (heads is not None):
            raise ValueError(
                f"kind {kind!r} and heads={'set' if heads is not None else 'None'} "
                "do not agree: predict_task batches carry per-row heads, "
                "pretrain kinds never do")
        timings: Dict[str, float] = {}
        with tracing.span("serve.place", batch=batch) as placed:
            annotations = inference.check_annotations(annotations, rows,
                                                      self.cfg)
            cls = self.batch_class(rows)
            if timed:
                real = int((tokens != PAD_ID).sum())
                timings["pad_fraction"] = round(1.0 - real / (cls * L), 6)
            if rows < cls:
                tokens = np.pad(tokens, ((0, cls - rows), (0, 0)))
                annotations = np.pad(annotations,
                                     ((0, cls - rows), (0, 0)))
            tb, ab = self._place(tokens, annotations)
        if timed:
            timings["prep_s"] = round(placed.seconds, 9)
        run_params, ref_params = self._arm_snapshot(arm)
        parity_due = (arm == "resident"
                      and self._quant_batch_tick(timings))
        if heads is not None:
            # Multi-tenant path: ONE shared trunk executable for the
            # whole (possibly mixed-head) batch, then each distinct
            # head's cheap tail over the full batch — every row keeps
            # its own head's output (heads/apply.py). The tails ride
            # in the fetch closure: they are tiny, and the trunk — the
            # device work worth overlapping — is already in flight.
            with tracing.span("serve.launch", batch=batch):
                trunk_out = self._trunk_fn()(run_params, tb, ab,
                                             self.cfg.model)
            self._note_warm(("trunk", L, cls))

            def fetch():
                return heads_apply.apply_heads(trunk_out, heads)

            def reference():
                return heads_apply.apply_heads(
                    heads_apply.trunk_batch(ref_params, tb, ab,
                                            self.cfg.model), heads)
        else:
            fn = self._fn(kind)
            with tracing.span("serve.launch", batch=batch):
                res = fn(run_params, tb, ab, self.cfg.model)
            self._note_warm((kind, L, cls))

            def fetch():
                return jax.tree.map(lambda a: np.asarray(a)[:rows], res)

            def reference():
                return jax.tree.map(
                    lambda a: np.asarray(a)[:rows],
                    self._fn(kind, quantized=False)(
                        ref_params, tb, ab, self.cfg.model))

        def finalize_fetch():
            # `serve.fetch`: blocked on the device, then the copy to the
            # host (and, on a parity tick, the fp32 shadow). The rows
            # are split per request by the scheduler's seal loop.
            with tracing.span("serve.fetch", batch=batch) as fetched:
                out = fetch()
                if parity_due:
                    self._shadow_parity(out, reference, timings)
            if timed:
                timings["device_s"] = round(
                    (fetched.end_ns - placed.end_ns) * 1e-9, 9)
                timings["finalize_s"] = round(fetched.seconds, 9)
            return out

        return InFlightBatch(rows, timings, finalize_fetch)

    def warmup(self, kinds: Sequence[str] = ("embed",)) -> int:
        """Pre-compile every (bucket_len, batch_class) executable for the
        given kinds so no live request pays a compile; returns how many
        shape classes were warmed. Cost is |kinds| x |buckets| x
        |classes| compiles — keep `kinds` to what the deployment
        serves (the others compile lazily on first use).

        The predict_task family warms automatically whenever heads are
        registered (or "predict_task" is named in `kinds`): the SHARED
        trunk compiles once per (bucket, class) — counted in the return
        value and `warmup_report["trunk_executables"]` — and every
        registered head's tail is pre-run with its per-head incremental
        cost recorded in `warmup_report["heads"]`. Heads added LATER to
        a live server never recompile the trunk (`add_head(warm=True)`
        pays only the tail).

        Wall seconds spent here accumulate into the
        `serve_warmup_seconds_total` gauge (`warmup_seconds_total`
        attribute) and every warm shape lands in
        `serve_executable_count` — the executable-zoo accounting
        (ISSUE 9 satellite) the ragged dispatcher's O(kinds) claim is
        measured against."""
        t_warm = time.perf_counter()
        n = 0
        kinds = tuple(kinds)
        self._warming = True
        try:
            for kind in kinds:
                if kind == TASK_KIND:
                    continue
                if kind not in KINDS:
                    raise ValueError(f"unknown request kind {kind!r}; "
                                     f"have {KINDS + (TASK_KIND,)}")
                for L in self.buckets:
                    for cls in self.batch_classes:
                        if (kind, L, cls) in self._warm:
                            continue
                        dummy, _ = self._dummy_batch(L, cls)
                        with tracing.startup_span(
                                "startup.warmup", cls=cls, kind=kind,
                                bucket=L) as warmed:
                            self.run(kind, dummy)
                        if self._compile_hist is not None:
                            self._compile_hist.observe(warmed.seconds)
                        n += 1
            if TASK_KIND in kinds or self.heads:
                n += self._warmup_task()
        finally:
            self._warming = False
        self._note_warmup_seconds(time.perf_counter() - t_warm)
        return n

    def _warmup_task(self) -> int:
        """Warm the shared trunk once per (bucket, class) and every
        registered head's tail at each shape; returns NEW trunk
        executables warmed. Per-head seconds land in
        `warmup_report["heads"]` — on a warm trunk they are the cost of
        compiling one tiny matmul tail (and near-zero for a second head
        of the same structure, which shares the tail executable)."""
        report = self.warmup_report
        with self._heads_lock:
            heads = list(self.heads.values())
        n = 0
        for L in self.buckets:
            for cls in self.batch_classes:
                tokens, ann = self._dummy_batch(L, cls)
                tb, ab = self._place(tokens, ann)
                with self._warm_lock:
                    new = ("trunk", L, cls) not in self._warm
                with tracing.startup_span("startup.warmup", cls=cls,
                                          kind="trunk", bucket=L) as warmed:
                    trunk_out = self._trunk_fn()(self._run_params(), tb, ab,
                                                 self.cfg.model)
                    jax.block_until_ready(trunk_out)
                dt = warmed.seconds
                if new:
                    self._note_warm(("trunk", L, cls))
                    report["trunk_executables"] += 1
                    report["trunk_s"] = round(report["trunk_s"] + dt, 6)
                    if self._compile_hist is not None:
                        self._compile_hist.observe(dt)
                    n += 1
                for head in heads:
                    t0 = time.perf_counter()
                    jax.block_until_ready(heads_apply.head_batch(
                        head.params, trunk_out["local"],
                        trunk_out["global"], trunk_out["pad_mask"],
                        head.task.kind))
                    report["heads"][head.head_id] = round(
                        report["heads"].get(head.head_id, 0.0)
                        + time.perf_counter() - t0, 6)
        return n

    # ------------------------------------------------- offline batch path

    def run_rows(self, kind: str, tokens: np.ndarray,
                 annotations: Optional[np.ndarray], batch_size: int):
        """Offline whole-matrix entry: group (N, seq_len) rows by
        bucket, run each group at its bucket length in input-order
        chunks of `batch_size`, reassemble results by original row
        index. `predict_residues` probability tails beyond a row's
        bucket are zero-filled back to seq_len (pad positions)."""
        n = tokens.shape[0]
        annotations = inference.check_annotations(annotations, n, self.cfg)
        lengths = (tokens != PAD_ID).sum(axis=1)
        bucket_of = np.searchsorted(self.buckets, lengths)
        out: Dict[str, np.ndarray] = {}
        flat: Optional[np.ndarray] = None
        for b, L in enumerate(self.buckets):
            idx = np.flatnonzero(bucket_of == b)
            for lo in range(0, len(idx), batch_size):
                sel = idx[lo : lo + batch_size]
                res = self.run(kind, tokens[sel][:, :L], annotations[sel])
                if kind == "embed":
                    for k, v in res.items():
                        if k not in out:
                            out[k] = np.zeros((n,) + v.shape[1:], v.dtype)
                        out[k][sel] = v
                elif kind == "predict_go":
                    if flat is None:
                        flat = np.zeros((n, res.shape[1]), res.dtype)
                    flat[sel] = res
                else:  # predict_residues: zero-fill the pad tail
                    if flat is None:
                        flat = np.zeros(
                            (n, self.cfg.data.seq_len, res.shape[2]),
                            res.dtype)
                    flat[sel, :L] = res
        return out if kind == "embed" else flat


class RaggedDispatcher(BucketDispatcher):
    """Ragged PACKED dispatch (ISSUE 9 tentpole): warm executables per
    request kind at the shapes (row class, seq_len), consuming the
    training-side packed representation {tokens, segment_ids,
    annotations} (data/packing.py) instead of a (bucket_len,
    batch_class) ladder.

    Row classes (ISSUE 25): `rows_per_batch` is the LARGEST batch, not
    the only one. `batch_classes` is the short ladder R, R/2, R/4, R/8
    (`default_row_classes`: whole numbers the mesh's data*fsdp extent
    divides, at most four), and `PackedBatchScheduler` runs an
    under-full batch at the class that fits its open rows instead of
    padding every dispatch to R rows. Nothing in the packed programs
    depends on the row count: the local track is linear in rows and
    attention is per row.

    Requests are packed at BUCKET-QUANTIZED spans: a request's span is
    its `bucket_len` (same ladder as the bucketed dispatcher), its
    tokens `[<sos> seq <eos> <pad>...]` fill the span, and segment_ids
    cover the WHOLE span. That quantization is what makes ragged-mode
    outputs match the bucketed dispatcher's on identical traffic
    (within the documented jitted ≤1e-5 tolerance, PR 7 precedent):

    - the boundary-masked conv (`kernels/fused_block._segment_conv`)
      zeroes taps outside the span, which is EXACTLY the zero halo a
      'SAME'-padded conv sees at a (cls, bucket_len) array's edges —
      and in-span <pad> positions contribute their <pad> embeddings to
      nearby taps just as they do inside a bucketed row;
    - attention/pooling exclude in-span <pad> positions via the real-
      token mask, exactly as the bucketed path's pad_mask does.

    Unlike the bucketed ladder, the bucket set here costs NO
    executables — it is purely a span-quantization rule (the compiled
    width is always seq_len), so a deployment that prefers density over
    bucketed-parity can run a much denser ladder for free
    (docs/serving.md, ragged batching).

    Executable count: O(request kinds x at most 4 row classes) + the
    shared packed trunk for predict_task at each class + per-head-
    structure tails, versus the bucketed |buckets| x |classes| x kinds
    zoo — tracked by the same `serve_executable_count` gauge.

    The model is picked by the type of `cfg.model` (ISSUE 33): a
    `DecoderConfig` (the causal hybrid decoder, models/glm_moe.py) is
    served through the same rows, ladder and classes with `embed` alone.
    Its requests are documents of token ids with no special tokens (a
    span is the smallest of the ladder that holds the document, the
    rest of it marked by the id DECODER_PAD), its packed executable
    returns the batch's routing counters with the answers
    (`routing_stats`), and what is not built for it (int8, heads, the
    other kinds) is refused by name.
    """

    def __init__(
        self,
        params,
        cfg: PretrainConfig,
        buckets: Optional[Sequence[int]] = None,
        rows_per_batch: int = 4,
        max_segments: int = 8,
        mesh=None,
        metrics=None,
        quant: str = "fp32",
        quant_parity_every: int = 0,
    ):
        if rows_per_batch < 1:
            raise ValueError(f"rows_per_batch must be >= 1, "
                             f"got {rows_per_batch}")
        if max_segments < 1:
            raise ValueError(f"max_segments must be >= 1, "
                             f"got {max_segments}")
        if quant == "int8_act":
            raise ValueError(
                "quant='int8_act' is a bucketed-arm option: the packed "
                "executables have no activation fake-quant variant "
                "(use quant='int8' for weight-only quantized ragged "
                "serving — docs/serving.md)")
        # Mesh support (ISSUE 11 satellite, PR 8 residual): packed rows
        # shard over the joint ('data','fsdp') batch axis exactly like
        # bucketed micro-batches (serve_batch_sharding — segment_ids
        # shard like the tokens they annotate). rows_per_batch must
        # split evenly across the replicas (the parent ctor enforces it
        # and builds self._shardings); a smaller class that does not is
        # left out of the ladder.
        self.decoder = isinstance(cfg.model, DecoderConfig)
        if self.decoder and quant != "fp32":
            raise ValueError(
                f"quant={quant!r}: int8 weights are not built for the "
                "decoder (its weights are held in bfloat16)")
        divisor = 1
        if mesh is not None:
            divisor = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
        super().__init__(params, cfg, buckets=buckets,
                         max_batch=rows_per_batch,
                         batch_classes=default_row_classes(
                             rows_per_batch, divisor), mesh=mesh,
                         metrics=metrics, quant=quant,
                         quant_parity_every=quant_parity_every)
        self.rows_per_batch = int(rows_per_batch)
        self.max_segments = int(max_segments)
        # The decoder's routing counters, summed over the batches run
        # (warm-up's are not counted).
        self._routing_lock = threading.Lock()
        self._routing = {"batches": 0, "assignments_held": 0,  # guarded-by: _routing_lock
                         "dropped_assignments": 0, "block_rows": 0,
                         "real_tokens": 0, "load_max_over_mean_sum": 0.0}

    def bucket_len(self, length: int) -> int:
        """The decoder's documents carry no <sos> / <eos>."""
        if not self.decoder:
            return super().bucket_len(length)
        return self.buckets[int(np.searchsorted(
            self.buckets, min(length, self.cfg.data.seq_len)))]

    def add_head(self, head: LoadedHead, warm: bool = False) -> float:
        if self.decoder:
            raise ValueError("heads are not built for the decoder: its "
                             "server answers `embed` only")
        return super().add_head(head, warm=warm)

    def routing_stats(self) -> Optional[Dict]:
        """The decoder's counters over the batches run so far: batches,
        (token, slot) assignments that fell on held experts, those of
        them no block took, the rows of the blocks they filled
        (`assignments_held` over `block_rows`: the share of them that
        is real, which is what the experts' loop moves), real tokens,
        and the sum over batches of the fullest held expert's load over
        the mean (all expert layers pooled). None for a model without
        experts."""
        if not self.decoder:
            return None
        with self._routing_lock:
            return dict(self._routing)

    def _note_routing(self, routing) -> None:
        held = np.asarray(routing["held_counts"], np.float64)
        with self._routing_lock:
            r = self._routing
            r["batches"] += 1
            r["assignments_held"] += int(held.sum())
            r["dropped_assignments"] += int(routing["dropped"])
            r["block_rows"] += int(routing["block_rows"])
            r["real_tokens"] += int(routing["real_tokens"])
            r["load_max_over_mean_sum"] += float(
                held.max() / max(held.mean(), 1.0))

    # ----------------------------------------------------------- execution

    def _place_packed(self, tokens: np.ndarray, segment_ids: np.ndarray,
                      annotations: np.ndarray):
        """Host packed batch → device arrays, batch-dim-sharded over the
        mesh when one was passed (serve_batch_sharding)."""
        if self._shardings is None:
            return (jnp.asarray(tokens), jnp.asarray(segment_ids),
                    jnp.asarray(annotations))
        return (jax.device_put(tokens, self._shardings["tokens"]),
                jax.device_put(segment_ids, self._shardings["segment_ids"]),
                jax.device_put(annotations, self._shardings["annotations"]))

    def _over_mesh(self, entry):
        """`entry`, a packed executable of the encoder, as this
        dispatcher calls it: itself on one device; over a mesh, every
        replica running it on its own rows
        (parallel/sharding.on_each_replica: the entries hold a Mosaic
        kernel on a TPU, which the partitioner cannot split, and rows
        never mix). The decoder's entry, which sums its routing counters
        over the batch, never comes here and stays the partitioner's."""
        if self.mesh is None:
            return entry
        from proteinbert_tpu.parallel.sharding import on_each_replica

        return on_each_replica(entry, self.mesh)

    def _packed_fn(self, kind: str, quantized: Optional[bool] = None):
        if quantized is None:
            quantized = self.quant != "fp32"
        if self.decoder:
            if kind != "embed":
                raise ValueError(
                    f"request kind {kind!r} is not built for the decoder: "
                    "its server answers `embed` only")
            return inference._packed_decoder_embed_batch
        if quantized:
            from proteinbert_tpu.parallel.quant import quant_packed_entry

            return self._over_mesh(quant_packed_entry(kind))
        if kind == "embed":
            return self._over_mesh(inference._packed_encode_batch)
        if kind == "predict_go":
            return self._over_mesh(inference._packed_go_probs_batch)
        if kind == "predict_residues":
            return self._over_mesh(inference._packed_residue_probs_batch)
        raise ValueError(f"unknown request kind {kind!r}; have {KINDS}")

    def _packed_trunk_fn(self, quantized: Optional[bool] = None):
        if quantized is None:
            quantized = self.quant != "fp32"
        if quantized:
            from proteinbert_tpu.parallel.quant import (
                _q_packed_trunk_batch,
            )

            return self._over_mesh(_q_packed_trunk_batch)
        return self._over_mesh(heads_apply.packed_trunk_batch)

    def run_timed(self, *args, **kwargs):
        raise NotImplementedError(
            "RaggedDispatcher consumes packed batches only — use "
            "run_packed()/run_packed_timed() "
            "(serve/scheduler.PackedBatchScheduler builds them)")

    def run_timed_async(self, *args, **kwargs):
        raise NotImplementedError(
            "RaggedDispatcher consumes packed batches only — use "
            "run_packed_timed_async() "
            "(serve/scheduler.PackedBatchScheduler builds them)")

    def run_packed(self, kind: str, tokens: np.ndarray,
                   segment_ids: np.ndarray, annotations: np.ndarray,
                   riders: Sequence[Tuple[int, int, int, int]],
                   heads=None) -> List:
        outs, _ = self.run_packed_timed(kind, tokens, segment_ids,
                                        annotations, riders, heads=heads,
                                        timed=False)
        return outs

    def run_packed_timed(self, kind: str, tokens: np.ndarray,
                         segment_ids: np.ndarray, annotations: np.ndarray,
                         riders: Sequence[Tuple[int, int, int, int]],
                         heads=None, timed: bool = True):
        """Run one packed batch synchronously — submit + immediate
        finalize of `run_packed_timed_async`, so sync and pipelined
        dispatch share one code path (bit-identical outputs)."""
        return self.run_packed_timed_async(
            kind, tokens, segment_ids, annotations, riders, heads=heads,
            timed=timed).finalize()

    def run_packed_timed_async(self, kind: str, tokens: np.ndarray,
                               segment_ids: np.ndarray,
                               annotations: np.ndarray,
                               riders: Sequence[Tuple[int, int, int, int]],
                               heads=None, timed: bool = True,
                               arm: str = "resident",
                               batch: Optional[int] = None,
                               ) -> InFlightBatch:
        """Submit one packed batch through the kind's warm executable
        of its row class; the returned `InFlightBatch.finalize()` fans
        per-segment outputs back out after the host fetch (ISSUE 19).

        tokens/segment_ids are (c, seq_len) with c one of
        `batch_classes`, annotations (c, max_segments, A). `riders`
        carries one
        (row, segment_index, start, span) per request, row-major, with
        segment_index 0-based; for `predict_task`, `heads` is the
        aligned per-rider LoadedHead list. Returns (per-rider outputs
        aligned with `riders`, timings) — each output has the SAME
        shape the bucketed dispatcher returns for that request:
        {"global" (G,), "local_mean" (C,)} / (A,) probs /
        (span, V) probs / the rider's head output.
        """
        if kind == NEIGHBORS_KIND:
            kind = "embed"  # identical device work, shared executable
        R, L = tokens.shape
        if R not in self.batch_classes or L != self.cfg.data.seq_len:
            raise ValueError(
                f"packed tokens shape {(R, L)} is none of the compiled "
                f"({self.batch_classes}, {self.cfg.data.seq_len})")
        if (kind == TASK_KIND) != (heads is not None):
            raise ValueError(
                f"kind {kind!r} and "
                f"heads={'set' if heads is not None else 'None'} do not "
                "agree: predict_task batches carry per-rider heads, "
                "pretrain kinds never do")
        timings: Dict[str, float] = {}
        with tracing.span("serve.place", batch=batch) as placed:
            if timed:
                real = int(((segment_ids > 0) & (tokens != DECODER_PAD)).sum()
                           if self.decoder else (tokens != PAD_ID).sum())
                timings["pad_fraction"] = round(1.0 - real / (R * L), 6)
                timings["segments"] = len(riders)
                timings["segments_per_row"] = round(len(riders) / R, 4)
            tb, sb, ab = self._place_packed(tokens, segment_ids,
                                            annotations)
        if timed:
            timings["prep_s"] = round(placed.seconds, 9)
        run_params, ref_params = self._arm_snapshot(arm)
        parity_due = (arm == "resident"
                      and self._quant_batch_tick(timings))

        # riders are row-major: the last one sits in the last real row
        rows = riders[-1][0] + 1 if len(riders) else 0
        if heads is not None:
            with tracing.span("serve.launch", batch=batch, rows=rows,
                              cls=R):
                trunk_out = self._packed_trunk_fn()(
                    run_params, tb, sb, ab, self.cfg.model)
            self._note_warm(("trunk", L, R))
            tails = [(h,) + tuple(r) for h, r in zip(heads, riders)]

            def fetch():
                return heads_apply.apply_heads_packed(trunk_out, tails)

            def fan_out(outs):  # the tails come back one per rider
                return outs

            def reference():
                return heads_apply.apply_heads_packed(
                    self._packed_trunk_fn(quantized=False)(
                        ref_params, tb, sb, ab, self.cfg.model), tails)
        else:
            fn = self._packed_fn(kind)
            tracing.note_program(fn.__name__, fn, (
                run_params, tb, sb, ab, self.cfg.model))
            with tracing.span("serve.launch", batch=batch, rows=rows,
                              cls=R):
                res = fn(run_params, tb, sb, ab, self.cfg.model)
            self._note_warm((kind, L, R))

            def fetch():
                host = jax.tree.map(np.asarray, res)
                if self.decoder and not self._warming:
                    self._note_routing(host["routing"])
                return host

            def fan_out(host):
                fanned = []
                for row, seg, start, span_len in riders:
                    if kind == "embed":
                        fanned.append(
                            {"global": host["global"][row, seg],
                             "local_mean": host["local_mean"][row, seg]})
                    elif kind == "predict_go":
                        fanned.append(host[row, seg])
                    else:  # predict_residues: the span lines up with the
                        # bucketed (bucket_len, V) output
                        fanned.append(host[row, start:start + span_len])
                return fanned

            def reference():
                return fan_out(jax.tree.map(
                    np.asarray,
                    self._packed_fn(kind, quantized=False)(
                        ref_params, tb, sb, ab, self.cfg.model)))

        def finalize_fetch():
            # `serve.fetch`: blocked on the device, then the copy to the
            # host. `serve.fan_out`: one output per rider (and, on a
            # parity tick, the fp32 shadow).
            with tracing.span("serve.fetch", batch=batch) as fetched:
                host = fetch()
            with tracing.span("serve.fan_out", batch=batch) as fanned:
                outs = fan_out(host)
                if parity_due:
                    self._shadow_parity(outs, reference, timings)
            if timed:
                timings["device_s"] = round(
                    (fanned.end_ns - placed.end_ns) * 1e-9, 9)
                timings["finalize_s"] = round(
                    (fanned.end_ns - fetched.start_ns) * 1e-9, 9)
            return outs

        return InFlightBatch(len(riders), timings, finalize_fetch)

    # ------------------------------------------------------------- warmup

    def _dummy_packed(self, rows: Optional[int] = None):
        """One syntactically valid packed batch of `rows` rows (default
        rows_per_batch; a minimal-span segment per row) — content is
        irrelevant to the compile."""
        R = self.rows_per_batch if rows is None else rows
        L = self.cfg.data.seq_len
        span = self.buckets[0]
        if self.decoder:
            seg = np.zeros((R, L), np.int32)
            seg[:, :span] = 1
            return (np.zeros((R, L), np.int32), seg,
                    np.zeros((R, self.max_segments, 0), np.float32),
                    [(r, 0, 0, span) for r in range(R)])
        tokens = np.full((R, L), PAD_ID, np.int32)
        tokens[:, 0] = SOS_ID
        tokens[:, 1] = EOS_ID
        seg = np.zeros((R, L), np.int32)
        seg[:, :span] = 1
        ann = np.zeros((R, self.max_segments,
                        self.cfg.model.num_annotations), np.float32)
        riders = [(r, 0, 0, span) for r in range(R)]
        return tokens, seg, ann, riders

    def warm_candidate(self) -> float:
        """Pre-run the candidate arm over the warm PACKED executables,
        each on a dummy of its own row class — same zero-new-compiles
        contract as the bucketed override (the packed fns are
        shape-keyed too). Returns wall seconds."""
        with self._warm_lock:
            keys = sorted(self._warm, key=lambda k: (k[2], k[0]))
        run_params, _ = self._arm_snapshot("candidate")
        t0 = time.perf_counter()
        self._warming = True
        try:
            for cls, group in itertools.groupby(keys, key=lambda k: k[2]):
                tokens, seg, ann, _riders = self._dummy_packed(cls)
                placed = self._place_packed(tokens, seg, ann)
                for kind, _L, _cls in group:
                    fn = (self._packed_trunk_fn() if kind == "trunk"
                          else self._packed_fn(kind))
                    jax.block_until_ready(
                        fn(run_params, *placed, self.cfg.model))
        finally:
            self._warming = False
        return time.perf_counter() - t0

    def run_candidate(self, *args, **kwargs):
        raise NotImplementedError(
            "RaggedDispatcher consumes packed batches only — use "
            "run_packed_candidate() (serve/server.shadow_submit builds "
            "the single-rider packed batch)")

    def run_packed_candidate(self, kind: str, tokens: np.ndarray,
                             segment_ids: np.ndarray,
                             annotations: np.ndarray,
                             riders: Sequence[Tuple[int, int, int, int]],
                             heads=None) -> List:
        """`run_packed` on the CANDIDATE arm — the ragged shadow-mirror
        entry (see the bucketed `run_candidate`)."""
        outs, _ = self.run_packed_timed_async(
            kind, tokens, segment_ids, annotations, riders, heads=heads,
            timed=False, arm="candidate").finalize()
        return outs

    def warmup(self, kinds: Sequence[str] = ("embed",)) -> int:
        """Pre-compile the packed executable of every row class for
        every kind (plus the shared packed trunk + per-head tails when
        heads are in play); returns how many were warmed. Compare with
        the bucketed dispatcher's |kinds| x |buckets| x |classes| —
        this is the executable-zoo collapse the
        `serve_executable_count` gauge measures. Largest class first:
        the first call under a program's name is the one
        `tracing.note_program` keeps, and the full batch is the shape a
        saturated server runs."""
        t_warm = time.perf_counter()
        n = 0
        kinds = tuple(kinds)
        L = self.cfg.data.seq_len
        for kind in kinds:
            if kind != TASK_KIND and kind not in KINDS:
                raise ValueError(f"unknown request kind {kind!r}; "
                                 f"have {KINDS + (TASK_KIND,)}")
        self._warming = True
        try:
            for cls in reversed(self.batch_classes):
                with self._warm_lock:
                    cold = [k for k in kinds if k != TASK_KIND
                            and (k, L, cls) not in self._warm]
                if not cold:
                    continue
                tokens, seg, ann, riders = self._dummy_packed(cls)
                for kind in cold:
                    with tracing.startup_span("startup.warmup", cls=cls,
                                              kind=kind) as warmed:
                        self.run_packed(kind, tokens, seg, ann, riders)
                    if self._compile_hist is not None:
                        self._compile_hist.observe(warmed.seconds)
                    n += 1
            if TASK_KIND in kinds or self.heads:
                n += self._warmup_task()
        finally:
            self._warming = False
        self._note_warmup_seconds(time.perf_counter() - t_warm)
        return n

    def _warmup_task(self) -> int:
        """Warm the shared PACKED trunk at every row class and every
        registered head's packed tail on each; returns new trunk
        executables."""
        report = self.warmup_report
        with self._heads_lock:
            heads = list(self.heads.values())
        L = self.cfg.data.seq_len
        n = 0
        for cls in reversed(self.batch_classes):
            tokens, seg, ann, _ = self._dummy_packed(cls)
            tb, sb, ab = self._place_packed(tokens, seg, ann)
            with self._warm_lock:
                new = ("trunk", L, cls) not in self._warm
            with tracing.startup_span("startup.warmup", cls=cls,
                                      kind="trunk") as warmed:
                trunk_out = self._packed_trunk_fn()(
                    self._run_params(), tb, sb, ab, self.cfg.model)
                jax.block_until_ready(trunk_out)
            dt = warmed.seconds
            if new:
                self._note_warm(("trunk", L, cls))
                report["trunk_executables"] += 1
                report["trunk_s"] = round(report["trunk_s"] + dt, 6)
                if self._compile_hist is not None:
                    self._compile_hist.observe(dt)
                n += 1
            for head in heads:
                t0 = time.perf_counter()
                jax.block_until_ready(heads_apply.packed_head_batch(
                    head.params, trunk_out["local"], trunk_out["global"],
                    trunk_out["seg_mask"], head.task.kind))
                report["heads"][head.head_id] = round(
                    report["heads"].get(head.head_id, 0.0)
                    + time.perf_counter() - t0, 6)
        return n

    def warm_head(self, head: LoadedHead) -> float:
        """Compile one head's PACKED tail against every warm packed
        trunk shape on zero dummies — no trunk execution, the same
        control-plane/data-plane separation as the bucketed
        `warm_head`. The trunk never compiles here."""
        with self._warm_lock:
            classes = sorted(k[2] for k in self._warm if k[0] == "trunk")
        dtype = jnp.dtype(self.cfg.model.dtype)
        L, S = self.cfg.data.seq_len, self.max_segments
        total = 0.0
        for cls in classes:
            local = jnp.zeros((cls, L, self.cfg.model.local_dim), dtype)
            global_ = jnp.zeros((cls, S, self.cfg.model.global_dim), dtype)
            seg_mask = jnp.zeros((cls, S, L), bool)
            t0 = time.perf_counter()
            jax.block_until_ready(heads_apply.packed_head_batch(
                head.params, local, global_, seg_mask, head.task.kind))
            total += time.perf_counter() - t0
        self.warmup_report["heads"][head.head_id] = round(total, 6)
        return total
