"""Step-time / throughput / MFU accounting.

The reference logs raw per-iteration wall-clock only (reference
utils.py:284,306-313). The north-star metric for this build is
residues/sec/chip and MFU (BASELINE.json), which needs an analytic FLOPs
model of the conv+attention hybrid — per-block shapes in SURVEY §3.4.

All matmul/conv terms count 2·MACs; training ≈ 3× forward (fwd + 2×bwd).
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax

from proteinbert_tpu.configs import ModelConfig

# Peak dense FLOPs/s per chip (bf16), by jax device_kind substring
# (Google Cloud TPU documentation, per-chip figures). A device that is
# not in the table is an error, never a default: an MFU over a guessed
# peak is not a measurement.
PEAK_FLOPS = {
    "v5 lite": 197e12,     # TPU v5e (device_kind "TPU v5 lite")
    "v5e": 197e12,
    "v5p": 459e12,
    "v4": 275e12,
    "v6 lite": 918e12,     # TPU v6e (Trillium)
    "v6e": 918e12,
    "cpu": 5e11,           # nominal, the CPU's own entry: a CPU run's
                           # "MFU" is a sanity ratio, not a device metric
}


def forward_flops(cfg: ModelConfig, batch: int, seq_len: int,
                  nonpad_tokens: Optional[float] = None) -> float:
    """Analytic forward-pass FLOPs (2·MACs) for one batch.

    `nonpad_tokens` (total real tokens in the batch, default B·L) makes
    the estimate reflect the ACTUAL per-batch work rather than the
    padded shape: every L-proportional term — the convs, the local
    dense/head, the attention K/V/score/sum — scales with real tokens,
    since pad FLOPs produce no useful output. This is the honest
    denominator for pad-adjusted MFU (ISSUE 4 satellite): a 70%-pad
    batch at the padded count reports an MFU three times the
    useful-work utilisation.
    """
    B, L = batch, seq_len
    C, G, A = cfg.local_dim, cfg.global_dim, cfg.num_annotations
    H, k = cfg.num_heads, cfg.key_dim
    v = cfg.value_dim
    K = cfg.narrow_kernel
    # Total real-token count; L-proportional terms use T where the
    # padded-shape expression has B·L.
    T = float(B * L if nonpad_tokens is None else nonpad_tokens)

    per_block = (
        2 * T * K * C * C              # narrow conv (modules.py:126 analogue)
        + 2 * T * cfg.wide_kernel * C * C  # wide dilated conv
        + 2 * B * G * C                # global->local broadcast dense
        + 2 * T * C * C                # local residual dense
        + 2 * B * G * G                # global dense 1
        + 2 * B * H * G * k            # attention q
        + 2 * T * H * C * k            # attention K
        + 2 * T * H * C * v            # attention V
        + 2 * H * T * k                # scores
        + 2 * H * T * v                # weighted sum
        + 2 * B * G * G                # global dense 2
    )
    io = (
        2 * B * A * G                  # global input dense
        + 2 * T * C * cfg.vocab_size   # local head
        + 2 * B * G * A                # global head
    )
    return float(cfg.num_blocks * per_block + io)


def train_flops(cfg: ModelConfig, batch: int, seq_len: int,
                nonpad_tokens: Optional[float] = None) -> float:
    return 3.0 * forward_flops(cfg, batch, seq_len, nonpad_tokens)


def peak_flops_per_chip(device: Optional[jax.Device] = None) -> float:
    if device is None:
        device = jax.devices()[0]
    kind = device.device_kind.lower()
    for pat, val in PEAK_FLOPS.items():
        if pat in kind:
            return val
    raise ValueError(
        f"no peak FLOP/s entry for device_kind {device.device_kind!r} "
        f"(platform {device.platform!r}); add it to "
        "train/metrics.PEAK_FLOPS with its source")


class DeviceMetricAccumulator:
    """Sum per-batch DEVICE metric dicts without one device→host
    roundtrip per batch.

    Scalars stay on device; every `drain_every` add()s the pending
    dicts are fetched in ONE device_get and folded into host float
    sums. The drain doubles as dispatch backpressure (it blocks until
    those batches' computations finish) and bounds buffer growth to
    O(drain_every) — the per-scalar float(v) pattern this replaces paid
    ~10 device→host roundtrips per batch across the trainer eval bracket
    and both fine-tune loops.
    Host-side float summation preserves float64 accumulation numerics.
    """

    def __init__(self, drain_every: int = 8):
        # drain_every=0 defers EVERY fetch to sums(): the overlapped-eval
        # dispatch path wants zero mid-loop device syncs (the single
        # resolve-time device_get is the only host block). Memory then
        # grows with the batch count — fine for eval splits, do not use
        # for unbounded streams.
        self.drain_every = drain_every
        self._pending: list = []
        self._sums: Dict[str, float] = {}
        self.count = 0

    def add(self, m: Dict[str, jax.Array], weight: float = 1.0,
            key_fn=None) -> None:
        self._pending.append((m, weight, key_fn))
        self.count += 1
        if self.drain_every and len(self._pending) >= self.drain_every:
            self._drain()

    def _drain(self) -> None:
        if not self._pending:
            return
        fetched = jax.device_get([m for m, _, _ in self._pending])
        for (_, w, key_fn), m in zip(self._pending, fetched):
            for k, v in m.items():
                key = key_fn(k) if key_fn else k
                self._sums[key] = self._sums.get(key, 0.0) + float(v) * w
        self._pending = []

    def sums(self) -> Dict[str, float]:
        self._drain()
        return dict(self._sums)


class StepTimer:
    """Wall-clock meter → steps/s, residues/s/chip, MFU.

    `update()` once per host-side step loop iteration; the first
    `warmup_steps` are excluded (compile + cache warmup).

    Each `summary()` reports TWO rates: the cumulative-since-warmup rate
    (the honest whole-run number) and a `window_*` rate covering only the
    steps since the previous `summary()` call. The window is what a live
    operator needs: a transient stall permanently depresses every later
    cumulative line (the round-3 sustained run re-reported one early
    stall for 4,000 steps — VERDICT r3 Weak #2), while the window rate
    recovers on the next log line and distinguishes "currently slow"
    from "was slow once". `summary()` therefore ADVANCES the window
    anchor — call it once per log cadence.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        batch: int,
        seq_len: int,
        n_chips: int = 1,
        warmup_steps: int = 2,
    ):
        # No MFU for a model this module has no count for (the decoder:
        # what a packed batch of it needs depends on its documents and
        # its routing, which `benchmark/lm_flops.py` counts batch by batch).
        self.flops_per_step = (train_flops(cfg, batch, seq_len)
                               if isinstance(cfg, ModelConfig) else None)
        self.residues_per_step = batch * seq_len
        self.n_chips = max(n_chips, 1)
        self.warmup_steps = warmup_steps
        self.peak = peak_flops_per_chip()
        self._count = 0
        self._t0 = None
        self._t_last = None
        self._steps_timed = 0
        # Window anchor: None means "window starts at _t0" (first window
        # after warmup); advanced to the last summary()'s snapshot after.
        self._win_t = None
        self._win_steps = 0
        # Overlap account: boundary seconds that ran HIDDEN behind the
        # train stream (staged checkpoint fetch+write). Unlike
        # discount(), these do NOT shift the anchors — the wall clock
        # never stopped for them, so the window stays honest with them
        # in; the account exists so the hidden cost is REPORTED (the
        # counterfactual stall a synchronous boundary would have paid),
        # not bookkept away.
        self._overlap_s = 0.0
        self._win_overlap_s = 0.0

    def discount(self, seconds: float) -> None:
        """Remove non-training wall time (an eval pass, a blocking save)
        from the measured interval so throughput/MFU stay honest."""
        if self._t0 is not None:
            self._t0 += seconds
            if self._win_t is not None:
                # The discounted wait also falls inside the current
                # window — shift its anchor the same way, else the
                # window charges the eval/save the cumulative rate
                # just excluded.
                self._win_t += seconds

    def overlap(self, seconds: float) -> None:
        """Record boundary work that executed CONCURRENTLY with training
        (a staged checkpoint's device→host fetch + write). The anchors
        do not move — hidden seconds cost no wall time — but summary()
        reports them (`overlap_s` / `window_overlap_s`) so the overlap
        win is measured, not assumed, and the wall-gap attribution tool
        can tell an overlapped boundary from a stop-the-world one."""
        if seconds > 0:
            self._overlap_s += seconds
            self._win_overlap_s += seconds

    def sync(self) -> None:
        """Extend the measured window to now. Call right after a
        device→host fetch that drained the dispatch queue: the per-step
        `update()` timestamps only measure host ENQUEUE rate (dispatch
        is async), so without this the first log windows report
        enqueue throughput — physically impossible MFUs — not device
        throughput. A drain that lands before any step has been timed
        re-anchors the window START instead: the backlog being waited
        on there is compile/warmup work, which must not be charged to
        the first timed window."""
        if self._t0 is None:
            return
        if self._steps_timed:
            self._t_last = time.perf_counter()
        else:
            self._t0 = time.perf_counter()

    def update(self) -> None:
        self._count += 1
        if self._count == self.warmup_steps:
            self._t0 = time.perf_counter()
        elif self._count > self.warmup_steps:
            self._steps_timed = self._count - self.warmup_steps
            # Snapshot here, not in summary(): work done AFTER the last
            # step (final checkpoint save, host teardown) must not
            # deflate the reported throughput/MFU.
            self._t_last = time.perf_counter()

    def _rates(self, steps: int, dt: float, prefix: str) -> Dict[str, float]:
        steps_per_sec = steps / dt
        rates = {
            f"{prefix}steps_per_sec": steps_per_sec,
            f"{prefix}step_ms": 1000.0 / steps_per_sec,
            f"{prefix}residues_per_sec_per_chip": steps_per_sec
            * self.residues_per_step / self.n_chips,
        }
        if self.flops_per_step is not None:
            rates[f"{prefix}mfu"] = (steps_per_sec * self.flops_per_step
                                     / (self.peak * self.n_chips))
        return rates

    def summary(self) -> Dict[str, float]:
        if not self._steps_timed or self._t0 is None:
            return {}
        out = self._rates(self._steps_timed, self._t_last - self._t0, "")
        win_steps = self._steps_timed - self._win_steps
        win_dt = self._t_last - (self._win_t if self._win_t is not None
                                 else self._t0)
        if win_steps > 0 and win_dt > 0:
            out.update(self._rates(win_steps, win_dt, "window_"))
        if self._overlap_s:
            out["overlap_s"] = self._overlap_s
            out["window_overlap_s"] = self._win_overlap_s
        # Close the window: the next summary() measures from here.
        self._win_t = self._t_last
        self._win_steps = self._steps_timed
        self._win_overlap_s = 0.0
        return out
