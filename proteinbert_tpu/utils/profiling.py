"""Wall-clock + device profiling (reference C20, TPU-aware).

The reference ships `TimeMeasure` (a with-block wall-clock logger,
shared_utils/util.py:1212-1223) and `Profiler` (named aggregating
time/invoke counters, shared_utils/util.py:1226-1263). Both are kept —
they are genuinely useful on the host side — and joined by
`device_trace()`, a thin wrapper over `jax.profiler` that captures an XLA
trace viewable in TensorBoard/Perfetto, which is the real profiling story
on TPU (per-op time lives on device, invisible to host timers).
"""

from __future__ import annotations

import contextlib
import logging
import os
import time
from typing import Dict, Optional

from proteinbert_tpu.utils.logging import log


class TimeMeasure:
    """`with TimeMeasure('phase'):` — logs elapsed wall-clock on exit."""

    def __init__(self, name: str = "", verbose: bool = True):
        self.name = name
        self.verbose = verbose
        self.elapsed: Optional[float] = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.elapsed = time.perf_counter() - self._t0
        if self.verbose:
            log(f"{self.name or 'block'}: {self.elapsed:.3f}s")
        return False


class Profiler:
    """Named aggregating profiler — now a thin shim over the telemetry
    metrics registry (obs/metrics.py), which absorbed the host-timer
    aggregation this class used to hold privately. The API is unchanged
    (`measure`/`summary`/`report`), and existing call sites keep
    working; pass a shared `registry` to fold a Profiler's sections
    into a run's unified metrics stream instead of a private one."""

    def __init__(self, registry=None):
        from proteinbert_tpu.obs.metrics import MetricsRegistry

        self._reg = registry if registry is not None else MetricsRegistry()

    def measure(self, name: str):
        return self._reg.timer(name)

    def summary(self) -> Dict[str, Dict[str, float]]:
        return self._reg.timer_summary()

    def report(self) -> str:
        rows = sorted(self.summary().items(),
                      key=lambda kv: -kv[1]["total_s"])
        return "\n".join(
            f"{name}: {s['total_s']:.3f}s / {s['count']} calls "
            f"({s['mean_s'] * 1e3:.2f} ms each)"
            for name, s in rows
        )


@contextlib.contextmanager
def device_trace(log_dir: str, host_profile: bool = False):
    """Capture a jax.profiler trace (XLA ops, HBM, fusion view) to
    `log_dir`; open with TensorBoard or ui.perfetto.dev.

    While the capture runs the program's span spine records
    (obs/tracing). On exit two files land beside the xplane:
    `host_spans.json.gz`, the capture's host spans as Perfetto
    trace-event JSON, and `program_scopes.json`, for every hot jitted
    program noted during the capture the map from its compiled
    instructions' names (what the trace's "XLA Ops" carry) to the
    `jax.named_scope`s they came from (`tracing.program_scopes`: read
    from the step this run compiled or from the map kept beside the
    compile cache; a compile only for a loaded step nobody has mapped)."""
    import glob
    import json

    import jax

    from proteinbert_tpu.obs import tracing

    tracing.recorder().clear()
    jax.profiler.start_trace(log_dir, create_perfetto_trace=host_profile)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        try:
            planes = sorted(glob.glob(os.path.join(
                log_dir, "plugins", "profile", "*", "*.xplane.pb")),
                key=os.path.getmtime)
            beside = os.path.dirname(planes[-1]) if planes else log_dir
            tracing.recorder().dump(os.path.join(beside, "host_spans.json.gz"))
            with open(os.path.join(beside, "program_scopes.json"), "w") as f:
                json.dump({name: tracing.program_scopes(name)
                           for name in tracing.noted_programs()}, f)
            log(f"device trace, host spans and program scopes written to "
                f"{beside}")
        except Exception as e:
            # The xplane is on disk; a failed extra (the scope map lowers
            # the step again) must not mask whatever the body itself raised.
            log(f"device trace written to {log_dir}; host spans / program "
                f"scopes NOT written: {e!r}", level=logging.WARNING)


def monitor_memory(threshold_bytes: int = 100 * 1024 ** 2,
                   collect: bool = False, verbose: bool = True):
    """Log every live host array buffer >= `threshold_bytes` (reference
    shared_utils/util.py:175-228's heap walker). Walks every gc-tracked
    container (module __dict__s included) plus the `__dict__` of every
    gc-tracked instance, and recurses through UNTRACKED containers found
    inside them — CPython untracks a dict/tuple whose members are all
    untracked (a tuple-of-arrays pytree, an instance __dict__ holding
    only arrays), so such nests are reachable only through a tracked
    ancestor. Returns [(type_name, nbytes), ...] largest first,
    deduplicated by identity; optionally gc.collect()s afterwards like
    the reference.
    """
    import collections
    import gc

    def size_of(obj):
        # Probe `nbytes` (numpy / jax buffers) through the TYPE, never the
        # instance: instance getattr would fire arbitrary __getattr__ on
        # every live object (observed force-registering pytest marks;
        # would force-initialize lazy proxies heap-wide). Everything is
        # guarded — even isinstance raises on a dead weakref.proxy.
        try:
            if isinstance(obj, (bytes, bytearray)):
                return len(obj)
            desc = getattr(type(obj), "nbytes", None)
            if desc is None or not hasattr(desc, "__get__"):
                return None
            n = desc.__get__(obj, type(obj))
        except Exception:  # dead weakproxies, raising descriptors
            return None
        return n if isinstance(n, int) else None

    seen: Dict[int, tuple] = {}
    visited: set = set()
    # Strong references to every object whose id() lands in `visited` or
    # `seen`: the stack pops its only reference to intermediate objects,
    # and if one were collected mid-walk CPython could reuse its id for a
    # genuinely new container/buffer — silently skipping it or
    # overwriting a seen entry (ADVICE r1). Pinning them for the walk's
    # duration makes id-dedup sound; the list is released on return.
    pinned: list = []
    # The walker's own bookkeeping is gc-tracked and MUTATES during the
    # walk — iterating it would raise "changed size during iteration".
    internals = {id(seen), id(visited), id(pinned)}

    # Iterative walk (an explicit stack): deep pathological nests must
    # not RecursionError a diagnostic tool. Only containers enter
    # `visited` — recording every leaf id would balloon the walker's own
    # footprint on multi-million-element lists (`seen` already dedups
    # leaf buffers by id).
    containers = (dict, list, tuple, set, frozenset, collections.deque)
    stack = []
    internals.add(id(stack))  # gc-listed below; must not walk itself
    for c in gc.get_objects():
        # Everything here is guarded: a dead weakref.proxy raises
        # ReferenceError from isinstance itself (it forwards __class__ to
        # the collected referent).
        try:
            if isinstance(c, containers):
                stack.append(c)
            else:
                # Instances are gc-tracked even when their __dict__ is
                # not (all-untracked values, e.g. only numpy arrays on
                # self) — the commonest big-buffer holder. Find the
                # __dict__ slot through the TYPE's mro: plain getattr
                # would fall through to instance __getattr__ on
                # __slots__ classes and fire lazy-proxy side effects
                # heap-wide (the same hazard size_of avoids).
                d = None
                for klass in type(c).__mro__:
                    desc = klass.__dict__.get("__dict__")
                    if desc is not None:
                        d = desc.__get__(c, type(c))
                        break
                if isinstance(d, dict):
                    stack.append(d)
        except Exception:
            continue
    while stack:
        obj = stack.pop()
        # issubclass(type(obj), ...) not isinstance: a dead weakref.proxy
        # forwards __class__ to its collected referent and raises from
        # isinstance, while type() never forwards.
        if issubclass(type(obj), containers):
            if id(obj) in visited or id(obj) in internals:
                continue
            visited.add(id(obj))
            pinned.append(obj)
            try:
                if isinstance(obj, dict):
                    # keys too: bytes keys are legal and can be large
                    stack.extend(list(obj.keys()))
                    stack.extend(list(obj.values()))
                else:
                    stack.extend(list(obj))
            except Exception:
                # Mutated mid-iteration by another thread (prefetch,
                # jax-internal), or a container subclass whose iteration
                # raises; skip it rather than crash a diagnostic.
                continue
        else:
            n = size_of(obj)
            if n is not None and n >= threshold_bytes:
                if id(obj) not in seen:
                    pinned.append(obj)
                seen[id(obj)] = (type(obj).__name__, n)

    found = sorted(seen.values(), key=lambda kv: -kv[1])
    if verbose:
        for name, n in found:
            log(f"monitor_memory: {name} {n / 1024 ** 2:.0f} MB")
        if not found:
            log(f"monitor_memory: no object >= "
                f"{threshold_bytes / 1024 ** 2:.0f} MB")
    if collect:
        gc.collect()
    return found


def device_memory_report() -> Dict[str, Dict[str, int]]:
    """Per-device HBM stats ({device: {bytes_in_use, peak_bytes_in_use,
    bytes_limit, ...}}) — the on-chip counterpart of monitor_memory; the
    numbers XLA's allocator actually enforces (a 16 GB v5e OOMs on
    bytes_in_use, not on host heap size)."""
    import jax

    out: Dict[str, Dict[str, int]] = {}
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:  # backends without memory_stats (e.g. some CPU)
            stats = {}
        out[str(d)] = {k: int(v) for k, v in stats.items()
                       if isinstance(v, (int, float))}
    return out
