"""The program's one span spine: host spans, the compile spans and the
map from a compiled program's instructions to the scopes they came from.

`span("name", batch=7)` times a host region. Called directly it is
always a `jax.profiler.TraceAnnotation` (`StepTraceAnnotation` with
`step=`), so whenever anybody captures a device trace the span is on the
xplane's host plane, on the device trace's own clock, with its ids as
the event's stats. It is RECORDED, into the process's one bounded
`SpanCollector` (`recorder()`), only while a profiler session is live at
entry — "tracing on" means "a device trace is being captured": the
benchmark's `--trace 1` window, `pbt pretrain --profile-dir`, the SLO
profile trigger — or into an explicit `collector=` (what
`Telemetry(spans=True)` hands out). With tracing off a span costs the
annotation and one flag read. Either way the object the `with` yields
carries `.start_ns`, `.end_ns` and `.seconds` after exit, so a caller
that needs the duration (a histogram, a `timings` key) reads the span's
own and keeps no second clock.

A record holds name, start and end (`perf_counter_ns`), thread, its own
id, the id of the span that enclosed it on that thread, and the ids
passed in. `SpanCollector.dump` writes Perfetto `traceEvents` JSON on the
wall clock.

The process's START has a collector of its own, `startup_spans()`: the
compiles, cache loads, lowerings and traces `jax.monitoring` tells of and
the `startup.*` spans (`startup_span`), from the import of this module
until the first profiler session goes live or the bound is reached. It
is sealed for good after that, and the hot loops' spans never go there.

`note_program` keeps the abstract arguments of a hot jitted program at
its first call; `program_scopes` lowers from them on demand and returns
`{HLO instruction name: scope path}` from each instruction's
`metadata={op_name=...}`: read from the executable this process compiled,
or from the map kept beside the compile cache, and only then from a
compile of its own. Instruction names are what a device trace's
"XLA Ops" events carry (`fusion.1081`), so the map joins the device trace
with the `jax.named_scope`s inside the program.

jax is NEVER imported by this module — only used if something else
already did — so the obs package stays importable on artifact-only
machines.
"""

from __future__ import annotations

import gzip
import hashlib
import itertools
import json
import logging
import math
import os
import re
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

logger = logging.getLogger(__name__)

# Records live on perf_counter_ns; dumps and post-hoc `add()` speak wall
# clock. One offset, read once: both clocks tick at the same rate.
_WALL_OFFSET_NS = time.time_ns() - time.perf_counter_ns()

_tls = threading.local()        # .open: id of the innermost recorded span
_ids = itertools.count(1)       # next() is atomic under the GIL


class SpanCollector:
    """Bounded buffer of finished spans (oldest dropped past capacity —
    a long run must not grow host memory without bound). `dropped`
    counts what went since the last `clear()`: a sum over the records is
    whole only while it reads 0."""

    def __init__(self, capacity: int = 20000):
        self._spans: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self.dropped = 0
        # getpid() is a real syscall on every dump row — measurably slow
        # under sandboxed kernels (~90us observed) — and the pid cannot
        # change under us: collectors are not expected to survive fork.
        self._pid = os.getpid()

    def _append(self, name: str, start_ns: int, end_ns: int, tid: int,
                span_id: int, parent: Optional[int], ids: Dict) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append({
                "name": name, "start_ns": start_ns, "end_ns": end_ns,
                "tid": tid, "id": span_id, "parent": parent, "ids": ids})

    def add(self, name: str, wall_start: float, dur_s: float,
            tid: Optional[int] = None, parent: Optional[int] = None,
            **ids) -> int:
        """Record one span after the fact, from wall-clock seconds;
        returns its id (a later `add` names it as `parent`). `tid`
        defaults to the calling thread; post-hoc emitters (serve request
        traces, which replay a request's stages after it resolves) pass
        a synthetic tid so each request renders on its own lane —
        overlapping requests on one thread id would nest into nonsense."""
        span_id = next(_ids)
        start_ns = round(wall_start * 1e9) - _WALL_OFFSET_NS
        self._append(name, start_ns, start_ns + round(dur_s * 1e9),
                     threading.get_ident() if tid is None else tid,
                     span_id, parent, ids)
        return span_id

    def __len__(self) -> int:
        return len(self._spans)

    def spans(self) -> List[Dict[str, Any]]:
        """A copy of the records, oldest first."""
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    def to_perfetto(self) -> Dict[str, Any]:
        events = [{"ph": "M", "name": "process_name", "pid": self._pid,
                   "args": {"name": "proteinbert_tpu host spans"}}]
        for s in self.spans():
            args = {"id": s["id"], **s["ids"]}
            if s["parent"] is not None:
                args["parent"] = s["parent"]
            events.append({
                "ph": "X", "name": s["name"], "pid": self._pid,
                "tid": s["tid"],      # perfetto: microseconds
                "ts": (s["start_ns"] + _WALL_OFFSET_NS) / 1e3,
                "dur": (s["end_ns"] - s["start_ns"]) / 1e3,
                "args": args})
        return {"traceEvents": events}

    def dump(self, path: str) -> str:
        """Write trace-event JSON (gzipped when the path ends in .gz) —
        loadable by ui.perfetto.dev beside the device trace."""
        data = json.dumps(self.to_perfetto())
        if path.endswith(".gz"):
            with gzip.open(path, "wt") as f:
                f.write(data)
        else:
            with open(path, "w") as f:
                f.write(data)
        return path


_RECORDER = SpanCollector()


def recorder() -> SpanCollector:
    """The process's one collector: what was recorded while a profiler
    session was live."""
    return _RECORDER


# --------------------------------------------------------- the profiler

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# A duration jax.monitoring tells of -> the span it is recorded as.
_EVENT_SPANS = {
    _COMPILE_EVENT: "jax.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_load",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
}
_TRACE_FLOOR_S = 1e-3           # one tiny step fires hundreds of 0.0 s traces
_profiler = None                # jax.profiler, once jax is live
_is_enabled = None              # TraceAnnotation.is_enabled, where it exists
_lock = threading.Lock()
# fun_name -> [compiled here, loaded from the persistent cache], since the
# listener was armed: what `program_scopes` knows an executable's names by.
_compiled: Dict[str, List[int]] = {}


def arm():
    """Bind to jax.profiler the first time jax is found imported, and
    hang the listeners on jax.monitoring (once: two threads may open
    their first span together). Checked through sys.modules: telemetry
    must not be the thing that pays the jax import. Every span arms;
    `utils/compat.configure_compile_cache` does it before a process's
    first compile, so that the start-up collector sees that one too."""
    global _profiler, _is_enabled
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    with _lock:
        if _profiler is None:
            _is_enabled = getattr(jax.profiler.TraceAnnotation,
                                  "is_enabled", None)
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _profiler = jax.profiler
    return _profiler


def _live() -> bool:
    """True while a profiler session is capturing (never, where the
    installed jax cannot say)."""
    return _is_enabled is not None and _is_enabled()


def _on_event(event: str, **_kw) -> None:
    """A hit of the persistent cache comes before the compile event of
    the same program on the same thread: that one is a load."""
    if event == _HIT_EVENT:
        _tls.hit = True


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    """A backend compile, or the load from the persistent cache that
    fires the same event, is a `jax.compile` span on the compiling
    thread with `cached=` telling which: into the recorder while a
    session is live (which step recompiled), into the start-up collector
    while that is open, where a load's retrieval, a lowering and a trace
    of 1 ms or more are spans too."""
    name = _EVENT_SPANS.get(event)
    if name is None or (name == "jax.trace" and duration_secs < _TRACE_FLOOR_S):
        return
    # the retrieval's event names no program; its `jax.compile` does
    ids = {} if name == "jax.cache_load" else {"program": kw.get("fun_name", "")}
    if name == "jax.compile":
        cached = getattr(_tls, "hit", False)
        _tls.hit = False
        ids["cached"] = int(cached)
        _compiled.setdefault(ids["program"], [0, 0])[cached] += 1
        watch = getattr(_tls, "watch", None)
        if watch is not None:       # `program_scopes` is asking: see there
            watch.append(cached)
    if _live():
        _seal_startup()
        sink = _RECORDER if name == "jax.compile" else None
    else:
        sink = _startup_sink()
    if sink is not None:
        end = time.perf_counter_ns()
        sink._append(name, end - round(duration_secs * 1e9), end,
                     threading.get_ident(), next(_ids),
                     getattr(_tls, "open", None), ids)


# ------------------------------------------------------------- start-up

STARTUP_CAPACITY = 4096
_STARTUP = SpanCollector(STARTUP_CAPACITY)
_startup_open = True


def _seal_startup() -> None:
    global _startup_open
    _startup_open = False


def _startup_sink() -> Optional[SpanCollector]:
    """The start-up collector while it takes records. It is sealed, for
    good, by the first span or compile that finds a profiler session
    live, or here once the bound is reached (its oldest records are the
    ones to keep)."""
    if not _startup_open:
        return None
    if _profiler is None:
        arm()
    if _live() or len(_STARTUP) >= STARTUP_CAPACITY:
        _seal_startup()
        return None
    return _STARTUP


def startup_spans() -> List[Dict[str, Any]]:
    """What the process spent before its first profiler session: the
    `jax.compile` (`program=`, `cached=`), `jax.cache_load`, `jax.lower`
    and `jax.trace` records of the monitoring listener and the
    `startup.*` spans, oldest first, on the clock of every other span."""
    return _STARTUP.spans()


def startup_span(name: str, **ids) -> "span":
    """A `startup.*` span: kept by the start-up collector while that is
    open; after its seal an annotation and the span's own seconds, never
    a record (a window's spans are the hot loops' alone)."""
    started = span(name, collector=_startup_sink(), **ids)
    started._mute = started._sink is None
    return started


_backend_up = False


def backend():
    """`jax.devices()`, the process's first call of it under a
    `startup.backend` span: the runtime's start. The entry points that
    may be first to touch the backend call this first."""
    global _backend_up
    jax = sys.modules["jax"]
    if _backend_up:
        return jax.devices()
    _backend_up = True
    with startup_span("startup.backend") as up:
        devices = jax.devices()
        up.ids.update(platform=devices[0].platform, devices=len(devices))
    return devices


class span:
    """Nested host span; see the module docstring."""

    __slots__ = ("name", "ids", "start_ns", "end_ns", "_step", "_sink",
                 "_ann", "_id", "_parent", "_mute")

    def __init__(self, name: str, collector: Optional[SpanCollector] = None,
                 step: Optional[int] = None, **ids):
        self.name = name
        self.ids = ids          # a record's ids: `step` joins them at exit
        self._step = step
        self._sink = collector
        self._mute = False      # a start-up span after the collector's seal
        self._ann = None
        self.start_ns = self.end_ns = 0

    def __enter__(self) -> "span":
        prof = _profiler or arm()
        if prof is not None:
            if self._step is not None:
                ann = prof.StepTraceAnnotation(
                    self.name, step_num=self._step, **self.ids)
            else:
                ann = prof.TraceAnnotation(self.name, **self.ids)
            ann.__enter__()
            self._ann = ann
            if self._sink is None and not self._mute and _live():
                self._sink = _RECORDER
                if _startup_open:
                    _seal_startup()
        if self._sink is not None:
            self._id = next(_ids)
            self._parent = getattr(_tls, "open", None)
            _tls.open = self._id
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._sink is not None:
            _tls.open = self._parent
            if self._step is not None:
                self.ids["step"] = self._step
            self._sink._append(self.name, self.start_ns, self.end_ns,
                               threading.get_ident(), self._id,
                               self._parent, self.ids)
        return False

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


# ------------------------------------------- programs and their scopes

_programs: Dict[str, tuple] = {}    # name -> (jitted, args, kwargs)
# name -> which leaves of (args, kwargs), by their place among the
# leaves, were arrays never committed to a device
_uncommitted: Dict[str, frozenset] = {}
_scope_maps: Dict[str, Dict[str, str]] = {}     # name -> its map, once read
_compiling = threading.Lock()       # one around-the-cache compile at a time


def note_program(name: str, jitted, args: tuple,
                 kwargs: Optional[Dict] = None) -> None:
    """Keep what it takes to lower `jitted` again as it was called:
    every array argument as its shape, dtype and sharding, everything
    else (static arguments) as it is, and which arrays were never
    committed to a device (`_as_called` lowers from those WITHOUT their
    sharding, as the call did). Only the first call under a name costs
    anything: a pass over the leaves and two `tree.map`s."""
    if name in _programs or not hasattr(jitted, "lower"):
        return      # noted already, or a plain function around jitted parts
    jax = sys.modules["jax"]

    def abstract(x):
        if not (hasattr(x, "shape") and hasattr(x, "dtype")):
            return x
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=getattr(x, "sharding", None),
            weak_type=getattr(getattr(x, "aval", None), "weak_type", False))

    args, kwargs = tuple(args), dict(kwargs or {})
    _uncommitted[name] = frozenset(
        i for i, x in enumerate(jax.tree.leaves((args, kwargs)))
        if not getattr(x, "committed", True))
    _programs[name] = (jitted, jax.tree.map(abstract, args),
                       jax.tree.map(abstract, kwargs))


def _as_called(name: str) -> tuple:
    """(args, kwargs) of the program noted under `name` to lower from:
    the lowering is then the call's own, and jax hands back the
    executable that ran instead of compiling another."""
    jax = sys.modules["jax"]
    _, args, kwargs = _programs[name]
    loose = _uncommitted.get(name, ())
    leaves, tree = jax.tree.flatten((args, kwargs))
    return jax.tree.unflatten(tree, [
        jax.ShapeDtypeStruct(x.shape, x.dtype, weak_type=x.weak_type)
        if i in loose else x for i, x in enumerate(leaves)])


def noted_programs() -> List[str]:
    return sorted(_programs)


# `%fusion.12 = bf16[...] fusion(...), ..., metadata={op_name="..." ...}`
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"[\w\-]+\(%?([A-Za-z_][\w.\-]*)")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
# Wrappers that say how the compiler got there, not where in the model:
# the jit frames, a scan's `body` under its `while` (kept), a remat's
# `checkpoint` (its `rematted_computation` is kept: the recomputation).
_NOISE = re.compile(r"^(?:jit\(.*\)|pjit|closed_call|checkpoint|body|cond"
                    r"|core_call|custom_jvp_call|custom_vjp_call(?:_jaxpr)?)$")


def scope_path(op_name: str) -> str:
    """An `op_name` with the wrappers stripped and its last component,
    the primitive, dropped: `jit(step)/transpose(jvp(forward))/while/body/
    closed_call/checkpoint/attention/dot_general` ->
    `transpose(jvp(forward))/while/attention`. `transpose(jvp(...))`
    stays as the mark of the backward pass."""
    parts = [p for p in op_name.split("/")[:-1] if not _NOISE.match(p)]
    return "/".join(parts)


def scopes_from_hlo(text: str) -> Dict[str, str]:
    """{instruction name: scope path} of an HLO module's text, from each
    instruction's `op_name`. An instruction that carries none takes the
    scope of the first instruction inside the computation it calls (a
    layout-only fusion) or else of its first operand (the start / done
    pair of an asynchronous copy or slice: the scope of what it moves)."""
    named: Dict[str, str] = {}
    borrows: Dict[str, str] = {}    # unnamed instruction -> whom to ask
    first_in: Dict[str, str] = {}   # computation -> its first named scope
    computation = None
    for line in text.splitlines():
        header = _COMPUTATION.match(line)
        if header and " = " not in line.split("(")[0]:
            computation = header.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        if op:
            named[m.group(1)] = scope_path(op.group(1))
            if computation is not None:
                first_in.setdefault(computation, named[m.group(1)])
            continue
        called = _CALLS.search(line)
        operand = _OPERAND.search(line.split(" = ", 1)[1])
        if called:
            borrows[m.group(1)] = "calls:" + called.group(1)
        elif operand:
            borrows[m.group(1)] = operand.group(1)
    for instruction in borrows:
        seen, at = set(), instruction
        while at in borrows and at not in seen:     # a -done asks its -start
            seen.add(at)
            at = borrows[at]
        scope = (first_in.get(at[6:]) if at.startswith("calls:")
                 else named.get(at))
        if scope is not None:
            named[instruction] = scope
    return named


_COLLECTIVE = re.compile(
    r"=\s*(\(.*?\)|\S+)\s+(all-gather|all-reduce|reduce-scatter|all-to-all"
    r"|collective-permute)(-start)?\(")
_ARRAY = re.compile(r"\b([a-z]+\d+[a-z0-9]*|pred)\[([\d,]*)\]")
_WHILE = re.compile(
    r"\swhile\(.*?\bcondition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_BOUND = re.compile(r"=\s*s32\[\]\S*\s+constant\((\d+)\)")
_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_CALLED = re.compile(r"\b(?:calls|to_apply|condition|body)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def _element_bytes(dtype: str) -> int:
    bits = re.search(r"\d+", dtype)
    return max(int(bits.group()) // 8, 1) if bits else 1     # pred: 1


def collective_census(text: str, rows: int, parameter_shapes) -> Dict[str, Any]:
    """What a compiled (partitioned) step moves between chips, from its
    module's text (`compiled.as_text()`): a count, not a time.

    `rows` is the GLOBAL row count of the step's batch;
    `parameter_shapes` every parameter leaf's shape: a parameter travels
    in that shape or, a slice of a stack, without its leading axis, and
    extents of 1 are dropped on both sides (such a slice keeps one). Each
    collective is taken by its results: `parameter` if every one has a parameter's
    shape, `activation` if one has rank 2 or more and `rows` as its
    first extent and no parameter's shape (a chip's own share of the
    batch has fewer rows: such a result is the batch, or what was
    computed from it, brought together), else `other` (loss sums, a
    vector, a reshard of a chip's own rows). A collective inside a loop
    counts once a trip: the loop's `known_trip_count`, else the one
    integer constant its condition compares with (a scan counts up from
    0), else 1.

    Returns {"by_kind": {kind: {"count", "bytes"}}, "activation": count,
    "parameter": count, "parameter_gathers": count (`all-gather`s among
    them), "activation_shapes": the distinct result shapes of the
    first, "bytes": of every result, a step}."""
    parameter_shapes = {_squeezed(s[cut:]) for s in parameter_shapes
                        for cut in (0, 1)}
    trips: Dict[str, int] = {}      # loop body -> its trips
    conditions: Dict[str, str] = {}  # loop body -> condition, trips unsaid
    bounds: Dict[str, List[int]] = {}   # computation -> its s32 constants
    callers: Dict[str, str] = {}    # computation -> the one that names it
    found = []                      # (computation, kind, [(dtype, dims)])
    computation = None
    for line in text.splitlines():
        header = _COMPUTATION.match(line)
        if header and " = " not in line.split("(")[0]:
            computation = header.group(1)
            continue
        if computation is None or " = " not in line:
            continue
        bound = _BOUND.search(line)
        if bound:
            bounds.setdefault(computation, []).append(int(bound.group(1)))
        loop = _WHILE.search(line)
        if loop:
            n = _TRIPS.search(line)
            if n:
                trips[loop.group(2)] = int(n.group(1))
            else:
                conditions[loop.group(2)] = loop.group(1)
        called = _CALLED.findall(line)
        branches = _BRANCHES.search(line)
        if branches:
            called += [b.strip().lstrip("%") for b in branches.group(1).split(",")]
        for name in called:
            callers.setdefault(name, computation)
        m = _COLLECTIVE.search(line)
        if m:
            arrays = [(d, _squeezed(int(x) for x in dims.split(",") if x))
                      for d, dims in _ARRAY.findall(m.group(1))]
            if m.group(3) and m.group(2) in ("all-gather", "collective-permute"):
                # A start's result is (operands, results[, two scalars]).
                arrays = [a for a in arrays if a[1]] or arrays
                arrays = arrays[len(arrays) // 2:]
            found.append((computation, m.group(2), arrays))

    for body, condition in conditions.items():
        said = bounds.get(condition, [])
        trips[body] = said[0] if len(said) == 1 else 1

    def times(comp):
        n, seen = 1, set()
        while comp is not None and comp not in seen:
            seen.add(comp)
            n *= trips.get(comp, 1)
            comp = callers.get(comp)
        return n

    census = {"by_kind": {}, "activation": 0, "parameter": 0,
              "parameter_gathers": 0, "activation_shapes": [], "bytes": 0}
    for comp, kind, arrays in found:
        n = times(comp)
        size = n * sum(_element_bytes(d) * math.prod(dims) for d, dims in arrays)
        entry = census["by_kind"].setdefault(kind, {"count": 0, "bytes": 0})
        entry["count"] += n
        entry["bytes"] += size
        census["bytes"] += size
        shapes = [dims for _, dims in arrays]
        batch = [list(s) for s in shapes if len(s) >= 2 and s[0] == rows
                 and s not in parameter_shapes]
        if shapes and all(s in parameter_shapes for s in shapes):
            census["parameter"] += n
            census["parameter_gathers"] += n * (kind == "all-gather")
        elif batch:
            census["activation"] += n
            census["activation_shapes"] += [
                s for s in batch if s not in census["activation_shapes"]]
    return census


def _squeezed(dims) -> tuple:
    return tuple(d for d in dims if d != 1)


# What a map's key must not depend on: where in which file a line stands.
_LOCATION = re.compile(
    r'\s?(?:stack_frame_id|source_(?:end_)?(?:line|column))=\d+'
    r'|\s?source_file="[^"]*"')
_LOCATION_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames")


def names_key(lowered) -> Optional[str]:
    """A key equal for two lowerings iff the computation AND the names
    its instructions carry are: a hash of the module printed WITH its
    metadata and without source files, lines and stack frames, and of
    what else decides the compiler's instruction names (the backend's
    version, the device, the compiler's flags). None where this jax
    cannot print a module so."""
    jax = sys.modules["jax"]
    try:
        from jax._src.lib import _jax

        options = _jax.HloPrintOptions.short_parsable()
        options.print_metadata = True
        text = lowered.compiler_ir("hlo").as_hlo_module().to_string(options)
        device = jax.devices()[0]
        said = (jax.__version__, device.client.platform_version,
                device.device_kind, os.environ.get("XLA_FLAGS", ""),
                os.environ.get("LIBTPU_INIT_ARGS", ""))
    except Exception as e:
        logger.warning("no key for a scope map: %r", e)
        return None
    digest = hashlib.sha256(repr(said).encode())
    table = False
    for line in text.splitlines():
        if table:                   # a table of locations ends at a blank line
            table = bool(line.strip())
        elif line.strip() in _LOCATION_TABLES:
            table = True
        else:
            digest.update(_LOCATION.sub("", line).encode())
            digest.update(b"\n")
    return digest.hexdigest()


def _map_file(key: Optional[str]) -> Optional[str]:
    """Where the map under `key` is kept: `scope_maps/` inside the
    compile cache's directory, so the maps live and die with it."""
    directory = sys.modules["jax"].config.jax_compilation_cache_dir
    if not key or not directory:
        return None
    return os.path.join(directory, "scope_maps", key + ".json")


def _stored_map(key: Optional[str]) -> Optional[Dict[str, str]]:
    path = _map_file(key)
    try:
        with open(path) as f:
            return json.load(f)["scopes"] or None
    except (TypeError, OSError, ValueError, KeyError):
        return None


def _store_map(key: Optional[str], program: str, scopes: Dict[str, str]) -> None:
    path = _map_file(key)
    if path is None or not scopes:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"program": program, "scopes": scopes}, f)
        os.replace(tmp, path)       # a reader never sees half a map
    except OSError as e:
        logger.warning("scope map of %s not kept: %r", program, e)


def _read_or_compile(jitted, lowered):
    """(map, "own_compile" | "compiled") of a lowering nobody has mapped
    yet. `lowered.compile()` hands back the executable the process
    already has for this lowering, else loads or compiles it; the
    listener tells which, and whether every executable of the program's
    name this process made was compiled HERE: those carry this
    checkout's names. One that was loaded may carry another checkout's
    (the cache's key leaves the metadata out), so it is compiled again
    AROUND the cache: the process-wide `jax_enable_compilation_cache`
    off and on again (one caller at a time, `_compiling`; a compile
    another thread starts meanwhile merely misses the cache too), and
    with a compiler option at its default, because a lowering keeps the
    executable it made and would hand that back."""
    jax = sys.modules["jax"]
    here, loaded = _compiled.get(f"jit({getattr(jitted, '__name__', '')})",
                                 (0, 0))
    _tls.watch = watch = []
    try:
        compiled = lowered.compile()
    finally:
        _tls.watch = None
    fresh = bool(watch) and not any(watch)      # compiled just now: a miss
    # No event at all: jax handed back an executable this process holds.
    # A load just now (a 1 in `watch`) may be another checkout's entry.
    if fresh or (here and not loaded and not watch):
        scopes = scopes_from_hlo(compiled.as_text())
        if scopes:
            return scopes, "compiled" if fresh else "own_compile"
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()     # the cache memoizes "am I used"
    try:
        text = lowered.compile(
            compiler_options={"xla_hlo_profile": False}).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()
    return scopes_from_hlo(text), "compiled"


_SAID = {"own_compile": "read from the executable compiled here",
         "stored": "read from the map kept beside the cache",
         "compiled": "compiled for it"}


def program_scopes(name: str) -> Optional[Dict[str, str]]:
    """`scopes_from_hlo` of the program noted under `name`, lowered from
    its abstract arguments; None where none was noted.

    What a map needs is {instruction of the executable that ran: scope
    path under THIS checkout's names}. The instructions are the
    compiler's and the same for the same computation; the names are not,
    and the persistent cache's key leaves them out, so a cache shared
    with another checkout may hand back THAT checkout's executable,
    names and all. So, in this order: the map kept under the lowering's
    `names_key` in `scope_maps/` beside the cache (`source="stored"`:
    one small file read); the text of the executable, where this process
    compiled it itself (`"own_compile"`); a compile (`"compiled"`:
    because nothing was cached, or around the cache). Whatever was read
    is kept under the key, so a (computation, names) costs a cache
    directory one compile at most. The seconds are logged, counted in
    `program_scopes_total{source=}` and recorded as a
    `tracing.program_scopes` span (`program=`, `source=`) in the
    process's recorder, session or none: it is asked for after a traced
    window, never inside one."""
    if name in _scope_maps:
        return _scope_maps[name]
    noted = _programs.get(name)
    if noted is None:
        return None
    from proteinbert_tpu.obs.metrics import process_counter

    args, kwargs = _as_called(name)
    with _compiling, span("tracing.program_scopes", collector=_RECORDER,
                          program=name) as took:
        lowered = noted[0].lower(*args, **kwargs)
        key = names_key(lowered)
        scopes, source = _stored_map(key), "stored"
        if scopes is None:
            scopes, source = _read_or_compile(noted[0], lowered)
            _store_map(key, name, scopes)
        took.ids["source"] = source
    process_counter("program_scopes_total", source=source).inc()
    logger.info("program_scopes(%s): %s in %.1f s", name, _SAID[source],
                took.seconds)
    _scope_maps[name] = scopes
    return scopes
