"""Readers of the span spine's START-UP collector
(`proteinbert_tpu.obs.tracing.startup_spans`): what the process spent
before the traced window opened, the part of `setup_s` the program can
name. A compile or a load fires the same `jax.compile` span; `cached=`
tells them apart. `scope_map_s` reads the other end of the process: the
`tracing.program_scopes` spans the scope readers caused after the window.

Like every reader, one that finds nothing to read returns None: a program
without the collector (a parent commit: `_spine` finds no
`startup_spans`), a collector that was filled before the window (a sum
over part of a start is not the start's). A test hands the records in
through `obs["startup_spans"]` (`obs["spans"]` for `scope_map_s`).
"""

from __future__ import annotations

from benchmark import span_readers

COMPILE, LOWER, TRACE = "jax.compile", "jax.lower", "jax.trace"
WARMUP = "startup.warmup"
SCOPE_MAP = "tracing.program_scopes"


def _spine():
    """The program's tracing module, where it has the start-up collector."""
    try:
        from proteinbert_tpu.obs import tracing
    except Exception:
        return None
    return tracing if hasattr(tracing, "startup_spans") else None


def started(obs) -> list:
    """The start-up records: {name, start_ns, end_ns, tid, id, parent,
    ids}, none of them where the collector reached its bound."""
    if "startup_spans" in obs:
        return obs["startup_spans"]
    spine = _spine()
    if spine is None:
        return []
    spans = spine.startup_spans()
    return [] if len(spans) >= spine.STARTUP_CAPACITY else spans


def _seconds(span) -> float:
    return (span["end_ns"] - span["start_ns"]) * 1e-9


def _compiles(obs, cached):
    """The `jax.compile` records that were loads (`cached`) or were not;
    None where nothing at all was recorded: the spine was not listening,
    not that nothing compiled."""
    spans = started(obs)
    if not spans:
        return None
    return [s for s in spans
            if s["name"] == COMPILE and bool(s["ids"].get("cached")) == cached]


def compile_s(obs):
    """Seconds of the backend compiles that missed the persistent cache."""
    found = _compiles(obs, cached=False)
    return None if found is None else sum(map(_seconds, found))


def compiles(obs):
    """Their number: 0 in a run whose cache is warm."""
    found = _compiles(obs, cached=False)
    return None if found is None else len(found)


def cache_load_s(obs):
    """Seconds of the `jax.compile` records that were loads: the
    retrieval (`jax.cache_load`, nested inside) and the deserialization
    around it."""
    found = _compiles(obs, cached=True)
    return None if found is None else sum(map(_seconds, found))


def outermost(spans) -> list:
    """Of records of one name, those no other of them on the same thread
    encloses: a function traced inside another's trace fires an event of
    its own, which ends first and lies inside the outer one's."""
    kept, open_until = [], {}
    for s in sorted(spans, key=lambda s: (s["start_ns"], -s["end_ns"])):
        if s["start_ns"] >= open_until.get(s["tid"], -1):
            kept.append(s)
            open_until[s["tid"]] = s["end_ns"]
    return kept


def trace_lower_s(obs):
    """Seconds of `jax.lower` plus the outermost `jax.trace` records (a
    trace is a record from 1 ms up): the Python a warm run still pays."""
    spans = started(obs)
    if not spans:
        return None
    traces = outermost([s for s in spans if s["name"] == TRACE])
    return (sum(_seconds(s) for s in spans if s["name"] == LOWER)
            + sum(map(_seconds, traces)))


def warmup_s(obs):
    """`startup.warmup` summed over the row classes and kinds the
    dispatcher warmed; None where it warmed none."""
    found = [s for s in started(obs) if s["name"] == WARMUP]
    return sum(map(_seconds, found)) if found else None


def scope_map_s(obs):
    """Seconds of the `tracing.program_scopes` spans of the run: what
    the scope readers BEFORE this one spent on their maps (it is listed
    last), 0 where they asked for none. The program records the span
    whether a session is live or not only where it has the start-up
    collector too; a parent commit opens it unrecorded."""
    if "spans" not in obs and _spine() is None:
        return None
    spans = span_readers.recorded(obs)
    if not spans:
        return None
    return sum(_seconds(s) for s in spans if s["name"] == SCOPE_MAP)
