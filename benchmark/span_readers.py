"""Readers of the program's own span spine (`proteinbert_tpu.obs.tracing`).

Host metrics read the spans the program recorded while the traced window's
profiler session was live (`tracing.recorder()`): that is exactly the
window. Device metrics join the device trace's "XLA Ops" events with
`tracing.program_scopes(obs["program"])`, the map from a compiled
instruction's name to the `jax.named_scope`s it came from.

Like every reader, one that finds nothing to read returns None: a
program without the spine (a parent commit), a CPU trace without a device
plane, an empty `obs`. A test hands the spans and the map in through
`obs["spans"]` / `obs["scopes"]`.
"""

from __future__ import annotations

import logging
import re

import numpy as np

from benchmark import trace_reduce

logger = logging.getLogger(__name__)

BLOCK_SCOPES = {"local_track", "attention", "global_track", "onepass"}
TRAIN_FORWARD = {"corrupt", "forward", "loss"}
TRAIN_UPDATE = {"optimizer", "step_metrics"}
PACKED = {"encode", "pool"}
BACKWARD = "transpose(jvp("


def _spine():
    """The program's tracing module, where it has the spine."""
    try:
        from proteinbert_tpu.obs import tracing
    except Exception:
        return None
    return tracing if hasattr(tracing, "recorder") else None


# ------------------------------------------------------------ host spans

def recorded(obs) -> list:
    """The window's span records: {name, start_ns, end_ns, tid, id,
    parent, ids}. None of them where the bounded recorder dropped any:
    a sum over part of a window is not the window's."""
    if "spans" in obs:
        return obs["spans"]
    spine = _spine()
    if spine is None or spine.recorder().dropped:
        return []
    return spine.recorder().spans()


def _seconds(obs, name) -> np.ndarray:
    return np.array([(s["end_ns"] - s["start_ns"]) * 1e-9
                     for s in recorded(obs) if s["name"] == name])


def span_mean_ms(obs, name):
    """Mean duration of the spans of one name."""
    seconds = _seconds(obs, name)
    return None if len(seconds) == 0 else 1e3 * float(seconds.mean())


def spans_per_batch_ms(obs, names, per="serve.launch"):
    """Seconds of the spans named, summed, over the batches launched."""
    batches = len(_seconds(obs, per))
    if batches == 0:
        return None
    return 1e3 * float(sum(_seconds(obs, n).sum() for n in names)) / batches


def compiles_in_window(obs):
    """`jax.compile` spans among what was recorded; nothing recorded at
    all means the spine was not recording, not that nothing compiled."""
    spans = recorded(obs)
    if not spans:
        return None
    return sum(1 for s in spans if s["name"] == "jax.compile")


# --------------------------------------------------- device time by scope

_scope_maps = {}
_op_seconds = {}


def _scopes(obs):
    if "scopes" in obs:
        return obs["scopes"]
    program, spine = obs.get("program"), _spine()
    if program is None or spine is None or not hasattr(spine, "program_scopes"):
        return None
    if program not in _scope_maps:
        _scope_maps[program] = spine.program_scopes(program)
    return _scope_maps[program]


def _events(plane, line_name):
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def op_seconds(obs):
    """(runs of the cell's program, {instruction: device seconds}, the
    scope map) over the operations that ran inside those runs. A loop or
    a branch is one event around the operations inside it and is left
    out, as in `top_ops`."""
    trace = obs.get("trace")
    if not trace or "plane" not in trace or not obs.get("program"):
        return None
    scopes = _scopes(obs)
    if not scopes:
        return None
    memo = (id(trace["plane"]), obs["program"])     # seven readers, one pass
    if memo not in _op_seconds:
        _op_seconds.clear()
        _op_seconds[memo] = _sum_op_seconds(trace["plane"], obs["program"])
    runs, totals = _op_seconds[memo]
    return None if runs == 0 else (runs, totals, scopes)


def _sum_op_seconds(plane, program):
    runs = np.array(sorted(
        (s, s + d) for name, s, d in _events(plane, trace_reduce.MODULES_LINE)
        if trace_reduce.program_name(name) == program), np.int64)
    totals = {}
    if len(runs) == 0:
        return 0, totals
    for name, start, duration in _events(plane, trace_reduce.OPS_LINE):
        name = trace_reduce.short_name(name)
        if name.split(".")[0] in trace_reduce.CONTAINERS:
            continue
        at = np.searchsorted(runs[:, 0], start, side="right") - 1
        if at < 0 or start >= runs[at, 1]:
            continue
        totals[name] = totals.get(name, 0.0) + duration * 1e-9
    return len(runs), totals


def scope_seconds(obs):
    """(runs, {scope path: device seconds}); an instruction the map does
    not know goes under the path ""."""
    got = op_seconds(obs)
    if got is None:
        return None
    runs, by_op, scopes = got
    totals = {}
    for name, seconds in by_op.items():
        path = scopes.get(name, "")
        totals[path] = totals.get(path, 0.0) + seconds
    return runs, totals


def scope_table(obs, ours, top=14):
    """The lines a traced run can be read by: the scopes with most
    device time (ms a run), and the instructions with most time among
    those the map gives none of `ours`, and among those directly in the
    scan's body (what `scan_save_ms` sums)."""
    runs, by_op, scopes = op_seconds(obs)
    _, by_scope = scope_seconds(obs)
    first = sorted(by_scope.items(), key=lambda kv: -kv[1])[:top]

    def longest(keep):
        rows = sorted(((t, name) for name, t in by_op.items()
                       if keep(scopes.get(name, ""))), reverse=True)[:top]
        return "; ".join(f"{name} [{scopes.get(name) or 'no op_name'}] "
                         f"{1e3 * t / runs:.3f}" for t, name in rows)

    return ["scopes, device ms a run: " + "; ".join(
                f"{path or '(none)'} {1e3 * t / runs:.3f}" for path, t in first),
            "outside every scope, device ms a run: "
            + longest(lambda path: not _names(path) & ours),
            "in the scan's body outside every block, device ms a run: "
            + longest(_in_scan_body)]


def _names(path) -> set:
    """The bare scope names along a path: `transpose(jvp(forward))/while/
    local_track` -> {transpose, jvp, forward, while, local_track}."""
    return set(re.findall(r"[A-Za-z_]\w*", path))


def _in_scan_body(path) -> bool:
    names = _names(path)
    return "while" in names and not names & BLOCK_SCOPES


def _train_part(path):
    names = _names(path)
    if BACKWARD in path:
        return "bwd"
    if names & TRAIN_FORWARD:
        return "fwd"
    return "opt" if names & TRAIN_UPDATE else None


def _device_ms(obs, keep):
    got = scope_seconds(obs)
    if got is None:
        return None
    runs, totals = got
    return 1e3 * sum(t for path, t in totals.items() if keep(path)) / runs


def train_part_ms(obs, part):
    """Device ms a step of the forward pass (`corrupt`, `jvp(forward)`,
    `loss`), the backward pass (everything under `transpose(jvp(`, the
    recomputation with it) or the update (`optimizer`, `step_metrics`)."""
    return _device_ms(obs, lambda path: _train_part(path) == part)


def scope_ms(obs, scope):
    """Device ms a run of everything under one scope, forward, backward
    and recomputation together."""
    return _device_ms(obs, lambda path: scope in _names(path))


def scan_save_ms(obs):
    """Device ms a run of the operations directly in the scan's
    `while` body, outside every block scope: the stacked saves and the
    slices that read them back. A save the compiler fused into the output
    of the operation that produced it (a conv or a dot writing straight
    into the stacked buffer: `bitcast_dynamic-update-slice_fusion.N` with
    a block's `op_name`) costs no time of its own and stays with its
    block."""
    return _device_ms(obs, _in_scan_body)


def coverage_pct(obs, ours):
    """Share of the program's operation time whose instruction the map
    names with one of `ours`."""
    got = scope_seconds(obs)
    if got is None or not sum(got[1].values()):
        return None
    _, totals = got
    if logger.isEnabledFor(logging.INFO):   # python -m benchmark.scope_table
        for line in scope_table(obs, ours):
            logger.info(line)
    named = sum(t for path, t in totals.items() if _names(path) & ours)
    return 100.0 * named / sum(totals.values())


def train_coverage_pct(obs):
    return coverage_pct(obs, TRAIN_FORWARD | TRAIN_UPDATE)


def packed_coverage_pct(obs):
    return coverage_pct(obs, PACKED)
