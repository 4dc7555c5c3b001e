"""Readers of the program's own span spine (`proteinbert_tpu.obs.tracing`).

Host metrics read the spans the program recorded while the traced window's
profiler session was live (`tracing.recorder()`): that is exactly the
window. Device metrics join the device trace's "XLA Ops" events with
`tracing.program_scopes(obs["program"])`, the map from a compiled
instruction's name to the `jax.named_scope`s it came from. The packed
serving program runs as one executable a row class under one name: its
runs are told apart by the executable id the trace gives each run, an
executable's class is read from the `cls=` of the window's
`serve.launch` spans, and each class has a scope map of its own.

Like every reader, one that finds nothing to read returns None: a
program without the spine (a parent commit), a CPU trace without a device
plane, an empty `obs`. A test hands the spans and the map in through
`obs["spans"]` / `obs["scopes"]` (`obs["class_scopes"]`: one map a class).
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


# ------------------------------------------ runs by row class (serving)

LAUNCH = "serve.launch"
AHEAD = 3       # runs at a trace's head launched before it: the depth-2
#                 window's two and one being placed

def executable_classes(runs, launched):
    """({executable id: row class}, runs out of step) of a program that
    runs in several classes under one name. `runs` are its runs in the
    trace in order, (start, end, executable id); `launched` the `cls=`
    of the window's `serve.launch` spans in order. The chip runs batches
    in the order they were launched, so run a + k is launch k, where the
    first a runs (0 to AHEAD) were launched before the recorder was
    live; a is the shift under which the fewest runs disagree with the
    class most runs of their executable were launched with. Nothing is
    assumed about which class an executable is."""
    best = None
    for ahead in range(AHEAD + 1):
        votes = {}
        for (_, _, exe), cls in zip(runs[ahead:], launched):
            seen = votes.setdefault(exe, {})
            seen[cls] = seen.get(cls, 0) + 1
        odd = sum(sum(v.values()) - max(v.values()) for v in votes.values())
        if votes and (best is None or odd < best[0]):
            best = (odd, ahead, votes)
    if best is None:
        return {}, 0
    odd, ahead, votes = best
    logger.info("classes by executable: %s (shift %d, %d runs disagree)",
                votes, ahead, odd)
    return {exe: max(v, key=v.get) for exe, v in votes.items()}, odd


def _runs_and_classes(obs):
    """(the program's runs in the traced window, {executable id: class}),
    or None where the join is not sound: more runs whose executable no
    launch told, or more launches out of step with their executable's
    class, than the window's edges explain (AHEAD each), or two
    executables told the same class (there is one a class). A share or a
    sum over the runs that did join would read like the window's and is
    not; the counts are logged beside the refusal."""
    trace = obs.get("trace")
    if not trace or "plane" not in trace or not obs.get("program"):
        return None
    launched = [s["ids"].get("cls") for s in sorted(
        (s for s in recorded(obs) if s["name"] == LAUNCH),
        key=lambda s: s["start_ns"])]
    if not launched or None in launched:
        return None
    memo = trace.setdefault("_class_join", {})      # many readers, one join
    if obs["program"] not in memo:
        runs = trace_reduce.program_runs(trace["plane"], obs["program"])
        class_of, odd = executable_classes(runs, launched)
        untold = sum(1 for _, _, exe in runs if exe not in class_of)
        shared = len(class_of) - len(set(class_of.values()))
        sound = untold <= AHEAD and odd <= AHEAD and shared == 0
        (logger.info if sound else logger.warning)(
            "%s: %d runs in the trace, %d classified, %d untold, %d out of "
            "step with %d launches, %d executables share a class%s",
            obs["program"], len(runs), len(runs) - untold, untold, odd,
            len(launched), shared,
            "" if sound else ": the join is NOT sound, nothing is read")
        memo[obs["program"]] = (runs, class_of) if sound else None
    return memo[obs["program"]]


def class_runs(obs):
    """[(row class, device seconds), ...] of the runs of the cell's
    program in the traced window, in the order they ran; the class is
    None for a run (at most AHEAD of them) whose executable no launch of
    the window told."""
    got = _runs_and_classes(obs)
    if got is None or not got[0]:
        return None
    runs, class_of = got
    return [(class_of.get(exe), (end - start) * 1e-9)
            for start, end, exe in runs]


# --------------------------------------------------- device time by scope

_scope_maps = {}


def _class_scopes(obs, cls):
    """The scope map of the executable of one row class: the benchmark
    hands the program's own `note_program` / `program_scopes` (a compile
    around the persistent cache, after the window) the class's program,
    `obs["class_program"](cls)`, under a name of its own."""
    if "class_scopes" in obs:
        return obs["class_scopes"].get(cls)
    spine = _spine()
    if spine is None or not hasattr(spine, "program_scopes"):
        return None
    name = f"{obs['program']}@{cls}"
    if name not in _scope_maps:
        jitted, args, static = obs["class_program"](cls)
        spine.note_program(name, jitted, args, static)
        _scope_maps[name] = spine.program_scopes(name)
    return _scope_maps[name]


def _scopes(obs, executables):
    """{executable id: {instruction: scope path}} for the executables of
    the cell's program that ran. A program that runs in row classes has
    one map a class, joined through the class of each executable; any
    other has the one map of `tracing.program_scopes`."""
    if "class_scopes" in obs or "class_program" in obs:
        _, class_of = _runs_and_classes(obs) or (None, {})
        maps = {exe: _class_scopes(obs, class_of[exe])
                for exe in executables if exe in class_of}
        return {exe: m for exe, m in maps.items() if m}
    if "scopes" in obs:
        one = obs["scopes"]
    else:
        program, spine = obs.get("program"), _spine()
        if program is None or spine is None or not hasattr(spine, "program_scopes"):
            return None
        if program not in _scope_maps:
            _scope_maps[program] = spine.program_scopes(program)
        one = _scope_maps[program]
    return {exe: one for exe in executables} if one else None


def _events(plane, line_name):
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def op_seconds(obs):
    """(runs of the cell's program, {(executable id, instruction): device
    seconds}, {executable id: scope map}) over the operations that ran
    inside those runs, for the executables that have a scope map: an
    instruction's name means something only within its own executable. A
    loop or a branch is one event around the operations inside it and is
    left out, as in `top_ops`."""
    trace = obs.get("trace")
    if not trace or "plane" not in trace or not obs.get("program"):
        return None
    memo = trace.setdefault("_op_seconds", {})      # seven readers, one pass
    if obs["program"] not in memo:
        memo[obs["program"]] = _sum_op_seconds(trace["plane"], obs["program"])
    runs_of, totals = memo[obs["program"]]
    scopes = _scopes(obs, list(runs_of))
    if not scopes:
        return None
    runs = sum(n for exe, n in runs_of.items() if exe in scopes)
    by_op = {key: t for key, t in totals.items() if key[0] in scopes}
    return None if runs == 0 else (runs, by_op, scopes)


def _sum_op_seconds(plane, program):
    """({executable id: runs}, {(executable id, instruction): seconds})."""
    found = trace_reduce.program_runs(plane, program)
    runs_of, totals = {}, {}
    if not found:
        return runs_of, totals
    for _, _, exe in found:
        runs_of[exe] = runs_of.get(exe, 0) + 1
    runs = np.array([(s, e) for s, e, _ in found], np.int64)
    for name, start, duration in _events(plane, trace_reduce.OPS_LINE):
        name = trace_reduce.short_name(name)
        if name.split(".")[0] in trace_reduce.CONTAINERS:
            continue
        at = np.searchsorted(runs[:, 0], start, side="right") - 1
        if at < 0 or start >= runs[at, 1]:
            continue
        key = (found[at][2], name)
        totals[key] = totals.get(key, 0.0) + duration * 1e-9
    return runs_of, totals


def scope_seconds(obs):
    """(runs, {scope path: device seconds}); an instruction the map does
    not know goes under the path ""."""
    got = op_seconds(obs)
    if got is None:
        return None
    runs, by_op, scopes = got
    totals = {}
    for (exe, name), seconds in by_op.items():
        path = scopes[exe].get(name, "")
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
        rows = sorted(((t, exe, name) for (exe, name), t in by_op.items()
                       if keep(scopes[exe].get(name, ""))), reverse=True)[:top]
        return "; ".join(f"{name} [{scopes[exe].get(name) or 'no op_name'}] "
                         f"{1e3 * t / runs:.3f}" for t, exe, name in rows)

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
