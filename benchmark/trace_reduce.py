"""From a profiler trace to the numbers the per-layer metrics read.

`load` turns the newest `.xplane.pb` under a directory into plain lists
(`jax.profiler.ProfileData`, nothing but JAX); everything else works on
that plain form, so the reductions are checked on a small recorded trace
(tests/benchmark/fixtures). A trace is

    {"planes": [{"name": str,
                 "lines": [{"name": str,
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

A device plane is one chip ("/device:TPU:0"). Its "XLA Ops" line holds
one event per operation that ran, its "XLA Modules" line one per
executed program. Host planes hold the host's threads, with every
`jax.profiler.TraceAnnotation` as an event, on the same clock.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
MIN_GAP_NS = 2_000          # shorter pauses between ops are not idle gaps
NAMED_GAPS = 400            # how many of the longest gaps get a name


def load(directory: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[short_name(ev.name), int(ev.start_ns),
                       int(ev.duration_ns)] for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def short_name(name: str) -> str:
    """An operation's event carries its whole HLO line as its name:
    keep what stands before the " = " ("%fusion.12 = bf16[...] ..." ->
    "fusion.12"). Other events keep their names."""
    return name.split(" = ")[0].lstrip("%")[:96]


CONTAINERS = ("while", "conditional", "call")


def _is_chip(plane: dict) -> bool:
    return re.fullmatch(r"/device:TPU:\d+", plane["name"]) is not None


def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if _is_chip(p)]


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def busy_intervals(plane: dict) -> np.ndarray:
    """The union of the intervals in which an operation ran on this
    chip: an (n, 2) array of [start, end) in ns, sorted, disjoint."""
    events = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
    if not events:
        return np.zeros((0, 2), np.int64)
    spans = np.array([[s, s + d] for _, s, d in events], np.int64)
    spans = spans[np.argsort(spans[:, 0])]
    merged = [spans[0].copy()]
    for s, e in spans[1:]:
        if s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append(np.array([s, e]))
    return np.array(merged, np.int64)


def extent_ns(trace: dict):
    """First start and last end over the device planes and over every
    host event the benchmark's or the program's annotations made."""
    lo, hi = None, None
    for plane in trace["planes"]:
        is_device = _is_chip(plane)
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                if is_device or name.startswith(("benchmark.", "trainer.")):
                    lo = s if lo is None else min(lo, s)
                    hi = s + d if hi is None else max(hi, s + d)
    return lo, hi


def program_times(plane: dict) -> dict:
    """name -> [count, total seconds] of the programs this chip ran."""
    out = {}
    for name, _, d in _line(plane, MODULES_LINE):
        key = program_name(name)
        n, t = out.get(key, (0, 0.0))
        out[key] = (n + 1, t + d * 1e-9)
    return out


def program_name(event_name: str) -> str:
    """'jit_train_step(1234567)' -> 'train_step'."""
    name = event_name.split("(")[0].strip()
    return name[4:] if name.startswith("jit_") else name


def executable_id(event_name: str) -> str:
    """'jit_train_step(1234567)' -> '1234567': one id per compiled
    executable, so two shapes of one jitted function are told apart."""
    return event_name.partition("(")[2].partition(")")[0]


def program_runs(plane: dict, program: str) -> list:
    """[(start ns, end ns, executable id), ...] of the runs of one
    program on this chip, in the order they ran."""
    return sorted((s, s + d, executable_id(name))
                  for name, s, d in _line(plane, MODULES_LINE)
                  if program_name(name) == program)


def program_gaps_s(plane: dict, program: str) -> np.ndarray:
    """Seconds the chip sat idle between one run of `program` and the
    next run of it: from the end of the busy time in between."""
    runs = program_runs(plane, program)
    if len(runs) < 2:
        return np.zeros(0)
    busy = busy_intervals(plane)
    gaps = []
    for (_, e0, _), (s1, _, _) in zip(runs, runs[1:]):
        inside = busy[(busy[:, 1] > e0) & (busy[:, 0] < s1)]
        covered = np.clip(inside[:, 1], e0, s1) - np.clip(inside[:, 0], e0, s1)
        gaps.append(max(0, (s1 - e0) - int(covered.sum())) * 1e-9)
    return np.array(gaps)


def host_events(trace: dict):
    """(names, starts, ends) of every event on a host plane."""
    names, starts, ends = [], [], []
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                names.append(name)
                starts.append(s)
                ends.append(s + d)
    return names, np.array(starts, np.int64), np.array(ends, np.int64)


def name_gaps(busy: np.ndarray, trace: dict) -> list:
    """[[what the host was doing, idle seconds], ...], longest first.
    Each gap between busy intervals takes the name of the shortest host
    event that covers its middle."""
    if len(busy) < 2:
        return []
    gaps = np.stack([busy[:-1, 1], busy[1:, 0]], axis=1)
    gaps = gaps[(gaps[:, 1] - gaps[:, 0]) >= MIN_GAP_NS]
    order = np.argsort(gaps[:, 0] - gaps[:, 1])[:NAMED_GAPS]
    names, starts, ends = host_events(trace)
    totals = {}
    for s, e in gaps[order]:
        mid = (s + e) // 2
        cover = np.flatnonzero((starts <= mid) & (ends >= mid))
        if len(cover):
            what = names[cover[np.argmin(ends[cover] - starts[cover])]]
        else:
            what = "(no host span)"
        totals[what] = totals.get(what, 0.0) + float(e - s) * 1e-9
    rest = gaps[np.argsort(gaps[:, 0] - gaps[:, 1])[NAMED_GAPS:]]
    if len(rest):
        totals["(shorter gaps, unnamed)"] = float(
            (rest[:, 1] - rest[:, 0]).sum() * 1e-9)
    return sorted(([k, v] for k, v in totals.items()), key=lambda kv: -kv[1])


def top_ops(plane: dict, n: int = 10) -> list:
    """[[operation, device seconds], ...], most time first. A loop or a
    branch is one event around the operations inside it: left out, so
    that the list names the operations that did the work."""
    totals = {}
    for name, _, d in _line(plane, OPS_LINE):
        name = short_name(name)
        if name.split(".")[0] in CONTAINERS:
            continue
        totals[name] = totals.get(name, 0.0) + d * 1e-9
    return sorted(([k, v] for k, v in totals.items()),
                  key=lambda kv: -kv[1])[:n]


def summarize(trace: dict) -> dict:
    """Everything the per-layer readers and the result line take from a
    trace. Busy seconds are averaged over the chips used."""
    planes = device_planes(trace)
    lo, hi = extent_ns(trace)
    window_s = 0.0 if lo is None else (hi - lo) * 1e-9
    if not planes:
        return {"busy_s": 0.0, "window_s": window_s, "programs": {},
                "top_ops": [], "top_gaps": [], "planes": [
                    [p["name"], [[ln["name"], len(ln["events"])]
                                 for ln in p["lines"]]]
                    for p in trace["planes"]]}
    busy = [busy_intervals(p) for p in planes]
    busy_s = float(np.mean([(b[:, 1] - b[:, 0]).sum() * 1e-9 for b in busy]))
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "programs": {k: list(v) for k, v in program_times(planes[0]).items()},
        "top_ops": top_ops(planes[0]),
        "top_gaps": name_gaps(busy[0], trace),
        "plane": planes[0],
    }
