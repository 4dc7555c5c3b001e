"""Record the small fixture the span readers are tested on (run on the chip).

    chiprun -- python3 -m benchmark.record_span_fixture

One pretrain cell and one serve cell at their rehearsal sizes, traced the
way the harness traces a window. Of each it keeps, cut to the first three
runs of the cell's program: the program's recorded spans
(`proteinbert_tpu.obs.tracing.recorder()`), the instruction -> scope map
(`program_scopes`), the device plane's "XLA Ops" and "XLA Modules"
lines in the plain form `benchmark.trace_reduce` works on, and the
program's annotations as the xplane's host planes hold them: the same
spans on the device trace's own clock. Written to
chiprun_out/spans_v5e.json (copied to tests/benchmark/fixtures by hand).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
from unittest import mock

from benchmark import run as harness
from benchmark import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = ("pretrain-base-dense", "serve-base-sat")
RUNS_KEPT = 3
SPINE = ("train.", "serve.", "data.")


def _first_runs(plane, program):
    """The plane's two lines, cut to the first runs of `program`."""
    lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
    runs = sorted((s, s + d) for name, s, d in lines[trace_reduce.MODULES_LINE]
                  if trace_reduce.program_name(name) == program)[:RUNS_KEPT]
    lo, hi = runs[0][0], runs[-1][1]
    return {"name": plane["name"], "lines": [
        {"name": name, "events": [e for e in lines[name] if lo <= e[1] < hi]}
        for name in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE)]}


def _annotations(trace, plane):
    """[[name, start_ns, duration_ns], ...] of the program's annotations
    on the host planes, around the runs kept, by start."""
    starts = [e[1] for e in plane["lines"][1]["events"]]
    ends = [e[1] + e[2] for e in plane["lines"][1]["events"]]
    lo, hi = min(starts), max(ends)
    lo, hi = lo - (hi - lo), hi + (hi - lo)
    names, begin, end = trace_reduce.host_events(trace)
    return sorted(([n, int(b), int(e - b)] for n, b, e in zip(names, begin, end)
                   if n.startswith(SPINE) and lo <= b and e <= hi),
                  key=lambda e: e[1])


def _first_spans(spans, key):
    """The spans of the first steps or batches (by `ids[key]`), what they
    enclose, and every compile."""
    first = sorted({s["ids"][key] for s in spans if key in s["ids"]})[:RUNS_KEPT]
    kept = [s for s in spans if s["ids"].get(key) in first]
    ids = {s["id"] for s in kept}
    kept += [s for s in spans if s["parent"] in ids and s["id"] not in ids]
    kept += [s for s in spans if s["name"] in ("jax.compile", "data.produce")
             and s not in kept][:RUNS_KEPT]
    return sorted(kept, key=lambda s: s["start_ns"])


def record(cell, manifest):
    from proteinbert_tpu.obs import tracing

    args = argparse.Namespace(workload=cell, seed=2147483659, seconds=1.0,
                              trace=1, rehearse=True)
    run = harness.Run(args, manifest)
    devices = harness._devices(run)
    driver = importlib.import_module("benchmark.drivers." + run.workload["driver"])
    tracing.recorder().clear()
    loaded = []     # the whole trace, host planes too, as the window read it
    summarize = trace_reduce.summarize
    with mock.patch.object(trace_reduce, "summarize", side_effect=lambda t: (
            loaded.append(t), summarize(t))[1]):
        out = driver.run(run, devices)
    program = out["obs"]["program"]
    plane = _first_runs(run.trace_summary["plane"], program)
    ops = {trace_reduce.short_name(e[0]) for e in plane["lines"][0]["events"]}
    scopes = tracing.program_scopes(program)
    spans = tracing.recorder().spans()
    return {
        "program": program,
        "device": devices[0].device_kind,
        "spans": _first_spans(spans, "step" if "pretrain" in cell else "batch"),
        "scopes": {k: v for k, v in scopes.items() if k in ops},
        "plane": plane,
        "annotations": _annotations(loaded[0], plane),
        "recorded": {"spans": len(spans), "instructions": len(scopes)},
    }


def main() -> int:
    from proteinbert_tpu.utils.compat import configure_compile_cache

    configure_compile_cache()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    fixture = {cell: record(cell, manifest) for cell in CELLS}
    for cell, got in fixture.items():
        print(cell, got["program"], got["recorded"], len(got["spans"]), "spans,",
              [len(ln["events"]) for ln in got["plane"]["lines"]], "events and",
              len(got["annotations"]), "annotations kept")
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "spans_v5e.json"), "w") as f:
        json.dump(fixture, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
