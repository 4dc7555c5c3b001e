"""Readers PR 28 added: the decoder's step counters, the grouped products'
roofline share, and the collectives of a sharded step. Like every reader,
one that finds nothing to read returns None."""

from __future__ import annotations

import re

import numpy as np

from benchmark import flops, span_readers, trace_reduce

COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|all-to-all"
                        r"|collective-permute)")


def _counter(obs, name):
    values = [h[name] for h in obs.get("counters") or () if name in h]
    return np.array(values, float) if values else None


def counter_mean(obs, name):
    """Mean of one of the step's counters over the steps whose metrics
    the trainer fetched inside the window (its log cadence)."""
    values = _counter(obs, name)
    return None if values is None else float(values.mean())


def counter_sum(obs, name):
    values = _counter(obs, name)
    return None if values is None else float(values.sum())


def moe_experts_roofline_pct(obs):
    """The least time the chip could take for the grouped products of one
    step (`lm_flops.moe_experts_flops` over the assignments that fell on
    the held experts, `moe_experts_min_bytes`) over the device time of
    everything under the `moe_experts` scope, recomputation included."""
    ms = span_readers.scope_ms(obs, "moe_experts")
    if not ms or not obs.get("peaks") or not obs.get("moe_experts_flops"):
        return None
    least = flops.roofline(obs["moe_experts_flops"], obs["moe_experts_min_bytes"],
                           obs["peaks"])
    return 100.0 * least["min_s"] / (ms * 1e-3)


def collective_ms(obs):
    """Device ms a run of the cell's program in collective operations, on
    the first chip's plane: an asynchronous pair counts from its start
    to its done only where the events themselves say so (each event's
    own duration is summed, a loop around them left out)."""
    trace = obs.get("trace")
    if not trace or "plane" not in trace or not obs.get("program"):
        return None
    runs = trace_reduce.program_runs(trace["plane"], obs["program"])
    if not runs:
        return None
    spans = np.array([(s, e) for s, e, _ in runs], np.int64)
    total = 0
    for line in trace["plane"]["lines"]:
        if line["name"] != trace_reduce.OPS_LINE:
            continue
        for name, start, duration in line["events"]:
            if not COLLECTIVE.match(trace_reduce.short_name(name)):
                continue
            at = np.searchsorted(spans[:, 0], start, side="right") - 1
            if at >= 0 and start < spans[at, 1]:
                total += duration
    return 1e3 * total * 1e-9 / len(runs)
