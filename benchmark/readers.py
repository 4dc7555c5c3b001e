"""What the per-layer readers share. Each reader takes `obs`: what the
driver observed (counts, host-clock samples), the trace's summary
(`benchmark.trace_reduce.summarize`), the chip's peaks and the window's
length. A reader that finds nothing to read returns None and the metric is
left out of the line."""

from __future__ import annotations

import numpy as np

from benchmark import flops, trace_reduce


def _program(obs):
    """(runs, device seconds) of the cell's program in the traced window."""
    trace = obs.get("trace")
    if not trace or obs.get("program") not in trace["programs"]:
        return None
    return trace["programs"][obs["program"]]


def program_device_ms(obs):
    """Device time of one run of the cell's program: its operations'
    busy time in the trace over the number of runs."""
    got = _program(obs)
    return None if got is None or got[0] == 0 else 1e3 * got[1] / got[0]


def program_gap_ms(obs):
    """Mean time the chip sat idle between two runs of the program."""
    trace = obs.get("trace")
    if not trace or "plane" not in trace:
        return None
    gaps = trace_reduce.program_gaps_s(trace["plane"], obs["program"])
    return None if len(gaps) == 0 else 1e3 * float(gaps.mean())


def device_idle_pct(obs):
    trace = obs.get("trace")
    if not trace or not trace["window_s"] or not trace["busy_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def peak_hbm_gib(obs):
    peak = obs.get("memory_peak_bytes")
    return None if not peak else peak / 2.0 ** 30


def mfu_pct(obs):
    """Operations the forward and backward passes need, over all padded
    positions of the steps completed, over the window and the peak."""
    if not obs.get("peaks") or not obs.get("steps"):
        return None
    rate = obs["steps"] * obs["call_flops"] / obs["window_s"]
    return 100.0 * rate / obs["peaks"]["bf16_flops_per_s"]


def program_roofline_pct(obs):
    """The least time the chip could take for one run of the program
    (the larger of operations over peak FLOP/s and bytes over peak
    bytes/s, both from shapes) over its device time per run."""
    ms = program_device_ms(obs)
    if ms is None or not obs.get("peaks"):
        return None
    least = flops.roofline(obs["call_flops"], obs["call_min_bytes"], obs["peaks"])
    return 100.0 * least["min_s"] / (ms * 1e-3)


WAIT_STAGES = ("queue", "batch_form", "dispatch")


def queue_wait_ms(obs):
    """Median, over the requests' own traces (RequestTrace.stages()), of
    the time from the queue to the device: the stages `queue` (pushed,
    not yet taken by the scheduler), `batch_form` (in an open packed
    row) and `dispatch` (popped, waiting for a slot in the pipeline)."""
    stages = obs.get("stages")
    waits = [sum(s.get(k, 0.0) for k in WAIT_STAGES) for s in stages or ()
             if "batch_form" in s]
    return None if not waits else 1e3 * float(np.median(waits))


def batch_fill_pct(obs):
    """Real residues answered by the batches the server counted inside
    the window (`Server.stats()`, read before the window closes) over
    the positions of those batches."""
    if not obs.get("batches"):
        return None
    return 100.0 * obs["residues_in_batches"] / (
        obs["batches"] * obs["positions_per_batch"])


def latency_p95_ms(obs):
    lat = obs.get("latency_s")
    return None if lat is None or len(lat) == 0 else 1e3 * float(np.percentile(lat, 95))


def generator_late_ms(obs):
    late = obs.get("late_s")
    return None if late is None or len(late) == 0 else 1e3 * float(np.percentile(late, 95))
