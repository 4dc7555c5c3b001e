"""What the per-layer readers share. Each reader takes `obs`: what the
driver observed (counts, host-clock samples), the trace's summary
(`benchmark.trace_reduce.summarize`), the chip's peaks and the window's
length. A reader that finds nothing to read returns None and the metric is
left out of the line."""

from __future__ import annotations

import numpy as np

from benchmark import flops, span_readers, trace_reduce


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
    the positions those batches really computed, row class x length
    each (`batched_positions`, the difference of the same two reads)."""
    if not obs.get("batched_positions"):
        return None
    return 100.0 * obs["residues_in_batches"] / obs["batched_positions"]


def serve_mfu_pct(obs):
    """Operations the forward pass needs for the batches the window ran,
    each at its own row class, over the WHOLE window and the peak: idle
    time, the host's share and padding rows all count against it."""
    counts, classes = obs.get("batch_class_counts"), obs.get("classes")
    if not obs.get("peaks") or not counts or not classes or not obs.get("window_s"):
        return None
    work = sum(n * classes[cls]["flops"] for cls, n in counts.items())
    return 100.0 * work / obs["window_s"] / obs["peaks"]["bf16_flops_per_s"]


def packed_roofline_pct(obs):
    """Over the runs of the packed executable in the traced window whose
    row class the trace itself tells (`span_readers.class_runs`): the
    least seconds the chip could take for each run's OWN class, summed,
    over the device seconds of the same runs. Where every run is of one
    class this is `program_roofline_pct` of that class. `class_runs`
    gives nothing where the join of runs to launches is not sound (more
    runs untold or out of step than a window's edges explain): a share
    over part of the runs is not reported as the window's."""
    runs, classes = span_readers.class_runs(obs), obs.get("classes")
    if not runs or not classes or not obs.get("peaks"):
        return None
    least = {cls: flops.roofline(c["flops"], c["min_bytes"], obs["peaks"])["min_s"]
             for cls, c in classes.items()}
    told = [(cls, seconds) for cls, seconds in runs if cls in least]
    device_s = sum(seconds for _, seconds in told)
    if not device_s:
        return None
    return 100.0 * sum(least[cls] for cls, _ in told) / device_s


def latency_p95_ms(obs):
    """The 95th percentile of latency from the due time over EVERY
    request that was due in the window, process stalls and all; one that
    failed or was never answered is in `latency_s` with the time it
    waited. The steady cell's `embed_latency_p95_ms` is this number."""
    lat = obs.get("latency_s")
    return None if lat is None or len(lat) == 0 else 1e3 * float(np.percentile(lat, 95))


INTERVAL_S = 0.25   # 40 to a 10 s window; ~560 requests each at 2,250/s
STALLED = 1.5       # an interval whose p95 is over this x the typical one


def interval_p95s_ms(latency_s, due_s, seconds, width=INTERVAL_S) -> np.ndarray:
    """The 95th percentile of latency in each WHOLE interval of the
    window (a quarter of a second), over all the requests that were DUE
    in it. A last part of an interval is left out, and so is an interval
    in which nothing was due."""
    latency_s, due_s = np.asarray(latency_s), np.asarray(due_s)
    at = np.floor(due_s / width).astype(np.int64)
    return np.array([1e3 * float(np.percentile(latency_s[at == k], 95))
                     for k in range(int(seconds / width)) if (at == k).any()])


def _interval_p95s(obs):
    if obs.get("latency_s") is None or obs.get("due_s") is None:
        return None
    per_interval = interval_p95s_ms(obs["latency_s"], obs["due_s"],
                                    obs["seconds"])
    return per_interval if len(per_interval) else None


def typical_p95_ms(obs):
    """Recorded, not judged: the median of the p95s of the window's whole
    quarter-seconds. The process stalls some 0.1 s three times in ten
    seconds (full garbage collections) and a second or two more now and
    then; a stall and the backlog it leaves lift the intervals they fall
    in and no other, so this reads the server between its stalls, the
    floor under the whole window's tail (`embed_latency_p95_ms`), and
    `stalled_seconds` how much of the window lay above it."""
    per_interval = _interval_p95s(obs)
    return None if per_interval is None else float(np.median(per_interval))


def stalled_seconds(obs):
    """The seconds of the window (whole quarter-seconds, summed) whose
    p95 reads over 1.5 x the typical one: what lifts the whole window's
    tail above the typical interval's."""
    per_interval = _interval_p95s(obs)
    if per_interval is None:
        return None
    return INTERVAL_S * float(
        (per_interval > STALLED * np.median(per_interval)).sum())


def backlog_growth_per_s(latency_s, due_s, seconds, every=0.05) -> float:
    """How fast the requests due and not yet answered pile up, in
    requests a second: the backlog sampled every 50 ms, its median over
    the window's last quarter less its median over the third quarter,
    over a quarter's length. Medians, so that a stall of up to half a
    quarter and the backlog it leaves (or one that falls on the close)
    do not decide it: at a rate the server carries this reads within a
    hundredth of the rate of 0 (-13 to +22 requests/s over ten 10 s
    windows at 2,250/s), past it the rate less what the server carries."""
    due = np.sort(np.asarray(due_s, float))
    done = np.sort(np.asarray(due_s, float) + np.asarray(latency_s, float))
    at = np.arange(every, seconds + every / 2, every)
    backlog = (np.searchsorted(due, at, side="right")
               - np.searchsorted(done, at, side="right"))
    third = backlog[(at > 0.5 * seconds) & (at <= 0.75 * seconds)]
    last = backlog[at > 0.75 * seconds]
    if len(third) == 0 or len(last) == 0:
        return 0.0
    return float(np.median(last) - np.median(third)) / (0.25 * seconds)


def generator_late_ms(obs):
    late = obs.get("late_s")
    return None if late is None or len(late) == 0 else 1e3 * float(np.percentile(late, 95))
