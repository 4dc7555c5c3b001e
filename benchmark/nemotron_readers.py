"""Reader PR 43 added: the roofline share of the Mamba-2 mixer's
state-space recurrence (`ops/ssd.py`, the `ssd_core` scope). Like every
reader, one that finds nothing to read (a program without the scope, a run
without a trace) returns None."""

from __future__ import annotations

from benchmark import flops, span_readers


def ssd_core_roofline_pct(obs):
    """The least time the chip could take for the recurrences of one
    batch (`nemotron_flops.ssd_core_flops`, the token recurrence's
    operations over the real tokens, or x, B, C and dt read and y written
    once, whichever takes longer) over the device time of everything
    under `ssd_core`."""
    ms = span_readers.scope_ms(obs, "ssd_core")
    if not ms or not obs.get("peaks") or not obs.get("ssd_core_flops"):
        return None
    least = flops.roofline(obs["ssd_core_flops"], obs["ssd_core_min_bytes"],
                           obs["peaks"])
    return 100.0 * least["min_s"] / (ms * 1e-3)
