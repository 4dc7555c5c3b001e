"""Readers PR 35 added: the roofline shares of the CCA mixer's attention
core (the flash forward kernel with grouped keys) and of the served
experts' grouped products. Like every reader, one that finds nothing to
read (a program without the scope, a run without a trace) returns None."""

from __future__ import annotations

from benchmark import flops, span_readers


def _share_pct(obs, scope: str, ops_key: str, bytes_key: str):
    ms = span_readers.scope_ms(obs, scope)
    if not ms or not obs.get("peaks") or not obs.get(ops_key):
        return None
    least = flops.roofline(obs[ops_key], obs[bytes_key], obs["peaks"])
    return 100.0 * least["min_s"] / (ms * 1e-3)


def cca_core_roofline_pct(obs):
    """The least time the chip could take for the attention cores of one
    batch (`cca_flops.core_flops` over the pairs causal and inside a
    document, or q, k, v read and o written once, whichever takes
    longer) over the device time of everything under `cca_core`."""
    return _share_pct(obs, "cca_core", "cca_core_flops", "cca_core_min_bytes")


def moe_experts_roofline_pct(obs):
    """The same of the experts' grouped products of one batch
    (`cca_flops.experts_flops` over the batches' own assignments, or
    every expert's matrices read once a layer) over `moe_experts`."""
    return _share_pct(obs, "moe_experts", "served_experts_flops",
                      "served_experts_min_bytes")
