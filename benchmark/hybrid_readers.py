"""Readers PR 33 added: the served hybrid decoder's routing counters (the
packed executable returns them with its answers; `Server.stats()` sums
them, the driver takes the window's difference) and the KDA core's
roofline share. Like every reader, one that finds nothing to read returns
None."""

from __future__ import annotations

from benchmark import flops, span_readers


def _routing(obs):
    routing = obs.get("routing")
    return routing if routing and routing.get("batches") else None


def load_max_over_mean(obs):
    """Mean over the window's batches of the fullest held expert's
    tokens over the held experts' mean, all expert layers pooled."""
    r = _routing(obs)
    return None if r is None else r["load_max_over_mean_sum"] / r["batches"]


def routed_here_share_pct(obs):
    """Share of the window's (real token, slot, expert layer)
    assignments that fell on the experts held here: 25 when balanced."""
    r = _routing(obs)
    if r is None or not r["real_tokens"]:
        return None
    return (100.0 * r["assignments_held"]
            / (r["real_tokens"] * r["top_k"] * r["expert_layers"]))


def dropped_assignments(obs):
    r = _routing(obs)
    return None if r is None else float(r["dropped_assignments"])


def kda_core_roofline_pct(obs):
    """The least time the chip could take for the KDA cores of one batch
    (the token recurrence's operations over the real tokens, or q, k, v,
    the log decay and beta read once and o written once, whichever takes
    longer: `hybrid_flops`) over the device time of everything under the
    `kda_core` scope."""
    ms = span_readers.scope_ms(obs, "kda_core")
    if not ms or not obs.get("peaks") or not obs.get("kda_core_flops"):
        return None
    least = flops.roofline(obs["kda_core_flops"], obs["kda_core_min_bytes"],
                           obs["peaks"])
    return 100.0 * least["min_s"] / (ms * 1e-3)
