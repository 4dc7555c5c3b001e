"""Model (models/glm_moe.py): share of the real tokens' assignments that fell on
the experts this chip holds (8 of 64: 12.5 where the router is balanced)."""
from benchmark import lm_readers


def read(obs):
    share = lm_readers.counter_mean(obs, "routed_here_share")
    return None if share is None else 100.0 * share
