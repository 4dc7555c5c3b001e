"""Model (models/glm_moe.py): share of the window's assignments that fell on the experts
held here (25 when balanced); the packed executable's own counters."""
from benchmark import hybrid_readers


def read(obs):
    return hybrid_readers.routed_here_share_pct(obs)
