"""Model (models/glm_moe.py): assignments to held experts that no block of the
grouped products took, summed over the fetched steps (expected 0)."""
from benchmark import lm_readers


def read(obs):
    return lm_readers.counter_sum(obs, "dropped_assignments")
