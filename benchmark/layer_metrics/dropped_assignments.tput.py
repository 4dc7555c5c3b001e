"""Model (models/glm_moe.py): assignments to held experts that no block took, summed over
the window's batches; the packed executable's own counter."""
from benchmark import hybrid_readers


def read(obs):
    return hybrid_readers.dropped_assignments(obs)
