"""Model (models/glm_moe.py): the fullest held expert's tokens over the held experts' mean,
all expert layers pooled; the packed executable's own counter, mean over the window's batches."""
from benchmark import hybrid_readers


def read(obs):
    return hybrid_readers.load_max_over_mean(obs)
