"""Model (models/glm_moe.py): the fullest held expert's tokens over the held
experts' mean, all expert layers pooled; the step's own counter, mean over
the steps whose metrics the trainer fetched in the window."""
from benchmark import lm_readers


def read(obs):
    return lm_readers.counter_mean(obs, "expert_load_max_over_mean")
