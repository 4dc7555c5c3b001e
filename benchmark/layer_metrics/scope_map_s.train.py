"""Tracing's own cost: seconds of the `tracing.program_scopes` spans of the
run, the scope map of `train_step`; listed last, after the readers that ask."""
from benchmark import startup_readers


def read(obs):
    return startup_readers.scope_map_s(obs)
