"""Queue + scheduler: mean `serve.assemble` span (pop the rows, fill the grids)
a batch."""
from benchmark import span_readers


def read(obs):
    return span_readers.span_mean_ms(obs, "serve.assemble")
