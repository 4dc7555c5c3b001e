"""Model (ops/moe.py): device ms a served batch under the `moe_latent` scope: the products down to
the experts' latent before the loop and up from it after, all expert layers of a batch."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "moe_latent")
