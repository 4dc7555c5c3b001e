"""Model: device ms a packed batch under the `attention` scope of
`_packed_encode_batch`."""
from benchmark import span_readers


def read(obs):
    return span_readers.scope_ms(obs, "attention")
