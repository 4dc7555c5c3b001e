"""Operations and bytes of one call, from shapes alone, and the peaks.

The arithmetic is a copy of `proteinbert_tpu/train/metrics.forward_flops`
(checked against it in tests/benchmark), extended by the packed serving
forward pass, and kept here so that no later PR can move the yardstick.
Every matrix product counts 2 operations per multiply-add; training is
three forward passes (forward, and twice that for the backward pass);
recomputation under `remat` is not counted.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; unknown = error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise ValueError(
            f"no peaks for device_kind {device_kind!r} in benchmark/peaks.json; "
            "add a row with its public source")
    return table[device_kind]


def param_count(m: dict) -> int:
    C, G, A, V = m["local_dim"], m["global_dim"], m["num_annotations"], m["vocab_size"]
    H, k, v = m["num_heads"], m["key_dim"], m["global_dim"] // m["num_heads"]
    block = (
        (m["narrow_kernel"] + m["wide_kernel"]) * C * C + 2 * C   # convs
        + G * C + C + C * C + C + 4 * C                           # g->l, dense, 2 LN
        + 2 * (G * G + G) + 4 * G                                 # 2 dense, 2 LN
        + H * (G * k + C * k + C * v)                             # attention
    )
    return m["num_blocks"] * block + V * C + A * G + G + C * V + V + G * A + A


def forward_flops(m: dict, rows: int, seq_len: int, segments: int = 1,
                  heads: bool = True) -> float:
    """Forward pass over `rows` rows of `seq_len` positions.

    `segments` > 1 is the packed form: every row carries that many global
    tracks, and each scores all of the row's positions. `heads=False`
    leaves out the two output heads and adds the per-segment mean of the
    local track, which is what an `embed` request runs.
    """
    C, G, A = m["local_dim"], m["global_dim"], m["num_annotations"]
    H, k, v = m["num_heads"], m["key_dim"], m["global_dim"] // m["num_heads"]
    T = float(rows * seq_len)          # positions
    B = float(rows * segments)         # global tracks
    per_block = (
        2 * T * m["narrow_kernel"] * C * C
        + 2 * T * m["wide_kernel"] * C * C
        + 2 * B * G * C                # global -> local broadcast
        + 2 * T * C * C                # local dense
        + 2 * B * G * G                # global dense 1
        + 2 * B * H * G * k            # attention q
        + 2 * T * H * C * k            # attention K
        + 2 * T * H * C * v            # attention V
        + 2 * H * T * k * segments     # scores
        + 2 * H * T * v * segments     # weighted sum
        + 2 * B * G * G                # global dense 2
    )
    io = 2 * B * A * G                 # global input dense
    if heads:
        io += 2 * T * C * m["vocab_size"] + 2 * B * G * A
    else:
        io += 2 * T * segments * C     # mean of the local track per segment
    return float(m["num_blocks"] * per_block + io)


def train_flops(m: dict, rows: int, seq_len: int) -> float:
    return 3.0 * forward_flops(m, rows, seq_len)


def train_min_bytes(m: dict, rows: int, seq_len: int) -> float:
    """The least HBM traffic one optimizer step needs: float32
    parameters and both Adam moments read and written once, the clean
    batch read once. Activations kept for the backward pass are a choice
    of the program and are not counted."""
    return 24.0 * param_count(m) + 4.0 * rows * (seq_len + m["num_annotations"])


def embed_min_bytes(m: dict, rows: int, seq_len: int, segments: int) -> float:
    """The least HBM traffic one packed `embed` batch needs: the
    parameters read once in float32, tokens, segment ids and per-segment
    annotations read once, both float32 outputs written once."""
    io_in = 4.0 * rows * (2 * seq_len + segments * m["num_annotations"])
    io_out = 4.0 * rows * segments * (m["local_dim"] + m["global_dim"])
    return 4.0 * param_count(m) + io_in + io_out


def roofline(flops: float, nbytes: float, peaks: dict) -> dict:
    """The least seconds the chip could take, and which peak sets it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"min_s": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
