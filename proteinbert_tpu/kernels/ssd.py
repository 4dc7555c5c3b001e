"""Pallas TPU kernel of the chunked Mamba-2 recurrence (`ops/ssd.py` has
the mathematics, the plain form and the dispatch).

In plain jax the recurrence is a `lax.scan` over chunks: a dozen small
operations a step with the (B, H, P, N) float32 state carried through
HBM, between a transpose of x, B, C, dt into chunks and a transpose of
the stacked y back (11.9 ms a layer at 8,192 tokens on the v5e, 2.7 % of
its roofline: PERF.md section 5). Here one grid step is ONE group's heads
over ONE chunk, the chunks of a row in order on the last grid axis, and
the group's state stays in VMEM for the whole row:

  x       (B, L, H * P)     block (Q, R * P): a group's R heads are
                            contiguous channels, read where the split
                            of the convolution's output leaves them
  B, C    (B, L, G * N)     block (Q, N), read ONCE a group and never
                            repeated to its heads
  y       (B, L, H * P)     float32, written where the gated norm reads it
  state   (N, R * P)        float32 scratch, S^T of the group's heads side
                            by side, zeroed at a row's first chunk

so that `C S` and `B^T (w x)` are one product each for the sixteen heads
and only the masked (Q, Q) product is a head's own. Heads narrower than a
lane tile share one: their product takes x with the other heads' lanes
zeroed, so no result is ever shifted along the lanes.

What is (B, L, H) float32, a 64th of x, XLA makes outside (`operands`):
the running sum Lam of dt a inside the chunk and dt with one position a
LANE (`rows`: they scale the mask's columns), Lam and the two decays that
scale ROWS with one position a sublane (`cols`), and the decay of the old
state (`kept`) spread over each head's lanes. The segment ids ride along
in both, as float32 (small whole numbers). The masks are `ssd_chunked`'s:
`carried` (this position continues the document the previous chunk ended
in), `to_end` (it belongs to the chunk's last document), `kept` (the
whole chunk continues it).

The numbers are the plain form's at the same places: products take their
operands in `dtype` and accumulate in float32; dt, every exponent (none
positive) and the state are float32, the state cast only as an operand.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from proteinbert_tpu.kernels.path_counter import KernelPathCounter
from proteinbert_tpu.kernels.vmem_budget import LANE, VMEM_BUDGET, fits, itemsize

_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_HI = lax.Precision.HIGHEST
PAD_ROWS = 8                        # sublanes the segment ids take in `rows`

_COUNTER = KernelPathCounter("ssd_core", "ssd_core_kernel_path_total")
SSD_CORE_PATH_TOTAL: Dict[Tuple[str, str], int] = _COUNTER.total


def register_ssd_core_path_observer(cb) -> None:
    _COUNTER.register(cb)


def unregister_ssd_core_path_observer(cb) -> None:
    _COUNTER.unregister(cb)


def note_ssd_core_path(path: str, reason: str,
                       shape: Optional[tuple] = None) -> None:
    _COUNTER.note(path, reason, shape)


def _tile(P: int) -> int:
    """Lanes one product's result takes: a head's, or a lane tile that
    whole heads share."""
    return max(P, LANE)


def tiles_fit(L: int, H: int, P: int, G: int, N: int, Q: int,
              dtype=jnp.bfloat16) -> bool:
    """Whether the kernel takes these sizes: the chunk, the state and a
    group's channels whole lane tiles, whole heads a tile, whole chunks a
    row, and a step's working set inside the budget."""
    if H % G or L % Q or Q % LANE or N % LANE:
        return False
    R, T = H // G, _tile(P)
    if T % P or (R * P) % T:
        return False
    wide, item = Q * R * P, itemsize(dtype)
    return fits(2 * wide * item, 2 * wide * 4,          # x, y: two buffers
                2 * 2 * Q * N * item,                   # B, C
                2 * Q * LANE * 4,                       # cols, padded to a tile
                N * R * P * (4 + item),                 # the state, and as an operand
                wide * (4 + item),                      # C S, w x
                4 * Q * Q * 4)                          # a head's mask in the making


def _chunk_kernel(x_ref, b_ref, c_ref, rows_ref, cols_ref, kept_ref, y_ref,
                  state_ref, *, R, P, dtype):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    f32 = jnp.float32
    Q, T = x_ref.shape[0], _tile(P)
    precision = _HI if jnp.dtype(dtype) == f32 else None
    dot = lambda m, n, dims=_NN: lax.dot_general(  # noqa: E731
        m.astype(dtype), n.astype(dtype), dims, precision=precision,
        preferred_element_type=f32)
    b, c = b_ref[...], c_ref[...]
    t = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    s = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    pair = (s <= t) & (cols_ref[:, 3 * R:3 * R + 1] == rows_ref[2 * R:2 * R + 1, :])
    scores = jnp.where(pair, dot(c, b, _NT), 0.0)                   # (t, s)
    state = state_ref[...]
    from_state = dot(c, state)                                      # (Q, R * P)
    head_of_lane = lax.broadcasted_iota(jnp.int32, (Q, T), 1) // P
    wx = []
    for j in range(R * P // T):
        x = x_ref[:, j * T:(j + 1) * T]
        y = carried = w = None
        for k in range(T // P):
            h = j * (T // P) + k
            mine = head_of_lane == k
            # inside a pair Lam_t - Lam_s is a sum of negatives; outside
            # the score is already zero and the exponent must not overflow
            reach = jnp.minimum(cols_ref[:, h:h + 1] - rows_ref[R + h:R + h + 1, :], 0.0)
            m = scores * jnp.exp(reach) * rows_ref[h:h + 1, :]
            part = dot(m, x if T == P else jnp.where(mine, x, jnp.zeros_like(x)))
            y = part if y is None else y + part
            carried_h, w_h = cols_ref[:, R + h:R + h + 1], cols_ref[:, 2 * R + h:2 * R + h + 1]
            carried = carried_h if carried is None else jnp.where(mine, carried_h, carried)
            w = w_h if w is None else jnp.where(mine, w_h, w)
        y_ref[:, j * T:(j + 1) * T] = y + carried * from_state[:, j * T:(j + 1) * T]
        wx.append((w * x.astype(f32)).astype(dtype))
    state_ref[...] = (kept_ref[...] * state
                      + dot(b, jnp.concatenate(wx, axis=1), _TN))


def operands(dt, a, segment_ids, G: int, P: int, Q: int):
    """dt: (B, L, H) float32; a: (H,); segment_ids: (B, L). -> (rows
    (B, G, n, 2R + PAD_ROWS, Q), cols (B, G, n, Q, 3R + 1), kept
    (B, G, n, 1, R * P)), float32: what the kernel reads of dt, the decays
    and the documents' bounds, chunk by chunk, by `ssd_chunked`'s own
    expressions."""
    f32 = jnp.float32
    B, L, H = dt.shape
    n, R = L // Q, H // G
    dt = dt.astype(f32).reshape(B, n, Q, H)
    seg = segment_ids.reshape(B, n, Q)
    lam = jnp.cumsum(dt * a.astype(f32), axis=2)
    end = lam[:, :, -1:, :]
    last = jnp.concatenate([jnp.full((B, 1), -1, seg.dtype), seg[:, :-1, -1]], axis=1)
    carried = (seg == last[..., None])[..., None]
    to_end = (seg == seg[..., -1:])[..., None]
    kept = jnp.where((seg[..., -1] == last)[..., None], jnp.exp(end[:, :, 0]), 0.0)
    ids = jnp.broadcast_to(seg.astype(f32)[:, None], (B, G, n, Q))
    # (B, n, Q, H) -> (B, G, n, Q, R), and with one position a lane
    col = lambda m: m.reshape(B, n, Q, G, R).transpose(0, 3, 1, 2, 4)  # noqa: E731
    row = lambda m: m.reshape(B, n, Q, G, R).transpose(0, 3, 1, 4, 2)  # noqa: E731
    rows = jnp.concatenate(
        [row(dt), row(lam), ids[..., None, :],
         jnp.zeros((B, G, n, PAD_ROWS - 1, Q), f32)], axis=3)
    cols = jnp.concatenate(
        [col(lam), col(jnp.where(carried, jnp.exp(lam), 0.0)),
         col(jnp.where(to_end, jnp.exp(end - lam) * dt, 0.0)), ids[..., None]], axis=4)
    kept = jnp.repeat(kept.reshape(B, n, G, R).transpose(0, 2, 1, 3), P, axis=-1)
    return rows, cols, kept[:, :, :, None, :]


def ssd_chunks(x, b, c, rows, cols, kept, heads: int, dtype,
               interpret: bool = False):
    """x: (B, L, H * P); b, c: (B, L, G * N); rows, cols, kept of
    `operands`. -> y (B, L, H * P) float32."""
    B, L, width = x.shape
    G, Q = rows.shape[1], rows.shape[4]
    R, N = heads // G, b.shape[2] // G
    P = width // heads
    per_token = lambda d: pl.BlockSpec(  # noqa: E731
        (None, Q, d), lambda i, g, n: (i, n, g))
    per_chunk = lambda m: pl.BlockSpec(  # noqa: E731
        (None, None, None) + m.shape[3:], lambda i, g, n: (i, g, n, 0, 0))
    return pl.pallas_call(
        partial(_chunk_kernel, R=R, P=P, dtype=dtype),
        grid=(B, G, L // Q),
        in_specs=[per_token(R * P), per_token(N), per_token(N),
                  per_chunk(rows), per_chunk(cols), per_chunk(kept)],
        out_specs=per_token(R * P),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, R * P), jnp.float32)],
        interpret=interpret, name="ssd_chunks",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET),
    )(x, b, c, rows, cols, kept)
