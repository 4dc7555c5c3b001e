"""Selective state-space recurrence with a scalar decay a head (Mamba-2,
"state space duality", arXiv:2405.21060) over packed documents, and the
gated group norm behind it.

Per head h (its B and C those of group h // (H / G)), with a state S
(P x N) that is ZERO at each document's first token:

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T        a < 0, dt_t > 0
    y_t = S_t C_t

`ssd_recurrent` is that, token by token (`lax.scan` over tokens): what
the chunked form is tested and differentiated against. `ssd_chunked` is
what the model runs, in chunks of Q tokens with the state carried from
chunk to chunk: on a TPU, at sizes its tiles take, one Pallas kernel with
the state in VMEM (`kernels/ssd.py`); elsewhere, and at other sizes on a
TPU too, a `lax.scan` over the chunks in plain `jax.numpy` (`_ssd_plain`;
on the v5e that scan was 18.7 % of a served batch at 2.7 % of its
roofline: PERF.md sections 5 and 6, PR 44). Both compute the same numbers
at the same places. With l_t = dt_t a, Lam the running sum of l inside
the chunk and S_0 the state the chunk starts from:

    y_t  = sum over s <= t of one document of
               exp(Lam_t - Lam_s) dt_s (C_t . B_s) x_s          (inside)
         + exp(Lam_t) S_0 C_t      where t continues the document that
                                   the previous chunk ended in   (carried)
    S_Q  = exp(Lam_Q) S_0          where the whole chunk continues it
         + sum over the s of the chunk's LAST document of
               exp(Lam_Q - Lam_s) dt_s x_s B_s^T

Every exponent is a sum of l over a range, so none is positive. A chunk
may hold the end of one document and the start of the next: the state is
reset INSIDE the chunk by the segment ids, no document is padded to a
chunk. That rests on the packer's contract that a segment id is one
contiguous run (`data/packing.py`), as `ops/kda.py` does.

The four products of a chunk (C B^T, its masked and decayed form against
x, C against the state, x against B) take their operands in `dtype` and
accumulate in float32; dt, the decays and the carried state are float32.
The plain form is differentiable as it stands and the dispatching one
through it (the kernel's backward is the plain form's, recomputed);
nothing of the size tokens x heads x Q ever stands in HBM whole (the
plain form's decay mask is rows x heads x Q x Q a chunk, 16 MiB at two
rows; the kernel's is one head's, in VMEM).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST


def ssd_recurrent(x, dt, a, b, c, segment_ids):
    """x: (B, L, H, P); dt: (B, L, H) float32, after its softplus; a:
    (H,) float32, negative; b, c: (B, L, G, N); segment_ids: (B, L).
    -> y (B, L, H, P) float32. One token a step, everything float32 at
    full precision."""
    f32 = jnp.float32
    B, L, H, P = x.shape
    G = b.shape[2]
    heads = lambda m: jnp.repeat(m.astype(f32), H // G, axis=2)  # noqa: E731
    first = jnp.concatenate(
        [jnp.ones((B, 1), bool), segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1)

    def step(S, t):
        x_t, dt_t, b_t, c_t, first_t = t
        decay = jnp.where(first_t[:, None], 0.0, jnp.exp(dt_t * a))     # (B, H)
        S = (decay[..., None, None] * S
             + jnp.einsum("bhp,bhn->bhpn", dt_t[..., None] * x_t, b_t, precision=_HI))
        return S, jnp.einsum("bhpn,bhn->bhp", S, c_t, precision=_HI)

    time_first = lambda m: jnp.moveaxis(m, 1, 0)  # noqa: E731
    _, y = lax.scan(step, jnp.zeros((B, H, P, b.shape[-1]), f32),
                    tuple(map(time_first, (x.astype(f32), dt.astype(f32), heads(b),
                                           heads(c), first))))
    return jnp.moveaxis(y, 0, 1)


def _ssd_plain(x, dt, a, b, c, segment_ids, chunk: int, dtype=jnp.float32):
    """The chunked recurrence as a `lax.scan` over chunks. Inside the scan
    the heads are ONE leading batch axis beside the rows, B and C repeated
    to their group's heads a chunk at a time."""
    f32 = jnp.float32
    B, L, H, P = x.shape
    G, N = b.shape[2:]
    R, Q = H // G, chunk
    precision = _HI if jnp.dtype(dtype) == f32 else None
    dot = lambda spec, m, n: jnp.einsum(  # noqa: E731
        spec, m.astype(dtype), n.astype(dtype), precision=precision,
        preferred_element_type=f32)
    # (B, L, heads, d) -> (chunks, B, heads, Q, d)
    chunks = lambda m: m.reshape(  # noqa: E731
        (B, L // Q, Q) + m.shape[2:]).transpose(1, 0, 3, 2, 4)
    heads = lambda m: jnp.repeat(m, R, axis=1)  # noqa: E731
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    a = a.astype(f32)[:, None]

    def step(carry, t):
        S, last_seg = carry                 # (B, H, P, N) float32, (B,)
        x_c, dt_c, b_c, c_c, seg = t        # (B, H, Q, P), (B, H, Q), (B, G, Q, N) x 2, (B, Q)
        lam = jnp.cumsum(dt_c * a, axis=-1)                             # (B, H, Q)
        pair = causal & (seg[:, :, None] == seg[:, None, :])            # (B, t, s)
        reach = jnp.where(pair[:, None], lam[..., :, None] - lam[..., None, :],
                          -jnp.inf)                                     # (B, H, t, s)
        scores = dot("bgtn,bgsn->bgts", c_c, b_c)
        m = heads(scores) * jnp.exp(reach) * dt_c[:, :, None, :]
        y = dot("bhts,bhsp->bhtp", m, x_c)
        carried = (seg == last_seg[:, None])[:, None, :]                # (B, 1, Q)
        y = y + (jnp.where(carried, jnp.exp(lam), 0.0)[..., None]
                 * dot("bhtn,bhpn->bhtp", heads(c_c), S))
        end = lam[..., -1]                                              # (B, H)
        to_end = (seg == seg[:, -1:])[:, None, :]
        w = jnp.where(to_end, jnp.exp(end[..., None] - lam) * dt_c, 0.0)
        kept = jnp.where((seg[:, -1] == last_seg)[:, None], jnp.exp(end), 0.0)
        S = (kept[..., None, None] * S
             + dot("bhsp,bhsn->bhpn", w[..., None] * x_c.astype(f32), heads(b_c)))
        return (S, seg[:, -1]), y

    init = (jnp.zeros((B, H, P, N), f32), jnp.full((B,), -1, segment_ids.dtype))
    _, y = lax.scan(step, init, (
        chunks(x), chunks(dt.astype(f32)[..., None])[..., 0], chunks(b), chunks(c),
        segment_ids.reshape(B, L // Q, Q).transpose(1, 0, 2)))
    return y.transpose(1, 0, 3, 2, 4).reshape(B, L, H, P)


def _ssd_tpu(x, dt, a, b, c, segment_ids, chunk: int, dtype=jnp.float32,
             interpret: bool = False):
    """The same through the Pallas kernel: x, B and C are read where they
    lie and y is written where the gated norm reads it; XLA makes only
    what is (B, L, H) float32 (`kernels/ssd.operands`)."""
    from proteinbert_tpu.kernels import ssd as kernels

    B, L, H, P = x.shape
    G = b.shape[2]
    y = kernels.ssd_chunks(
        x.reshape(B, L, H * P), b.reshape(B, L, -1), c.reshape(B, L, -1),
        *kernels.operands(dt, a, segment_ids, G, P, chunk), H, dtype,
        interpret=interpret)
    return y.reshape(B, L, H, P)


@partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd_by_platform(x, dt, a, b, c, segment_ids, chunk, dtype):
    return lax.platform_dependent(
        x, dt, a, b, c, segment_ids,
        tpu=partial(_ssd_tpu, chunk=chunk, dtype=dtype),
        default=partial(_ssd_plain, chunk=chunk, dtype=dtype))


def _ssd_fwd(x, dt, a, b, c, segment_ids, chunk, dtype):
    return (_ssd_by_platform(x, dt, a, b, c, segment_ids, chunk, dtype),
            (x, dt, a, b, c, segment_ids))


def _ssd_bwd(chunk, dtype, saved, g):
    *floats, segment_ids = saved
    _, vjp = jax.vjp(lambda *o: _ssd_plain(*o, segment_ids, chunk, dtype), *floats)
    return (*vjp(g), None)


_ssd_by_platform.defvjp(_ssd_fwd, _ssd_bwd)


def kernel_takes(x, b, chunk: int, dtype=jnp.float32) -> bool:
    """Whether `ssd_chunked` on a TPU runs these operands through the
    kernel (x: (B, L, H, P); b: (B, L, G, N))."""
    from proteinbert_tpu.kernels.ssd import tiles_fit

    return tiles_fit(x.shape[1], x.shape[2], x.shape[3], *b.shape[2:], chunk, dtype)


def ssd_chunked(x, dt, a, b, c, segment_ids, chunk: int, dtype=jnp.float32):
    """`ssd_recurrent` in chunks of `chunk` tokens (L a multiple of it);
    the products' operands in `dtype`. Where the kernel's tiles take the
    sizes (`kernel_takes`) a program lowered for a TPU runs the kernel and
    any other the plain scan; at other sizes the plain scan everywhere.
    One backward, the plain scan's."""
    if x.shape[1] % chunk:
        raise ValueError(
            f"a row of {x.shape[1]} positions is no multiple of the chunk {chunk}")
    if kernel_takes(x, b, chunk, dtype):
        return _ssd_by_platform(x, dt, a, b, c, segment_ids, chunk, dtype)
    return _ssd_plain(x, dt, a, b, c, segment_ids, chunk, dtype)


def gated_group_norm(scale, y, z, groups: int, eps: float):
    """RMSNorm(y * silu(z)) over each of `groups` equal runs of channels
    apart (gate, THEN norm), one learned scale a channel; statistics
    float32. y, z: (..., C) -> (..., C) float32."""
    f32 = jnp.float32
    g = y.astype(f32) * jax.nn.silu(z.astype(f32))
    by_group = g.reshape(g.shape[:-1] + (groups, g.shape[-1] // groups))
    normed = by_group * lax.rsqrt(
        jnp.mean(by_group * by_group, axis=-1, keepdims=True) + eps)
    return normed.reshape(g.shape) * scale.astype(f32)
