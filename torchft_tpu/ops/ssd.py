"""The selective state-space scan of a Mamba-2 layer, by chunks, and the short
causal depthwise convolution in front of it. Plain XLA: einsums for the MXU,
elementwise float32 for the decays.

Per head ``h`` (``x_t`` in R^P, the state ``S`` in R^{P x N}, ``B_t`` and
``C_t`` in R^N shared by the heads of a group, ``dt_t > 0``, ``A < 0``):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S_{-1} = 0
    y_t = S_t C_t + D x_t

:func:`ssd_scan` computes it in chunks of ``chunk`` positions (the
"state-space duality" form of Mamba-2, arXiv:2405.21060, section 6), four
parts under four ``jax.named_scope``s, with ``a_t = dt_t A`` and ``c`` its
running sum inside a chunk:

- ``tpuft::ssd::intra_chunk``: what a chunk's own inputs give its outputs,
  the masked quadratic form ``y_t += sum_{u <= t} exp(c_t - c_u) (C_t . B_u)
  dt_u x_u``: one ``(chunk, chunk)`` score matrix a group, one decay matrix a
  head (``(b, chunks, H, chunk, chunk)``), one product with ``x`` a head;
- ``tpuft::ssd::chunk_states``: what a chunk's inputs leave in the state at
  its end, ``sum_u exp(c_last - c_u) dt_u x_u B_u^T``: ``(b, chunks, H, P, N)``;
- ``tpuft::ssd::inter_chunk``: the recurrence over the chunk boundaries, the
  state ENTERING each chunk, as one masked product over (chunk, earlier chunk)
  pairs of the decays between them: no loop, 32 x 32 at 8192 positions;
- ``tpuft::ssd::state_out``: what the entering state gives a chunk's outputs,
  ``y_t += exp(c_t) S_entering C_t``.

Decays, ``dt``, the running sums and the carried state are float32; the
operands of the three large products are in ``x``'s dtype with float32
accumulation (the carried state is rounded to it as an operand of the last
product, nowhere else). A decay is always ``exp`` of a DIFFERENCE of running
sums taken under the mask, never a quotient of two exponentials: a chunk's
total log-decay runs to minus several hundred. The backward is autodiff through
these four parts (under the layer stack's remat a layer's decay matrices live
only while that layer's backward runs); a sequence that is not whole chunks is
padded with ``dt = 0`` positions, which neither decay nor feed the state.

:func:`ssd_recurrence` is the same mathematics position by position in
float32, the oracle of the tests and of ``scripts/granite_check.py``;
:func:`causal_conv` the depthwise convolution; :func:`chunk_log_decay` what a
model sows to say whether its seeded decays carry state across chunks.
Nothing here imports models/.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["causal_conv", "chunk_log_decay", "ssd_recurrence", "ssd_scan"]

_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def causal_conv(x: jnp.ndarray, kernel: jnp.ndarray, bias: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal convolution along the sequence: ``out[t] = bias +
    sum_j kernel[:, j] * x[t - (width - 1) + j]`` with zeros before the
    sequence. x (b, s, channels), kernel (channels, width), bias (channels,);
    float32 out (the caller's activation rounds it)."""
    width, s = kernel.shape[-1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0))).astype(_F32)
    taps = kernel.astype(_F32)
    out = bias.astype(_F32)
    for j in range(width):
        out = out + padded[:, j:j + s] * taps[:, j]
    return out


def _by_chunk(x: jnp.ndarray, dt: jnp.ndarray, b_in: jnp.ndarray, c_out: jnp.ndarray, chunk: int):
    """The four sequences cut into chunks, heads split (group, head of the
    group): x (b, c, q, g, r, p), dt (b, c, q, g, r), B and C (b, c, q, g, n).
    A ragged tail is padded with ``dt = 0`` positions."""
    b, s, heads, p = x.shape
    groups, n = b_in.shape[2:]
    q = min(chunk, s)
    pad = -s % q
    if pad:
        x, dt, b_in, c_out = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (x, dt, b_in, c_out)
        )
    c, r = (s + pad) // q, heads // groups
    return (
        x.reshape(b, c, q, groups, r, p), dt.astype(_F32).reshape(b, c, q, groups, r),
        b_in.reshape(b, c, q, groups, n), c_out.reshape(b, c, q, groups, n),
    )


def chunk_log_decay(dt: jnp.ndarray, a: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """(smallest, largest) total log-decay of a chunk over batch, chunks and
    heads: the sum of ``dt_t A`` over a chunk's positions. Near 0 a head
    carries its state across chunks whole; at -20 nothing of it arrives.
    dt (b, s, H) positive, a (H,) negative; float32 (2,)."""
    b, s, heads = dt.shape
    q = min(chunk, s)
    whole = (s // q) * q  # a ragged tail is not a chunk's worth
    totals = jnp.sum((dt.astype(_F32) * a.astype(_F32))[:, :whole].reshape(b, -1, q, heads), axis=2)
    return jnp.stack([jnp.min(totals), jnp.max(totals)])


def ssd_scan(
    x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray, b_in: jnp.ndarray, c_out: jnp.ndarray,
    d_skip: jnp.ndarray, chunk: int = 256,
) -> jnp.ndarray:
    """x (b, s, H, P), dt (b, s, H) (after its softplus), a (H,) (negative),
    b_in and c_out (b, s, G, N) with G dividing H, d_skip (H,) ->
    y (b, s, H, P) in x's dtype. See the module's docstring."""
    b, s, heads, p = x.shape
    dtype = x.dtype
    xs, dts, bs, cs = _by_chunk(x, dt, b_in, c_out, chunk)
    q, groups, r = xs.shape[2:5]
    log_decay = dts * a.astype(_F32).reshape(groups, r)
    within = jnp.cumsum(log_decay, axis=2)  # (b, c, q, g, r), inclusive
    x_dt = (xs.astype(_F32) * dts[..., None]).astype(dtype)

    with jax.named_scope("tpuft::ssd::intra_chunk"):
        scores = jnp.einsum("bctgn,bcugn->bcgtu", cs, bs, preferred_element_type=_F32)
        by_head = within.transpose(0, 1, 3, 4, 2)  # (b, c, g, r, q)
        apart = by_head[..., :, None] - by_head[..., None, :]  # c_t - c_u
        causal = jnp.tril(jnp.ones((q, q), dtype=bool))
        decay = jnp.exp(jnp.where(causal, apart, -jnp.inf))  # (b, c, g, r, t, u)
        weights = (scores[:, :, :, None] * decay).astype(dtype)
        y = jnp.einsum("bcgrtu,bcugrp->bctgrp", weights, x_dt, preferred_element_type=_F32)

    with jax.named_scope("tpuft::ssd::chunk_states"):
        to_end = jnp.exp(within[:, :, -1:] - within)  # (b, c, q, g, r)
        fed = (x_dt.astype(_F32) * to_end[..., None]).astype(dtype)
        states = jnp.einsum("bcugn,bcugrp->bcgrpn", bs, fed, preferred_element_type=_F32)

    with jax.named_scope("tpuft::ssd::inter_chunk"):
        total = within[:, :, -1]  # (b, c, g, r): a chunk's whole log-decay
        through = jnp.cumsum(total, axis=1)  # up to and with chunk c
        between = (through - total)[:, :, None] - through[:, None, :]  # (b, z, c, g, r)
        n_chunks = total.shape[1]
        earlier = jnp.tril(jnp.ones((n_chunks, n_chunks), dtype=bool), -1)[..., None, None]
        carried = jnp.exp(jnp.where(earlier, between, -jnp.inf))
        entering = jnp.einsum("bzcgr,bcgrpn->bzgrpn", carried, states, precision=_HIGHEST)

    with jax.named_scope("tpuft::ssd::state_out"):
        from_state = jnp.einsum(
            "bctgn,bcgrpn->bctgrp", cs, entering.astype(dtype), preferred_element_type=_F32
        )
        y = y + from_state * jnp.exp(within)[..., None]

    y = y + xs.astype(_F32) * d_skip.astype(_F32).reshape(groups, r, 1)
    return y.reshape(b, -1, heads, p)[:, :s].astype(dtype)


def ssd_recurrence(
    x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray, b_in: jnp.ndarray, c_out: jnp.ndarray,
    d_skip: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The recurrence itself, one position after the other in float32 at the
    highest precision: (y (b, s, H, P), the final state (b, H, P, N)). The
    oracle :func:`ssd_scan` is held to; arguments as there."""
    b, s, heads, p = x.shape
    groups, n = b_in.shape[2:]
    of_head = lambda z: jnp.repeat(z.astype(_F32), heads // groups, axis=2)  # (b, s, H, n)
    a, d_skip = a.astype(_F32), d_skip.astype(_F32)

    def step(state, at):
        x_t, dt_t, b_t, c_t = at  # (b, H, P), (b, H), (b, H, n), (b, H, n)
        fed = jnp.einsum("bhp,bhn->bhpn", x_t * dt_t[..., None], b_t, precision=_HIGHEST)
        state = jnp.exp(dt_t * a)[..., None, None] * state + fed
        y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=_HIGHEST) + d_skip[:, None] * x_t
        return state, y_t

    rows = (x.astype(_F32), dt.astype(_F32), of_head(b_in), of_head(c_out))
    by_position = tuple(jnp.moveaxis(z, 1, 0) for z in rows)
    final, ys = jax.lax.scan(step, jnp.zeros((b, heads, p, n), _F32), by_position)
    return jnp.moveaxis(ys, 0, 1), final
