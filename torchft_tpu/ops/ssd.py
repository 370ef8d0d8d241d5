"""The selective state-space scan of a Mamba-2 layer, by chunks, and the short
causal depthwise convolution in front of it: Mosaic (Pallas) kernels on a TPU,
plain XLA einsums everywhere else.

Per head ``h`` (``x_t`` in R^P, the state ``S`` in R^{P x N}, ``B_t`` and
``C_t`` in R^N shared by the heads of a group, ``dt_t > 0``, ``A < 0``):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S_{-1} = 0
    y_t = S_t C_t + D x_t

:func:`ssd_scan` computes it in chunks of ``chunk`` positions (the
"state-space duality" form of Mamba-2, arXiv:2405.21060, section 6), with
``a_t = dt_t A`` and ``c`` its running sum inside a chunk, as four parts:

- *intra-chunk*: what a chunk's own inputs give its outputs, the masked
  quadratic form ``y_t += sum_{u <= t} exp(c_t - c_u) (C_t . B_u) dt_u x_u``:
  one ``(chunk, chunk)`` score matrix a group, one decay matrix a head;
- *chunk states*: what a chunk's inputs leave in the state at its end,
  ``sum_u exp(c_last - c_u) dt_u x_u B_u^T``;
- *inter-chunk*: the recurrence over the chunk boundaries, the state ENTERING
  each chunk;
- *state out*: what the entering state gives a chunk's outputs,
  ``y_t += exp(c_t) S_entering C_t``.

Decays, ``dt``, the running sums and the carried state are float32; the
operands of the three large products are in ``x``'s dtype with float32
accumulation (``x dt``, the masked weights, the fed inputs, and the carried
state as an operand of the last product, nowhere else). A decay is always
``exp`` of a DIFFERENCE of running sums taken under the mask, never a quotient
of two exponentials: a chunk's total log-decay runs to minus several thousand.
That contract is both paths':

**Which path runs where.** :func:`ssd_scan` and :func:`conv_silu` take the
kernels where ``on_tpu()`` (or ``interpret=True``, the tests') and the shapes
fit them (:func:`scan_kernel_fits`, :func:`conv_kernel_fits`: whole chunks of
128 or 256 positions, heads whose width packs into 128 lanes or is a multiple
of them, a state of 128 or 256; rows by sixteens and channels by 128), else
the einsum path, which stays as the off-chip path, the ragged-tail path and the kernels'
oracle. No option chooses: the call's platform and shapes do.

- ``mamba_conv_fwd``: a ``(rows, channels)`` tile of the input in the run
  dtype with the sixteen rows before it, the taps and the bias float32 in
  registers, silu, ONE write in the run dtype. ``mamba_conv_bwd``: the row
  tiles in REVERSE order, the pre-activation recomputed, the input's gradient
  written once; the pre-activation's gradient of a tile's first rows is
  carried to the tile before it in VMEM, and the taps' and the bias's
  gradients accumulate in a resident float32 block. No padded copy and no
  float32 tensor in HBM.
- ``ssd_fwd``: a grid over (batch, chunk, block of heads), chunks in order;
  the state entering a chunk is carried in float32 VMEM scratch (``S <-
  exp(total) S + chunk state``: the einsum path's masked product over pairs of
  chunks is gone), a group's ``C B^T`` is made once a chunk, the running sums
  are formed in float32 by log-step shifts, and ``x dt`` and the ``D`` skip
  are inside. It writes y and, for the backward, the state entering each
  chunk. ``ssd_bwd``: the same grid in reverse chunk order carrying the
  state's gradient, recomputing a chunk's scores and decays (transposed, so
  that every product is one the MXU takes as it lies); a running sum's
  gradient is its column's sum of ``d(weights) * weights`` less its row's, in
  float32 from the unrounded weights as autodiff of the einsum path has it,
  summed from the chunk's end by the same log-step shifts. Scores, decays, running sums and chunk states live in VMEM only: HBM sees
  the inputs, the outputs, their gradients and the entering states.

The einsum path's parts are under four ``jax.named_scope``s
(``tpuft::ssd::intra_chunk``, ``::chunk_states``, ``::inter_chunk``,
``::state_out``); its backward is autodiff through them. A sequence that is
not whole chunks is padded there with ``dt = 0`` positions, which neither
decay nor feed the state.

:func:`ssd_recurrence` is the same mathematics position by position in
float32, the oracle of the tests and of ``scripts/granite_check.py``;
:func:`causal_conv` the depthwise convolution; :func:`chunk_log_decay` what a
model sows to say whether its seeded decays carry state across chunks.
Nothing here imports models/.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.utils.platform import on_tpu

__all__ = [
    "CONV_BWD", "CONV_FWD", "SSD_BWD", "SSD_FWD", "causal_conv", "chunk_log_decay",
    "conv_kernel_fits", "conv_silu", "scan_kernel_fits", "ssd_recurrence", "ssd_scan",
    "ssd_scan_einsums",
]

_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32

# What a device trace and the compiled step call the four Mosaic calls.
CONV_FWD = "mamba_conv_fwd"
CONV_BWD = "mamba_conv_bwd"
SSD_FWD = "ssd_fwd"
SSD_BWD = "ssd_bwd"

# Rows a convolution tile reads of the tile before it: one bfloat16 tile of
# sublanes, and no fewer than the taps reach back.
_HALO = 16
# Contractions of a dot_general by where the contracted dimension lies.
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


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


# -- the convolution's kernels --------------------------------------------------


def _conv_tiles(s: int, channels: int, width: int) -> Optional[Tuple[int, int, int]]:
    """(rows of a tile, rows of a step inside it, channels of a tile) the
    convolution's kernels walk ``(s, channels)`` in, or None where they do not
    take it."""
    if channels % 128 or not 1 <= width <= _HALO:
        return None
    lanes = 256 if channels % 256 == 0 else 128
    for rows in (1024, 512, 256, 128, 64, 32, 16):
        if s % rows == 0:
            return rows, min(rows, 64), lanes
    return None


def conv_kernel_fits(x: jnp.ndarray, kernel: jnp.ndarray) -> bool:
    """Whether :func:`conv_silu` runs x (b, s, channels) under kernel
    (channels, width) in its Mosaic kernels where the platform has them."""
    return (
        jnp.issubdtype(x.dtype, jnp.floating) and x.dtype.itemsize <= 4
        and _conv_tiles(x.shape[1], x.shape[2], kernel.shape[1]) is not None
    )


def _pre_activation(ext, taps, bias):
    """The convolution of the last ``rows - _HALO`` rows of ext (rows, C)
    float32, and the shifted inputs it read: ``shifted[d][t] = x[t - d]``."""
    width = taps.shape[0]
    shifted = [ext[_HALO:]] + [pltpu.roll(ext, d, 0)[_HALO:] for d in range(1, width)]
    pre = bias
    for d in range(width):
        pre = pre + taps[width - 1 - d: width - d] * shifted[d]
    return pre, shifted


def _conv_fwd_kernel(x_ref, halo_ref, taps_ref, bias_ref, o_ref, *, sub: int):
    """One (rows, C) tile: silu(bias + sum_j taps[j] x[t - (width - 1) + j]),
    ``sub`` rows a step so that a step's values stay in registers."""
    taps, bias = taps_ref[...], bias_ref[...]
    before = jnp.where(pl.program_id(1) > 0, halo_ref[0].astype(_F32), 0.0)

    def rows_of(ext):
        pre, _ = _pre_activation(ext, taps, bias)
        return (pre * jax.nn.sigmoid(pre)).astype(o_ref.dtype)

    o_ref[0, :sub] = rows_of(jnp.concatenate([before, x_ref[0, :sub].astype(_F32)]))

    def step(k, _):
        at = pl.multiple_of(k * sub, sub)
        ext = x_ref[0, pl.ds(pl.multiple_of(at - _HALO, _HALO), _HALO + sub), :]
        o_ref[0, pl.ds(at, sub), :] = rows_of(ext.astype(_F32))
        return _

    if x_ref.shape[1] > sub:
        jax.lax.fori_loop(1, x_ref.shape[1] // sub, step, None)


def _conv_bwd_kernel(
    x_ref, halo_ref, g_ref, taps_ref, bias_ref, dx_ref, sums_ref, carry_ref, *, sub: int
):
    """One (rows, C) tile, the tiles from the sequence's end: the input's
    gradient, and into ``sums_ref`` (resident over the row axis) eight partial
    rows of each tap's gradient and of the bias's."""
    taps, bias = taps_ref[...], bias_ref[...]
    width = taps.shape[0]
    tile = pl.program_id(2)  # 0 is the LAST tile of the sequence
    n_sub = x_ref.shape[1] // sub

    @pl.when(tile == 0)
    def _start():
        carry_ref[...] = jnp.zeros_like(carry_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    before = jnp.where(tile < pl.num_programs(2) - 1, halo_ref[0].astype(_F32), 0.0)

    def rows_of(ext, at, after):
        """x with its halo, where the step's rows lie, and the pre-activation's
        gradient of the _HALO rows after them -> that of its own first rows."""
        pre, shifted = _pre_activation(ext, taps, bias)
        gate = jax.nn.sigmoid(pre)
        d_pre = g_ref[0, pl.ds(at, sub), :].astype(_F32) * (gate * (1.0 + pre * (1.0 - gate)))
        below = jnp.concatenate([d_pre, after])
        dx = taps[width - 1: width] * d_pre
        for e in range(1, width):  # d_pre[t + e] came through tap width - 1 - e
            dx = dx + taps[width - 1 - e: width - e] * pltpu.roll(below, sub + _HALO - e, 0)[:sub]
        dx_ref[0, pl.ds(at, sub), :] = dx.astype(dx_ref.dtype)
        fold = lambda v: sum(v[r: r + 8] for r in range(0, sub, 8))
        for d in range(width):
            j = width - 1 - d
            sums_ref[0, 8 * j: 8 * j + 8] += fold(d_pre * shifted[d])
        sums_ref[0, 8 * width: 8 * width + 8] += fold(d_pre)
        return d_pre[:_HALO]

    def step(n, after):
        at = pl.multiple_of((n_sub - 1 - n) * sub, sub)
        ext = x_ref[0, pl.ds(pl.multiple_of(at - _HALO, _HALO), _HALO + sub), :]
        return rows_of(ext.astype(_F32), at, after)

    after = carry_ref[...]
    if n_sub > 1:
        after = jax.lax.fori_loop(0, n_sub - 1, step, after)
    carry_ref[...] = rows_of(jnp.concatenate([before, x_ref[0, :sub].astype(_F32)]), 0, after)


def _conv_specs(rows: int, lanes: int, tile_of):
    """BlockSpecs of a tile, of the _HALO rows before it, and of a (k, lanes)
    block of per-channel rows; ``tile_of(grid indices) -> (batch, row tile,
    channel tile)``."""
    def before(*grid):
        b, i, j = tile_of(*grid)
        return b, jnp.maximum(i * (rows // _HALO) - 1, 0), j

    return (
        pl.BlockSpec((1, rows, lanes), tile_of),
        pl.BlockSpec((1, _HALO, lanes), before),
        lambda k: pl.BlockSpec((k, lanes), lambda *grid: (0, tile_of(*grid)[2])),
    )


# The four calls are jitted where they are made: a model's layers trace each
# kernel's body once and lower it once, not once a layer (the step of nine
# Mamba layers: 14 s of every process start otherwise).
@partial(jax.jit, static_argnames="interpret")
def _conv_fwd_call(x, taps, bias, interpret: bool):
    b, s, channels = x.shape
    rows, sub, lanes = _conv_tiles(s, channels, taps.shape[0])
    tile, halo, per_channel = _conv_specs(rows, lanes, lambda bi, i, j: (bi, i, j))
    return pl.pallas_call(
        partial(_conv_fwd_kernel, sub=sub),
        grid=(b, s // rows, channels // lanes),
        in_specs=[tile, halo, per_channel(taps.shape[0]), per_channel(1)],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        name=CONV_FWD,
        interpret=interpret,
    )(x, x, taps, bias)


@partial(jax.jit, static_argnames="interpret")
def _conv_bwd_call(x, taps, bias, g, interpret: bool):
    b, s, channels = x.shape
    width = taps.shape[0]
    rows, sub, lanes = _conv_tiles(s, channels, width)
    n_tiles = s // rows
    tile, halo, per_channel = _conv_specs(
        rows, lanes, lambda bi, j, i: (bi, n_tiles - 1 - i, j)
    )
    return pl.pallas_call(
        partial(_conv_bwd_kernel, sub=sub),
        grid=(b, channels // lanes, n_tiles),
        in_specs=[tile, halo, tile, per_channel(width), per_channel(1)],
        out_specs=[tile, pl.BlockSpec((1, 8 * (width + 1), lanes), lambda bi, j, i: (bi, 0, j))],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((b, 8 * (width + 1), channels), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((_HALO, lanes), _F32)],
        name=CONV_BWD,
        interpret=interpret,
    )(x, x, g, taps, bias)


def _taps(kernel, bias):
    """The kernels' float32 operands: taps (width, channels), bias (1, channels)."""
    return kernel.astype(_F32).T, bias.astype(_F32)[None]


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_silu_kernels(x, kernel, bias, interpret):
    return _conv_fwd_call(x, *_taps(kernel, bias), interpret=interpret)


def _conv_silu_fwd(x, kernel, bias, interpret):
    return _conv_silu_kernels(x, kernel, bias, interpret), (x, kernel, bias)


def _conv_silu_bwd(interpret, kept, g):
    x, kernel, bias = kept
    dx, sums = _conv_bwd_call(x, *_taps(kernel, bias), g, interpret=interpret)
    width = kernel.shape[1]
    sums = jnp.sum(sums.reshape(x.shape[0], width + 1, 8, -1), axis=(0, 2))  # (width + 1, C)
    return dx, sums[:width].T.astype(kernel.dtype), sums[width].astype(bias.dtype)


_conv_silu_kernels.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def conv_silu(
    x: jnp.ndarray, kernel: jnp.ndarray, bias: jnp.ndarray, interpret: Optional[bool] = None
) -> jnp.ndarray:
    """``silu(causal_conv(x, kernel, bias))`` rounded ONCE, to x's dtype: the
    front of a Mamba-2 mixer. On a TPU, where the shapes fit
    (:func:`conv_kernel_fits`), the two Mosaic kernels of the module's
    docstring; elsewhere the XLA path. ``interpret=True`` runs the kernels in
    the Pallas interpreter (the tests'), ``False`` compiles them whatever the
    platform says (the chipless compiles')."""
    take = on_tpu() if interpret is None else True
    if take and conv_kernel_fits(x, kernel):
        return _conv_silu_kernels(x, kernel, bias, bool(interpret))
    return jax.nn.silu(causal_conv(x, kernel, bias)).astype(x.dtype)


# -- the scan's kernels -----------------------------------------------------------


def _heads_of_a_block(heads: int, p: int, groups: int) -> int:
    """Heads one grid step of the scan's kernels takes: of one group, their
    lanes a multiple of 128 and at most 1024 (sixteen heads of 64, which the
    backward's step holds in 15.5 of its 16 MiB); 0 where nothing does."""
    per_group = heads // groups
    for block in (16, 8, 4, 2, 1):
        if per_group % block == 0 and block * p % 128 == 0 and block * p <= 1024:
            return block
    return 0


def scan_kernel_fits(x: jnp.ndarray, b_in: jnp.ndarray, chunk: int) -> bool:
    """Whether :func:`ssd_scan` runs x (b, s, H, P) with b_in (b, s, G, N) by
    chunks of ``chunk`` in its Mosaic kernels where the platform has them:
    whole chunks of 128 or 256 positions (a step's scores, decays and their
    gradients are (chunk, chunk) float32 in the 16 MiB of VMEM a call gets
    without asking), a head width that packs into 128 lanes or is a multiple
    of them, a state of 128 or 256, and heads of one group that fill whole
    slabs of lanes."""
    _, s, heads, p = x.shape
    groups, n = b_in.shape[2:]
    return (
        jnp.issubdtype(x.dtype, jnp.floating) and x.dtype.itemsize <= 4
        and chunk in (128, 256) and s % chunk == 0 and n in (128, 256)
        and (128 % p == 0 or p % 128 == 0) and _heads_of_a_block(heads, p, groups) > 0
    )


def _running_sum(a, axis: int, reverse: bool = False):
    """Inclusive running sum of float32 ``a`` along ``axis`` by log-step
    shifts (the sums of the same positions in the same order whichever axis
    they lie along); ``reverse``: from the end."""
    n = a.shape[axis]
    at = jax.lax.broadcasted_iota(jnp.int32, a.shape, axis)
    step = 1
    while step < n:
        if reverse:
            a = a + jnp.where(at < n - step, pltpu.roll(a, n - step, axis), 0.0)
        else:
            a = a + jnp.where(at >= step, pltpu.roll(a, step, axis), 0.0)
        step *= 2
    return a


class _Slab:
    """A slab of 128 lanes (or one head's, where wider) of a block of heads:
    which heads lie in it, and the moves between a value a head and a value a
    lane."""

    @staticmethod
    def count(n_heads: int, p: int) -> int:
        """Slabs in a block of ``n_heads`` heads of ``p`` lanes."""
        return n_heads * p // (max(1, 128 // p) * p)

    def __init__(self, k: int, p: int):
        self.per = max(1, 128 // p)
        self.width = self.per * p
        self.lanes = slice(k * self.width, (k + 1) * self.width)
        self.heads = range(k * self.per, (k + 1) * self.per)
        self.head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (1, self.width), 1) // p

    def of(self, j: int):
        """(1, width) mask of the lanes of the slab's j-th head."""
        return self.head_of_lane == j

    def spread(self, by_head):
        """(rows, heads of the BLOCK) -> (rows, width): a head's column over
        its lanes."""
        # (a select and not a bare broadcast: Mosaic broadcasts along one of
        # sublanes and lanes at a time)
        out = jnp.where(self.of(0), by_head[:, self.heads[0]: self.heads[0] + 1], 0.0)
        for j in range(1, self.per):
            h = self.heads[j]
            out = jnp.where(self.of(j), by_head[:, h: h + 1], out)
        return jnp.broadcast_to(out, (by_head.shape[0], self.width))

    def gather(self, by_lane, into):
        """(rows, width) -> the sums over each head's lanes, set into the
        heads' columns of ``into`` (rows, heads of the block)."""
        column = jax.lax.broadcasted_iota(jnp.int32, (1, into.shape[1]), 1)
        for j, h in enumerate(self.heads):
            mine = by_lane if self.per == 1 else jnp.where(self.of(j), by_lane, 0.0)
            into = jnp.where(column == h, jnp.sum(mine, axis=1, keepdims=True), into)
        return into


def _decays(a_col_ref, a_row_ref):
    """A chunk's running sums of ``a_t = dt_t A`` for a block of heads, down
    the sublanes (Q, heads) and along the lanes (heads, Q), and the three
    decays a position or the chunk has on its own: to the chunk's end and
    from its start (Q, heads), and the whole chunk's (1, heads)."""
    within = _running_sum(a_col_ref[0, 0], 0)
    along = _running_sum(a_row_ref[0, 0], 1)
    total = within[within.shape[0] - 1:]
    return within, along, jnp.exp(total - within), jnp.exp(within), jnp.exp(total)


def _ssd_fwd_kernel(
    x_ref, b_ref, c_ref, dt_ref, a_col_ref, a_row_ref, skip_ref, y_ref, *rest,
    p: int, blocks_of_a_group: int, keep_states: bool,
):
    """One chunk of one block of heads: ``rest`` is [the entering states'
    output], the carried state (blocks, N, lanes) and a group's scores (Q, Q)."""
    enter_ref = rest[0] if keep_states else None
    state_ref, scores_ref = rest[-2:]
    block = pl.program_id(2)
    q, dtype = x_ref.shape[1], x_ref.dtype
    n_heads = dt_ref.shape[3]

    @pl.when(pl.program_id(1) == 0)
    def _start():
        state_ref[block] = jnp.zeros(state_ref.shape[1:], _F32)

    b_in, c_out = b_ref[0], c_ref[0]  # (Q, N)

    @pl.when(block % blocks_of_a_group == 0)
    def _scores():
        scores_ref[...] = _dot(c_out, b_in, _NT)  # [t, u] = C_t . B_u

    dt = dt_ref[0, 0]  # (Q, heads)
    # c_t down the sublanes, c_u along the lanes
    within, along, to_end, from_start, whole = _decays(a_col_ref, a_row_ref)
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    )
    scores = scores_ref[...]
    for slab in (_Slab(k, p) for k in range(_Slab.count(n_heads, p))):
        x = x_ref[0, :, slab.lanes].astype(_F32)
        x_dt = (x * slab.spread(dt)).astype(dtype)
        entering = state_ref[block, :, slab.lanes]  # (N, width)
        y = _dot(c_out, entering.astype(dtype)) * slab.spread(from_start)
        for j, h in enumerate(slab.heads):
            apart = within[:, h: h + 1] - along[h: h + 1, :]  # c_t - c_u
            weights = (scores * jnp.exp(jnp.where(causal, apart, -jnp.inf))).astype(dtype)
            mine = _dot(weights, x_dt)  # every head of the slab under this head's weights
            y = y + (mine if slab.per == 1 else jnp.where(slab.of(j), mine, 0.0))
        y_ref[0, :, slab.lanes] = (y + skip_ref[:, slab.lanes] * x).astype(dtype)
        fed = (x_dt.astype(_F32) * slab.spread(to_end)).astype(dtype)
        if keep_states:
            enter_ref[0, 0, :, slab.lanes] = entering
        state_ref[block, :, slab.lanes] = slab.spread(whole) * entering + _dot(b_in, fed, _TN)


def _ssd_bwd_kernel(
    x_ref, b_ref, c_ref, dt_ref, a_col_ref, a_row_ref, skip_ref, dy_ref, enter_ref,
    dx_ref, db_ref, dc_ref, ddt_ref, da_col_ref, da_row_ref, dskip_ref,
    dstate_ref, scores_ref, dscores_ref,
    *, p: int, blocks_of_a_group: int,
):
    """One chunk of one block of heads, the chunks from the sequence's end.
    ``dstate_ref`` carries the gradient of the state LEAVING the chunk;
    ``scores_ref`` is a group's ``B C^T`` ([u, t]) and ``dscores_ref`` its
    gradient summed over the group's heads."""
    block = pl.program_id(2)
    q, dtype = x_ref.shape[1], x_ref.dtype
    n_heads = dt_ref.shape[3]
    first_of_group = block % blocks_of_a_group == 0

    @pl.when(pl.program_id(1) == 0)
    def _start():
        dstate_ref[block] = jnp.zeros(dstate_ref.shape[1:], _F32)

    b_in, c_out = b_ref[0], c_ref[0]  # (Q, N)

    @pl.when(first_of_group)
    def _scores():
        scores_ref[...] = _dot(b_in, c_out, _NT)  # [u, t] = B_u . C_t
        dscores_ref[...] = jnp.zeros_like(dscores_ref)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    dt = dt_ref[0, 0]  # (Q, heads)
    # c_u down the sublanes, c_t along the lanes
    within, along, to_end, from_start, whole = _decays(a_col_ref, a_row_ref)
    later = (  # [u, t]: t >= u
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
        >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    )
    scores = scores_ref[...]
    # The running sums' gradient: of c_u down the sublanes (with the total's
    # in its last row) and of c_t along the lanes, each the sum of its part.
    d_dt = jnp.zeros((q, n_heads), _F32)
    d_within = jnp.zeros((q, n_heads), _F32)
    d_along = jnp.zeros((n_heads, q), _F32)
    d_total = jnp.zeros((1, n_heads), _F32)
    column = jax.lax.broadcasted_iota(jnp.int32, (1, n_heads), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (n_heads, 1), 0)
    for slab in (_Slab(k, p) for k in range(_Slab.count(n_heads, p))):
        x = x_ref[0, :, slab.lanes].astype(_F32)
        dy_low = dy_ref[0, :, slab.lanes]
        dy = dy_low.astype(_F32)
        skip = skip_ref[:, slab.lanes]
        dt_lanes, to_end_lanes = slab.spread(dt), slab.spread(to_end)
        x_dt = (x * dt_lanes).astype(dtype)
        fed = x_dt.astype(_F32) * to_end_lanes
        entering = enter_ref[0, 0, :, slab.lanes]  # (N, width)
        d_state = dstate_ref[block, :, slab.lanes]
        d_state_low = d_state.astype(dtype)
        # y += exp(c_t) C_t . S_entering
        from_start_lanes = slab.spread(from_start)
        d_from_state = (dy * from_start_lanes).astype(dtype)
        dc_ref[0] += _dot(d_from_state, entering.astype(dtype), _NT)
        from_state = _dot(c_out, entering.astype(dtype)) * from_start_lanes
        whole_lanes = slab.spread(whole)
        dstate_ref[block, :, slab.lanes] = whole_lanes * d_state + _dot(c_out, d_from_state, _TN)
        # S_leaving = exp(total) S_entering + sum_u fed_u B_u^T
        d_fed = _dot(b_in, d_state_low)  # (Q, width)
        db_ref[0] += _dot(fed.astype(dtype), d_state_low, _NT)
        d_x_dt = d_fed * to_end_lanes
        # the chunk's own quadratic form, transposed: [u, t]
        d_intra = jnp.zeros((q, slab.width), _F32)
        for j, h in enumerate(slab.heads):
            apart = along[h: h + 1, :] - within[:, h: h + 1]  # c_t - c_u
            decay = jnp.exp(jnp.where(later, apart, -jnp.inf))
            weights = scores * decay
            mine = _dot(weights.astype(dtype), dy_low)
            d_intra = mine if slab.per == 1 else jnp.where(slab.of(j), mine, d_intra)
            own = x_dt if slab.per == 1 else jnp.where(slab.of(j), x_dt, jnp.zeros_like(x_dt))
            d_weights = _dot(own, dy_low, _NT)
            dscores_ref[...] += d_weights * decay
            # d(c_t - c_u) = d_weights * weights: c_t takes its column's sum, c_u
            # loses its row's.
            through = d_weights * weights
            d_along = jnp.where(row == h, jnp.sum(through, axis=0, keepdims=True), d_along)
            d_within = jnp.where(column == h, -jnp.sum(through, axis=1, keepdims=True), d_within)
        d_x_dt = d_x_dt + d_intra
        dx_ref[0, :, slab.lanes] = (d_x_dt * dt_lanes + skip * dy).astype(dtype)
        dskip_ref[0, 0, :, slab.lanes] = jnp.sum(dy * x, axis=0, keepdims=True)
        d_dt = slab.gather(d_x_dt * x, d_dt)
        # exp(c_t) of the state's way out, exp(total - c_u) of the fed input's,
        # exp(total) of the state's recurrence.
        moved = fed * d_fed
        d_within = d_within + slab.gather(dy * from_state - moved, jnp.zeros_like(d_within))
        carried = jnp.sum(moved, axis=0, keepdims=True) + whole_lanes * jnp.sum(
            d_state * entering, axis=0, keepdims=True
        )
        d_total = slab.gather(carried, d_total)

    last = jax.lax.broadcasted_iota(jnp.int32, (q, n_heads), 0) == q - 1
    ddt_ref[0, 0] = d_dt
    da_col_ref[0, 0] = _running_sum(d_within + jnp.where(last, d_total, 0.0), 0, reverse=True)
    da_row_ref[0, 0] = _running_sum(d_along, 1, reverse=True)

    @pl.when(block % blocks_of_a_group == blocks_of_a_group - 1)
    def _group():
        d_scores = dscores_ref[...].astype(dtype)
        db_ref[0] += _dot(d_scores, c_out)
        dc_ref[0] += _dot(d_scores, b_in, _TN)


def _by_block(per_head, n_blocks: int):
    """(b, s, H) float32 -> (b, blocks, s, H / blocks)."""
    b, s, heads = per_head.shape
    return per_head.astype(_F32).reshape(b, s, n_blocks, heads // n_blocks).transpose(0, 2, 1, 3)


def _ssd_operands(x, dt, a, b_in, c_out, d_skip, chunk: int):
    """The kernels' operands and grid from :func:`ssd_scan`'s arguments."""
    b, s, heads, p = x.shape
    groups, n = b_in.shape[2:]
    block = _heads_of_a_block(heads, p, groups)
    n_blocks = heads // block
    dt_col = _by_block(dt, n_blocks)
    a_col = dt_col * a.astype(_F32).reshape(1, n_blocks, 1, block)
    skip = jnp.repeat(d_skip.astype(_F32), p)[None]  # (1, H P)
    operands = (
        x.reshape(b, s, heads * p), b_in.reshape(b, s, groups * n), c_out.reshape(b, s, groups * n),
        dt_col, a_col, a_col.transpose(0, 1, 3, 2), skip,
    )
    return operands, (b, s // chunk, n_blocks), block


def _ssd_specs(chunk: int, block: int, p: int, n: int, blocks_of_a_group: int, chunk_of):
    """BlockSpecs by kind for a grid (batch, chunk step, block of heads);
    ``chunk_of(step)`` the chunk a step works on."""
    lanes = block * p
    return {
        "x": pl.BlockSpec((1, chunk, lanes), lambda bi, c, k: (bi, chunk_of(c), k)),
        "group": pl.BlockSpec(
            (1, chunk, n), lambda bi, c, k: (bi, chunk_of(c), k // blocks_of_a_group)
        ),
        "col": pl.BlockSpec((1, 1, chunk, block), lambda bi, c, k: (bi, k, chunk_of(c), 0)),
        "row": pl.BlockSpec((1, 1, block, chunk), lambda bi, c, k: (bi, k, 0, chunk_of(c))),
        "skip": pl.BlockSpec((1, lanes), lambda bi, c, k: (0, k)),
        "state": pl.BlockSpec((1, 1, n, lanes), lambda bi, c, k: (bi, chunk_of(c), 0, k)),
        "sums": pl.BlockSpec((1, 1, 1, lanes), lambda bi, c, k: (bi, chunk_of(c), 0, k)),
    }


@partial(jax.jit, static_argnames=("chunk", "interpret", "keep_states"))
def _ssd_fwd_call(x, dt, a, b_in, c_out, d_skip, chunk: int, interpret: bool, keep_states: bool):
    """y (b, s, H P) and, where asked, the state entering every chunk
    (b, chunks, N, H P) float32."""
    b, s, heads, p = x.shape
    groups, n = b_in.shape[2:]
    operands, grid, block = _ssd_operands(x, dt, a, b_in, c_out, d_skip, chunk)
    blocks_of_a_group = heads // groups // block
    spec = _ssd_specs(chunk, block, p, n, blocks_of_a_group, lambda c: c)
    out = pl.pallas_call(
        partial(_ssd_fwd_kernel, p=p, blocks_of_a_group=blocks_of_a_group, keep_states=keep_states),
        grid=grid,
        in_specs=[spec[kind] for kind in ("x", "group", "group", "col", "col", "row", "skip")],
        out_specs=[spec["x"]] + [spec["state"]] * keep_states,
        out_shape=[jax.ShapeDtypeStruct((b, s, heads * p), x.dtype)]
        + [jax.ShapeDtypeStruct((b, s // chunk, n, heads * p), _F32)] * keep_states,
        scratch_shapes=[
            pltpu.VMEM((grid[2], n, block * p), _F32), pltpu.VMEM((chunk, chunk), _F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        name=SSD_FWD,
        interpret=interpret,
    )(*operands)
    return out if keep_states else out[0]


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_bwd_call(x, dt, a, b_in, c_out, d_skip, entering, dy, chunk: int, interpret: bool):
    """The gradients of x (b, s, H P), B and C (b, s, G N) float32, dt's
    direct share (b, blocks, s, heads of a block), ``a_t``'s in two parts that
    add (that layout and (b, blocks, heads of a block, s)), and D's by chunk
    and lane (b, chunks, 1, H P)."""
    b, s, heads, p = x.shape
    groups, n = b_in.shape[2:]
    operands, grid, block = _ssd_operands(x, dt, a, b_in, c_out, d_skip, chunk)
    blocks_of_a_group = heads // groups // block
    n_chunks = grid[1]
    spec = _ssd_specs(chunk, block, p, n, blocks_of_a_group, lambda c: n_chunks - 1 - c)
    by_block = jax.ShapeDtypeStruct((b, grid[2], s, block), _F32)
    by_group = jax.ShapeDtypeStruct((b, s, groups * n), _F32)
    return pl.pallas_call(
        partial(_ssd_bwd_kernel, p=p, blocks_of_a_group=blocks_of_a_group),
        grid=grid,
        in_specs=[
            spec[kind]
            for kind in ("x", "group", "group", "col", "col", "row", "skip", "x", "state")
        ],
        out_specs=[
            spec[kind] for kind in ("x", "group", "group", "col", "col", "row", "sums")
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, heads * p), x.dtype), by_group, by_group, by_block, by_block,
            jax.ShapeDtypeStruct((b, grid[2], block, s), _F32),
            jax.ShapeDtypeStruct((b, n_chunks, 1, heads * p), _F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((grid[2], n, block * p), _F32), pltpu.VMEM((chunk, chunk), _F32),
            pltpu.VMEM((chunk, chunk), _F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        name=SSD_BWD,
        interpret=interpret,
    )(*operands, dy, entering)


@partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd_scan_kernels(x, dt, a, b_in, c_out, d_skip, chunk, interpret):
    y = _ssd_fwd_call(x, dt, a, b_in, c_out, d_skip, chunk=chunk, interpret=interpret, keep_states=False)
    return y.reshape(x.shape)


def _ssd_scan_fwd(x, dt, a, b_in, c_out, d_skip, chunk, interpret):
    y, entering = _ssd_fwd_call(
        x, dt, a, b_in, c_out, d_skip, chunk=chunk, interpret=interpret, keep_states=True
    )
    return y.reshape(x.shape), (x, dt, a, b_in, c_out, d_skip, entering)


def _ssd_scan_bwd(chunk, interpret, kept, dy):
    x, dt, a, b_in, c_out, d_skip, entering = kept
    b, s, heads, p = x.shape
    dx, db, dc, d_dt, d_a, d_a_along, d_skip_lanes = _ssd_bwd_call(
        x, dt, a, b_in, c_out, d_skip, entering, dy.reshape(b, s, heads * p),
        chunk=chunk, interpret=interpret,
    )
    by_head = lambda z: z.transpose(0, 2, 1, 3).reshape(b, s, heads)  # from (b, blocks, s, block)
    d_a = by_head(d_a) + by_head(d_a_along.transpose(0, 1, 3, 2))  # the gradient of a_t = dt_t A
    dt32, a32 = dt.astype(_F32), a.astype(_F32)
    return (
        dx.reshape(x.shape),
        (by_head(d_dt) + d_a * a32).astype(dt.dtype),
        jnp.sum(d_a * dt32, axis=(0, 1)).astype(a.dtype),
        db.reshape(b_in.shape).astype(b_in.dtype),
        dc.reshape(c_out.shape).astype(c_out.dtype),
        jnp.sum(d_skip_lanes.reshape(-1, heads, p), axis=(0, 2)).astype(d_skip.dtype),
    )


_ssd_scan_kernels.defvjp(_ssd_scan_fwd, _ssd_scan_bwd)


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


def ssd_scan_einsums(
    x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray, b_in: jnp.ndarray, c_out: jnp.ndarray,
    d_skip: jnp.ndarray, chunk: int = 256,
) -> jnp.ndarray:
    """:func:`ssd_scan` as plain XLA whatever the platform: the module
    docstring's four parts under their scopes, the backward autodiff through
    them. The path off a TPU and of shapes the kernels do not take, and the
    kernels' oracle."""
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


def ssd_scan(
    x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray, b_in: jnp.ndarray, c_out: jnp.ndarray,
    d_skip: jnp.ndarray, chunk: int = 256, interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """x (b, s, H, P), dt (b, s, H) (after its softplus), a (H,) (negative),
    b_in and c_out (b, s, G, N) with G dividing H, d_skip (H,) ->
    y (b, s, H, P) in x's dtype. On a TPU, where the shapes fit
    (:func:`scan_kernel_fits`), the two Mosaic kernels of the module's
    docstring; elsewhere the einsum path. ``interpret`` as :func:`conv_silu`'s."""
    take = on_tpu() if interpret is None else True
    q = min(chunk, x.shape[1])
    if take and scan_kernel_fits(x, b_in, q):
        return _ssd_scan_kernels(x, dt, a, b_in, c_out, d_skip, q, bool(interpret))
    return ssd_scan_einsums(x, dt, a, b_in, c_out, d_skip, chunk)


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
