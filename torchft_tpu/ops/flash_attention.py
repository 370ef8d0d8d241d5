"""Pallas TPU flash attention: the fused-kernel path for the hot op.

The scan-based :func:`torchft_tpu.ops.ring_attention.blockwise_attention`
already gives O(s·block) memory, but each block update is a separate XLA
fusion: scores, mask, softmax bookkeeping, and the PV matmul round-trip
through HBM between blocks. This module fuses the whole online-softmax
inner loop into ONE Pallas kernel so the accumulators (acc, running max,
running sum) live in VMEM for the duration and the two matmuls per block
ride the MXU back-to-back (pallas_guide.md: grid iterated sequentially on
TPU with the last axis minor, which makes cross-grid-step VMEM scratch the
canonical accumulation pattern).

Forward AND backward are fused Pallas kernels on TPU. The backward is the
FlashAttention-2 recompute from the saved (out, lse) residuals as ONE
kernel: it visits each needed (q block, KV block) pair once, recomputes the
pair's probabilities once and adds to all three gradients, five matmuls a
pair. It walks q blocks innermost, so dk and dv accumulate in
(block_k, d) scratch across a KV block's steps, and dq across the KV blocks in
a float32 scratch that holds every q row of the head, written out once a
head through an output block that is resident as long (a sequence whose
rows do not fit is walked in chunks of q blocks, ``_q_chunks``). The
per-row ``delta = rowsum(dO*O)`` identity is computed by XLA outside the
kernel (it fuses into the surrounding graph).
The two residuals the forward kernel produced carry
``checkpoint_name`` tags, ``FLASH_OUT`` and ``FLASH_LSE``: a Pallas call is
no ``dot_general``, so a remat policy that keeps dot results alone would
drop them and run the whole forward kernel again in the backward. The
models' ``remat="dots"`` keeps both by name (models/decoder.py
``remat_policy``); ``remat="full"`` and any policy that does not name them
recompute the kernel, and without remat the names do nothing.
:func:`flash_attention_partial` is untagged: its VJP is the ring's own.
Both kernels know where the causal diagonal runs through the (q block, KV
block) grid. A pair ABOVE the diagonal (every key later than every query) runs
no matmul and fetches nothing; a pair UNDER it (every key at or before every
query) runs its matmuls without the compare and select; a pair the DIAGONAL
crosses, and any pair with a padded row or column, takes the mask. The classes
follow the positions alone (:func:`_pair_class`), and HOW a call walks the grid
follows from whether it was given them (:class:`_Walk`):
- ``flash_attention`` brings no position arrays: the positions are the
  sequence's own, every pair's class is known while tracing, and the grid is
  the LIST of the needed pairs: (b, h, steps) in the forward, (b, h, q chunks,
  steps of a chunk) in the backward, in the order a dense walk would visit them
  (a q block's KV blocks ascending; in the backward a KV block's q blocks). One
  int32 step table, a numpy constant passed as scalar prefetch
  (:func:`_fwd_steps`, :func:`_bwd_steps`), gives each step its pair, its class
  and where its accumulators begin and end; the index maps read the pair from
  it. No grid step is spent on a pair that needs nothing, and the step before
  a new row is a needed one, so the row's first fetch hides behind its
  matmuls. The table is 16 bytes a step in SMEM (4.4 KB at 16,384 rows in
  512 x 1024 blocks, 264 KB at 131,072); a call whose table would pass
  ``_MAX_TABLE_BYTES`` (over 131,072 rows at those blocks) takes the walk
  below instead, by its shape alone. In this walk a step's class also says
  WHICH HALF of its KV block is needed. The table classes each pair's two
  halves of ``block_k // 2`` keys with the same :func:`_pair_class`
  (:func:`_own_classes`): where the diagonal enters a 512 x 1024 block at an
  even q block its right half lies wholly above it, where a window's edge
  enters at an odd one its left half lies wholly behind, where the sequence
  ends in a left half the right half is padding. Such a pair takes the class
  of the half that is left (3 or 4 the left half masked or bare, 5 or 6 the
  right), and the bodies run that step on that half alone: static slices of
  ``block_k // 2`` rows of k, v and the dk / dv accumulators and as many lanes
  of the key positions or the selection's block, so half the matmuls, half
  the exponentials, no second softmax update. A pair that needs both halves
  keeps class 1 or 2 and stays one whole step; the table's size, order and
  flags are what they were. A kernel traces a body only for the classes its
  table holds (three or four of the six). The halves engage where a half
  block is whole lane tiles (``block_k`` a multiple of 256): 16 of a head's
  272 steps at 1 x 16,384, 28 of 140 under a window of 4,096, 8 of 72 at
  1 x 8192, 2 of 6 at 2048, in the default blocks;
  ``_class_counts(...)["halves"]`` counts them. What the half left out would
  have added is exact zeros, so the results are the walk below's.
- ``flash_attention_partial`` / ``flash_attention_partial_bwd`` with position
  ARRAYS (a ring's zigzag hops, ``sq != sk``): the needed pairs are data, so
  the grid is DENSE, every pair a step: XLA reduces the arrays to each block's
  lowest and highest position once a call (:func:`_block_schedule`), the two
  small tables ride in as scalar prefetch, a grid step reads its pair's class
  from SMEM before it begins, and the index maps of a pair that needs nothing
  name the block the neighbouring needed step holds, so the pipeline copies
  nothing for it. Every needed pair is computed whole: a half's class would
  be four more compares of scalars a step and a table row more, for hops
  whose blocks the diagonal seldom crosses.
Both walks run the same kernel bodies on the same pairs in the same order, so
their results are the same bits (tests/test_flash_attention.py and
:func:`verify_on_chip` hold that); ``_class_counts(...)["steps"]`` says which
walk a call takes.
A call may bring a SELECTION, a (b, s, s) int8 operand that says which keys
each query attends to (``flash_attention(..., selection=...)``; the learned
key selection of models/keye.py, made once a layer step by
ops/sparse_attention.py ``select_keys``): every pair the schedule needs then
masks by its (block_q, block_k) block of the operand in place of the
position compare, in the forward and in the one backward call, which read
it through one more BlockSpec built from the same block indices; pairs above
the diagonal are skipped as ever. What decides is the presence of the operand
in the call, and the bodies are specialised at trace time: a call without it
lowers to the Mosaic call it was before the argument existed, operand for
operand (tests/test_mosaic_lowering.py holds that). Which attention runs
where: models/llama.py calls the kernels with no selection where
``attention_impl`` says (``auto``: on a TPU at long sequences), ring
attention calls the partial pair a hop, models/keye.py calls them with the
selection on a TPU and ops/sparse_attention.py's tiled XLA path elsewhere.
A call may bring a WINDOW instead (``flash_attention(..., window=...)``; the
windowed layers of models/smallthinker.py): query t attends to key u iff
``0 <= t - u < window``. That is a second edge through the grid, and the
schedule knows it as it knows the diagonal: a pair wholly BEHIND the window is
neither fetched nor computed (in the listed walk it is no step at all), a pair
the window's EDGE crosses takes the mask (both compares), a pair wholly INSIDE
runs bare (:func:`_pair_class`); the dense walk's two tables gain a fourth row,
the first KV block a q block needs and the last q block a KV block needs, so
its index maps stop on both sides; forward and the one backward call alike.
The two calls carry names of their own (``WINDOW_FWD``, ``WINDOW_BWD``), which
is what a device trace tells them from the causal calls by; a call without a
window has no name.
GQA is handled by emitting per-q-head dk/dv partials and summing over the
group axis outside — keeps every output block written exactly once per
grid pass (no cross-step output aliasing, which Mosaic cannot express; the
resident dq block is the forward's pattern, an accumulator written out
when its block index moves on, and not aliasing).
The scan-based blockwise backward remains the
interpret/CPU fallback (``use_pallas_bwd`` selects; CPU tests run the
Pallas backward in interpret mode explicitly). Run :func:`verify_on_chip`
on the chip after any kernel change (the CLAUDE.md kernel-verification
gate — every chip_smoke.py run re-executes it, forward and backward). In
the CPU suite, tests/test_mosaic_lowering.py cross-lowers every kernel here
for a TPU target (block-layout violations, the class interpret mode cannot
see) and tests/test_tpu_aot_compile.py compiles them
at real widths for a described v5e (VMEM limits, misaligned slices) — both
without a chip.
Note "auto" attention (ops/attention.py) SELECTS this kernel on real TPU
for long sequences, so a kernel edit reaches default-configured runs:
never ship one without the on-chip gate.

The reference has no attention code at all (SURVEY.md §2.7: long-sequence
scaling is delegated to torchtitan); this is part of the beyond-reference
long-context stack, sitting below ring attention (which shards the
sequence across chips) as the per-chip kernel.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from torchft_tpu.utils.platform import on_tpu

from torchft_tpu.ops.ring_attention import _blockwise_core_bwd

__all__ = [
    "FLASH_OUT",
    "FLASH_LSE",
    "WINDOW_FWD",
    "WINDOW_BWD",
    "flash_attention",
    "flash_attention_partial",
    "flash_attention_partial_bwd",
    "merge_attention_partials",
]

# checkpoint_name tags of the forward kernel's two residuals (module
# docstring: who keeps them).
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"

# What XLA names the two Mosaic calls of a call with a window (a device
# trace's kernel names hold them); calls without one keep the name their
# caller's scope gives them.
WINDOW_FWD = "window_attn_fwd"
WINDOW_BWD = "window_attn_bwd"

_NEG_INF = -1e30
_PAD_POS = 2**31 - 1  # position for padded rows: beyond every real query


def _out_struct(shape, dtype, inputs):
    """ShapeDtypeStruct carrying the union of the inputs' varying-mesh-axes
    (vma): under shard_map(check_vma=True) pallas_call outputs must declare
    how they vary over manual axes; outside shard_map the union is empty."""
    try:
        vma = frozenset().union(*(jax.typeof(x).vma for x in inputs))
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    except (AttributeError, TypeError):
        return jax.ShapeDtypeStruct(shape, dtype)


# Rows of the two schedule tables (:func:`_block_schedule`); the last only
# where the call has a window.
_LO, _HI, _EDGE, _FAR_EDGE = 0, 1, 2, 3


def _padded_positions(q_positions, k_positions, b, sq, sk, block_q, block_k):
    """(b, sq_p) and (b, sk_p) int32 positions, padded to block multiples;
    ``None`` means ``arange``. Padded q rows sit at -1, below every key, and
    padded KV rows at _PAD_POS, above every query: the mask gives both zero
    weight (the forward's padded q rows come out empty and are sliced off),
    and neither can turn a block pair into one the schedule calls under."""
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(sq, dtype=jnp.int32), (b, sq))
    if k_positions is None:
        k_positions = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32), (b, sk))
    qp = jnp.pad(
        q_positions.astype(jnp.int32), ((0, 0), (0, (-sq) % block_q)),
        constant_values=-1,
    )
    kp = jnp.pad(
        k_positions.astype(jnp.int32), ((0, 0), (0, (-sk) % block_k)),
        constant_values=_PAD_POS,
    )
    return qp, kp


def _pair_class(q_lo, q_hi, k_lo, k_hi, window=None):
    """(needed, under) of a (q block, KV block) pair from the blocks' lowest
    and highest positions. Not needed is ABOVE the diagonal: the mask is
    false all over it. Under: the mask is true all over it. Needed and not
    under is DIAGONAL: the mask has to be applied. With a ``window`` (query t
    sees key u iff ``0 <= t - u < window``) a second edge runs through the
    grid: a pair whose nearest (query, key) is already ``window`` apart lies
    BEHIND it and is not needed, and only a pair whose farthest is still
    inside it is under (INSIDE); the window's EDGE takes the mask like the
    diagonal. Scalars in the kernels, arrays in :func:`_block_classes`, numpy
    arrays while tracing in :func:`_own_classes`."""
    needed, under = k_lo <= q_hi, k_hi <= q_lo
    if window is None:
        return needed, under
    return needed & (q_lo - k_hi < window), under & (q_hi - k_lo < window)


def _typed_unvarying(x):
    """``x`` as it is, or through an identity host callback that types it as
    varying over no manual mesh axis where shard_map had typed it varying.
    Interpret mode only: the CPU interpreter evaluates index maps and kernel
    scalars op by op against grid indices that vary over nothing, and under
    shard_map(check_vma=True) refuses to mix those with a table that does
    (a ring hop's positions differ by shard). Each shard keeps its own
    values; Mosaic never sees this."""
    if not getattr(jax.typeof(x), "vma", None):
        return x
    return jax.pure_callback(lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x)


def _block_schedule(qp, kp, block_q, block_k, interpret, window=None):
    """Causal block schedule from the padded positions: ``q_sched``
    (b, 3, nq) and ``k_sched`` (b, 3, nk) int32, XLA's work once a call,
    the kernels' scalar prefetch. Rows _LO and _HI hold each block's lowest
    and highest position. Row _EDGE of ``q_sched`` is the last KV block the
    q block needs (the forward walks KV blocks innermost) and of ``k_sched``
    the first q block the KV block needs (the backward walks q blocks
    innermost): the index maps stop there, so a step beyond the edge names
    the block already in VMEM and the pipeline copies nothing. With a
    ``window`` the needed blocks end on the other side too, and both tables
    have a fourth row, _FAR_EDGE: the FIRST KV block a q block needs and the
    LAST q block a KV block needs, where the index maps stop as well."""
    b = qp.shape[0]
    qb = qp.reshape(b, -1, block_q)
    kb = kp.reshape(b, -1, block_k)
    q_lo, q_hi = qb.min(axis=2), qb.max(axis=2)
    k_lo, k_hi = kb.min(axis=2), kb.max(axis=2)
    nq, nk = q_lo.shape[1], k_lo.shape[1]
    needed, _ = _pair_class(
        q_lo[:, :, None], q_hi[:, :, None], k_lo[:, None, :], k_hi[:, None, :],
        window,
    )  # (b, nq, nk)
    at_k = jnp.arange(nk, dtype=jnp.int32)
    at_q = jnp.arange(nq, dtype=jnp.int32)[:, None]
    k_last = jnp.max(jnp.where(needed, at_k, 0), axis=2)
    q_first = jnp.min(jnp.where(needed, at_q, nq - 1), axis=1)
    q_rows, k_rows = [q_lo, q_hi, k_last], [k_lo, k_hi, q_first]
    if window is not None:
        q_rows.append(jnp.min(jnp.where(needed, at_k, nk - 1), axis=2))
        k_rows.append(jnp.max(jnp.where(needed, at_q, 0), axis=1))
    q_sched = jnp.stack(q_rows, axis=1)
    k_sched = jnp.stack(k_rows, axis=1)
    if interpret:
        return _typed_unvarying(q_sched), _typed_unvarying(k_sched)
    return q_sched, k_sched


def _block_classes(q_sched, k_sched, window=None):
    """(b, nq, nk) int32 of the schedule's pairs: 0 not needed (above the
    diagonal or behind the window), 1 masked (the diagonal or the window's
    edge crosses it), 2 under (inside). What the kernels decide a step at a
    time, as one array."""
    needed, under = _pair_class(
        q_sched[:, _LO, :, None], q_sched[:, _HI, :, None],
        k_sched[:, _LO, None, :], k_sched[:, _HI, None, :],
        window,
    )
    return needed.astype(jnp.int32) + under.astype(jnp.int32)


def _kv_block(ib, iq, ik, q_sched, windowed=False):
    """KV block that step (iq, ik) of the forward names."""
    if windowed:
        return jnp.clip(ik, q_sched[ib, _FAR_EDGE, iq], q_sched[ib, _EDGE, iq])
    return jnp.minimum(ik, q_sched[ib, _EDGE, iq])


def _q_block(ib, ik, iq, k_sched, windowed=False):
    """q block that step (ik, iq) of the backward names."""
    if windowed:
        return jnp.clip(iq, k_sched[ib, _EDGE, ik], k_sched[ib, _FAR_EDGE, ik])
    return jnp.maximum(iq, k_sched[ib, _EDGE, ik])


# Rows of a step table (:func:`_fwd_steps`, :func:`_bwd_steps`) and the bits of
# its _FLAGS row: the step is the first / the last of its row of the walk (a q
# block's KV blocks in the forward, a KV block's q blocks in the backward), and
# in the backward the first / the last that names its q block.
_IQ, _IK, _CLASS, _FLAGS = 0, 1, 2, 3
_ROW_FIRST, _ROW_LAST, _Q_FIRST, _Q_LAST = 1, 2, 4, 8

# How much of its KV block a step computes. A class of the _CLASS row is
# 2 * span + (1 masked, 2 bare), 0 still a step that needs nothing: 1 and 2
# the whole block, 3 and 4 its left half alone, 5 and 6 its right half alone.
_WHOLE, _LEFT, _RIGHT = 0, 1, 2


def _body_of(kind):
    """(masked, span) of a class over 0."""
    return kind % 2 == 1, (kind - 1) // 2


def _columns(span, block_k):
    """The static slice of a KV block's ``block_k`` rows (of k, v, dk, dv) or
    lanes (of the key positions, of a selection's block) that ``span`` names."""
    half = block_k // 2
    return (slice(None), slice(0, half), slice(half, block_k))[span]


def _own_classes(sq, sk, q_rows, block_q, block_k, window=None):
    """(nq, nk) numpy classes of the pairs of a call whose positions are the
    sequence's own, made while tracing: what :func:`_padded_positions` and
    :func:`_block_schedule` give for no position arrays (0, 1 and 2 of
    :func:`_block_classes`), the q rows padded to a multiple of ``q_rows``.
    Where a KV block is two halves of whole lane tiles, each half is classed by
    the same :func:`_pair_class`, and a pair of which ONE half is needed (the
    diagonal, the window's edge or the sequence's end runs between the two)
    takes that half's class, 3 to 6 (``_LEFT``, ``_RIGHT``): the kernels then
    compute that half alone. A pair that needs both halves keeps its class and
    stays one step: two would update the softmax twice and save nothing."""

    def blocks(n, multiple, block, fill):
        at = np.full(_next_multiple(n, multiple), fill, np.int32)
        at[:n] = np.arange(n)
        at = at.reshape(-1, block)
        return at.min(axis=1), at.max(axis=1)

    q_lo, q_hi = blocks(sq, q_rows, block_q, -1)

    def classes(columns):
        k_lo, k_hi = blocks(sk, block_k, columns, _PAD_POS)
        needed, under = _pair_class(
            q_lo[:, None], q_hi[:, None], k_lo[None, :], k_hi[None, :], window
        )
        return needed.astype(np.int32) + under

    whole = classes(block_k)
    if block_k % 256:
        return whole
    halves = classes(block_k // 2)
    left, right = halves[:, 0::2], halves[:, 1::2]
    whole = np.where((left > 0) & (right == 0), 2 * _LEFT + left, whole)
    return np.where((right > 0) & (left == 0), 2 * _RIGHT + right, whole)


def _row_steps(listed):
    """(row, column, flags) of the steps of a walk over the (rows, columns)
    bool ``listed``, three numpy arrays: rows outer, a row's listed columns
    ascending, its first and last step flagged. A row that lists nothing
    still takes one step, at the column the step before it named, so it is
    begun and ended and the pipeline copies nothing for it."""
    rows, columns, flags = [], [], []
    held = 0
    for row, mine in enumerate(listed):
        mine = np.flatnonzero(mine)
        if not mine.size:
            mine = np.array([held])
        mark = np.zeros(mine.size, np.int64)
        mark[0] |= _ROW_FIRST
        mark[-1] |= _ROW_LAST
        rows.append(np.full(mine.size, row))
        columns.append(mine)
        flags.append(mark)
        held = mine[-1]
    return np.concatenate(rows), np.concatenate(columns), np.concatenate(flags)


def _fwd_steps(classes):
    """The forward's step table from the (nq, nk) ``classes``: (4, n_steps)
    int32, a column a grid step, in the order the dense walk visits the
    needed pairs (q blocks outer, a q block's KV blocks ascending). Rows _IQ
    and _IK name the pair, _CLASS is its class, _FLAGS says where the q
    block's accumulators begin and end. 16 bytes a step in SMEM: 4.4 KB at
    16,384 rows in 512 x 1024 blocks, 264 KB at 131,072."""
    iq, ik, flags = _row_steps(classes > 0)
    return np.stack([iq, ik, classes[iq, ik], flags]).astype(np.int32)


def _bwd_steps(classes, nqc):
    """The backward's step table from the (nc * nqc, nk) ``classes``:
    (4, nc * n) int32, chunk by chunk ``n`` steps each, and ``n``. KV blocks
    outer and the chunk's needed q blocks ascending inside one, as the dense
    walk visits them; _FLAGS says where dk and dv begin and end (the KV
    block's first and last step) and where the q block's dq rows do (the
    first and the last step that names it: under a window neither is at the
    first or the last KV block). A KV block that needs nothing of the chunk
    takes one step of class 0, which writes its zero partials; a q block
    wholly of padding is listed once, for its rows of dq; a chunk with fewer
    steps than the longest ends in steps of class 0 that name the blocks it
    already holds."""
    chunks = []
    for first in range(0, classes.shape[0], nqc):
        mine = classes[first : first + nqc]
        listed = mine > 0
        listed[~listed.any(axis=1), 0] = True
        ik, jq, flags = _row_steps(listed.T)
        for block in np.unique(jq):
            named = np.flatnonzero(jq == block)
            flags[named[0]] |= _Q_FIRST
            flags[named[-1]] |= _Q_LAST
        chunks.append(np.stack([first + jq, ik, mine[jq, ik], flags]))
    n = max(steps.shape[1] for steps in chunks)
    for c, steps in enumerate(chunks):
        rest = np.repeat(steps[:, -1:], n - steps.shape[1], axis=1)
        rest[[_CLASS, _FLAGS]] = 0
        chunks[c] = np.concatenate([steps, rest], axis=1)
    return np.concatenate(chunks, axis=1).astype(np.int32), n


class _Walk(NamedTuple):
    """How a call steps through its (q block, KV block) pairs. ``tables``: its
    scalar prefetch. ``grid``: the grid's axes after (b, h). ``q_of`` and
    ``k_of``: the q block and the KV block a step names, from (ib, the step's
    further axes, the tables). ``step``: called in the kernel with the
    tables' refs, what the body needs to know of its step, the last of it the
    step's ``cases`` (:func:`_when_needed`)."""

    tables: tuple
    grid: tuple
    q_of: Callable
    k_of: Callable
    step: Callable


def _scheduled_cases(qs_ref, ks_ref, ib, iq, ik, window):
    """The cases of pair (iq, ik) in a kernel of the dense walk, from the
    schedule tables in SMEM: the whole block, bare under the diagonal (inside
    a window), masked where it is needed and not under."""
    needed, under = _pair_class(
        qs_ref[ib, _LO, iq], qs_ref[ib, _HI, iq],
        ks_ref[ib, _LO, ik], ks_ref[ib, _HI, ik],
        window,
    )
    return [
        (under, False, _WHOLE),
        (jnp.logical_and(needed, jnp.logical_not(under)), True, _WHOLE),
    ]


def _listed_cases(kind, steps):
    """The cases of a step of class ``kind`` in a kernel of a listed walk: one
    for each class over 0 that the table ``steps`` holds, so a call traces the
    bodies its steps run and no other."""
    return [
        (kind == held, *_body_of(held))
        for held in np.unique(steps[_CLASS]).tolist() if held
    ]


def _dense_fwd_walk(qp, kp, block_q, block_k, interpret, window):
    """The forward over every pair, grid (nq, nk), the classes read from the
    positions' schedule a step at a time: for calls with position arrays,
    whose needed pairs are data. A step beyond a q block's edge names the KV
    block at the edge (under a window, at either edge)."""
    from jax.experimental import pallas as pl

    q_sched, k_sched = _block_schedule(qp, kp, block_q, block_k, interpret, window)
    nq, nk = q_sched.shape[2], k_sched.shape[2]
    windowed = window is not None

    def step(qs_ref, ks_ref):
        ib, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)
        return ik == 0, ik == nk - 1, _scheduled_cases(qs_ref, ks_ref, ib, iq, ik, window)

    return _Walk(
        (q_sched, k_sched), (nq, nk),
        lambda ib, iq, ik, qs, ks: iq,
        lambda ib, iq, ik, qs, ks: _kv_block(ib, iq, ik, qs, windowed),
        step,
    )


def _listed_fwd_walk(steps):
    """The forward over the needed pairs alone, grid (n_steps,), for calls
    whose positions are the sequence's own: the classes are then known while
    tracing and the walk is a constant, the table ``steps``
    (:func:`_fwd_steps`)."""
    from jax.experimental import pallas as pl

    def step(steps_ref):
        at = pl.program_id(2)
        flags, cases = steps_ref[_FLAGS, at], _listed_cases(steps_ref[_CLASS, at], steps)
        return flags & _ROW_FIRST != 0, flags & _ROW_LAST != 0, cases

    return _Walk(
        (jnp.asarray(steps),), (steps.shape[1],),
        lambda ib, at, steps: steps[_IQ, at],
        lambda ib, at, steps: steps[_IK, at],
        step,
    )


def _dense_bwd_walk(qp, kp, block_q, block_k, interpret, window, nqc):
    """The backward over every pair, grid (nc, nk, nqc): the dense forward
    walk's other half. The q index starts at the KV block's edge (under a
    window it ends at the other one too) and stays in the chunk."""
    from jax.experimental import pallas as pl

    q_sched, k_sched = _block_schedule(qp, kp, block_q, block_k, interpret, window)
    nq, nk = q_sched.shape[2], k_sched.shape[2]
    windowed = window is not None

    def q_of(ib, ic, ik, jq, qs, ks):
        block = _q_block(ib, ik, ic * nqc + jq, ks, windowed)
        if windowed:
            return jnp.clip(block, ic * nqc, ic * nqc + nqc - 1)
        return jnp.minimum(block, ic * nqc + nqc - 1)

    def step(qs_ref, ks_ref):
        ib, ic, ik, jq = (pl.program_id(axis) for axis in (0, 2, 3, 4))
        cases = _scheduled_cases(qs_ref, ks_ref, ib, ic * nqc + jq, ik, window)
        return jq, jq == 0, jq == nqc - 1, ik == 0, ik == nk - 1, cases

    return _Walk(
        (q_sched, k_sched), (nq // nqc, nk, nqc),
        q_of, lambda ib, ic, ik, jq, qs, ks: ik, step,
    )


def _listed_bwd_walk(steps, n, nqc):
    """The backward over the needed pairs alone, grid (nc, n): the listed
    forward walk's other half, its table ``steps`` of ``n`` a chunk
    (:func:`_bwd_steps`)."""
    from jax.experimental import pallas as pl

    def step(steps_ref):
        ic, at = pl.program_id(2), pl.program_id(2) * n + pl.program_id(3)
        flags = steps_ref[_FLAGS, at]
        return (
            steps_ref[_IQ, at] - ic * nqc,
            flags & _ROW_FIRST != 0, flags & _ROW_LAST != 0,
            flags & _Q_FIRST != 0, flags & _Q_LAST != 0,
            _listed_cases(steps_ref[_CLASS, at], steps),
        )

    return _Walk(
        (jnp.asarray(steps),), (steps.shape[1] // n, n),
        lambda ib, ic, at, steps: steps[_IQ, ic * n + at],
        lambda ib, ic, at, steps: steps[_IK, ic * n + at],
        step,
    )


# A step table rides in SMEM whole, and the v5e has 1 MiB of it for a call's
# prefetched operands (its compiler refuses 1,052,672 bytes, the forward's
# table at 262,144 rows in 512 x 1024 blocks, and takes the 264 KB of
# 131,072). A call whose table would be larger walks every pair instead.
_MAX_TABLE_BYTES = 512 * 2**10


def _fwd_walk(own, qp, kp, sq, sk, block_q, block_k, interpret, window):
    """The walk of a forward call, by what it was given: without position
    arrays (``own``: the positions are the sequence's) the needed pairs
    alone, if their table fits; with them, or over that size, every pair."""
    if own:
        steps = _fwd_steps(_own_classes(sq, sk, block_q, block_q, block_k, window))
        if steps.nbytes <= _MAX_TABLE_BYTES:
            return _listed_fwd_walk(steps)
    return _dense_fwd_walk(qp, kp, block_q, block_k, interpret, window)


def _bwd_walk(own, qp, kp, sq, sk, block_q, block_k, interpret, window, nqc):
    """The walk of a backward call: :func:`_fwd_walk`'s choice, by the
    backward's own table."""
    if own:
        classes = _own_classes(sq, sk, nqc * block_q, block_q, block_k, window)
        steps, n = _bwd_steps(classes, nqc)
        if steps.nbytes <= _MAX_TABLE_BYTES:
            return _listed_bwd_walk(steps, n, nqc)
    return _dense_bwd_walk(qp, kp, block_q, block_k, interpret, window, nqc)


def _block_specs(block_q, block_k, d, group, q_of, k_of):
    """BlockSpecs of one pass over a grid (b, h, further axes), by kind of
    operand: ``q`` (a q head's rows: q, dO, out), ``kv`` (a KV head's rows,
    shared by the q heads of its group), ``col`` (a q head's per-row
    scalars: lse, delta), ``qp`` and ``kp`` (the positions), ``sel`` (the
    pair's block of a selection, shared by every head). ``q_of`` and
    ``k_of`` give the q block and the KV block a grid step names, from the
    step's (ib, further axes) and the two schedule tables."""
    from jax.experimental import pallas as pl

    def spec(block, index):
        return pl.BlockSpec(
            block,
            lambda ib, ih, *rest: index(ib, ih, q_of(ib, *rest), k_of(ib, *rest)),
        )

    return {
        "q": spec((None, None, block_q, d), lambda ib, ih, jq, jk: (ib, ih, jq, 0)),
        "kv": spec(
            (None, None, block_k, d), lambda ib, ih, jq, jk: (ib, ih // group, jk, 0)
        ),
        "col": spec((None, None, block_q, 1), lambda ib, ih, jq, jk: (ib, ih, jq, 0)),
        "qp": spec((None, block_q, 1), lambda ib, ih, jq, jk: (ib, jq, 0)),
        "kp": spec((None, 1, block_k), lambda ib, ih, jq, jk: (ib, 0, jk)),
        "sel": spec((None, block_q, block_k), lambda ib, ih, jq, jk: (ib, jq, jk)),
    }


def _mask_operands(selection, qp, kp, pad_q, pad_k):
    """The operands a needed pair masks by, with their kinds of spec
    (:func:`_block_specs`): the selection, padded with zeros like q and k, or
    without one the two position arrays. Positions ride as 3-D so each block
    is a 2-D tile (a column for q, a row for k, so that the in-kernel compare
    broadcasts without a transpose)."""
    if selection is not None:
        return (jnp.pad(selection, ((0, 0), (0, pad_q), (0, pad_k))),), ("sel",)
    b = qp.shape[0]
    return (qp.reshape(b, -1, 1), kp.reshape(b, 1, -1)), ("qp", "kp")


def _allowed(mask_refs, columns, window=None):
    """(block_q, keys) bool of the keys ``columns`` of a pair
    (:func:`_columns`) from the call's mask operands (:func:`_mask_operands`):
    the one block of the selection, which already implies that the key is no
    later than the query, or the two positions' compare, with a ``window``
    both of its compares. Which it is, is the call's operands and so known at
    trace time."""
    if len(mask_refs) == 1:
        return mask_refs[0][:, columns] != 0
    qp_ref, kp_ref = mask_refs
    if window is None:
        return qp_ref[...] >= kp_ref[:, columns]
    # A padded row (-1) against a padded column (_PAD_POS) is -2**31: no
    # difference of two positions leaves int32.
    apart = qp_ref[...] - kp_ref[:, columns]
    return jnp.logical_and(apart >= 0, apart < window)


def _when_needed(cases, update, mask_refs):
    """Runs ``update(masked, span)`` for the step's class, one of ``cases``,
    each (whether this step is of it, masked, span): not at all above the
    diagonal (or behind a window), without the mask under it (inside), with
    it on it (and on the window's edge), on the whole KV block or on the one
    half that needs it (``span``: ``_WHOLE``, ``_LEFT``, ``_RIGHT``); where
    the mask is a selection (:func:`_allowed`) every needed pair takes it, so
    a span's two cases are one."""
    from jax.experimental import pallas as pl

    bodies = {}
    for mine, masked, span in cases:
        body = masked or len(mask_refs) == 1, span
        bodies[body] = jnp.logical_or(bodies[body], mine) if body in bodies else mine
    for body, mine in bodies.items():
        pl.when(mine)(partial(update, *body))


def _fwd_kernel(
    *refs, scale: float, n_tables: int, step: Callable, window: Optional[int] = None
):
    """One grid step of the forward: one (q block, KV block) pair of a
    (batch, head), the pairs in the order of the call's walk (:class:`_Walk`:
    ``step`` is its, and the first ``n_tables`` refs), a q block's KV blocks
    one after another.

    Refs: the walk's tables in SMEM (the schedule tables qs (b, 3, nq) and
    ks (b, 3, nk) of :func:`_block_schedule`, or the one step table of
    :func:`_fwd_steps`); q (block_q, d); k/v (block_k, d); the mask
    operands (:func:`_allowed`): positions qp (block_q, 1) and kp
    (1, block_k) int32 — explicit arrays, not iota, so permuted layouts
    (ring/zigzag shards) mask correctly — or the pair's (block_q, block_k)
    int8 block of a selection; o (block_q, d);
    lse (block_q, 1) — scalars-per-row ride as a column, rank-1 tiled
    outputs fail Mosaic lowering (see ops/quantization.py). Scratch acc
    (block_q, d) f32, m/l (block_q, 1) f32 persist across the q block's
    steps (the grid's last axis, sequential on TPU).
    """
    from jax.experimental import pallas as pl

    tables, refs = refs[:n_tables], refs[n_tables:]
    q_ref, k_ref, v_ref, *mask_refs, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    first, last, cases = step(*tables)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _update(masked, span):
        # The keys of the step: the KV block's, or those of its needed half
        # (the other's are all masked: probabilities of exactly 0 and a maximum
        # that cannot win, so leaving them out changes no result).
        keys = _columns(span, k_ref.shape[0])
        q = q_ref[...]
        k = k_ref[keys, :]
        scores = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # (block_q, keys) f32
        if masked:
            scores = jnp.where(_allowed(mask_refs, keys, window), scores, _NEG_INF)

        m_prev = m_ref[...]  # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        correction = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)  # (block_q, keys) f32
        l_ref[...] = l_ref[...] * correction + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype),
            v_ref[keys, :],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * correction + pv
        m_ref[...] = m_new

    _when_needed(cases, _update, mask_refs)

    @pl.when(last)
    def _finalize():
        # Rows whose running max never left the sentinel saw only masked
        # scores: their p = exp(score - m) degenerated to 1 (the classic
        # all-masked-row trap), so acc holds sum-of-V garbage — zero them
        # and pin lse to the sentinel so partial merges weight them out.
        empty = m_ref[...] <= _NEG_INF
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = jnp.where(empty, 0.0, acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[...] = jnp.where(empty, _NEG_INF, m_ref[...] + jnp.log(l))


def _flash_fwd(
    q, k, v, scale, block_q, block_k, interpret,
    q_positions=None, k_positions=None, selection=None, window=None,
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk = k.shape[1]
    kv_heads = k.shape[2]
    group = h // kv_heads

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    qp, kp = _padded_positions(
        q_positions, k_positions, b, sq, sk, block_q, block_k
    )
    own = q_positions is None and k_positions is None
    walk = _fwd_walk(own, qp, kp, sq, sk, block_q, block_k, interpret, window)
    mask, mask_kinds = _mask_operands(selection, qp, kp, pad_q, pad_k)

    # Kernels run on (b, heads, seq, d): Mosaic requires the last two BLOCK
    # dims be (mult-of-8, mult-of-128-or-whole-dim), so seq and head_dim must
    # be minor. The model-facing (b, seq, heads, d) layout would squeeze the
    # heads dim into second-to-last block position (block 1 vs array h — an
    # on-chip lowering error interpret mode never sees). The transposes are
    # plain XLA copies at the kernel boundary.
    qt = q.transpose(0, 2, 1, 3)  # (b, h, sq_p, d)
    kt = k.transpose(0, 2, 1, 3)  # (b, kv_heads, sk_p, d)
    vt = v.transpose(0, 2, 1, 3)

    spec = _block_specs(block_q, block_k, d, group, walk.q_of, walk.k_of)
    inputs = (*walk.tables, qt, kt, vt, *mask)
    out, lse = pl.pallas_call(
        partial(
            _fwd_kernel, scale=scale, n_tables=len(walk.tables), step=walk.step,
            window=window,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk.tables),
            grid=(b, h, *walk.grid),
            in_specs=[spec[kind] for kind in ("q", "kv", "kv", *mask_kinds)],
            out_specs=[spec["q"], spec["col"]],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
        ),
        out_shape=[
            _out_struct((b, h, sq + pad_q, d), q.dtype, inputs),
            _out_struct((b, h, sq + pad_q, 1), jnp.float32, inputs),
        ],
        name=WINDOW_FWD if window is not None else None,
        interpret=interpret,
    )(*inputs)
    out = out.transpose(0, 2, 1, 3)  # back to (b, sq_p, h, d)
    lse = lse[..., 0].transpose(0, 2, 1)  # (b, sq_p, h)
    if pad_q:
        out = out[:, :sq]
        lse = lse[:, :sq]
    # (b, sq, h) -> (b, sq, kv, group): head h is kv-head h // group, the
    # same layout blockwise_attention's backward expects for its residual.
    return out, lse.reshape(b, sq, kv_heads, group)


# VMEM of one backward call, in bytes (the TPU v5e has 128 MiB a core). A
# Mosaic call gets _SCOPED_VMEM_BYTES without asking, and asking is not free:
# with a limit stated on the call, XLA tiles other fusions of the same program
# differently (PERF.md section 6, PR 42: a matmul of the loss head 3 ms a step
# slower in every cell). So the call states a limit only where its shapes need
# more, and holds at most _MAX_VMEM_BYTES: a sequence whose dq rows do not fit
# that is walked in chunks of q blocks (:func:`_q_chunks`).
_SCOPED_VMEM_BYTES = 16 * 2**20
_MAX_VMEM_BYTES = 64 * 2**20


def _bwd_kernel(
    *refs,
    scale: float, n_tables: int, step: Callable, block_q: int,
    window: Optional[int] = None,
):
    """One grid step of the backward: one (q block, KV block) pair of a
    (batch, head, q chunk), the pairs in the order of the call's walk
    (:class:`_Walk`: ``step`` is its, and the first ``n_tables`` refs), a KV
    block's q blocks of the chunk one after another. A needed pair recomputes
    its probabilities once from the saved logsumexp and adds to all three
    gradients (FlashAttention-2 backward, five matmuls a pair). dk and dv
    accumulate in (block_k, d) scratch across the q blocks of one KV block
    and leave as PER-Q-HEAD, per-chunk partials: the GQA group sum happens
    outside, so every output block is written exactly once. dq accumulates
    across the KV blocks in a scratch that holds the whole chunk's rows, a
    step adding into its q block's; the dq output block is the chunk's too,
    so it stays in VMEM for the chunk and goes out once, cast. Refs after
    the tables: q, k, v, dO, lse, delta, the mask operands
    (:func:`_allowed`), then the three outputs and the three accumulators."""
    from jax.experimental import pallas as pl

    tables, refs = refs[:n_tables], refs[n_tables:]
    q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *rest = refs
    *mask_refs, dq_ref, dk_ref, dv_ref, dq_acc_ref, dk_acc_ref, dv_acc_ref = rest
    jq, kv_first, kv_last, q_first, q_last, cases = step(*tables)
    rows = pl.ds(pl.multiple_of(jq * block_q, block_q), block_q)

    @pl.when(kv_first)
    def _init_dkv():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    @pl.when(q_first)
    def _init_dq():
        dq_acc_ref[rows, :] = jnp.zeros((block_q, dq_acc_ref.shape[1]), jnp.float32)

    def _update(masked, span):
        # The keys of the step (the forward's ``_update``): a half that needs
        # nothing adds exact zeros to dq and to its own rows of dk and dv.
        keys = _columns(span, k_ref.shape[0])
        q = q_ref[...]
        k = k_ref[keys, :]
        scores = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # (block_q, keys) f32
        p = jnp.exp(scores - lse_ref[...])
        if masked:
            # p from the saved lse; masked entries exactly 0 (also kills
            # padded q rows, whose position is -1 — below every key).
            p = jnp.where(_allowed(mask_refs, keys, window), p, 0.0)
        do = do_ref[...]
        dv_acc_ref[keys, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (keys, d)
        dp = jax.lax.dot_general(
            do, v_ref[keys, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, keys) f32
        ds = (p * (dp - dl_ref[...]) * scale).astype(q.dtype)
        dk_acc_ref[keys, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (keys, d)
        dq_acc_ref[rows, :] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, d)

    _when_needed(cases, _update, mask_refs)

    @pl.when(kv_last)
    def _finalize_dkv():
        dk_ref[...] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc_ref[...].astype(dv_ref.dtype)

    @pl.when(q_last)
    def _finalize_dq():
        dq_ref[rows, :] = dq_acc_ref[rows, :].astype(dq_ref.dtype)


def _bwd_vmem_bytes(
    q_rows, block_q, block_k, d, in_bytes, out_bytes, selected=False
):
    """VMEM the backward call needs with ``q_rows`` rows of dq resident, from
    its shapes: every pipelined block twice (a (block_q, 1) column and the
    (1, block_k) row pad to whole tiles; ``selected``: the int8 block of a
    selection rides in place of the two position blocks), the three
    accumulators, and what
    Mosaic keeps of a pair's (block_q, block_k) probabilities outside the
    registers. That last term is a bound, not a derivation: the v5e's
    compiler reports what a call used (``used_scoped_memory_configs`` in the
    compiled text), and over 4096 to 57,344 rows of 128, blocks from
    256 x 512 to 1024 x 2048 and gradients in bf16 and float32 the sum reads
    0.4 MiB or more over it (13.4 MiB used of 15.6 estimated at the cells'
    8192 rows in 512 x 1024); tests/test_tpu_aot_compile.py holds the sum
    over the compiler's account at the shapes it compiles."""
    width = _next_multiple(d, 128)
    mask = block_q * block_k if selected else block_q * 128 * 4 + 8 * block_k * 4
    blocks = (
        2 * block_q * width * in_bytes  # q, dO
        + 2 * block_k * width * in_bytes  # k, v
        + 2 * block_q * 128 * 4  # lse, delta
        + mask  # the selection's block, or qp and kp
        + 2 * block_k * width * out_bytes  # dk, dv
        + q_rows * width * out_bytes  # dq
    )
    scratch = (q_rows + 2 * block_k) * width * 4
    pair = block_q * block_k * (3 + in_bytes)
    return 2 * blocks + scratch + pair


def _q_chunks(sq, vmem_bytes, block_q, *tile):
    """(nc, nqc): the backward walks a head's q blocks as nc chunks of nqc,
    the fewest chunks whose dq rows (accumulator and output block) fit
    ``vmem_bytes`` beside the call's tiles, evened out. One chunk is the
    whole sequence resident. ``tile``: the rest of :func:`_bwd_vmem_bytes`'s
    arguments."""
    tiles = _bwd_vmem_bytes(0, block_q, *tile)
    fit = (vmem_bytes - tiles) // (_bwd_vmem_bytes(block_q, block_q, *tile) - tiles)
    nq = -(-sq // block_q)
    nc = -(-nq // max(1, fit))
    nqc = -(-nq // nc)
    return -(-nq // nqc), nqc


def flash_attention_partial_bwd(
    q, k, v, d_out, out, lse,
    q_positions, k_positions,
    scale, block_q, block_k, interpret,
    delta=None,
    out_dtype=None,
    vmem_bytes=_MAX_VMEM_BYTES,
    selection=None,
    window=None,
):
    """Fused Pallas backward PARTIAL over an arbitrary KV block: the ring
    backward building block (and, with arange positions, the full causal
    backward). Masking uses explicit global position arrays, so permuted
    (zigzag) ring layouts work; ``lse`` is the GLOBAL logsumexp per q-head
    (b, sq, h) f32 — with it, one call yields this KV block's exact (dk,
    dv) and this query shard's dq contribution, no forward recompute
    (FlashAttention-2 identity).

    ``delta = rowsum(dO*O)`` may be precomputed (ring callers reuse it
    across hops). Returns (dq_partial, dk, dv) in ``out_dtype`` (default
    f32 — ring callers accumulate partials across hops in f32 and cast
    once at the end; the single-block full-causal caller passes the input
    dtype so the kernel casts in VMEM and halves the gradient writeback for
    bf16 models). dk/dv are group-summed. Padding: q rows pad with
    position -1 (below every key → zero contribution to every gradient);
    KV rows pad with _PAD_POS (above every query → likewise zero).

    One Mosaic call (:func:`_bwd_kernel`). Its float32 dq accumulator holds
    all the q rows of a head where the call then fits ``vmem_bytes``; a
    longer sequence is walked in equal chunks of as many q blocks as fit,
    each chunk one more set of dk/dv partials for the group sum. The choice
    is by shape alone; the argument is for tests, which make it small.

    ``selection`` (b, sq, sk) int8: what the forward was given
    (:func:`flash_attention`); a needed pair then masks by its block of it.
    ``window``: the forward's too; the pairs behind it are skipped and the
    pairs on its edge masked, as there."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk = k.shape[1]
    kv_heads = k.shape[2]
    group = h // kv_heads
    selected = selection is not None
    block_q, block_k = _block_sizes(block_q, block_k, sq, sk, selected)
    if out_dtype is None:
        out_dtype = jnp.float32

    if delta is None:
        # Cheap elementwise+reduce, XLA fuses it into the surrounding graph.
        delta = jnp.sum(
            d_out.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
        )  # (b, sq, h)

    tile = (
        block_q, block_k, d, q.dtype.itemsize, jnp.dtype(out_dtype).itemsize,
        selected,
    )
    nc, nqc = _q_chunks(sq, vmem_bytes, *tile)
    q_rows = nqc * block_q
    need = _bwd_vmem_bytes(q_rows, *tile)
    pad_q = nc * q_rows - sq
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        d_out = jnp.pad(d_out, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        lse = jnp.pad(lse, ((0, 0), (0, pad_q), (0, 0)))
        delta = jnp.pad(delta, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    qp, kp = _padded_positions(
        q_positions, k_positions, b, sq, sk, q_rows, block_k
    )
    own = q_positions is None and k_positions is None
    walk = _bwd_walk(own, qp, kp, sq, sk, block_q, block_k, interpret, window, nqc)
    mask, mask_kinds = _mask_operands(selection, qp, kp, pad_q, pad_k)
    # Same heads-major transposition as _flash_fwd (see comment there): the
    # kernel sees (b, h, seq, d) / (b, h, seq, 1) so seq and d are the block
    # minor dims Mosaic requires.
    qt = q.transpose(0, 2, 1, 3)  # (b, h, sq_p, d)
    kt = k.transpose(0, 2, 1, 3)  # (b, kv_heads, sk_p, d)
    vt = v.transpose(0, 2, 1, 3)
    dot = d_out.transpose(0, 2, 1, 3)  # (b, h, sq_p, d)
    lse_col = lse.reshape(b, sq + pad_q, h, 1).transpose(0, 2, 1, 3)
    delta_col = delta.reshape(b, sq + pad_q, h, 1).transpose(0, 2, 1, 3)
    inputs = (*walk.tables, qt, kt, vt, dot, lse_col, delta_col, *mask)

    spec = _block_specs(block_q, block_k, d, group, walk.q_of, walk.k_of)
    # The outputs are per q head (dk, dv: and per chunk), so not "q" / "kv";
    # the chunk is the walk's first axis.
    dq_out = pl.BlockSpec(
        (None, None, q_rows, d), lambda ib, ih, ic, *rest: (ib, ih, ic, 0)
    )
    dkv_out = pl.BlockSpec(
        (None, None, None, block_k, d),
        lambda ib, ih, ic, *rest: (ib, ih, ic, walk.k_of(ib, ic, *rest), 0),
    )
    dq, dk_h, dv_h = pl.pallas_call(
        partial(
            _bwd_kernel, scale=scale, n_tables=len(walk.tables), step=walk.step,
            block_q=block_q, window=window,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk.tables),
            grid=(b, h, *walk.grid),
            # q, k, v, dO, lse, delta and the mask by kind of spec
            # (_block_specs).
            in_specs=[
                spec[kind]
                for kind in ("q", "kv", "kv", "q", "col", "col", *mask_kinds)
            ],
            out_specs=[dq_out, dkv_out, dkv_out],
            scratch_shapes=[
                pltpu.VMEM((q_rows, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
        ),
        out_shape=[
            _out_struct((b, h, sq + pad_q, d), out_dtype, inputs),
            _out_struct((b, h, nc, sk + pad_k, d), out_dtype, inputs),
            _out_struct((b, h, nc, sk + pad_k, d), out_dtype, inputs),
        ],
        compiler_params=(
            pltpu.CompilerParams(vmem_limit_bytes=need)
            if need > _SCOPED_VMEM_BYTES
            else None
        ),
        name=WINDOW_BWD if window is not None else None,
        interpret=interpret,
    )(*inputs)
    dq = dq.transpose(0, 2, 1, 3)  # (b, sq_p, h, d)
    dk_h = dk_h.transpose(0, 3, 1, 2, 4)  # (b, sk_p, h, nc, d)
    dv_h = dv_h.transpose(0, 3, 1, 2, 4)

    if pad_q:
        dq = dq[:, :sq]
    if pad_k:
        dk_h = dk_h[:, :sk]
        dv_h = dv_h[:, :sk]
    # Sum of the per-q-head, per-chunk partials over the GQA group and the
    # chunks (one XLA reduction).
    dk = dk_h.reshape(b, sk, kv_heads, group * nc, d).sum(axis=3)
    dv = dv_h.reshape(b, sk, kv_heads, group * nc, d).sum(axis=3)
    return dq, dk, dv


def _flash_bwd(
    q, k, v, selection, out, lse, d_out, scale, block_q, block_k, interpret,
    window=None,
):
    """Full-causal fused backward: the partial backward with arange
    positions and a single all-KV block set."""
    dq, dk, dv = flash_attention_partial_bwd(
        q, k, v, d_out, out, lse, None, None,
        scale, block_q, block_k, interpret,
        out_dtype=q.dtype,  # no cross-call accumulation: cast in VMEM
        selection=selection,
        window=window,
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_core(
    q, k, v, selection, scale, block_q, block_k, interpret, pallas_bwd, window
):
    return _flash_fwd(
        q, k, v, scale, block_q, block_k, interpret, selection=selection,
        window=window,
    )[0]


def _flash_core_fwd(
    q, k, v, selection, scale, block_q, block_k, interpret, pallas_bwd, window
):
    out, lse = _flash_fwd(
        q, k, v, scale, block_q, block_k, interpret, selection=selection,
        window=window,
    )
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, selection, out, lse)


def _flash_core_bwd(
    scale, block_q, block_k, interpret, pallas_bwd, window, residuals, d_out
):
    q, k, v, selection, out, lse = residuals
    if pallas_bwd:
        b, s, h, d = q.shape
        # Residual lse is (b, s, kv, group); the kernels index it per
        # q-head h = kvh * group + g — the exact inverse reshape. The
        # selection is no function of q, k or v here: no cotangent.
        return *_flash_bwd(
            q, k, v, selection, out, lse.reshape(b, s, h), d_out,
            scale, block_q, block_k, interpret, window,
        ), None
    # Scan-based flash backward (recompute per KV block from the saved
    # logsumexp) — shared with blockwise_attention; the CPU/fallback path,
    # which knows no selection (flash_attention never sends it one).
    return *_blockwise_core_bwd(scale, block_k, (q, k, v, out, lse), d_out, window), None


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention_partial(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    k_positions: jnp.ndarray,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
):
    """One causal-attention PARTIAL over an arbitrary KV block: the ring
    attention building block. Masking uses the explicit global position
    arrays (so zigzag/permuted shard layouts work), and the result is
    returned with its logsumexp so partials from different KV shards merge
    exactly (see :func:`merge_attention_partials`).

    Shapes: q (b, sq, h, d); k/v (b, sk, kv_heads, d); positions (b, sq) /
    (b, sk). Returns (out (b, sq, h, d) in q.dtype, lse (b, sq, h) f32;
    fully-masked rows come back as out=0, lse≈-1e30). Forward-only — ring
    callers define their own VJP (ops/ring_attention.py: per-hop
    :func:`flash_attention_partial_bwd` on TPU, einsum ring backward as
    the interpret/CPU fallback).
    """
    b, sq, h, d = q.shape
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        interpret = not on_tpu()
    block_q, block_k = _block_sizes(block_q, block_k, sq, k.shape[1])
    out, lse = _flash_fwd(
        q, k, v, float(scale), block_q, block_k, bool(interpret),
        q_positions=q_positions, k_positions=k_positions,
    )
    return out, lse.reshape(b, sq, h)


def merge_attention_partials(out_a, lse_a, out_b, lse_b):
    """Combines two normalized attention partials of the same queries over
    disjoint KV sets via their logsumexps (the flash/ring merge identity).
    out: (..., d) f32; lse: (...,) f32 with -1e30 as the empty sentinel."""
    m = jnp.maximum(lse_a, lse_b)
    # Guard the both-empty case: exp(-1e30 - -1e30) = 1 would resurrect
    # fully-masked rows with weight 1 each; keep them exactly empty.
    both_empty = m <= _NEG_INF
    wa = jnp.where(both_empty, 0.0, jnp.exp(lse_a - m))
    wb = jnp.where(both_empty, 0.0, jnp.exp(lse_b - m))
    l = wa + wb
    safe_l = jnp.maximum(l, 1e-30)
    out = (out_a * wa[..., None] + out_b * wb[..., None]) / safe_l[..., None]
    lse = jnp.where(both_empty, _NEG_INF, m + jnp.log(safe_l))
    return out, lse


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    use_pallas_bwd: Optional[bool] = None,
    selection: Optional[jnp.ndarray] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Fused causal GQA attention on one device: Pallas forward AND
    FlashAttention-2-style Pallas backward (one kernel that recomputes the
    probabilities from the saved logsumexp once a block pair and gives dq,
    dk and dv).

    Shapes: q (b, s, h, d); k/v (b, s, kv_heads, d); h % kv_heads == 0.
    The sequence is padded to block multiples internally; outputs are
    returned in the original length. The default blocks are 512x1024
    (``LlamaConfig.attention_block_k`` says what measured them).
    Oversized blocks clamp to the padded sequence below, so short
    sequences are unaffected. ``interpret=None`` auto-selects interpret
    mode off-TPU so the same call works in CPU tests.
    ``use_pallas_bwd=None`` picks the fused backward exactly when the
    forward compiles (on TPU); CPU tests pass True to exercise the
    backward kernel in interpret mode, and False forces the scan-based
    blockwise fallback.

    ``selection`` (b, s, s) int8, nonzero where query t attends to key s,
    one selection for all heads and never a key later than its query
    (ops/sparse_attention.py ``select_keys`` makes it): with it, every block
    pair the causal schedule needs masks by its (block_q, block_k) block of
    the operand in place of the position compare, in the forward and in the
    one backward call, which is then always the Pallas one. A row with no
    selected key comes out zero. The operand gets no gradient. Without it the
    kernels are the calls they are without this argument, operand for
    operand.

    ``window``: query t attends to key u iff ``0 <= t - u < window``, the
    query's own position counted: ``window`` keys in all. The schedule then
    has a second edge (:func:`_pair_class`): pairs behind the window are
    neither fetched nor computed, pairs its edge crosses are masked, pairs
    inside run bare, in both calls, which carry names of their own
    (``WINDOW_FWD``, ``WINDOW_BWD``) so that a device trace tells them from
    the causal calls. A window that covers the sequence is the causal call,
    and no window with a selection.
    """
    b, s, h, d = q.shape
    kv_heads = k.shape[2]
    if h % kv_heads:
        raise ValueError(f"n_heads {h} not a multiple of kv_heads {kv_heads}")
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        # Same device-platform check as ops/quantization.py's *_device
        # helpers: only the default device's platform says whether Mosaic
        # can compile the kernel.
        interpret = not on_tpu()
    if selection is not None:
        if selection.shape != (b, s, k.shape[1]):
            raise ValueError(
                f"selection {selection.shape} for q {q.shape} and k {k.shape}"
            )
        if use_pallas_bwd is False:
            raise ValueError("the scan-based backward takes no selection")
        if window is not None:
            raise ValueError("a selection already says what a window would")
        selection = selection.astype(jnp.int8)
        use_pallas_bwd = True
    if window is not None:
        if window < 1:
            raise ValueError(f"a window of {window} keys")
        window = int(window) if window < s else None
    if use_pallas_bwd is None:
        use_pallas_bwd = not interpret
    block_q, block_k = _block_sizes(block_q, block_k, s, s, selection is not None)
    return _flash_core(
        q, k, v, selection, float(scale), block_q, block_k, bool(interpret),
        bool(use_pallas_bwd), window,
    )


def _next_multiple(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _block_sizes(block_q, block_k, sq, sk, selected=False):
    """The block sizes every entry point runs: block_q aligned to 16 — the
    bf16 sublane tile (and a multiple of f32's 8), or to 32, the int8 one,
    where the call has a selection, whose block has block_q rows; block_k to
    128 — the LANE tile, because the kp position row rides as a (1, block_k)
    block whose last dim must be a 128-multiple or the whole padded dim; then
    oversized blocks clamped to the padded sequence. A ragged block would
    pass interpret-mode tests and fail Mosaic lowering on the chip
    (tests/test_mosaic_lowering.py pins this)."""
    rows = 32 if selected else 16
    return (
        min(_next_multiple(int(block_q), rows), _next_multiple(sq, rows)),
        min(_next_multiple(int(block_k), 128), _next_multiple(sk, 128)),
    )


def _class_counts(
    sq, sk, block_q, block_k, q_positions=None, k_positions=None, window=None
):
    """How many (q block, KV block) pairs of one call over these lengths,
    blocks and (b, s) positions the schedule classes above the diagonal, on
    it and under it (the first batch row's). With a ``window`` the pairs it
    takes off the walk are counted apart as ``behind``, those its edge alone
    crosses as ``edge`` (``diagonal`` keeps every masked pair the causal
    compare masks, a corner both lines cross included) and ``under`` reads
    inside both. ``steps``: the grid steps a head takes in one forward call,
    which says which walk the call takes: the needed pairs alone where it
    has no position arrays, every pair where it has. ``halves``: how many of
    those steps compute one half of their KV block alone (:func:`_own_classes`;
    0 in the walk over every pair, and where a half block is no whole lane
    tiles)."""
    block_q, block_k = _block_sizes(block_q, block_k, sq, sk)
    qp, kp = _padded_positions(q_positions, k_positions, 1, sq, sk, block_q, block_k)

    def classes(window):
        tables = _block_schedule(qp[:1], kp[:1], block_q, block_k, False, window)
        return _block_classes(*tables, window)

    causal = classes(None)
    counts = {
        name: int(jnp.sum(causal == c))
        for c, name in enumerate(("above", "diagonal", "under"))
    }
    own = q_positions is None and k_positions is None
    walk = _fwd_walk(own, qp[:1], kp[:1], sq, sk, block_q, block_k, False, window)
    counts["steps"] = int(np.prod(walk.grid))
    listed = len(walk.tables) == 1  # its one table, the steps; the dense walk has two
    counts["halves"] = int(np.sum(np.asarray(walk.tables[0][_CLASS]) > 2)) if listed else 0
    if window is None:
        return counts
    mine = classes(window)
    behind = int(jnp.sum((mine == 0) & (causal > 0)))
    edge = int(jnp.sum((mine == 1) & (causal == 2)))
    counts["behind"], counts["edge"] = behind, edge
    counts["diagonal"] = int(jnp.sum((mine == 1) & (causal == 1)))
    counts["under"] = int(jnp.sum(mine == 2))
    return counts


def verify_on_chip() -> dict:
    """Compile (not interpret) the kernels on the attached accelerator and
    check them against dense attention — the CLAUDE.md 'verify kernels on the
    real chip' gate (chip_smoke.py runs it; by hand through the chip tool):

        python -c "from torchft_tpu.ops.flash_attention import verify_on_chip; print(verify_on_chip())"

    One case runs both kernels under a selection
    (``flash_attention(..., selection=...)``), four under a window
    (``window=``), and the last five (``listed``) are the grid of needed pairs
    at the cells' sizes, some of its steps computing one half of their KV
    block alone, each also compared to the bit with the walk over every pair,
    whole (``listed_differs_from_every_pair``: differing elements by output).
    Returns the largest error of
    each case; under ``classes``, how many
    block pairs of the case the schedule classed above, on and under the
    diagonal, the grid ``steps`` a head's forward takes and how many of them
    run one half (``halves``): how often the
    scheduling engaged; and under ``bwd_q_chunks``
    the path each case's backward calls took: 1 is dq resident in VMEM for
    the whole sequence, more is that many chunks of q blocks a head.
    """
    from torchft_tpu.ops.attention import causal_attention

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"no TPU attached (devices()[0] is {dev})")
    b, s, h, kv, d = 2, 256, 4, 2, 64
    scale = d**-0.5
    classes, chunks = {}, {}

    def qkv(sq, sk, seed=0):
        kq, kk, kvk = jax.random.split(jax.random.PRNGKey(seed), 3)
        return (
            jax.random.normal(kq, (b, sq, h, d), jnp.bfloat16),
            jax.random.normal(kk, (b, sk, kv, d), jnp.bfloat16),
            jax.random.normal(kvk, (b, sk, kv, d), jnp.bfloat16),
        )

    def worst(got, want):
        return jnp.max(
            jnp.stack([
                jnp.max(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)))
                for g, w in zip(got, want)
            ])
        )

    def check(what, err, bound):
        err = float(err)
        if err > bound:
            raise AssertionError(f"on-chip flash {what} mismatch: max err {err}")
        return err

    def full(sq, block_q, block_k, case):
        """flash_attention against dense, forward and gradients. The
        kernels' side is one program; the dense side runs op by op, as it
        has since the errors on record were first read (jitted, XLA fuses
        the bf16 reference's gradient and ITS rounding moves the backward
        error from 0.125 to 0.13–0.21)."""

        def attend(q, k, v):
            return flash_attention(
                q, k, v, block_q=block_q, block_k=block_k,
                interpret=False, use_pallas_bwd=True,
            )

        def dense(q, k, v):
            return causal_attention(q, k, v, scale=scale)

        def grads(fn, *x):
            return jax.grad(
                lambda *x: jnp.sum(fn(*x).astype(jnp.float32) ** 2), argnums=(0, 1, 2)
            )(*x)

        @jax.jit
        def errors(q, k, v, ref, ref_grads):
            return (
                worst([attend(q, k, v)], [ref]),
                worst(grads(attend, q, k, v), ref_grads),
            )

        classes[case] = _class_counts(sq, sq, block_q, block_k)
        chunks[case], _ = _q_chunks(  # bf16 in, bf16 out
            sq, _MAX_VMEM_BYTES, *_block_sizes(block_q, block_k, sq, sq), d, 2, 2
        )
        q, k, v = qkv(sq, sq)
        return errors(q, k, v, dense(q, k, v), grads(dense, q, k, v))

    def dense_under(mask, q, k, v):
        """Attention in float32 under a (b, sq, sk) bool mask, one mask for
        all heads; a row that sees nothing comes out zero."""
        sq = q.shape[1]
        qg = q.astype(jnp.float32).reshape(b, sq, kv, h // kv, d)
        sc = jnp.einsum("bskgd,btkd->bskgt", qg, k.astype(jnp.float32)) * scale
        mask = mask[:, :, None, None, :]
        pr = jax.nn.softmax(jnp.where(mask, sc, _NEG_INF), axis=-1)
        pr = jnp.where(mask.any(axis=-1, keepdims=True), pr, 0.0)
        return jnp.einsum(
            "bskgt,btkd->bskgd", pr, v.astype(jnp.float32)
        ).reshape(b, sq, h, d)

    @partial(jax.jit, static_argnums=(3, 4, 5))
    def hop_errors(q, qp, shards, block_q, block_k, vmem_bytes):
        merged = lse = None
        for k, v, kp in shards:
            o, l = flash_attention_partial(
                q, k, v, qp, kp, block_q=block_q, block_k=block_k, interpret=False
            )
            o = o.astype(jnp.float32)
            merged, lse = (
                (o, l) if merged is None
                else merge_attention_partials(merged, lse, o, l)
            )
        d_out = jax.random.normal(jax.random.PRNGKey(7), merged.shape, jnp.float32)
        dq, dks, dvs = 0.0, [], []
        for k, v, kp in shards:
            dq_p, dk, dv = flash_attention_partial_bwd(
                q, k, v, d_out.astype(q.dtype), merged.astype(q.dtype), lse,
                qp, kp, scale, block_q, block_k, False,
                vmem_bytes=vmem_bytes,
            )
            dq, dks, dvs = dq + dq_p, dks + [dk], dvs + [dv]

        kp_all = jnp.concatenate([kp for _, _, kp in shards], axis=1)
        k_all = jnp.concatenate([k for k, _, _ in shards], axis=1)
        v_all = jnp.concatenate([v for _, v, _ in shards], axis=1)
        ref, vjp = jax.vjp(
            partial(dense_under, qp[:, :, None] >= kp_all[:, None, :]), q, k_all, v_all
        )
        return worst([merged], [ref]), worst(
            [dq, jnp.concatenate(dks, axis=1), jnp.concatenate(dvs, axis=1)],
            vjp(d_out.astype(ref.dtype)),
        )

    def hops(q, qp, shards, block_q, block_k, case, vmem_bytes=_MAX_VMEM_BYTES):
        """The ring's building blocks against dense attention under the same
        position mask: one :func:`flash_attention_partial` a KV shard, merged
        by logsumexp, then one :func:`flash_attention_partial_bwd` a shard
        with the merged (global) logsumexp; dq is the sum over the shards."""
        classes[case] = [
            _class_counts(q.shape[1], k.shape[1], block_q, block_k, qp, kp)
            for k, _, kp in shards
        ]
        chunks[case], _ = _q_chunks(  # bf16 in, f32 out
            q.shape[1], vmem_bytes, block_q, block_k, d, 2, 4
        )
        return hop_errors(q, qp, shards, block_q, block_k, vmem_bytes)

    err, _ = full(s, 128, 128, "causal")
    err = check("forward", err, 0.05)  # bf16 tolerance
    # Gradients square the bf16 rounding; the scan-backward CPU tests hold
    # the same bound.
    _, err_bwd = full(s, 512, 1024, "causal-one-block")
    err_bwd = check("BACKWARD", err_bwd, 0.25)

    # The partial surface: explicit PERMUTED position arrays (the
    # (1, block_k) row tile), sq != sk, a ragged length (200 pads to 208),
    # and the logsumexp merge over the two halves of the keys — everything
    # the ring path lowers that the full-attention call above does not.
    sq = 200
    q, k, v = qkv(sq, s)
    pos = jax.random.permutation(jax.random.PRNGKey(3), s)[:sq]
    qp = jnp.broadcast_to(pos.astype(jnp.int32), (b, sq))
    kp = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    half = s // 2
    err_p, err_pb = hops(
        q, qp,
        [(k[:, :half], v[:, :half], kp[:, :half]), (k[:, half:], v[:, half:], kp[:, half:])],
        128, 128, "permuted",
    )
    err_p = check("PARTIAL/merge", err_p, 0.05)
    err_pb = check("PARTIAL BACKWARD", err_pb, 0.25)

    # Three hops of a zigzag ring of 4 at rank 1: its own shard (chunks 1
    # and 6 of 8: the diagonal crosses two corners), rank 0's (0 and 7: its
    # low chunk wholly under every query, its high chunk wholly above) and
    # rank 2's (2 and 5: the low queries see nothing of it, the high ones
    # all). Most pairs are above or under: where the schedule does the most.
    chunk = 256

    def shard(rank):
        return jnp.concatenate(
            [jnp.arange(c * chunk, (c + 1) * chunk, dtype=jnp.int32) for c in (rank, 7 - rank)]
        )

    q, k0, v0 = qkv(2 * chunk, 2 * chunk, seed=11)
    (_, k1, v1), (_, k2, v2) = qkv(8, 2 * chunk, seed=12), qkv(8, 2 * chunk, seed=13)

    def at(rank):
        return jnp.broadcast_to(shard(rank), (b, 2 * chunk))

    err_z, err_zb = hops(
        q, at(1), [(k0, v0, at(1)), (k1, v1, at(0)), (k2, v2, at(2))],
        128, 128, "zigzag",
    )
    err_z = check("ZIGZAG", err_z, 0.05)
    err_zb = check("ZIGZAG BACKWARD", err_zb, 0.25)
    # The same hops with room for one q block of dq: a sequence longer than
    # the resident accumulator, walked as four chunks a head.
    _, err_cb = hops(
        q, at(1), [(k0, v0, at(1)), (k1, v1, at(0)), (k2, v2, at(2))],
        128, 128, "zigzag-chunked",
        vmem_bytes=_bwd_vmem_bytes(128, 128, 128, d, 2, 4),
    )
    err_cb = check("CHUNKED BACKWARD", err_cb, 0.25)

    # A ragged causal length over several blocks: 600 = 4 x 128 + 88 =
    # 2 x 256 + 88, so the last q block and the last KV block are padded and
    # read diagonal, with blocks under the diagonal beside them.
    err_r, err_rb = full(600, 128, 256, "ragged")
    err_r = check("RAGGED", err_r, 0.05)
    err_rb = check("RAGGED BACKWARD", err_rb, 0.25)

    # Under a selection, at the default blocks (what models/keye.py runs):
    # 256 keys a query of 2048, by ops/sparse_attention.py's select_topk on
    # random scores; every other row prefers its latest keys, so from row
    # 1280 on those rows have no key in the first KV block (their running
    # max leaves the sentinel only in the second).
    from torchft_tpu.ops.sparse_attention import select_topk

    ss, topk = 2048, 256
    at = jnp.arange(ss)
    near = (at[:, None] - at[None, :] < topk) & (at[:, None] % 2 == 1)
    index = jax.random.normal(jax.random.PRNGKey(5), (b, ss, ss)) + 16.0 * near
    causal = jnp.broadcast_to(at[:, None] >= at[None, :], (b, ss, ss))
    selection = select_topk(index, causal, topk).astype(jnp.int8)
    classes["selected"] = _class_counts(ss, ss, 512, 1024)

    @jax.jit
    def selected_errors(q, k, v, selection, d_out):
        got, vjp = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, interpret=False, selection=selection
            ),
            q, k, v,
        )
        ref, ref_vjp = jax.vjp(partial(dense_under, selection != 0), q, k, v)
        return worst([got], [ref]), worst(vjp(d_out.astype(got.dtype)), ref_vjp(d_out))

    q, k, v = qkv(ss, ss, seed=21)
    d_out = jax.random.normal(jax.random.PRNGKey(22), q.shape, jnp.float32)
    err_s, err_sb = selected_errors(q, k, v, selection, d_out)
    err_s = check("SELECTED", err_s, 0.05)
    err_sb = check("SELECTED BACKWARD", err_sb, 0.25)

    # Under a window, against dense attention under the same mask: a window
    # that is a multiple of neither block, in several small blocks; then, at
    # the default blocks with 14 query heads over 2 KV heads of 128 (the group
    # of 7 models/smallthinker.py runs), a window of whole blocks, one shorter
    # than a block and one over a ragged length.
    def windowed(sq, window, block_q, block_k, case, heads=h, kv_heads=kv, width=d):
        def attend(q, k, v):
            return flash_attention(
                q, k, v, block_q=block_q, block_k=block_k, interpret=False, window=window
            )

        def dense(q, k, v):
            return causal_attention(q, k, v, width**-0.5, window)

        keys = jax.random.split(jax.random.PRNGKey(31), 4)
        q = jax.random.normal(keys[0], (b, sq, heads, width), jnp.bfloat16)
        k = jax.random.normal(keys[1], (b, sq, kv_heads, width), jnp.bfloat16)
        v = jax.random.normal(keys[2], (b, sq, kv_heads, width), jnp.bfloat16)
        d_out = jax.random.normal(keys[3], q.shape, jnp.float32)

        @jax.jit
        def errors(q, k, v, d_out):
            got, vjp = jax.vjp(attend, q, k, v)
            ref, ref_vjp = jax.vjp(dense, q, k, v)
            d_out = d_out.astype(got.dtype)
            return worst([got], [ref]), worst(vjp(d_out), ref_vjp(d_out))

        classes[case] = _class_counts(sq, sq, block_q, block_k, window=window)
        return errors(q, k, v, d_out)

    window_errors = {}
    for case, args, more in (
        ("window-300", (1000, 300, 128, 256), {}),
        ("window-1024-group7", (3072, 1024, 512, 1024), dict(heads=14, kv_heads=2, width=128)),
        ("window-100-group7", (2048, 100, 512, 1024), dict(heads=14, kv_heads=2, width=128)),
        ("window-700-ragged", (2500, 700, 512, 1024), dict(heads=14, kv_heads=2, width=128)),
    ):
        err_w, err_wb = windowed(*args, case, **more)
        window_errors[case] = (
            check(f"WINDOW {case}", err_w, 0.05), check(f"WINDOW BACKWARD {case}", err_wb, 0.25),
        )
    # The grid of needed pairs (what ``flash_attention`` walks: it brings no
    # position arrays) at the size the cell
    # ``smallthinker-21b-a3b-1chip.ftddp-seq16k`` runs it, 1 x 16,384 with 28
    # query heads over 4 KV heads of 128, full and under its window of 4,096; a
    # ragged length under a window that is a multiple of no block; a
    # selection of 512 keys a query of 4,096; and the Mistral cells' 4 x 2,048
    # at 32 / 8 heads. In each, some steps compute one half of their KV block
    # alone (``halves`` under ``classes``). Each against attention in
    # float32 under the same mask, a query head at a time (the scores of 28
    # heads at once are 30 GB), and against the same two kernels walking every
    # pair whole (the call given the sequence's own positions as arrays): every
    # accumulator adds the same terms in the same order, but for the exact
    # zeros of the halves left out, so nothing differs.
    def listed(case, sq, window=None, topk=None, heads=28, kv_heads=4, width=128, batch=1):
        rows = jnp.arange(sq, dtype=jnp.int32)
        keys = jax.random.split(jax.random.PRNGKey(41), 5)
        q = jax.random.normal(keys[0], (batch, sq, heads, width), jnp.bfloat16)
        k = jax.random.normal(keys[1], (batch, sq, kv_heads, width), jnp.bfloat16)
        v = jax.random.normal(keys[2], (batch, sq, kv_heads, width), jnp.bfloat16)
        d_out = jax.random.normal(keys[3], q.shape, jnp.bfloat16)
        apart = rows[:, None] - rows[None, :]
        mask = (apart >= 0) & (apart < (window or sq))
        selection = None
        if topk is not None:
            index = jax.random.normal(keys[4], (1, sq, sq))
            mask = select_topk(index, mask[None], topk)[0]
            selection = jnp.broadcast_to(mask[None].astype(jnp.int8), (batch, sq, sq))
        blocks = _block_sizes(512, 1024, sq, sq, topk is not None)

        @partial(jax.jit, static_argnums=4)
        def walk(q, k, v, d_out, own):
            at = None if own else jnp.broadcast_to(rows, (batch, sq))
            out, lse = _flash_fwd(
                q, k, v, width**-0.5, *blocks, False, at, at,
                selection=selection, window=window,
            )
            lse = lse.reshape(batch, sq, heads)
            return out, lse, *flash_attention_partial_bwd(
                q, k, v, d_out, out, lse, at, at, width**-0.5, *blocks, False,
                out_dtype=q.dtype, selection=selection, window=window,
            )

        @jax.jit
        def reference(q, k, v, d_out):
            def head(i):
                ib, ih = i // heads, i % heads

                def attend(q, k, v):
                    scores = jnp.einsum("sd,td->st", q, k) * width**-0.5
                    probs = jax.nn.softmax(jnp.where(mask, scores, _NEG_INF), axis=-1)
                    return jnp.einsum("st,td->sd", probs, v)

                mine = [
                    x[ib, :, j].astype(jnp.float32)
                    for x, j in ((q, ih), (k, ih // (heads // kv_heads)), (v, ih // (heads // kv_heads)))
                ]
                out, vjp = jax.vjp(attend, *mine)
                return (out, *vjp(d_out[ib, :, ih].astype(jnp.float32)))

            # (batch x heads, sq, width) each
            out, dq, dk, dv = jax.lax.map(head, jnp.arange(batch * heads))
            group = lambda x: x.reshape(batch, kv_heads, -1, sq, width).sum(axis=2)
            per_head = lambda x: x.reshape(batch, heads, sq, width)
            return [x.transpose(0, 2, 1, 3) for x in (per_head(out), per_head(dq), group(dk), group(dv))]

        got, every_pair = walk(q, k, v, d_out, True), walk(q, k, v, d_out, False)
        want = reference(q, k, v, d_out)
        classes[case] = _class_counts(sq, sq, *blocks, window=window)
        walks_differ[case] = {
            name: int(jnp.sum(a != b))
            for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, every_pair)
        }
        if any(walks_differ[case].values()):
            raise AssertionError(
                f"on-chip flash {case}: elements differ between the grid of needed "
                f"pairs and the walk over every pair: {walks_differ[case]}"
            )
        return (
            check(f"LISTED {case}", worst(got[:1], want[:1]), 0.05),
            check(f"LISTED BACKWARD {case}", worst(got[2:], want[1:]), 0.25),
        )

    walks_differ = {}
    listed_errors = {
        case: listed(case, *args, **more)
        for case, args, more in (
            ("listed-16384", (16384,), {}),
            ("listed-16384-window-4096", (16384, 4096), {}),
            ("listed-ragged-5000-window-1300", (5000, 1300), {}),
            ("listed-4096-selection-512", (4096,), dict(topk=512)),
            ("listed-4x2048", (2048,), dict(heads=32, kv_heads=8, batch=4)),
        )
    }
    return {
        "device": str(dev),
        "max_err": err,
        "max_err_bwd": err_bwd,
        "max_err_partial": err_p,
        "max_err_partial_bwd": err_pb,
        "max_err_zigzag": err_z,
        "max_err_zigzag_bwd": err_zb,
        "max_err_chunked_bwd": err_cb,
        "max_err_ragged": err_r,
        "max_err_ragged_bwd": err_rb,
        "max_err_selected": err_s,
        "max_err_selected_bwd": err_sb,
        "max_err_window": {case: errs[0] for case, errs in window_errors.items()},
        "max_err_window_bwd": {case: errs[1] for case, errs in window_errors.items()},
        "max_err_listed": {case: errs[0] for case, errs in listed_errors.items()},
        "max_err_listed_bwd": {case: errs[1] for case, errs in listed_errors.items()},
        "listed_differs_from_every_pair": walks_differ,
        "classes": classes,
        "bwd_q_chunks": chunks,
        "ok": True,
    }
