"""The learned key selection of ops/sparse_attention.py as ONE Mosaic call a
layer step: index scores, the per-row threshold and the int8 rows that the
flash kernels read, a block of ``ROWS`` queries at a time, in VMEM.

What a grid step ``(batch, row block, key chunk)`` does:

- a block whose rows all have no more than ``topk`` earlier keys scores
  nothing: its selection is the causal mask;
- otherwise the chunk of keys is scored, if the block may see any of it (the
  chunks after the block's last row are neither fetched nor multiplied):
  ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])`` in float32. The product
  is the six-product float32 form that ``Precision.HIGHEST`` computes
  (``hi.hi, hi.mid, mid.hi, mid.mid, hi.lo, lo.hi`` of the operands' three
  bfloat16 parts, summed in float32), laid side by side along the contraction
  (:func:`packed_parts`): for an indexer head of 64 that is three passes of
  the MXU's full depth of 128 where six einsum passes fill half of it. The
  call packs a block's queries once and a chunk's keys a step, in VMEM; XLA
  only transposes the two operands. The scores go, as integers that sort as
  the floats do, into a ``(chunks, ROWS, KEYS)`` VMEM scratch and never to
  HBM;
- at the block's last step the threshold is found where the scores lie
  (``select_topk``'s 32 narrowing passes: count lane-wise, reduce across lanes
  once a pass), ties go to the earlier key (a binary search for the cut
  position over the tied keys, counting passes alone, and only in a block
  that has a tie at a threshold), and the int8 block is written into rows
  ``ROWS`` of the ``(b, s, s)`` operand itself, zeros right of the diagonal.

The call states no VMEM limit and fits the 16 MiB a call gets unasked (a
stated limit retiles other fusions of the step: ops/flash_attention.py
``_SCOPED_VMEM_BYTES``). It carries no gradient. Its name in a trace is
``key_selection.<n>``.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.ops.flash_attention import _out_struct

__all__ = ["KERNEL_NAME", "ROWS", "KEYS", "fits", "key_selection", "packed_parts"]

KERNEL_NAME = "key_selection"
# Query rows a grid step selects for (a multiple of 32, the int8 sublane tile)
# and keys it scores; ROWS x the sequence of int32 is the scratch (4 MiB at
# 8192 keys).
ROWS = 128
KEYS = 512
_LANES = 128
_LOWEST = -(2**31)  # the key of a position a query may not see


def _rounded(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> float32 with bfloat16's eight bits, nearest and ties to even,
    on the bits: a convert to bfloat16 and back is one XLA may drop
    (``xla_allow_excess_precision``), and what is left of ``x`` after it is
    then zero (my chip run, PR 67: the scores were single-pass bfloat16). The
    same line runs inside the call and outside it."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & jnp.int32(-(2**16))
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _parts(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """float32 -> its three bfloat16 parts, hi + mid + lo = x to 2^-24."""
    hi = _rounded(x)
    mid = _rounded(x - hi)
    return tuple(a.astype(jnp.bfloat16) for a in (hi, mid, x - hi - mid))


def packed_parts(qi: jnp.ndarray, ki: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """qi (..., e), ki (..., e) float32 -> bfloat16 (..., 6e) each, whose ONE
    contraction over 6e is the six products of the float32 product at the
    highest precision, the small terms first (a float32 sum that takes them
    in this order rounds as the six products summed apart do; with ``hi.hi``
    first its worst error is five times theirs): ``lo.hi + hi.lo``, ``mid.mid
    + mid.hi``, ``hi.mid + hi.hi``."""
    return _packed_queries(qi), _packed_keys(ki)


def _packed_queries(qi: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    hi, mid, lo = _parts(qi)
    return jnp.concatenate([lo, hi, mid, mid, hi, hi], axis=axis)


def _packed_keys(ki: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    hi, mid, lo = _parts(ki)
    return jnp.concatenate([hi, lo, mid, hi, mid, hi], axis=axis)


def fits(s: int, e: int, rows: int = ROWS, keys: int = KEYS) -> bool:
    """Whether the call takes a sequence of ``s`` with indexer heads of ``e``:
    whole blocks of rows and whole chunks of keys, and a packed contraction
    that is whole MXU passes."""
    return s % rows == 0 and s % min(keys, s) == 0 and (6 * e) % _LANES == 0


def _kernel(q_ref, kt_ref, w_ref, out_ref, packed_ref, keys_ref, *, topk: int, rows: int, keys: int):
    """One grid step; the refs are a row block's float32 queries (heads x rows,
    e) head-major, a chunk's float32 keys transposed (e, keys), the block's
    head weights (rows, heads), the block's rows of the selection (rows, s)
    and two scratches: the block's packed queries (heads x rows, 6e) and the
    ordered scores (s / keys, rows, keys)."""
    from jax.experimental import pallas as pl

    block, chunk, last = pl.program_id(1), pl.program_id(2), pl.num_programs(2) - 1
    first = block * rows
    heads = w_ref.shape[1]
    selects = first + rows > topk  # a row of the block has more keys than it may keep

    def at(shape, axis, offset):
        return offset + jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    @pl.when(selects & (chunk == 0))
    def _pack():
        for head in range(heads):
            span = slice(head * rows, (head + 1) * rows)
            packed_ref[span, :] = _packed_queries(q_ref[span, :])

    @pl.when(selects & (chunk * keys < first + rows))
    def _score():
        scores = jnp.zeros((rows, keys), jnp.float32)
        chunk_keys = _packed_keys(kt_ref[...], axis=0)  # (6e, keys): a sliver of the step's products
        for head in range(heads):
            dots = jnp.dot(
                packed_ref[head * rows:(head + 1) * rows, :], chunk_keys,
                preferred_element_type=jnp.float32,
            )
            scores += jnp.maximum(dots, 0.0) * w_ref[:, head:head + 1]
        # Integers that sort as the floats do (-0.0 and 0.0 tie); the lowest
        # of all is left to the keys after a query.
        bits = jax.lax.bitcast_convert_type(jnp.where(scores == 0, 0.0, scores), jnp.int32)
        ordered = jnp.where(bits < 0, bits ^ jnp.int32(2**31 - 1), bits)
        seen = at((rows, keys), 1, chunk * keys) <= at((rows, keys), 0, first)
        keys_ref[chunk] = jnp.where(seen, ordered, jnp.int32(_LOWEST))

    @pl.when(chunk == last)
    def _select():
        wide = (rows, _LANES)  # a row's counters, the same in every lane
        row = at(wide, 0, first)
        want = jnp.minimum(row + 1, topk)
        # Chunks that hold a key some row of the block sees.
        visible = (first + rows + keys - 1) // keys

        def count(hit):
            """Per row, the keys ``hit(keys, their positions)`` holds of."""
            def one(c, lanes):
                held = keys_ref[c]
                for lo in range(0, keys, _LANES):
                    found = hit(held[:, lo:lo + _LANES], at(wide, 1, c * keys + lo))
                    lanes = lanes + jnp.where(found, 1, 0)
                return lanes

            lanes = jax.lax.fori_loop(0, visible, one, jnp.zeros(wide, jnp.int32))
            return jnp.broadcast_to(jnp.sum(lanes, axis=1, keepdims=True), wide)

        def write(keep):
            """The block's rows: ``keep`` of the visible chunks, then zeros."""
            for c in range(out_ref.shape[1] // keys):
                @pl.when(c < visible)
                def _(c=c):
                    held = keys_ref[c]
                    for lo in range(0, keys, _LANES):
                        kept = keep(held[:, lo:lo + _LANES], at(wide, 1, c * keys + lo))
                        out_ref[:, c * keys + lo:c * keys + lo + _LANES] = (
                            jnp.where(kept, 1, 0).astype(jnp.int8)
                        )

                @pl.when(c >= visible)
                def _(c=c):
                    out_ref[:, c * keys:(c + 1) * keys] = jnp.zeros((rows, keys), jnp.int8)

        @pl.when(jnp.logical_not(selects))
        def _all_earlier_keys():
            write(lambda held, position: position <= row)

        @pl.when(selects)
        def _the_topk():
            # select_topk's radix select on the ordered bits, kept as the
            # unsigned threshold's bits; a comparison is the signed one of
            # both sides less 2^31.
            def narrow(bit, state):
                threshold, reached = state
                candidate = threshold | jnp.left_shift(jnp.int32(1), 31 - bit)
                n = count(lambda held, _: held >= (candidate ^ jnp.int32(_LOWEST)))
                enough = n >= want
                return jnp.where(enough, candidate, threshold), jnp.where(enough, n, reached)

            zero = jnp.zeros(wide, jnp.int32)
            threshold, reached = jax.lax.fori_loop(0, 32, narrow, (zero, zero))
            cut = threshold ^ jnp.int32(_LOWEST)
            tie = jnp.max(reached - want) > 0  # some row's threshold is shared

            @pl.when(jnp.logical_not(tie))
            def _no_tie():
                write(lambda held, _: held >= cut)

            @pl.when(tie)
            def _earlier_key_wins():
                room = want - count(lambda held, _: held > cut)

                # The largest position p with no more than `room` tied keys at
                # or before it: `tied & cumsum(tied) <= room` by counting.
                bits = max(out_ref.shape[1] - 1, 1).bit_length()

                def search(bit, p):
                    candidate = p | jnp.left_shift(jnp.int32(1), bits - 1 - bit)
                    n = count(lambda held, position: (held == cut) & (position <= candidate))
                    return jnp.where(n <= room, candidate, p)

                p = jax.lax.fori_loop(0, bits, search, zero)
                write(lambda held, position: (held > cut) | ((held == cut) & (position <= p)))


def _operands(qi: jnp.ndarray, ki: jnp.ndarray, rows: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """What the call reads, two transposes in XLA and nothing else: a row
    block's queries head-major (b, blocks, j x rows, e), so that a head's rows
    are a slice of sublanes, and the keys (b, e, s), so that a chunk is a
    slice of lanes. The call packs both in VMEM."""
    b, s, j, e = qi.shape
    by_block = qi.reshape(b, s // rows, rows, j, e).transpose(0, 1, 3, 2, 4)
    return by_block.reshape(b, s // rows, j * rows, e), ki.transpose(0, 2, 1)


def key_selection(
    qi: jnp.ndarray, ki: jnp.ndarray, w: jnp.ndarray, *, topk: int,
    rows: int = ROWS, keys: int = KEYS, interpret: bool = False,
) -> jnp.ndarray:
    """qi (b, s, j, e), ki (b, s, e), w (b, s, j), float32 -> (b, s, s) int8,
    1 where query t attends to key s: ``select_topk(index_scores(qi, ki, w),
    causal, topk)`` in one Mosaic call. ``fits(s, e, rows, keys)`` must hold."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, j, e = qi.shape
    keys = min(keys, s)
    if not fits(s, e, rows, keys):
        raise ValueError(f"{s} positions of {e}-wide indexer heads are not whole blocks of {rows} x {keys}")
    blocks, chunks, depth = s // rows, s // keys, 6 * e
    q, kt = _operands(qi, ki, rows)

    # A block that selects nothing fetches the first scoring block's queries
    # and one chunk of keys (the same index again is no fetch).
    scoring = min(topk // rows, blocks - 1)

    def last_chunk(block):
        return jnp.where(block * rows + rows > topk, (block * rows + rows - 1) // keys, 0)

    inputs = (q, kt, w)
    return pl.pallas_call(
        partial(_kernel, topk=topk, rows=rows, keys=keys),
        grid=(b, blocks, chunks),
        in_specs=[
            pl.BlockSpec((None, None, j * rows, e), lambda n, i, c: (n, jnp.maximum(i, scoring), 0, 0)),
            pl.BlockSpec((None, e, keys), lambda n, i, c: (n, 0, jnp.minimum(c, last_chunk(i)))),
            pl.BlockSpec((None, rows, j), lambda n, i, c: (n, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, rows, s), lambda n, i, c: (n, i, 0)),
        out_shape=_out_struct((b, s, s), jnp.int8, inputs),
        scratch_shapes=[
            pltpu.VMEM((j * rows, depth), jnp.bfloat16),
            pltpu.VMEM((chunks, rows, keys), jnp.int32),
        ],
        name=KERNEL_NAME,
        interpret=interpret,
    )(*inputs)
