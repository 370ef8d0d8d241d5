"""Attention under a learned key selection: the selection itself, and the
attention in plain tiled XLA.

Each query attends to the ``topk`` earlier keys an indexer scores highest
(all of them while it has no more than ``topk``), one selection a query and
shared by every head:

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        s <= t
    S_t     = the topk largest I[t, s], ties to the earlier key
    out[t]  = softmax over S_t of (q[t] . k[s]) * scale, times v

The index scores and the threshold are computed in float32 at the highest
matmul precision whatever the model's dtype: a key that flips in or out of
``S_t`` is a discontinuity of the output and not a rounding of it. The
selection carries no gradient (top-k is piecewise constant).

Two paths, and :func:`selected_attention` says which runs where.

- ON THE CHIP the selection is an operand: :func:`select_keys` makes it once
  a layer step as one (b, s, s) int8 array, named ``SELECTION`` for a remat
  policy to keep, and ops/flash_attention.py's forward and its one backward
  call read it block by block (``flash_attention(..., selection=...)``):
  the attention scores never leave VMEM and the backward makes no selection
  again. The operand is made by ONE Mosaic call (ops/key_selection.py,
  ``key_selection.<n>`` in a trace; since PR 67): a block of 128 queries at a
  time it scores the keys the block may see and no others (the six float32
  products packed into three passes of the MXU's full depth), finds each
  row's threshold where the scores lie in VMEM and writes the int8 rows where
  the flash kernels read them; rows with no more than ``topk`` earlier keys
  are the causal mask and score nothing.
- ELSEWHERE, for a sequence that is not whole blocks of that call, and as the
  oracle of the kernels' tests, plain tiled XLA: :func:`sparse_attention`
  selects and then attends under the mask tile by tile, and
  :func:`select_keys` keeps the same tiles for the operand alone. The queries
  are walked in tiles of ``block``, one at a time (``lax.map``), in
  ``KEY_GROUPS`` groups that share a key length, so that a tile early in the
  sequence does not pay for the keys after it (with 4 groups the pairs
  computed are 1.25 times the causal half, with 1 group twice); a
  tile scores itself against the keys up to its group's last position and
  finds each row's threshold by a radix select over the scores' bits (32
  counting passes, exact, no sort). In :func:`sparse_attention` a tile is one
  ``jax.checkpoint``: the backward recomputes its scores and selection and
  keeps nothing of a tile but its inputs.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import AxisType

from torchft_tpu.ops import key_selection
from torchft_tpu.ops.attention import flash_under_mesh
from torchft_tpu.ops.flash_attention import FLASH_OUT
from torchft_tpu.utils.platform import on_tpu

__all__ = [
    "SELECTION", "index_scores", "select_keys", "select_topk", "selected_attention",
    "sparse_attention",
]

# checkpoint_name tag of the selection :func:`select_keys` returns: kept by
# name (models/keye.py's remat line), a layer's backward reads the
# operand its forward read and selects nothing again.
SELECTION = "key_selection"

_HIGHEST = jax.lax.Precision.HIGHEST
# Key lengths the tiles of a sequence share (fewer where it has fewer tiles).
KEY_GROUPS = 4


def index_scores(qi: jnp.ndarray, ki: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """qi (b, t, j, e), ki (b, s, e), w (b, t, j), all float32 ->
    I (b, t, s) = sum_j w * relu(qi . ki), float32 at the highest precision."""
    dots = jnp.einsum("btje,bse->btjs", qi, ki, precision=_HIGHEST)
    return jnp.sum(w[..., None] * jax.nn.relu(dots), axis=2)


def _ordered(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 that sorts as the floats do; every finite score maps
    above 0, which is left to the keys a query may not see."""
    x = jnp.where(x == 0, 0.0, x)  # -0.0 and 0.0 tie
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def select_topk(scores: jnp.ndarray, allowed: jnp.ndarray, topk: int) -> jnp.ndarray:
    """scores (..., s) float32, allowed (..., s) bool -> bool (..., s): per row
    the ``topk`` largest allowed scores (all of them where fewer are allowed);
    of equal scores the earlier position wins."""
    keys = jnp.where(allowed, _ordered(scores), jnp.uint32(0))
    want = jnp.minimum(jnp.sum(allowed, axis=-1), topk)

    def narrow(i, threshold):
        candidate = threshold | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= candidate[..., None], axis=-1) >= want
        return jnp.where(enough, candidate, threshold)

    # The largest value that `want` keys reach: the want-th largest key.
    threshold = jax.lax.fori_loop(0, 32, narrow, jnp.zeros(want.shape, jnp.uint32))
    above = keys > threshold[..., None]
    tied = keys == threshold[..., None]
    room = want - jnp.sum(above, axis=-1)
    return allowed & (above | (tied & (jnp.cumsum(tied, axis=-1) <= room[..., None])))


def _tile_selection(first, qi, w, ki, topk: int) -> jnp.ndarray:
    """(b, t, s) bool: the keys of ``0 .. s - 1`` that each query of one
    tile, positions ``first .. first + t - 1``, selects."""
    b, t, s = qi.shape[0], qi.shape[1], ki.shape[1]
    at = first + jnp.arange(t)
    causal = jnp.broadcast_to(at[:, None] >= jnp.arange(s)[None, :], (b, t, s))
    with jax.named_scope("tpuft::indexer"):
        return select_topk(index_scores(qi, ki, w), causal, topk)


def _tile(first, q, qi, w, k, v, ki, *, scale: float, topk: int, with_selection: bool):
    """One tile of queries, positions ``first .. first + t - 1``, against the
    keys ``0 .. s - 1``. q (b, t, h, d); k, v (b, s, kv, d). Returns the
    attention output (b, t, h, d) and, where asked for, the selection
    (b, t, s)."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    chosen = _tile_selection(first, qi, w, ki, topk)
    with jax.named_scope("tpuft::sparse_attention"):
        grouped = q.reshape(b, t, kv, h // kv, d)
        scores = jnp.einsum("btkgd,bskd->bkgts", grouped, k).astype(jnp.float32) * scale
        scores = jnp.where(chosen[:, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bkgts,bskd->btkgd", probs, v).reshape(b, t, h, d)
    return out, chosen if with_selection else None


def _tile_groups(tiles: int) -> List[Tuple[int, int]]:
    """``tiles`` query tiles in at most ``KEY_GROUPS`` runs ``(lo, hi)`` of as
    equal a length as whole tiles allow; the tiles of a run see the keys of
    tiles ``0 .. hi - 1``."""
    per_group = -(-tiles // min(KEY_GROUPS, tiles))
    return [(lo, min(lo + per_group, tiles)) for lo in range(0, tiles, per_group)]


def _tiled(x: jnp.ndarray, tiles: int) -> jnp.ndarray:
    """(b, tiles * block, ...) -> (tiles, b, block, ...)."""
    b = x.shape[0]
    return jnp.moveaxis(x.reshape(b, tiles, -1, *x.shape[2:]), 1, 0)


def _untiled(xs: List[jnp.ndarray]) -> jnp.ndarray:
    """The groups' (tiles, b, block, ...) -> (b, all their tiles * block, ...)."""
    x = jnp.moveaxis(jnp.concatenate(xs), 0, 1)
    return x.reshape(x.shape[0], -1, *x.shape[3:])


def _indexer_inputs(s: int, block: int, *xs: jnp.ndarray):
    """The tile (``block``, or the sequence where that is shorter), how many
    there are, and the indexer's arrays in float32 with no gradient."""
    block = min(block, s)
    if s % block:
        raise ValueError(f"a sequence of {s} positions is not whole tiles of {block}")
    return block, s // block, *_no_gradient(*xs)


def _no_gradient(*xs: jnp.ndarray):
    return tuple(jax.lax.stop_gradient(x.astype(jnp.float32)) for x in xs)


def _one_program() -> bool:
    """No axis of an ambient mesh is left to XLA's partitioner, which cannot
    split a Mosaic call (ops/attention.py ``flash_under_mesh``)."""
    mesh = jax.sharding.get_abstract_mesh()
    return all(kind == AxisType.Manual for kind in getattr(mesh, "axis_types", ()))


def select_keys(
    qi: jnp.ndarray, ki: jnp.ndarray, w: jnp.ndarray, *, topk: int, block: int = 512
) -> jnp.ndarray:
    """The indexer's qi (b, s, j, e), ki (b, s, e), w (b, s, j) -> the
    selection as an array, (b, s, s) int8: 1 where query t attends to key s,
    never a later key, under the name ``SELECTION``. On a TPU ONE Mosaic call
    (ops/key_selection.py) where the sequence is whole blocks of it and no
    mesh would have XLA partition it; elsewhere, and for any other shape,
    :func:`sparse_attention`'s own arithmetic tile by tile. Nothing is
    checkpointed (no gradient passes: the inputs stop it)."""
    s, e = qi.shape[1], qi.shape[3]
    if on_tpu() and key_selection.fits(s, e) and _one_program():
        with jax.named_scope("tpuft::indexer"):
            chosen = key_selection.key_selection(*_no_gradient(qi, ki, w), topk=topk)
    else:
        chosen = _select_keys_tiled(qi, ki, w, topk=topk, block=block)
    return checkpoint_name(chosen, SELECTION)


def _select_keys_tiled(qi, ki, w, *, topk: int, block: int) -> jnp.ndarray:
    """:func:`select_keys` in plain XLA: a tile's selection by
    :func:`_tile_selection`, the int8 tiles padded to the sequence and laid
    into one array."""
    s = qi.shape[1]
    block, tiles, qi, ki, w = _indexer_inputs(s, block, qi, ki, w)
    rows = (jnp.arange(0, s, block), _tiled(qi, tiles), _tiled(w, tiles))
    chosen = []
    for lo, hi in _tile_groups(tiles):
        keys = ki[:, : hi * block]  # no tile of this group sees a later key
        sel = jax.lax.map(
            lambda args: _tile_selection(*args, keys, topk).astype(jnp.int8),
            tuple(x[lo:hi] for x in rows),
        )
        chosen.append(jnp.pad(sel, ((0, 0), (0, 0), (0, 0), (0, s - hi * block))))
    return _untiled(chosen)


def sparse_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    qi: jnp.ndarray, ki: jnp.ndarray, w: jnp.ndarray,
    *, topk: int, scale: float, block: int = 512, return_selection: bool = False,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """q (b, s, h, d); k, v (b, s, kv, d), positions encoded; the indexer's
    qi (b, s, j, e), ki (b, s, e), w (b, s, j) in float32. Returns (out
    (b, s, h, d), selection (b, s, s) bool or None). ``qi``, ``ki`` and ``w``
    get no gradient."""
    s = q.shape[1]
    block, tiles, qi, ki, w = _indexer_inputs(s, block, qi, ki, w)
    rows = (jnp.arange(0, s, block), *(_tiled(x, tiles) for x in (q, qi, w)))
    outs, chosen = [], []
    for lo, hi in _tile_groups(tiles):
        width = hi * block  # no tile of this group sees a later key
        one = jax.checkpoint(
            partial(_tile, scale=scale, topk=topk, with_selection=return_selection),
            prevent_cse=False,
        )
        keys = (k[:, :width], v[:, :width], ki[:, :width])
        out, sel = jax.lax.map(
            lambda args: one(*args, *keys), tuple(x[lo:hi] for x in rows)
        )
        outs.append(out)
        if return_selection:
            chosen.append(jnp.pad(sel, ((0, 0), (0, 0), (0, 0), (0, s - width))))
    return _untiled(outs), _untiled(chosen) if return_selection else None


def selected_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    qi: jnp.ndarray, ki: jnp.ndarray, w: jnp.ndarray,
    *, topk: int, scale: float, block: int = 512, return_selection: bool = False,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """:func:`sparse_attention`'s arguments and results by the path of the
    platform. On a TPU the selection once, as the flash kernels' operand (they
    name their own residuals; what a watcher is shown is the operand itself,
    int8, asked for or not); elsewhere the tiled path, its output kept under
    ``remat="dots"`` by the name the flash kernel's output has: the layer's
    backward then recomputes a tile once, not twice."""
    if on_tpu():
        chosen = select_keys(qi, ki, w, topk=topk, block=block)
        return flash_under_mesh(q, k, v, scale=scale, selection=chosen), chosen
    out, chosen = sparse_attention(
        q, k, v, qi, ki, w, topk=topk, scale=scale, block=block,
        return_selection=return_selection,
    )
    return checkpoint_name(out, FLASH_OUT), chosen
