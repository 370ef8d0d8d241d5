"""Grouped matrix product: rows sorted by group, one product over the groups.

``grouped_matmul(lhs, rhs, group_sizes)`` multiplies the first
``group_sizes[0]`` rows of ``lhs`` by ``rhs[0]``, the next ``group_sizes[1]``
by ``rhs[1]``, and so on. It is what an expert layer needs once its rows are
sorted by expert (models/keye.py): no row is padded to a capacity and none is
dropped.

``group_sizes`` may name MORE groups than ``rhs`` holds: the groups past
``rhs.shape[0]`` are rows that belong elsewhere (experts another chip holds).
They, and every row past the last group, come out zero and cost no product:
the caller sorts its own rows first and never slices to a data-dependent
length.

On a TPU this is ``jax.experimental.pallas.ops.tpu.megablox.gmm`` (Mosaic
kernels ``gmm`` forward and for the gradient of ``lhs``, ``tgmm`` for the
gradient of ``rhs``, under its own ``custom_vjp``), whose grid holds only the
row tiles of the groups it is given; elsewhere ``lax.ragged_dot``. A device
trace names the Mosaic calls after megablox's jitted functions, ``gmm.<n>`` and
``tgmm.<n>`` (a ``jax.named_scope`` around them reaches the ops' metadata and
not those names); chipbench's ``expert_time_pct`` reads them by that.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.utils.platform import on_tpu

__all__ = ["grouped_matmul"]


def _tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """(rows, contraction, columns) of one grid step. A group's first and last
    row tiles are shared with its neighbours and computed whole, so short row
    tiles waste less where a group is a few hundred rows; the other two are
    as wide as one block of VMEM takes (a 1024 x 1024 bf16 block is 2 MiB)."""
    tm = next(t for t in (256, 128, 64, 32, 16, 8, m) if m % t == 0)
    return tm, min(k, 1024), min(n, 1024)


def grouped_matmul(
    lhs: jnp.ndarray,
    rhs: jnp.ndarray,
    group_sizes: jnp.ndarray,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """lhs (m, k) sorted by group; rhs (groups held, k, n); group_sizes
    (groups named,) int32 with ``groups named >= groups held``. Returns (m, n)
    in ``lhs.dtype``, accumulated in float32. ``use_pallas=None`` picks the
    Mosaic kernels on a TPU."""
    held, named = rhs.shape[0], group_sizes.shape[0]
    if named < held:
        raise ValueError(f"{named} group sizes for {held} groups of rhs")
    if use_pallas is None:
        use_pallas = on_tpu()
    if not use_pallas:
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes[:held].astype(jnp.int32),
            preferred_element_type=lhs.dtype,
        )
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = lhs.shape
    group_sizes = group_sizes.astype(jnp.int32)
    if named == held:  # the rows past the last group are a group of nobody's
        group_sizes = jnp.append(group_sizes, m - jnp.sum(group_sizes))
    # ``group_offset`` 0 with fewer groups in rhs than are named is the kernel's
    # own "rhs is a shard" case: it visits the held groups' tiles only and
    # zeroes every other row of the result (and of lhs's gradient).
    return gmm(
        lhs, rhs, group_sizes, lhs.dtype, _tiling(m, k, rhs.shape[2]),
        jnp.zeros((), jnp.int32), None, False, interpret,
    )
