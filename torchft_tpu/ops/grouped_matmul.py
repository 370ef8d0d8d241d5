"""Grouped matrix product: rows sorted by group, one product over the groups.

``grouped_matmul(lhs, rhs, group_sizes)`` multiplies the first
``group_sizes[0]`` rows of ``lhs`` by ``rhs[0]``, the next ``group_sizes[1]``
by ``rhs[1]``, and so on. It is what an expert layer needs once its rows are
sorted by expert: no row is padded to a capacity and none is dropped.

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
``tgmm.<n>``, and where they are differentiated inside the ladder's backward
rule after those functions' differentiated forms (``jvp_jit_gmm__.<n>``,
``transpose_jvp_jit_tgmm___.<n>``); a ``jax.named_scope`` around them reaches
the ops' metadata and not those names. Every name holds ``gmm``: chipbench's
``expert_time_pct`` reads them by that.

``routed_experts`` is the expert layer around the product, from a router's
output to the tokens' sums (models/keye.py calls it with its router's):
gather the sorted rows, gate and up products, the activation, the down
product, each row weighted by its gate and added to its token's float32 sum.
It runs at a ROW COUNT chosen each call from ``group_sizes``. The held
experts' rows sort first, so the first ``C`` sorted rows hold every row this
chip computes whenever the held total is at most ``C``; the counts it may run
at are a short ladder (``dispatch_rungs``: twice and four times a uniform
router's share, then every token's every choice), the smallest that holds the
held total is taken (``lax.switch``), and the last always does: dropless and
exact on every rung, no capacity. A layer that holds half of the experts or
more has the one rung and no conditional. Nothing a rung sizes leaves its
conditional: the ladder is one differentiable unit whose residuals are its
arguments, with one switch in the forward rule and one in the backward rule,
each branch of the latter the ``jax.vjp`` of its own rung. A device trace
shows the rung in the row counts of the ops' result shapes.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.utils.platform import on_tpu

__all__ = ["grouped_matmul", "dispatch_rungs", "routed_experts"]


def _tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """(rows, contraction, columns) of one grid step. A group's first and last
    row tiles are shared with its neighbours and computed whole, so short row
    tiles waste less where a group is a few hundred rows; the other two are
    as wide as one block of VMEM takes (a 1024 x 1024 bf16 block is 2 MiB)."""
    return _row_tile(m), min(k, 1024), min(n, 1024)


def _row_tile(m: int) -> int:
    return next(t for t in (256, 128, 64, 32, 16, 8, m) if m % t == 0)


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


def dispatch_rungs(n: int, k: int, local: int, experts: int) -> Tuple[int, ...]:
    """The row counts ``routed_experts`` may run at for n tokens of k choices
    over ``experts``, ``local`` of them held: twice and four times the share
    a uniform router sends here, ``n * k * local / experts``, each rounded up
    to the grouped product's row tile, and last the worst case ``n * k``. A
    rung that is not under the worst case is dropped."""
    worst = n * k
    tile = _row_tile(worst)
    rungs = {-(-times * worst * local // (experts * tile)) * tile for times in (2, 4)}
    return tuple(sorted(rung for rung in rungs if rung < worst)) + (worst,)


def _experts_at(
    rows: int, activation: Callable, flat, order, gates, group_sizes, w_gate, w_up, w_down,
) -> jnp.ndarray:
    """The layer on the first ``rows`` sorted rows, which must hold every held
    expert's: (n, d) float32, each token's sum over its held choices."""
    n, k = gates.shape
    chosen = order[:rows]  # which (token, choice) sits in each sorted row
    token = chosen // k
    # The held groups alone: the rows past them, which fill the rung, are
    # nobody's, come out zero and add nothing to their tokens.
    product = partial(grouped_matmul, group_sizes=group_sizes[: w_gate.shape[0]])
    x = flat[token]  # (rows, d), sorted by held expert
    out = product(activation(product(x, w_gate)) * product(x, w_up), w_down)
    weighted = out.astype(jnp.float32) * gates.reshape(-1)[chosen][:, None]
    return jnp.zeros((n, flat.shape[1]), jnp.float32).at[token].add(weighted)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ladder(rungs, activation, taken, flat, order, gates, group_sizes, w_gate, w_up, w_down):
    """``_experts_at`` the rung of index ``taken``."""
    branches = [partial(_experts_at, rows, activation) for rows in rungs]
    return jax.lax.switch(taken, branches, flat, order, gates, group_sizes, w_gate, w_up, w_down)


def _ladder_fwd(rungs, activation, *operands):
    # The residuals are the arguments, which the layer has at any size.
    return _ladder(rungs, activation, *operands), operands


def _ladder_bwd(rungs, activation, operands, d_out):
    taken, flat, order, gates, group_sizes, *weights = operands

    def vjp_at(rows):
        def at(flat, gates, *weights):
            return _experts_at(rows, activation, flat, order, gates, group_sizes, *weights)

        return lambda d_out, *primals: jax.vjp(at, *primals)[1](d_out)

    d_flat, d_gates, *d_weights = jax.lax.switch(
        taken, [vjp_at(rows) for rows in rungs], d_out, flat, gates, *weights
    )
    return None, d_flat, None, d_gates, None, *d_weights


_ladder.defvjp(_ladder_fwd, _ladder_bwd)


def routed_experts(
    flat: jnp.ndarray,
    order: jnp.ndarray,
    gates: jnp.ndarray,
    group_sizes: jnp.ndarray,
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    *,
    num_experts: int,
    activation: Callable,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """What the held experts add to each token, and the row count it took.

    flat (n, d) rows in token order; order (n * k,) int32, the choice
    ``token * k + j`` in each row once the choices are sorted by held expert,
    those for experts held elsewhere last; gates (n, k) float32; group_sizes
    (held + 1,) rows by held expert and, last, the rows that belong elsewhere;
    w_gate, w_up (held, d, f) and w_down (held, f, d); ``num_experts`` the
    router's width. Returns ((n, d) float32, each token's gated sum over its
    held choices; () int32, the rung of ``dispatch_rungs`` the call ran at)."""
    n, k = gates.shape
    local = w_gate.shape[0]
    rungs = dispatch_rungs(n, k, local, num_experts)
    operands = (flat, order, gates, group_sizes, w_gate, w_up, w_down)
    if len(rungs) == 1:
        return _experts_at(rungs[0], activation, *operands), jnp.asarray(rungs[0], jnp.int32)
    # The smallest rung that holds the held experts' rows.
    held = jnp.sum(group_sizes[:local].astype(jnp.int32))
    taken = jnp.sum(held > jnp.asarray(rungs[:-1], jnp.int32)).astype(jnp.int32)
    return _ladder(rungs, activation, taken, *operands), jnp.asarray(rungs, jnp.int32)[taken]
