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

``rows_of(flat, token)`` and ``sum_by_token(rows, weights, token, n)`` carry
rows between token order and any other: the first is the gather
``flat[token]``, the second each token's float32 sum of ``weights[r] *
rows[r]`` over the rows that name it, and each is the other's transpose, so
each is the other's backward rule (with unit weights, rounded once to the
rows' dtype; the sum's own rule is a gather of the cotangent, times the weight
for the rows and dotted with the row for the weights, 16,384 rows at a time so
that the gather's float32 rows are never a whole rung's). On a TPU the sum is one
XLA gather of the rows INTO token order, where a block of tokens owns one
contiguous run of rows, and one Mosaic call, ``sum_by_token.<n>`` in a device
trace (no ``gmm`` in it): it walks blocks of 128 tokens, visits the aligned
256-row tiles that hold a block's rows by tables that ride in as scalar
prefetch, and adds a tile by ONE product on the MXU with the (tokens, rows)
matrix that holds a row's weight where the row is the token's: three bf16
terms of the float32 weight against bf16 rows, every product exact, float32
accumulation in VMEM, each output block written once. XLA's scatter-add, which
both sums were, permutes the rows into a second float32 buffer first and runs
at a tenth of HBM's rate; elsewhere than a TPU the sum is that ``.at[token]
.add`` still.

``routed_experts`` is the expert layer around the product, from a router's
output to the tokens' sums (models/keye.py calls it with its router's):
``rows_of`` the sorted rows, gate and up products, the activation, the down
product, ``sum_by_token`` of its rows with the gates as weights.
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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.utils.platform import on_tpu

__all__ = ["grouped_matmul", "dispatch_rungs", "routed_experts", "rows_of", "sum_by_token"]


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


# What a grid step of the sum by token is to its block of tokens.
_FIRST, _LAST, _VALID = 1, 2, 4


def _token_block(n: int) -> int:
    return next(t for t in (128, 64, 32, 16, 8, n) if n % t == 0)


def _column_block(d: int) -> int:
    return next((t for t in (2048, 1024, 512, 256, 128) if d % t == 0), d)


def _visits(token: jnp.ndarray, n: int, token_block: int, row_tile: int):
    """The (token block, row tile) pairs the kernel walks, for ``token``
    ascending: a block visits the aligned tiles that hold its rows, and a
    block with no row one tile (which adds nothing: none of its rows names a
    token of the block), so every block is written. Three (blocks + tiles,)
    int32 tables: the block, the tile, and ``_FIRST | _LAST | _VALID``; the
    steps past the last visit repeat it with no flag and copy nothing."""
    blocks, tiles = n // token_block, token.shape[0] // row_tile
    edges = jnp.arange(blocks + 1, dtype=jnp.int32) * token_block
    start = jnp.searchsorted(token, edges, method="compare_all").astype(jnp.int32)
    lo = jnp.minimum(start[:-1] // row_tile, tiles - 1)
    hi = jnp.maximum((start[1:] - 1) // row_tile, lo)
    end = jnp.cumsum(hi - lo + 1, dtype=jnp.int32)
    first = end - (hi - lo + 1)
    step = jnp.arange(blocks + tiles, dtype=jnp.int32)
    block = jnp.minimum(
        jnp.searchsorted(end, step, side="right", method="compare_all").astype(jnp.int32),
        blocks - 1,
    )
    valid = step < end[-1]
    tile = jnp.where(valid, lo[block] + step - first[block], hi[-1])
    flags = (_FIRST * (step == first[block]) + _LAST * (step == end[block] - 1) + _VALID) * valid
    return block, tile, flags.astype(jnp.int32)


def _token_sum_kernel(weighted, block_ref, tile_ref, flags_ref, token_ref, *refs):
    """One visit: the tile's rows that name a token of the block, each times
    its weight, added to the token's float32 sum. The sum is a product on the
    MXU with a (tokens, rows) matrix that holds a row's weight where the row is
    the token's and zero elsewhere. Against bf16 rows a float32 weight goes as
    three bf16 terms (8 + 8 + 8 bits: the whole of it) and a unit weight as
    one, so every product is exact and only the float32 additions round;
    float32 rows take the MXU's float32 passes."""
    del tile_ref
    weight_ref = refs[0] if weighted else None
    rows_ref, out_ref, acc_ref = refs[-3:]
    step = pl.program_id(1)
    flags = flags_ref[step]
    tokens = acc_ref.shape[0]

    @pl.when(flags & _FIRST != 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(flags & _VALID != 0)
    def _():
        at = token_ref[...] - block_ref[step] * tokens  # (1, rows)
        mine = at == jax.lax.broadcasted_iota(jnp.int32, (tokens, at.shape[1]), 0)
        weight = jnp.where(mine, weight_ref[...] if weighted else 1.0, 0.0)
        rows = rows_ref[...]
        if rows.dtype != jnp.bfloat16:
            acc_ref[...] += jnp.dot(
                weight, rows.astype(jnp.float32),
                preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST,
            )
            return
        terms = []
        for _ in range(3 if weighted else 1):
            terms.append(weight.astype(jnp.bfloat16))
            weight = weight - terms[-1].astype(jnp.float32)
        product = jnp.dot(
            jnp.concatenate(terms, axis=0), rows, preferred_element_type=jnp.float32
        )
        parts = [product[i * tokens : (i + 1) * tokens] for i in range(len(terms))]
        acc_ref[...] += sum(reversed(parts))  # the smallest term first

    @pl.when(flags & _LAST != 0)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _token_sum_pallas(rows, weights, token, n, dtype, interpret=False):
    """``_token_sum`` on a TPU: the rows to token order by one XLA gather, then
    one Mosaic call over the visits of ``_visits``, each output block written
    once. ``weights`` None is a weight of one a row."""
    count, d = rows.shape
    row_tile, token_block, columns = _row_tile(count), _token_block(n), _column_block(d)
    weighted = weights is not None
    # The weights ride in the sort: a gather of 16,384 scalars costs ten sorts.
    token, by_token, *weights = jax.lax.sort(
        (token, jnp.arange(count, dtype=jnp.int32), *([weights] if weighted else [])), num_keys=1
    )
    tables = _visits(token, n, token_block, row_tile)
    by_tile = pl.BlockSpec((None, 1, row_tile), lambda j, v, block, tile, flags: (tile[v], 0, 0))
    return pl.pallas_call(
        partial(_token_sum_kernel, weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(d // columns, tables[0].shape[0]),
            in_specs=[by_tile] * (1 + weighted) + [
                pl.BlockSpec((row_tile, columns), lambda j, v, block, tile, flags: (tile[v], j)),
            ],
            out_specs=pl.BlockSpec(
                (token_block, columns), lambda j, v, block, tile, flags: (block[v], j)
            ),
            scratch_shapes=[pltpu.VMEM((token_block, columns), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n, d), dtype),
        name="sum_by_token",
        interpret=interpret,
    )(*tables, *(a.reshape(-1, 1, row_tile) for a in (token, *weights)), rows[by_token])


def _token_sum(rows, weights, token, n, dtype):
    """(n, d) in ``dtype``: each token's float32 sum of ``weights[r] * rows[r]``
    over the rows r with ``token[r]`` its index, rounded once."""
    if on_tpu():
        return _token_sum_pallas(rows, weights, token, n, dtype)
    rows = rows.astype(jnp.float32)
    if weights is not None:
        rows = rows * weights[:, None]
    return jnp.zeros((n, rows.shape[1]), jnp.float32).at[token].add(rows).astype(dtype)


@jax.custom_vjp
def rows_of(flat: jnp.ndarray, token: jnp.ndarray) -> jnp.ndarray:
    """``flat[token]``: (rows, d), the token's row of ``flat`` (n, d) in each
    row. Its transpose is ``sum_by_token`` with unit weights, and that is its
    backward rule, rounded once to ``flat.dtype``."""
    return flat[token]


def _rows_of_fwd(flat, token):
    return flat[token], (flat, token)  # flat for its shape and dtype alone


def _rows_of_bwd(residuals, d_rows):
    flat, token = residuals
    return _token_sum(d_rows, None, token, flat.shape[0], flat.dtype), None


rows_of.defvjp(_rows_of_fwd, _rows_of_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def sum_by_token(rows: jnp.ndarray, weights: jnp.ndarray, token: jnp.ndarray, n: int):
    """(n, d) float32: each token's sum of ``weights[r] * rows[r]`` (float32
    products, float32 sums) over the rows r with ``token[r]`` its index; a
    token no row names is zero. On a TPU one gather of the rows into token
    order and one Mosaic call (``sum_by_token`` in a device trace), elsewhere
    ``.at[token].add``. The transpose of ``rows_of`` in ``rows``; its backward
    rule is a gather of the cotangent by token, times the weight for the rows
    and dotted with the row for the weights, 16,384 rows at a time."""
    return _token_sum(rows, weights, token, n, jnp.float32)


def _sum_by_token_fwd(rows, weights, token, n):
    return _token_sum(rows, weights, token, n, jnp.float32), (rows, weights, token)


def _sum_by_token_bwd(n, residuals, d_out):
    rows, weights, token = residuals
    count = rows.shape[0]
    chunk = next(c for c in (16384, 4096, 1024, 256, count) if count % c == 0)

    # A chunk of rows at a time: XLA fuses the float32 gather of the cotangent
    # into neither of its uses, and a whole rung's (512 MiB at the worst case)
    # would be the largest temporary of a step.
    def of_chunk(operands):
        rows, weights, token = operands
        d_weighted = d_out[token]
        d_rows = (d_weighted * weights[:, None]).astype(rows.dtype)
        return d_rows, jnp.sum(d_weighted * rows.astype(jnp.float32), axis=1)

    d_rows, d_weights = jax.lax.map(
        of_chunk, tuple(a.reshape(-1, chunk, *a.shape[1:]) for a in residuals)
    )
    return d_rows.reshape(rows.shape), d_weights.reshape(count).astype(weights.dtype), None


sum_by_token.defvjp(_sum_by_token_fwd, _sum_by_token_bwd)


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
    x = rows_of(flat, token)  # (rows, d), sorted by held expert
    out = product(activation(product(x, w_gate)) * product(x, w_up), w_down)
    return sum_by_token(out, gates.reshape(-1)[chosen], token, n)


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
