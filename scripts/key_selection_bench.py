#!/usr/bin/env python
"""On-chip time of the Keye cell's key selection alone, part by part.

At the cell ``keye-vl2-30b-a3b-1chip.ftddp-seq8k``'s shapes (1 x 8192, 16
indexer heads of 64, top-2048, float32, random normal inputs), one layer:

- ``scores_highest`` / ``scores_packed``: ``index_scores`` of ONE tile of 512
  queries against 8192 keys, the einsum at ``Precision.HIGHEST`` against the
  packed three-pass form in plain XLA (one einsum over
  ``ops.key_selection.packed_parts``: what the call multiplies);
- ``threshold``: ``select_topk`` alone on a ``(512, 8192)`` tile;
- ``xla_path``: the whole selection by the tiled XLA path (what
  ``select_keys`` ran on a TPU before PR 67 and runs where the call does not fit);
- ``kernel``: ``ops.key_selection.key_selection`` at its defaults, with the
  pairs on which its selection differs from the XLA path's; at chunks of 1024
  keys; with half the heads (half the products under the same threshold: the
  scores' part of a call is twice the difference); with no query selecting
  (the causal rows written, nothing else); with two counting passes where the
  threshold makes 32 (``kernel passes=2``: a pass is a 30th of the difference
  to the whole); and ``kernel operands``, the two transposes in XLA that lay
  out what the call reads.

A time is the host's clock over ``CALLS`` calls closed by one fetch, the median
of three, in ms a call. A chip script: it needs a TPU and exits non-zero
without one. One JSON line a row on stdout.

Usage: python scripts/key_selection_bench.py [SEED]
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

SEQ, HEADS, WIDTH, TOPK, TILE = 8192, 16, 64, 2048, 512
CALLS, WARMUP = 10, 2


def timed(fn, *args) -> float:
    """Median of three of the ms a call over ``CALLS`` calls."""
    import jax

    for _ in range(WARMUP):
        jax.block_until_ready(fn(*args))
    readings = []
    for _ in range(3):
        start = time.perf_counter()
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
        readings.append((time.perf_counter() - start) / CALLS * 1e3)
    return sorted(readings)[1]


def main() -> None:
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops import key_selection as ks
    from torchft_tpu.ops import sparse_attention as sa
    from torchft_tpu.utils.platform import require_tpu

    device = require_tpu()
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 0
    a, b, c = jax.random.split(jax.random.PRNGKey(seed), 3)
    qi = jax.random.normal(a, (1, SEQ, HEADS, WIDTH))
    ki = jax.random.normal(b, (1, SEQ, WIDTH))
    w = jax.random.normal(c, (1, SEQ, HEADS))
    tile = slice(SEQ - TILE, SEQ)

    def row(name, **values):
        print(json.dumps({"what": name, "device": device.device_kind, "seed": seed, **values}), flush=True)

    @jax.jit
    def packed(qi, ki, w):
        q, k = ks.packed_parts(qi, ki)
        dots = jnp.einsum("btjc,bsc->btjs", q, k, preferred_element_type=jnp.float32)
        return jnp.sum(w[..., None] * jax.nn.relu(dots), axis=2)

    highest = jax.jit(sa.index_scores)
    row("scores_highest", ms=timed(highest, qi[:, tile], ki, w[:, tile]))
    row("scores_packed", ms=timed(packed, qi[:, tile], ki, w[:, tile]),
        worst_difference=float(jnp.max(jnp.abs(
            packed(qi[:, tile], ki, w[:, tile]) - highest(qi[:, tile], ki, w[:, tile])))))
    scores = highest(qi[:, tile], ki, w[:, tile])
    causal = (SEQ - TILE + jnp.arange(TILE))[None, :, None] >= jnp.arange(SEQ)[None, None, :]
    row("threshold", ms=timed(jax.jit(lambda s, m: sa.select_topk(s, m, TOPK)), scores, causal))

    xla = jax.jit(lambda qi, ki, w: sa._select_keys_tiled(qi, ki, w, topk=TOPK, block=TILE))
    want = xla(qi, ki, w)
    row("xla_path", ms=timed(xla, qi, ki, w))
    def kernel(name, qi, w, topk=TOPK, keys=ks.KEYS):
        call = jax.jit(lambda qi, ki, w: ks.key_selection(qi, ki, w, topk=topk, keys=keys))
        values = {"ms": timed(call, qi, ki, w)}
        if qi.shape[2] == HEADS and topk == TOPK and "passes" not in name:
            values["pairs_that_differ"] = int(jnp.sum(call(qi, ki, w) != want))
            values["pairs_selected"] = int(jnp.sum(want))
        row(name, **values)

    kernel("kernel", qi, w)
    kernel("kernel keys=1024", qi, w, keys=1024)
    # Half the heads is half the products and the same threshold: twice the
    # difference to the whole is the scores' part of a call.
    kernel("kernel heads=8", qi[:, :, :8], w[:, :, :8])
    # No query selects: the causal rows written and nothing else.
    kernel("kernel topk=seq", qi, w, topk=SEQ)
    # ONE narrowing pass of the 32 and none of the tie search's 13 (a wrong
    # selection, timed only): two counting passes where a call makes 32, so a
    # pass is a 30th of the difference to the whole, and what is left is
    # scores, writes and operands.
    loop = jax.lax.fori_loop
    jax.lax.fori_loop = lambda lo, hi, *rest: loop(lo, {32: 1, 13: 0}.get(hi, hi) if isinstance(hi, int) else hi, *rest)
    try:
        kernel("kernel passes=2", qi, w)
    finally:
        jax.lax.fori_loop = loop
    row("kernel operands", ms=timed(jax.jit(lambda qi, ki: ks._operands(qi, ki, ks.ROWS)), qi, ki))


if __name__ == "__main__":
    main()
