#!/usr/bin/env python
"""On-chip time of the expert layer's dispatch alone, rung by rung.

``ops.grouped_matmul.routed_experts`` is the expert layer after its router:
gather the routed rows, three grouped products, gate, sum by token. It runs at
one of a short ladder of row counts (``dispatch_rungs``). This script times
that function by itself at the benchmark cell's shapes (8,192 tokens of 2,048,
top-8 of 128 experts, 16 held, 768 wide, bf16), forward and backward once a
dispatch, at EACH rung that holds the routing's rows, and the ladder as the
model calls it, under two routings:

- ``uniform``: a random router, about 8,192 rows for the sixteen held experts;
- ``collapsed``: every token's first two choices are held experts 0 and 1, so
  16,384 rows in two groups, the first rung full to its last row.

A time is the host's clock over ``DISPATCHES`` dispatches closed by one value
fetch (the device runs them in order), the median of three. It is the layer's
floor: what a step of the cell pays a layer beside attention and the rest.

A chip script: it needs a TPU and exits non-zero without one. Output: one
JSON line a (routing, rung) on stdout, the table in
chiprun_out/EXPERT_DISPATCH_BENCH.json.

Usage: python scripts/expert_dispatch_bench.py
"""

from __future__ import annotations

import json
import sys
import time
from functools import partial
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

OUT = REPO / "chiprun_out" / "EXPERT_DISPATCH_BENCH.json"
TOKENS, DIM, HIDDEN = 8192, 2048, 768
EXPERTS, CHOICES, HELD = 128, 8, 16
DISPATCHES = 10
WARMUP = 2


def routing(name: str, rng):
    """(order, gates, group_sizes) as ``models.experts.route`` gives them."""
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.models.experts import route

    logits = rng.standard_normal((TOKENS, EXPERTS)).astype(np.float32)
    if name == "collapsed":
        logits[:, :2] += 10.0
        logits[:, 2:HELD] -= 20.0
    probs = jnp.exp(jnp.asarray(logits) - jnp.max(jnp.asarray(logits), axis=1, keepdims=True))
    return route(probs / jnp.sum(probs, axis=1, keepdims=True), CHOICES, HELD, 0)


def main() -> None:
    from torchft_tpu.utils.platform import require_tpu

    dev = require_tpu()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.ops import grouped_matmul as grouped

    rng = np.random.default_rng(0)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape) * shape[-2] ** -0.5, jnp.bfloat16)
    flat = jnp.asarray(rng.standard_normal((TOKENS, DIM)), jnp.bfloat16)
    weights = normal(HELD, DIM, HIDDEN), normal(HELD, DIM, HIDDEN), normal(HELD, HIDDEN, DIM)
    cotangent = jnp.asarray(rng.standard_normal((TOKENS, DIM)), jnp.float32)
    rungs = grouped.dispatch_rungs(TOKENS, CHOICES, HELD, EXPERTS)

    def timed(layer, order, gates, group_sizes):
        """Seconds a dispatch of ``layer`` forward and backward."""

        def loss(flat, gates, *weights):
            return jnp.sum(layer(flat, order, gates, group_sizes, *weights) * cotangent)

        step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))
        for _ in range(WARMUP):
            out = step(flat, gates, *weights)
        float(out[0])
        times = []
        for _ in range(3):
            t0 = time.monotonic()
            for _ in range(DISPATCHES):
                out = step(flat, gates, *weights)
            float(out[0])
            times.append((time.monotonic() - t0) / DISPATCHES)
        return sorted(times)[1]

    ladder = lambda *operands: grouped.routed_experts(
        *operands, num_experts=EXPERTS, activation=jax.nn.silu
    )[0]
    table = []
    for name in ("uniform", "collapsed"):
        order, gates, group_sizes = routing(name, rng)
        held = int(jnp.sum(group_sizes[:HELD]))
        layers = [("ladder", ladder)] + [
            (rung, partial(grouped._experts_at, rung, jax.nn.silu))
            for rung in rungs if rung >= held
        ]
        for rung, layer in layers:
            row = {
                "routing": name, "held_rows": held, "rung": rung,
                "ms": round(1e3 * timed(layer, order, gates, group_sizes), 4),
            }
            table.append(row)
            print(json.dumps(row), flush=True)
    OUT.parent.mkdir(exist_ok=True)
    OUT.write_text(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "shapes": {"tokens": TOKENS, "dim": DIM, "hidden": HIDDEN, "experts": EXPERTS,
                   "choices": CHOICES, "held": HELD, "dtype": "bfloat16"},
        "dispatches": DISPATCHES, "rows": table,
    }, indent=1))


if __name__ == "__main__":
    main()
