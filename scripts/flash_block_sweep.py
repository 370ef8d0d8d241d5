#!/usr/bin/env python
"""On-chip block-size sweep for the Pallas flash-attention kernels.

The kernels take ``block_q``/``block_k`` at every entry point, so tuning is
a pure measurement problem — no kernel edits. At seq 2-8k larger blocks
than 128x128 amortize per-grid-step
overhead (mask compare, accumulator correction, block copies) and keep the
MXU busy longer per VMEM residency. VMEM bound: the f32 scores tile is
block_q x block_k x 4 B — 512x1024 is 2 MB, well inside the ~16 MB budget
even double-buffered.

Timing matches benchmarks/kernel_bench.py: data-chained iterations closed
by a value fetch, median of 3.

Usage: python scripts/flash_block_sweep.py [seq ...]   (default 2048 8192)
Prints one JSON line per (seq, block_q, block_k): fwd ms + fwd/bwd ms.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from torchft_tpu.utils.platform import require_tpu

require_tpu()  # a chip script: exits non-zero when jax answers on anything else

import jax
import jax.numpy as jnp

ITERS = 6
WARMUP = 2


def _force(x):
    leaf = jax.tree_util.tree_leaves(x)[0]
    float(jnp.asarray(leaf).reshape(-1)[0])


def _timed(fn, *args, fetch=None):
    out = None
    for _ in range(WARMUP):
        out = fn(*args)
    _force(out if fetch is None else fetch(out))
    times = []
    for _ in range(3):
        t0 = time.monotonic()
        cur = args
        for _ in range(ITERS):
            out = fn(*cur)
            first = jax.tree_util.tree_leaves(out)[0]
            if hasattr(cur[0], "shape") and first.shape == cur[0].shape:
                cur = (first.astype(cur[0].dtype),) + tuple(cur[1:])
        _force(out if fetch is None else fetch(out))
        times.append((time.monotonic() - t0) / ITERS)
    return sorted(times)[1]


def main() -> None:
    from torchft_tpu.ops.flash_attention import flash_attention

    seqs = [int(a) for a in sys.argv[1:]] or [2048, 8192]
    b, h, kv, d = 4, 8, 4, 128
    for s in seqs:
        kq, kk, kvk = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
        k = jax.random.normal(kk, (b, s, kv, d), jnp.bfloat16)
        v = jax.random.normal(kvk, (b, s, kv, d), jnp.bfloat16)
        r = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d), jnp.float32)
        for bq in (128, 256, 512):
            for bk in (128, 256, 512, 1024):
                if bk > s or bq > s:
                    continue

                def fwd(q, k, v, _bq=bq, _bk=bk):
                    return flash_attention(
                        q, k, v, block_q=_bq, block_k=_bk, interpret=False
                    )

                def loss(q, k, v, r, _bq=bq, _bk=bk):
                    return jnp.vdot(
                        flash_attention(
                            q, k, v, block_q=_bq, block_k=_bk, interpret=False
                        ).astype(jnp.float32),
                        r,
                    )

                try:
                    t_f = _timed(jax.jit(fwd), q, k, v)
                    t_g = _timed(
                        jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                        q, k, v, r,
                        fetch=lambda g: g[0],
                    )
                except Exception as e:
                    print(
                        json.dumps(
                            {
                                "seq": s, "block_q": bq, "block_k": bk,
                                "error": str(e).splitlines()[0][:160],
                            }
                        ),
                        flush=True,
                    )
                    continue
                print(
                    json.dumps(
                        {
                            "seq": s, "block_q": bq, "block_k": bk,
                            "fwd_ms": round(1e3 * t_f, 3),
                            "fwd_bwd_ms": round(1e3 * t_g, 3),
                        }
                    ),
                    flush=True,
                )


if __name__ == "__main__":
    main()
