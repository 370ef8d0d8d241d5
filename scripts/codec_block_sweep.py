#!/usr/bin/env python
"""On-chip tile sweep for the Pallas wire-codec kernels (ROADMAP 2a).

The codec kernels are a streaming pass over HBM, so their currency is
bytes moved per second against the chip's HBM bandwidth; their speed on
the v5e is not measured yet. This sweep treats the codec's one free
parameter, the grid tile height (``rows_per_tile``: rows of 256-element
blocks per grid step), as a measurement problem, in both directions
(quantize + dequantize) and both 8-bit formats.

A chip script: it needs a TPU and exits non-zero without one.

The leaf-layout kernels (a leaf read and written as it lies, its blocks
straight into the fragment's payload) have two: the tile of a grid step,
``tile_rows`` x ``chunk_segments`` blocks; they are swept at the benchmark
cell's leaf shapes, quantize reading two bf16 operands.

Output: one JSON line per (wire, direction, rows_per_tile), and per (leaf
shape, direction, tile_rows, chunk_segments), on stdout and
the full table to chiprun_out/CODEC_BLOCK_SWEEP.json, each row carrying
``gbps`` (bytes READ+WRITTEN per second — the roofline currency) and
``hbm_fraction`` = gbps / the chip's ~819 GB/s HBM. If no tile reaches
the >=100 GB/s bar the artifact IS the roofline: the best row names the
measured floor.

Usage: python scripts/codec_block_sweep.py [total_mb]   (default 256)
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

OUT = REPO / "chiprun_out" / "CODEC_BLOCK_SWEEP.json"
# v5e HBM bandwidth (819 GB/s nominal); the denominator of hbm_fraction.
HBM_GBPS = 819.0
TILE_CANDIDATES = (256, 512, 1024, 2048, 4096, 8192)
# The leaf-layout kernels: the cell's widest leaves, and tiles up to 2M values.
LEAF_SHAPES = ((2, 4096, 14336), (32768, 4096), (4096, 32768), (2, 32, 128, 4096))
LEAF_TILE_ROWS = (32, 64, 128, 256, 512, 1024, 2048)
LEAF_CHUNK_SEGMENTS = (1, 2, 4, 8, 16)
LEAF_MAX_TILE_VALUES = 2 * 1024 * 1024
ITERS = 8
WARMUP = 2


def main() -> None:
    from torchft_tpu.utils.platform import require_tpu

    dev = require_tpu()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.ops import quantization as q

    total_mb = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    n_blocks = total_mb * (1 << 20) // (4 * q.BLOCK)
    rng = np.random.default_rng(0)
    host = rng.normal(0, 2.0, (n_blocks, q.BLOCK)).astype(np.float32)
    x = jnp.asarray(host)

    def timed(fn, *args):
        # Each window is closed by a value fetch of the last output (the
        # device runs dispatched programs in order); median of 3 runs of
        # ITERS dispatches.
        out = None
        for _ in range(WARMUP):
            out = fn(*args)
        float(jnp.asarray(jax.tree_util.tree_leaves(out)[0]).reshape(-1)[0])
        times = []
        for _ in range(3):
            t0 = time.monotonic()
            for _ in range(ITERS):
                out = fn(*args)
            float(jnp.asarray(jax.tree_util.tree_leaves(out)[0]).reshape(-1)[0])
            times.append((time.monotonic() - t0) / ITERS)
        return sorted(times)[1]

    rows = []
    best = {"gbps": 0.0}
    for wire in ("fp8", "int8"):
        # Moved bytes per pass: quantize reads 4B/elem + writes 1B/elem
        # (+scales); dequantize the reverse. The roofline currency is
        # read+written bytes.
        q_bytes = host.nbytes + n_blocks * (q.BLOCK + 4)
        payload0, scales0 = jax.jit(
            lambda v, w=wire: q.quantize_blocks_pallas(v, wire=w)
        )(x)
        d_bytes = (
            int(np.prod(payload0.shape)) + n_blocks * 4 + host.nbytes
        )
        for rows_per_tile in TILE_CANDIDATES:
            if rows_per_tile > n_blocks:
                continue
            try:
                t_q = timed(
                    jax.jit(
                        lambda v, w=wire, r=rows_per_tile: q.quantize_blocks_pallas(
                            v, wire=w, rows_per_tile=r
                        )
                    ),
                    x,
                )
                t_d = timed(
                    jax.jit(
                        lambda p, s, r=rows_per_tile: q.dequantize_blocks_pallas(
                            p, s, rows_per_tile=r
                        )
                    ),
                    payload0,
                    scales0,
                )
            except Exception as e:  # noqa: BLE001 — a failing tile is data
                rows.append(
                    {"wire": wire, "rows_per_tile": rows_per_tile,
                     "error": f"{type(e).__name__}: {e}"[:200]}
                )
                print(json.dumps(rows[-1]))
                continue
            for direction, dt, moved in (
                ("quantize", t_q, q_bytes),
                ("dequantize", t_d, d_bytes),
            ):
                gbps = moved / dt / 1e9
                row = {
                    "wire": wire,
                    "direction": direction,
                    "rows_per_tile": rows_per_tile,
                    "ms": round(dt * 1e3, 3),
                    "gbps": round(gbps, 2),
                    "hbm_fraction": round(gbps / HBM_GBPS, 4),
                }
                rows.append(row)
                print(json.dumps(row))
                if gbps > best["gbps"]:
                    best = row
    leaf_rows = leaf_sweep(timed)
    rows += leaf_rows
    artifact = {
        "bench": "codec_block_sweep",
        "total_mb": total_mb,
        "n_blocks": n_blocks,
        "block": q.BLOCK,
        "hbm_gbps_nominal": HBM_GBPS,
        "device": str(dev.device_kind),
        "rows": rows,
        "best": best,
        "leaf_best": {
            f"{shape} {direction}": max(
                (r for r in leaf_rows
                 if r.get("shape") == list(shape) and r.get("direction") == direction),
                key=lambda r: r["gbps"], default=None,
            )
            for shape in LEAF_SHAPES for direction in ("quantize", "dequantize")
        },
        "target_gbps": 100.0,
        "target_met": best.get("gbps", 0.0) >= 100.0,
        "ts": time.time(),
        "notes": (
            "gbps = (bytes read + bytes written) / wall; hbm_fraction = "
            "gbps / nominal HBM bandwidth. If target_met is false, `best` "
            "is the measured roofline for the current kernel structure — "
            "the next lever is fusing the maxabs pass with the cast pass "
            "(today the kernel reads each tile twice)."
        ),
    }
    OUT.parent.mkdir(exist_ok=True)
    OUT.write_text(json.dumps(artifact, indent=2) + "\n")
    print(json.dumps({"best": best, "target_met": artifact["target_met"]}))


def leaf_sweep(timed):
    """The leaf-layout kernels over their tile, fp8, at LEAF_SHAPES."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.ops import quantization as q

    rows = []
    key = jax.random.PRNGKey(0)
    for shape in LEAF_SHAPES:
        a, b = (
            (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(jnp.bfloat16)
            for k in jax.random.split(key)
        )
        n = int(np.prod(shape))
        blocks = n // q.BLOCK
        moved = {
            "quantize": 2 * a.nbytes + n + 4 * blocks,
            "dequantize": n + 4 * blocks + 4 * n,
        }
        tuned = q.leaf_block_view(shape)
        for tile_rows in LEAF_TILE_ROWS:
            for segments in LEAF_CHUNK_SEGMENTS:
                if (
                    shape[-2] % tile_rows
                    or (shape[-1] // q.BLOCK) % segments
                    or tile_rows * segments * q.BLOCK > LEAF_MAX_TILE_VALUES
                ):
                    continue
                view = q.leaf_block_view(shape, tile_rows, segments)
                row = {
                    "kernel": "leaf", "wire": "fp8", "shape": list(shape),
                    "tile_rows": tile_rows, "chunk_segments": segments,
                    "tuned": view == tuned,
                }
                try:
                    quantize = jax.jit(
                        lambda x, y, v=view: q.quantize_leaf_pallas(
                            x, y, v, v.n_blocks, wire="fp8"
                        )
                    )
                    payload, scales = quantize(a, b)
                    times = {
                        "quantize": timed(quantize, a, b),
                        "dequantize": timed(
                            jax.jit(lambda p, s, v=view: q.dequantize_leaf_pallas(p, s, v)),
                            payload, scales,
                        ),
                    }
                except Exception as e:  # noqa: BLE001 — a failing tile is data
                    rows.append({**row, "error": f"{type(e).__name__}: {e}"[:200]})
                    print(json.dumps(rows[-1]))
                    continue
                for direction, dt in times.items():
                    gbps = moved[direction] / dt / 1e9
                    rows.append({
                        **row, "direction": direction, "ms": round(dt * 1e3, 3),
                        "gbps": round(gbps, 2),
                        "hbm_fraction": round(gbps / HBM_GBPS, 4),
                    })
                    print(json.dumps(rows[-1]))
    return rows


if __name__ == "__main__":
    main()
