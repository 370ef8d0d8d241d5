#!/usr/bin/env python
"""On-chip kernel microbenchmarks: Pallas flash attention vs XLA dense
attention, and the fp8 wire-codec device kernels.

The benchmark (chipbench/) measures the FT layer's overhead; this one
measures the per-chip hot ops themselves — the "don't stop at parity"
half of the perf story. Requires a live TPU (the kernels' compiled Mosaic
path, not interpret mode — interpret-mode timings are meaningless).

Usage:  TPUFT_LOG=warn python benchmarks/kernel_bench.py
Prints one JSON line per configuration plus a summary line.

Timing note: every timed region is closed by a value fetch of the last
output, which waits for everything it depends on. Attention iterations
are additionally data-chained (iteration i+1 consumes
iteration i's output) so the fetch provably covers the whole loop; the
fp8 codec shapes don't permit chaining, so those rely on the device
executing dispatched programs in order (true of single-stream TPU
execution) for the final fetch to imply the earlier iterations finished.
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

ITERS = 10
WARMUP = 2


def _timed(fn, *args, iters: int = ITERS, fetch=None):
    """Median-of-3 wall time for ``iters`` data-chained applications."""
    out = None
    for _ in range(WARMUP):
        out = fn(*args)
    _force(out if fetch is None else fetch(out))
    times = []
    for _ in range(3):
        t0 = time.monotonic()
        cur = args
        for _ in range(iters):
            out = fn(*cur)
            cur = _rechain(cur, out)
        _force(out if fetch is None else fetch(out))
        times.append((time.monotonic() - t0) / iters)
    return sorted(times)[1]


def _force(x):
    leaf = jax.tree_util.tree_leaves(x)[0]
    float(jnp.asarray(leaf).reshape(-1)[0])


def _rechain(args, out):
    """Feed the output back as the first argument (shapes permitting) so the
    device must execute iterations in order."""
    first = jax.tree_util.tree_leaves(out)[0]
    if hasattr(args[0], "shape") and first.shape == args[0].shape:
        return (first.astype(args[0].dtype),) + tuple(args[1:])
    return args


def bench_dispatch_floor(results: list) -> None:
    """Per-iteration cost of a trivial jitted op, timed with the identical
    chained-fetch schedule: the dispatch floor every row below pays. Rows
    whose kernel time is near the floor are comparing dispatch latency,
    not kernels."""
    x = jax.random.normal(jax.random.PRNGKey(9), (128, 128), jnp.float32)
    tiny = jax.jit(lambda x: x * 1.0000001)
    t = _timed(tiny, x)
    row = {"bench": "dispatch_floor", "floor_ms": round(1e3 * t, 3)}
    results.append(row)
    print(json.dumps(row))


def bench_attention(results: list) -> None:
    from torchft_tpu.ops.attention import causal_attention
    from torchft_tpu.ops.flash_attention import flash_attention

    b, h, kv, d = 4, 8, 4, 128
    # 16k/32k are the long-context rows: dense attention is already OOM at
    # 8k on this chip (the s^2 f32 scores alone are 8 GB), so past there
    # the flash kernel is the only implementation that runs at all.
    for s in (1024, 2048, 4096, 8192, 16384, 32768):
        kq, kk, kvk = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
        k = jax.random.normal(kk, (b, s, kv, d), jnp.bfloat16)
        v = jax.random.normal(kvk, (b, s, kv, d), jnp.bfloat16)

        flash = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=False))
        dense = jax.jit(lambda q, k, v: causal_attention(q, k, v, scale=d**-0.5))

        # Flash gets the same guard as dense: on a smaller-HBM chip (or a
        # block-size regression) a long-s OOM must produce a null row, not
        # abort the run before the codec rows and the summary line.
        try:
            t_flash = _timed(flash, q, k, v)
        except Exception as e:
            sys.stderr.write(f"kernel_bench: flash fwd s={s} failed: {e}\n")
            t_flash = None
        try:
            t_dense = _timed(dense, q, k, v)
        except Exception as e:  # dense O(s^2) logits can OOM at long s
            sys.stderr.write(f"kernel_bench: dense fwd s={s} failed: {e}\n")
            t_dense = None

        # Causal attention FLOPs: 2 matmuls x (s^2/2) x h x d x b x 2.
        flops = 2 * 2 * b * h * d * (s * s / 2)
        # `is not None`, never truthiness, for every timing-null guard: a
        # legitimate 0.0 timing must be reported, not nulled (and the
        # fwd_bwd row below already guards this way — keep them identical).
        row = {
            "bench": "attention_fwd",
            "seq": s,
            "flash_ms": round(1e3 * t_flash, 3) if t_flash is not None else None,
            "dense_ms": round(1e3 * t_dense, 3) if t_dense is not None else None,
            "speedup_vs_dense": (
                round(t_dense / t_flash, 3)
                if t_dense is not None and t_flash is not None
                else None
            ),
            "flash_tflops": (
                round(flops / t_flash / 1e12, 2) if t_flash is not None else None
            ),
        }
        results.append(row)
        print(json.dumps(row))

        # fwd+bwd through the kernel's custom VJP: the default on-chip path
        # (the fused Pallas backward), the scan-based blockwise backward
        # it replaced, and dense. The loss is a dot with a RANDOM cotangent
        # (passed as an argument, not a closed-over constant): a plain
        # ``out.sum()`` makes dO all-ones, which XLA's algebraic simplifier
        # exploits to collapse much of the dense backward — dense fwd+bwd
        # at s=8192 then "runs" while dense fwd ALONE runs out of memory,
        # i.e. the baseline isn't doing the work. A
        # custom-VJP kernel sees dO as opaque either way, so the old loss
        # biased every speedup_vs_dense down.
        r = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d), jnp.float32)

        def loss_flash(q, k, v, r):
            return jnp.vdot(
                flash_attention(q, k, v, interpret=False).astype(jnp.float32), r
            )

        def loss_flash_scan_bwd(q, k, v, r):
            return jnp.vdot(
                flash_attention(
                    q, k, v, interpret=False, use_pallas_bwd=False
                ).astype(jnp.float32),
                r,
            )

        def loss_dense(q, k, v, r):
            return jnp.vdot(
                causal_attention(q, k, v, scale=d**-0.5).astype(jnp.float32), r
            )

        gflash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))
        gscan = jax.jit(jax.grad(loss_flash_scan_bwd, argnums=(0, 1, 2)))
        gdense = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))
        try:
            t_gflash = _timed(gflash, q, k, v, r, fetch=lambda g: g[0])
        except Exception as e:
            sys.stderr.write(f"kernel_bench: flash fwd+bwd s={s} failed: {e}\n")
            t_gflash = None
        try:
            t_gscan = _timed(gscan, q, k, v, r, fetch=lambda g: g[0])
        except Exception as e:
            sys.stderr.write(f"kernel_bench: scan bwd s={s} failed: {e}\n")
            t_gscan = None
        try:
            t_gdense = _timed(gdense, q, k, v, r, fetch=lambda g: g[0])
        except Exception as e:
            sys.stderr.write(f"kernel_bench: dense fwd+bwd s={s} failed: {e}\n")
            t_gdense = None
        row = {
            "bench": "attention_fwd_bwd",
            "seq": s,
            "flash_ms": round(1e3 * t_gflash, 3) if t_gflash is not None else None,
            "scan_bwd_ms": round(1e3 * t_gscan, 3) if t_gscan is not None else None,
            "dense_ms": round(1e3 * t_gdense, 3) if t_gdense is not None else None,
            "speedup_vs_scan_bwd": (
                round(t_gscan / t_gflash, 3)
                if t_gscan is not None and t_gflash is not None
                else None
            ),
            "speedup_vs_dense": (
                round(t_gdense / t_gflash, 3)
                if t_gdense is not None and t_gflash is not None
                else None
            ),
        }
        results.append(row)
        print(json.dumps(row))


def bench_fp8_codec(results: list) -> None:
    from torchft_tpu.ops.quantization import (
        dequantize_blocks_device,
        quantize_blocks_device,
    )

    n = 64 * 1024 * 1024  # 256 MB of f32
    x = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.float32)
    quant = jax.jit(quantize_blocks_device)
    payload, scales = quant(x)
    dequant = jax.jit(dequantize_blocks_device)

    t_q = _timed(quant, x, iters=5, fetch=lambda o: o[0])
    t_d = _timed(lambda p, s: dequant(p, s), payload, scales, iters=5)
    gb = n * 4 / 1e9
    row = {
        "bench": "fp8_codec",
        "input_mb": n * 4 // (1 << 20),
        "quantize_ms": round(1e3 * t_q, 3),
        "quantize_gbps": round(gb / t_q, 1),
        "dequantize_ms": round(1e3 * t_d, 3),
        "dequantize_gbps": round(gb / t_d, 1),
    }
    results.append(row)
    print(json.dumps(row))


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(
            f"kernel_bench: needs a live TPU, devices()[0] is {dev}\n"
        )
        sys.exit(1)
    results: list = []
    bench_dispatch_floor(results)
    bench_attention(results)
    bench_fp8_codec(results)
    print(
        json.dumps(
            {
                "bench": "summary",
                "device_kind": str(getattr(dev, "device_kind", "unknown")),
                "rows": len(results),
            }
        )
    )


if __name__ == "__main__":
    main()
