#!/usr/bin/env python
"""On-chip block-size sweep for the Pallas flash-attention kernels, read
from the device trace.

The kernels take ``block_q``/``block_k`` at every entry point, so tuning is
a pure measurement problem — no kernel edits. One call a (seq, block pair):
``jax.grad`` of a loss over :func:`flash_attention`, which runs the two
kernels of a layer step (forward and the fused backward; a ``--tree`` from
before the backward was one call runs three: forward, dq, dkv), at the
benchmark cells' head geometry (32 q heads over 8 KV heads of 128, bf16)
and tokens a step (8192: batch 4 at 2048, batch 1 at 8192). Each kernel's
time is the sum of its Mosaic call's device durations in a profiler trace of
ITERS calls (``chipbench/trace_reduce.py`` reads the file), so no dispatch
cost and nothing of the XLA ops around the kernels is in it. ``mxu_pct`` is
the operations causal attention needs for the call (``chipbench/flops.py``:
7 matmuls, the mask's half) over the kernels' seconds, against the chip's
bf16 peak: what the cells report as ``flash_mxu_pct``.

Usage:
    python scripts/flash_block_sweep.py [--tree DIR] [--pairs 512x1024,...]
        [--heads 28x4] [--window 4096] [--whole ROWS] [seq ...]

``--heads`` (q heads x KV heads) and ``--window`` give another cell's calls:
28x4 at 16384 with and without a window of 4096 is a layer of
``smallthinker-21b-a3b-1chip.ftddp-seq16k`` (``mxu_pct`` then counts the
(query, key) pairs the window allows, as that cell's
``window_attention_flops`` does). A row says how many grid
steps a head's forward takes (``steps``), how many of them compute one half
of their KV block alone (``halves``; each where the tree's ``_class_counts``
tells), and each kernel's mean microseconds a step (``fwd_step_us``,
``bwd_step_us``: its time over batch x heads x steps). ``--whole ROWS`` names
a file of the rows this script printed for a tree whose steps all compute
their whole block (the parent of the half steps): a row with ``halves`` then
also gives what a half step costs, ``fwd_half_step_us`` / ``bwd_half_step_us``,
that tree's mean step less what each half step saved
(``(its ms - mine) / (batch x heads x halves)``).

``--tree`` imports ``torchft_tpu`` from another checkout (a parent commit
unpacked beside this one), so two trees are read the same way in one chip
call. Prints one JSON line a (seq, block_q, block_k); default seqs 2048 8192.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.append(str(ROOT))

from chipbench import trace_reduce  # noqa: E402  (no JAX at import)
HEADS, KV_HEADS, HEAD_DIM = 32, 8, 128
TOKENS = 8192
PAIRS = [(bq, bk) for bq in (256, 512, 1024) for bk in (256, 512, 1024, 2048)]
ITERS = 8
WARMUP = 2
# A Mosaic call by what it returns, other than the forward's (out and the
# logsumexp, a column): the fused backward three arrays (dq, dk, dv); the
# two-pass backward of an older tree one (dq) and two (dk, dv).
BACKWARD_BY_RESULTS = {1: "dq", 2: "dkv", 3: "bwd"}
KERNEL_SETS = (["bwd", "fwd"], ["dkv", "dq", "fwd"])


def kernel_ms(trace: Path) -> dict:
    """Milliseconds a call of each Mosaic kernel in the trace, by kernel.
    XLA orders and names the calls as it likes, so a call is told by what it
    returns (``BACKWARD_BY_RESULTS``)."""
    from jax.profiler import ProfileData

    total = {}
    for plane in ProfileData.from_file(str(trace)).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for event in line.events:
                if trace_reduce.KERNEL_MARK not in event.name:
                    continue
                result = event.name.partition(" = ")[2].partition(" custom-call(")[0]
                shapes = trace_reduce.SHAPE.findall(result)
                kernel = (
                    "fwd" if any(x.endswith(",1]") for x in shapes)
                    else BACKWARD_BY_RESULTS[len(shapes)]
                )
                total[kernel] = total.get(kernel, 0.0) + event.duration_ns
    return {k: v / ITERS / 1e6 for k, v in total.items()}


def step_costs(row: dict, whole: dict | None) -> dict:
    """Mean microseconds a grid step of each kernel of ``row``, and against
    the row ``whole`` of a tree without half steps (same call), what a half
    step costs (module docstring)."""
    if "steps" not in row:
        return {}
    calls = row["batch"] * int(row["heads"].partition("x")[0])
    costs = {}
    for kernel in ("fwd", "bwd"):
        ms = row.get(f"{kernel}_ms")
        if ms is None:
            continue
        costs[f"{kernel}_step_us"] = round(1e3 * ms / (calls * row["steps"]), 4)
        theirs = (whole or {}).get(f"{kernel}_ms")
        if theirs is not None and row.get("halves"):
            costs[f"{kernel}_half_step_us"] = round(
                1e3 * (theirs / whole["steps"] - (theirs - ms) / row["halves"]) / calls, 4
            )
    return costs


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--tree", default=str(ROOT))
    parser.add_argument("--pairs", default="")
    parser.add_argument("--heads", default=f"{HEADS}x{KV_HEADS}")
    parser.add_argument("--window", type=int, default=None)
    parser.add_argument("--whole", default=None)
    parser.add_argument("seqs", nargs="*", type=int)
    args = parser.parse_args()
    sys.path.insert(0, args.tree)

    from torchft_tpu.utils.platform import require_tpu

    require_tpu()  # a chip script: exits non-zero when jax answers on anything else

    import jax
    import jax.numpy as jnp

    from chipbench import flops
    from chipbench.harness import peaks_for
    from torchft_tpu.ops.flash_attention import _class_counts, flash_attention

    peak = peaks_for(jax.devices()[0].device_kind)["bf16_tflops"] * 1e12
    pairs = [
        tuple(int(x) for x in p.split("x")) for p in args.pairs.split(",") if p
    ] or PAIRS
    heads, kv_heads = (int(x) for x in args.heads.split("x"))
    geometry = {
        "head_dim": HEAD_DIM, "num_attention_heads": heads, "num_hidden_layers": 1,
    }
    more = {} if args.window is None else {"window": args.window}
    whole = {}
    if args.whole:
        for line in Path(args.whole).read_text().splitlines():
            if line.startswith("{"):
                row = json.loads(line)
                key = (row["seq"], row["block_q"], row["block_k"], row["heads"], row.get("window"))
                whole[key] = row
    for s in args.seqs or [2048, 8192]:
        b = max(1, TOKENS // s)
        kq, kk, kvk, kr = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(kq, (b, s, heads, HEAD_DIM), jnp.bfloat16)
        k = jax.random.normal(kk, (b, s, kv_heads, HEAD_DIM), jnp.bfloat16)
        v = jax.random.normal(kvk, (b, s, kv_heads, HEAD_DIM), jnp.bfloat16)
        r = jax.random.normal(kr, (b, s, heads, HEAD_DIM), jnp.float32)
        need = flops.flash_attention_flops(geometry, b, s)
        if args.window is not None and args.window < s:
            # The (query, key) pairs the window allows over the s^2 / 2 the
            # causal count takes.
            w = args.window
            need *= (w * (w + 1) / 2 + (s - w) * w) / (s * s / 2)
        for bq, bk in pairs:
            if bq > s or bk > s:
                continue
            row = {
                "tree": args.tree, "seq": s, "batch": b, "block_q": bq, "block_k": bk,
                "heads": args.heads, **more,
            }
            counts = _class_counts(s, s, bq, bk, **more)
            row.update({key: counts[key] for key in ("steps", "halves") if key in counts})

            def loss(q, k, v, _bq=bq, _bk=bk):
                out = flash_attention(
                    q, k, v, block_q=_bq, block_k=_bk, interpret=False, **more
                )
                return jnp.vdot(out.astype(jnp.float32), r)

            try:
                grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                for _ in range(WARMUP):
                    jax.block_until_ready(grad(q, k, v))
                with tempfile.TemporaryDirectory() as log_dir:
                    jax.profiler.start_trace(log_dir)
                    for _ in range(ITERS):
                        out = grad(q, k, v)
                    jax.block_until_ready(out)
                    jax.profiler.stop_trace()
                    (trace,) = Path(log_dir).rglob("*.xplane.pb")
                    ms = kernel_ms(trace)
            except Exception as e:  # a pair the compiler refuses is a row, not the end
                row["error"] = str(e).splitlines()[0][:200]
                print(json.dumps(row), flush=True)
                continue
            if sorted(ms) not in KERNEL_SETS:
                row["error"] = f"Mosaic kernels in the trace: {sorted(ms)}"
            else:
                seconds = sum(ms.values()) / 1e3
                row.update(
                    {f"{k}_ms": round(v, 4) for k, v in ms.items()},
                    kernels_ms=round(1e3 * seconds, 4),
                    mxu_pct=round(100 * need / seconds / peak, 2),
                )
                row.update(step_costs(row, whole.get((s, bq, bk, args.heads, args.window))))
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
