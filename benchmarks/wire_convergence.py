"""Wire-format convergence evidence: fp32 vs fp8 vs int4 outer syncs.

The lossy wire codecs
(ops/quantization.py) ship with speed numbers but no end-to-end quality
evidence. This bench closes that gap in pure Python: a same-seed,
same-batch-stream DiLoCo-style run per wire format, where every outer
sync's delta round-trips through the REAL host codec
(``quantize_blocks``/``dequantize_blocks``, the exact arrays the wire
carries) — for bitwise-identical replicas the allreduce of quantized
deltas IS that round trip, so a single-process run measures exactly the
quality effect of the wire format with no transport in the loop.

Protocol per wire: inner SGD for ``sync_every`` steps, then
``outer += roundtrip(inner - outer); inner = outer`` (outer lr 1 — the
delta itself is what the codec distorts; fp32 skips the round trip).
Loss curves are recorded every step; the artifact carries the curves
(downsampled), final/tail losses, and the max curve divergence vs fp32.

    python benchmarks/wire_convergence.py                 # quick preset
    python benchmarks/wire_convergence.py --preset 27m    # the 27M MLP
    python benchmarks/wire_convergence.py --steps 400 --sync-every 8

Writes WIRE_CONVERGENCE.json (see PERF.md for the headline deltas).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402

from torchft_tpu.ops.quantization import (  # noqa: E402
    dequantize_blocks,
    quantize_blocks,
)

PRESETS = {
    # ~1.1M params: seconds per wire on one core — the default evidence.
    "small": {"in_dim": 256, "widths": [512, 1024, 512], "out_dim": 128},
    # ~26M params (the 27M-CPU-config scale): minutes per wire on one
    # core; run when the box has the budget.
    "27m": {"in_dim": 1024, "widths": [2560, 4096, 2560], "out_dim": 1024},
}


def init_params(key, in_dim: int, widths: List[int], out_dim: int) -> Dict:
    dims = [in_dim] + widths + [out_dim]
    params = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        key, wk = jax.random.split(key)
        params[f"w{i}"] = jax.random.normal(wk, (a, b), jnp.float32) * (
            1.0 / np.sqrt(a)
        )
        params[f"b{i}"] = jnp.zeros((b,), jnp.float32)
    return params


def forward(params: Dict, x):
    h = x
    n = len(params) // 2
    for i in range(n):
        h = h @ params[f"w{i}"] + params[f"b{i}"]
        if i < n - 1:
            h = jax.nn.gelu(h)
    return h


def codec_roundtrip(delta: Dict, wire: Optional[str]) -> Dict:
    """The outer sync's wire effect: every delta leaf through the host
    codec and back. ``wire=None`` (fp32) is the identity."""
    if wire is None:
        return delta
    out = {}
    for name, leaf in delta.items():
        host = np.asarray(leaf)
        payload, scales = quantize_blocks(host, wire=wire)
        out[name] = jnp.asarray(
            dequantize_blocks(payload, scales, host.shape, host.dtype)
        )
    return out


def run_wire(
    wire: Optional[str],
    preset: Dict,
    steps: int,
    sync_every: int,
    batch: int,
    lr: float,
    seed: int,
) -> Dict:
    """One same-seed training run; returns its loss curve + timing."""
    key = jax.random.PRNGKey(seed)
    key, teacher_key, init_key = jax.random.split(key, 3)
    # Fixed random teacher: a real (noiseless) regression target so the
    # loss curve measures optimization quality, not noise floor.
    teacher = init_params(
        teacher_key, preset["in_dim"], preset["widths"], preset["out_dim"]
    )
    inner = init_params(
        init_key, preset["in_dim"], preset["widths"], preset["out_dim"]
    )
    outer = jax.tree_util.tree_map(lambda a: a, inner)

    def loss_fn(params, x):
        return jnp.mean((forward(params, x) - forward(teacher, x)) ** 2)

    @jax.jit
    def train_step(params, x):
        loss, grads = jax.value_and_grad(loss_fn)(params, x)
        new = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
        return loss, new

    losses: List[float] = []
    t0 = time.perf_counter()
    for step in range(steps):
        x = jax.random.normal(
            jax.random.PRNGKey(100_000 + step), (batch, preset["in_dim"]),
            jnp.float32,
        )
        loss, inner = train_step(inner, x)
        losses.append(float(loss))
        if (step + 1) % sync_every == 0:
            delta = jax.tree_util.tree_map(lambda a, b: a - b, inner, outer)
            decoded = codec_roundtrip(delta, wire)
            outer = jax.tree_util.tree_map(lambda o, d: o + d, outer, decoded)
            inner = jax.tree_util.tree_map(lambda a: a, outer)
    wall = time.perf_counter() - t0
    tail = losses[-max(1, steps // 10):]
    return {
        "wire": wire or "fp32",
        "final_loss": losses[-1],
        "tail_mean_loss": float(np.mean(tail)),
        "wall_s": round(wall, 3),
        "losses": losses,
    }


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--preset", choices=sorted(PRESETS), default="small")
    parser.add_argument("--steps", type=int, default=400)
    parser.add_argument("--sync-every", type=int, default=8)
    parser.add_argument("--batch", type=int, default=64)
    parser.add_argument("--lr", type=float, default=0.05)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--out", default=str(Path(__file__).resolve().parent.parent / "WIRE_CONVERGENCE.json")
    )
    args = parser.parse_args()
    preset = PRESETS[args.preset]
    n_params = sum(
        int(np.prod(leaf.shape))
        for leaf in init_params(
            jax.random.PRNGKey(0), preset["in_dim"], preset["widths"], preset["out_dim"]
        ).values()
    )

    runs = {}
    for wire in (None, "fp8", "int4"):
        label = wire or "fp32"
        print(f"[wire_convergence] running {label} ({args.steps} steps)...", flush=True)
        runs[label] = run_wire(
            wire, preset, args.steps, args.sync_every, args.batch, args.lr,
            args.seed,
        )
        print(
            f"[wire_convergence] {label}: final {runs[label]['final_loss']:.6f} "
            f"tail-mean {runs[label]['tail_mean_loss']:.6f} "
            f"({runs[label]['wall_s']}s)",
            flush=True,
        )

    fp32_curve = np.array(runs["fp32"]["losses"])
    result = {
        "config": {
            "preset": args.preset,
            "params": n_params,
            "steps": args.steps,
            "sync_every": args.sync_every,
            "batch": args.batch,
            "lr": args.lr,
            "seed": args.seed,
            "protocol": "DiLoCo-style outer sync; delta round-trips the "
            "host codec (quantize_blocks/dequantize_blocks) each sync; "
            "same seed + batch stream across wires",
        },
        "runs": {},
    }
    for label, run in runs.items():
        curve = np.array(run["losses"])
        result["runs"][label] = {
            "final_loss": run["final_loss"],
            "tail_mean_loss": run["tail_mean_loss"],
            "tail_mean_vs_fp32_pct": (
                round(
                    100.0
                    * (run["tail_mean_loss"] - runs["fp32"]["tail_mean_loss"])
                    / runs["fp32"]["tail_mean_loss"],
                    4,
                )
            ),
            "max_curve_divergence_vs_fp32": float(np.max(np.abs(curve - fp32_curve))),
            "wall_s": run["wall_s"],
            # Every 4th point keeps the artifact small but plottable.
            "loss_curve_every4": [round(v, 6) for v in run["losses"][::4]],
        }
    out = Path(args.out)
    out.write_text(json.dumps(result, indent=1))
    print(f"[wire_convergence] wrote {out}")


if __name__ == "__main__":
    main()
