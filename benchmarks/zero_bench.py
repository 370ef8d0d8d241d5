"""ZeRO plane bench: per-replica optimizer-state bytes and heal-payload
bytes at N ∈ {1, 2, 4}, on the 27M-param CPU bench config.

Usage::

    python benchmarks/zero_bench.py          # -> ZERO_BENCH.json (repo root)
    TPUFT_ZERO_BENCH_ELEMS=100000 python benchmarks/zero_bench.py  # quick

No training steps and no coordination plane: the bench measures the
*state geometry* — what each replica persists (f32 masters + adam
moments for its owned shards) and what the heal plane moves (the staged
checkpoint's chunk sizes through the REAL part-aware HTTPTransport
staging path, plus one live skip-parts fetch to validate the wire
numbers). Shapes come from a representative 27M Llama (``_bench_params``); set
``TPUFT_ZERO_BENCH_ELEMS`` to bench a synthetic tree of that many
elements instead (fast smoke). Runtime well under the default-workload
trap documented in CLAUDE.md — nothing here steps the model.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

from torchft_tpu import metrics  # noqa: E402
from torchft_tpu.checkpointing.http_transport import HTTPTransport  # noqa: E402
from torchft_tpu.zero import (  # noqa: E402
    DEFAULT_NUM_SHARDS,
    ShardSpec,
    shard_assignment,
    shard_part_name,
)

OUT = Path(__file__).resolve().parent.parent / "ZERO_BENCH.json"


def _bench_params():
    elems = os.environ.get("TPUFT_ZERO_BENCH_ELEMS")
    if elems:
        n = int(elems)
        # Synthetic stand-in with the same dtype story (bf16 model params).
        return {
            "w0": jnp.ones((n // 2,), jnp.bfloat16),
            "w1": jnp.ones((n - n // 2,), jnp.bfloat16),
        }, f"synthetic-{n}"
    from torchft_tpu.models.llama import Llama, LlamaConfig

    seq = 512
    config = LlamaConfig(
        vocab_size=8192, dim=512, n_layers=6, n_heads=8, n_kv_heads=4,
        ffn_hidden=1536, max_seq_len=seq, dtype=jnp.bfloat16,
    )
    model = Llama(config)
    tokens = jnp.zeros((2, seq), dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    return params, "llama-27M (dim 512, 6 layers, vocab 8192)"


def _tree_bytes(tree) -> int:
    return sum(int(np.asarray(x).nbytes) for x in jax.tree_util.tree_leaves(tree))


def main() -> None:
    t0 = time.time()
    params, config_name = _bench_params()
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    params_bytes = _tree_bytes(params)
    tx = optax.adam(1e-3)
    num_shards = int(os.environ.get("TPUFT_ZERO_SHARDS", str(DEFAULT_NUM_SHARDS)))
    spec = ShardSpec(params, num_shards)
    flat = np.asarray(spec.pack(params), dtype=np.float32)

    # One shard's persisted state (all shards are equal ranges): the f32
    # master plus adam's mu/nu moments for that range.
    shard_opt = tx.init(jnp.zeros((spec.shard_len,), jnp.float32))
    per_shard_bytes = spec.shard_len * 4 + _tree_bytes(shard_opt)

    # The unsharded baseline every replica pays today: full-tree moments
    # (adam on the model dtype tree).
    baseline_opt_bytes = _tree_bytes(tx.init(params))

    results = {}
    for n in (1, 2, 4):
        owners = shard_assignment(num_shards, n)
        owned = [s for s in range(num_shards) if owners[s] == 0]
        opt_bytes = len(owned) * per_shard_bytes

        # Stage rank 0's checkpoint through the real part-aware transport
        # and read the chunk geometry: what a full fetch vs a
        # skip-all-shards fetch moves.
        shards = {}
        for s in range(num_shards):
            if s in owned:
                start, stop = spec.shard_range(s)
                shards[shard_part_name(s)] = {
                    "step": 0,
                    "master": flat[start:stop],
                    "opt": shard_opt,
                }
            else:
                shards[shard_part_name(s)] = None
        state_dict = {
            "user": {
                "zero": {
                    "params": params,
                    "zero": {"num_shards": num_shards, "step": 0},
                    "shards": shards,
                }
            },
            "tpuft": {"step": 0, "batches_committed": 0},
        }
        transport = HTTPTransport(timeout=30.0)
        try:
            transport.send_checkpoint(
                [1], step=0, state_dict=state_dict, timeout=30.0
            )
            staged = transport._staged
            full_bytes = sum(c.total_size for c in staged.chunks)
            shard_part_bytes = sum(
                info["nbytes"] for info in staged.parts.values()
            )
            joiner_fetch_bytes = full_bytes - shard_part_bytes

            # Validate on the wire once per N: a live skip-parts fetch
            # must move exactly joiner_fetch_bytes of chunk payload.
            saved_before = metrics.counter_total(
                "tpuft_zero_heal_bytes_saved_total"
            )
            fetcher = HTTPTransport(timeout=30.0)
            try:
                fetcher.recv_checkpoint(
                    0,
                    transport.metadata(),
                    0,
                    30.0,
                    skip_parts=set(staged.parts),
                )
            finally:
                fetcher.shutdown()
            saved = (
                metrics.counter_total("tpuft_zero_heal_bytes_saved_total")
                - saved_before
            )
        finally:
            transport.shutdown()

        results[str(n)] = {
            "owned_shards": len(owned),
            "per_replica_opt_state_bytes": opt_bytes,
            "opt_state_vs_n1": round(
                opt_bytes / (num_shards * per_shard_bytes), 4
            ),
            "donor_checkpoint_bytes": full_bytes,
            "shard_part_bytes": shard_part_bytes,
            "joiner_fetch_bytes_skip_parts": joiner_fetch_bytes,
            "heal_bytes_saved_measured": int(saved),
        }

    # Quantized shard-wire legs (ISSUE-14 / TPUFT_ZERO_CODEC): per-step
    # bytes each replica puts on the replica-axis wire for the flat f32
    # plane, fp32 vs encoded — built through the EXACT payload builders
    # zero.py uses (quantize_blocks + pack_arrays per shard range for
    # the allgather; the quantized-allreduce packing math for the grad
    # reduce), so the byte counts are the wire's, not an estimate.
    from torchft_tpu.ops import quantization as q

    codec_legs = {}
    for codec in ("fp32", "fp8", "int8", "int4"):
        t_enc = time.perf_counter()
        if codec == "fp32":
            ag_bytes = spec.padded * 4  # raw f32 ranges, all shards
            rs_bytes = spec.padded * 4 * 2  # allreduce: ~2x payload on the wire
            decode_deterministic = True
        else:
            packed = []
            for s in range(num_shards):
                start, stop = spec.shard_range(s)
                packed.append(
                    q.pack_arrays(*q.quantize_blocks(flat[start:stop], wire=codec))
                )
            ag_bytes = sum(int(p.nbytes) for p in packed)
            n_blocks = -(-spec.padded // q.BLOCK)
            rs_bytes = 2 * (
                n_blocks * (4 + q.payload_cols(codec)) + q.WIRE_HEADER_BYTES
            )
            # The construction invariant's mechanical half: decoding the
            # SAME packed bytes twice is bitwise-identical (the host
            # codec is deterministic); the cross-replica drill lives in
            # tests/test_zero.py::test_zero_codec_multi_rank_bitwise...
            shard_blocks = -(-spec.shard_len // q.BLOCK)
            a = q.dequantize_blocks(
                *q.unpack_arrays(packed[0], shard_blocks, wire=codec),
                (spec.shard_len,), np.float32,
            )
            b = q.dequantize_blocks(
                *q.unpack_arrays(packed[0], shard_blocks, wire=codec),
                (spec.shard_len,), np.float32,
            )
            decode_deterministic = bool(np.array_equal(a, b))
        codec_legs[codec] = {
            "allgather_bytes_per_step": int(ag_bytes),
            "grad_reduce_bytes_per_step": int(rs_bytes),
            "vs_fp32_allgather": round(ag_bytes / (spec.padded * 4), 3),
            "bitwise_identical_decode": decode_deterministic,
            "encode_wall_s": round(time.perf_counter() - t_enc, 3),
        }
    codec_notes = (
        "allgather_bytes_per_step = what the owners collectively put on "
        "the wire for the full param buffer (every replica dequantizes "
        "the same encoded payload — bitwise identity by construction, "
        "drilled in tests/test_zero.py incl. kill/rejoin re-balance and "
        "strict+pipelined orderings); grad_reduce counts the quantized "
        "allreduce's ~2x-payload wire traffic vs the f32 allreduce's. "
        "Quality evidence: WIRE_CONVERGENCE.json (fp8/int4 outer syncs "
        "quality-neutral, same seed, ±0.007% tail loss vs fp32)"
    )

    out = {
        "bench": "zero_bench",
        "config": config_name,
        "n_params": n_params,
        "num_shards": num_shards,
        "params_bytes": params_bytes,
        "per_shard_state_bytes": per_shard_bytes,
        "baseline_unsharded_opt_state_bytes": baseline_opt_bytes,
        "per_n": results,
        "codec_wire": codec_legs,
        "codec_wire_notes": codec_notes,
        "wall_time_s": round(time.time() - t0, 2),
        "notes": (
            "per_replica_opt_state_bytes = f32 masters + adam moments for "
            "owned shards (scales ~1/N); donor_checkpoint_bytes = staged "
            "heal payload (params + the donor's 1/N of opt state); "
            "joiner_fetch_bytes_skip_parts = what a skip-all-shards joiner "
            "actually moves (shards re-balance from survivors over the PG)"
        ),
    }
    OUT.write_text(json.dumps(out, indent=2) + "\n")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
