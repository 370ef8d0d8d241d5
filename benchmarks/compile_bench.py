"""Compile-cost benchmark: loop vs lax.scan'd layer stack.

Measures what ``LlamaConfig.scan_layers`` buys at depth: jaxpr trace +
StableHLO lowering time, lowered-module text size, and XLA compile time
for the bench 'large' shape (dim 1024, seq 2048) at several depths, using
AOT lowering over ``jax.ShapeDtypeStruct`` avals — no parameters are
materialized, so the measurement isolates program size from memory.

Writes one JSON document (default ``SCAN_COMPILE_BENCH.json``) — the
artifact backing PARITY.md's "O(1) HLO in depth" claim. Each row records
the batch/seq it measured. Runs on local CPU XLA by design: the CPU
backend lowers the same HLO graph shapes the TPU backend would (backend
codegen differs; the *scaling* with depth is the claim).
"""

from __future__ import annotations

import json
import sys
import time
from dataclasses import replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp


def _abstract_step(config, batch: int, seq: int):
    """(grad_fn, params, tokens) for one value_and_grad step over abstract
    avals — nothing is allocated, so the measurement isolates program
    shape from memory. Dispatches to the fused linear+CE when the config
    selects it (loss_vocab_chunk), like the real train loops."""
    from torchft_tpu.models.llama import Llama, cross_entropy_loss

    model = Llama(config)
    tokens = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32)
    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    )

    def loss_fn(p, toks):
        if config.loss_vocab_chunk is not None:
            return model.apply(p, toks[:, :-1], targets=toks[:, 1:])
        logits = model.apply(p, toks[:, :-1])
        return cross_entropy_loss(logits, toks[:, 1:])

    return jax.jit(jax.value_and_grad(loss_fn)), params, tokens


def _measure(config, batch: int = 1, seq: int = 512) -> dict:
    grad_fn, params, tokens = _abstract_step(config, batch, seq)

    t0 = time.perf_counter()
    lowered = grad_fn.lower(params, tokens)
    t_lower = time.perf_counter() - t0
    hlo_bytes = len(lowered.as_text())
    t0 = time.perf_counter()
    lowered.compile()
    t_compile = time.perf_counter() - t0
    return {
        "batch": batch,
        "seq": seq,
        "lower_s": round(t_lower, 3),
        "hlo_bytes": hlo_bytes,
        "compile_s": round(t_compile, 3),
    }


def _measure_memory(config, batch: int = 4, seq: int = 1024) -> dict:
    """XLA temp-buffer bytes for one value_and_grad step — the compiler's
    own accounting of peak intermediate memory (CompiledMemoryStats), the
    honest CPU-side proxy for HBM pressure of the fused-CE and remat
    paths."""
    grad_fn, params, tokens = _abstract_step(config, batch, seq)
    compiled = grad_fn.lower(params, tokens).compile()
    stats = compiled.memory_analysis()
    return {
        "batch": batch,
        "seq": seq,
        "temp_bytes": int(stats.temp_size_in_bytes),
        "temp_gib": round(stats.temp_size_in_bytes / 2**30, 3),
    }


def main() -> None:
    from torchft_tpu.models.llama import large_bench_config

    out = sys.argv[1] if len(sys.argv) > 1 else "SCAN_COMPILE_BENCH.json"
    # The bench 'large' dims from the SHARED flagship definition, with
    # the features this bench measures (scan_layers, remat, fused CE)
    # reset to off so each _measure variant can flip them individually.
    base = large_bench_config(
        attention_impl="auto", scan_layers=False, loss_vocab_chunk=None,
        remat="none",
    )
    results = {"device_kind": jax.devices()[0].platform, "rows": []}
    for n_layers in (6, 12, 24):
        cfg = replace(base, n_layers=n_layers)
        row = {"n_layers": n_layers}
        row["loop"] = _measure(cfg)
        row["scan"] = _measure(replace(cfg, scan_layers=True))
        row["hlo_ratio_loop_over_scan"] = round(
            row["loop"]["hlo_bytes"] / row["scan"]["hlo_bytes"], 2
        )
        results["rows"].append(row)
        print(json.dumps(row), flush=True)

    # Peak intermediate memory: materialized CE vs fused CE vs fused+remat
    # on the scanned 12-layer stack (vocab 32768 — the f32 logits alone are
    # batch*seq*vocab*4 = 512 MiB at 4x1024).
    mem_base = replace(base, n_layers=12, scan_layers=True)
    mem = {
        "materialized_ce": _measure_memory(mem_base),
        "fused_ce": _measure_memory(replace(mem_base, loss_vocab_chunk=4096)),
        "fused_ce_remat_dots": _measure_memory(
            replace(mem_base, loss_vocab_chunk=4096, remat="dots")
        ),
    }
    mem["fused_ce_savings_gib"] = round(
        mem["materialized_ce"]["temp_gib"] - mem["fused_ce"]["temp_gib"], 3
    )
    results["memory"] = mem
    print(json.dumps(mem), flush=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
