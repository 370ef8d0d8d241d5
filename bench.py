#!/usr/bin/env python
"""Benchmark: fault-tolerant training throughput vs plain JAX on this chip.

Phases (one process, on the local accelerator):
 1. plain jitted train step — the no-fault-tolerance ceiling;
 2. Streaming DiLoCo through the full tpuft path (fused inner step, fp8
    outer syncs) — the headline metric;
 3. per-step FT-DDP with fp8 device-quantized pipelined gradient sync;
 4. a 2-replica-group (threads) drill that measures the actual cross-group
    wire sync cost, quorum latency percentiles, and steps lost when one
    group is killed mid-run.

The reference (pytorch/torchft) publishes no absolute numbers (BASELINE.md),
so the headline is fault-tolerant tokens/sec with ``vs_baseline`` =
FT throughput / plain throughput on identical hardware — 1.0 means the
fault-tolerance layer is free; the reference's design goal is the same
"async quorum + overlapped comm ≈ no overhead" property (SURVEY.md §6).

    python bench.py           # needs a TPU: exits non-zero when jax answers
                              # on anything else, and never falls back
    JAX_PLATFORMS=cpu TPUFT_BENCH_MODEL=default python bench.py --cpu
                              # a CPU run, asked for by name (the variable
                              # selects the CPU, the flag says it is meant;
                              # size it: TPUFT_BENCH_STEPS / _SYNC_EVERY /
                              # _SYNC_DELAY); the line it prints says
                              # platform cpu and carries no MFU

``TPUFT_BENCH_MODEL`` picks the config: ``large`` (~445M, the default) or
``default`` (27M); ``--cpu`` has no default and must name one. Any phase
that fails raises: there is no zero line and no retry. Prints exactly one
JSON line, which names the device it ran on.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def _ft_phase_fields() -> dict:
    """Per-phase FT accounting from the in-process metrics registry
    (torchft_tpu.metrics), flattened into ``ft_phase_*`` JSON fields —
    the where-does-the-tax-go decomposition next to the end-to-end
    ``ft_ddp_step_overhead_ms``. Purely additive: every pre-existing
    bench key is untouched. The registry is reset after warmup so compile
    time never contaminates the dispatch/sync means."""
    from torchft_tpu import metrics

    fields: dict = {}
    for metric, short in (
        ("tpuft_quorum_seconds", "quorum"),
        ("tpuft_commit_barrier_seconds", "commit_barrier"),
        ("tpuft_device_sync_seconds", "device_sync"),
        ("tpuft_update_dispatch_seconds", "update_dispatch"),
        ("tpuft_wire_bucket_seconds", "wire_bucket"),
        ("tpuft_quantized_pipeline_seconds", "quantized_pipeline"),
        ("tpuft_pg_configure_seconds", "pg_configure"),
        ("tpuft_heal_send_seconds", "heal_send"),
        ("tpuft_heal_recv_seconds", "heal_recv"),
    ):
        stats = metrics.histogram_stats(metric)
        if stats["count"]:
            fields[f"ft_phase_{short}_ms_mean"] = round(stats["mean"] * 1000, 3)
            fields[f"ft_phase_{short}_count"] = stats["count"]
    for counter, short in (
        ("tpuft_commits_total", "commits"),
        ("tpuft_commit_failures_total", "commit_failures"),
        ("tpuft_rollbacks_total", "rollbacks"),
        ("tpuft_phantom_commits_total", "phantom_commits"),
        ("tpuft_heals_total", "heals"),
        ("tpuft_errors_total", "errors"),
        ("tpuft_wire_bytes_total", "wire_bytes"),
    ):
        total = metrics.counter_total(counter)
        fields[f"ft_phase_{short}_total"] = (
            int(total) if float(total).is_integer() else total
        )
    return fields


def _ft_goodput_fields(t0: float, t1: float) -> dict:
    """Goodput attribution over the steady-state measurement window: the
    same conservation-exact trace-ring fold the fleet ledger runs
    (torchft_tpu.goodput.fold_events), reduced to the headline
    ``goodput_fraction`` plus the top-2 badput buckets. Additive like
    ``_ft_phase_fields``; empty when the trace plane is off or the window
    collapsed, so every pre-existing bench key is untouched."""
    from torchft_tpu import goodput, tracing

    journal = tracing.default()
    if not journal.enabled or t1 <= t0:
        return {}
    seconds = goodput.fold_events(journal._copy_ring(), t0, t1)
    wall = sum(seconds.values())
    if wall <= 0:
        return {}
    fields: dict = {
        "goodput_fraction": round(
            seconds.get("committed_compute", 0.0) / wall, 4
        )
    }
    for i, (bucket, secs) in enumerate(goodput.top_badput(seconds, n=2)):
        fields[f"badput_{i + 1}_bucket"] = bucket
        fields[f"badput_{i + 1}_share"] = round(secs / wall, 4)
    return fields


STEPS = int(os.environ.get("TPUFT_BENCH_STEPS", "20"))
WARMUP = 3
BATCH = int(os.environ.get("TPUFT_BENCH_BATCH", "8"))
SEQ = int(os.environ.get("TPUFT_BENCH_SEQ", "512"))

# Peak bf16 matmul throughput per chip, keyed by ``device_kind`` (for the
# MFU estimate). Source: Google Cloud documentation, "TPU v5e" system
# architecture page (197 TFLOP/s bf16 per chip); the other rows from the
# same documentation's per-generation pages.
_PEAK_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v6e": 918.0,
    "TPU v5 lite": 197.0,
}


def _peak_tflops(device) -> float:
    """The table's peak for this device; a ``device_kind`` that is not in
    it is an error, not a null MFU."""
    kind = str(device.device_kind)
    for name, peak in _PEAK_TFLOPS.items():
        if name.lower() in kind.lower():
            return peak
    raise KeyError(
        f"device_kind {kind!r} is not in bench._PEAK_TFLOPS: add its "
        "published bf16 peak (with the source) before reporting MFU on it"
    )


def main(cpu: bool = False) -> None:
    import jax
    import jax.numpy as jnp
    import optax

    from torchft_tpu.models.llama import Llama, LlamaConfig, cross_entropy_loss

    # One process, one device: the platform that answered is checked before
    # anything is measured, and printed with the result.
    from torchft_tpu.utils.platform import (
        cpu_by_name, enable_compile_cache, require_tpu,
    )

    model_name = os.environ.get("TPUFT_BENCH_MODEL")
    if not cpu:
        require_tpu()
    elif not cpu_by_name() or not model_name:
        raise SystemExit(
            "bench --cpu: the caller selects the CPU (JAX_PLATFORMS=cpu) — "
            "nothing here does it in code — and names the config "
            "(TPUFT_BENCH_MODEL=large|default)"
        )
    device = jax.devices()[0]
    enable_compile_cache()

    global BATCH, SEQ
    model_name = model_name or "large"
    if model_name == "large":
        # The ~445M config: enough compute per step that dispatch latency
        # stops dominating, with the fused Pallas attention kernel on the
        # long sequence. ONE definition shared with the compile bench and
        # the Mosaic cross-lowering gate (the factory documents the
        # choices). This supersedes an explicit
        # TPUFT_BENCH_SEQ — the workload is part of the named config.
        # dots-remat recomputes only elementwise ops and MFU counts 6N
        # model FLOPs either way — the recompute cost lands in the
        # measured step time.
        from torchft_tpu.models.llama import large_bench_config

        BATCH = 4
        config = large_bench_config()
        SEQ = config.max_seq_len
    elif model_name == "default":
        config = LlamaConfig(
            vocab_size=8192,
            dim=512,
            n_layers=6,
            n_heads=8,
            n_kv_heads=4,
            ffn_hidden=1536,
            max_seq_len=SEQ,
            dtype=jnp.bfloat16,
        )
    else:
        raise SystemExit(
            f"TPUFT_BENCH_MODEL={model_name!r} is not one of large, default"
        )
    model = Llama(config)
    tokens = jnp.zeros((BATCH, SEQ + 1), dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :SEQ])
    n_params = sum(
        int(leaf.size) for leaf in jax.tree_util.tree_leaves(params)
    )
    tx = optax.sgd(0.01, momentum=0.9)

    def loss_fn(p, batch_tokens):
        if config.loss_vocab_chunk is not None:
            # Fused linear+CE: the (b, s, vocab) logits never materialize
            # (ops/cross_entropy.py) — same FLOPs, so no MFU skew.
            return model.apply(
                p, batch_tokens[:, :-1], targets=batch_tokens[:, 1:]
            )
        logits = model.apply(p, batch_tokens[:, :-1])
        return cross_entropy_loss(logits, batch_tokens[:, 1:])


    @jax.jit
    def plain_step(p, opt_state, batch_tokens):
        loss, grads = jax.value_and_grad(loss_fn)(p, batch_tokens)
        updates, opt_state = tx.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state, loss

    def batch_for(step: int):
        return jax.random.randint(
            jax.random.PRNGKey(step), (BATCH, SEQ + 1), 0, config.vocab_size
        )

    tokens_per_step = BATCH * SEQ

    # ---- fault-tolerant paths ----
    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.local_sgd import DiLoCo
    from torchft_tpu.manager import Manager
    from torchft_tpu.optim import Optimizer
    from torchft_tpu.parallel.native_pg import ProcessGroupNative
    from torchft_tpu.parallel.store import StoreClient, StoreServer

    def make_manager(use_async_quorum: bool, commit_pipeline_depth: int = 0):
        lighthouse = LighthouseServer(min_replicas=1, join_timeout_ms=100)
        store = StoreServer()
        pg = ProcessGroupNative(timeout=30.0)
        manager = Manager(
            pg=pg,
            min_replica_size=1,
            store=StoreClient(store.address()),
            store_addr=store.address(),
            lighthouse_addr=lighthouse.address(),
            replica_id="bench",
            timeout=30.0,
            quorum_timeout=60.0,
            use_async_quorum=use_async_quorum,
            commit_pipeline_depth=commit_pipeline_depth,
        )
        return manager, (manager, pg, store, lighthouse)

    def teardown(handles) -> None:
        manager, pg, store, lighthouse = handles
        manager.shutdown(wait=False)
        pg.shutdown()
        store.shutdown()
        lighthouse.shutdown()

    # Headline: Streaming DiLoCo (the cross-DCN semi-sync config the
    # reference benchmarks against torchtitan; sync_every matches its demo,
    # train_diloco.py:195-204). Inner steps run fused (ONE jitted dispatch
    # for loss+grad+update); the cross-replica pseudogradient sync amortizes
    # over sync_every steps.
    sync_every = int(os.environ.get("TPUFT_BENCH_SYNC_EVERY", "20"))
    # Delay must leave room inside the per-fragment cycle; a configuration
    # that does not is surfaced loudly by DiLoCo, not clamped here.
    fragment_sync_delay = int(os.environ.get("TPUFT_BENCH_SYNC_DELAY", "5"))
    diloco_manager, diloco_handles = make_manager(use_async_quorum=False)
    algo = DiLoCo(
        diloco_manager,
        inner_tx=tx,
        outer_tx=optax.sgd(0.7, momentum=0.9, nesterov=True),
        params=params,
        sync_every=sync_every,
        n_fragments=2,
        should_quantize=True,
        fragment_sync_delay=fragment_sync_delay,
    )
    diloco_step = algo.make_step_fn(loss_fn)

    # Secondary: per-step FT-DDP via Optimizer.make_step_fn — for this
    # single-group config the lone-replica path fuses loss+grad+update into
    # ONE jitted dispatch (bitwise the plain program), adopted only under
    # the commit barrier; with >1 group the same step_fn switches to the
    # pipelined fp8 bucket sync + speculative update.
    ddp_manager, ddp_handles = make_manager(use_async_quorum=True)
    opt = Optimizer(ddp_manager, tx, params)
    ddp_steps = max(STEPS // 2, 6)
    quorum_times: list[float] = []
    # Warmup quorum waits (incl. cold first-quorum formation) must not
    # contaminate the steady-state p50.
    recording = [False]
    ddp_step = opt.make_step_fn(
        loss_fn,
        should_quantize=True,
        on_quorum=lambda dt: quorum_times.append(dt) if recording[0] else None,
    )

    # The same per-step FT-DDP path with the commit PIPELINED (depth 1):
    # step N's device sync + vote resolve under step N+1's dispatch, so
    # the serialized readiness wait and commit RPC leave the critical path.
    pipe_manager, pipe_handles = make_manager(
        use_async_quorum=True, commit_pipeline_depth=1
    )
    pipe_opt = Optimizer(pipe_manager, tx, params)
    pipe_step = pipe_opt.make_step_fn(loss_fn, should_quantize=True)

    # The decomposition datum that sits NEXT TO the overhead field: one
    # in-flight readiness wait, measured the way the FT step pays it
    # (dispatch a jitted op, immediately ask for readiness). On a chip the
    # process owns this is the tiny op's own run time plus the host's
    # wake-up — the floor under ft_ddp_step_overhead_ms.
    def measure_device_sync_rtt() -> "float | None":
        probe = jax.jit(lambda x: (x @ x).sum())
        x = jnp.ones((256, 256), jnp.float32)
        float(probe(x))  # compile + settle
        samples = []
        for _ in range(5):
            y = probe(x)
            t0 = time.monotonic()
            jax.block_until_ready(y)
            samples.append(time.monotonic() - t0)
        return round(1000 * statistics.median(samples), 3)

    # ---- measurement: INTERLEAVED rounds, order-alternated, summed ----
    # Per-step compute on this box drifts several percent over minutes
    # (thermal / scheduler / memory pressure), so sequential phases hand
    # whichever config ran in the quietest window a free advantage — and a
    # best-of max over windows then AMPLIFIES the noise into the ratio
    # (observed both directions: 0.94 and 1.11 for the same ~10ms/step FT
    # machinery). Instead every round measures all three configs back to
    # back, the round order flips each time (first slot pays any post-warmup
    # cold cost), and tps comes from TOTAL steps / TOTAL elapsed across
    # rounds — summation is unbiased under drift where max is not.
    # Timing closes each window by fetching a value, which waits for the
    # whole dispatched chain it depends on.
    diloco_round_steps = sync_every  # one full cycle (incl. its sync) per round
    totals = {
        "plain": [0, 0.0],
        "ddp": [0, 0.0],
        "ddp_pipe": [0, 0.0],
        "diloco": [0, 0.0],
    }
    device_sync_rtt_ms = None
    try:
        # Warmups: plain, one full DiLoCo cycle, two DDP steps (each mode).
        opt_state = tx.init(params)
        p = params
        for step in range(WARMUP):
            p, opt_state, loss = plain_step(p, opt_state, batch_for(step))
        float(loss)
        for step in range(sync_every):
            loss, _ = diloco_step(batch_for(step))
        float(loss)
        for step in range(2):
            ddp_step(batch_for(step))
        _ = float(jax.tree_util.tree_leaves(opt.params)[0].sum())
        for step in range(2):
            pipe_step(batch_for(step))
        pipe_opt.flush_pipeline()
        _ = float(jax.tree_util.tree_leaves(pipe_opt.params)[0].sum())
        device_sync_rtt_ms = measure_device_sync_rtt()
        recording[0] = True
        # Phase accounting starts clean here: the warmups above paid the
        # jit compiles, and compile time inside the dispatch/sync timers
        # would swamp the steady-state means the ft_phase_* fields report.
        from torchft_tpu import metrics as ft_metrics

        ft_metrics.REGISTRY.reset()
        goodput_window_t0 = time.monotonic()

        def run_plain() -> None:
            nonlocal p, opt_state
            t0 = time.monotonic()
            for step in range(STEPS):
                p, opt_state, loss = plain_step(p, opt_state, batch_for(step))
            float(loss)
            totals["plain"][0] += STEPS
            totals["plain"][1] += time.monotonic() - t0

        def run_ddp() -> None:
            t0 = time.monotonic()
            committed = 0
            for step in range(ddp_steps):
                _, ok = ddp_step(batch_for(step))
                committed += bool(ok)
            _ = float(jax.tree_util.tree_leaves(opt.params)[0].sum())
            totals["ddp"][0] += committed
            totals["ddp"][1] += time.monotonic() - t0

        def run_ddp_pipelined() -> None:
            t0 = time.monotonic()
            committed = 0
            for step in range(ddp_steps):
                _, prev_ok = pipe_step(batch_for(step))
                committed += bool(prev_ok)
            # The trailing in-flight step resolves inside the window so
            # the measured wall carries the FULL cost of every counted
            # step (conservative: the last sync isn't hidden by a next
            # dispatch here).
            committed += bool(pipe_opt.flush_pipeline())
            _ = float(jax.tree_util.tree_leaves(pipe_opt.params)[0].sum())
            totals["ddp_pipe"][0] += committed
            totals["ddp_pipe"][1] += time.monotonic() - t0

        def run_diloco() -> None:
            t0 = time.monotonic()
            for step in range(diloco_round_steps):
                loss, _ = diloco_step(batch_for(step))
            float(loss)
            totals["diloco"][0] += diloco_round_steps
            totals["diloco"][1] += time.monotonic() - t0

        order = [run_plain, run_ddp, run_ddp_pipelined, run_diloco]
        for _round in range(2):
            for run in order:
                run()
            order.reverse()
    finally:
        teardown(diloco_handles)
        teardown(ddp_handles)
        teardown(pipe_handles)

    def _tps(key: str) -> float:
        steps_done, elapsed = totals[key]
        return steps_done * tokens_per_step / elapsed if elapsed and steps_done else 0.0

    plain_tps, ddp_tps, diloco_tps = _tps("plain"), _tps("ddp"), _tps("diloco")
    ddp_pipe_tps = _tps("ddp_pipe")
    quorum_p50_ms = round(1000 * statistics.median(quorum_times), 2) if quorum_times else None

    # Snapshot the phase breakdown BEFORE the two-group drill: its heals
    # and kill-recovery commits belong to the drill's own fields, not to
    # the steady-state step decomposition measured above.
    ft_phase = _ft_phase_fields()
    ft_goodput = _ft_goodput_fields(goodput_window_t0, time.monotonic())

    # ---- 2-replica-group drill: wire sync cost + kill recovery ----
    two_group = _two_group_drill()

    # On the chip, also run the Pallas kernels through their compiled
    # (Mosaic) path against their references — a failed check fails the
    # bench; it is not reported as a string in a run that exits 0.
    flash_on_chip = None
    quant_on_chip = None
    if device.platform == "tpu":
        from torchft_tpu.ops import flash_attention, quantization

        flash_on_chip = flash_attention.verify_on_chip()["ok"]
        quant_on_chip = quantization.verify_on_chip()["ok"]

    # MFU estimate for the headline path: causal-LM forward+backward is
    # ~6·N_params FLOPs/token plus the attention term 12·L·d·s. A device
    # metric: reported only from a chip run, against the table's peak.
    flops_per_token = 6.0 * n_params + 12.0 * config.n_layers * config.dim * SEQ
    model_tflops = diloco_tps * flops_per_token / 1e12
    mfu_pct = (
        round(100.0 * model_tflops / _peak_tflops(device), 2)
        if device.platform == "tpu"
        else None
    )

    # Per-step-commit FT (the ft_ddp path) performs one readiness call
    # (jax.block_until_ready) per step before its vote resolves, where the
    # plain and DiLoCo inner loops just chain dispatches and fetch once.
    # Attribute that cost END-TO-END — the per-step wall difference
    # between the measured ft_ddp and plain phases: the call costs what
    # the remaining compute costs, so the field reads ≈ quorum + commit
    # RPCs plus the lost dispatch overlap. Phase-to-phase drift can exceed
    # that few-ms signal on quiet hosts, so the field can legitimately go
    # NEGATIVE — read values ≈0 or below as "overhead within noise", not
    # as a real speedup. The emulated-DCN bench shows the same structure
    # deliberately: per-step sync pays RTT every step, streaming DiLoCo
    # hides it.
    ft_ddp_step_overhead_ms = (
        round(1000 * (tokens_per_step / ddp_tps - tokens_per_step / plain_tps), 2)
        if ddp_tps and plain_tps
        else None
    )
    # Pipelined mode's residual overhead: with the sync off the critical
    # path this should collapse toward the quorum + commit RPC cost; read
    # it NEXT TO device_sync_rtt_ms.
    ft_ddp_pipelined_step_overhead_ms = (
        round(
            1000 * (tokens_per_step / ddp_pipe_tps - tokens_per_step / plain_tps), 2
        )
        if ddp_pipe_tps and plain_tps
        else None
    )

    print(
        json.dumps(
            {
                "metric": "ft_diloco_tokens_per_sec",
                "value": round(diloco_tps, 1),
                "unit": "tokens/sec",
                "vs_baseline": round(diloco_tps / plain_tps, 4),
                "plain_tokens_per_sec": round(plain_tps, 1),
                "ft_ddp_tokens_per_sec": round(ddp_tps, 1),
                "ft_ddp_vs_baseline": round(ddp_tps / plain_tps, 4) if plain_tps else None,
                "ft_ddp_pipelined_tokens_per_sec": round(ddp_pipe_tps, 1),
                "ft_ddp_pipelined_vs_baseline": (
                    round(ddp_pipe_tps / plain_tps, 4) if plain_tps else None
                ),
                "commit_pipeline_depth": 1,
                "model": model_name,
                "sync_every": sync_every,
                "fragment_sync_delay": fragment_sync_delay,
                "bench_steps": STEPS,
                "model_tflops_per_sec": round(model_tflops, 3),
                "mfu_pct": mfu_pct,
                "platform": device.platform,
                "device_kind": str(device.device_kind),
                "device_count": len(jax.devices()),
                "n_params": n_params,
                "flash_kernel_on_chip": flash_on_chip,
                "quant_kernel_on_chip": quant_on_chip,
                "quorum_p50_ms": quorum_p50_ms,
                "ft_ddp_step_overhead_ms": ft_ddp_step_overhead_ms,
                "ft_ddp_pipelined_step_overhead_ms": ft_ddp_pipelined_step_overhead_ms,
                "device_sync_rtt_ms": device_sync_rtt_ms,
                **ft_phase,
                **ft_goodput,
                **two_group,
            }
        )
    )


def _two_group_drill() -> dict:
    """2 replica groups on threads: measures the real cross-group wire sync
    cost per step, quorum latency with >1 participant, and steps lost when
    one group is killed mid-run (the BASELINE.md north stars)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.ddp import ft_allreduce_gradients
    from torchft_tpu.manager import Manager
    from torchft_tpu.optim import Optimizer
    from torchft_tpu.parallel.native_pg import ProcessGroupNative
    from torchft_tpu.parallel.store import StoreClient, StoreServer
    from torchft_tpu.utils.profiling import heal_wall_times

    # Tiny model: this drill measures coordination + wire costs, not FLOPs
    # (both thread-groups share one chip; compute throughput is phase 2/3's
    # job).
    def init_params(seed=0):
        key = jax.random.PRNGKey(seed)
        return {
            "w1": jax.random.normal(key, (256, 256), jnp.float32) * 0.02,
            "w2": jax.random.normal(key, (256, 128), jnp.float32) * 0.02,
        }

    def grad_like(params, step):
        return jax.tree_util.tree_map(
            lambda a: jnp.full(a.shape, 1e-3 * (step + 1), a.dtype), params
        )

    n_steps = 12
    kill_at = 5
    lighthouse = LighthouseServer(min_replicas=1, join_timeout_ms=2000)
    sync_times: dict[int, list] = {0: [], 1: []}
    quorum_times: dict[int, list] = {0: [], 1: []}
    failed_commits = {0: 0, 1: 0}
    committed_steps = {0: 0, 1: 0}
    commit_times: dict[int, list] = {0: [], 1: []}
    kill_time: dict[str, float] = {}

    class _Killed(Exception):
        pass

    def group(idx: int) -> None:
        attempts = 0
        while attempts < 3:
            attempts += 1
            store = StoreServer()
            # The C++ ring engine (the production default): ~2x lower sync
            # p50 than the Python TCP fallback in this same drill.
            pg = ProcessGroupNative(timeout=20.0)
            manager = Manager(
                pg=pg,
                min_replica_size=1,
                store=StoreClient(store.address()),
                store_addr=store.address(),
                lighthouse_addr=lighthouse.address(),
                replica_id=f"bench2g_{idx}",
                timeout=20.0,
                quorum_timeout=30.0,
                use_async_quorum=True,
                heartbeat_interval=0.05,
            )
            opt = Optimizer(manager, optax.sgd(0.05), init_params())
            try:
                while manager.current_step() < n_steps:
                    step = manager.current_step()
                    if idx == 1 and step == kill_at and attempts == 1:
                        kill_time["t"] = time.monotonic()
                        raise _Killed()  # simulated process death
                    q0 = time.monotonic()
                    opt.begin_step()
                    manager.wait_quorum()
                    quorum_times[idx].append(time.monotonic() - q0)
                    grads = grad_like(opt.params, step)
                    s0 = time.monotonic()
                    avg = ft_allreduce_gradients(manager, grads)
                    sync_times[idx].append(time.monotonic() - s0)
                    if opt.step(avg):
                        committed_steps[idx] += 1
                        commit_times[idx].append(time.monotonic())
                    else:
                        failed_commits[idx] += 1
                return
            except _Killed:
                time.sleep(0.5)  # supervisor restart delay
                continue
            finally:
                manager.shutdown(wait=False)
                pg.shutdown()
                store.shutdown()

    threads = [threading.Thread(target=group, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    lighthouse.shutdown()

    survivor_sync = sync_times[0]
    p50_sync_ms = (
        round(1000 * statistics.median(survivor_sync), 2) if survivor_sync else None
    )
    all_quorum = quorum_times[0] + quorum_times[1]
    return {
        "two_group_sync_p50_ms": p50_sync_ms,
        "two_group_quorum_p50_ms": (
            round(1000 * statistics.median(all_quorum), 2) if all_quorum else None
        ),
        # Both groups share one host: these p50s are a control-plane floor
        # over localhost, NOT a DCN measurement. The flag travels with the
        # numbers so no downstream table can quote them without the caveat.
        "two_group_numbers_are_loopback": True,
        # Survivor commits that failed around the kill = steps lost to the
        # failure (north star: < 1 outer step per kill).
        "steps_lost_per_kill": failed_commits[0],
        "two_group_committed_steps": committed_steps,
        # Kill -> first committed step, per role: the operator-facing
        # recovery TIME ("< 1 outer step" counted above, timed here). The
        # joiner's number includes the 0.5 s simulated supervisor restart
        # delay plus rejoin + live heal.
        "heal_wall_time_s": heal_wall_times(kill_time.get("t"), commit_times),
    }


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--cpu",
        action="store_true",
        help="run on the CPU by name (the caller also sets JAX_PLATFORMS=cpu); "
        "without it the bench needs a TPU and exits non-zero when there is none",
    )
    main(cpu=parser.parse_args().cpu)
