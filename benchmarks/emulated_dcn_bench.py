#!/usr/bin/env python
"""Latency-tolerance curve under an emulated DCN link.

Every cross-group number this box can produce natively is loopback, which
says nothing about the design claims that motivate streaming DiLoCo and
the int4 wire (the reference's DiLoCo pitch, reference local_sgd.py:
176-568 design comments): hiding outer-sync latency and halving bytes
only matter under non-zero RTT and bounded bandwidth. This bench injects
both via torchft_tpu.utils.netem (ProcessGroupTCP sends + HTTP heal
serves) and sweeps RTT for:

  1. FT-DDP per-step sync        — degrades with RTT (pays it every step)
  2. Streaming DiLoCo per-step   — holds ~flat (sync amortized/overlapped)
  3. Outer sync fp8 vs int4      — int4 ~2x faster at bounded bandwidth
  4. Heal transfer               — linear in RTT + bytes/bandwidth

Writes EMULATED_DCN_BENCH.json. Usage:

    python benchmarks/emulated_dcn_bench.py [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Any, Dict, List

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

os.environ.setdefault("TPUFT_LOG", "warn")

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import optax

from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.ddp import ft_allreduce_gradients
from torchft_tpu.manager import Manager
from torchft_tpu.optim import Optimizer
from torchft_tpu.parallel import collectives
from torchft_tpu.parallel.process_group import ProcessGroupTCP, ReduceOp
from torchft_tpu.parallel.store import StoreClient, StoreServer
from torchft_tpu.utils import netem

RTTS_MS = [0.0, 1.0, 10.0, 50.0]
GBPS = 1.0
OUTER_MB = 8  # f32 megabytes averaged per outer sync in the micro-bench
HEAL_MB = 8

# A model big enough that an inner step is real compute (~20-40 ms on this
# box): latency hiding is the whole design claim, and there is nothing to
# hide a sync behind when an inner step costs 1 ms. ~790 KB of f32 params.
_DIM = 512
_BATCH = 32

import jax.numpy as jnp


def _bench_params() -> Any:
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {
        "w1": jax.random.normal(k1, (_DIM, _DIM), dtype=jnp.float32) * 0.05,
        "b1": jnp.zeros((_DIM,), dtype=jnp.float32),
        "w2": jax.random.normal(k2, (_DIM, _DIM), dtype=jnp.float32) * 0.05,
        "b2": jnp.zeros((_DIM,), dtype=jnp.float32),
    }


@jax.jit
def _bench_loss(params: Any, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return jnp.mean((h @ params["w2"] + params["b2"] - y) ** 2)


_bench_grad = jax.jit(jax.grad(_bench_loss))


def _bench_batch(step: int, group: int) -> Any:
    kx, ky = jax.random.split(jax.random.PRNGKey(1000 * group + step))
    return (
        jax.random.normal(kx, (_BATCH, _DIM), dtype=jnp.float32),
        jax.random.normal(ky, (_BATCH, _DIM), dtype=jnp.float32),
    )


def _make_manager(group: int, lh_addr: str, store: StoreServer, **kw: Any) -> Manager:
    client = StoreClient(store.address(), prefix=f"g{group}")
    return Manager(
        pg=ProcessGroupTCP(timeout=30.0),
        min_replica_size=2,
        store=client,
        store_addr=store.address() + f"/g{group}",
        use_async_quorum=False,
        group_rank=0,
        group_world_size=1,
        lighthouse_addr=lh_addr,
        replica_id=f"dcnbench_{group}",
        heartbeat_interval=0.5,
        timeout=30.0,
        quorum_timeout=60.0,
        **kw,
    )


def bench_ft_ddp(lh_addr: str, num_steps: int) -> float:
    """Mean committed-step wall time (s) for 2-group FT-DDP; every step
    pays the cross-group allreduce on the emulated link."""
    step_walls: Dict[int, List[float]] = {0: [], 1: []}

    def replica(group: int) -> None:
        store = StoreServer()
        manager = _make_manager(group, lh_addr, store)
        opt = Optimizer(manager, optax.sgd(0.05), _bench_params())
        try:
            while manager.current_step() < num_steps:
                step = manager.current_step()
                t0 = time.perf_counter()
                opt.begin_step()
                manager.wait_quorum()
                x, y = _bench_batch(step, group)
                grads = _bench_grad(opt.params, x, y)
                avg = ft_allreduce_gradients(manager, grads)
                if opt.step(avg):
                    step_walls[group].append(time.perf_counter() - t0)
        finally:
            manager.shutdown(wait=False)
            store.shutdown()

    with ThreadPoolExecutor(max_workers=2) as pool:
        futs = [pool.submit(replica, g) for g in range(2)]
        for f in futs:
            f.result(timeout=600)
    # Mean over both groups, skipping each group's first two steps (jit
    # compile + PG rendezvous).
    walls = step_walls[0][2:] + step_walls[1][2:]
    return float(np.mean(walls))


def bench_diloco(lh_addr: str, num_outer: int, sync_every: int) -> Dict[str, float]:
    """Streaming DiLoCo (2 fragments, quantized wire): mean per-inner-step
    wall including sync steps (the amortized cost a user sees)."""
    from torchft_tpu.local_sgd import DiLoCo

    per_step: Dict[int, List[float]] = {0: [], 1: []}

    def replica(group: int) -> None:
        store = StoreServer()
        manager = _make_manager(group, lh_addr, store)
        try:
            algo = DiLoCo(
                manager,
                inner_tx=optax.sgd(0.05),
                outer_tx=optax.sgd(0.7, momentum=0.9, nesterov=True),
                params=_bench_params(),
                sync_every=sync_every,
                n_fragments=2,
                fragment_sync_delay=4,
                should_quantize=True,
            )
            inner_iter = 0
            while manager.current_step() < num_outer:
                t0 = time.perf_counter()
                x, y = _bench_batch(1000 + inner_iter, group)
                grads = _bench_grad(algo.params, x, y)
                algo.step(grads)
                per_step[group].append(time.perf_counter() - t0)
                inner_iter += 1
        finally:
            manager.shutdown(wait=False)
            store.shutdown()

    with ThreadPoolExecutor(max_workers=2) as pool:
        futs = [pool.submit(replica, g) for g in range(2)]
        for f in futs:
            f.result(timeout=600)
    # Each fragment's first sync pays one-time jit compiles (~1 s on this
    # box, measured); the first sync_every inner steps cover both
    # fragments' first syncs. Mean AFTER that warmup so the amortized
    # outer-sync cost stays in the number (a median would hide it).
    walls = per_step[0][sync_every:] + per_step[1][sync_every:]
    return {"per_step_s": float(np.mean(walls)), "p_max_s": float(np.max(walls))}


def bench_outer_sync(wire_dtype: str) -> Dict[str, float]:
    """Wall time of one outer-sync exchange of an ALREADY-quantized
    OUTER_MB-of-f32 pseudogradient (the streaming-DiLoCo hot path:
    quantization runs on device inside the jitted sync step, so the wire
    exchange is what the link sees) between 2 ranks over the emulated
    link. Also reports the wire bytes per rank."""
    from torchft_tpu.ops import quantization as q

    n = OUTER_MB * 1024 * 1024 // 4
    store = StoreServer()
    results: Dict[int, float] = {}
    wire_bytes: Dict[int, int] = {}

    def rank(r: int) -> None:
        pg = ProcessGroupTCP(timeout=60.0)
        pg.configure(store.address() + "/outer", f"rank{r}", r, 2)
        arr = np.full(n, float(r + 1), dtype=np.float32)
        payload, scales = q.quantize_blocks(arr, wire=wire_dtype)
        wire_bytes[r] = payload.nbytes + scales.nbytes
        try:
            # Warmup (rendezvous + first-message costs), then timed run.
            collectives.allreduce_quantized_wire(
                payload, scales, ReduceOp.AVG, pg
            ).wait()
            t0 = time.perf_counter()
            collectives.allreduce_quantized_wire(
                payload, scales, ReduceOp.AVG, pg
            ).wait()
            results[r] = time.perf_counter() - t0
        finally:
            pg.shutdown()

    with ThreadPoolExecutor(max_workers=2) as pool:
        futs = [pool.submit(rank, r) for r in range(2)]
        for f in futs:
            f.result(timeout=600)
    store.shutdown()
    return {"wall_s": float(max(results.values())), "wire_mb": wire_bytes[0] / 1e6}


def bench_quorum_rtt(rtt_ms: float, steps: int = 12) -> Dict[str, float]:
    """Control-plane sensitivity to lighthouse RTT: per-step quorum and
    commit-barrier p50 for one replica group whose manager reaches the
    lighthouse through a netem.LatencyProxy (the native manager's
    quorum/heartbeat RPCs ride it; the manager<->local-rank wire stays
    loopback, same-host by design). The quorum round pays the hop; the
    commit barrier is intra-group (local ranks) and should stay flat."""
    from torchft_tpu.parallel.process_group import ProcessGroupDummy

    lh = LighthouseServer(bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=5000)
    proxy = netem.LatencyProxy(lh.address(), rtt_ms)
    store = StoreServer()
    client = StoreClient(store.address(), prefix="cp")
    manager = Manager(
        pg=ProcessGroupDummy(0, 1),
        min_replica_size=1,
        store=client,
        store_addr=store.address() + "/cp",
        use_async_quorum=False,
        group_rank=0,
        group_world_size=1,
        lighthouse_addr=proxy.address(),
        replica_id="cp_rtt",
        heartbeat_interval=0.5,
        timeout=30.0,
        quorum_timeout=60.0,
    )
    quorum_walls: List[float] = []
    commit_walls: List[float] = []
    try:
        for _ in range(steps):
            t0 = time.perf_counter()
            manager.start_quorum()
            t1 = time.perf_counter()
            assert manager.should_commit() is True
            t2 = time.perf_counter()
            quorum_walls.append(t1 - t0)
            commit_walls.append(t2 - t1)
    finally:
        manager.shutdown()
        proxy.shutdown()
        lh.shutdown()
    quorum_walls, commit_walls = quorum_walls[1:], commit_walls[1:]
    return {
        "quorum_p50_ms": round(float(np.median(quorum_walls)) * 1000, 2),
        "commit_p50_ms": round(float(np.median(commit_walls)) * 1000, 2),
    }


def bench_commit_pipeline(quick: bool = False) -> Dict[str, Any]:
    """Commit-pipeline depth sweep {0, 1, 2, 4, auto} × RTT under an
    emulated cross-DC link: the swept RTT is charged BOTH at the device
    sync (``optim._bound_device`` shimmed with
    ``netem.emulated_device_sync`` — an in-flight probe costs completion
    plus one round trip, completed work is free: a generic high-latency
    device model) and at the commit-barrier RPC (the
    control-plane round trip the deployment regime of "Highly Available
    Data Parallel ML training on Mesh Networks" pays per step at 50-100 ms
    cross-DC RTT). The control plane is a scripted lone-replica manager
    (this bench must run without the native plane); the wire is the
    lone-replica identity, the topology of the benchmark's ``ftddp`` cells.

    Expectation encoded in the claims: depth 0 (the default overlapped
    ordering) pays ~RTT every step; a depth-1 window hides the RTT only
    up to ONE step of compute, so it regresses toward +RTT/step once
    RTT > step time; depth >= 2 holds ≈flat at 100 ms because the
    window's votes overlap on the wire across multiple steps' compute;
    and adaptive mode converges onto the best fixed depth at every RTT.
    """
    from unittest.mock import create_autospec, patch

    import torchft_tpu.optim as optim_mod
    from torchft_tpu.checkpointing.transport import CheckpointTransport
    from torchft_tpu.coordination import QuorumResult
    from torchft_tpu.parallel.process_group import ProcessGroup, ProcessGroupDummy

    steps = 5 if quick else 8
    warmup = 2
    auto_warmup = 10 if quick else 16  # the controller converges in-warmup
    rtts = [0.0, 10.0, 50.0, 100.0]
    depths = [("depth0", 0), ("depth1", 1), ("depth2", 2), ("depth4", 4),
              ("auto", "auto")]

    class _FakeStore:
        data = {"manager_addr": b"fake:0", "replica_id": b"cp_bench:0"}

        def get(self, key, timeout=0, wait=True):
            return self.data.get(key)

        def set(self, key, value, timeout=0):
            pass

    def make_scripted_manager(depth, commit_rpc_s: float) -> Manager:
        transport = create_autospec(CheckpointTransport, instance=True)
        transport.metadata.return_value = "http://fake:0"
        with patch("torchft_tpu.manager.ManagerClient", autospec=True):
            manager = Manager(
                pg=ProcessGroupDummy(0, 1),
                min_replica_size=1,
                store=_FakeStore(),
                store_addr="fake:0",
                use_async_quorum=True,
                group_rank=1,  # no embedded native server
                group_world_size=1,
                checkpoint_transport=transport,
                timeout=30.0,
                quorum_timeout=30.0,
                commit_pipeline_depth=depth,
            )
        manager._client._quorum.return_value = QuorumResult(
            quorum_id=1, replica_rank=0, replica_world_size=1,
            recover_src_manager_address="", recover_src_replica_rank=None,
            recover_dst_replica_ranks=[], store_address="fake:0",
            max_step=0, max_rank=0, max_world_size=1, heal=False,
        )

        def commit_rpc(rank, step, vote, timeout):
            time.sleep(commit_rpc_s)
            return vote

        manager._client.should_commit.side_effect = commit_rpc
        return manager

    # Workload: a fused MLP step with enough real compute (~50-80 ms on
    # this box) that there is something to hide a 50 ms probe behind — a
    # depth-1 pipeline can only absorb RTT up to one step of compute, and
    # latency hiding is the design claim being measured.
    dim = 768 if quick else 1024
    batch = 128

    def loss_fn(p, x, y):
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        h = jnp.tanh(h @ p["w2"] + p["b2"])
        return jnp.mean((h @ p["w3"] - y) ** 2)

    def make_params():
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        return {
            "w1": jax.random.normal(k1, (dim, dim), jnp.float32) * 0.05,
            "b1": jnp.zeros((dim,), jnp.float32),
            "w2": jax.random.normal(k2, (dim, dim), jnp.float32) * 0.05,
            "b2": jnp.zeros((dim,), jnp.float32),
            "w3": jax.random.normal(k3, (dim, dim), jnp.float32) * 0.05,
        }

    def batch_for(i):
        kx, ky = jax.random.split(jax.random.PRNGKey(100 + i))
        return (
            jax.random.normal(kx, (batch, dim), jnp.float32),
            jax.random.normal(ky, (batch, dim), jnp.float32),
        )

    # Calibrate the raw compute (no FT, no shim): the baseline every mode
    # is judged against.
    import optax as _optax

    from torchft_tpu.optim import make_jit_fused_step

    tx = _optax.sgd(0.01)
    fused = make_jit_fused_step(tx, loss_fn)
    p, s = make_params(), tx.init(make_params())
    for i in range(warmup):
        loss, p, s = fused(p, s, *batch_for(i))
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for i in range(steps):
        loss, p, s = fused(p, s, *batch_for(i))
    jax.block_until_ready(loss)
    compute_ms = (time.perf_counter() - t0) / steps * 1000

    from torchft_tpu import metrics as ft_metrics

    # The per-phase decomposition (torchft_tpu.metrics histograms) names
    # WHICH phase each depth pays per step: shallow windows keep the
    # device-sync / barrier RTT on the critical path, deep windows hide
    # them under younger steps' compute — the wall sweep shows THAT the
    # window wins, this shows WHERE.
    PHASES = (
        ("tpuft_device_sync_seconds", "device_sync"),
        ("tpuft_commit_barrier_seconds", "commit_barrier"),
        ("tpuft_update_dispatch_seconds", "update_dispatch"),
    )
    real_sync = optim_mod._bound_device
    modes: Dict[str, Dict[str, float]] = {}
    per_phase: Dict[str, Dict[str, Dict[str, float]]] = {}
    auto_final_depth: Dict[str, int] = {}
    for mode, depth in depths:
        rows: Dict[str, float] = {}
        phase_rows: Dict[str, Dict[str, float]] = {}
        for rtt in rtts:
            manager = make_scripted_manager(depth, commit_rpc_s=rtt / 1000.0)
            opt = Optimizer(manager, tx, make_params())
            optim_mod._bound_device = netem.emulated_device_sync(rtt)
            try:
                step_fn = opt.make_step_fn(loss_fn)
                # Adaptive mode gets a longer warmup: the controller
                # deepens one slot per few observations, and the measured
                # window must see the converged depth.
                for i in range(auto_warmup if depth == "auto" else warmup):
                    step_fn(*batch_for(i))
                # Phase histograms cover exactly the measured window (the
                # warmup's compile dispatches would skew the means).
                ft_metrics.REGISTRY.reset()
                t0 = time.perf_counter()
                for i in range(steps):
                    step_fn(*batch_for(i))
                if depth != 0:
                    # The trailing resolutions belong to the window.
                    opt.flush_pipeline()
                wall = time.perf_counter() - t0
                phase_rows[f"{int(rtt)}ms"] = {
                    short: round(
                        ft_metrics.histogram_stats(name)["sum"] / steps * 1000, 2
                    )
                    for name, short in PHASES
                }
                if depth == "auto":
                    auto_final_depth[f"{int(rtt)}ms"] = (
                        manager.commit_pipeline_depth
                    )
            finally:
                optim_mod._bound_device = real_sync
                manager.shutdown(wait=False)
            rows[f"{int(rtt)}ms"] = round(wall / steps * 1000, 2)
        modes[mode] = rows
        per_phase[mode] = phase_rows
        print(json.dumps({"pipeline_depth_mode": mode, "per_step_ms": rows}), flush=True)

    lo, hi = f"{int(rtts[0])}ms", f"{int(rtts[-1])}ms"
    fixed = [m for m, _ in depths if m != "auto"]
    claims = {
        "per_step_compute_ms": round(compute_ms, 2),
        "commit_rpc_rides_swept_rtt": True,
        # Inflation 0 -> 100 ms per depth: depth0/depth1 regress toward
        # +RTT/step (a one-step window hides only ONE round trip); depth2+
        # hold ≈flat (votes overlap across the window's compute).
        "inflation_ms_0_to_100": {
            m: round(modes[m][hi] - modes[m][lo], 2) for m, _ in depths
        },
        "depth2_holds_flat_at_100ms": (
            modes["depth2"][hi] - modes["depth2"][lo]
            < 0.5 * (modes["depth1"][hi] - modes["depth1"][lo])
        ),
        # Adaptive lands within the best fixed depth at every swept RTT
        # (tolerance: 20% + 5 ms of the best fixed wall, noise on a 1-core
        # box).
        "auto_within_best_fixed": {
            f"{int(rtt)}ms": bool(
                modes["auto"][f"{int(rtt)}ms"]
                <= 1.2 * min(modes[m][f"{int(rtt)}ms"] for m in fixed) + 5.0
            )
            for rtt in rtts
        },
        "auto_final_depth": auto_final_depth,
        # The phases the window removes, named: observed per-step device
        # sync + barrier wait at the worst RTT, per depth. Shallow windows
        # carry ~RTT in one of them; deep windows collapse both.
        "observed_phase_ms_at_100ms": {
            m: per_phase[m][hi] for m in per_phase
        },
    }
    return {
        "emulation": "netem.emulated_device_sync at optim._bound_device "
        "(in-flight probe = completion + one full RTT, completed work "
        "free — a generic high-latency-device model) AND the swept RTT "
        "charged on the commit-barrier RPC (cross-DC control plane); "
        "scripted lone-replica manager",
        "device_rtt_sweep_ms": rtts,
        "pipeline_depth": modes,
        "per_phase_ms": per_phase,
        "claims": claims,
    }


def bench_heal() -> float:
    """Wall time to receive a HEAL_MB checkpoint over the emulated link."""
    from torchft_tpu.checkpointing import HTTPTransport

    state = {"w": np.ones(HEAL_MB * 1024 * 1024 // 4, dtype=np.float32)}
    donor = HTTPTransport(num_chunks=4)
    joiner = HTTPTransport()
    try:
        donor.send_checkpoint([1], step=1, state_dict=state, timeout=60)
        t0 = time.perf_counter()
        restored = joiner.recv_checkpoint(0, donor.metadata(), step=1, timeout=60)
        dt = time.perf_counter() - t0
        assert np.array_equal(restored["w"], state["w"])
        return dt
    finally:
        donor.shutdown()
        joiner.shutdown()


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true", help="fewer steps")
    parser.add_argument(
        "--pipeline-only",
        action="store_true",
        help="run only the commit-ordering sweep and merge it into the "
        "existing EMULATED_DCN_BENCH.json (no native plane required)",
    )
    args = parser.parse_args()

    if args.pipeline_only:
        section = bench_commit_pipeline(quick=args.quick)
        out = REPO / "EMULATED_DCN_BENCH.json"
        try:
            result = json.loads(out.read_text())
        except (OSError, json.JSONDecodeError):
            result = {"bench": "emulated_dcn", "device_kind": "cpu"}
        result["commit_pipeline"] = section
        out.write_text(json.dumps(result, indent=2) + "\n")
        print(json.dumps({"commit_pipeline_claims": section["claims"]}), flush=True)
        print(f"wrote {out}", flush=True)
        return
    num_steps = 6 if args.quick else 10
    num_outer = 4 if args.quick else 6
    # 2 fragments x (sync every 8 inner steps) with a 4-step overlap
    # window (~60 ms of inner compute) — the streaming schedule whose
    # point is hiding the sync's wire time behind inner steps.
    sync_every = 16

    # (rtt_ms, gbps): the RTT sweep at DCN-class bandwidth, plus one
    # bandwidth-CONSTRAINED point where the int4 wire's halved bytes
    # dominate the outer-sync wall (inter-region links are often
    # ~0.1 Gbps per flow).
    points = [(rtt, GBPS) for rtt in RTTS_MS] + [(50.0, 0.1)]
    sweep = []
    for rtt, gbps in points:
        netem.configure(rtt, gbps)
        lh = LighthouseServer(
            bind="127.0.0.1:0", min_replicas=2, join_timeout_ms=10000
        )
        try:
            ddp_s = bench_ft_ddp(lh.address(), num_steps)
            diloco = bench_diloco(lh.address(), num_outer=num_outer, sync_every=sync_every)
        finally:
            lh.shutdown()
        outer = {}
        for wire in ("fp8", "int4"):
            outer[wire] = bench_outer_sync(wire)
        heal_s = bench_heal()
        row = {
            "rtt_ms": rtt,
            "gbps": gbps,
            "ddp_step_s": round(ddp_s, 4),
            "diloco_step_s": round(diloco["per_step_s"], 4),
            "diloco_step_max_s": round(diloco["p_max_s"], 4),
            "outer_sync_s": {k: round(v["wall_s"], 4) for k, v in outer.items()},
            "outer_wire_mb": {k: round(v["wire_mb"], 3) for k, v in outer.items()},
            "heal_s": round(heal_s, 4),
        }
        sweep.append(row)
        print(json.dumps(row), flush=True)
        netem.configure(0, 0)

    # Wire-bound outer-sync point: at 0.01 Gbps serialization dominates
    # everything else, so the int4-vs-fp8 wall ratio approaches the byte
    # ratio's 1.97x asymptote (fixed RTT + host reduce costs cap it at
    # ~1.6x on the 0.1 Gbps row above). Outer sync only — the per-step
    # loops would crawl pointlessly at this bandwidth.
    WIRE_BOUND_RTT_MS, WIRE_BOUND_GBPS = 50.0, 0.01
    netem.configure(WIRE_BOUND_RTT_MS, WIRE_BOUND_GBPS)
    outer_wire_bound = {w: bench_outer_sync(w) for w in ("fp8", "int4")}
    netem.configure(0, 0)
    print(
        json.dumps(
            {"outer_sync_wire_bound_s": {k: round(v["wall_s"], 3) for k, v in outer_wire_bound.items()}}
        ),
        flush=True,
    )

    # Control-plane RTT sensitivity: quorum pays the lighthouse hop, the
    # intra-group commit barrier stays flat (RTT-only; bandwidth is
    # irrelevant at quorum message sizes).
    control_plane = {
        f"{int(rtt)}ms": bench_quorum_rtt(rtt) for rtt in RTTS_MS
    }
    print(json.dumps({"control_plane_rtt": control_plane}), flush=True)

    # Commit-ordering sweep under the emulated DEVICE link (the serialized
    # per-step readiness RTT the pipelined mode kills).
    commit_pipeline = bench_commit_pipeline(quick=args.quick)

    # Select rows by predicate, not position — editing `points` above must
    # not silently re-aim the headline claims.
    full_bw = [r for r in sweep if r["gbps"] == GBPS]
    base = min(full_bw, key=lambda r: r["rtt_ms"])
    worst = max(full_bw, key=lambda r: r["rtt_ms"])
    constrained = min(sweep, key=lambda r: r["gbps"])
    ddp_infl = worst["ddp_step_s"] - base["ddp_step_s"]
    diloco_infl = worst["diloco_step_s"] - base["diloco_step_s"]
    claims = {
        # Absolute per-step inflation at the worst RTT (the honest
        # comparison: the two loops have different RTT=0 baselines).
        "ddp_step_inflation_ms_at_worst_rtt": round(ddp_infl * 1000, 1),
        "diloco_step_inflation_ms_at_worst_rtt": round(diloco_infl * 1000, 1),
        "diloco_hides_fraction_of_ddp_inflation": round(
            1.0 - diloco_infl / ddp_infl, 3
        ) if ddp_infl > 0 else None,
        "ddp_slowdown_at_worst_rtt": round(worst["ddp_step_s"] / base["ddp_step_s"], 3),
        "diloco_slowdown_at_worst_rtt": round(
            worst["diloco_step_s"] / base["diloco_step_s"], 3
        ),
        "int4_outer_speedup_vs_fp8_at_worst_rtt": round(
            worst["outer_sync_s"]["fp8"] / worst["outer_sync_s"]["int4"], 3
        ),
        "int4_outer_speedup_vs_fp8_constrained_bw": round(
            constrained["outer_sync_s"]["fp8"] / constrained["outer_sync_s"]["int4"], 3
        ),
        "int4_outer_speedup_vs_fp8_wire_bound": round(
            outer_wire_bound["fp8"]["wall_s"] / outer_wire_bound["int4"]["wall_s"], 3
        ),
        "int4_wire_bytes_vs_fp8": round(
            worst["outer_wire_mb"]["int4"] / worst["outer_wire_mb"]["fp8"], 3
        ),
        "sync_every": sync_every,
        "n_fragments": 2,
        "fragment_sync_delay": 4,
        "outer_payload_mb": OUTER_MB,
        "heal_payload_mb": HEAL_MB,
    }
    result = {
        "bench": "emulated_dcn",
        "device_kind": "cpu",
        "emulation": "netem shim at ProcessGroupTCP/HTTP wire choke points "
        "(per-flow: RTT/2 per message + bytes/bandwidth)",
        "sweep": sweep,
        "outer_sync_wire_bound": {
            "rtt_ms": WIRE_BOUND_RTT_MS,
            "gbps": WIRE_BOUND_GBPS,
            "wall_s": {k: round(v["wall_s"], 3) for k, v in outer_wire_bound.items()},
            "wire_mb": {k: round(v["wire_mb"], 3) for k, v in outer_wire_bound.items()},
        },
        "control_plane_rtt": control_plane,
        "commit_pipeline": commit_pipeline,
        "claims": claims,
    }
    out = REPO / "EMULATED_DCN_BENCH.json"
    out.write_text(json.dumps(result, indent=2) + "\n")
    print(json.dumps({"claims": claims}), flush=True)
    print(f"wrote {out}", flush=True)


if __name__ == "__main__":
    main()
