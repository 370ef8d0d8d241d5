#!/usr/bin/env python
"""Fault-tolerant HSDP demo: FSDP/TP inside each replica group x the FT
replica axis (reference parity: torchtitan HSDP composition via
ft_init_device_mesh, SURVEY.md §2.7).

Each replica-group process builds a real jax Mesh over its devices and
shards a Llama-family model with the megatron layout; gradients reduce
across groups shard-by-shard via ft_allreduce_sharded, preserving the
intra-slice sharding end to end. The mesh spans every device the process
sees: on a TPU host ``--demo`` gives each group process its own share of
the chips (torchft_tpu.launch.chip_envs); the CPU is something the caller
asks for by name.

    python examples/train_hsdp.py --demo --num-replica-groups 2 --steps 10
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python examples/train_hsdp.py --demo --num-replica-groups 2 --steps 10
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def train(args: argparse.Namespace) -> None:
    import jax

    from torchft_tpu.bootstrap import init_group_jax_cluster, init_manager

    clustered = init_group_jax_cluster()
    import jax.numpy as jnp
    import optax
    from torchft_tpu.models.llama import (
        CONFIGS,
        Llama,
        apply_sharding_plan,
        cross_entropy_loss,
        sharding_plan,
    )
    from torchft_tpu.optim import Optimizer
    from torchft_tpu.parallel.mesh import ft_allreduce_sharded, ft_init_device_mesh
    from torchft_tpu.parallel.native_pg import ProcessGroupNative

    group_id = int(os.environ.get("REPLICA_GROUP_ID", "0"))
    pg = ProcessGroupNative(timeout=args.timeout)
    manager, store = init_manager(
        pg,
        min_replica_size=1,
        replica_id=f"train_hsdp_{group_id}",
        timeout=args.timeout,
        quorum_timeout=args.quorum_timeout,
        heartbeat_interval=0.1,
    )

    from dataclasses import replace

    # The 70B-class fit levers, composable with the HSDP sharding: scanned
    # layer stack (O(1) HLO in depth), dots-remat, fused linear+CE.
    config = replace(
        CONFIGS["tiny"],
        scan_layers=args.scan_layers,
        remat="dots" if args.remat else "none",
        loss_vocab_chunk=128 if args.fused_ce else None,
    )
    model = Llama(config)
    tokens = jnp.zeros((args.batch_size, args.seq_len), dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)

    # Intra-slice mesh: fsdp x tp over ALL the group's devices (global when
    # the group forms a jax cluster); the replica axis stays virtual.
    n_devices = len(jax.devices())
    fsdp = max(n_devices // 2, 1)
    ft_mesh = ft_init_device_mesh(
        manager, mesh_shape=(fsdp, 2 if n_devices >= 2 else 1),
        axis_names=("fsdp", "tp"),
    )
    if clustered:
        print(
            f"[group {group_id}] jax cluster: {n_devices} global devices "
            f"({len(jax.local_devices())} local)",
            flush=True,
        )
    params = apply_sharding_plan(params, ft_mesh.mesh, sharding_plan("fsdp", "tp"))
    opt = Optimizer(manager, optax.adamw(1e-3), params)

    def loss_fn(p, batch_tokens):
        if config.loss_vocab_chunk is not None:
            return model.apply(
                p, batch_tokens[:, :-1], targets=batch_tokens[:, 1:]
            )
        logits = model.apply(p, batch_tokens[:, :-1])
        return cross_entropy_loss(logits, batch_tokens[:, 1:])

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    print(
        f"[group {group_id}] HSDP mesh {ft_mesh} starting at step "
        f"{manager.current_step()}",
        flush=True,
    )
    t_start = time.monotonic()
    try:
        # set_mesh (not a legacy `with mesh:`) so the flash path can
        # shard_map itself under the fsdp/tp axes on real TPU.
        with jax.set_mesh(ft_mesh.mesh):
            while manager.current_step() < args.steps:
                step = manager.current_step()
                key = jax.random.PRNGKey(5000 * group_id + step)
                batch = jax.random.randint(
                    key, (args.batch_size, args.seq_len + 1), 0, config.vocab_size
                )
                opt.begin_step()
                loss, grads = grad_fn(opt.params, batch)
                avg = ft_allreduce_sharded(manager, grads)
                committed = opt.step(avg)
                print(
                    f"[group {group_id}] step={step} loss={float(loss):.4f} "
                    f"replica_axis={ft_mesh.size('replica')} committed={committed}",
                    flush=True,
                )
        elapsed = time.monotonic() - t_start
        # Jitted reduce -> replicated scalar, fetchable from any process
        # (multi-host arrays' remote shards are not addressable directly).
        digest = float(
            jax.jit(
                lambda p: sum(jnp.abs(l).sum() for l in jax.tree_util.tree_leaves(p))
            )(opt.params)
        )
        print(
            f"[group {group_id}] done in {elapsed:.1f}s param_digest={digest:.6f}",
            flush=True,
        )
    finally:
        manager.shutdown(wait=False)
        pg.shutdown()
        if store is not None:
            store.shutdown()


def demo(args: argparse.Namespace) -> None:
    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.launch import chip_envs

    chips = chip_envs(args.num_replica_groups)
    lighthouse = LighthouseServer(
        min_replicas=1, join_timeout_ms=5000, heartbeat_timeout_ms=2000
    )
    env_base = {**os.environ, "TPUFT_LIGHTHOUSE": lighthouse.address()}

    def spawn(group: int) -> subprocess.Popen:
        env = {**env_base, **chips[group], "REPLICA_GROUP_ID": str(group)}
        argv = [
            sys.executable, os.path.abspath(__file__),
            "--steps", str(args.steps),
            "--batch-size", str(args.batch_size),
            "--seq-len", str(args.seq_len),
        ]
        for flag, on in (
            ("--scan-layers", args.scan_layers),
            ("--remat", args.remat),
            ("--fused-ce", args.fused_ce),
        ):
            if on:
                argv.append(flag)
        return subprocess.Popen(argv, env=env)

    procs = {g: spawn(g) for g in range(args.num_replica_groups)}
    victim = args.num_replica_groups - 1
    try:
        time.sleep(args.kill_after)
        print(f"[demo] killing group {victim}", flush=True)
        procs[victim].send_signal(signal.SIGKILL)
        procs[victim].wait()
        time.sleep(2)
        print(f"[demo] restarting group {victim}", flush=True)
        procs[victim] = spawn(victim)
        exit_codes = {g: p.wait() for g, p in procs.items()}
        print(f"[demo] exit codes: {exit_codes}", flush=True)
        if any(code != 0 for code in exit_codes.values()):
            sys.exit(1)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        lighthouse.shutdown()


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--num-replica-groups", type=int, default=2)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--batch-size", type=int, default=4)
    parser.add_argument("--seq-len", type=int, default=64)
    parser.add_argument(
        "--scan-layers", action="store_true",
        help="lax.scan'd layer stack (O(1) HLO in depth)",
    )
    parser.add_argument(
        "--remat", action="store_true", help="dots-policy gradient checkpointing"
    )
    parser.add_argument(
        "--fused-ce", action="store_true",
        help="fused linear+cross-entropy (logits never materialize)",
    )
    parser.add_argument("--timeout", type=float, default=30.0)
    parser.add_argument("--quorum-timeout", type=float, default=60.0)
    parser.add_argument("--demo", action="store_true")
    parser.add_argument("--kill-after", type=float, default=12.0)
    args = parser.parse_args()
    if args.demo:
        demo(args)
    else:
        train(args)


if __name__ == "__main__":
    main()
