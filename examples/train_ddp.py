#!/usr/bin/env python
"""Fault-tolerant DDP demo (reference parity: /root/reference/train_ddp.py).

One OS process per replica group trains a small CNN on synthetic
CIFAR-shaped data, averaging gradients across groups through the manager.
Kill any group mid-run (Ctrl-C it, `kill -9`, or use --demo's built-in
chaos) and watch the survivors shrink the quorum and keep stepping; restart
it and watch it live-heal from a donor.

Run a 2-group cluster on one machine:

    python examples/train_ddp.py --demo --num-replica-groups 2 --steps 30

Or by hand (per replica group, plus a lighthouse):

    python -m torchft_tpu.lighthouse --bind "[::]:29510" --min-replicas 1
    REPLICA_GROUP_ID=0 TPUFT_LIGHTHOUSE=host:29510 python examples/train_ddp.py
    REPLICA_GROUP_ID=1 TPUFT_LIGHTHOUSE=host:29510 python examples/train_ddp.py
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
    import jax.numpy as jnp
    import optax

    from torchft_tpu.bootstrap import init_manager
    from torchft_tpu.data import DistributedSampler
    from torchft_tpu.ddp import ft_allreduce_gradients
    from torchft_tpu.models.simple import DemoCNN
    from torchft_tpu.optim import Optimizer
    from torchft_tpu.parallel.native_pg import ProcessGroupNative

    group_id = int(os.environ.get("REPLICA_GROUP_ID", args.replica_group_id))

    model = DemoCNN(padding_mb=args.padding_mb)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))

    pg = ProcessGroupNative(timeout=args.timeout)
    manager, store = init_manager(
        pg,
        min_replica_size=args.min_replica_size,
        replica_id=f"train_ddp_{group_id}",
        timeout=args.timeout,
        quorum_timeout=args.quorum_timeout,
        heartbeat_interval=0.1,
    )
    opt = Optimizer(manager, optax.sgd(0.01, momentum=0.9), params)

    @jax.jit
    def loss_fn(p, x, y):
        logits = model.apply(p, x)
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    if args.microbatches > 1:
        # Gradient accumulation inside one jitted program (lax.scan over
        # equal batch chunks) — the HBM lever when the global batch
        # doesn't fit. Same mean gradient up to f32 reduction order.
        from torchft_tpu.optim import make_microbatch_grad

        grad_fn = jax.jit(make_microbatch_grad(loss_fn, args.microbatches))
    else:
        grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    # Synthetic CIFAR-shaped data, deterministic per index.
    dataset_size = 50_000

    def batch_for(indices):
        key = jax.random.PRNGKey(int(indices[0]))
        x = jax.random.normal(key, (len(indices), 32, 32, 3), dtype=jnp.float32)
        y = jnp.asarray(indices) % 10
        return x, y

    sampler = DistributedSampler(
        dataset_size,
        replica_rank=group_id,
        num_replica_groups=args.num_replica_groups,
        batch_size=args.batch_size,
        seed=1234,
    )

    print(f"[group {group_id}] starting at manager step {manager.current_step()}", flush=True)
    batches = sampler.batches()
    t_start = time.monotonic()

    # Profiler export (reference train_ddp.py:159-174 chrome-trace loops):
    # --profile-dir captures BOTH a jax.profiler trace (TensorBoard/perfetto)
    # and a self-contained chrome trace of the manager-phase spans.
    from contextlib import ExitStack

    profile_stack = ExitStack()
    if args.profile_dir:
        import jax.profiler

        from torchft_tpu.utils.profiling import chrome_trace

        os.makedirs(args.profile_dir, exist_ok=True)
        profile_stack.enter_context(jax.profiler.trace(args.profile_dir))
        trace_path = os.path.join(args.profile_dir, f"tpuft_spans_g{group_id}.json")
        profile_stack.enter_context(chrome_trace(trace_path))
        print(f"[group {group_id}] profiling to {args.profile_dir}", flush=True)
    try:
        while manager.current_step() < args.steps:
            step = manager.current_step()
            try:
                indices = next(batches)
            except StopIteration:
                sampler.set_epoch(sampler.epoch + 1)
                batches = sampler.batches()
                indices = next(batches)
            x, y = batch_for(indices)

            opt.begin_step()
            loss, grads = grad_fn(opt.params, x, y)
            avg = ft_allreduce_gradients(manager, grads)
            committed = opt.step(avg)
            print(
                f"[group {group_id}] step={step} loss={float(loss):.4f} "
                f"participants={manager.num_participants()} committed={committed}",
                flush=True,
            )
        elapsed = time.monotonic() - t_start
        examples = manager.batches_committed() * args.batch_size
        print(
            f"[group {group_id}] done: {args.steps} steps in {elapsed:.1f}s "
            f"({examples / elapsed:.1f} examples/sec global)",
            flush=True,
        )
        # Emit a digest so observers can check cross-group convergence.
        leaves = jax.tree_util.tree_leaves(opt.params)
        digest = float(sum(jnp.sum(jnp.abs(l)) for l in leaves))
        print(f"[group {group_id}] param_digest={digest:.6f}", flush=True)
    finally:
        try:
            profile_stack.close()
            if args.profile_dir:
                print(
                    f"[group {group_id}] trace artifacts in {args.profile_dir} "
                    f"(tpuft_spans_g{group_id}.json loads in chrome://tracing)",
                    flush=True,
                )
        except Exception as e:  # noqa: BLE001  — profiling must never break teardown
            print(f"[group {group_id}] trace export failed: {e}", flush=True)
        manager.shutdown(wait=False)
        pg.shutdown()
        if store is not None:
            store.shutdown()


def demo(args: argparse.Namespace) -> None:
    """Launches a lighthouse + N replica-group processes, kills one group a
    few steps in, restarts it, and checks everyone converges."""
    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.launch import chip_envs

    # One process per chip: each group gets its own (refused when the host
    # has fewer chips than groups and the caller did not ask for the CPU).
    chips = chip_envs(args.num_replica_groups)
    lighthouse = LighthouseServer(
        min_replicas=1, join_timeout_ms=3000, heartbeat_timeout_ms=2000
    )
    env_base = {**os.environ, "TPUFT_LIGHTHOUSE": lighthouse.address()}

    def spawn(group: int) -> subprocess.Popen:
        env = {**env_base, **chips[group], "REPLICA_GROUP_ID": str(group)}
        return subprocess.Popen(
            [
                sys.executable,
                os.path.abspath(__file__),
                "--steps",
                str(args.steps),
                "--num-replica-groups",
                str(args.num_replica_groups),
                "--batch-size",
                str(args.batch_size),
                "--padding-mb",
                str(args.padding_mb),
            ],
            env=env,
        )

    procs = {g: spawn(g) for g in range(args.num_replica_groups)}
    victim = args.num_replica_groups - 1
    try:
        time.sleep(args.kill_after)
        print(f"[demo] killing group {victim} (pid {procs[victim].pid})", flush=True)
        procs[victim].send_signal(signal.SIGKILL)
        procs[victim].wait()
        time.sleep(args.restart_after)
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
    parser.add_argument("--replica-group-id", type=int, default=0)
    parser.add_argument("--num-replica-groups", type=int, default=2)
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument(
        "--microbatches", type=int, default=1,
        help="gradient-accumulation chunks per step (batch-size must divide)",
    )
    parser.add_argument("--min-replica-size", type=int, default=1)
    parser.add_argument("--padding-mb", type=int, default=0)
    parser.add_argument("--timeout", type=float, default=30.0)
    parser.add_argument("--quorum-timeout", type=float, default=60.0)
    parser.add_argument(
        "--profile-dir",
        default="",
        help="capture jax.profiler + chrome-trace span artifacts here",
    )
    parser.add_argument("--demo", action="store_true", help="run the chaos demo")
    parser.add_argument("--kill-after", type=float, default=8.0)
    parser.add_argument("--restart-after", type=float, default=2.0)
    args = parser.parse_args()
    if args.demo:
        demo(args)
    else:
        train(args)


if __name__ == "__main__":
    main()
