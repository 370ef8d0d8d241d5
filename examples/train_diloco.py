#!/usr/bin/env python
"""Streaming DiLoCo demo (reference parity: /root/reference/train_diloco.py).

One OS process per replica group trains an MLP with per-step local SGD and
periodic cross-group pseudogradient averaging (Streaming DiLoCo fragments,
optionally fp8-quantized). Communication happens only every
``--sync-every`` steps — the pattern for replica groups connected over DCN.

    python examples/train_diloco.py --demo --num-replica-groups 2 \
        --syncs 6 --sync-every 8 --fragments 2 [--quantize]
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
    import numpy as np
    import optax

    from torchft_tpu.bootstrap import init_manager
    from torchft_tpu.local_sgd import DiLoCo
    from torchft_tpu.models.simple import DemoMLP
    from torchft_tpu.parallel.native_pg import ProcessGroupNative

    group_id = int(os.environ.get("REPLICA_GROUP_ID", "0"))

    model = DemoMLP(hidden=args.hidden)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64)))

    pg = ProcessGroupNative(timeout=args.timeout)
    manager, store = init_manager(
        pg,
        min_replica_size=1,
        replica_id=f"train_diloco_{group_id}",
        use_async_quorum=False,  # DiLoCo requires sync quorum
        timeout=args.timeout,
        quorum_timeout=args.quorum_timeout,
        heartbeat_interval=0.1,
    )
    algo = DiLoCo(
        manager,
        inner_tx=optax.adamw(1e-3),
        outer_tx=optax.sgd(0.7, momentum=0.9, nesterov=True),
        params=params,
        sync_every=args.sync_every,
        n_fragments=args.fragments,
        should_quantize=args.quantize,
        fragment_sync_delay=args.fragment_sync_delay,
    )

    @jax.jit
    def loss_fn(p, x, y):
        logits = model.apply(p, x)
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    inner_iter = 0
    t_start = time.monotonic()
    try:
        while manager.current_step() < args.syncs:
            key = jax.random.PRNGKey(10_000 * group_id + inner_iter)
            kx, ky = jax.random.split(key)
            x = jax.random.normal(kx, (args.batch_size, 64), jnp.float32)
            y = jax.random.randint(ky, (args.batch_size,), 0, 10)
            loss, grads = grad_fn(algo.params, x, y)
            committed = algo.step(grads)
            if committed:
                print(
                    f"[group {group_id}] outer_step={manager.current_step()} "
                    f"inner_iter={inner_iter} loss={float(loss):.4f} "
                    f"participants={manager.num_participants()}",
                    flush=True,
                )
            inner_iter += 1
        elapsed = time.monotonic() - t_start
        digest = float(
            sum(np.abs(np.asarray(b)).sum() for f in algo._fragments for b in f.backup)
        )
        print(
            f"[group {group_id}] done: {args.syncs} outer steps "
            f"({inner_iter} inner) in {elapsed:.1f}s global_digest={digest:.6f}",
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

    # One process per chip: each group gets its own (refused when the host
    # has fewer chips than groups and the caller did not ask for the CPU).
    chips = chip_envs(args.num_replica_groups)
    lighthouse = LighthouseServer(
        min_replicas=1, join_timeout_ms=5000, heartbeat_timeout_ms=2000
    )
    env_base = {**os.environ, "TPUFT_LIGHTHOUSE": lighthouse.address()}

    def spawn(group: int) -> subprocess.Popen:
        env = {**env_base, **chips[group], "REPLICA_GROUP_ID": str(group)}
        argv = [
            sys.executable, os.path.abspath(__file__),
            "--syncs", str(args.syncs),
            "--sync-every", str(args.sync_every),
            "--fragments", str(args.fragments),
            "--num-replica-groups", str(args.num_replica_groups),
        ]
        if args.quantize:
            argv.append("--quantize")
        return subprocess.Popen(argv, env=env)

    procs = {g: spawn(g) for g in range(args.num_replica_groups)}
    victim = args.num_replica_groups - 1
    try:
        time.sleep(args.kill_after)
        print(f"[demo] killing group {victim} (pid {procs[victim].pid})", flush=True)
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
    parser.add_argument("--syncs", type=int, default=6, help="outer steps to run")
    parser.add_argument("--sync-every", type=int, default=8)
    parser.add_argument("--fragments", type=int, default=2)
    parser.add_argument("--fragment-sync-delay", type=int, default=0)
    parser.add_argument(
        "--quantize", action="store_true",
        help="quantized outer syncs (wire format via TPUFT_WIRE_DTYPE: "
        "fp8 default, int8, or packed int4 at half the bytes)",
    )
    parser.add_argument("--hidden", type=int, default=128)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--timeout", type=float, default=30.0)
    parser.add_argument("--quorum-timeout", type=float, default=60.0)
    parser.add_argument("--demo", action="store_true")
    parser.add_argument("--kill-after", type=float, default=15.0)
    args = parser.parse_args()
    if args.demo:
        demo(args)
    else:
        train(args)


if __name__ == "__main__":
    main()
