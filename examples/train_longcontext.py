#!/usr/bin/env python
"""Long-context fault-tolerant training: ring attention x FT replica axis.

Each replica group trains a Llama-family model whose attention runs as
**ring attention** over a sequence-parallel mesh axis — the sequence is
sharded across the group's devices and K/V blocks rotate over ICI — while
gradients average across replica groups through the fault-tolerant manager.
This composition (context parallelism inside the slice, elastic replicas
across slices) is the long-context deployment shape; the reference has no
context-parallel path at all (SURVEY.md §2.7).

    python examples/train_longcontext.py --demo --num-replica-groups 2 \
        --seq-len 512 --sp 4

Each group needs ``--sp`` devices: on a TPU host ``--demo`` splits the chips
between the group processes; the CPU is asked for by name
(``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4``).
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
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchft_tpu.bootstrap import init_manager
    from torchft_tpu.ddp import ft_allreduce_gradients
    from torchft_tpu.models.llama import Llama, LlamaConfig, cross_entropy_loss
    from torchft_tpu.optim import Optimizer
    from torchft_tpu.parallel.native_pg import ProcessGroupNative

    group_id = int(os.environ.get("REPLICA_GROUP_ID", "0"))
    config = LlamaConfig(
        vocab_size=512,
        dim=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        ffn_hidden=128,
        max_seq_len=args.seq_len,
        dtype=jnp.float32,
        attention_impl="auto",  # ring attention under the sp mesh below
        # --ring-flash: per-hop block compute as the fused Pallas kernel
        # (compiled on TPU, interpret elsewhere).
        ring_use_flash=args.ring_flash,
    )
    model = Llama(config)
    if len(jax.devices()) < args.sp:
        raise SystemExit(
            f"--sp {args.sp} needs {args.sp} devices, this process sees "
            f"{len(jax.devices())} ({jax.devices()[0].platform}); on the CPU "
            "ask for them by name: JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={args.sp}"
        )
    mesh = Mesh(np.array(jax.devices()[: args.sp]), ("sp",))

    tokens0 = jnp.zeros((args.batch_size, args.seq_len), dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens0)
    # Replicate params over the sp mesh so they cohabit with the shard_map
    # outputs (grads) in one jitted update.
    params = jax.device_put(params, NamedSharding(mesh, P()))

    pg = ProcessGroupNative(timeout=args.timeout)
    manager, store = init_manager(
        pg,
        min_replica_size=1,
        replica_id=f"train_longctx_{group_id}",
        timeout=args.timeout,
        quorum_timeout=args.quorum_timeout,
        heartbeat_interval=0.1,
    )
    opt = Optimizer(manager, optax.adamw(1e-3), params)

    def loss_fn(p, tokens, positions):
        logits = model.apply(p, tokens, positions)
        # Within-shard next-token loss (boundary tokens are a negligible
        # fraction at long context; avoids a cross-shard shift collective).
        return cross_entropy_loss(logits[:, :-1], tokens[:, 1:])

    # The sequence dim shards over sp; the model dispatches to ring
    # attention because the sp axis is present in the ambient mesh. Each
    # shard's loss/grads cover its sequence slice, pmean'd over the ring so
    # the outputs are truly replicated.
    def loss_and_grad(p, tokens, positions):
        loss, grads = jax.value_and_grad(loss_fn)(p, tokens, positions)
        loss = jax.lax.pmean(loss, "sp")
        grads = jax.tree_util.tree_map(lambda g: jax.lax.pmean(g, "sp"), grads)
        return loss, grads

    sharded_grad = shard_map(
        loss_and_grad,
        mesh=mesh,
        in_specs=(P(), P(None, "sp"), P(None, "sp")),
        out_specs=(P(), P()),
    )

    positions = jnp.broadcast_to(
        jnp.arange(args.seq_len), (args.batch_size, args.seq_len)
    )

    print(
        f"[group {group_id}] ring attention over sp={args.sp}, "
        f"seq={args.seq_len} ({args.seq_len // args.sp}/device)",
        flush=True,
    )
    t_start = time.monotonic()
    try:
        with mesh:
            while manager.current_step() < args.steps:
                step = manager.current_step()
                key = jax.random.PRNGKey(7000 * group_id + step)
                tokens = jax.random.randint(
                    key, (args.batch_size, args.seq_len), 0, config.vocab_size
                )
                opt.begin_step()
                (loss, grads) = sharded_grad(opt.params, tokens, positions)
                avg = ft_allreduce_gradients(manager, grads)
                committed = opt.step(avg)
                print(
                    f"[group {group_id}] step={step} loss={float(jnp.mean(loss)):.4f} "
                    f"participants={manager.num_participants()} committed={committed}",
                    flush=True,
                )
        elapsed = time.monotonic() - t_start
        digest = float(
            jax.jit(
                lambda p: sum(jnp.abs(l).sum() for l in jax.tree_util.tree_leaves(p))
            )(opt.params)
        )
        tokens_sec = args.steps * args.batch_size * args.seq_len / elapsed
        print(
            f"[group {group_id}] done in {elapsed:.1f}s "
            f"({tokens_sec:.0f} tokens/sec) param_digest={digest:.6f}",
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
        return subprocess.Popen(
            [
                sys.executable, os.path.abspath(__file__),
                "--steps", str(args.steps),
                "--seq-len", str(args.seq_len),
                "--sp", str(args.sp),
                "--batch-size", str(args.batch_size),
                "--timeout", str(args.timeout),
                "--quorum-timeout", str(args.quorum_timeout),
                *(["--ring-flash"] if args.ring_flash else []),
            ],
            env=env,
        )

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
    parser.add_argument("--steps", type=int, default=6)
    parser.add_argument("--batch-size", type=int, default=2)
    parser.add_argument("--seq-len", type=int, default=512)
    parser.add_argument("--sp", type=int, default=4, help="sequence-parallel degree")
    parser.add_argument(
        "--ring-flash", action="store_true",
        help="fused Pallas kernel for the per-hop ring block compute",
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
