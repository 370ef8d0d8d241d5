"""Programmatic orchestration with fault injection (monarch-example role).

The reference ships an actor-based orchestration demo
(/root/reference/examples/monarch/train_distributed.py: LighthouseActor +
TrainerActor + FailureActor with a SEGFAULT/KILL/COMMS/DEADLOCK menu).
tpuft's equivalent is plain objects + processes: an embedded lighthouse,
supervised trainer groups (torchft_tpu.launch), and a chaos thread driving
the same fault menu through the punisher — everything in one script you can
lift into your own scheduler.

    python examples/orchestrate.py --groups 2 --steps 80 --mtbf 15 \
        --menu exit,segfault,deadlock,partition

Exit code 0 means every group finished and their final parameter digests
are identical (the fault-tolerance master invariant).
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import random
import sys
import tempfile
import threading
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from torchft_tpu.coordination import LighthouseClient, LighthouseServer
from torchft_tpu.launch import supervise
from torchft_tpu.punisher import FAULT_MODES, kill_one

_TRAINER = r"""
import hashlib, json, os, pathlib, sys, time
sys.path.insert(0, os.environ["TPUFT_REPO"])
import jax
import jax.numpy as jnp
import numpy as np
import optax

from torchft_tpu.bootstrap import init_manager
from torchft_tpu.ddp import ft_allreduce_gradients
from torchft_tpu.models.simple import DemoCNN
from torchft_tpu.optim import Optimizer
from torchft_tpu.parallel.native_pg import ProcessGroupNative

group = os.environ["REPLICA_GROUP_ID"]
out_dir = pathlib.Path(os.environ["ORCH_OUT"])
steps = int(os.environ["ORCH_STEPS"])
step_interval = float(os.environ.get("ORCH_STEP_INTERVAL", "0.5"))

model = DemoCNN()
params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))
pg = ProcessGroupNative(timeout=10.0)
manager, store = init_manager(
    pg, min_replica_size=1, replica_id=f"orch_{group}",
    timeout=10.0, quorum_timeout=20.0, heartbeat_interval=0.1,
)
opt = Optimizer(manager, optax.sgd(0.01, momentum=0.9), params)

@jax.jit
def loss_fn(p, x, y):
    logits = model.apply(p, x)
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

grad_fn = jax.jit(jax.value_and_grad(loss_fn))

try:
    while manager.current_step() < steps:
        step = manager.current_step()
        key = jax.random.PRNGKey(step)
        x = jax.random.normal(key, (8, 32, 32, 3), jnp.float32)
        y = jnp.arange(8) % 10
        opt.begin_step()
        loss, grads = grad_fn(opt.params, x, y)
        opt.step(ft_allreduce_gradients(manager, grads))
        time.sleep(step_interval)
    digest = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(opt.params):
        digest.update(np.asarray(leaf).tobytes())
    (out_dir / f"group{group}.json").write_text(
        json.dumps({"step": manager.current_step(), "digest": digest.hexdigest()})
    )
    print(f"[trainer {group}] finished at step {manager.current_step()}", flush=True)
finally:
    manager.shutdown(wait=False)
    pg.shutdown()
    if store is not None:
        store.shutdown()
"""


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--groups", type=int, default=2)
    parser.add_argument("--steps", type=int, default=40)
    parser.add_argument("--mtbf", type=float, default=20.0, help="mean seconds between faults (0 = no chaos)")
    parser.add_argument("--menu", default="exit", help="comma list of: " + ",".join(FAULT_MODES))
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--max-restarts", type=int, default=50)
    parser.add_argument(
        "--step-interval",
        type=float,
        default=0.5,
        help="seconds per step; keep total runtime well above the ~15s "
        "restart window or a group killed near the end restarts after its "
        "peers exited and retrains solo (no donor -> digests can differ)",
    )
    args = parser.parse_args()

    menu = tuple(m.strip() for m in args.menu.split(",") if m.strip())
    for mode in menu:
        if mode not in FAULT_MODES:
            parser.error(f"unknown fault mode {mode!r}")

    workdir = pathlib.Path(tempfile.mkdtemp(prefix="tpuft_orch_"))
    script = workdir / "trainer.py"
    script.write_text(_TRAINER)

    # LighthouseActor role: one embedded lighthouse for the job.
    lighthouse = LighthouseServer(
        min_replicas=1, join_timeout_ms=2000, heartbeat_timeout_ms=3000
    )
    print(f"[orchestrate] lighthouse at {lighthouse.address()}", flush=True)

    # FailureActor role: a chaos thread drawing from the fault menu.
    stop = threading.Event()

    def chaos() -> None:
        if args.mtbf <= 0:
            return
        rng = random.Random(args.seed)
        client = LighthouseClient(lighthouse.address())
        time.sleep(8.0)  # let the first quorum form
        while not stop.is_set():
            time.sleep(rng.expovariate(1.0 / args.mtbf))
            if stop.is_set():
                return
            try:
                kill_one(client, rng, mode=rng.choice(list(menu)))
            except Exception as e:  # noqa: BLE001
                print(f"[orchestrate] chaos injection ended with: {e}", flush=True)

    chaos_thread = threading.Thread(target=chaos, daemon=True)
    chaos_thread.start()

    # TrainerActor role: supervised replica-group processes.
    try:
        code = supervise(
            [sys.executable, str(script)],
            num_replica_groups=args.groups,
            lighthouse_addr=lighthouse.address(),
            relaunch_interval=0.5,
            max_restarts=args.max_restarts,
            extra_env={
                "ORCH_OUT": str(workdir),
                "ORCH_STEPS": str(args.steps),
                "ORCH_STEP_INTERVAL": str(args.step_interval),
                "TPUFT_REPO": str(pathlib.Path(__file__).resolve().parents[1]),
                "TPUFT_LOG": os.environ.get("TPUFT_LOG", "warn"),
            },
        )
    finally:
        stop.set()
        lighthouse.shutdown()
    if code != 0:
        print(f"[orchestrate] supervise failed with {code}")
        return code

    digests = {}
    for group in range(args.groups):
        data = json.loads((workdir / f"group{group}.json").read_text())
        digests[group] = data["digest"]
        print(f"[orchestrate] group {group}: step={data['step']} digest={data['digest'][:16]}")
    if len(set(digests.values())) != 1:
        print("[orchestrate] DIVERGENCE: digests differ across groups")
        return 2
    print("[orchestrate] all groups bitwise identical — fault tolerance held")
    return 0


if __name__ == "__main__":
    sys.exit(main())
