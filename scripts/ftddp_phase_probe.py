#!/usr/bin/env python
"""On-chip phase instrumentation for the FT-DDP lone-replica step.

Times each phase of make_step_fn's lone path — quorum wait, fused
dispatch, device sync, commit barrier — on the real chip, to locate the
per-step FT overhead before optimizing it.

Usage: python scripts/ftddp_phase_probe.py [dim n_layers]
"""

from __future__ import annotations

import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from torchft_tpu.utils.platform import require_tpu

require_tpu()  # a chip script: exits non-zero when jax answers on anything else

import jax
import jax.numpy as jnp
import optax


def main() -> None:
    dim = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    n_layers = int(sys.argv[2]) if len(sys.argv) > 2 else 6

    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.manager import Manager
    from torchft_tpu.models.llama import Llama, LlamaConfig, cross_entropy_loss
    from torchft_tpu.optim import Optimizer, make_jit_fused_step
    from torchft_tpu.parallel.native_pg import ProcessGroupNative
    from torchft_tpu.parallel.store import StoreClient, StoreServer

    BATCH, SEQ = 8, 512
    config = LlamaConfig(
        vocab_size=8192, dim=dim, n_layers=n_layers, n_heads=8, n_kv_heads=4,
        ffn_hidden=dim * 3, max_seq_len=SEQ, dtype=jnp.bfloat16,
    )
    model = Llama(config)
    tokens = jnp.zeros((BATCH, SEQ + 1), dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :SEQ])
    tx = optax.sgd(0.01, momentum=0.9)

    def loss_fn(p, batch_tokens):
        logits = model.apply(p, batch_tokens[:, :-1])
        return cross_entropy_loss(logits, batch_tokens[:, 1:])

    def batch_for(step: int):
        return jax.random.randint(
            jax.random.PRNGKey(step), (BATCH, SEQ + 1), 0, config.vocab_size
        )

    lighthouse = LighthouseServer(min_replicas=1, join_timeout_ms=100)
    store = StoreServer()
    pg = ProcessGroupNative(timeout=30.0)
    manager = Manager(
        pg=pg, min_replica_size=1,
        store=StoreClient(store.address()), store_addr=store.address(),
        lighthouse_addr=lighthouse.address(), replica_id="probe",
        timeout=30.0, quorum_timeout=60.0, use_async_quorum=True,
    )
    opt = Optimizer(manager, tx, params)
    fused = make_jit_fused_step(tx, loss_fn)

    phases = {k: [] for k in ("quorum", "dispatch", "sync", "commit", "total")}

    # Warmup: compile + first quorum.
    manager.start_quorum()
    manager.wait_quorum()
    loss, p2, o2 = fused(opt.params, opt.opt_state, batch_for(0))
    jax.block_until_ready(loss)
    assert manager.should_commit()
    opt.params, opt.opt_state = p2, o2

    for step in range(1, 11):
        batch = batch_for(step)
        t0 = time.monotonic()
        manager.start_quorum()
        manager.wait_quorum()
        t1 = time.monotonic()
        loss, p2, o2 = fused(opt.params, opt.opt_state, batch)
        t2 = time.monotonic()
        fut = manager.should_commit_async(None)
        jax.block_until_ready(loss)
        t3 = time.monotonic()
        ok = fut.result()
        t4 = time.monotonic()
        assert ok
        opt.params, opt.opt_state = p2, o2
        phases["quorum"].append(t1 - t0)
        phases["dispatch"].append(t2 - t1)
        phases["sync"].append(t3 - t2)
        phases["commit"].append(t4 - t3)
        phases["total"].append(t4 - t0)

    # Plain baseline on the identical program, chained, one fetch.
    t0 = time.monotonic()
    p, o = opt.params, opt.opt_state
    for step in range(10):
        loss, p, o = fused(p, o, batch_for(step))
    float(loss)
    plain_ms = 100.0 * (time.monotonic() - t0)  # per-step ms over 10 steps

    for k, v in phases.items():
        print(f"{k:>9}: p50 {1e3 * statistics.median(v):8.1f} ms   "
              f"max {1e3 * max(v):8.1f} ms")
    print(f"    plain: p50 {plain_ms:8.1f} ms/step (chained, single fetch)")

    manager.shutdown(wait=False)
    pg.shutdown()
    store.shutdown()
    lighthouse.shutdown()


if __name__ == "__main__":
    main()
