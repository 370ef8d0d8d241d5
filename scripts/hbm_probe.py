#!/usr/bin/env python
"""Size a train step for one v5e chip without a chip.

The TPU's compiler is installed beside JAX and compiles for a chip that is
described, not attached (``jax.experimental.topologies``). This probe
compiles the plain SGD-momentum step (donated, as a user's would be) or
the FT-DDP fused step as the lone replica's depth-0 step runs it since
PR 60 (given its state after the vote: one copy; a strict or pipelined
step keeps the committed state beside the speculative one, so add one
state by hand there) for one described ``v5e:2x2`` device and prints what
``memory_analysis()`` says against the chip's 15.75 GiB — that compile does
not itself refuse a program that is too large. It counts one program: what
else the process keeps on the device (a pipelined manager's history ring of
depth + 1 versions — at depth 0 its one version is the committed state the
step already counts as its input — DiLoCo's backups and outer state) is
added by hand. A compile that passes is a
rehearsal, never a chip run.

Usage (run with JAX_PLATFORMS=cpu):
    python scripts/hbm_probe.py config=1b,layers=4 config=1b,layers=8,step=ftddp
    python scripts/hbm_probe.py batch=8,remat=none        # config=large

Keys: config (large | a models.llama.CONFIGS name), layers, batch, seq,
remat, step (plain | ftddp).
"""

from __future__ import annotations

import os
import sys
from dataclasses import replace
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import optax
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

import chip_smoke  # the step and loss the smoke runs are what gets sized

V5E_HBM_GIB = 15.75
GiB = 2**30


def steer_to_chip_branches() -> None:
    """Code that asks ``on_tpu()`` sees the CPU during a described-topology
    compile and would take its interpret/jnp branches: steer it here, in
    the probe, not with an option of the program."""
    import torchft_tpu.ops.attention as attention
    import torchft_tpu.ops.flash_attention as flash
    import torchft_tpu.ops.quantization as quant
    import torchft_tpu.utils.platform as platform

    for module in (platform, attention, flash, quant):
        module.on_tpu = lambda: True


def probe(spec: dict, device) -> None:
    from torchft_tpu.models.llama import CONFIGS, Llama, large_bench_config
    from torchft_tpu.optim import make_jit_fused_step

    name = spec.get("config", "large")
    seq = int(spec.get("seq", 2048))
    batch = int(spec.get("batch", 4))
    if name == "large":
        config = large_bench_config(max_seq_len=seq)
    else:
        config = replace(
            CONFIGS[name], max_seq_len=seq, attention_impl="flash",
            scan_layers=True, remat="dots", loss_vocab_chunk=4096,
        )
    config = replace(
        config,
        n_layers=int(spec.get("layers", config.n_layers)),
        remat=spec.get("remat", config.remat),
    )
    model = Llama(config)
    tx = optax.sgd(0.01, momentum=0.9)
    one = SingleDeviceSharding(device)

    def on_device(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree
        )

    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((batch, seq), jnp.int32))
    )
    opt_state = jax.eval_shape(tx.init, params)
    tokens = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32, sharding=one)

    loss_fn = chip_smoke.make_loss_fn(model)
    step = spec.get("step", "plain")
    jitted = (
        make_jit_fused_step(tx, loss_fn, donate_state=True)
        if step == "ftddp"
        else chip_smoke.make_plain_step(tx, loss_fn)
    )
    compiled = jitted.lower(on_device(params), on_device(opt_state), tokens).compile()
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )
    n_params = sum(int(l.size) for l in jax.tree_util.tree_leaves(params))
    print(
        f"[hbm_probe] config={name} layers={config.n_layers} batch={batch} "
        f"seq={seq} remat={config.remat} step={step}: {n_params / 1e6:.0f}M params; "
        f"args {mem.argument_size_in_bytes / GiB:.2f} + out "
        f"{mem.output_size_in_bytes / GiB:.2f} + temp {mem.temp_size_in_bytes / GiB:.2f} "
        f"- aliased {mem.alias_size_in_bytes / GiB:.2f} = {total / GiB:.2f} GiB "
        f"({'fits' if total / GiB < V5E_HBM_GIB else 'DOES NOT FIT'} {V5E_HBM_GIB} GiB); "
        f"tpu_custom_call {'present' if 'tpu_custom_call' in compiled.as_text() else 'absent'}",
        flush=True,
    )


def main() -> None:
    steer_to_chip_branches()
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for arg in sys.argv[1:] or ["config=large"]:
        probe(dict(part.split("=") for part in arg.split(",")), topo.devices[0])


if __name__ == "__main__":
    main()
