#!/usr/bin/env python
"""What the cell ``granite-4.0-h-micro-1chip.ftddp-seq8k`` carries and how
exactly, at its own size and seed, and the control of its limits:

- ``ssd_chunk_log_decay`` by Mamba layer (``models.granite.chunk_log_decay``):
  the smallest and largest total log-decay of a chunk over heads, on the
  seeded weights and, with ``--steps 0 30 60``, again after so many of the
  cell's own AdamW steps. Near 0 a head carries its state across chunks whole,
  at -20 nothing of it arrives: it says whether the scan the cell times really
  crosses its 31 chunk boundaries;
- the chunked scan (``ops.ssd.ssd_scan``, bfloat16 operands, chunk 256) against
  the recurrence position by position in float32 (``ssd_recurrence``) at the
  cell's shapes, 1 x 8192 with 64 heads of 64 and a state of 128, on inputs
  drawn as a layer makes them: the largest difference over the largest output;
- the flash kernels at the cell's attention geometry, 32 / 8 heads of 64 at
  scale 1/64 (the first cell at head width 64 and at a scale that is not
  ``d ** -0.5``), forward and gradients against the dense path at 1 x 2048;
- the reference's own update: how far ONE AdamW step of the float32 reference
  moves the second loss (the harness asks for 4 limits or more), and the fp8
  control: the float32 reference with every weight in fp8
  (e4m3, one scale a tensor) through ``harness.reference_check`` under the
  cell's limits. It has to come out NOT correct; the script exits 1 where it
  does not (``--no-control`` leaves it out);
- with ``--update-by-path`` and nothing else: how far the program's first
  AdamW step lowers the NEXT batch's loss, to first order, as a share of what
  the float32 reference's own step does, once with the attention layer on the
  path the cell takes (``auto``: the flash kernels on a TPU) and once on the
  blockwise path. PR 57's review round found 0.992 and 1.000 there on the
  chip: the shortfall of the cell's second loss follows the flash FORWARD
  call's presence in the step and no precision of the program's arithmetic
  (PERF.md sections 6 and 7).

    python scripts/granite_check.py SEED [SEED ...] [--steps N ...]   (needs a TPU)
    python scripts/granite_check.py SEED --update-by-path             (needs a TPU)
    JAX_PLATFORMS=cpu python scripts/granite_check.py --rehearse 7

One JSON line a seed on stdout; PERF.md section 6 (PR 57) has the readings.
``control`` and ``fp8`` are scripts/keye_selection_check.py's.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

CELL = "granite-4.0-h-micro-1chip.ftddp-seq8k"
OVERLAY = ROOT / "chipbench/fixtures/rehearsal-granite.json"


def decays_after(system, params, steps) -> dict:
    """``chunk_log_decay`` of batch N on the state N plain AdamW steps in (the
    cell's optimizer on its own batches 0 .. N - 1), for each N of ``steps``:
    {N: [[smallest, largest] by Mamba layer]}."""
    import jax

    from torchft_tpu.models.granite import chunk_log_decay
    from torchft_tpu.optim import make_jit_fused_step

    seen = jax.jit(lambda p, tokens: chunk_log_decay(system.model, p, tokens[:, :-1]))
    step = make_jit_fused_step(system.tx, system.loss_fn)
    opt_state, at = system.tx.init(params), {}
    for n in range(max(steps) + 1):
        if n in steps:
            at[n] = jax.device_get(seen(params, system.tokens(n))).tolist()
        if n < max(steps):
            _, params, opt_state = step(params, opt_state, system.tokens(n))
    return at


def _milliseconds(fn, *args, repeats: int = 10) -> float:
    """Wall ms a call of jitted ``fn`` once warm (the mean of ``repeats``)."""
    import time

    import jax

    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - start) / repeats


def scan_against_recurrence(config, seq: int, seed: int, rehearse: bool) -> dict:
    """The chunked scan on bfloat16 operands against the float32 recurrence
    at the cell's shapes, by path: ``kernels`` is what ``ssd_scan`` takes on a
    TPU (the Mosaic calls; interpreted in a rehearsal, at a toy size they
    take), ``einsums`` the XLA path. Inputs of unit scale, steps and decays
    drawn as the model's initialisers draw them. Beside it the kernels'
    gradients against the einsum path's autodiff (largest difference over the
    largest entry, by argument), the convolution's kernels against
    ``silu(causal_conv)`` in float32, and (on a chip) the wall ms of one
    layer's forward and gradient by path."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops import ssd

    heads, p, n, groups = (config[k] for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups"))
    chunk, width = config["mamba_chunk_size"], config["mamba_d_conv"]
    if rehearse:  # the smallest the kernels take
        seq, heads, p, n, groups, chunk = 256, 4, 64, 128, 1, 128
    interpret = True if rehearse else None
    keys = jax.random.split(jax.random.PRNGKey(seed & 0x7FFFFFFF), 9)
    x = jax.random.normal(keys[0], (1, seq, heads, p), jnp.bfloat16)
    b_in = jax.random.normal(keys[1], (1, seq, groups, n), jnp.bfloat16)
    c_out = jax.random.normal(keys[2], (1, seq, groups, n), jnp.bfloat16)
    dt = jnp.exp(jax.random.uniform(keys[3], (1, seq, heads), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    a = -jax.random.uniform(keys[4], (heads,), minval=1.0, maxval=16.0)
    d_skip = jnp.ones((heads,))
    operands = (x, dt, a, b_in, c_out, d_skip)
    assert ssd.scan_kernel_fits(x, b_in, chunk)
    paths = {
        "kernels": lambda *z: ssd.ssd_scan(*z, chunk=chunk, interpret=interpret),
        "einsums": lambda *z: ssd.ssd_scan_einsums(*z, chunk=chunk),
    }
    relative = lambda got, want: float(
        jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))
        / jnp.max(jnp.abs(want.astype(jnp.float32)))
    )
    want, _ = jax.jit(ssd.ssd_recurrence)(*operands)
    weigh = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)
    out = {
        "shape": list(x.shape), "chunk": chunk, "largest_output": float(jnp.max(jnp.abs(want))),
        "chunk_log_decay": jax.device_get(ssd.chunk_log_decay(dt, a, chunk)).tolist(), "relative": {},
    }
    gradients = {}
    for name, path in paths.items():
        out["relative"][name] = relative(jax.jit(path)(*operands), want)
        gradient = jax.jit(jax.grad(
            lambda *z: jnp.sum(weigh * path(*z).astype(jnp.float32)), argnums=tuple(range(6))
        ))
        gradients[name] = gradient(*operands)
        if not rehearse:
            out.setdefault("gradient_ms", {})[name] = _milliseconds(gradient, *operands)
    out["gradients_kernels_against_einsums"] = {
        leaf: relative(got, other)
        for leaf, got, other in zip(("x", "dt", "A", "B", "C", "D"), gradients["kernels"], gradients["einsums"])
    }
    # The convolution in front of it, over x, B and C together.
    channels = heads * p + 2 * groups * n
    xbc = jax.random.normal(keys[5], (1, seq, channels), jnp.bfloat16)
    kernel = jax.random.uniform(keys[6], (channels, width), minval=-0.5, maxval=0.5).astype(jnp.bfloat16)
    bias = (0.1 * jax.random.normal(keys[7], (channels,))).astype(jnp.bfloat16)
    assert ssd.conv_kernel_fits(xbc, kernel)
    spread = jnp.cos(jnp.arange(xbc.size, dtype=jnp.float32)).reshape(xbc.shape)
    convolutions = {
        "kernels": lambda *z: ssd.conv_silu(*z, interpret=interpret),
        "float32": lambda *z: jax.nn.silu(ssd.causal_conv(*z)),
    }
    results = {}
    for name, path in convolutions.items():
        gradient = jax.jit(jax.value_and_grad(
            lambda *z: jnp.sum(spread * path(*z).astype(jnp.float32)), argnums=(0, 1, 2)
        ))
        results[name] = (jax.jit(path)(xbc, kernel, bias), *gradient(xbc, kernel, bias)[1])
        if not rehearse:
            out.setdefault("convolution_gradient_ms", {})[name] = _milliseconds(gradient, xbc, kernel, bias)
    out["convolution_kernels_against_float32"] = {
        leaf: relative(got, other)
        for leaf, got, other in zip(("out", "dx", "dkernel", "dbias"), results["kernels"], results["float32"])
    }
    return out


def flash_at_head_width_64(config, seq: int, rehearse: bool) -> dict:
    """The flash kernels (compiled; interpreted in a rehearsal) against the
    dense path at the cell's heads and scale, forward and the three gradients:
    the largest absolute difference of each."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops.attention import causal_attention
    from torchft_tpu.ops.flash_attention import flash_attention

    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, scale = config["hidden_size"] // heads, float(config["attention_multiplier"])
    keys = jax.random.split(jax.random.PRNGKey(64), 3)
    q = jax.random.normal(keys[0], (1, seq, heads, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, seq, kv, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, seq, kv, d), jnp.bfloat16)
    blocks = {"block_q": min(512, seq), "block_k": min(1024, seq)}
    weigh = lambda out: jnp.sum(out.astype(jnp.float32) * jnp.cos(jnp.arange(d, dtype=jnp.float32)))
    mine = lambda *z: flash_attention(*z, scale=scale, interpret=rehearse, **blocks)
    plain = lambda *z: causal_attention(*z, scale)
    out, want = jax.jit(mine)(q, k, v), jax.jit(plain)(q, k, v)
    got_grads = jax.jit(jax.grad(lambda *z: weigh(mine(*z)), argnums=(0, 1, 2)))(q, k, v)
    want_grads = jax.jit(jax.grad(lambda *z: weigh(plain(*z)), argnums=(0, 1, 2)))(q, k, v)
    worst = lambda g, w: float(jnp.max(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32))))
    return {
        "shape": [1, seq, heads, kv, d], "scale": scale, "out": worst(out, want),
        **{f"d{name}": worst(g, w) for name, g, w in zip("qkv", got_grads, want_grads)},
    }


def update_by_attention_path(system, params) -> dict:
    """By path of the attention layer (and, on a TPU, of the Mamba layers'
    scan and convolution: ``<path>`` has them on the Mosaic kernels the cell
    runs, ``<path>+einsum_scan`` on the XLA path), over the matrices (every
    kernel but the tied one) and over all leaves: ``sum(g2 * u(gp)) / sum(g2
    * u(g))`` with g and g2 the float32 reference's gradients of batches 0 and
    1, gp the program's of batch 0 and ``u(x) = x / (|x| + eps)``, AdamW's
    first step from zero moments but for the learning rate; ``linear`` is the
    same without ``u``. 1.0 is the reference's own descent."""
    import dataclasses
    import itertools
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import reference
    from torchft_tpu.ops import ssd

    config, architecture = system.config, system.architecture
    eps = float(config["optimizer"]["eps"])

    @jax.jit
    def reference_gradient(params, tokens):
        with jax.default_matmul_precision("highest"):
            p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
            return reference.grad_sum(architecture, p32, tokens, config)

    def leaves(tree):
        return [np.asarray(leaf, np.float32) for leaf in jax.tree_util.tree_leaves(jax.device_get(tree))]

    first, then = system.tokens(0), system.tokens(1)
    g, g2 = leaves(reference_gradient(params, first)), leaves(reference_gradient(params, then))
    matrices = [leaf.ndim >= 2 and leaf.shape[-2:] != (config["vocab_size"], config["hidden_size"]) for leaf in g]
    step = lambda x: x / (np.abs(x) + eps)
    out = {}
    on_kernels = ssd.on_tpu()  # elsewhere the Mamba layers have the one path
    for path, einsum_scan in itertools.product(("auto", "blockwise"), (False, True)[: 1 + on_kernels]):
        model = type(system.model)(dataclasses.replace(system.model.config, attention_impl=path))
        loss = lambda p, tokens: model.apply(p, tokens[:, :-1], targets=tokens[:, 1:])
        with mock.patch.object(ssd, "on_tpu", lambda: on_kernels and not einsum_scan):
            gp = leaves(jax.jit(jax.grad(loss))(params, first))
        path += "+einsum_scan" * einsum_scan
        sums = np.zeros((2, 4))  # (matrices, all) x (step got, step ref, linear got, linear ref)
        for a, b, c, is_matrix in zip(g, g2, gp, matrices):
            # the reference's gradients are sums over the batch's tokens: u() wants the mean's size
            a, b = a / first[:, 1:].size, b / first[:, 1:].size
            row = [np.vdot(step(c), b), np.vdot(step(a), b), np.vdot(c, b), np.vdot(a, b)]
            sums[1] += row
            if is_matrix:
                sums[0] += row
        out[path] = {
            name: {"step": float(s[0] / s[1]), "linear": float(s[2] / s[3])}
            for name, s in zip(("matrices", "all_leaves"), sums)
        }
    return out


def check(bench, config, traffic, seed: int, args, shared) -> dict:
    import jax

    from chipbench import harness
    from chipbench.model import System

    architecture = bench.architecture(config["model_type"])
    system = System(config, architecture, traffic, seed)
    params = system.init_params()
    out = {"seed": seed, "device": jax.devices()[0].device_kind}
    if args.update_by_path:
        out["update_by_attention_path"] = update_by_attention_path(system, params)
        return out
    if args.control:
        out["fp8_control"] = shared.control(system, params)
    else:
        system.reference = harness.reference_losses(system, params)
    want = system.reference
    out["reference"] = want
    out["update_moved_second_loss_by"] = (
        abs(want["second"]["0"] - want["second_without_update"]) / abs(want["first"])
    )
    if not args.reference_only:
        out["scan_against_recurrence"] = scan_against_recurrence(
            config, system.seq, seed, args.rehearse
        )
        out["flash_at_head_width_64"] = flash_at_head_width_64(
            config, min(system.seq, 2048), args.rehearse
        )
        out["ssd_chunk_log_decay_after_steps"] = decays_after(
            system, params, sorted(set(args.steps)) or [0]
        )
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("seeds", type=int, nargs="+")
    parser.add_argument(
        "--steps", type=int, nargs="+", default=[],
        help="the decays after each of these many AdamW steps of the cell (default: 0)",
    )
    parser.add_argument("--no-control", dest="control", action="store_false")
    parser.add_argument("--reference-only", action="store_true", help="the update and the control alone")
    parser.add_argument(
        "--update-by-path", action="store_true",
        help="only the first step's descent on the next batch by the attention layer's path",
    )
    parser.add_argument("--rehearse", action="store_true", help="toy size, any platform")
    args = parser.parse_args()

    from chipbench import harness, reference, spec

    shared = spec.load_module(ROOT / "scripts/keye_selection_check.py")
    bench = spec.Benchmark(ROOT)
    cell = bench.cell(CELL)
    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    if args.rehearse:
        overlay = json.loads(OVERLAY.read_text())
        config = {**config, **overlay["config"]}
        config["run"] = {**config["run"], **overlay["run"]}
        traffic = {**traffic, **overlay["traffic"][cell["traffic"]]}
        for constant, value in overlay["reference"].items():
            setattr(reference, constant, value)
    harness.require_devices(1, args.rehearse)
    harness.enable_compile_cache()
    passed = 0
    for seed in args.seeds:
        out = check(bench, config, traffic, seed, args, shared)
        print(json.dumps(out), flush=True)
        passed += args.control and not args.update_by_path and not out["fp8_control"]["problems"]
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
