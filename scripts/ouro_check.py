#!/usr/bin/env python
"""What the cell ``ouro-2.6b-1chip.ftddp-seq8k`` computes and how exactly, at
its own size and seed, and the controls of its limits:

- the exits at the seeded weights (``models.ouro.exit_stats``): the mean
  probability of leaving at each of the four exits and each exit's mean
  cross-entropy, so a reader sees what the gate weighs;
- the reference's own update: how far ONE AdamW step of the float32 reference
  moves the second loss (the harness asks for 4 limits or more);
- the fp8 control: the float32 reference with every weight in fp8 (e4m3, one
  scale a tensor) through ``harness.reference_check`` under the cell's limits.
  It has to come out NOT correct; the script exits 1 where it does not;
- three broken programs through the same comparison, each of which has to come
  out NOT correct too (exit 1 where one passes): the stack run three times
  instead of four, the loss without its entropy term, and the exits weighed
  uniformly instead of by the gate. Each is the program itself (bfloat16, the
  fused step) with one thing wrong, on the cell's own weights and batches;
- with ``--grad-sum``: the second loss of the program's own step with a shared
  weight's gradient summed over its four uses in bfloat16 (what the cell
  runs: autodiff carries the sum in the weight's dtype) and in float32 (the
  layers' leaves widened ahead of the loop, so the sum is carried wide and
  rounded once), each against the reference's; and what the wide sum costs.

    python scripts/ouro_check.py SEED [SEED ...]              (needs a TPU)
    python scripts/ouro_check.py SEED --grad-sum
    JAX_PLATFORMS=cpu python scripts/ouro_check.py --rehearse 7

One JSON line a seed on stdout; PERF.md section 6 (PR 62) has the readings.
``control`` and ``fp8`` are scripts/keye_selection_check.py's.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

CELL = "ouro-2.6b-1chip.ftddp-seq8k"
OVERLAY = ROOT / "chipbench/fixtures/rehearsal-ouro.json"


def first_two_losses(system, params, loss_fn) -> list:
    """The losses of batch 0 and, after ONE step of the cell's optimizer on
    ``loss_fn``'s gradient, of batch 1: what the harness compares."""
    import jax

    from torchft_tpu.optim import make_jit_fused_step

    # Donated, as the cell's own step is: undonated, the new state beside the
    # old and the step's temporaries do not fit the chip. ``params`` stays whole.
    step = make_jit_fused_step(system.tx, loss_fn, donate_state=True)
    mine = jax.tree_util.tree_map(lambda a: a.copy(), params)
    first, stepped, _ = step(mine, system.tx.init(params), system.tokens(0))
    second = jax.jit(loss_fn)(stepped, system.tokens(1))
    return [float(first), float(second)]


def broken_programs(system) -> dict:
    """{name: loss_fn} of the program with one thing wrong."""
    import jax.numpy as jnp

    import torchft_tpu.models.ouro as ouro

    cfg = system.model.config

    def loss_of(model):
        return lambda p, tokens: model.apply(p, tokens[:, :-1], targets=tokens[:, 1:])

    uniform = loss_of(system.model)

    def uniform_exits(p, tokens):
        flat = lambda z: jnp.full(z.shape, -math.log(z.shape[0]), jnp.float32)
        with mock.patch.object(ouro, "exit_log_probs", flat):
            return uniform(p, tokens)

    return {
        "three_passes": loss_of(ouro.Ouro(dataclasses.replace(cfg, loops=cfg.loops - 1))),
        "no_entropy_term": loss_of(ouro.Ouro(dataclasses.replace(cfg, exit_entropy_coef=0.0))),
        "uniform_exit_weights": uniform_exits,
    }


def differences(system, losses) -> dict:
    """The two relative differences the harness compares, and its verdict."""
    from chipbench import harness

    want = system.reference
    problems = [p for p in harness.reference_check(system, losses) if "loss differs" in p]
    return {
        "first": abs(losses[0] - want["first"]) / abs(want["first"]),
        "second": abs(losses[1] - want["second"]["0"]) / abs(want["second"]["0"]),
        "problems": problems,
    }


def grad_sum_by_dtype(system, params) -> dict:
    """The program's second loss with the shared weights' gradient summed in
    bfloat16 (the parameters' dtype: the cell) and in float32 (the layers'
    leaves widened AHEAD of the loop; a projection rounds its kernel to the
    run dtype where it uses it, so the products are the same and only the
    cotangent's sum over the passes is wide). Both under ``remat`` ``full``,
    which changes no number: the wide copy and the wide gradient are 2.5 GiB
    that do not fit beside what ``dots`` keeps."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models.ouro import Ouro

    model = Ouro(dataclasses.replace(system.model.config, remat="full"))
    narrow = lambda p, tokens: model.apply(p, tokens[:, :-1], targets=tokens[:, 1:])

    def widened(p, tokens):
        layers = {k: v for k, v in p["params"].items() if k == "layers" or k.startswith("layer_")}
        wide = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), layers)
        return narrow({"params": {**p["params"], **wide}}, tokens)

    out = {}
    for name, loss_fn in (("bfloat16", narrow), ("float32", widened)):
        start = time.perf_counter()
        out[name] = differences(system, first_two_losses(system, params, loss_fn))
        out[name]["seconds_with_compile"] = time.perf_counter() - start
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("seeds", type=int, nargs="+")
    parser.add_argument("--grad-sum", action="store_true", help="the gradient's sum by dtype, alone")
    parser.add_argument("--rehearse", action="store_true", help="toy size, any platform")
    args = parser.parse_args()

    import jax

    from chipbench import harness, reference, spec
    from chipbench.model import System
    from torchft_tpu.models.ouro import exit_stats

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
    architecture = bench.architecture(config["model_type"])
    checks = spec.load_module(ROOT / "scripts/keye_selection_check.py")
    failed = False
    for seed in args.seeds:
        system = System(config, architecture, traffic, seed)
        params = system.init_params()
        line = {"seed": seed, "device": jax.devices()[0].device_kind}
        system.reference = harness.reference_losses(system, params)
        if args.grad_sum:
            line["grad_sum"] = grad_sum_by_dtype(system, params)
            print(json.dumps(line), flush=True)
            continue
        tokens = system.tokens(0)
        seen = jax.jit(lambda p, t: exit_stats(system.model, p, t[:, :-1], t[:, 1:]))(params, tokens)
        line["exits"] = {name: jax.device_get(value).tolist() for name, value in seen.items()}
        want = system.reference
        line["reference"] = want
        line["update_moves_second_loss"] = abs(want["second"]["0"] - want["second_without_update"]) / abs(want["first"])
        line["program"] = differences(system, first_two_losses(system, params, system.loss_fn))
        line["broken"] = {
            name: differences(system, first_two_losses(system, params, loss_fn))
            for name, loss_fn in broken_programs(system).items()
        }
        control = checks.control(system, params)
        line["fp8_control"] = {
            "first": abs(control["first"] - want["first"]) / abs(want["first"]),
            "second": abs(control["second"] - want["second"]["0"]) / abs(want["second"]["0"]),
            "problems": control["problems"],
        }
        passed = [name for name, got in line["broken"].items() if not got["problems"]]
        if line["program"]["problems"] or passed or not control["problems"]:
            failed = True
        print(json.dumps(line), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
