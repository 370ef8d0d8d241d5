#!/usr/bin/env python
"""Beside the benchmark's comparison, for the cell
``keye-vl2-30b-a3b-1chip.ftddp-seq8k``, at the cell's own size and on the chip:

- how far the PROGRAM's key selection (bf16 hidden states, float32 indexer) is
  from the float32 reference's, layer by layer: the share of a layer's
  selected (query, key) pairs that the other side did not select. The two
  differ because the hidden states differ by bf16 rounding and a key near a
  query's threshold then falls on the other side of it;
- on a TPU, beside it, the pairs on which the selection of the ONE Mosaic call
  (``ops/key_selection.py``) and the tiled XLA path's differ, layer by layer
  (the same model run again with the call switched off: layer 0 reads the
  same inputs on both sides, a later layer also what an earlier difference did
  to its hidden states): the two differ by the order of a float32 sum, so 0 to
  a few pairs in 10^8;
- the share of the flash kernels' needed blocks (those that hold a pair under
  the diagonal) that the selection leaves WHOLLY EMPTY (a schedule made from
  the operand would not step them) and WHOLLY SELECTED (every pair the causal
  mask allows: they would run without the operand), at 128, 256 and 512 rows
  by 1024 keys, layer by layer: ROADMAP Speed 1(a)'s "measure before
  building";
- the rows each held expert receives (``models.keye.router_load``), so that the
  cell's ``why`` can say how near uniform the routing of seeded weights is,
  and the row count each layer's expert dispatch runs at for them
  (``models.keye.dispatch_rows``: a rung of ``ops.grouped_matmul.dispatch_rungs``);
  with ``--steps 0 30 60`` both again after that many of the cell's own AdamW
  steps on its own batches, and the layer steps by rung over the steps asked
  for: the routing drifts as the cell trains on random tokens;
- the control of ``reference_tolerance``: the float32 reference with every
  weight rounded to fp8 (e4m3, one scale a tensor: the precision below bf16),
  its two losses put through ``harness.reference_check`` as a run's are. It
  has to come out NOT correct; the script exits 1 where it does not.

    python scripts/keye_selection_check.py SEED [SEED ...] [--steps N ...]   (needs a TPU)
    JAX_PLATFORMS=cpu python scripts/keye_selection_check.py --rehearse 7

One JSON line a seed on stdout; PERF.md section 6 (PR 46) has the readings.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

CELL = "keye-vl2-30b-a3b-1chip.ftddp-seq8k"


def fp8(tree):
    import jax
    import jax.numpy as jnp

    def leaf(a):
        scale = jnp.maximum(jnp.max(jnp.abs(a.astype(jnp.float32))), 1e-30) / 448.0
        low = (a.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return (low * scale).astype(a.dtype)

    return jax.tree_util.tree_map(leaf, tree)


def routing_of(system, expected=None):
    """(params, tokens) -> rows by held expert and the rung each layer's
    dispatch takes for them; one compiled program for every call.
    ``expected``: the rows a held expert receives under uniform routing (None:
    from this cell's keys)."""
    import jax
    import numpy as np

    from torchft_tpu.models.experts import dispatch_rows, router_load

    config = system.config
    if expected is None:
        expected = system.tokens_per_step * config["num_experts_per_tok"] / config["num_experts"]
    seen = jax.jit(lambda params, tokens: (
        router_load(system.model, params, tokens), dispatch_rows(system.model, params, tokens)
    ))

    def routing(params, tokens) -> dict:
        rows, taken = (np.asarray(a) for a in seen(params, tokens[:, :-1]))
        return {
            "expected": expected, "min": int(rows.min()), "max": int(rows.max()),
            "mean": float(rows.mean()), "by_layer": rows.tolist(),
            "held_rows_by_layer": rows.sum(axis=1).tolist(),
            "dispatch_rows_by_layer": taken.tolist(),
        }

    return routing


def routing_after(system, routing, params, steps) -> dict:
    """``routing`` of batch N on the state N plain AdamW steps in (the cell's
    optimizer on its own batches 0 .. N - 1), for each N of ``steps``; and the
    count of those layer steps by the rung they take."""
    from torchft_tpu.optim import make_jit_fused_step

    step = make_jit_fused_step(system.tx, system.loss_fn)
    opt_state, at, by_rung = system.tx.init(params), {}, {}
    for n in range(max(steps) + 1):
        if n in steps:
            at[n] = routing(params, system.tokens(n))
            for rung in at[n]["dispatch_rows_by_layer"]:
                by_rung[rung] = by_rung.get(rung, 0) + 1
        if n < max(steps):
            _, params, opt_state = step(params, opt_state, system.tokens(n))
    return {"at_step": at, "layer_steps_by_rung": dict(sorted(by_rung.items()))}


def check(bench, config, traffic, seed: int, steps) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.model import System

    architecture = bench.architecture(config["model_type"])
    system = System(config, architecture, traffic, seed)
    params, tokens = system.init_params(), system.tokens(0)
    out = {"seed": seed, "device": jax.devices()[0].device_kind}

    def program(params, tokens):
        _, seen = system.model.apply(params, tokens[:, :-1], mutable=["intermediates"])
        return seen["intermediates"]["layers"]["block"]["attn"]["selection"][0][:, 0]

    @jax.jit
    def plain(params, tokens):
        with jax.default_matmul_precision("highest"):
            return architecture.selections(params, tokens[0], config)

    @jax.jit
    def differing(mine, theirs):
        # On a TPU the program's selection is the kernels' int8 operand.
        only = jnp.sum((mine != 0) & ~theirs, axis=(1, 2))
        return only, jnp.sum(theirs, axis=(1, 2))

    mine = jax.jit(program)(params, tokens)
    only, selected = differing(mine, plain(params, tokens))
    out["pairs_selected_by_layer"] = np.asarray(selected).tolist()
    out["share_of_pairs_that_differ_by_layer"] = (np.asarray(only) / np.asarray(selected)).tolist()
    out["kernel_pairs_that_differ_from_the_xla_path_by_layer"] = kernel_against_xla(
        program, system.config["sa_config"], params, tokens, mine
    )
    out["flash_blocks_by_rows"] = {
        rows: block_shares(mine != 0, rows, FLASH_BLOCK_KEYS) for rows in (128, 256, 512)
    }

    routing = routing_of(system)
    out["rows_by_held_expert"] = routing(params, tokens)
    out["fp8_control"] = control(system, params)
    if steps:
        out["routing_after_steps"] = routing_after(system, routing, params, steps)
    return out


def kernel_against_xla(program, sa, params, tokens, mine):
    """Per layer, the pairs on which ``mine`` (``program``'s selection as the
    platform makes it) and the tiled XLA path's differ; None where the program
    made its selection by that path itself (off a TPU, or a sequence the call
    does not take)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.ops import key_selection
    from torchft_tpu.ops.sparse_attention import select_keys

    probe = jax.ShapeDtypeStruct((1, mine.shape[-1], 1, sa["indexer_head_dim"]), jnp.float32)
    ran = jax.make_jaxpr(lambda x: select_keys(x, x[:, :, 0], x[..., 0], topk=sa["topk"]))(probe)
    if "pallas_call" not in str(ran):
        return None
    fits = key_selection.fits
    key_selection.fits = lambda *shape: False  # the same model, the call switched off
    try:
        # A function of its own: jit's cache is by function, and ``program``'s
        # entry is the one traced with the call in it.
        tiled = jax.jit(lambda params, tokens: program(params, tokens))(params, tokens)
    finally:
        key_selection.fits = fits
    return np.asarray(jnp.sum((mine != 0) != (tiled != 0), axis=(1, 2))).tolist()


FLASH_BLOCK_KEYS = 1024


def block_shares(chosen, rows: int, keys: int) -> dict:
    """chosen (layers, s, s) bool -> of the (rows x keys) blocks that hold a
    pair under the diagonal, by layer, the share with no selected pair and the
    share whose every causal pair is selected."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    s = chosen.shape[-1]
    rows, keys = min(rows, s), min(keys, s)

    @jax.jit
    def shares(chosen):
        def blocks(x):
            pairs = x.reshape(*x.shape[:-2], s // rows, rows, s // keys, keys)
            return jnp.sum(pairs, axis=(-3, -1), dtype=jnp.int32)

        selected, allowed = blocks(chosen), blocks(jnp.tril(jnp.ones((s, s), bool)))
        needed = allowed > 0
        share = lambda hit: jnp.sum(hit & needed, axis=(1, 2)) / jnp.sum(needed)
        return jnp.sum(needed), share(selected == 0), share(selected == allowed)

    needed, empty, full = shares(chosen)
    return {
        "needed": int(needed),
        "empty_share_by_layer": np.asarray(empty).tolist(),
        "full_share_by_layer": np.asarray(full).tolist(),
    }


def control(system, params) -> dict:
    """The float32 reference on fp8 weights, held to the cell's limits by the
    harness's own comparison: ``problems`` names each loss that is not within
    its limit, and there has to be one."""
    from chipbench import harness, reference

    system.reference = harness.reference_losses(system, params)
    low = fp8(params)
    first = float(reference.make_loss(system.architecture, system.config)(low, system.tokens(0)))
    second = float(reference.make_loss_after_first_update(system.architecture, system.config)(
        low, system.tokens(0), system.tokens(1)))
    problems = harness.reference_check(system, [first, second])
    return {
        "first": first, "second": second,
        "problems": [p for p in problems if "loss differs" in p],
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("seeds", type=int, nargs="+")
    parser.add_argument(
        "--steps", type=int, nargs="+", default=[],
        help="also the routing after each of these many AdamW steps of the cell",
    )
    parser.add_argument("--rehearse", action="store_true", help="toy size, any platform")
    args = parser.parse_args()

    from chipbench import harness, reference, spec

    bench = spec.Benchmark(ROOT)
    cell = bench.cell(CELL)
    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    if args.rehearse:
        overlay = json.loads((ROOT / "chipbench/fixtures/rehearsal-keye.json").read_text())
        config = {**config, **overlay["config"]}
        config["run"] = {**config["run"], **overlay["run"]}
        traffic = {**traffic, **overlay["traffic"][cell["traffic"]]}
        for constant, value in overlay["reference"].items():
            setattr(reference, constant, value)
    harness.require_devices(1, args.rehearse)
    harness.enable_compile_cache()
    passed = 0
    for seed in args.seeds:
        out = check(bench, config, traffic, seed, sorted(set(args.steps)))
        print(json.dumps(out), flush=True)
        passed += not out["fp8_control"]["problems"]
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
