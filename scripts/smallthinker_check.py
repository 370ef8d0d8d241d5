#!/usr/bin/env python
"""What the cell ``smallthinker-21b-a3b-1chip.ftddp-seq16k`` routes and walks,
at its own size and seed, and the control of its limits:

- the rows each held expert receives (``models.experts.router_load``) and
  the row count each layer's expert dispatch runs at for them
  (``dispatch_rows``: a rung of ``ops.grouped_matmul.dispatch_rungs``; 24,576 /
  49,152 / 98,304 rows in the cell), on the seeded weights and, with
  ``--steps 0 30 60``, again after so many of the cell's own AdamW steps, with
  the layer steps by rung;
- the (q block, KV block) pairs the flash kernels' schedule classes above the
  diagonal, on it, under it and, in a windowed layer, behind the window and on
  its edge (``ops.flash_attention._class_counts``), for the two kinds of layer
  at the cell's sequence and the model's blocks, with the grid ``steps`` a
  head's forward call takes (the needed pairs alone, since PR 55: the model's
  calls bring no position arrays) and how many of them compute one half of
  their KV block alone (``halves``, since PR 56: 16 of a full layer's 272, 28
  of a windowed layer's 140), beside the closed form of the (query, key)
  pairs each kind needs;
- the fp8 control: the float32 reference with every weight in fp8 (e4m3, one
  scale a tensor) through ``harness.reference_check`` under the cell's limits.
  It has to come out NOT correct; the script exits 1 where it does not.

    python scripts/smallthinker_check.py SEED [SEED ...] [--steps N ...]   (needs a TPU)
    JAX_PLATFORMS=cpu python scripts/smallthinker_check.py --rehearse 7

One JSON line a seed on stdout; PERF.md section 6 (PR 54) has the readings.
``routing_of``, ``routing_after`` and ``control`` are scripts/keye_selection_check.py's.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

CELL = "smallthinker-21b-a3b-1chip.ftddp-seq16k"
OVERLAY = ROOT / "chipbench/fixtures/rehearsal-smallthinker.json"


def pair_classes(system, architecture) -> dict:
    """The schedule's block pairs by class, the grid steps of a head's
    forward (``block_pairs["steps"]``) and those of them that run one half of
    their KV block (``block_pairs["halves"]``) for a full and for a windowed
    layer of the cell, and the (query, key) pairs each needs by the closed
    form."""
    from torchft_tpu.ops.flash_attention import _class_counts

    cfg, seq = system.model.config, system.seq
    blocks = (cfg.attention_block_size, cfg.attention_block_k or cfg.attention_block_size)
    window = min(cfg.window, seq)
    return {
        "blocks": list(blocks),
        "full": {
            "block_pairs": _class_counts(seq, seq, *blocks),
            "needed_pairs": architecture.attention_pairs(seq),
        },
        "windowed": {
            "window": window,
            "block_pairs": _class_counts(seq, seq, *blocks, window=window if window < seq else None),
            "needed_pairs": architecture.attention_pairs(seq, window),
        },
    }


def check(bench, config, traffic, seed: int, steps, shared) -> dict:
    import jax

    from chipbench.model import System

    architecture = bench.architecture(config["model_type"])
    system = System(config, architecture, traffic, seed)
    params = system.init_params()
    routing = shared.routing_of(
        system,
        system.tokens_per_step * config["moe_num_active_primary_experts"] / config["router_width"],
    )
    out = {
        "seed": seed, "device": jax.devices()[0].device_kind,
        "pairs_by_class": pair_classes(system, architecture),
        "rows_by_held_expert": routing(params, system.tokens(0)),
        "fp8_control": shared.control(system, params),
    }
    if steps:
        out["routing_after_steps"] = shared.routing_after(system, routing, params, steps)
    return out


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
        out = check(bench, config, traffic, seed, sorted(set(args.steps)), shared)
        print(json.dumps(out), flush=True)
        passed += not out["fp8_control"]["problems"]
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
