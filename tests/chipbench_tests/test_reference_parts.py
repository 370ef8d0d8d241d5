"""The float32 reference's first update made a part at a time (PR 64,
``reference.make_first_update_by_parts``) against the whole program
(``make_loss_after_first_update``), at toy sizes on the CPU: the same stepped
tree, the same second loss, for the five architecture files and for
``test_another_architecture.py``'s throwaway one, whose tree keeps its layers
in a list that no file of the benchmark has seen. And the rule that chooses
between the two ways, on sizes alone. Tier-1 collects it.

    JAX_PLATFORMS=cpu python -m pytest tests/chipbench_tests/test_reference_parts.py -q

What may differ between the two ways, and so what the comparison allows. AdamW's
first step is ``p - lr * (g / (|g| + eps) + wd * p)``: all but a sign of the
gradient, so an element whose gradient is within rounding of zero (|g| near
eps, 1e-8) lands anywhere between ``-lr`` and ``+lr`` according to the order of
a sum, and the two ways are two programs whose sums XLA may order differently.
Such elements are few (under 0.2% of the state-space toy's, none in the others:
read on the CPU, PR 64) and each differs by under ``2 * lr`` and the rounding of
``run.dtype``. A part left out, moved double or stepped with another part's
gradient differs in a half or in all of that part's elements.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import spec  # noqa: E402
from test_another_architecture import TWOKINDS_ARCHITECTURE  # noqa: E402

HERE = ROOT / "chipbench" / "fixtures"
# (configuration, rehearsal overlay, traffic mix, scan_layers, parts stepped).
# Each of the program's layouts is met: ``layers/block`` (one kind of layer,
# stacked), ``layers/block_<k>`` (a period of kinds, stacked by period: the
# windowed stack's eight layers are two periods of four, the state-space
# stack's ten are one) and ``layer_<i>`` (no scan). A program a part is a
# compilation a part, so the two deep toys step three parts, a layer of each
# kind and of each period among them, and take the whole program's leaves for
# the others.
CASES = {
    "mistral-stacked": ("mistral-7b-v0.3-1chip", "rehearsal.json", "ftddp-seq8k", True, None),
    "mistral-layer_i": ("mistral-7b-v0.3-1chip", "rehearsal.json", "ftddp-seq8k", False, None),
    "keye-stacked": ("keye-vl2-30b-a3b-ep8-1chip", "rehearsal-keye.json", "ftddp-seq8k", True, None),
    "ouro-stacked": ("ouro-2.6b-1chip", "rehearsal-ouro.json", "ftddp-seq8k", True, None),
    "smallthinker-by-period": ("smallthinker-21b-a3b-ep8-1chip", "rehearsal-smallthinker.json",
                               "ftddp-seq16k", True, ("outside", "layer_0", "layer_7")),
    "granite-by-period": ("granite-4.0-h-micro-1chip", "rehearsal-granite.json", "ftddp-seq8k",
                          True, ("outside", "layer_0", "layer_5")),
    "twokinds-list": ("twokinds", "rehearsal.json", "ftddp-seq8k", None, None),
}
# Of a part's elements, the share that may differ between the two ways; a
# broken part differs in half of its elements or more.
MAY_DIFFER, BROKEN_DIFFERS = 0.01, 0.3
# Relative, the two ways' second losses: the elements that differ have gradients
# within rounding of zero, and an element moves the loss by its gradient times
# its difference (under 2 * lr): 1e3 elements x 1e-7 x 6e-3 is under 1e-6 of a
# loss near 6; the rest is the float32 loss's own last digits (6e-8 each).
SECOND_LOSS_RELATIVE = 1e-6


@pytest.fixture(scope="module")
def bench():
    return spec.Benchmark(ROOT)


def system_of(bench, case: str, tmp_path: Path, seed: int = 7):
    """The toy system of a case, and the reference's block sizes its overlay
    sets (to be laid on ``chipbench.reference`` by the caller)."""
    from chipbench.model import System

    name, fixture, traffic_name, scan, _ = CASES[case]
    overlay = json.loads((HERE / fixture).read_text())
    if name == "twokinds":
        (tmp_path / "twokinds.py").write_text(TWOKINDS_ARCHITECTURE)
        architecture = spec.load_module(tmp_path / "twokinds.py")
        config = {**bench.config("mistral-7b-v0.3-1chip"), **overlay["config"]}
        config.update(model_type="twokinds", num_hidden_layers=4, num_experts=4, vocab_size=64)
    else:
        config = {**bench.config(name), **overlay["config"]}
        architecture = bench.architecture(config["model_type"])
    config["run"] = {**config["run"], **overlay.get("run", {})}
    if scan is not None:
        config["run"]["scan_layers"] = scan
    traffic = {**bench.traffic(traffic_name), **overlay["traffic"][traffic_name]}
    return System(config, architecture, traffic, seed=seed), overlay.get("reference", {})


def whole_step(system, params, first):
    """The stepped tree of the WHOLE program, as ``make_loss_after_first_update``
    makes it inside its one program."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference

    @jax.jit
    def step(params, first):
        with jax.default_matmul_precision("highest"):
            p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
            total = reference.grad_sum(system.architecture, p32, first, system.config)
            count = first.shape[0] * (first.shape[1] - 1)
            return reference.first_adamw_step(p32, total, count, system.config)

    return step(params, first)


def differing_share(want, got, places):
    """Of the elements of the leaves ``places`` (positions among the tree's
    leaves), the share that differs between two trees, and the largest
    difference."""
    import jax

    a_leaves, b_leaves = jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got)
    differ = size = 0
    worst = 0.0
    for at in places:
        a, b = np.asarray(a_leaves[at], np.float32), np.asarray(b_leaves[at], np.float32)
        assert a.shape == b.shape and a_leaves[at].dtype == b_leaves[at].dtype
        differ, size = differ + int((a != b).sum()), size + a.size
        worst = max(worst, float(np.abs(a - b).max()))
    return differ / size, worst


@pytest.mark.parametrize("case", list(CASES))
def test_the_update_by_parts_is_the_whole_programs(case, bench, tmp_path, monkeypatch):
    """Leaf by leaf and by the second loss, in the layout the system's tree has;
    and a part left out, moved double or stepped with another part's gradient
    is not."""
    import jax

    from chipbench import reference

    system, blocks = system_of(bench, case, tmp_path)
    for name, value in blocks.items():
        monkeypatch.setattr(reference, name, value)
    config, params = system.config, system.init_params()
    first, then = system.tokens(0), system.tokens(1)

    want = whole_step(system, params, first)
    names = reference.parts_of(params)
    assert "outside" in names and len(names) == 1 + config["num_hidden_layers"]
    step = lambda name: reference.make_part_step(system.architecture, config, name)(params, first)
    by_parts = CASES[case][4] or tuple(names)
    leaves_of_want = jax.tree_util.tree_leaves(reference._unstacked(want["params"]))
    stepped = {
        name: step(name) if name in by_parts
        else [leaves_of_want[at] for at in places]
        for name, places in names.items()
    }
    kept = {name: list(leaves) for name, leaves in stepped.items()}
    got = reference.assemble(params, stepped)
    assert not stepped  # the tree is the only holder of its arrays
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(params)

    # The same places of the system's tree, by part: the unstacked tree's
    # leaves are the stacked one's slices, so compare part by part UNSTACKED.
    unstacked = lambda tree: reference._unstacked(tree["params"])
    lr = float(config["optimizer"]["learning_rate"])
    for name, places in names.items():
        share, worst = differing_share(unstacked(want), unstacked(got), places)
        assert share <= MAY_DIFFER and worst <= 2.5 * lr, (name, share, worst)

    # The whole program's own second loss is read in the test below; here the
    # same program reads both trees.
    loss = reference.make_loss(system.architecture, config)
    whole = float(loss(want, then))
    assert abs(float(loss(got, then)) - whole) <= SECOND_LOSS_RELATIVE * abs(whole)

    # Broken on purpose, one part each: the first layer and what is outside.
    layers = [name for name in by_parts if name != "outside"]
    original = jax.tree_util.tree_leaves(unstacked(params))
    of = lambda name: [original[at] for at in names[name]]
    cast = lambda leaves, like: [a.astype(b.dtype) for a, b in zip(leaves, like)]
    alike = [n for n in layers[1:] if [a.shape for a in of(n)] == [a.shape for a in of(layers[0])]]
    broken = {
        "left out": lambda name: cast(of(name), kept[name]),
        "moved double": lambda name: [2 * s - p.astype(s.dtype) for s, p in zip(kept[name], of(name))],
    }
    for part in by_parts[:2]:
        for how, make in broken.items():
            wrong = reference.assemble(params, {**{n: list(v) for n, v in kept.items()}, part: make(part)})
            share, _ = differing_share(unstacked(want), unstacked(wrong), names[part])
            assert share >= BROKEN_DIFFERS, (part, how, share)
    if alike:  # another layer's step laid on the first layer's weights
        other = alike[0]
        moved = [p.astype(s.dtype) + (s - q.astype(s.dtype))
                 for p, s, q in zip(of(layers[0]), kept[other], of(other))]
        wrong = reference.assemble(params, {**{n: list(v) for n, v in kept.items()}, layers[0]: moved})
        share, _ = differing_share(unstacked(want), unstacked(wrong), names[layers[0]])
        assert share >= BROKEN_DIFFERS, ("another part's gradient", share)


@pytest.mark.parametrize("groups", [2])
def test_reference_losses_reads_the_same_by_either_way(groups, bench, tmp_path, monkeypatch):
    """``harness.reference_losses`` through both ways, for one group and for
    the three sets of two groups that can have taken part in step 0."""
    from chipbench import harness, reference

    system, blocks = system_of(bench, "mistral-stacked", tmp_path)
    for name, value in blocks.items():
        monkeypatch.setattr(reference, name, value)
    params = system.init_params()
    whole = harness.reference_losses(system, params, 0, groups, by_parts=False)
    parts = harness.reference_losses(system, params, 0, groups, by_parts=True)
    assert list(whole["second"]) == (["0"] if groups == 1 else ["0", "1", "0+1"])
    assert parts["first"] == whole["first"]
    assert parts["second_without_update"] == whole["second_without_update"]
    for members, want in whole["second"].items():
        assert abs(parts["second"][members] - want) <= SECOND_LOSS_RELATIVE * abs(want), members
    if groups == 2:  # the sets differ, so the check can tell which took part
        assert len(set(whole["second"].values())) == 3
    # With no device limit to read (the CPU reports none) the rule takes the whole.
    assert harness.reference_losses(system, params, 0, 1) == harness.reference_losses(
        system, params, 0, 1, by_parts=False)


def test_the_rule_takes_the_whole_for_every_listed_configuration_and_the_parts_above_it(bench):
    """On sizes alone: every configuration the benchmark lists, by its
    architecture file's ``parameter_counts``, on a chip of 15.75 GiB takes the
    whole program, as it did before PR 64; the Mistral file at five layers
    (1.36G parameters) takes the parts; a device that reports no limit takes
    the whole whatever the size."""
    from chipbench import reference

    chip = int(15.75 * 2**30)
    totals = {}
    for entry in bench.data["configs"]:
        config = bench.config(entry["name"])
        totals[entry["name"]] = bench.architecture(config["model_type"]).parameter_counts(config)["total"]
        assert reference.whole_update_fits(totals[entry["name"]], chip), entry["name"]
    assert max(totals.values()) == totals["granite-4.0-h-micro-1chip"] > 790e6
    mistral = bench.config("mistral-7b-v0.3-1chip")
    five = bench.architecture("mistral").parameter_counts({**mistral, "num_hidden_layers": 5})["total"]
    assert 1.35e9 < five < 1.37e9 and not reference.whole_update_fits(five, chip)
    assert reference.whole_update_fits(five, 0)
    # The ceiling of the whole program on this chip, in parameters.
    assert 0.93e9 < chip / reference.WHOLE_UPDATE_BYTES_PER_PARAMETER < 0.95e9
