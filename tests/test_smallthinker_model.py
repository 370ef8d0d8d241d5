"""models/smallthinker.py against plain float32 mathematics, at a small size on
seeded weights (CPU): the model against the benchmark's float32 reference
(chipbench/architectures/smallthinker.py, written from the equations), loss and
every leaf's gradient, on the CPU's attention paths and through the flash
kernels (interpreted); the eight shares of the routed layer against the uncut
layer; that a layer's routing does not move with its attention; and that a
remat policy which keeps the router's decisions decides once and differentiates
the same. The stack,
the window's edge and the positions are tests/test_smallthinker_stack.py's.

    JAX_PLATFORMS=cpu python -m pytest tests/test_smallthinker_model.py -q
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import reference, spec  # noqa: E402
from torchft_tpu.models.experts import RoutedExperts, route, routing_saveable  # noqa: E402
from torchft_tpu.models.smallthinker import dispatch_rows, router_load  # noqa: E402
from torchft_tpu.ops.grouped_matmul import dispatch_rungs, routed_experts  # noqa: E402

ARCHITECTURE = spec.load_module(ROOT / "chipbench/architectures/smallthinker.py")
SEQ, BATCH = 96, 2
# Float32 on both sides: they differ in the order of their sums.
TOLERANCE = 1e-5


def toy_config() -> dict:
    """The cell's configuration file under its rehearsal overlay: every key the
    architecture file reads, at a toy size (two periods of four layers, a
    window of 24, 14 query heads over 2 key-value heads)."""
    config = json.loads(
        (ROOT / "chipbench/configs/smallthinker-21b-a3b-ep8-1chip.json").read_text()
    )
    overlay = json.loads((ROOT / "chipbench/fixtures/rehearsal-smallthinker.json").read_text())
    config = {**config, **overlay["config"]}
    config["run"] = {**config["run"], **overlay["run"]}
    # The model's own initialisation: the scales the cell lays over it are the
    # yardstick's (tests/chipbench_tests/test_smallthinker_cell.py), and at the
    # cell's embedding scale a layer's branches are too small beside the
    # stream to move a toy's routing.
    config["run"].update(embedding_init_scale=1.0, head_init_scale=1.0)
    return config


@pytest.fixture(scope="module")
def toy():
    config = toy_config()
    model = ARCHITECTURE.build(config, SEQ)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (BATCH, SEQ + 1), 0, config["vocab_size"])
    params = model.init(jax.random.PRNGKey(3), tokens[:, :-1])
    return config, model, params, tokens


@pytest.fixture(autouse=True)
def small_reference_blocks(monkeypatch):
    """A toy sequence is still three blocks of the reference's head and twelve
    of its attention."""
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)
    monkeypatch.setattr(reference, "HEAD_BLOCK", 32)


def program_loss(model, params, tokens):
    return model.apply(params, tokens[:, :-1], targets=tokens[:, 1:])


def relative(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def flat(tree) -> dict:
    return {
        "/".join(str(getattr(k, "key", k)) for k in path): leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


def reference_loss_and_gradient(config, params, tokens):
    count = tokens.shape[0] * (tokens.shape[1] - 1)

    def mean_loss(p):
        with jax.default_matmul_precision("highest"):
            return sum(ARCHITECTURE.sequence_loss(p, seq, config) for seq in tokens) / count

    return jax.value_and_grad(mean_loss)(params)


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_loss_and_every_leafs_gradient_agree_with_the_float32_reference(impl, toy):
    """On the CPU's dense path (what a rehearsal runs) and through the flash
    kernels, interpreted, forward and the one backward call."""
    config, _, params, tokens = toy
    tokens = tokens[:1]  # one sequence: the reference writes each out, layer by layer
    config = {**config, "run": {**config["run"], "attention_impl": impl}}
    model = ARCHITECTURE.build(config, SEQ)
    want_loss, want = reference_loss_and_gradient(config, params, tokens)
    with jax.default_matmul_precision("highest"):
        got_loss, got = jax.value_and_grad(lambda p: program_loss(model, p, tokens))(params)
    assert abs(float(got_loss) - float(want_loss)) / float(want_loss) < TOLERANCE
    got, want = flat(got), flat(want)
    assert set(got) == set(want) and len(got) == 4 * 10 + 3
    for name in sorted(want):
        assert float(jnp.linalg.norm(want[name])) > 0, name
        assert relative(got[name], want[name]) < 10 * TOLERANCE, name


@pytest.mark.parametrize("impl", ["auto", "blockwise"])
def test_the_harness_reference_program_agrees_with_the_model(impl, toy):
    """``reference.make_loss`` around the architecture's ``sequence_loss``, as
    the harness calls it, on the first batch; the model as the file builds it
    and on the blockwise path."""
    config, _, params, tokens = toy
    config = {**config, "run": {**config["run"], "attention_impl": impl}}
    want = reference.make_loss(ARCHITECTURE, config)(params, tokens)
    got = program_loss(ARCHITECTURE.build(config, SEQ), params, tokens)
    assert abs(float(got) - float(want)) / float(want) < TOLERANCE


def test_the_eight_shares_add_up_to_the_uncut_expert_layer():
    """The parts of the result that the eight shares give add up to the uncut
    layer's (the residual is outside the layer, so it is counted once), with
    the router's logits handed in from OTHER rows than the experts see, and
    the uncut layer is the reference's sum over all experts (ReLU in the gated
    unit)."""
    whole = RoutedExperts(
        dim=32, hidden=24, num_experts=16, experts_per_token=3, num_local_experts=16,
        activation=jax.nn.relu, dtype=jnp.float32,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32))
    seen_by_router = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 32))
    params = whole.init(jax.random.PRNGKey(1), x)
    apply = lambda layer, p: layer.apply(
        p, x, layer.apply(p, seen_by_router, method=layer.logits)
    )
    uncut = apply(whole, params)
    parts = []
    for share in range(8):
        held = slice(2 * share, 2 * share + 2)
        mine = {"params": {**params["params"], **{
            name: params["params"][name][held] for name in ("w_gate", "w_up", "w_down")
        }}}
        cut = whole.clone(num_local_experts=2, expert_share=share)
        parts.append(apply(cut, mine))
    assert relative(sum(parts), uncut) < 1e-6
    assert all(float(jnp.linalg.norm(p)) > 0 for p in parts)
    config = {
        "moe_num_primary_experts": 16, "expert_share": 0, "moe_num_active_primary_experts": 3,
    }
    weights = {name: params["params"][name] for name in ("w_gate", "w_up", "w_down")}
    router = params["params"]["router"]["kernel"]
    want = jnp.stack([
        ARCHITECTURE._experts(rows, seen @ router, weights, config)
        for rows, seen in zip(x, seen_by_router)
    ])
    assert relative(uncut, want) < 1e-5
    # Read from the rows themselves it is another routing.
    assert relative(whole.apply(params, x), uncut) > 1e-2


@pytest.mark.parametrize("kept", ["routing", "nothing"])
def test_a_rematerialised_routed_layer_differentiates_as_the_plain_one(kept):
    """A share of the routed layer (four of sixteen experts: the ladder and its
    conditional) under ``jax.checkpoint``: with ``routing_saveable`` the
    backward reads what ``route`` decided (the differentiated program holds
    the top-k, the sort and the tally as often as the plain one's), with
    ``nothing_saveable`` it decides again (each once more); either way the
    router kernel's gradient, the input's and, through ``route`` and the
    grouped product alone, the probabilities' (which is the top-k's and the
    gates' backward) are the plain layer's."""
    policy = {
        "routing": routing_saveable, "nothing": jax.checkpoint_policies.nothing_saveable,
    }[kept]
    layer = RoutedExperts(
        dim=32, hidden=24, num_experts=16, experts_per_token=3, num_local_experts=4,
        expert_share=1, activation=jax.nn.relu, dtype=jnp.float32,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32))
    params = layer.init(jax.random.PRNGKey(1), x)
    target = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    weights = [params["params"][name] for name in ("w_gate", "w_up", "w_down")]

    def through_layer(p, x):
        return jnp.sum(layer.apply(p, x) * target)

    def through_route(probs, flat):
        out, _ = routed_experts(
            flat, *route(probs, 3, 4, 1), *weights, num_experts=16, activation=jax.nn.relu
        )
        return jnp.sum(out * target.reshape(out.shape))

    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(3), (48, 16)), axis=-1)
    router_ops = ("top_k", "sort", "scatter-add")  # the tally is the last: ``bincount``
    for fn, operands in ((through_layer, (params, x)), (through_route, (probs, x.reshape(48, 32)))):
        plain = jax.grad(fn, argnums=(0, 1))
        again = jax.grad(jax.checkpoint(fn, policy=policy), argnums=(0, 1))
        want, got = flat(plain(*operands)), flat(again(*operands))
        assert any("router" in name for name in want) or fn is through_route
        for name, leaf in want.items():
            assert float(jnp.linalg.norm(leaf)) > 0 and relative(got[name], leaf) < 1e-6, name
        once, decided = (
            [str(jax.make_jaxpr(g)(*operands)).count(f" {prim}[") for prim in router_ops]
            for g in (plain, again)
        )
        assert once[:2] == [1, 1] and decided == [n + (kept == "nothing") for n in once], decided


def test_a_layers_routing_does_not_move_with_its_attention(toy):
    """The router reads the block's input ahead of attention: every layer's
    attention turned upside down (its output projection negated) leaves layer
    0's rows by expert where they were (and moves the later layers', whose
    inputs it changes), where a router behind attention would move layer 0's
    too."""
    _, model, params, tokens = toy
    before = router_load(model, params, tokens[:, :-1])
    assert before.shape == (8, 4)
    shaken = jax.tree_util.tree_map_with_path(
        lambda path, a: -a if any(getattr(k, "key", "") == "wo" for k in path) else a, params
    )
    after = router_load(model, shaken, tokens[:, :-1])
    assert np.array_equal(np.asarray(before[0]), np.asarray(after[0]))
    assert not np.array_equal(np.asarray(before[1:]), np.asarray(after[1:]))
    rungs = dispatch_rungs(BATCH * SEQ, 3, 4, 16)
    assert set(np.asarray(dispatch_rows(model, params, tokens[:, :-1])).tolist()) <= set(rungs)
    assert int(jnp.sum(before)) <= BATCH * SEQ * 3 * 8


