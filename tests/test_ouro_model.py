"""models/ouro.py at toy size on the CPU (PR 62): the program against the
float32 reference of chipbench/architectures/ouro.py, loss and every leaf's
gradient; the scanned stack against the unrolled one; the looped stack at one
pass against ``layer_stack``; a shared weight's gradient against four untied
copies'; the exit distribution; what is sown and which scopes the program
carries.

    JAX_PLATFORMS=cpu python -m pytest tests/test_ouro_model.py -q
"""

from __future__ import annotations

import json
import re
import sys
from dataclasses import replace
from pathlib import Path

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import reference, spec  # noqa: E402
from torchft_tpu.models import ouro  # noqa: E402
from torchft_tpu.models.decoder import RMSNorm, layer_stack, looped_stack  # noqa: E402

ARCHITECTURE = spec.load_module(ROOT / "chipbench/architectures/ouro.py")
OVERLAY = json.loads((ROOT / "chipbench/fixtures/rehearsal-ouro.json").read_text())
BATCH, SEQ = 2, 64  # two of the reference's toy blocks of 32


def toy_config() -> dict:
    config = json.loads((ROOT / "chipbench/configs/ouro-2.6b-1chip.json").read_text())
    config.update(OVERLAY["config"])
    config["run"] = {**config["run"], **OVERLAY["run"]}
    config["num_hidden_layers"] = 3  # a toy's depth; the passes stay four
    return config


def by_path(tree) -> dict:
    return {
        "/".join(str(k.key) for k in path): leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


def unstacked(params):
    """A scanned model's tree in the unrolled model's layout."""
    tree = dict(params["params"])
    stacked = tree.pop("layers")["block"]
    depth = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    for layer in range(depth):
        tree[f"layer_{layer}"] = jax.tree_util.tree_map(lambda a: a[layer], stacked)
    return {"params": tree}


@pytest.fixture(scope="module")
def toy():
    """The toy model (scanned, ``dots``), seeded weights and tokens, and the
    loss and gradient of the program and of the reference, once."""
    config = toy_config()
    model = ARCHITECTURE.build(config, SEQ)
    tokens = jax.random.randint(jax.random.PRNGKey(62), (BATCH, SEQ + 1), 0, config["vocab_size"])
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])
    loss_fn = lambda p: model.apply(p, tokens[:, :-1], targets=tokens[:, 1:])
    saved = {name: getattr(reference, name) for name in OVERLAY["reference"]}
    for name, value in OVERLAY["reference"].items():
        setattr(reference, name, value)
    try:
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
        want = float(reference.make_loss(ARCHITECTURE, config)(params, tokens))
        with jax.default_matmul_precision("highest"):
            total = reference.grad_sum(ARCHITECTURE, params, tokens, config)
    finally:
        for name, value in saved.items():
            setattr(reference, name, value)
    want_grads = jax.tree_util.tree_map(lambda g: g / (BATCH * SEQ), total)
    return {
        "config": config, "model": model, "params": params, "tokens": tokens, "loss_fn": loss_fn,
        "loss": float(loss), "grads": by_path(grads), "want": want, "want_grads": by_path(want_grads),
    }


LEAVES = [
    "final_norm/scale", "lm_head/kernel", "tok_embed/embedding", "exit_gate/kernel", "exit_gate/bias",
    "layers/block/attn_norm/scale", "layers/block/attn_post_norm/scale",
    "layers/block/mlp_norm/scale", "layers/block/mlp_post_norm/scale",
    "layers/block/attn/wq/kernel", "layers/block/attn/wk/kernel", "layers/block/attn/wv/kernel",
    "layers/block/attn/wo/kernel", "layers/block/mlp/w_gate/kernel", "layers/block/mlp/w_up/kernel",
    "layers/block/mlp/w_down/kernel",
]


def test_the_loss_is_the_references(toy):
    assert toy["loss"] == pytest.approx(toy["want"], rel=2e-6)
    # four exits of a model that knows nothing yet: log(vocabulary) and the logits' variance
    assert 5.5 < toy["loss"] < 7.0


@pytest.mark.parametrize("leaf", LEAVES)
def test_a_leafs_gradient_is_the_references(leaf, toy):
    got, want = toy["grads"]["params/" + leaf], toy["want_grads"]["params/" + leaf]
    assert got.shape == want.shape and float(jnp.max(jnp.abs(want))) > 0
    # The gate's bias is ONE number, the sum over every token and exit of terms of
    # either sign: float32's order of summation shows in it where a matrix's largest
    # entry hides it.
    limit = 1e-4 if leaf == "exit_gate/bias" else 2e-5
    assert float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))) < limit, leaf


def test_the_leaves_tested_are_all_the_leaves_and_one_copy_of_each(toy):
    assert sorted("params/" + name for name in LEAVES) == sorted(by_path(toy["params"]))
    counts = ARCHITECTURE.parameter_counts(toy["config"])
    leaves = jax.tree_util.tree_leaves(toy["params"])
    assert counts["total"] == sum(leaf.size for leaf in leaves)
    # The tree of a model whose stack runs once: the loop adds no leaf and no axis.
    once = ARCHITECTURE.build({**toy["config"], "total_ut_steps": 1}, SEQ)
    shapes = lambda p: {k: v.shape for k, v in by_path(p).items()}
    assert shapes(jax.eval_shape(once.init, jax.random.PRNGKey(0), toy["tokens"][:, :-1])) == shapes(toy["params"])
    assert by_path(toy["params"])["params/layers/block/mlp/w_up/kernel"].shape == (3, 64, 96)


@pytest.mark.parametrize("remat", ["none", "dots", "full"])
def test_the_unrolled_stack_is_the_scanned_one(remat, toy):
    """``scan_layers`` off: the same modules called pass after pass, leaves
    under ``layer_<i>``; the same loss and gradients on the same weights."""
    cfg = replace(toy["model"].config, scan_layers=False, remat=remat)
    tokens = toy["tokens"]
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: ouro.Ouro(cfg).apply(p, tokens[:, :-1], targets=tokens[:, 1:])
    ))(unstacked(toy["params"]))
    assert float(loss) == pytest.approx(toy["loss"], rel=1e-6)
    want = by_path(toy_grads_tree(toy))
    for path, got in by_path(grads).items():
        np.testing.assert_allclose(got, want[path], rtol=2e-4, atol=1e-7, err_msg=path)


def toy_grads_tree(toy):
    """The scanned model's gradients as a tree again, in the unrolled layout."""
    tree = {}
    for path, leaf in toy["grads"].items():
        node = tree
        *parents, last = path.split("/")
        for key in parents:
            node = node.setdefault(key, {})
        node[last] = leaf
    return unstacked(tree)


class Looped(nn.Module):
    """A stack of models/ouro.py's blocks through ``looped_stack``, or (``loops``
    None) once through ``layer_stack`` and the same final norm."""

    config: ouro.OuroConfig
    loops: int | None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        norm = lambda: RMSNorm(cfg.norm_eps, cfg.dtype, cfg.norm_dtype, name="final_norm")
        if self.loops is None:
            return norm()(layer_stack(ouro.Block, cfg, None, x, positions))[None]
        return looped_stack(self, ouro.Block, cfg, None, x, positions, self.loops, norm)


@pytest.fixture(scope="module")
def stack_inputs(toy):
    cfg = replace(toy["model"].config, remat="none")
    x = jax.random.normal(jax.random.PRNGKey(3), (BATCH, SEQ, cfg.dim))
    positions = jnp.broadcast_to(jnp.arange(SEQ), (BATCH, SEQ))
    return cfg, x, positions


@pytest.mark.parametrize("scan_layers", [False, True], ids=["loop", "scan"])
def test_the_looped_stack_at_one_pass_is_layer_stack_on_the_same_tree(scan_layers, stack_inputs):
    cfg, x, positions = stack_inputs
    cfg = replace(cfg, scan_layers=scan_layers)
    plain, looped = Looped(cfg, None), Looped(cfg, 1)
    params = plain.init(jax.random.PRNGKey(1), x, positions)
    again = looped.init(jax.random.PRNGKey(1), x, positions)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(again)
    for path, leaf in by_path(params).items():
        np.testing.assert_array_equal(leaf, by_path(again)[path], err_msg=path)
    run = lambda model: jax.jit(model.apply)(params, x, positions)
    np.testing.assert_array_equal(run(plain), run(looped))


@pytest.mark.parametrize("scan_layers", [False, True], ids=["loop", "scan"])
def test_a_shared_weights_gradient_is_the_sum_over_four_untied_copies(scan_layers, stack_inputs):
    """The loop's states from four copies of the weights, pass t reading copy
    t: the shared weights' gradient is the sum of the four copies'."""
    cfg, x, positions = stack_inputs
    cfg = replace(cfg, scan_layers=scan_layers)
    looped, once = Looped(cfg, 4), Looped(cfg, 1)
    params = looped.init(jax.random.PRNGKey(1), x, positions)
    weigh = jnp.cos(jnp.arange(4 * x.size, dtype=jnp.float32)).reshape(4, *x.shape)

    def untied(copies):
        h, total = x, 0.0
        for t, copy in enumerate(copies):
            h = once.apply(copy, h, positions)[0]
            total = total + jnp.sum(weigh[t] * h)
        return total

    shared = jax.jit(jax.grad(lambda p: jnp.sum(weigh * looped.apply(p, x, positions))))(params)
    by_copy = jax.jit(jax.grad(untied))([params] * 4)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *by_copy)
    for path, got in by_path(shared).items():
        want = by_path(summed)[path]
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(want))), err_msg=path)
        # ... and no copy's share is nothing: every pass reaches every weight
        assert all(float(jnp.max(jnp.abs(by_path(g)[path]))) > 0 for g in by_copy), path


def test_the_exit_distribution_sums_to_one_and_the_last_exit_takes_the_rest():
    z = 3.0 * jax.random.normal(jax.random.PRNGKey(5), (4, 7, 11))
    log_p = ouro.exit_log_probs(z)
    p, lam = jnp.exp(log_p), jax.nn.sigmoid(z)
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-6)
    np.testing.assert_allclose(p[1], lam[1] * (1 - lam[0]), rtol=2e-4, atol=1e-7)
    np.testing.assert_allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]), rtol=2e-4, atol=1e-7)
    np.testing.assert_allclose(p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), rtol=2e-4, atol=1e-7)
    np.testing.assert_allclose(log_p, ARCHITECTURE.exit_log_probs(z.reshape(4, -1)).reshape(z.shape), rtol=1e-6)
    # The last pass's gate takes no part: no gradient reaches its logit.
    grad = jax.grad(lambda z: jnp.sum(jnp.sin(ouro.exit_log_probs(z))))(z)
    assert float(jnp.max(jnp.abs(grad[-1]))) == 0.0 and float(jnp.min(jnp.abs(grad[:-1]))) > 0.0
    # A gate far on either side: the logarithms stay finite where log(1 - lambda) would not.
    far = ouro.exit_log_probs(jnp.array([[60.0], [-60.0], [0.0], [0.0]]))
    assert bool(jnp.all(jnp.isfinite(far))) and float(far[1, 0]) == pytest.approx(-120.0)
    # One pass: the one exit takes everything.
    assert float(ouro.exit_log_probs(jnp.array([[2.5]]))[0, 0]) == 0.0


def test_a_stack_run_once_is_a_plain_decoder_with_the_plain_loss(toy):
    """``loops`` 1: the remainder is the whole, the entropy is 0 and the loss
    is the mean cross-entropy of the one exit, whatever the gate says."""
    tokens = toy["tokens"]
    model = ouro.Ouro(replace(toy["model"].config, loops=1))
    loss = jax.jit(lambda p: model.apply(p, tokens[:, :-1], targets=tokens[:, 1:]))(toy["params"])
    logits = jax.jit(lambda p: model.apply(p, tokens[:, :-1]))(toy["params"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    want = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
    assert float(loss) == pytest.approx(float(want), rel=1e-5)


def test_what_the_model_sows_is_the_references_by_exit(toy):
    tokens = toy["tokens"]
    seen = jax.jit(lambda p: ouro.exit_stats(toy["model"], p, tokens[:, :-1], tokens[:, 1:]))(toy["params"])
    assert seen["exit_probs"].shape == seen["exit_ce"].shape == (4,)
    assert float(jnp.sum(seen["exit_probs"])) == pytest.approx(1.0, rel=1e-6)
    by_exit = jax.jit(lambda p, row: ARCHITECTURE.exit_losses(p, row, toy["config"]))
    losses, logits = zip(*(by_exit(toy["params"], row) for row in tokens))
    np.testing.assert_allclose(seen["exit_ce"], jnp.mean(jnp.stack(losses), axis=(0, 2)), rtol=1e-5)
    p = jnp.exp(jnp.stack([ARCHITECTURE.exit_log_probs(z) for z in logits]))
    np.testing.assert_allclose(seen["exit_probs"], jnp.mean(p, axis=(0, 2)), rtol=1e-5)
    # The gate's initialiser: logits of order one by token, so no exit is starved at the start.
    assert 0.05 < float(jnp.min(seen["exit_probs"])) and float(jnp.max(seen["exit_probs"])) < 0.75


def test_the_program_carries_its_three_scopes_and_keeps_what_dots_names(toy):
    lowered = jax.jit(jax.grad(toy["loss_fn"])).lower(toy["params"])
    text = lowered.as_text(debug_info=True)
    for scope in ("tpuft::ouro_attention", "tpuft::loop_pass", "tpuft::exit"):
        assert scope in text, scope
    # One projection result is tagged for ``dots`` to keep, and no other.
    jaxpr = str(jax.make_jaxpr(toy["loss_fn"])(toy["params"]))
    assert set(re.findall(r"name=(ouro_\w+)", jaxpr)) == {ouro.MLP_OUT}


def test_a_config_that_cannot_be_is_refused():
    with pytest.raises(ValueError, match="loops"):
        ouro.OuroConfig(loops=0)
    with pytest.raises(ValueError, match="remat"):
        ouro.OuroConfig(remat="some")
    with pytest.raises(ValueError, match="heads"):
        ouro.OuroConfig(n_heads=16, n_kv_heads=5)
