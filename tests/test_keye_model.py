"""models/keye.py against plain float32 mathematics, at a small size on seeded
weights (CPU): the model against the benchmark's float32 reference
(chipbench/architectures/KeyeVL2.py, written from the equations), loss and
every leaf's gradient, on the tiled path and through the kernels; the expert
layer's shares against the uncut layer; its selection against the
reference's; ``router_load`` and ``dispatch_rows``; a sliced vocabulary through
the fused loss; the dtypes its leaves are stored in; and that a lower precision
in the indexer or the experts is not within the small-size tolerance. Its two
ops alone are tests/test_grouped_matmul.py's and tests/test_sparse_attention.py's.

    JAX_PLATFORMS=cpu python -m pytest tests/test_keye_model.py -q
"""

from __future__ import annotations

import json
import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import reference, spec  # noqa: E402
from torchft_tpu.models.keye import (  # noqa: E402
    Keye, KeyeConfig, dispatch_rows, expert_layer, router_load,
)
from torchft_tpu.ops import attention  # noqa: E402
from torchft_tpu.ops.cross_entropy import chunked_cross_entropy  # noqa: E402
from torchft_tpu.ops.grouped_matmul import dispatch_rungs  # noqa: E402
from torchft_tpu.ops import sparse_attention as tiled  # noqa: E402

ARCHITECTURE = spec.load_module(ROOT / "chipbench/architectures/KeyeVL2.py")
SEQ, BATCH = 64, 2
# Float32 on both sides: they differ in the order of their sums.
TOLERANCE = 1e-5


def toy_config() -> dict:
    """The cell's configuration file under its rehearsal overlay: every key the
    architecture file reads, at a toy size."""
    config = json.loads((ROOT / "chipbench/configs/keye-vl2-30b-a3b-ep8-1chip.json").read_text())
    overlay = json.loads((ROOT / "chipbench/fixtures/rehearsal-keye.json").read_text())
    config = {**config, **overlay["config"]}
    config["run"] = {**config["run"], **overlay["run"]}
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
    """A toy sequence is still two blocks of the reference's attention and head."""
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)
    monkeypatch.setattr(reference, "HEAD_BLOCK", 32)


def program_loss(model, params, tokens):
    return model.apply(params, tokens[:, :-1], targets=tokens[:, 1:])


def relative(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def test_the_loss_agrees_with_the_float32_reference(toy):
    config, model, params, tokens = toy
    want = reference.make_loss(ARCHITECTURE, config)(params, tokens)
    got = program_loss(model, params, tokens)
    assert abs(float(got) - float(want)) / float(want) < TOLERANCE


def by_path(tree) -> dict:
    return {
        "/".join(str(k.key) for k in path): leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


@pytest.fixture(scope="module")
def both_gradients(toy):
    config, model, params, tokens = toy
    got = jax.grad(lambda p: program_loss(model, p, tokens))(params)
    with jax.default_matmul_precision("highest"):
        total = reference.grad_sum(ARCHITECTURE, params, tokens, config)
    want = jax.tree_util.tree_map(lambda g: g / (BATCH * SEQ), total)
    return by_path(got), by_path(want)


LEAVES = [
    "final_norm/scale", "lm_head/kernel", "tok_embed/embedding",
    "layers/block/attn_norm/scale", "layers/block/mlp_norm/scale",
    "layers/block/attn/wq/kernel", "layers/block/attn/wk/kernel", "layers/block/attn/wv/kernel",
    "layers/block/attn/wo/kernel", "layers/block/attn/q_norm/scale", "layers/block/attn/k_norm/scale",
    "layers/block/moe/router/kernel", "layers/block/moe/w_gate", "layers/block/moe/w_up",
    "layers/block/moe/w_down",
]
INDEXER_LEAVES = [
    "layers/block/attn/indexer/wq/kernel", "layers/block/attn/indexer/wk/kernel",
    "layers/block/attn/indexer/weights/kernel", "layers/block/attn/indexer/k_norm/scale",
    "layers/block/attn/indexer/k_norm/bias",
]


@pytest.mark.parametrize("scan_layers", [False, True], ids=["loop", "scan"])
def test_the_parameter_tree_is_the_golden(scan_layers, golden_param_tree):
    """As the model made it before the stack, norm and head were
    models/decoder.py's (tests/conftest.py ``golden_param_tree``)."""
    config = toy_config()
    config["run"]["scan_layers"] = scan_layers
    model = ARCHITECTURE.build(config, SEQ)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, SEQ), jnp.int32))
    golden_param_tree("keye-scan" if scan_layers else "keye-loop", params)


def test_the_leaves_tested_are_all_the_leaves(toy):
    assert sorted("params/" + name for name in LEAVES + INDEXER_LEAVES) == sorted(by_path(toy[2]))


@pytest.mark.parametrize("leaf", LEAVES)
def test_a_leafs_gradient_agrees_with_the_float32_reference(leaf, both_gradients):
    got, want = both_gradients
    assert float(jnp.linalg.norm(want["params/" + leaf])) > 0
    assert relative(got["params/" + leaf], want["params/" + leaf]) < 1e-4


def _steer_onto_the_kernels(patch) -> None:
    """The path a TPU takes, on the CPU: ops/ is told it is on one and its
    flash kernels are interpreted (the test steers; the program has no
    option for it)."""
    from functools import partial

    patch.setattr(tiled, "on_tpu", lambda: True)
    patch.setattr(attention, "flash_attention", partial(attention.flash_attention, interpret=True))


@pytest.fixture
def kernel_path(monkeypatch):
    _steer_onto_the_kernels(monkeypatch)


@pytest.fixture(scope="module")
def kernel_path_gradients(toy, both_gradients):
    config, model, params, tokens = toy
    with pytest.MonkeyPatch.context() as patch:
        _steer_onto_the_kernels(patch)
        got = jax.grad(lambda p: program_loss(model, p, tokens))(params)
    return by_path(got), both_gradients[1]


def test_the_loss_through_the_kernels_agrees_with_the_float32_reference(toy, kernel_path):
    config, model, params, tokens = toy
    want = reference.make_loss(ARCHITECTURE, config)(params, tokens)
    jaxpr = str(jax.make_jaxpr(lambda p: program_loss(model, p, tokens))(params))
    assert "pallas_call" in jaxpr  # the path under test is the kernels'
    assert abs(float(program_loss(model, params, tokens)) - float(want)) / float(want) < TOLERANCE


@pytest.mark.parametrize("leaf", LEAVES)
def test_a_leafs_gradient_through_the_kernels_agrees_with_the_float32_reference(
    leaf, kernel_path_gradients
):
    got, want = kernel_path_gradients
    assert relative(got["params/" + leaf], want["params/" + leaf]) < 1e-4


@pytest.mark.parametrize("leaf", INDEXER_LEAVES)
def test_the_indexers_leaves_get_gradient_exactly_zero_through_the_kernels(
    leaf, kernel_path_gradients
):
    assert not np.any(np.asarray(kernel_path_gradients[0]["params/" + leaf]))


def test_the_kernels_operand_is_the_tiled_paths_selection(toy, kernel_path):
    """What a watcher is shown on the kernel path is the operand itself, int8,
    and it is the selection the tiled path makes (and so the reference's)."""
    config, model, params, tokens = toy
    _, seen = model.apply(params, tokens[:1, :-1], mutable=["intermediates"])
    got = seen["intermediates"]["layers"]["block"]["attn"]["selection"][0][:, 0]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(tiled, "on_tpu", lambda: False)
        _, seen = model.apply(params, tokens[:1, :-1], mutable=["intermediates"])
    want = seen["intermediates"]["layers"]["block"]["attn"]["selection"][0][:, 0]
    assert got.dtype == jnp.int8 and want.dtype == jnp.bool_
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("leaf", INDEXER_LEAVES)
def test_the_indexers_leaves_get_gradient_exactly_zero(leaf, both_gradients):
    """The selection carries no gradient: ``stop_gradient`` in the program, and
    ``jax.grad`` of the reference, which has none, gives the same zero."""
    got, want = both_gradients
    assert not np.any(np.asarray(got["params/" + leaf]))
    assert not np.any(np.asarray(want["params/" + leaf]))


def test_the_eight_shares_add_up_to_the_uncut_expert_layer():
    """The parts of the result that the eight shares give add up to the uncut
    layer's (the residual is outside the layer, so it is counted once), and the
    uncut layer is the reference's sum over all experts."""
    whole = KeyeConfig(
        dim=32, moe_hidden=24, num_experts=16, experts_per_token=4, num_local_experts=16,
        dtype=jnp.float32, n_heads=2, n_kv_heads=1, head_dim=16,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32))
    params = expert_layer(whole).init(jax.random.PRNGKey(1), x)
    uncut = expert_layer(whole).apply(params, x)
    parts = []
    for share in range(8):
        held = slice(2 * share, 2 * share + 2)
        mine = {"params": {**params["params"], **{
            name: params["params"][name][held] for name in ("w_gate", "w_up", "w_down")
        }}}
        cut = replace(whole, num_local_experts=2, expert_share=share)
        parts.append(expert_layer(cut).apply(mine, x))
    assert relative(sum(parts), uncut) < 1e-6
    assert all(float(jnp.linalg.norm(p)) > 0 for p in parts)
    config = {"num_local_experts": 16, "expert_share": 0, "num_experts_per_tok": 4}
    weights = {"router": params["params"]["router"]["kernel"], **{
        name: params["params"][name] for name in ("w_gate", "w_up", "w_down")
    }}
    want = jnp.stack([ARCHITECTURE._experts(row, weights, config) for row in x])
    assert relative(uncut, want) < 1e-5


def test_the_programs_selection_is_the_references(toy):
    """Layer by layer on the same weights and tokens: the program's selected
    set (its tiled radix select) is the reference's (``lax.top_k``)."""
    config, model, params, tokens = toy
    _, seen = model.apply(params, tokens[:1, :-1], mutable=["intermediates"])
    got = seen["intermediates"]["layers"]["block"]["attn"]["selection"][0][:, 0]
    with jax.default_matmul_precision("highest"):
        want = ARCHITECTURE.selections(params, tokens[0], config)
    assert got.shape == want.shape == (config["num_hidden_layers"], SEQ, SEQ)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    topk = config["sa_config"]["topk"]
    assert (np.asarray(got).sum(axis=-1) == np.minimum(np.arange(SEQ) + 1, topk)).all()
    assert topk < SEQ  # some queries do select


def test_router_load_counts_the_rows_of_each_held_expert(toy):
    config, model, params, tokens = toy
    rows = np.asarray(router_load(model, params, tokens[:, :-1]))
    assert rows.shape == (config["num_hidden_layers"], config["num_local_experts"])
    expected = BATCH * SEQ * config["num_experts_per_tok"] / config["num_experts"]
    assert rows.sum() > 0 and abs(rows.mean() - expected) < expected  # near uniform, not equal
    assert rows.max() <= BATCH * SEQ  # a token chooses an expert once


def test_dispatch_rows_is_the_smallest_rung_that_holds_each_layers_rows(toy):
    config, model, params, tokens = toy
    rungs = dispatch_rungs(
        BATCH * SEQ, config["num_experts_per_tok"], config["num_local_experts"], config["num_experts"]
    )
    assert len(rungs) > 1  # the toy's share is a quarter: a ladder
    taken = np.asarray(dispatch_rows(model, params, tokens[:, :-1]))
    held = np.asarray(router_load(model, params, tokens[:, :-1])).sum(axis=1)
    assert taken.shape == (config["num_hidden_layers"],)
    assert [int(t) for t in taken] == [min(r for r in rungs if r >= h) for h in held]


def test_the_uncut_layer_has_one_path_and_a_cut_one_a_conditional():
    cut = KeyeConfig(
        dim=48, moe_hidden=24, num_experts=32, experts_per_token=4, num_local_experts=4,
        dtype=jnp.float32, n_heads=2, n_kv_heads=1, head_dim=16,
    )
    x = jnp.zeros((1, 256, cut.dim))
    for cfg, conditional in ((cut, True), (replace(cut, num_local_experts=32), False)):
        layer = expert_layer(cfg)
        params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
        program = str(jax.make_jaxpr(jax.grad(lambda p: jnp.sum(layer.apply(p, x))))(params))
        assert ("cond" in program) is conditional


def test_a_sliced_vocabulary_of_18992_goes_through_the_fused_loss():
    """18,992 rows are a multiple neither of the chunk (4,096) nor of 128: the
    tail slab is padded and masked, value and both gradients as the dense loss."""
    vocab, d, n = 18992, 16, 24
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d, vocab)) * 0.1
    targets = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, vocab).at[0].set(vocab - 1)

    def dense(x, w):
        logp = jax.nn.log_softmax(x @ w, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))

    fused = lambda x, w: chunked_cross_entropy(x, w, targets, 4096)
    assert abs(float(fused(x, w)) - float(dense(x, w))) < 1e-5
    got, want = jax.grad(fused, argnums=(0, 1))(x, w), jax.grad(dense, argnums=(0, 1))(x, w)
    assert got[1].shape == (d, vocab)
    assert relative(got[0], want[0]) < 1e-5 and relative(got[1], want[1]) < 1e-5


def rounded(tree, names, dtype):
    def leaf(path, a):
        name = "/".join(str(k.key) for k in path)
        return a.astype(dtype).astype(a.dtype) if any(n in name for n in names) else a
    return jax.tree_util.tree_map_with_path(leaf, tree)


@pytest.mark.parametrize("what", ["indexer", "experts"])
def test_a_lower_precision_is_not_within_the_small_size_tolerance(what, toy):
    """The reference on the stored weights against the program with the
    indexer's, or the expert layer's (router and experts), arithmetic in
    bfloat16: outside the tolerance the float32 program is held to, so the
    tolerance would catch it. On weights whose branches weigh as much as the
    stream they write into (initialised for the toy's own depth, embeddings of
    norm one): with the cell's initialisation two toy layers move the loss too
    little for any precision to show."""
    config, model, _, tokens = toy
    model = Keye(replace(model.config, init_depth=None))
    params = model.init(jax.random.PRNGKey(3), tokens[:, :-1])
    params["params"]["tok_embed"]["embedding"] /= config["hidden_size"] ** 0.5
    want = float(reference.make_loss(ARCHITECTURE, config)(params, tokens))
    assert abs(float(program_loss(model, params, tokens)) - want) / want < TOLERANCE
    if what == "indexer":
        low = Keye(replace(model.config, indexer_dtype=jnp.bfloat16))
        got = float(program_loss(low, rounded(params, ["indexer"], jnp.bfloat16), tokens))
    else:
        got = float(program_loss(model, rounded(params, ["moe/"], jnp.bfloat16), tokens))
    assert abs(got - want) / want > 3 * TOLERANCE



NORMS = [
    "final_norm/scale", "layers/block/attn_norm/scale", "layers/block/mlp_norm/scale",
    "layers/block/attn/q_norm/scale", "layers/block/attn/k_norm/scale",
    "layers/block/attn/indexer/k_norm/scale", "layers/block/attn/indexer/k_norm/bias",
]


@pytest.mark.parametrize("norm_dtype", [None, jnp.bfloat16], ids=["default", "bfloat16"])
def test_the_norms_are_stored_in_norm_dtype_and_every_other_leaf_in_dtype(norm_dtype, toy):
    """float32 unless a configuration says otherwise, as models/llama.py keeps
    its scales; the cell's file says bfloat16 and lists it as a departure."""
    _, model, _, tokens = toy
    cfg = replace(model.config, dtype=jnp.bfloat16)
    if norm_dtype is not None:
        cfg = replace(cfg, norm_dtype=norm_dtype)
    assert KeyeConfig().norm_dtype == jnp.float32
    shapes = jax.eval_shape(Keye(cfg).init, jax.random.PRNGKey(0), tokens[:, :-1])
    for name, leaf in by_path(shapes).items():
        norm = name.removeprefix("params/") in NORMS
        assert leaf.dtype == ((norm_dtype or jnp.float32) if norm else jnp.bfloat16), name


@pytest.mark.parametrize("norm_dtype,moves", [(jnp.float32, True), (jnp.bfloat16, False)], ids=["float32", "bfloat16"])
def test_an_adamw_step_of_3e_4_moves_a_scale_only_where_it_is_stored_in_float32(norm_dtype, moves, toy):
    """What the cell's third departure says: in bfloat16 the norms stay where
    they started, in float32 (the model's default) they train."""
    import optax

    _, model, _, tokens = toy
    model = Keye(replace(model.config, norm_dtype=norm_dtype))
    params = model.init(jax.random.PRNGKey(3), tokens[:, :-1])
    grads = jax.grad(lambda p: program_loss(model, p, tokens))(params)
    tx = optax.adamw(3e-4, weight_decay=0.1)
    updates, _ = tx.update(grads, tx.init(params), params)
    after = by_path(optax.apply_updates(params, updates))
    before = by_path(params)
    for name in ("final_norm/scale", "layers/block/attn_norm/scale", "layers/block/attn/q_norm/scale"):
        changed = bool(np.any(np.asarray(after["params/" + name] != before["params/" + name])))
        assert changed is moves, name
