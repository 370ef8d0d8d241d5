"""models/keye.py and its two ops against plain float32 mathematics, at a small
size on seeded weights (CPU): the model against the benchmark's float32
reference (chipbench/architectures/KeyeVL2.py, written from the equations),
loss and every leaf's gradient; the expert layer's shares against the uncut
layer, and every rung of its dispatch's ladder of row counts against the worst
case; the sum by token's kernel, interpreted, against the scatter-add it
replaces, and the gradient through the dispatch against the parent's body; the
selection against a sorted top-k; the grouped product against a loop
over experts; a sliced vocabulary through the fused loss; and that a lower
precision in the indexer or the experts is not within the small-size tolerance.

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
from torchft_tpu.models import keye  # noqa: E402
from torchft_tpu.models.keye import (  # noqa: E402
    ExpertLayer, Keye, KeyeConfig, dispatch_rows, router_load,
)
from torchft_tpu.ops import grouped_matmul as grouped  # noqa: E402
from torchft_tpu.ops.cross_entropy import chunked_cross_entropy  # noqa: E402
from torchft_tpu.ops.grouped_matmul import dispatch_rungs, grouped_matmul  # noqa: E402
from torchft_tpu.ops import sparse_attention as tiled  # noqa: E402
from torchft_tpu.ops.sparse_attention import select_topk, sparse_attention  # noqa: E402

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


def test_the_leaves_tested_are_all_the_leaves(toy):
    assert sorted("params/" + name for name in LEAVES + INDEXER_LEAVES) == sorted(by_path(toy[2]))


@pytest.mark.parametrize("leaf", LEAVES)
def test_a_leafs_gradient_agrees_with_the_float32_reference(leaf, both_gradients):
    got, want = both_gradients
    assert float(jnp.linalg.norm(want["params/" + leaf])) > 0
    assert relative(got["params/" + leaf], want["params/" + leaf]) < 1e-4


def _steer_onto_the_kernels(patch) -> None:
    """The path a TPU takes, on the CPU: the model is told it is on one and
    its flash kernels are interpreted (the test steers; the program has no
    option for it)."""
    from functools import partial

    patch.setattr(keye, "on_tpu", lambda: True)
    patch.setattr(keye, "flash_attention", partial(keye.flash_attention, interpret=True))


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
        patch.setattr(keye, "on_tpu", lambda: False)
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
    params = ExpertLayer(whole).init(jax.random.PRNGKey(1), x)
    uncut = ExpertLayer(whole).apply(params, x)
    parts = []
    for share in range(8):
        held = slice(2 * share, 2 * share + 2)
        mine = {"params": {**params["params"], **{
            name: params["params"][name][held] for name in ("w_gate", "w_up", "w_down")
        }}}
        cut = replace(whole, num_local_experts=2, expert_share=share)
        parts.append(ExpertLayer(cut).apply(mine, x))
    assert relative(sum(parts), uncut) < 1e-6
    assert all(float(jnp.linalg.norm(p)) > 0 for p in parts)
    config = {"num_local_experts": 16, "expert_share": 0, "num_experts_per_tok": 4}
    weights = {"router": params["params"]["router"]["kernel"], **{
        name: params["params"][name] for name in ("w_gate", "w_up", "w_down")
    }}
    want = jnp.stack([ARCHITECTURE._experts(row, weights, config) for row in x])
    assert relative(uncut, want) < 1e-5


def sorted_topk(scores: np.ndarray, allowed: np.ndarray, topk: int) -> np.ndarray:
    """The definition, row by row: allowed keys by falling score, earlier key
    first among equals, the first ``topk`` of them."""
    out = np.zeros(scores.shape, bool)
    for row in range(scores.shape[0]):
        keys = [s for s in range(scores.shape[1]) if allowed[row, s]]
        keys.sort(key=lambda s: (-scores[row, s], s))
        out[row, keys[:topk]] = True
    return out


@pytest.mark.parametrize("case", ["random", "ties", "all-equal", "zeros-of-both-signs"])
def test_the_selection_is_the_topk_with_ties_to_the_earlier_key(case):
    rows, keys, topk = 48, 48, 16
    scores = np.array(jax.random.normal(jax.random.PRNGKey(5), (rows, keys)), np.float32)
    if case == "ties":
        scores = np.round(scores * 2) / 2  # a handful of distinct values
    elif case == "all-equal":
        scores[:] = 0.25
    elif case == "zeros-of-both-signs":
        scores = np.where(scores > 0.3, scores, np.where(scores > 0, 0.0, -0.0)).astype(np.float32)
    causal = np.tril(np.ones((rows, keys), bool))
    got = np.asarray(select_topk(jnp.asarray(scores), jnp.asarray(causal), topk))
    assert np.array_equal(got, sorted_topk(scores, causal, topk))
    assert np.array_equal(got[:topk], causal[:topk])  # rows under topk select all
    assert (got.sum(axis=1) == np.minimum(np.arange(rows) + 1, topk)).all()


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


@pytest.mark.parametrize("tiles", [1, 3, 4, 7, 16, 17])
def test_the_tiles_fall_into_runs_that_share_a_key_length(tiles):
    """At most ``KEY_GROUPS`` runs, in order, every tile in exactly one."""
    runs = tiled._tile_groups(tiles)
    assert 1 <= len(runs) <= min(tiled.KEY_GROUPS, tiles)
    assert [t for lo, hi in runs for t in range(lo, hi)] == list(range(tiles))
    lengths = [hi - lo for lo, hi in runs]
    assert len(set(lengths[:-1])) <= 1 and lengths[-1] <= lengths[0]


@pytest.mark.parametrize("key_groups", [1, 3, 8])
def test_selected_attention_is_the_same_however_the_tiles_share_their_keys(key_groups, monkeypatch):
    b, s, h, kv, d, j, e = 2, 64, 4, 2, 16, 2, 8
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    q, k, v = (jax.random.normal(key, (b, s, n, d)) for key, n in zip(keys, (h, kv, kv)))
    qi = jax.random.normal(keys[3], (b, s, j, e))
    ki = jax.random.normal(keys[4], (b, s, e))
    w = jax.random.normal(keys[5], (b, s, j))

    def run(groups):
        monkeypatch.setattr(tiled, "KEY_GROUPS", groups)
        assert len(tiled._tile_groups(s // 8)) == groups
        return sparse_attention(q, k, v, qi, ki, w, topk=12, scale=d**-0.5, block=8, return_selection=True)

    (got, again), (want, chosen) = run(key_groups), run(tiled.KEY_GROUPS)
    assert np.array_equal(np.asarray(chosen), np.asarray(again))
    assert relative(got, want) < 1e-6


@pytest.mark.parametrize("use_pallas", [False, True], ids=["ragged_dot", "megablox-interpreted"])
def test_the_grouped_product_is_a_loop_over_experts(use_pallas):
    """With an expert that receives no row and one that receives all the rest,
    rows that belong elsewhere, and both gradients."""
    m, k, n = 256, 64, 128
    lhs = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (4, k, n))
    for sizes in ([40, 0, 100, 20, 96], [0, 0, 256, 0, 0], [0, 0, 0, 0, 256]):
        group_sizes = jnp.asarray(sizes, jnp.int32)

        def product(lhs, rhs):
            return grouped_matmul(lhs, rhs, group_sizes, use_pallas=use_pallas, interpret=True)

        def loop(lhs, rhs):
            out, start = jnp.zeros((m, n)), 0
            for expert, size in enumerate(sizes[:4]):
                out = out.at[start:start + size].set(lhs[start:start + size] @ rhs[expert])
                start += size
            return out

        assert relative(product(lhs, rhs), loop(lhs, rhs)) < 1e-5 or not any(sizes[:4])
        assert not np.any(np.asarray(product(lhs, rhs))[sum(sizes[:4]):])
        if any(sizes[:4]):
            scalar = lambda f: lambda lhs, rhs: jnp.sum(jnp.sin(f(lhs, rhs)))
            got = jax.grad(scalar(product), argnums=(0, 1))(lhs, rhs)
            want = jax.grad(scalar(loop), argnums=(0, 1))(lhs, rhs)
            assert relative(got[0], want[0]) < 1e-5 and relative(got[1], want[1]) < 1e-5


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


@pytest.mark.parametrize(
    "n, k, local, experts, rungs",
    [
        (8192, 8, 16, 128, (16384, 32768, 65536)),  # the cell: E = 8192
        (256, 4, 4, 32, (256, 512, 1024)),
        (48, 4, 2, 16, (64, 128, 192)),  # E = 24: 48 and 96 up to the row tile of 192 rows, 64
        (8192, 8, 32, 128, (32768, 65536)),  # 4E is the worst case
        (8192, 8, 64, 128, (65536,)),  # half held: 2E is
        (8192, 8, 128, 128, (65536,)),
        (48, 4, 16, 16, (192,)),
    ],
)
def test_the_rungs_are_twice_and_four_times_the_uniform_share_then_the_worst_case(
    n, k, local, experts, rungs
):
    assert dispatch_rungs(n, k, local, experts) == rungs
    assert (len(rungs) == 1) == (local * 2 >= experts)


# A layer of 256 tokens x 4 choices that holds 4 of 32 experts: a uniform
# router would send it E = 128 rows, and its rungs are 256, 512 and 1,024.
LADDER = KeyeConfig(
    dim=48, moe_hidden=24, num_experts=32, experts_per_token=4, num_local_experts=4,
    dtype=jnp.float32, n_heads=2, n_kv_heads=1, head_dim=16,
)
LADDER_TOKENS = 256


def steered_layer(held_rows: int):
    """(params, x) of an ``ExpertLayer(LADDER)`` whose router sends exactly
    ``held_rows`` of the 1,024 choices to held experts: the router reads a
    token's logits off its first 32 features (an identity block over a little
    noise), and x carries, for each token, high scores for as many held
    experts as its part of ``held_rows`` and for experts held elsewhere for
    the rest of its four choices."""
    n, k, local, experts = LADDER_TOKENS, 4, 4, 32
    rng = np.random.default_rng(held_rows)
    held_of = np.full(n, held_rows // n) + (np.arange(n) < held_rows % n)
    logits = rng.uniform(-1.0, 0.0, (n, experts)).astype(np.float32)
    for t in range(n):
        mine = rng.permutation(local)[: held_of[t]]
        others = local + rng.permutation(experts - local)[: k - held_of[t]]
        logits[t, np.concatenate([mine, others])] = rng.uniform(2.0, 3.0, k)
    x = np.concatenate([logits, rng.normal(size=(n, 16)).astype(np.float32)], axis=1)
    params = ExpertLayer(LADDER).init(jax.random.PRNGKey(1), jnp.asarray(x[None]))
    kernel = np.concatenate([np.eye(experts), 0.01 * rng.normal(size=(16, experts))])
    params["params"]["router"]["kernel"] = jnp.asarray(kernel, jnp.float32)
    return params, jnp.asarray(x[None])


@pytest.mark.parametrize("use_pallas", [False, True], ids=["ragged_dot", "megablox-interpreted"])
@pytest.mark.parametrize(
    "held_rows, rung",
    [(0, 256), (128, 256), (256, 256), (257, 512), (513, 1024), (1024, 1024)],
    ids=["no-row", "E", "2E", "2E+1", "4E+1", "every-choice"],
)
def test_every_rung_is_the_worst_case_path(held_rows, rung, use_pallas, monkeypatch):
    """Output and the gradient of every leaf and of the input on the rung the
    routing lands on, against the same layer with the worst case as its only
    rung (no conditional, plain autodiff): the held rows and their order are
    the same on both, so the arithmetic is, and so are the bits; but for the
    expert weights' gradients through ``ragged_dot``, whose sum over a group's
    rows the CPU blocks by the length of the buffer: float32 rounding."""
    from functools import partial

    monkeypatch.setattr(
        grouped, "grouped_matmul", partial(grouped_matmul, use_pallas=use_pallas, interpret=True)
    )
    params, x = steered_layer(held_rows)
    layer = ExpertLayer(LADDER)
    _, seen = layer.apply(params, x, mutable=["intermediates"])
    seen = seen["intermediates"]
    assert int(seen["rows_by_expert"][0].sum()) == held_rows
    assert int(seen["dispatch_rows"][0]) == rung

    def loss(params, x):
        return jnp.sum(jnp.sin(layer.apply(params, x)))

    got = layer.apply(params, x), jax.grad(loss, argnums=(0, 1))(params, x)
    monkeypatch.setattr(grouped, "dispatch_rungs", lambda n, k, local, experts: (n * k,))
    # (megablox's own kernels hold conditionals)
    assert use_pallas or "cond" not in str(jax.make_jaxpr(loss)(params, x))
    want = layer.apply(params, x), jax.grad(loss, argnums=(0, 1))(params, x)
    assert bool(jnp.any(want[0])) == bool(held_rows)
    mine, theirs = (jax.tree_util.tree_leaves_with_path(side) for side in (got, want))
    assert len(mine) == 6  # the output, the router, three expert weights, the input
    for (path, a), (_, b) in zip(mine, theirs):
        name = jax.tree_util.keystr(path)
        if "['w_" in name and not use_pallas:
            assert relative(a, b) < 1e-6, name
        else:
            assert np.array_equal(np.asarray(a), np.asarray(b)), name
        assert held_rows == 0 or np.any(np.asarray(b)), name


# 512 tokens of 8 choices over 128 experts, 8 held: a uniform router sends
# E = 256 rows, the rungs are 512, 1,024 and 4,096, and the sum by token walks
# 4 blocks of 128 tokens over 2, 4 and 16 tiles of 256 rows.
SUM_TOKENS, SUM_CHOICES, SUM_HELD, SUM_EXPERTS, SUM_WIDTH = 512, 8, 8, 128, 64
SUM_RUNGS = (512, 1024, 4096)


def routed(routing: str):
    """(order, gates, group_sizes) as ``keye.route`` gives them, for a routing
    made by hand: which expert each of a token's eight choices names."""
    n, k, local, experts = SUM_TOKENS, SUM_CHOICES, SUM_HELD, SUM_EXPERTS
    rng = np.random.default_rng(len(routing))
    elsewhere = lambda count: local + rng.permutation(experts - local)[:count]
    chosen = np.stack([elsewhere(k) for _ in range(n)])  # no held row at all
    if routing == "uniform":
        chosen = np.stack([rng.permutation(experts)[:k] for _ in range(n)])
    elif routing == "collapsed":  # every token's first two choices: held experts 0 and 1
        chosen[:, :2] = [0, 1]
    elif routing == "every-row":
        chosen = np.stack([rng.permutation(local) for _ in range(n)])
    elif routing == "off-tile":  # 300 held rows: not a multiple of the 256-row tile
        chosen[:300, 0] = rng.integers(0, local, 300)
    elif routing == "eight-of-a-token":  # token 77's eight choices all held, few others
        chosen[77] = rng.permutation(local)
        chosen[::5, 3] = 2
    group = np.where(chosen < local, chosen, local).reshape(-1)
    order = np.argsort(group, kind="stable").astype(np.int32)
    gates = rng.uniform(0.05, 1.0, (n, k)).astype(np.float32)
    gates /= gates.sum(axis=1, keepdims=True)
    sizes = np.bincount(group, minlength=local + 1).astype(np.int32)
    return jnp.asarray(order), jnp.asarray(gates), jnp.asarray(sizes)


HELD_ROWS = {
    "uniform": None, "collapsed": 1024, "none": 0, "every-row": 4096, "off-tile": 300,
    "eight-of-a-token": 8 + 103,
}
SUM_CASES = [
    (routing, rung) for routing, held in HELD_ROWS.items() for rung in SUM_RUNGS
    if rung >= (held or 0)
]


@pytest.mark.parametrize("routing, rung", SUM_CASES, ids=[f"{r}-{c}" for r, c in SUM_CASES])
def test_the_sum_by_token_kernel_is_the_scatter_add_it_replaces(routing, rung):
    """The Mosaic kernel, interpreted, against ``.at[token].add`` in float32 on
    the held rows ALONE: bf16 rows with float32 weights into float32 (the
    forward's return to token order) and with unit weights into bf16 (the
    transpose of the gather), and float32 rows. The rows past the held total,
    which fill the rung, are zero as the grouped product leaves them, and add
    nothing to the tokens they name."""
    order, gates, sizes = routed(routing)
    held = int(sizes[:SUM_HELD].sum())
    assert HELD_ROWS[routing] in (None, held) and held <= rung
    assert routing != "uniform" or 150 < held < 400
    chosen = order[:rung]
    token, weights = chosen // SUM_CHOICES, gates.reshape(-1)[chosen]
    rows = jax.random.normal(jax.random.PRNGKey(rung), (rung, SUM_WIDTH))
    rows = jnp.where(jnp.arange(rung)[:, None] < held, rows, 0.0)
    if routing == "eight-of-a-token":
        assert int(jnp.sum(token[:held] == 77)) == 8

    def scatter_add(rows, weights):
        return jnp.zeros((SUM_TOKENS, SUM_WIDTH), jnp.float32).at[token[:held]].add(
            rows[:held].astype(jnp.float32) * weights[:held, None]
        )

    for dtype in (jnp.bfloat16, jnp.float32):
        x = rows.astype(dtype)
        got = grouped._token_sum_pallas(x, weights, token, SUM_TOKENS, jnp.float32, interpret=True)
        want = scatter_add(x, weights)
        assert got.dtype == jnp.float32 and bool(jnp.any(want)) == bool(held)
        assert float(jnp.max(jnp.abs(got - want))) <= 2e-6 * max(1.0, float(jnp.max(jnp.abs(want))))
        unit = grouped._token_sum_pallas(x, None, token, SUM_TOKENS, dtype, interpret=True)
        want = scatter_add(x, jnp.ones_like(weights)).astype(dtype)
        assert unit.dtype == dtype
        assert float(jnp.max(jnp.abs((unit - want).astype(jnp.float32)))) <= 2e-6 * max(
            1.0, float(jnp.max(jnp.abs(want)))
        ) + (2.0**-7 * float(jnp.max(jnp.abs(want))) if dtype == jnp.bfloat16 else 0.0)
    # The CPU path of the same function is the scatter-add itself.
    assert np.array_equal(
        np.asarray(grouped.sum_by_token(rows, weights, token, SUM_TOKENS)),
        np.asarray(jnp.zeros((SUM_TOKENS, SUM_WIDTH)).at[token].add(rows * weights[:, None])),
    )


def interpret_the_sums(monkeypatch):
    """Both sums by token in the Mosaic kernel, interpreted, on the CPU."""
    from functools import partial

    monkeypatch.setattr(grouped, "_token_sum", partial(grouped._token_sum_pallas, interpret=True))


def parents_experts_at(rows, activation, flat, order, gates, group_sizes, w_gate, w_up, w_down):
    """``_experts_at`` as PR 50 left it: the gather and the two scatter-adds
    as plain XLA under plain autodiff. The oracle of the pair of functions."""
    from functools import partial

    n, k = gates.shape
    chosen = order[:rows]
    token = chosen // k
    product = partial(grouped_matmul, group_sizes=group_sizes[: w_gate.shape[0]], use_pallas=False)
    x = flat[token]
    out = product(activation(product(x, w_gate)) * product(x, w_up), w_down)
    weighted = out.astype(jnp.float32) * gates.reshape(-1)[chosen][:, None]
    return jnp.zeros((n, flat.shape[1]), jnp.float32).at[token].add(weighted)


@pytest.mark.parametrize("routing", ["uniform", "collapsed", "eight-of-a-token", "none"])
def test_the_gradient_through_routed_experts_is_the_parents(routing, monkeypatch):
    """Output and ``jax.grad`` of the rows, the gates and the three weights
    through ``routed_experts`` with both sums in the interpreted kernel,
    against the parent's body at the worst case under plain autodiff."""
    interpret_the_sums(monkeypatch)
    order, gates, sizes = routed(routing)
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    flat = jax.random.normal(keys[0], (SUM_TOKENS, SUM_WIDTH))
    weights = [
        jax.random.normal(key, shape) * shape[1] ** -0.5
        for key, shape in zip(keys[1:], [(SUM_HELD, SUM_WIDTH, 32)] * 2 + [(SUM_HELD, 32, SUM_WIDTH)])
    ]
    cotangent = jax.random.normal(keys[4], (SUM_TOKENS, SUM_WIDTH))

    def ladder(flat, gates, *weights):
        out, rung = grouped.routed_experts(
            flat, order, gates, sizes, *weights, num_experts=SUM_EXPERTS, activation=jax.nn.silu
        )
        return jnp.sum(out * cotangent), (out, rung)

    def parent(flat, gates, *weights):
        out = parents_experts_at(
            SUM_TOKENS * SUM_CHOICES, jax.nn.silu, flat, order, gates, sizes, *weights
        )
        return jnp.sum(out * cotangent), (out, None)

    argnums = (0, 1, 2, 3, 4)
    (_, (got, rung)), d_got = jax.value_and_grad(ladder, argnums, has_aux=True)(flat, gates, *weights)
    (_, (want, _)), d_want = jax.value_and_grad(parent, argnums, has_aux=True)(flat, gates, *weights)
    held = int(sizes[:SUM_HELD].sum())
    assert int(rung) == min(r for r in SUM_RUNGS if r >= held)
    assert relative(got, want) < TOLERANCE and bool(jnp.any(want)) == bool(held)
    for name, a, b in zip(("rows", "gates", "w_gate", "w_up", "w_down"), d_got, d_want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert relative(a, b) < TOLERANCE, name
        assert not held or bool(jnp.any(b)), name


@pytest.mark.parametrize("kernel", [False, True], ids=["scatter-add", "kernel-interpreted"])
def test_rows_of_and_sum_by_token_are_each_others_transpose(kernel, monkeypatch):
    """<rows_of(x), y> == <x, sum_by_token(y)> with unit weights, by the
    functions themselves and by each one's backward rule."""
    if kernel:
        interpret_the_sums(monkeypatch)
    order, _, _ = routed("uniform")
    token = order[:1024] // SUM_CHOICES
    x = jax.random.normal(jax.random.PRNGKey(0), (SUM_TOKENS, SUM_WIDTH))
    y = jax.random.normal(jax.random.PRNGKey(1), (1024, SUM_WIDTH))
    ones = jnp.ones((1024,))
    inner = lambda a, b: float(np.vdot(np.asarray(a, np.float64), np.asarray(b, np.float64)))
    summed = grouped.sum_by_token(y, ones, token, SUM_TOKENS)
    assert abs(inner(grouped.rows_of(x, token), y) - inner(x, summed)) < 1e-3
    (d_x,) = jax.vjp(lambda x: grouped.rows_of(x, token), x)[1](y)
    assert relative(d_x, summed) < 1e-6
    d_y, d_ones = jax.vjp(lambda y, w: grouped.sum_by_token(y, w, token, SUM_TOKENS), y, ones)[1](x)
    assert np.array_equal(np.asarray(d_y), np.asarray(grouped.rows_of(x, token)))
    assert relative(d_ones, jnp.sum(x[token] * y, axis=1)) < 1e-6


def test_the_uncut_layer_has_one_path_and_a_cut_one_a_conditional():
    x = jnp.zeros((1, LADDER_TOKENS, LADDER.dim))
    for cfg, conditional in ((LADDER, True), (replace(LADDER, num_local_experts=32), False)):
        layer = ExpertLayer(cfg)
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
