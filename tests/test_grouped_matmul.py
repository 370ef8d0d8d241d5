"""ops/grouped_matmul.py alone, against plain float32 mathematics on the CPU:
the grouped product against a loop over experts; the ladder of row counts, and
every rung of an expert layer's dispatch against the worst case; the sum by
token's kernel, interpreted, against the scatter-add it replaces; the gradient
through the dispatch against the parent's body; and the gather and the sum by
token as each other's transpose. The model that calls it is
tests/test_keye_model.py's.

    JAX_PLATFORMS=cpu python -m pytest tests/test_grouped_matmul.py -q
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models.keye import KeyeConfig, expert_layer
from torchft_tpu.ops import grouped_matmul as grouped
from torchft_tpu.ops.grouped_matmul import dispatch_rungs, grouped_matmul

# Float32 on both sides: they differ in the order of their sums.
TOLERANCE = 1e-5


def relative(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


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
    """(params, x) of an ``expert_layer(LADDER)`` whose router sends exactly
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
    params = expert_layer(LADDER).init(jax.random.PRNGKey(1), jnp.asarray(x[None]))
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
    layer = expert_layer(LADDER)
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
    """(order, gates, group_sizes) as ``models.experts.route`` gives them, for a routing
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
