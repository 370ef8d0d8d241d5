"""ops/ssd.py on the CPU at small sizes: the chunked state-space scan and its
gradient against the recurrence position by position, the state's way across
chunk boundaries, the causal convolution, and how far bfloat16 operands stray.

    JAX_PLATFORMS=cpu python -m pytest tests/test_ssd.py -q
"""

from __future__ import annotations

import ast
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops import ssd

ROOT = Path(__file__).resolve().parents[1]

# One compiled program a call: op by op, the CPU compiles every primitive.
scan = jax.jit(ssd.ssd_scan, static_argnames="chunk")
recurrence = jax.jit(ssd.ssd_recurrence)


def inputs(seed: int, b: int, s: int, heads: int, p: int, groups: int, n: int, dtype=jnp.float32):
    """Seeded inputs as a Mamba-2 layer makes them: steps of 0.05 to 1,
    ``A`` in -16 .. -1, unit-scale x, B and C."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (b, s, heads, p), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, s, heads)) - 1.5)
    a = -jax.random.uniform(keys[2], (heads,), minval=1.0, maxval=16.0)
    b_in = jax.random.normal(keys[3], (b, s, groups, n), jnp.float32).astype(dtype)
    c_out = jax.random.normal(keys[4], (b, s, groups, n), jnp.float32).astype(dtype)
    d_skip = 1.0 + 0.1 * jax.random.normal(keys[5], (heads,))
    return x, dt, a, b_in, c_out, d_skip


def close(got, want, relative: float) -> bool:
    """Within ``relative`` of the largest entry of ``want``: the two sides sum
    in another order."""
    bound = relative * float(jnp.max(jnp.abs(want))) + 1e-30
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) <= bound


# (batch, positions, heads, head width, groups, state, chunk)
CASES = {
    "one-chunk": (1, 32, 4, 8, 1, 16, 32),
    "several-chunks": (1, 96, 4, 8, 1, 16, 16),
    "the-chunk-is-the-sequence": (1, 48, 4, 8, 1, 16, 256),
    "batch-of-three": (3, 64, 4, 8, 1, 16, 16),
    "two-groups": (2, 64, 6, 8, 2, 16, 32),
    "a-ragged-tail": (1, 50, 4, 8, 1, 16, 16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunked_scan_is_the_recurrence(case):
    """float32 on both sides at the highest matmul precision: 1e-5 of the
    largest output, the order of the sums."""
    *sizes, chunk = CASES[case]
    args = inputs(1, *sizes)
    with jax.default_matmul_precision("highest"):
        got = scan(*args, chunk=chunk)
    want, _ = recurrence(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert close(got, want, 1e-5)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunked_scans_gradient_is_the_recurrences(case):
    """Every argument's gradient (x, dt, A, B, C, D) of a loss that weighs
    every output differently: 2e-5 of the leaf's largest entry."""
    *sizes, chunk = CASES[case]
    args = inputs(2, *sizes)
    weights = jnp.cos(jnp.arange(np.prod(args[0].shape), dtype=jnp.float32)).reshape(args[0].shape)
    chunked = lambda *a: jnp.sum(weights * scan(*a, chunk=chunk))
    plain = lambda *a: jnp.sum(weights * recurrence(*a)[0])
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(chunked, argnums=tuple(range(6))))(*args)
    want = jax.jit(jax.grad(plain, argnums=tuple(range(6))))(*args)
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), got, want):
        assert np.all(np.isfinite(g)) and close(g, w, 2e-5), name


def test_the_state_crosses_chunk_boundaries():
    """Zeroing chunk 0's input changes chunk 2's output, by what the recurrence
    says, and leaves nothing else of the layer out: slow decays, so that the
    state of chunk 0 is still there two boundaries on."""
    b, s, heads, p, groups, n, chunk = 1, 48, 4, 8, 1, 16, 16
    x, dt, a, b_in, c_out, d_skip = inputs(3, b, s, heads, p, groups, n)
    dt, a = 0.05 * dt, a / 16.0  # a chunk's log-decay stays above -1
    assert float(ssd.chunk_log_decay(dt, a, chunk)[0]) > -1.0
    muted = x.at[:, :chunk].set(0.0)
    with jax.default_matmul_precision("highest"):
        moved = scan(x, dt, a, b_in, c_out, d_skip, chunk) - scan(
            muted, dt, a, b_in, c_out, d_skip, chunk
        )
    want = recurrence(x, dt, a, b_in, c_out, d_skip)[0] - recurrence(
        muted, dt, a, b_in, c_out, d_skip
    )[0]
    last = moved[:, 2 * chunk:]
    assert float(jnp.max(jnp.abs(last))) > 1e-2  # it arrives
    assert close(last, want[:, 2 * chunk:], 1e-5)
    # and a scan that dropped the carried state would not see it
    alone = scan(
        x[:, 2 * chunk:], dt[:, 2 * chunk:], a, b_in[:, 2 * chunk:], c_out[:, 2 * chunk:], d_skip, chunk
    )
    with jax.default_matmul_precision("highest"):
        whole = scan(x, dt, a, b_in, c_out, d_skip, chunk)[:, 2 * chunk:]
    assert not close(alone, whole, 1e-3)


def test_fast_decays_forget_within_a_chunk_and_nothing_overflows():
    """A chunk's total log-decay of minus several hundred: every decay is the
    exponential of a masked difference, so nothing is inf or nan, forward or
    backward, and the result is still the recurrence's."""
    b, s, heads, p, groups, n, chunk = 1, 64, 4, 8, 1, 16, 32
    x, dt, a, b_in, c_out, d_skip = inputs(4, b, s, heads, p, groups, n)
    dt = 20.0 * dt
    assert float(ssd.chunk_log_decay(dt, a, chunk)[0]) < -300.0
    with jax.default_matmul_precision("highest"):
        got = scan(x, dt, a, b_in, c_out, d_skip, chunk)
        grads = jax.jit(
            jax.grad(lambda *z: jnp.sum(scan(*z, chunk=chunk) ** 2), argnums=(0, 1, 2))
        )(x, dt, a, b_in, c_out, d_skip)
    assert close(got, recurrence(x, dt, a, b_in, c_out, d_skip)[0], 1e-5)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


def test_chunk_log_decay_is_the_smallest_and_largest_sum_of_a_chunk():
    _, dt, a, *_ = inputs(5, 2, 64, 4, 8, 1, 16)
    got = ssd.chunk_log_decay(dt, a, 16)
    sums = (dt * a).reshape(2, 4, 16, 4).sum(axis=2)
    assert got.shape == (2,) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, [sums.min(), sums.max()], rtol=1e-6)
    assert float(got[1]) < 0.0


@pytest.mark.parametrize("width", [4, 2])
def test_the_convolution_is_causal_and_starts_on_zeros(width):
    """``out[t] = bias + sum_j kernel[:, j] x[t - (width - 1) + j]``: position t
    reads positions t - width + 1 .. t and no later one, and the first width -
    1 positions see zeros before the sequence."""
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(keys[0], (2, 12, 5))
    kernel, bias = jax.random.normal(keys[1], (5, width)), jax.random.normal(keys[2], (5,))
    got = ssd.causal_conv(x, kernel, bias)
    want = np.tile(np.asarray(bias), (2, 12, 1))
    for t in range(12):
        for j in range(width):
            if t - (width - 1) + j >= 0:
                want[:, t] += np.asarray(kernel[:, j]) * np.asarray(x[:, t - (width - 1) + j])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # Position 0 is the last tap on x[0] alone.
    np.testing.assert_allclose(got[:, 0], bias + kernel[:, -1] * x[:, 0], rtol=1e-5, atol=1e-6)
    # No output moves with a later input.
    later = ssd.causal_conv(x.at[:, 7:].add(1.0), kernel, bias)
    np.testing.assert_array_equal(got[:, :7], later[:, :7])
    assert not np.allclose(got[:, 7], later[:, 7])


def test_bfloat16_operands_stay_within_a_stated_bound_of_float32():
    """x, B and C in bfloat16, as the cell runs them: the decays, dt and the
    carried state stay float32, the three products round their operands to 8
    bits of mantissa and accumulate in float32. Bound: 2% of the largest
    output (2^-8 an operand, three operands a term, sums of hundreds of terms
    of either sign), and the float32 call on the SAME rounded inputs within
    1%: what the bfloat16 path adds is the rounding of x dt, of the masked
    weights and of the carried state."""
    sizes = (2, 128, 4, 16, 1, 32)
    x, dt, a, b_in, c_out, d_skip = inputs(7, *sizes, dtype=jnp.bfloat16)
    exact, _ = recurrence(x, dt, a, b_in, c_out, d_skip)
    low = scan(x, dt, a, b_in, c_out, d_skip, chunk=32)
    assert low.dtype == jnp.bfloat16
    assert close(low, exact, 2e-2)
    with jax.default_matmul_precision("highest"):
        same_inputs = scan(*(z.astype(jnp.float32) for z in (x, dt, a, b_in, c_out, d_skip)), chunk=32)
    assert close(same_inputs, exact, 1e-5) and close(low, same_inputs, 1e-2)


def test_ops_ssd_imports_nothing_from_models():
    tree = ast.parse((ROOT / "torchft_tpu/ops/ssd.py").read_text())
    imported = [
        node.module if isinstance(node, ast.ImportFrom) else alias.name
        for node in ast.walk(tree) if isinstance(node, (ast.Import, ast.ImportFrom))
        for alias in (node.names if isinstance(node, ast.Import) else [None])
    ]
    assert imported and not [name for name in imported if name and "models" in name], imported
