"""ops/ssd.py on the CPU at small sizes: the chunked state-space scan and its
gradient against the recurrence position by position, the state's way across
chunk boundaries, the causal convolution, and how far bfloat16 operands stray.
Each by both paths: the XLA einsums, and the Mosaic kernels in the Pallas
interpreter (``interpret=True``) at the smallest shapes they take, held to
the recurrence and to the einsum path's autodiff; and the shapes the kernels
do not take fall to the einsum path.

    JAX_PLATFORMS=cpu python -m pytest tests/test_ssd.py -q
"""

from __future__ import annotations

import ast
import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops import ssd

ROOT = Path(__file__).resolve().parents[1]

# One compiled program a call: op by op, the CPU compiles every primitive.
# ``interpret=True`` takes the kernels (interpreted) where the shapes fit them;
# off a TPU the default is the einsum path.
scan = jax.jit(ssd.ssd_scan, static_argnames=("chunk", "interpret"))
einsums = jax.jit(ssd.ssd_scan_einsums, static_argnames="chunk")
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
# The same, at shapes the kernels take (a chunk of 128, heads that pack into
# 128 lanes, a state of 128), run in the interpreter.
KERNEL_CASES = {
    "kernels-two-chunks": (1, 256, 4, 64, 1, 128, 128),
    "kernels-two-groups-a-batch-of-two": (2, 256, 4, 64, 2, 128, 128),
    "kernels-a-head-of-128": (1, 128, 2, 128, 1, 128, 128),
    "kernels-four-heads-of-32-a-slab": (1, 128, 4, 32, 1, 128, 128),
    "kernels-the-chunk-is-the-sequence": (1, 128, 2, 64, 1, 128, 256),
    "kernels-chunks-of-256-in-two-blocks-of-rows": (1, 512, 2, 64, 1, 128, 256),
}
CASES.update(KERNEL_CASES)


def path_of(case):
    """(interpret, bound scale): the kernels' cases run interpreted, and their
    chunk of 128 carries running sums eight times a chunk of 16's (to -400 at
    these decays, where float32 holds 3e-5; twice that at 256): five times
    the einsum cases' bounds."""
    return (True, 5.0) if case in KERNEL_CASES else (None, 1.0)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunked_scan_is_the_recurrence(case):
    """float32 on both sides at the highest matmul precision: 1e-5 of the
    largest output, the order of the sums (the kernels' longer chunks:
    ``path_of``)."""
    *sizes, chunk = CASES[case]
    args = inputs(1, *sizes)
    interpret, scale = path_of(case)
    assert ssd.scan_kernel_fits(args[0], args[3], min(chunk, sizes[1])) == (case in KERNEL_CASES)
    with jax.default_matmul_precision("highest"):
        got = scan(*args, chunk=chunk, interpret=interpret)
    want, _ = recurrence(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert close(got, want, scale * 1e-5)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunked_scans_gradient_is_the_recurrences(case):
    """Every argument's gradient (x, dt, A, B, C, D) of a loss that weighs
    every output differently: 2e-5 of the leaf's largest entry (the kernels'
    cases 1e-4: ``path_of``), and the kernels' backward against autodiff
    through the einsum path besides."""
    *sizes, chunk = CASES[case]
    args = inputs(2, *sizes)
    interpret, scale = path_of(case)
    weights = jnp.cos(jnp.arange(np.prod(args[0].shape), dtype=jnp.float32)).reshape(args[0].shape)
    chunked = lambda *a: jnp.sum(weights * scan(*a, chunk=chunk, interpret=interpret))
    plain = lambda *a: jnp.sum(weights * recurrence(*a)[0])
    leaves = ("x", "dt", "A", "B", "C", "D")
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(chunked, argnums=tuple(range(6))))(*args)
    want = jax.jit(jax.grad(plain, argnums=tuple(range(6))))(*args)
    for name, g, w in zip(leaves, got, want):
        assert np.all(np.isfinite(g)) and close(g, w, scale * 2e-5), name
    if interpret:
        by_einsums = lambda *a: jnp.sum(weights * einsums(*a, chunk=chunk))
        with jax.default_matmul_precision("highest"):
            other = jax.jit(jax.grad(by_einsums, argnums=tuple(range(6))))(*args)
        for name, g, w in zip(leaves, got, other):
            assert close(g, w, scale * 2e-5), name


# (positions, heads, head width, state, chunk, interpret) by path.
PATHS = {"einsums": (48, 4, 8, 16, 16, None), "kernels": (384, 2, 64, 128, 128, True)}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_state_crosses_chunk_boundaries(path):
    """Zeroing chunk 0's input changes chunk 2's output, by what the recurrence
    says, and leaves nothing else of the layer out: slow decays, so that the
    state of chunk 0 is still there two boundaries on."""
    s, heads, p, n, chunk, interpret = PATHS[path]
    b, groups = 1, 1
    x, dt, a, b_in, c_out, d_skip = inputs(3, b, s, heads, p, groups, n)
    dt, a = 0.05 * dt * 16 / chunk, a / 16.0  # a chunk's log-decay stays above -1
    assert float(ssd.chunk_log_decay(dt, a, chunk)[0]) > -1.0
    assert ssd.scan_kernel_fits(x, b_in, chunk) == bool(interpret)
    scan = jax.jit(ssd.ssd_scan, static_argnums=6, static_argnames="interpret")
    scan = functools.partial(scan, interpret=interpret)
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


@pytest.mark.parametrize("path", sorted(PATHS))
def test_fast_decays_forget_within_a_chunk_and_nothing_overflows(path):
    """A chunk's total log-decay of minus several hundred (under -600 in the
    kernels' chunk of 128): every decay is the exponential of a masked
    difference, so nothing is inf or nan, forward or backward, and the result
    is still the recurrence's."""
    _, heads, p, n, chunk, interpret = PATHS[path]
    b, s, groups = 1, 2 * chunk, 1
    x, dt, a, b_in, c_out, d_skip = inputs(4, b, s, heads, p, groups, n)
    dt = (20.0 if path == "einsums" else 10.0) * dt
    assert float(ssd.chunk_log_decay(dt, a, chunk)[0]) < (-600.0 if interpret else -300.0)
    with jax.default_matmul_precision("highest"):
        got = scan(x, dt, a, b_in, c_out, d_skip, chunk=chunk, interpret=interpret)
        grads = jax.jit(
            jax.grad(
                lambda *z: jnp.sum(scan(*z, chunk=chunk, interpret=interpret) ** 2),
                argnums=tuple(range(6)),
            )
        )(x, dt, a, b_in, c_out, d_skip)
    assert bool(jnp.all(jnp.isfinite(got)))
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


@pytest.mark.parametrize("path", sorted(PATHS))
def test_bfloat16_operands_stay_within_a_stated_bound_of_float32(path):
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


# (positions, channels): three tiles of 32 rows; one tile of four steps of 64
# rows over two tiles of 128 channels.
CONV_SHAPES = {"three-tiles": (96, 128), "four-steps-two-channel-tiles": (256, 256)}


@pytest.mark.parametrize("shape", sorted(CONV_SHAPES))
@pytest.mark.parametrize("width", [4, 2])
def test_the_convolutions_kernels_are_silu_of_the_causal_convolution(width, shape):
    """``conv_silu`` in the interpreter against ``silu(causal_conv(...))``: the
    output and the gradients of the input, the taps and the bias (1e-5 of the
    largest entry, float32 on both sides); causal across the tiles' edges and
    starting on zeros."""
    s, channels = CONV_SHAPES[shape]
    keys = jax.random.split(jax.random.PRNGKey(8), 4)
    x = jax.random.normal(keys[0], (2, s, channels))
    kernel, bias = jax.random.normal(keys[1], (channels, width)), jax.random.normal(keys[2], (channels,))
    weights = jax.random.normal(keys[3], x.shape)
    assert ssd.conv_kernel_fits(x, kernel)
    by_kernels = lambda *z: ssd.conv_silu(*z, interpret=True)
    plain = lambda *z: jax.nn.silu(ssd.causal_conv(*z))
    got = jax.jit(by_kernels)(x, kernel, bias)
    assert got.dtype == x.dtype and close(got, plain(x, kernel, bias), 1e-5)
    gradient = lambda f: jax.jit(jax.grad(lambda *z: jnp.sum(weights * f(*z)), argnums=(0, 1, 2)))
    for name, g, w in zip(
        ("x", "kernel", "bias"), gradient(by_kernels)(x, kernel, bias), gradient(plain)(x, kernel, bias)
    ):
        assert g.shape == w.shape and close(g, w, 1e-5), name
    # Position 0 is the last tap on x[0] alone.
    np.testing.assert_allclose(
        got[:, 0], jax.nn.silu(bias + kernel[:, -1] * x[:, 0]), rtol=1e-5, atol=1e-6
    )
    # No output moves with a later input, a tile's first row among them.
    edge = 32 if s == 96 else 64
    later = jax.jit(by_kernels)(x.at[:, edge:].add(1.0), kernel, bias)
    np.testing.assert_array_equal(got[:, :edge], later[:, :edge])
    assert not np.allclose(got[:, edge], later[:, edge])


def test_the_convolutions_kernels_round_once_to_bfloat16():
    """bfloat16 in and out, the taps, the bias and the sums float32 between:
    the output is the float32 path's rounded, to a unit of bfloat16, and the
    gradients are within 1% of the float32 path's on the same operands."""
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    x = jax.random.normal(keys[0], (1, 128, 128)).astype(jnp.bfloat16)
    kernel = (0.5 * jax.random.normal(keys[1], (128, 4))).astype(jnp.bfloat16)
    bias = (0.1 * jax.random.normal(keys[2], (128,))).astype(jnp.bfloat16)
    weights = jax.random.normal(keys[3], x.shape)
    by_kernels = lambda *z: ssd.conv_silu(*z, interpret=True).astype(jnp.float32)
    plain = lambda *z: jax.nn.silu(ssd.causal_conv(*z))
    got = ssd.conv_silu(x, kernel, bias, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(got, plain(x, kernel, bias).astype(jnp.bfloat16))
    gradient = lambda f: jax.jit(jax.grad(lambda *z: jnp.sum(weights * f(*z)), argnums=(0, 1, 2)))
    for g, w in zip(gradient(by_kernels)(x, kernel, bias), gradient(plain)(x, kernel, bias)):
        assert g.dtype == jnp.bfloat16 and close(g, w.astype(jnp.float32), 1e-2)


# What the kernels do not take: (scan sizes and chunk) or (convolution shape).
NOT_TAKEN = {
    "a-ragged-tail": (1, 300, 4, 64, 1, 128, 128),
    "a-chunk-of-64": (1, 256, 4, 64, 1, 128, 64),
    "a-head-of-8": (1, 256, 4, 8, 1, 128, 128),
    "a-state-of-16": (1, 256, 4, 64, 1, 16, 128),
    "one-head-of-64": (1, 256, 1, 64, 1, 128, 128),
}


@pytest.mark.parametrize("case", sorted(NOT_TAKEN))
def test_shapes_the_scans_kernels_do_not_take_fall_to_the_einsum_path(case):
    """``scan_kernel_fits`` says so, and ``ssd_scan`` asked for the kernels
    traces no Pallas call and gives the einsum path's result to the bit."""
    *sizes, chunk = NOT_TAKEN[case]
    args = inputs(1, *sizes)
    assert not ssd.scan_kernel_fits(args[0], args[3], chunk)
    asked = lambda *z: ssd.ssd_scan(*z, chunk=chunk, interpret=True)
    assert "pallas_call" not in str(jax.make_jaxpr(asked)(*args))
    np.testing.assert_array_equal(jax.jit(asked)(*args), einsums(*args, chunk=chunk))


@pytest.mark.parametrize("shape", [(50, 128), (64, 96)], ids=["positions-not-by-16", "channels-not-by-128"])
def test_shapes_the_convolutions_kernels_do_not_take_fall_to_the_xla_path(shape):
    x = jax.random.normal(jax.random.PRNGKey(10), (1, *shape))
    kernel, bias = jnp.ones((shape[1], 4)), jnp.zeros((shape[1],))
    assert not ssd.conv_kernel_fits(x, kernel)
    asked = lambda *z: ssd.conv_silu(*z, interpret=True)
    assert "pallas_call" not in str(jax.make_jaxpr(asked)(x, kernel, bias))
    np.testing.assert_array_equal(asked(x, kernel, bias), jax.nn.silu(ssd.causal_conv(x, kernel, bias)))


def test_a_kernel_path_traces_its_two_named_calls():
    """Where the shapes fit, a gradient through ``ssd_scan`` and ``conv_silu``
    is the four Mosaic calls under their names."""
    args = inputs(1, 1, 128, 2, 64, 1, 128)
    loss = lambda *z: jnp.sum(ssd.ssd_scan(*z, chunk=128, interpret=True))
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(*args))
    assert text.count("pallas_call") == 2 and ssd.SSD_FWD in text and ssd.SSD_BWD in text
    x, kernel, bias = jnp.ones((1, 64, 128)), jnp.ones((128, 4)), jnp.zeros((128,))
    text = str(jax.make_jaxpr(jax.grad(lambda *z: jnp.sum(ssd.conv_silu(*z, interpret=True))))(x, kernel, bias))
    assert text.count("pallas_call") == 2 and ssd.CONV_FWD in text and ssd.CONV_BWD in text


def test_ops_ssd_imports_nothing_from_models():
    tree = ast.parse((ROOT / "torchft_tpu/ops/ssd.py").read_text())
    imported = [
        node.module if isinstance(node, ast.ImportFrom) else alias.name
        for node in ast.walk(tree) if isinstance(node, (ast.Import, ast.ImportFrom))
        for alias in (node.names if isinstance(node, ast.Import) else [None])
    ]
    assert imported and not [name for name in imported if name and "models" in name], imported
