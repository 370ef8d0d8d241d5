"""ops/sparse_attention.py alone, against plain mathematics on the CPU: the
selection against a sorted top-k, the runs of tiles that share a key length,
the tiled attention the same however many runs there are, and the selection
as one Mosaic call (ops/key_selection.py, interpreted) against the XLA path. The model that
calls it is tests/test_keye_model.py's; the flash kernels under its selection
are tests/test_flash_attention.py's.

    JAX_PLATFORMS=cpu python -m pytest tests/test_sparse_attention.py -q
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops import key_selection
from torchft_tpu.ops import sparse_attention as tiled
from torchft_tpu.ops.sparse_attention import select_topk, sparse_attention


def relative(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


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


# -- the selection as one Mosaic call (ops/key_selection.py), interpreted ------

KERNEL_SIZES = {"s256-r32-top64": (256, 32, 64), "s512-r64-top128": (512, 64, 128)}


def indexer_inputs(b, s, j=4, e=64, seed=3, tied=False):
    a, c, d = jax.random.split(jax.random.PRNGKey(seed), 3)
    ki = jax.random.normal(c, (b, s, e))
    if tied:  # every key twice: two scores of a row agree to the bit
        ki = ki.at[:, 1::2].set(ki[:, 0::2])
    return jax.random.normal(a, (b, s, j, e)), ki, jax.random.normal(d, (b, s, j))


@functools.lru_cache(maxsize=None)
def kernel_run(size: str, tied: bool = False):
    """(the kernel's selection, the XLA path's, the scores, topk, rows) of one
    size, computed once a module."""
    s, rows, topk = KERNEL_SIZES[size]
    qi, ki, w = indexer_inputs(2, s, tied=tied)
    got = key_selection.key_selection(qi, ki, w, topk=topk, rows=rows, keys=128, interpret=True)
    scores = tiled.index_scores(qi, ki, w)
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((s, s), bool)), scores.shape)
    want = select_topk(scores, causal, topk)
    return tuple(np.asarray(x) for x in (got, want, scores)) + (topk, rows)


@pytest.mark.parametrize("size", KERNEL_SIZES)
def test_the_kernels_selection_is_the_xla_paths_element_for_element(size):
    got, want, _, _, _ = kernel_run(size)
    assert got.dtype == np.int8 and got.shape == want.shape
    assert np.array_equal(got != 0, want)


@pytest.mark.parametrize("size", KERNEL_SIZES)
def test_the_kernels_rows_at_or_under_topk_are_the_causal_mask(size):
    got, _, _, topk, _ = kernel_run(size)
    s = got.shape[-1]
    assert np.array_equal(got[:, :topk], np.broadcast_to(np.tril(np.ones((s, s), np.int8))[:topk], got[:, :topk].shape))


@pytest.mark.parametrize("size", KERNEL_SIZES)
def test_the_kernels_rows_select_their_count_and_no_later_key(size):
    got, _, _, topk, _ = kernel_run(size)
    s = got.shape[-1]
    assert set(np.unique(got)) == {0, 1}
    assert (got.sum(axis=-1) == np.minimum(np.arange(s) + 1, topk)).all()
    assert not np.triu(got, k=1).any()


@pytest.mark.parametrize("size", KERNEL_SIZES)
def test_the_kernels_forced_ties_go_to_the_earlier_key(size):
    got, want, scores, topk, _ = kernel_run(size, tied=True)
    assert np.array_equal(scores[..., 0::2][..., 1:, :], scores[..., 1::2][..., 1:, :])  # the pairs do tie
    assert np.array_equal(got != 0, want)
    first, second = got[..., 0::2] != 0, got[..., 1::2] != 0
    assert not (second & ~first).any()  # never the later key of a pair alone
    both_allowed = np.tril(np.ones(got.shape[1:], bool))[:, 1::2]
    assert (first & ~second & both_allowed).any()  # and some threshold did cut a pair
    assert (got.sum(axis=-1) == np.minimum(np.arange(got.shape[-1]) + 1, topk)).all()


def test_the_kernel_takes_a_topk_that_cuts_a_block_of_rows():
    """topk no multiple of the rows: the block that holds row ``topk`` scores,
    and its rows under ``topk`` still select every earlier key."""
    s, rows, topk = 256, 32, 80
    qi, ki, w = indexer_inputs(1, s, j=2, seed=9)
    got = np.asarray(key_selection.key_selection(qi, ki, w, topk=topk, rows=rows, keys=128, interpret=True))
    scores = tiled.index_scores(qi, ki, w)
    want = select_topk(scores, jnp.tril(jnp.ones((s, s), bool))[None], topk)
    assert np.array_equal(got != 0, np.asarray(want))


@pytest.mark.parametrize(
    "s, e, fits",
    [(8192, 64, True), (1024, 128, True), (8192, 16, False), (8200, 64, False), (64, 64, False), (640, 64, False)],
)
def test_the_kernel_takes_whole_blocks_and_whole_mxu_passes_only(s, e, fits):
    assert key_selection.fits(s, e) is fits


def test_select_keys_keeps_the_tiled_path_off_a_tpu_and_for_other_shapes(monkeypatch):
    """The choice is by platform and shape: off a TPU, and on one for a
    sequence that is not whole blocks, no Mosaic call is traced."""
    qi, ki, w = indexer_inputs(1, 64, j=2, e=16)
    trace = lambda: str(jax.make_jaxpr(lambda *xs: tiled.select_keys(*xs, topk=16, block=16))(qi, ki, w))
    assert "pallas_call" not in trace()
    monkeypatch.setattr(tiled, "on_tpu", lambda: True)
    assert "pallas_call" not in trace()
    qi, ki, w = indexer_inputs(1, 256, j=2, e=64)
    traced = str(jax.make_jaxpr(lambda *xs: tiled.select_keys(*xs, topk=128))(qi, ki, w))
    assert traced.count("pallas_call") == 1 and "key_selection" in traced


def test_the_packed_three_pass_product_is_as_near_float64_as_the_six_products():
    """Both from the explicit bfloat16 parts, summed in float32 on the CPU:
    the six products of ``Precision.HIGHEST`` one by one, and the packed
    contraction over 6e. Neither is more than a few float32 roundings from the
    float64 product, and the packed form's worst error is no more than 1.5
    times the other's."""
    a, c = jax.random.split(jax.random.PRNGKey(21))
    q, k = jax.random.normal(a, (512, 64)), jax.random.normal(c, (2048, 64))
    exact = np.asarray(q, np.float64) @ np.asarray(k, np.float64).T
    (qh, qm, ql), (kh, km, kl) = (
        [np.asarray(part, np.float32) for part in key_selection._parts(x)] for x in (q, k)
    )
    assert np.array_equal(qh + qm + ql, np.asarray(q)) and np.array_equal(kh + km + kl, np.asarray(k))
    six = sum(
        (x @ y.T for x, y in [(qh, kl), (ql, kh), (qm, km), (qm, kh), (qh, km), (qh, kh)]),
        np.zeros(exact.shape, np.float32),
    )
    packed_q, packed_k = (np.asarray(x, np.float32) for x in key_selection.packed_parts(q, k))
    assert packed_q.shape == (512, 384) and packed_k.shape == (2048, 384)
    packed = packed_q @ packed_k.T
    worst = lambda got: float(np.max(np.abs(got - exact)))
    assert worst(six) < 1e-4 and worst(packed) <= 1.5 * worst(six)
    single = worst(qh @ kh.T)  # what one bfloat16 pass would give
    assert worst(packed) < single / 1000
