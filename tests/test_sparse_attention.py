"""ops/sparse_attention.py alone, against plain mathematics on the CPU: the
selection against a sorted top-k, the runs of tiles that share a key length,
and the tiled attention the same however many runs there are. The model that
calls it is tests/test_keye_model.py's; the flash kernels under its selection
are tests/test_flash_attention.py's.

    JAX_PLATFORMS=cpu python -m pytest tests/test_sparse_attention.py -q
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
