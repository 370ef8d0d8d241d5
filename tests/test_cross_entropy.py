"""Fused linear+CE (ops/cross_entropy.py): value/grad parity with the
materialized path, and the Llama targets= loss mode."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models.llama import CONFIGS, Llama, cross_entropy_loss
from torchft_tpu.ops.cross_entropy import chunked_cross_entropy


def _dense_ref(x, w, targets):
    logits = jnp.dot(
        x.reshape(-1, x.shape[-1]).astype(jnp.float32), w.astype(jnp.float32)
    )
    logp = jax.nn.log_softmax(logits, axis=-1)
    tl = jnp.take_along_axis(logp, targets.reshape(-1)[:, None], axis=1)[:, 0]
    return -jnp.mean(tl)


@pytest.mark.parametrize(
    "dtype,vocab",
    [
        (jnp.float32, 512),
        (jnp.bfloat16, 512),
        # Non-multiple vocab (Llama-3's 128256 is not a power-of-two
        # multiple of any useful chunk): the tail slab is padded + masked.
        (jnp.float32, 500),
    ],
)
def test_chunked_ce_matches_dense(dtype, vocab) -> None:
    n, d = 24, 32
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (n, d), dtype)
    w = jax.random.normal(kw, (d, vocab), dtype) * 0.1
    targets = jax.random.randint(kt, (n,), 0, vocab)

    ref_v, (ref_dx, ref_dw) = jax.value_and_grad(_dense_ref, argnums=(0, 1))(
        x, w, targets
    )
    tol = dict(rtol=2e-2, atol=2e-3) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=1e-6
    )
    for chunk in (64, vocab, None):
        v, (dx, dw) = jax.jit(
            jax.value_and_grad(
                lambda x, w: chunked_cross_entropy(x, w, targets, chunk),
                argnums=(0, 1),
            )
        )(x, w)
        np.testing.assert_allclose(float(v), float(ref_v), rtol=1e-4)
        np.testing.assert_allclose(
            np.asarray(dx, np.float32), np.asarray(ref_dx, np.float32), **tol
        )
        np.testing.assert_allclose(
            np.asarray(dw, np.float32), np.asarray(ref_dw, np.float32), **tol
        )
        assert dw.shape == w.shape  # pad AD restores the true vocab width


def test_out_of_range_targets_clamp_consistently() -> None:
    """Targets outside [0, vocab) are clamped once in the wrapper, so the
    chunked and dense paths return the SAME value for invalid input
    (previously the chunked path silently used a 0.0 target logit while
    the dense path clamped — round-3 advisor)."""
    n, d, vocab = 8, 16, 256
    kx, kw = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, (n, d), jnp.float32)
    w = jax.random.normal(kw, (d, vocab), jnp.float32) * 0.1
    bad = jnp.array([-5, 0, vocab - 1, vocab, vocab + 7, 3, -1, 2 * vocab])
    clamped = jnp.clip(bad, 0, vocab - 1)

    dense = chunked_cross_entropy(x, w, bad, None)
    chunked = chunked_cross_entropy(x, w, bad, 64)
    ref = chunked_cross_entropy(x, w, clamped, None)
    np.testing.assert_allclose(float(dense), float(ref), rtol=1e-6)
    np.testing.assert_allclose(float(chunked), float(ref), rtol=1e-5)


@pytest.mark.parametrize("tied", [False, True])
def test_llama_fused_loss_matches_materialized(tied) -> None:
    """model.apply(params, tokens, targets=...) with loss_vocab_chunk equals
    cross_entropy_loss over the materialized logits — value and grads."""
    cfg = replace(
        CONFIGS["tiny"], tie_embeddings=tied, loss_vocab_chunk=128
    )
    model = Llama(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, cfg.vocab_size)
    targets = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(2), tokens)

    def loss_materialized(p):
        return cross_entropy_loss(model.apply(p, tokens), targets)

    def loss_fused(p):
        return model.apply(p, tokens, targets=targets)

    v_ref, g_ref = jax.jit(jax.value_and_grad(loss_materialized))(params)
    v_fused, g_fused = jax.jit(jax.value_and_grad(loss_fused))(params)
    np.testing.assert_allclose(float(v_fused), float(v_ref), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-4, atol=2e-6,
        ),
        g_fused, g_ref,
    )


def test_llama_head_param_layout_unchanged() -> None:
    """LMHead (models/decoder.py) keeps the nn.Dense param contract the
    sharding plan and existing checkpoints rely on: lm_head/kernel,
    (dim, vocab), cfg dtype."""
    cfg = CONFIGS["tiny"]
    model = Llama(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    kernel = params["params"]["lm_head"]["kernel"]
    assert kernel.shape == (cfg.dim, cfg.vocab_size)
    assert kernel.dtype == cfg.dtype
