"""Fused linear+CE (ops/cross_entropy.py): value/grad parity with the
materialized path, the loss by token under a cotangent by token, and the
Llama targets= loss mode."""

import hashlib

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models.llama import CONFIGS, Llama, cross_entropy_loss
from torchft_tpu.ops.cross_entropy import chunked_cross_entropy, chunked_cross_entropy_by_token


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


@pytest.mark.parametrize(
    "dtype,vocab,chunk",
    [
        (jnp.float32, 512, 64), (jnp.float32, 500, 64), (jnp.float32, 500, None),
        (jnp.bfloat16, 512, 128), (jnp.float32, 512, 512),
    ],
)
def test_the_loss_by_token_matches_dense_under_a_cotangent_by_token(dtype, vocab, chunk) -> None:
    """Every token's own loss, in ``targets``' shape, and its two gradients
    when each token's loss is weighed by a number of its own (a model that
    weighs its exits by a learned probability: models/ouro.py)."""
    b, s, d = 3, 8, 32
    kx, kw, kt, kg = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(kx, (b, s, d), dtype)
    w = jax.random.normal(kw, (d, vocab), dtype) * 0.1
    targets = jax.random.randint(kt, (b, s), 0, vocab)
    weights = jax.random.uniform(kg, (b, s), minval=-1.0, maxval=2.0)  # no two tokens alike

    def dense(x, w):
        logits = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

    fused = lambda x, w: chunked_cross_entropy_by_token(x, w, targets, chunk)
    got, want = jax.jit(fused)(x, w), dense(x, w)
    assert got.shape == targets.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    weighed = lambda f: jax.jit(jax.grad(lambda x, w: jnp.sum(weights * f(x, w)), argnums=(0, 1)))
    tol = dict(rtol=2e-2, atol=2e-3) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=1e-6)
    for mine, plain in zip(weighed(fused)(x, w), weighed(dense)(x, w)):
        assert mine.shape == plain.shape and mine.dtype == plain.dtype
        np.testing.assert_allclose(np.asarray(mine, np.float32), np.asarray(plain, np.float32), **tol)


@pytest.mark.parametrize("vocab,chunk", [(512, 64), (500, 64), (500, None)])
def test_the_mean_of_the_loss_by_token_is_the_mean_loss_to_the_bit(vocab, chunk) -> None:
    n, d = 24, 32
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (n, d), jnp.bfloat16)
    w = jax.random.normal(kw, (d, vocab), jnp.bfloat16) * 0.1
    targets = jax.random.randint(kt, (n,), 0, vocab)
    mean = lambda x, w: chunked_cross_entropy(x, w, targets, chunk)
    of_tokens = lambda x, w: jnp.mean(chunked_cross_entropy_by_token(x, w, targets, chunk))
    value, grads = jax.jit(jax.value_and_grad(mean, argnums=(0, 1)))(x, w)
    again, again_grads = jax.jit(jax.value_and_grad(of_tokens, argnums=(0, 1)))(x, w)
    assert float(value) == float(again)
    for a, b in zip(grads, again_grads):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


@pytest.mark.parametrize(
    "chunk,golden",
    [
        (64, "a065409c323640de07b722901786fe188d98b4c98540bfb6b35f78378a09c226"),
        (None, "3934d1ea7ebfd018cc1dbacc3936fa052f3d196c0bd8e8ca94281be5007f9578"),
    ],
)
def test_the_mean_losss_program_is_the_one_it_was_before_the_loss_by_token(chunk, golden) -> None:
    """The jaxpr of the mean loss and its two gradients, as commit 712ab83 (the
    parent of PR 62, which had no loss by token) traced it: ``by_token`` is
    static, so six cells' step programs did not move with the seventh's loss
    (their lowered modules were compared whole in that PR; this holds the op)."""
    x, w = jnp.zeros((24, 32), jnp.bfloat16), jnp.zeros((32, 500), jnp.bfloat16)
    targets = jnp.zeros((24,), jnp.int32)
    traced = jax.make_jaxpr(jax.value_and_grad(
        lambda x, w: chunked_cross_entropy(x, w, targets, chunk), argnums=(0, 1)
    ))(x, w)
    assert hashlib.sha256(str(traced).encode()).hexdigest() == golden


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
