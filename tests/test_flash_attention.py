"""Pallas flash attention vs dense causal attention (interpret mode on CPU;
the same kernel compiles via Mosaic on real TPU — ops/quantization.py
convention)."""

from __future__ import annotations

import contextlib
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models.llama import causal_attention
from torchft_tpu.ops.flash_attention import flash_attention


def _qkv(b, s, h, kv, d, seed=0, dtype=jnp.float32):
    kq, kk, kvk = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (b, s, h, d), dtype)
    k = jax.random.normal(kk, (b, s, kv, d), dtype)
    v = jax.random.normal(kvk, (b, s, kv, d), dtype)
    return q, k, v


@pytest.mark.parametrize(
    "b,s,h,kv,d,block",
    [
        (2, 64, 4, 4, 16, 32),   # MHA, block divides s
        (1, 128, 8, 2, 32, 32),  # GQA group=4
        (2, 100, 4, 2, 16, 32),  # ragged: s not a block multiple
        (1, 24, 2, 1, 8, 64),    # block larger than s (clamped)
    ],
)
def test_forward_matches_dense(b, s, h, kv, d, block):
    q, k, v = _qkv(b, s, h, kv, d)
    dense = causal_attention(q, k, v, scale=d**-0.5)
    out = flash_attention(q, k, v, block_q=block, block_k=block, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5)


def test_forward_jits_and_matches_blockwise_lse_layout():
    # jit the whole thing (the kernel is traced once inside) and cross-check
    # against the scan-based blockwise path, which shares the backward.
    from torchft_tpu.ops.ring_attention import blockwise_attention

    q, k, v = _qkv(1, 96, 4, 2, 16, seed=3)
    f = jax.jit(
        lambda q, k, v: flash_attention(
            q, k, v, block_q=32, block_k=32, interpret=True
        )
    )
    np.testing.assert_allclose(
        np.asarray(f(q, k, v)),
        np.asarray(blockwise_attention(q, k, v, block_size=32)),
        atol=2e-5,
    )


def test_gradients_match_dense():
    b, s, h, kv, d = 1, 64, 4, 2, 16
    q, k, v = _qkv(b, s, h, kv, d, seed=1)
    w = jax.random.normal(jax.random.PRNGKey(9), (b, s, h, d), jnp.float32)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
        return jnp.sum(out * w)

    def loss_dense(q, k, v):
        return jnp.sum(causal_attention(q, k, v, scale=d**-0.5) * w)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), atol=5e-5, err_msg=f"d{name}"
        )


@pytest.mark.parametrize(
    "b,s,h,kv,d,block",
    [
        (1, 64, 4, 2, 16, 32),   # GQA group=2
        (2, 100, 4, 4, 16, 32),  # MHA, ragged length (padding path)
        (1, 24, 2, 1, 8, 64),    # block larger than s (clamped)
    ],
)
def test_pallas_backward_matches_dense(b, s, h, kv, d, block):
    """The fused dq/dkv backward kernels (interpret mode) against dense
    attention gradients — the TPU training path's backward."""
    q, k, v = _qkv(b, s, h, kv, d, seed=3)
    w = jax.random.normal(jax.random.PRNGKey(11), (b, s, h, d), jnp.float32)

    def loss_pallas(q, k, v):
        out = flash_attention(
            q, k, v, block_q=block, block_k=block,
            interpret=True, use_pallas_bwd=True,
        )
        return jnp.sum(out * w)

    def loss_dense(q, k, v):
        return jnp.sum(causal_attention(q, k, v, scale=d**-0.5) * w)

    g_pallas = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gp, gd, name in zip(g_pallas, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gp), np.asarray(gd), atol=5e-5, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("s", [320, 300])
def test_multi_kv_block_forward_matches_dense(s):
    # block_k rounds UP to the 128 lane tile (the kp row-tile constraint),
    # so every s <= 128 case above runs with a single KV grid step —
    # multi-KV-block machinery (ik==0 init, exp(m_prev-m_new) correction,
    # finalize, causal block skip) needs s > 128: 320 -> nk=3 exact,
    # 300 -> nk=3 through the ragged-padding path.
    b, h, kv, d = 1, 2, 1, 16
    q, k, v = _qkv(b, s, h, kv, d, seed=5)
    dense = causal_attention(q, k, v, scale=d**-0.5)
    out = flash_attention(q, k, v, block_q=64, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5)


def test_multi_kv_block_pallas_backward_matches_dense():
    # Cross-KV-block dq accumulation and the dkv pass's multi-q-block loop
    # (nq=5, nk=3) — see the forward test above for why s must exceed 128.
    b, s, h, kv, d = 1, 320, 2, 1, 16
    q, k, v = _qkv(b, s, h, kv, d, seed=6)
    w = jax.random.normal(jax.random.PRNGKey(13), (b, s, h, d), jnp.float32)

    def loss_pallas(q, k, v):
        out = flash_attention(
            q, k, v, block_q=64, block_k=128,
            interpret=True, use_pallas_bwd=True,
        )
        return jnp.sum(out * w)

    def loss_dense(q, k, v):
        return jnp.sum(causal_attention(q, k, v, scale=d**-0.5) * w)

    g_pallas = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gp, gd, name in zip(g_pallas, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gp), np.asarray(gd), atol=5e-5, err_msg=f"d{name}"
        )


def test_multi_kv_block_partial_matches_dense():
    # The ring building block with a KV window spanning two 128-blocks.
    from torchft_tpu.ops.flash_attention import flash_attention_partial

    b, s, h, kv, d = 1, 256, 2, 1, 16
    q, k, v = _qkv(b, s, h, kv, d, seed=7)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    out, _lse = flash_attention_partial(
        q, k, v, pos, pos, block_q=64, block_k=128, interpret=True
    )
    dense = causal_attention(q, k, v, scale=d**-0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5)


def test_pallas_backward_jits():
    """The whole value_and_grad step jits with the fused backward."""
    b, s, h, kv, d = 1, 96, 4, 2, 32
    q, k, v = _qkv(b, s, h, kv, d, seed=5)

    @jax.jit
    def step(q, k, v):
        def loss(q_, k_, v_):
            return jnp.sum(
                flash_attention(
                    q_, k_, v_, block_q=32, block_k=32,
                    interpret=True, use_pallas_bwd=True,
                ) ** 2
            )
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    loss1, grads = step(q, k, v)

    def loss_dense(q_, k_, v_):
        return jnp.sum(causal_attention(q_, k_, v_, scale=d**-0.5) ** 2)

    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gp, gd in zip(grads, g_dense):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gd), atol=1e-4)


def test_llama_flash_impl_trains():
    from torchft_tpu.models.llama import Llama, LlamaConfig, cross_entropy_loss

    config = LlamaConfig(
        vocab_size=128, dim=32, n_layers=1, n_heads=4, n_kv_heads=2,
        ffn_hidden=64, max_seq_len=64, dtype=jnp.float32,
        attention_impl="flash", attention_block_size=32,
    )
    model = Llama(config)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 33), 0, 128)
    params = model.init(jax.random.PRNGKey(1), tokens[:, :-1])

    def loss_fn(p):
        return cross_entropy_loss(
            model.apply(p, tokens[:, :-1]), tokens[:, 1:]
        )

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert np.isfinite(float(loss))
    # Against the identical model with dense attention: same loss & grads.
    dense_model = Llama(
        LlamaConfig(
            **{**config.__dict__, "attention_impl": "dense"}
        )
    )
    dense_loss = jax.jit(
        lambda p: cross_entropy_loss(
            dense_model.apply(p, tokens[:, :-1]), tokens[:, 1:]
        )
    )(params)
    np.testing.assert_allclose(float(loss), float(dense_loss), atol=1e-5)
    assert all(
        np.all(np.isfinite(np.asarray(g)))
        for g in jax.tree_util.tree_leaves(grads)
    )


def _count_pallas_calls(jaxpr) -> int:
    """pallas_call equations in a jaxpr, nested ones (remat, custom_vjp,
    shard_map, pjit bodies) included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call"
        n += sum(
            _count_pallas_calls(sub)
            for sub in jax.core.jaxprs_in_params(eqn.params)
        )
    return n


@pytest.mark.parametrize("mesh_axis", [None, "fsdp"], ids=["bare", "fsdp2-mesh"])
@pytest.mark.parametrize(
    "remat, calls", [("none", 3), ("dots", 3), ("full", 4)]
)
def test_remat_runs_the_forward_kernel_once_unless_full(
    remat, calls, mesh_axis, monkeypatch
):
    """A GQA attention layer (projections, flash, wo, residual) under the
    model's remat policies, Pallas backward in interpret mode. The
    gradient's jaxpr holds forward + dq + dkv; only ``full`` may add a
    second forward: ``dots`` keeps the kernel's named (out, lse)
    (FLASH_OUT / FLASH_LSE) beside the dot results — plain
    ``checkpoint_dots`` sees no dot_general in a pallas_call and reran it.
    Gradients are the unremat'd ones bit for bit (a kept value replaces the
    same value recomputed by the same kernel). Under a bound mesh the
    dispatcher's shard_map sits between the remat and the names, and they
    survive it."""
    import torchft_tpu.ops.flash_attention as fa
    from torchft_tpu.models.llama import (
        CONFIGS, _flash_under_ambient_mesh, _remat_policy,
    )

    # The dispatcher imports the kernel at call time: force the interpreted
    # Pallas backward (off-TPU it would pick the scan fallback).
    monkeypatch.setattr(
        fa, "flash_attention",
        partial(flash_attention, interpret=True, use_pallas_bwd=True),
    )
    cfg = replace(
        CONFIGS["tiny"], attention_impl="flash",
        attention_block_size=32, attention_block_k=128,
    )
    b, s, h, kv, d, dim = 2, 64, 4, 2, 16, 32
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    w = {
        "wq": jax.random.normal(keys[0], (dim, h, d)) * 0.1,
        "wk": jax.random.normal(keys[1], (dim, kv, d)) * 0.1,
        "wv": jax.random.normal(keys[2], (dim, kv, d)) * 0.1,
        "wo": jax.random.normal(keys[3], (h, d, dim)) * 0.1,
    }
    x = jax.random.normal(keys[4], (b, s, dim))

    def layer(w, x):
        q, k, v = (
            jnp.einsum("bsd,dhe->bshe", x, w[name])
            for name in ("wq", "wk", "wv")
        )
        out = _flash_under_ambient_mesh(cfg, q, k, v, d**-0.5)
        return x + jnp.einsum("bshe,hed->bsd", out, w["wo"])

    def grad_of(f):
        return jax.grad(lambda w, x: jnp.sum(f(w, x) ** 2), argnums=(0, 1))

    remat_layer = layer
    if remat != "none":
        remat_layer = jax.checkpoint(layer, policy=_remat_policy(remat))
    bound = (
        jax.set_mesh(jax.sharding.Mesh(np.array(jax.devices()[:2]), (mesh_axis,)))
        if mesh_axis
        else contextlib.nullcontext()
    )
    with bound:
        jaxpr = jax.make_jaxpr(grad_of(remat_layer))(w, x)
        grads = jax.jit(grad_of(remat_layer))(w, x)
        plain = jax.jit(grad_of(layer))(w, x)
    assert _count_pallas_calls(jaxpr.jaxpr) == calls
    if mesh_axis:
        assert "shard_map" in str(jaxpr)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        grads, plain,
    )


# ---------------------------------------------------------------------------
# Ring attention with the fused per-hop kernel (interpret mode, CPU mesh)
# ---------------------------------------------------------------------------


def _sp_mesh(sp):
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:sp]), ("sp",))


def test_ring_flash_forward_matches_scan_and_dense():
    from torchft_tpu.ops.ring_attention import ring_attention_sharded

    b, sp, h, kv, d = 2, 4, 4, 2, 16
    s = 32 * sp
    q, k, v = _qkv(b, s, h, kv, d, seed=5)
    mesh = _sp_mesh(sp)
    flash = ring_attention_sharded(q, k, v, mesh, use_flash=True)
    scan = ring_attention_sharded(q, k, v, mesh, use_flash=False)
    dense = causal_attention(q, k, v, scale=d**-0.5)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), atol=3e-5)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(scan), atol=3e-5)


def test_ring_flash_zigzag_matches_dense():
    from torchft_tpu.ops.ring_attention import ring_attention_zigzag

    b, sp, h, kv, d = 1, 4, 4, 2, 16
    s = 8 * 2 * sp  # zigzag needs s % (2*sp) == 0
    q, k, v = _qkv(b, s, h, kv, d, seed=6)
    mesh = _sp_mesh(sp)
    out = ring_attention_zigzag(q, k, v, mesh, use_flash=True)
    dense = causal_attention(q, k, v, scale=d**-0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=3e-5)


def test_ring_flash_gradients_match_dense():
    from torchft_tpu.ops.ring_attention import ring_attention_sharded

    b, sp, h, kv, d = 1, 4, 4, 2, 16
    s = 16 * sp
    q, k, v = _qkv(b, s, h, kv, d, seed=7)
    w = jax.random.normal(jax.random.PRNGKey(11), (b, s, h, d), jnp.float32)
    mesh = _sp_mesh(sp)

    def loss_flash(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh, use_flash=True) * w)

    def loss_dense(q, k, v):
        return jnp.sum(causal_attention(q, k, v, scale=d**-0.5) * w)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), atol=1e-4, err_msg=f"d{name}"
        )


def test_llama_ring_flash_under_sp_mesh_matches_dense():
    """attention_impl='ring' + ring_use_flash routes per-hop compute through
    the fused kernel; logits must match the dense single-device result."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from torchft_tpu.models.llama import Llama, LlamaConfig

    cfg = LlamaConfig(
        vocab_size=128, dim=32, n_layers=1, n_heads=4, n_kv_heads=2,
        ffn_hidden=64, max_seq_len=64, dtype=jnp.float32,
        attention_impl="ring", ring_use_flash=True,
    )
    model = Llama(cfg)
    dense_model = Llama(
        LlamaConfig(**{**cfg.__dict__, "attention_impl": "dense"})
    )
    tokens = (jnp.arange(64, dtype=jnp.int32) % cfg.vocab_size).reshape(1, 64)
    # init through the dense twin: explicit 'ring' requires an sp axis,
    # which only exists inside the shard_map below.
    params = dense_model.init(jax.random.PRNGKey(0), tokens)
    dense_logits = dense_model.apply(params, tokens)

    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    positions = jnp.broadcast_to(jnp.arange(64), (1, 64))
    sharded_fwd = shard_map(
        lambda p, t, pos: model.apply(p, t, pos),
        mesh=mesh,
        in_specs=(P(), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    with mesh:
        ring_logits = sharded_fwd(params, tokens, positions)
    np.testing.assert_allclose(
        np.asarray(ring_logits), np.asarray(dense_logits), rtol=2e-4, atol=2e-4
    )


def test_ring_flash_zigzag_gradients_match_dense():
    """The positions-aware ring backward under the permuted (zigzag)
    layout: gradients must match dense exactly like the forward does."""
    from torchft_tpu.ops.ring_attention import ring_attention_zigzag

    b, sp, h, kv, d = 1, 4, 4, 2, 16
    s = 8 * 2 * sp
    q, k, v = _qkv(b, s, h, kv, d, seed=8)
    w = jax.random.normal(jax.random.PRNGKey(12), (b, s, h, d), jnp.float32)
    mesh = _sp_mesh(sp)

    def loss_flash(q, k, v):
        return jnp.sum(ring_attention_zigzag(q, k, v, mesh, use_flash=True) * w)

    def loss_dense(q, k, v):
        return jnp.sum(causal_attention(q, k, v, scale=d**-0.5) * w)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), atol=1e-4, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("zigzag", [False, True])
def test_ring_flash_pallas_backward_matches_dense(zigzag):
    """The per-hop fused Pallas backward (flash_attention_partial_bwd with
    the global logsumexp) under natural and zigzag layouts — the TPU
    long-context training path's backward — vs dense gradients."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from torchft_tpu.ops.ring_attention import (
        ring_attention_flash,
        zigzag_permutation,
    )

    b, sp, h, kv, d = 1, 4, 4, 2, 16
    s = 16 * sp
    q, k, v = _qkv(b, s, h, kv, d, seed=13)
    w = jax.random.normal(jax.random.PRNGKey(14), (b, s, h, d), jnp.float32)
    mesh = _sp_mesh(sp)
    spec = P(None, "sp", None, None)

    if zigzag:
        perm, inv = zigzag_permutation(s, sp)
        perm_j, inv_j = jnp.asarray(perm), jnp.asarray(inv)
    else:
        perm_j = inv_j = jnp.arange(s)
    positions = jnp.broadcast_to(perm_j, (b, s))

    def inner(q_, k_, v_, pos):
        return ring_attention_flash(
            q_, k_, v_, axis_name="sp", scale=d**-0.5,
            q_positions=pos, k_positions=pos,
            block_q=16, block_k=16, use_pallas_bwd=True,
        )

    mapped = shard_map(
        inner, mesh=mesh,
        in_specs=(spec, spec, spec, P(None, "sp")), out_specs=spec,
    )

    def loss_ring(q, k, v):
        out = mapped(q[:, perm_j], k[:, perm_j], v[:, perm_j], positions)
        return jnp.sum(out[:, inv_j] * w)

    def loss_dense(q, k, v):
        return jnp.sum(causal_attention(q, k, v, scale=d**-0.5) * w)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gr, gd, name in zip(g_ring, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gr), np.asarray(gd), atol=1e-4, err_msg=f"d{name}"
        )
