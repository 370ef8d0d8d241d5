"""Pallas flash attention vs dense causal attention (interpret mode on CPU;
the same kernel compiles via Mosaic on real TPU — ops/quantization.py
convention)."""

from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops.attention import causal_attention
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
    """The fused backward kernel (interpret mode) against dense attention
    gradients — the TPU training path's backward."""
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
    # dq accumulating across KV blocks and dk/dv across q blocks in the one
    # backward call (nq=5, nk=3) — see the forward test above for why s must
    # exceed 128.
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
    "remat, calls", [("none", 2), ("dots", 2), ("full", 3)]
)
def test_remat_runs_the_forward_kernel_once_unless_full(
    remat, calls, mesh_axis, monkeypatch
):
    """A GQA attention layer (projections, flash, wo, residual) under the
    model's remat policies, Pallas backward in interpret mode. The
    gradient's jaxpr holds the forward and the one backward call; only
    ``full`` may add a second forward: ``dots`` keeps the kernel's named
    (out, lse) (FLASH_OUT / FLASH_LSE) beside the dot results — plain
    ``checkpoint_dots`` sees no dot_general in a pallas_call and reran it.
    Gradients are the unremat'd ones bit for bit (a kept value replaces the
    same value recomputed by the same kernel). Under a bound mesh the
    dispatcher's shard_map sits between the remat and the names, and they
    survive it."""
    from torchft_tpu.models.decoder import remat_policy
    from torchft_tpu.ops import attention
    from torchft_tpu.ops.flash_attention import FLASH_LSE, FLASH_OUT

    # The dispatcher calls its module's name for the kernel: force the
    # interpreted Pallas backward (off-TPU it would pick the scan fallback).
    monkeypatch.setattr(
        attention, "flash_attention",
        partial(flash_attention, interpret=True, use_pallas_bwd=True),
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
        out = attention.flash_under_mesh(q, k, v, scale=d**-0.5, block_q=32, block_k=128)
        return x + jnp.einsum("bshe,hed->bsd", out, w["wo"])

    def grad_of(f):
        return jax.grad(lambda w, x: jnp.sum(f(w, x) ** 2), argnums=(0, 1))

    remat_layer = layer
    if remat != "none":
        # models/llama.py's line
        policy = remat_policy(
            remat, jax.checkpoint_policies.checkpoint_dots, FLASH_OUT, FLASH_LSE
        )
        remat_layer = jax.checkpoint(layer, policy=policy)
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


# --- causal block scheduling ------------------------------------------------
#
# A schedule case is (q positions, k positions, block_q, block_k): positions
# as one sequence's 1-D arrays, or None for arange over (sq, sk).


def _zigzag_hop(rank, src, sp=4, chunk=512):
    """Positions of ring rank ``rank``'s queries and of the keys it holds on
    the hop that brings rank ``src``'s shard: each shard is chunks
    (r, 2 sp - 1 - r) of the sequence."""
    def shard(r):
        return np.concatenate(
            [np.arange(c * chunk, (c + 1) * chunk) for c in (r, 2 * sp - 1 - r)]
        ).astype(np.int32)

    return shard(rank), shard(src)


_SCHEDULES = {
    # name: (sq, sk, q_pos, k_pos, block_q, block_k), (above, diagonal, under)
    "causal-8192": ((8192, 8192, None, None, 512, 1024), (56, 16, 56)),
    "causal-2048": ((2048, 2048, None, None, 512, 1024), (2, 4, 2)),
    # q shard (1, 6) against k shard (2, 5): the low chunk sees nothing, the
    # high chunk everything; no pair needs the mask.
    "zigzag-hop": ((1024, 1024, *_zigzag_hop(1, 2), 256, 256), (8, 0, 8)),
    "zigzag-self": ((1024, 1024, *_zigzag_hop(1, 1), 256, 256), (6, 4, 6)),
    # 512 queries at positions 512.. against 1024 keys.
    "sq-ne-sk": (
        (512, 1024, np.arange(512, 1024, dtype=np.int32), None, 256, 256),
        (1, 2, 5),
    ),
    # 300 = 4 x 64 + 44 = 2 x 128 + 44: the last q block and the last KV
    # block are padded.
    "ragged-300": ((300, 300, None, None, 64, 128), (6, 7, 2)),
}


def _schedule(sq, sk, q_pos, k_pos, block_q, block_k):
    from torchft_tpu.ops import flash_attention as fa

    qp, kp = fa._padded_positions(
        None if q_pos is None else jnp.asarray(q_pos)[None],
        None if k_pos is None else jnp.asarray(k_pos)[None],
        1, sq, sk, block_q, block_k,
    )
    q_sched, k_sched = fa._block_schedule(qp, kp, block_q, block_k, True)
    return np.asarray(q_sched), np.asarray(k_sched)


def _classes(case):
    from torchft_tpu.ops import flash_attention as fa

    return np.asarray(fa._block_classes(*_schedule(*case)))[0]  # (nq, nk)


@pytest.mark.parametrize("name", list(_SCHEDULES))
def test_block_pairs_classify_by_position(name):
    """The class of every (q block, KV block) pair is a pure function of the
    positions and the block sizes: above (0), diagonal (1), under (2)."""
    case, (above, diagonal, under) = _SCHEDULES[name]
    classes = _classes(case)
    counts = tuple(int(np.sum(classes == c)) for c in (0, 1, 2))
    assert counts == (above, diagonal, under), classes
    if name == "causal-8192":
        assert classes.size == 128 and diagonal + under == 72
    if name == "ragged-300":
        # A padded row (-1) or column (INT32_MAX) is in the last q block and
        # the last KV block: masked where needed, never taken for clear.
        assert 2 not in classes[-1] and 2 not in classes[:, -1]
        assert classes[-1].tolist() == [1, 1, 1]


@pytest.mark.parametrize("name", list(_SCHEDULES))
def test_no_block_above_the_diagonal_is_fetched(name):
    """The index maps name, for a pair above the diagonal, the block its
    neighbour in the walk names (forward: the KV block of the step before;
    backward: the q block of the step after), so the pipeline sees an
    unchanged index and copies nothing; a needed pair names its own."""
    from torchft_tpu.ops import flash_attention as fa

    case, (above, _, _) = _SCHEDULES[name]
    q_sched, k_sched = _schedule(*case)
    classes = _classes(case)
    nq, nk = classes.shape
    kv = np.array(
        [[int(fa._kv_block(0, iq, ik, q_sched)) for ik in range(nk)] for iq in range(nq)]
    )
    qb = np.array(
        [[int(fa._q_block(0, ik, iq, k_sched)) for ik in range(nk)] for iq in range(nq)]
    )
    seen = 0
    for iq in range(nq):
        for ik in range(nk):
            if classes[iq, ik]:
                assert kv[iq, ik] == ik and qb[iq, ik] == iq
                continue
            seen += 1
            # A q block (KV block) that needs nothing names one block for
            # the whole walk: compare inside the walk only.
            if ik > 0:
                assert kv[iq, ik] == kv[iq, ik - 1], (iq, ik)
            else:
                assert not classes[iq].any() and len(set(kv[iq])) == 1
            if iq < nq - 1:
                assert qb[iq, ik] == qb[iq + 1, ik], (iq, ik)
            else:
                assert not classes[:, ik].any() and len(set(qb[:, ik])) == 1
    assert seen == above


# The layouts the kernels are run on (interpret mode), an eighth of the
# lengths above at 64 x 128 blocks; the ragged case as it is.
_RUN_CASES = {
    "causal": (512, 512, None, None, 64, 128),
    "zigzag-hop": (256, 256, *_zigzag_hop(1, 2, chunk=128), 64, 128),
    "zigzag-self": (256, 256, *_zigzag_hop(1, 1, chunk=128), 64, 128),
    "ragged-300": _SCHEDULES["ragged-300"][0],
}


def _positioned_inputs(sq, sk, q_pos, k_pos, b=2, h=4, kv=2, d=16):
    """f32 q, k, v, dO and the (b, s) position arrays of a run case."""
    qp = jnp.broadcast_to(
        jnp.arange(sq, dtype=jnp.int32) if q_pos is None else jnp.asarray(q_pos), (b, sq)
    )
    kp = jnp.broadcast_to(
        jnp.arange(sk, dtype=jnp.int32) if k_pos is None else jnp.asarray(k_pos), (b, sk)
    )
    keys = jax.random.split(jax.random.PRNGKey(21), 4)
    q = jax.random.normal(keys[0], (b, sq, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, sk, kv, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, sk, kv, d), jnp.float32)
    d_out = jax.random.normal(keys[3], (b, sq, h, d), jnp.float32)
    return q, k, v, d_out, qp, kp


def _dense_positioned(q, k, v, qp, kp):
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, sq, kvh, h // kvh, d)
    scores = jnp.einsum("bskgd,btkd->bskgt", qg, k) * d**-0.5
    mask = qp[:, :, None, None, None] >= kp[:, None, None, None, :]
    p = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    p = jnp.where(mask.any(axis=-1, keepdims=True), p, 0.0)  # empty rows give 0
    return jnp.einsum("bskgt,btkd->bskgd", p, v).reshape(b, sq, h, d)


@pytest.mark.parametrize("name", list(_RUN_CASES))
def test_scheduled_kernels_equal_the_masked_ones_bit_for_bit(name, monkeypatch):
    """Forward, dq, dk, dv with the schedule as the positions give it equal,
    bit for bit, the same kernels run with no pair classed under (every q
    block's lowest position forced to INT32_MIN, so every needed pair takes
    the mask: the arithmetic before the kernels had a schedule), and both
    match dense attention under the same position mask."""
    from torchft_tpu.ops import flash_attention as fa

    *layout, block_q, block_k = _RUN_CASES[name]
    q, k, v, d_out, qp, kp = _positioned_inputs(*layout)
    d = q.shape[-1]

    def run():
        out, lse = fa.flash_attention_partial(
            q, k, v, qp, kp, block_q=block_q, block_k=block_k, interpret=True
        )
        grads = fa.flash_attention_partial_bwd(
            q, k, v, d_out, out, lse, qp, kp, d**-0.5, block_q, block_k, True
        )
        return (out, lse) + tuple(grads)

    scheduled = run()
    classes = []
    schedule = fa._block_schedule

    def all_diagonal(*args):
        q_sched, k_sched = schedule(*args)
        classes.append(np.asarray(fa._block_classes(q_sched, k_sched)))
        return q_sched.at[:, fa._LO].set(np.iinfo(np.int32).min), k_sched

    monkeypatch.setattr(fa, "_block_schedule", all_diagonal)
    masked = run()
    assert len(classes) == 2  # forward, backward
    assert (classes[0] == 2).any(), "the case exercises no pair under the diagonal"
    for got, want, what in zip(scheduled, masked, ("out", "lse", "dq", "dk", "dv")):
        assert np.array_equal(np.asarray(got), np.asarray(want)), what

    dense, vjp = jax.vjp(lambda q, k, v: _dense_positioned(q, k, v, qp, kp), q, k, v)
    np.testing.assert_allclose(np.asarray(scheduled[0]), np.asarray(dense), atol=2e-5)
    for got, want, what in zip(scheduled[2:], vjp(d_out), ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=5e-5, err_msg=what
        )


# --- the fused backward -------------------------------------------------------
#
# One Mosaic call gives dq, dk and dv. A case is a layout (sq, sk, q positions,
# k positions, block_q, block_k), the heads (h, kv), the dtype of the inputs and
# of the gradients, and how many q blocks of dq the call may keep resident
# in VMEM (None: the module's own limit, which holds every sequence here whole).

_F32, _BF16 = jnp.float32, jnp.bfloat16
_PERMUTED = np.random.default_rng(5).permutation(512).astype(np.int32)
_BWD_CASES = {
    "causal-gqa4": ((512, 512, None, None, 64, 128), (4, 1), _F32, _F32, None),
    "causal-gqa4-bf16": ((512, 512, None, None, 64, 128), (4, 1), _BF16, _BF16, None),
    "bf16-in-f32-out": ((512, 512, None, None, 64, 128), (4, 2), _BF16, _F32, None),
    "ragged-600": ((600, 600, None, None, 128, 256), (4, 2), _F32, _F32, None),
    # 256 queries at positions 256.. against 512 keys.
    "sq-ne-sk": (
        (256, 512, np.arange(256, 512, dtype=np.int32), None, 64, 128),
        (4, 2), _F32, _F32, None,
    ),
    # A hop with no diagonal pair: every pair is wholly above or under.
    "zigzag-hop": (_RUN_CASES["zigzag-hop"], (4, 2), _F32, _F32, None),
    # Queries and keys shuffled alike: every block spans the sequence, so
    # every pair reads diagonal.
    "permuted": ((512, 512, _PERMUTED, _PERMUTED, 64, 128), (4, 2), _F32, _F32, None),
    # Longer than the resident budget: 8 q blocks as 4 chunks of 2; 5 (ragged,
    # padded to 6) as 3 of 2; 4 as 4 of 1, where two chunks of the zigzag hop
    # need no KV block at all.
    "chunked-causal": ((512, 512, None, None, 64, 128), (4, 1), _F32, _F32, 2),
    "chunked-causal-bf16": ((512, 512, None, None, 64, 128), (4, 1), _BF16, _BF16, 2),
    "chunked-ragged-600": ((600, 600, None, None, 128, 256), (4, 2), _F32, _F32, 2),
    "chunked-zigzag-hop": (_RUN_CASES["zigzag-hop"], (4, 2), _F32, _F32, 1),
}
_BWD_CHUNKS = {
    "chunked-causal": 4, "chunked-causal-bf16": 4, "chunked-ragged-600": 3,
    "chunked-zigzag-hop": 4,
}


@pytest.mark.parametrize("name", list(_BWD_CASES))
def test_fused_backward_matches_dense_and_blockwise(name):
    """dq, dk, dv of the one backward call (interpret mode) against the dense
    gradients under the same position mask and, where the layout is plain
    causal, against the scan-based ``_blockwise_core_bwd``: GQA 4:1, a ragged
    length, ``sq != sk``, a zigzag hop without a diagonal pair, a layout that
    is all diagonal, gradients in float32 and in bfloat16, and sequences
    longer than the resident dq accumulator (the call's VMEM set small
    through the function's own argument), which are walked in chunks of q
    blocks: dq is then bit for bit the resident call's, and dk, dv are sums
    of one more partial a chunk."""
    from torchft_tpu.ops import flash_attention as fa
    from torchft_tpu.ops.ring_attention import _blockwise_core_bwd

    (sq, sk, q_pos, k_pos, block_q, block_k), (h, kv), dtype, out_dtype, resident = (
        _BWD_CASES[name]
    )
    d = 16
    q, k, v, d_out, qp, kp = (
        x if x.dtype == jnp.int32 else x.astype(dtype)
        for x in _positioned_inputs(sq, sk, q_pos, k_pos, h=h, kv=kv, d=d)
    )
    b = q.shape[0]
    tile = (
        block_q, block_k, d,
        jnp.dtype(dtype).itemsize, jnp.dtype(out_dtype).itemsize,
    )
    budget = {}
    if resident is not None:
        budget["vmem_bytes"] = fa._bwd_vmem_bytes(resident * block_q, *tile)
    chunks, _ = fa._q_chunks(sq, budget.get("vmem_bytes", fa._MAX_VMEM_BYTES), *tile)
    assert chunks == _BWD_CHUNKS.get(name, 1)
    counts = fa._class_counts(sq, sk, block_q, block_k, qp, kp)
    if "zigzag-hop" in name:
        assert counts["diagonal"] == 0 and counts["above"] and counts["under"]
    if name == "permuted":
        assert counts["above"] == counts["under"] == 0

    out, lse = fa.flash_attention_partial(
        q, k, v, qp, kp, block_q=block_q, block_k=block_k, interpret=True
    )

    def backward(**kw):
        return fa.flash_attention_partial_bwd(
            q, k, v, d_out, out, lse, qp, kp, d**-0.5, block_q, block_k, True,
            out_dtype=out_dtype, **kw,
        )

    grads = backward(**budget)
    assert all(g.dtype == out_dtype for g in grads)
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]

    # The references compute in float32 from the same (rounded) inputs.
    wide = [x.astype(_F32) for x in (q, k, v)]
    _, vjp = jax.vjp(lambda q, k, v: _dense_positioned(q, k, v, qp, kp), *wide)
    references = {"dense": vjp(d_out.astype(_F32))}
    if q_pos is None and k_pos is None:
        references["blockwise"] = _blockwise_core_bwd(
            d**-0.5, block_k,
            (*wide, out.astype(_F32), lse.reshape(b, sq, kv, h // kv)),
            d_out.astype(_F32),
        )
    # bf16: the kernel rounds p and ds to the inputs' dtype before each
    # matmul and (bf16 out) the per-head partials before the group sum;
    # gradients of up to 8 here, so 0.03 is an ulp of bf16.
    atol = 5e-5 if dtype == _F32 else 0.03 if out_dtype == _F32 else 0.05
    for ref_name, reference in references.items():
        for got, want, what in zip(grads, reference, ("dq", "dk", "dv")):
            np.testing.assert_allclose(
                np.asarray(got.astype(_F32)), np.asarray(want), atol=atol,
                err_msg=f"{what} against {ref_name}",
            )

    if resident is not None:
        whole = backward()
        assert np.array_equal(np.asarray(grads[0]), np.asarray(whole[0])), "dq"
        for got, want, what in zip(grads[1:], whole[1:], ("dk", "dv")):
            np.testing.assert_allclose(
                np.asarray(got.astype(_F32)), np.asarray(want.astype(_F32)),
                atol=2e-5 if out_dtype == _F32 else 0.07, err_msg=what,
            )


# ---------------------------------------------------------------------------
# Under a selection: the kernels with the operand against the tiled XLA path
# of ops/sparse_attention.py, which is their oracle (and what runs off a TPU)
# ---------------------------------------------------------------------------

# name: (s, q heads, kv heads, topk, the indexer's taste, tile of the
# selection, block_q, block_k). "recent": index scores that fall with the
# distance, so a query's keys are its latest ``topk``: from row block_k + topk
# on, a row's first KV blocks hold none of its keys (its running max stays at
# the sentinel until one arrives), and with nothing else mixed in, whole
# (block_q, block_k) blocks of the operand that the causal schedule needs
# are zero. "mixed": every other row so, the rest random.
_SELECTED_CASES = {
    "gqa-group-of-8": (256, 8, 1, 24, "random", 32, 32, 128),
    "a-head-a-group": (256, 2, 2, 24, "random", 32, 32, 128),
    "topk-over-the-sequence": (256, 4, 2, 300, "random", 32, 32, 128),
    "first-kv-blocks-hold-no-key-of-a-row": (384, 4, 2, 24, "mixed", 32, 32, 128),
    "a-block-of-the-operand-all-zero": (384, 4, 2, 24, "recent", 32, 32, 128),
    "sq-not-a-multiple-of-the-block": (200, 4, 2, 24, "random", 8, 64, 128),
}


def _indexer_inputs(b, s, taste):
    """qi (b, s, 1, 4), ki (b, s, 4), w (b, s, 1) float32. A key is a point
    on a quarter circle by its position beside two random numbers; a "recent"
    row asks for the circle alone, so its ``relu(qi . ki)`` is the cosine of
    an angle that grows with the distance, a "random" row for the random
    half alone; "mixed": odd rows recent, even rows random."""
    keys = jax.random.split(jax.random.PRNGKey(31), 2)
    angle = jnp.arange(s) * (np.pi / 2 / s)
    circle = jnp.broadcast_to(jnp.stack([jnp.cos(angle), jnp.sin(angle)], -1), (b, s, 2))
    ki = jnp.concatenate([circle, jax.random.normal(keys[1], (b, s, 2))], -1)
    recent = jnp.concatenate([circle, jnp.zeros((b, s, 2))], -1)
    random = jnp.concatenate([jnp.zeros((b, s, 2)), jax.random.normal(keys[0], (b, s, 2))], -1)
    rows = {
        "recent": jnp.ones((s,), bool), "random": jnp.zeros((s,), bool),
        "mixed": jnp.arange(s) % 2 == 1,
    }[taste]
    qi = jnp.where(rows[None, :, None], recent, random)
    return qi[:, :, None, :], ki, jnp.ones((b, s, 1))


@pytest.mark.parametrize("name", list(_SELECTED_CASES))
def test_selected_kernels_match_the_tiled_path(name):
    """Output and the gradients into q, k and v: ``select_keys`` then
    ``flash_attention(..., selection=...)`` (both kernels interpreted)
    against ``sparse_attention``, and the operand against its selection."""
    from torchft_tpu.ops.sparse_attention import select_keys, sparse_attention

    s, h, kv, topk, taste, tile, block_q, block_k = _SELECTED_CASES[name]
    b, d = 2, 16
    q, k, v = _qkv(b, s, h, kv, d, seed=4)
    qi, ki, w = _indexer_inputs(b, s, taste)
    weight = jax.random.normal(jax.random.PRNGKey(9), (b, s, h, d))

    def tiled(q, k, v):
        return sparse_attention(
            q, k, v, qi, ki, w, topk=topk, scale=d**-0.5, block=tile,
            return_selection=True,
        )

    selection = select_keys(qi, ki, w, topk=topk, block=tile)
    assert selection.dtype == jnp.int8 and selection.shape == (b, s, s)
    want, chosen = tiled(q, k, v)
    assert np.array_equal(np.asarray(selection), np.asarray(chosen))
    picked = np.asarray(selection[0])
    assert (picked.sum(axis=1) == np.minimum(np.arange(s) + 1, topk)).all()
    blocks = picked[: s // block_q * block_q, : s // block_k * block_k].reshape(
        s // block_q, block_q, s // block_k, block_k
    )
    if taste == "mixed":
        assert (picked[block_k + topk:, :block_k].sum(axis=1) == 0).any()
        assert blocks.any(axis=(1, 3))[-1].all()  # and yet no needed block is empty
    if taste == "recent":
        assert not blocks.any(axis=(1, 3))[-1, 0]  # needed by the schedule, all zero

    def kernels(q, k, v):
        return flash_attention(
            q, k, v, block_q=block_q, block_k=block_k, interpret=True,
            selection=selection,
        )

    np.testing.assert_allclose(np.asarray(kernels(q, k, v)), np.asarray(want), atol=2e-5)
    got_grads = jax.grad(lambda *x: jnp.sum(kernels(*x) * weight), argnums=(0, 1, 2))(q, k, v)
    want_grads = jax.grad(lambda *x: jnp.sum(tiled(*x)[0] * weight), argnums=(0, 1, 2))(q, k, v)
    for got, ref, what in zip(got_grads, want_grads, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=5e-5, err_msg=what)

    if topk >= s:
        # The selection is the causal mask: the position-masked call, bit for
        # bit (a pair under the diagonal loses a select that was true all over).
        plain = partial(
            flash_attention, block_q=block_q, block_k=block_k, interpret=True,
            use_pallas_bwd=True,
        )
        assert np.array_equal(np.asarray(kernels(q, k, v)), np.asarray(plain(q, k, v)))
        plain_grads = jax.grad(lambda *x: jnp.sum(plain(*x) * weight), argnums=(0, 1, 2))(q, k, v)
        for got, ref in zip(got_grads, plain_grads):
            assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_a_row_that_selects_nothing_comes_out_zero_with_zero_gradients():
    """No selection ``select_keys`` makes has such a row (a query sees itself),
    but the operand is any int8 array: rows 3 and 40 select nothing, and the
    kernels give them zero output and take no gradient through them."""
    b, s, h, kv, d = 1, 64, 2, 1, 16
    q, k, v = _qkv(b, s, h, kv, d, seed=6)
    selection = jnp.tril(jnp.ones((s, s), jnp.int8)).at[jnp.array([3, 40])].set(0)[None]

    def kernels(q, k, v):
        return flash_attention(
            q, k, v, block_q=32, block_k=128, interpret=True, selection=selection
        )

    out = kernels(q, k, v)
    assert not np.any(np.asarray(out[0, [3, 40]])) and np.all(np.isfinite(np.asarray(out)))
    dq, dk, dv = jax.grad(lambda *x: jnp.sum(kernels(*x) ** 2), argnums=(0, 1, 2))(q, k, v)
    assert not np.any(np.asarray(dq[0, [3, 40]]))
    assert all(np.all(np.isfinite(np.asarray(g))) for g in (dq, dk, dv))


def test_the_selection_takes_no_scan_backward_and_no_other_shape():
    q, k, v = _qkv(1, 64, 2, 1, 16)
    selection = jnp.ones((1, 64, 64), jnp.int8)
    with pytest.raises(ValueError, match="scan-based backward"):
        flash_attention(q, k, v, interpret=True, use_pallas_bwd=False, selection=selection)
    with pytest.raises(ValueError, match="selection"):
        flash_attention(q, k, v, interpret=True, selection=selection[:, :32])


# --- a window: the second edge of the schedule --------------------------------
#
# Query t sees key u iff 0 <= t - u < window (the query's own position counts).


def _needed_pairs_closed_form(s, window):
    """(query, key) pairs a window allows in a sequence of ``s``."""
    full = min(s, window)
    return full * (full + 1) // 2 + (s - full) * full


# name: (b, s, heads, kv heads, head_dim, block_q, block_k, window)
_WINDOW_CASES = {
    "multiple-of-both-blocks": (1, 512, 4, 2, 16, 64, 128, 256),
    "multiple-of-neither": (2, 300, 4, 2, 16, 64, 128, 100),
    "longer-than-the-sequence": (1, 200, 4, 2, 16, 64, 128, 1000),
    "exactly-the-sequence": (1, 256, 2, 1, 16, 64, 128, 256),
    "shorter-than-a-block": (1, 320, 4, 4, 16, 64, 128, 20),
    "one-key": (1, 160, 2, 2, 16, 32, 128, 1),
    "group-of-7": (1, 384, 14, 2, 32, 64, 128, 144),
    "ragged-with-a-padded-tail": (1, 333, 7, 1, 16, 48, 128, 129),
}


@pytest.mark.parametrize("name", list(_WINDOW_CASES))
def test_windowed_kernels_match_dense_forward_and_gradients(name):
    """The forward and the one backward call under a window (interpret mode),
    against dense attention under the same mask: the output and the three
    gradients; and the scan-based backward, the CPU's own, agrees too."""
    b, s, h, kv, d, block_q, block_k, window = _WINDOW_CASES[name]
    q, k, v = _qkv(b, s, h, kv, d, seed=31)
    d_out = jax.random.normal(jax.random.PRNGKey(32), q.shape, jnp.float32)
    dense, dense_vjp = jax.vjp(
        lambda q, k, v: causal_attention(q, k, v, d**-0.5, window), q, k, v
    )
    want = (dense, *dense_vjp(d_out))
    for pallas_bwd in (True, False):
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, block_q=block_q, block_k=block_k, interpret=True,
                use_pallas_bwd=pallas_bwd, window=window,
            ),
            q, k, v,
        )
        for got, ref, what in zip((out, *vjp(d_out)), want, ("out", "dq", "dk", "dv")):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(ref), atol=5e-5, err_msg=f"{what} ({pallas_bwd})"
            )


def test_a_window_that_covers_the_sequence_is_the_causal_call():
    """``window >= s`` changes nothing, and is not a call of another name: the
    traced program holds the causal call's tables of three rows."""
    q, k, v = _qkv(1, 128, 2, 1, 16, seed=5)
    call = lambda window: jax.make_jaxpr(
        lambda q, k, v: flash_attention(
            q, k, v, block_q=32, block_k=128, interpret=True, window=window
        )
    )(q, k, v)
    assert str(call(128)) == str(call(None)) == str(call(4096))
    assert str(call(127)) != str(call(None))


@pytest.mark.parametrize(
    "s, window, block_q, block_k",
    [(2048, 512, 128, 256), (1000, 300, 64, 128), (512, 512, 64, 128), (768, 64, 128, 128)],
)
def test_the_windows_pairs_by_class_add_up_to_the_closed_form(s, window, block_q, block_k):
    """Every (query, key) pair the window allows lies in a block pair the
    schedule needs (inside: all of its pairs allowed; diagonal or edge: some),
    none in a pair it skips (above or behind); counted pair by pair, the
    allowed ones are the closed form."""
    from torchft_tpu.ops import flash_attention as fa

    qp, kp = fa._padded_positions(None, None, 1, s, s, block_q, block_k)
    tables = fa._block_schedule(qp, kp, block_q, block_k, True, window)
    classes = np.asarray(fa._block_classes(*tables, window))[0]
    at_q = np.asarray(qp[0]).reshape(-1, block_q)
    at_k = np.asarray(kp[0]).reshape(-1, block_k)
    total = 0
    for iq in range(classes.shape[0]):
        for ik in range(classes.shape[1]):
            apart = at_q[iq][:, None].astype(np.int64) - at_k[ik][None, :]
            allowed = int(np.sum((apart >= 0) & (apart < window) & (at_q[iq][:, None] >= 0)))
            if classes[iq, ik] == 0:
                assert allowed == 0, (iq, ik)
            if classes[iq, ik] == 2:
                assert allowed == block_q * block_k, (iq, ik)
            total += allowed
    assert total == _needed_pairs_closed_form(s, window)
    counts = fa._class_counts(s, s, block_q, block_k, window=window)
    causal = fa._class_counts(s, s, block_q, block_k)
    assert counts["above"] == causal["above"]
    assert counts["behind"] + counts["edge"] + counts["under"] == causal["under"]
    assert counts["behind"] == int(np.sum(classes == 0)) - causal["above"]


def test_the_cells_windowed_layers_walk_under_half_of_the_causal_block_pairs():
    """At 1 x 16,384 with a window of 4,096 in 512 x 1,024 blocks: the closed
    form (43.7% of causal attention's pairs) and the block pairs by class."""
    from torchft_tpu.ops import flash_attention as fa

    s, window = 16384, 4096
    assert _needed_pairs_closed_form(s, window) == 58_722_304
    assert round(100 * 58_722_304 / (s * (s + 1) // 2), 1) == 43.7
    counts = fa._class_counts(s, s, 512, 1024, window=window)
    assert counts == {
        "above": 240, "diagonal": 32, "under": 84, "behind": 132, "edge": 24, "steps": 140,
        "halves": 28,
    }
    causal = fa._class_counts(s, s, 512, 1024)
    assert causal == {"above": 240, "diagonal": 32, "under": 240, "steps": 272, "halves": 16}
    # 140 block pairs walked of the causal call's 272: 51.5% of its blocks
    # for 43.7% of its pairs (of the diagonal's and the edge's blocks, 28 are
    # computed by the half that needs it, the other 28 whole). They are the
    # grid steps a head takes, too.
    assert counts["diagonal"] + counts["under"] + counts["edge"] == 140


@pytest.mark.parametrize("s, window, block_q, block_k", [(1024, 256, 64, 128), (300, 100, 64, 128)])
def test_no_block_behind_the_window_is_fetched(s, window, block_q, block_k):
    """Under a window the index maps stop on both sides: a step before a q
    block's first needed KV block names that block, a step after its last
    names the last (the backward's q blocks likewise), so a skipped pair names
    the block its neighbour in the walk holds; a needed pair names its own."""
    from torchft_tpu.ops import flash_attention as fa

    qp, kp = fa._padded_positions(None, None, 1, s, s, block_q, block_k)
    q_sched, k_sched = (
        np.asarray(t) for t in fa._block_schedule(qp, kp, block_q, block_k, True, window)
    )
    assert q_sched.shape[1] == k_sched.shape[1] == 4
    classes = np.asarray(fa._block_classes(q_sched, k_sched, window))[0]
    nq, nk = classes.shape
    for iq in range(nq):
        needed = np.flatnonzero(classes[iq])
        named = [int(fa._kv_block(0, iq, ik, q_sched, True)) for ik in range(nk)]
        assert named == [int(np.clip(ik, needed[0], needed[-1])) for ik in range(nk)]
    for ik in range(nk):
        needed = np.flatnonzero(classes[:, ik])
        named = [int(fa._q_block(0, ik, iq, k_sched, True)) for iq in range(nq)]
        assert named == [int(np.clip(iq, needed[0], needed[-1])) for iq in range(nq)]
    assert int(np.sum(classes == 0)) > int(np.sum(np.triu(np.ones((nq, nk)), 1)) // 2)


def test_a_window_and_a_selection_do_not_meet_and_the_blockwise_path_takes_a_window():
    from torchft_tpu.ops.attention import attend

    q, k, v = _qkv(1, 96, 4, 2, 16, seed=8)
    with pytest.raises(ValueError, match="selection"):
        flash_attention(
            q, k, v, interpret=True, window=8, selection=jnp.ones((1, 96, 96), jnp.int8)
        )
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, interpret=True, window=0)
    dense = causal_attention(q, k, v, 16**-0.5, 40)
    for impl in ("dense", "blockwise", "flash"):
        out = attend(q, k, v, scale=16**-0.5, impl=impl, block_size=32, window=40)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5, err_msg=impl)
    with pytest.raises(ValueError, match="ring"):
        attend(q, k, v, scale=16**-0.5, impl="ring", window=40)


# --- the grid of needed pairs -------------------------------------------------
#
# A call without position arrays (``flash_attention``: the sequence's own
# positions) knows every pair's class while tracing, and its grid is the list
# of the needed pairs; a call with them (a ring hop) walks every pair.

# name: (s, block_q, block_k, window, q blocks of a backward chunk or None for
# one chunk), (steps of a head's forward, steps of a backward chunk)
_STEP_TABLES = {
    # The cells' geometries: 4 x 2048 and 1 x 8192 (the Mistral and Keye
    # cells), 1 x 16,384 without and with the window of 4,096 (SmallThinker).
    "cells-2048": ((2048, 512, 1024, None, None), (6, 6)),
    "cells-8192": ((8192, 512, 1024, None, None), (72, 72)),
    "cells-16384": ((16384, 512, 1024, None, None), (272, 272)),
    "cells-16384-window-4096": ((16384, 512, 1024, 4096, None), (140, 140)),
    # Ragged: 300 = 4 x 64 + 44 = 2 x 128 + 44, the last q block and the last
    # KV block padded; 1000 under a window that is a multiple of neither.
    "ragged-300": ((300, 64, 128, None, None), (9, 9)),
    "ragged-1000-window-300": ((1000, 64, 128, 300, None), (52, 52)),
    "window-shorter-than-a-block": ((768, 128, 128, 20, None), (11, 11)),
    # Chunks of q blocks (a sequence over the VMEM budget): 16 q blocks as 4
    # chunks of 4, the longest chunk's steps for all (the last chunk's 30
    # pairs; under the window 12 pairs and the 4 KV blocks that need nothing
    # of the chunk); 5 q blocks padded to 6, the sixth wholly padding.
    "chunked-8192": ((8192, 512, 1024, None, 4), (72, 30)),
    "chunked-8192-window-2048": ((8192, 512, 1024, 2048, 4), (42, 16)),
    "chunked-ragged-600": ((600, 128, 256, None, 2), (9, 5)),
    "chunked-ragged-600-window-200": ((600, 128, 256, 200, 2), (9, 5)),
}


def _step_tables(name):
    from torchft_tpu.ops import flash_attention as fa

    (s, block_q, block_k, window, nqc), _ = _STEP_TABLES[name]
    nq = -(-s // block_q)
    nqc = nqc or nq
    forward = fa._fwd_steps(fa._own_classes(s, s, block_q, block_q, block_k, window))
    classes = fa._own_classes(s, s, nqc * block_q, block_q, block_k, window)
    backward, n = fa._bwd_steps(classes, nqc)
    return forward, backward.reshape(4, -1, n), classes, nqc


@pytest.mark.parametrize("name", list(_STEP_TABLES))
def test_the_step_tables_list_the_needed_pairs_in_the_order_of_the_dense_walk(name):
    """The trace-time classes are the ones the schedule gives from the
    position arrays; the forward's table lists exactly the needed pairs, q
    blocks outer and a q block's KV blocks ascending, with each pair's class
    and each q block's first and last step flagged once; the backward's lists
    them chunk by chunk, KV blocks outer and the chunk's q blocks ascending,
    each KV block's first and last step and each q block's first and last
    visit flagged once; what is listed beside them has class 0 and names a
    block the walk already holds (or a q block of padding, once)."""
    from torchft_tpu.ops import flash_attention as fa

    (s, block_q, block_k, window, _), (n_forward, n_backward) = _STEP_TABLES[name]
    forward, backward, classes, nqc = _step_tables(name)
    nc, nk = backward.shape[1], classes.shape[1]
    qp, kp = fa._padded_positions(None, None, 1, s, s, nqc * block_q, block_k)
    tables = fa._block_schedule(qp, kp, block_q, block_k, True, window)
    # A pair computed by one half is one the schedule masks whole.
    assert np.array_equal(
        np.where(classes > 2, 1, classes), np.asarray(fa._block_classes(*tables, window))[0]
    )

    # Forward: the q blocks the unchunked call has (no block wholly padding).
    own = classes[: -(-s // block_q)]
    assert forward.dtype == np.int32 and forward.shape == (4, n_forward)
    assert [tuple(pair) for pair in forward[: fa._CLASS].T] == [
        (iq, ik) for iq in range(own.shape[0]) for ik in range(nk) if own[iq, ik]
    ]
    assert np.array_equal(forward[fa._CLASS], own[forward[fa._IQ], forward[fa._IK]])
    for iq in range(own.shape[0]):
        flags = forward[fa._FLAGS][forward[fa._IQ] == iq]
        assert flags[0] & fa._ROW_FIRST and flags[-1] & fa._ROW_LAST
        assert np.sum(flags & fa._ROW_FIRST != 0) == np.sum(flags & fa._ROW_LAST != 0) == 1
    assert fa._class_counts(s, s, block_q, block_k, window=window)["steps"] == n_forward

    # Backward, chunk by chunk.
    assert backward.shape == (4, nc, n_backward)
    assert max(
        np.sum(classes[c * nqc : (c + 1) * nqc] > 0) for c in range(nc)
    ) <= n_backward
    for c in range(nc):
        iq, ik, kind, flags = backward[:, c]
        assert np.all((c * nqc <= iq) & (iq < (c + 1) * nqc))
        assert np.array_equal(kind, classes[iq, ik] * (kind > 0))
        needed = [(i, k) for i, k, cls in zip(iq, ik, kind) if cls]
        assert needed == [
            (i, k) for k in range(nk) for i in range(c * nqc, (c + 1) * nqc) if classes[i, k]
        ]
        # KV blocks never go back, and a KV block's steps are one run: first
        # and last flagged once for each of the nk, needed or not.
        assert np.all(np.diff(ik) >= 0) and set(ik) == set(range(nk))
        for k in range(nk):
            mine = flags[ik == k]
            assert np.sum(mine & fa._ROW_FIRST != 0) == np.sum(mine & fa._ROW_LAST != 0) == 1
            first = np.flatnonzero(mine & fa._ROW_FIRST)[0]
            last = np.flatnonzero(mine & fa._ROW_LAST)[0]
            assert first == 0 and np.all(kind[ik == k][last + 1 :] == 0)
        # Every q block of the chunk has its dq rows begun once, before its
        # first needed pair, and ended once, after its last.
        for block in range(c * nqc, (c + 1) * nqc):
            at = np.flatnonzero(iq == block)
            begun = at[flags[at] & fa._Q_FIRST != 0]
            ended = at[flags[at] & fa._Q_LAST != 0]
            assert len(begun) == len(ended) == 1
            work = at[kind[at] > 0]
            if len(work):
                assert begun[0] <= work[0] and work[-1] <= ended[0]
        # A step of class 0 names the KV block and (but for a q block of
        # padding, listed for its rows of dq) the q block of the step before.
        for at in np.flatnonzero(kind == 0):
            padding = not classes[iq[at]].any()
            assert padding or at == 0 or iq[at] == iq[at - 1], (c, at)


@pytest.mark.parametrize(
    "s, window, steps",
    [(2048, None, 6), (8192, None, 72), (16384, None, 272), (16384, 4096, 140)],
)
def test_the_count_of_steps_says_which_walk_a_call_takes(s, window, steps):
    """``_class_counts(...)["steps"]``: the grid steps of a head's forward.
    Without position arrays, the needed pairs (diagonal + under + edge); with
    them, every pair of the grid; and the needed pairs no longer where their
    table would outgrow SMEM (by shape alone)."""
    from torchft_tpu.ops import flash_attention as fa

    counts = fa._class_counts(s, s, 512, 1024, window=window)
    assert counts["steps"] == steps
    assert steps == counts["diagonal"] + counts["under"] + counts.get("edge", 0)
    at = jnp.arange(s, dtype=jnp.int32)[None]
    dense = fa._class_counts(s, s, 512, 1024, at, at, window=window)
    assert dense["steps"] == (s // 512) * (s // 1024) and dense["halves"] == 0
    assert {k: v for k, v in dense.items() if k not in ("steps", "halves")} == {
        k: v for k, v in counts.items() if k not in ("steps", "halves")
    }


# name: (sq, sk, block_q, block_k, window), (steps of a head's forward, those
# of them that compute one half of their KV block alone)
_HALF_CLASSES = {
    # The cells' geometries, 512 x 1024: an even q block's diagonal pair needs
    # its left half alone; under the window of 4,096 an odd q block's first
    # pair (q blocks 9, 11, ... 31) needs its right half alone.
    "cells-16384": ((16384, 16384, 512, 1024, None), (272, 16)),
    "cells-16384-window-4096": ((16384, 16384, 512, 1024, 4096), (140, 28)),
    "cells-8192": ((8192, 8192, 512, 1024, None), (72, 8)),
    "cells-2048": ((2048, 2048, 512, 1024, None), (6, 2)),
    # Ragged, the last KV block 904 real keys of 1,024, under a window that is
    # a multiple of no block; a window that is no multiple of a half block.
    "ragged-5000-window-1300": ((5000, 5000, 512, 1024, 1300), (24, 8)),
    "window-1000-of-8192": ((8192, 8192, 512, 1024, 1000), (30, 15)),
    # 1,536 keys = 1,024 + 512: the last KV block's right half is all padding,
    # and its left half lies wholly under the queries from 1,536 on: a bare
    # half, which takes more queries than keys.
    "more-queries-than-keys": ((2560, 1536, 256, 1024, None), (16, 8)),
    # A half of 64 or 192 keys is no whole lane tiles: every step computes
    # its block.
    "block-k-128": ((1000, 1000, 64, 128, 300), (52, 0)),
    "block-k-384": ((1536, 1536, 128, 384, None), (30, 0)),
}


@pytest.mark.parametrize("name", list(_HALF_CLASSES))
def test_a_pair_with_one_half_needed_is_classed_by_that_half(name):
    """``_own_classes`` against the mask itself, pair by pair: a class of 3 to
    6 names the one half that holds allowed (query, key) pairs, masked (3, 5)
    where it holds disallowed ones too and bare (4, 6) where it does not, and
    the other half holds none; a pair of class 1 or 2 holds allowed pairs in
    both halves; ``_class_counts`` counts the former as ``halves``, and none
    where a half block is no multiple of the 128 lanes."""
    from torchft_tpu.ops import flash_attention as fa

    (sq, sk, block_q, block_k, window), (steps, halves) = _HALF_CLASSES[name]
    classes = fa._own_classes(sq, sk, block_q, block_q, block_k, window)
    counts = fa._class_counts(sq, sk, block_q, block_k, window=window)
    assert (counts["steps"], counts["halves"]) == (steps, halves)
    assert int(np.sum(classes > 0)) == steps and int(np.sum(classes > 2)) == halves
    if block_k % 256:
        assert classes.max() <= 2
        return
    qp, kp = fa._padded_positions(None, None, 1, sq, sk, block_q, block_k)
    at_q = np.asarray(qp[0]).reshape(-1, block_q)
    at_k = np.asarray(kp[0]).reshape(-1, 2, block_k // 2)
    seen = set()
    for iq, ik in zip(*np.nonzero(classes)):
        apart = at_q[iq][:, None, None].astype(np.int64) - at_k[ik][None]
        allowed = (apart >= 0) & (apart < (window or sq)) & (at_q[iq][:, None, None] >= 0)
        left, right = (int(n) for n in allowed.sum(axis=(0, 2)))
        kind, full = int(classes[iq, ik]), block_q * block_k // 2
        seen.add(kind)
        if kind <= 2:
            # (A q block with padded rows reads -1 as its lowest position, so
            # the schedule cannot tell what lies behind a window for it.)
            padded = at_q[iq].min() < 0
            assert padded or (left and right), (iq, ik)
            assert (kind == 2) == (left + right == 2 * full), (iq, ik)
            continue
        masked, span = fa._body_of(kind)
        mine, other = (left, right) if span == fa._LEFT else (right, left)
        assert other == 0 and mine and masked == (mine < full), (iq, ik, kind)
    if name == "cells-16384-window-4096":
        assert seen == {1, 2, 3, 5}
        assert [iq for iq, ik in zip(*np.nonzero(classes == 5))] == list(range(9, 32, 2))
    if name == "more-queries-than-keys":
        assert seen == {1, 2, 3, 4}


def test_a_step_table_over_the_smem_budget_falls_back_to_the_dense_walk(monkeypatch):
    from torchft_tpu.ops import flash_attention as fa

    assert fa._class_counts(131072, 131072, 512, 1024)["steps"] == 16512
    assert 16 * 16512 <= fa._MAX_TABLE_BYTES < 16 * 65792
    assert fa._class_counts(262144, 262144, 512, 1024)["steps"] == 512 * 256
    # The same choice in the calls themselves, at a size the interpreter runs.
    q, k, v = _qkv(1, 256, 2, 1, 16, seed=2)
    call = lambda: str(jax.make_jaxpr(
        lambda q, k, v: jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, block_q=64, block_k=128, interpret=True, use_pallas_bwd=True
        )))(q)
    )(q, k, v))
    listed = call()
    monkeypatch.setattr(fa, "_MAX_TABLE_BYTES", 16 * 6 - 1)
    dense = call()
    assert "grid=(1, 2, 6)" in listed and "grid=(1, 2, 1, 6)" in listed
    assert "grid=(1, 2, 4, 2)" in dense and "grid=(1, 2, 1, 2, 4)" in dense


def test_a_bare_half_runs_without_the_mask():
    """More queries than keys, the keys ending with the left half of their
    last KV block: for the queries from there on that half lies wholly under
    the diagonal and runs bare (class 4), the right half, all padding, not at
    all. Forward and backward against the walk over every pair, bit for bit,
    and against dense attention."""
    from torchft_tpu.ops import flash_attention as fa

    b, sq, sk, h, kv, d, block_q, block_k = 2, 512, 384, 4, 2, 16, 64, 256
    classes = fa._own_classes(sq, sk, block_q, block_q, block_k)
    assert set(classes[:, 1]) == {0, 3, 4} and np.sum(classes == 4) == 2
    q, _, _ = _qkv(b, sq, h, kv, d, seed=51)
    _, k, v = _qkv(b, sk, h, kv, d, seed=52)
    d_out = jax.random.normal(jax.random.PRNGKey(53), q.shape, jnp.float32)
    at_q = jnp.broadcast_to(jnp.arange(sq, dtype=jnp.int32), (b, sq))
    at_k = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32), (b, sk))

    def run(qp, kp):
        out, lse = fa._flash_fwd(q, k, v, d**-0.5, block_q, block_k, True, qp, kp)
        grads = fa.flash_attention_partial_bwd(
            q, k, v, d_out, out, lse.reshape(b, sq, h), qp, kp,
            d**-0.5, block_q, block_k, True,
        )
        return (out, lse, *grads)

    listed, dense = run(None, None), run(at_q, at_k)
    for got, want, what in zip(listed, dense, ("out", "lse", "dq", "dk", "dv")):
        assert np.array_equal(np.asarray(got), np.asarray(want)), what

    def reference(q, k, v):
        qg = q.reshape(b, sq, kv, h // kv, d)
        scores = jnp.einsum("bskgd,btkd->bskgt", qg, k) * d**-0.5
        mask = (at_q[:, :, None] >= at_k[:, None, :])[:, :, None, None, :]
        p = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return jnp.einsum("bskgt,btkd->bskgd", p, v).reshape(b, sq, h, d)

    ref, vjp = jax.vjp(reference, q, k, v)
    np.testing.assert_allclose(np.asarray(listed[0]), np.asarray(ref), atol=2e-5)
    for got, want, what in zip(listed[2:], vjp(d_out), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5, err_msg=what)


def _random_selection(b, s, topk, seed):
    """(b, s, s) int8: ``topk`` keys a query, never one later than it."""
    scores = jax.random.normal(jax.random.PRNGKey(seed), (b, s, s))
    at = jnp.arange(s)
    causal = at[:, None] >= at[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    kth = jnp.sort(scores, axis=-1)[..., -topk][..., None]
    return (causal & (scores >= kth)).astype(jnp.int8)


# name: (s, block_q, block_k), (q heads, kv heads), window, selection's topk,
# q blocks of dq resident in the backward (None: all, one chunk)
_LISTED_CASES = {
    "causal-gqa4": ((512, 64, 128), (4, 1), None, None, None),
    "causal-ragged-300": ((300, 64, 128), (4, 2), None, None, None),
    "window-160": ((512, 64, 128), (4, 2), 160, None, None),
    "window-100-ragged-300": ((300, 64, 128), (4, 2), 100, None, None),
    "window-20-shorter-than-a-block": ((384, 64, 128), (2, 2), 20, None, None),
    "selection-gqa4": ((256, 32, 128), (4, 1), None, 24, None),
    "selection-ragged-200": ((200, 32, 128), (4, 2), None, 24, None),
    "chunked-causal": ((512, 64, 128), (4, 1), None, None, 2),
    "chunked-ragged-600": ((600, 128, 256), (4, 2), None, None, 2),
    "chunked-window-200-ragged-600": ((600, 128, 256), (4, 2), 200, None, 2),
    "chunked-selection": ((384, 32, 128), (4, 2), None, 24, 4),
    # KV blocks of two halves of 128 lanes: the pairs the diagonal, the
    # window's edge or the sequence's end cuts between the halves run the
    # needed half alone in the listed walk, and whole in the dense one.
    "halves-causal-gqa4": ((1024, 128, 256), (4, 1), None, None, None),
    "halves-causal-ragged-900": ((900, 128, 256), (4, 2), None, None, None),
    "halves-window-384": ((1024, 128, 256), (4, 2), 384, None, None),
    "halves-window-300-ragged-900": ((900, 128, 256), (4, 2), 300, None, None),
    "halves-window-50-shorter-than-a-half": ((768, 128, 256), (2, 2), 50, None, None),
    "halves-selection-gqa4": ((512, 64, 256), (4, 1), None, 24, None),
    "halves-selection-ragged-600": ((600, 64, 256), (4, 2), None, 24, None),
    "halves-chunked-selection": ((768, 64, 256), (4, 2), None, 24, 4),
}


@pytest.mark.parametrize("name", list(_LISTED_CASES))
def test_the_listed_walk_equals_the_dense_walk_bit_for_bit(name):
    """out, lse, dq, dk, dv of a call without position arrays (the grid of
    needed pairs) equal, bit for bit, the same call given the sequence's own
    positions as arrays (the grid of every pair): the same pairs in the same
    order into every accumulator, and where the listed walk computes one half
    of a KV block alone, the other half's terms are exact zeros. Causal, under
    a window, under a selection, GQA, ragged lengths, and the backward in
    chunks of q blocks (its VMEM set small); and both match dense attention
    under the same mask."""
    from torchft_tpu.ops import flash_attention as fa

    (s, block_q, block_k), (h, kv), window, topk, resident = _LISTED_CASES[name]
    halves = fa._class_counts(s, s, block_q, block_k, window=window)["halves"]
    assert (halves > 0) == (block_k == 256)
    b, d = 2, 16
    q, k, v = _qkv(b, s, h, kv, d, seed=41)
    d_out = jax.random.normal(jax.random.PRNGKey(42), q.shape, jnp.float32)
    selection = None if topk is None else _random_selection(b, s, topk, seed=43)
    at = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    budget = {}
    if resident is not None:
        tile = (block_q, block_k, d, 4, 4, selection is not None)
        budget["vmem_bytes"] = fa._bwd_vmem_bytes(resident * block_q, *tile)
        assert fa._q_chunks(s, budget["vmem_bytes"], *tile)[0] > 1

    def run(qp, kp):
        out, lse = fa._flash_fwd(
            q, k, v, d**-0.5, block_q, block_k, True, qp, kp,
            selection=selection, window=window,
        )
        grads = fa.flash_attention_partial_bwd(
            q, k, v, d_out, out, lse.reshape(b, s, h), qp, kp,
            d**-0.5, block_q, block_k, True,
            selection=selection, window=window, **budget,
        )
        return (out, lse, *grads)

    def grids(qp, kp):
        return [
            eqn.params["grid_mapping"].grid
            for eqn in jax.make_jaxpr(lambda: run(qp, kp))().eqns
            if eqn.primitive.name == "pallas_call"
        ]

    listed, dense = run(None, None), run(at, at)
    (fwd_listed, bwd_listed), (fwd_dense, bwd_dense) = grids(None, None), grids(at, at)
    assert len(fwd_listed) == 3 and len(fwd_dense) == 4
    assert len(bwd_listed) == 4 and len(bwd_dense) == 5
    assert fwd_listed[2] < fwd_dense[2] * fwd_dense[3]
    for got, want, what in zip(listed, dense, ("out", "lse", "dq", "dk", "dv")):
        assert np.array_equal(np.asarray(got), np.asarray(want)), what

    if selection is not None:
        mask = selection != 0
    else:
        apart = at[:, :, None] - at[:, None, :]
        mask = (apart >= 0) & (apart < (window or s))

    def reference(q, k, v):
        qg = q.reshape(b, s, kv, h // kv, d)
        scores = jnp.einsum("bskgd,btkd->bskgt", qg, k) * d**-0.5
        p = jax.nn.softmax(jnp.where(mask[:, :, None, None, :], scores, -1e30), axis=-1)
        return jnp.einsum("bskgt,btkd->bskgd", p, v).reshape(b, s, h, d)

    ref, vjp = jax.vjp(reference, q, k, v)
    np.testing.assert_allclose(np.asarray(listed[0]), np.asarray(ref), atol=2e-5)
    for got, want, what in zip(listed[2:], vjp(d_out), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5, err_msg=what)
