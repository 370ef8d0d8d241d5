"""Model family + long-context tests: llama forward/grad, sharding plan on
the virtual 8-device mesh, ring attention vs dense reference."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchft_tpu.models.llama import (
    CONFIGS,
    Llama,
    LlamaConfig,
    apply_sharding_plan,
    cross_entropy_loss,
    sharding_plan,
)
from torchft_tpu.ops.attention import causal_attention, flash_under_mesh
from torchft_tpu.ops.ring_attention import ring_attention_sharded


def test_llama_tiny_forward_and_grad() -> None:
    cfg = CONFIGS["tiny"]
    model = Llama(cfg)
    tokens = jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % cfg.vocab_size
    params = model.init(jax.random.PRNGKey(0), tokens)
    logits = jax.jit(model.apply)(params, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32

    def loss(p):
        return cross_entropy_loss(model.apply(p, tokens), tokens)

    value, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert np.isfinite(float(value))
    # Every param gets a finite gradient.
    for leaf in jax.tree_util.tree_leaves(grads):
        assert np.all(np.isfinite(np.asarray(leaf, dtype=np.float32)))


def test_llama_causal_masking() -> None:
    """Changing future tokens must not change past logits."""
    cfg = CONFIGS["tiny"]
    model = Llama(cfg)
    tokens = jnp.ones((1, 8), dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    logits_a = model.apply(params, tokens)
    tokens_b = tokens.at[0, 6].set(3)
    logits_b = model.apply(params, tokens_b)
    np.testing.assert_allclose(
        np.asarray(logits_a[0, :6]), np.asarray(logits_b[0, :6]), rtol=1e-5
    )
    assert not np.allclose(np.asarray(logits_a[0, 6:]), np.asarray(logits_b[0, 6:]))


def test_gqa_grouping() -> None:
    b, s, h, kv, d = 2, 8, 4, 2, 16
    key = jax.random.PRNGKey(1)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, kv, d), jnp.float32)
    v = jax.random.normal(kv_, (b, s, kv, d), jnp.float32)
    out = causal_attention(q, k, v, d**-0.5)
    assert out.shape == (b, s, h, d)
    # Heads 0,1 share kv head 0: with identical q rows they'd match; with
    # distinct q they must differ from heads 2,3 (kv head 1).
    q_same = jnp.broadcast_to(q[:, :, :1], q.shape)
    out_same = causal_attention(q_same, k, v, d**-0.5)
    np.testing.assert_allclose(out_same[:, :, 0], out_same[:, :, 1], rtol=1e-5)
    assert not np.allclose(out_same[:, :, 0], out_same[:, :, 2])


def test_sharding_plan_applies_on_mesh() -> None:
    cfg = LlamaConfig(
        vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_hidden=128, max_seq_len=64, dtype=jnp.float32,
    )
    model = Llama(cfg)
    tokens = jnp.zeros((1, 16), dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("fsdp", "tp"))
    sharded = apply_sharding_plan(params, mesh, sharding_plan())
    flat = jax.tree_util.tree_flatten_with_path(sharded)[0]
    specs = {
        "/".join(str(getattr(k, "key", k)) for k in path): leaf.sharding.spec
        for path, leaf in flat
    }
    # Column-parallel qkv kernels sharded (fsdp, tp, None).
    wq = next(spec for name, spec in specs.items() if "wq/kernel" in name)
    assert wq == P("fsdp", "tp", None)
    # Norm scales replicated.
    norm = next(spec for name, spec in specs.items() if "scale" in name)
    assert norm == P()
    # Forward still runs under jit with sharded params.
    with mesh:
        logits = jax.jit(model.apply)(sharded, tokens)
    assert logits.shape == (1, 16, cfg.vocab_size)


@pytest.mark.parametrize("sp_size", [2, 4])
def test_ring_attention_matches_dense(sp_size: int) -> None:
    b, s, h, kv, d = 2, 32, 4, 2, 16
    key = jax.random.PRNGKey(2)
    kq, kk, kvk = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, kv, d), jnp.float32)
    v = jax.random.normal(kvk, (b, s, kv, d), jnp.float32)

    dense = causal_attention(q, k, v, d**-0.5)

    mesh = Mesh(np.array(jax.devices()[:sp_size]), ("sp",))
    ring = ring_attention_sharded(q, k, v, mesh, axis_name="sp", scale=d**-0.5)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense), rtol=2e-4, atol=2e-5)


def test_llama_auto_ring_attention_under_sp_mesh() -> None:
    """With an sp axis in the mesh, the model's attention goes through the
    ring path and matches the dense single-device result."""
    cfg = LlamaConfig(
        vocab_size=128, dim=32, n_layers=1, n_heads=4, n_kv_heads=2,
        ffn_hidden=64, max_seq_len=64, dtype=jnp.float32,
    )
    model = Llama(cfg)
    tokens = (jnp.arange(32, dtype=jnp.int32) % cfg.vocab_size).reshape(1, 32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    dense_logits = model.apply(params, tokens)

    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    from jax import shard_map

    def fwd(p, t, pos):
        return model.apply(p, t, pos)

    positions = jnp.broadcast_to(jnp.arange(32), (1, 32))
    sharded_fwd = shard_map(
        fwd,
        mesh=mesh,
        in_specs=(P(), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
            )
    with mesh:
        ring_logits = sharded_fwd(params, tokens, positions)
    np.testing.assert_allclose(
        np.asarray(ring_logits), np.asarray(dense_logits), rtol=2e-4, atol=2e-4
    )


def test_llama_ring_impl_without_bound_axis_fails_loudly() -> None:
    """attention_impl='ring' outside shard_map must raise (unbound axis
    name) at trace time — never silently compute per-shard local attention.
    A legacy ``with mesh:`` block does NOT bind the collective axis, so it
    must fail the same way; sp detection reads only public jax.sharding
    APIs."""
    cfg = LlamaConfig(
        vocab_size=128, dim=32, n_layers=1, n_heads=4, n_kv_heads=2,
        ffn_hidden=64, max_seq_len=64, dtype=jnp.float32,
        attention_impl="ring",
    )
    model = Llama(cfg)
    tokens = (jnp.arange(32, dtype=jnp.int32) % cfg.vocab_size).reshape(1, 32)
    auto_model = Llama(LlamaConfig(
        vocab_size=128, dim=32, n_layers=1, n_heads=4, n_kv_heads=2,
        ffn_hidden=64, max_seq_len=64, dtype=jnp.float32,
    ))
    params = auto_model.init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(NameError, match="axis name"):
        model.apply(params, tokens)
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    with mesh, pytest.raises(NameError, match="axis name"):
        model.apply(params, tokens)
    # And auto under a bare legacy with-mesh picks a non-ring impl instead
    # of crashing: finishing without error is the assertion.
    with mesh:
        auto_model.apply(params, tokens)


def test_ring_attention_gradients_match_dense() -> None:
    """Training through ring attention: reverse-mode through the
    fori_loop + ppermute ring must match dense attention gradients.

    sp=2 (like the zigzag gradient test): the reverse-mode shard_map
    compile grows with ring hops and dominated suite time at sp=4; two
    hops already exercise every backward mechanism, and sp=4 forward
    coverage lives in test_ring_attention_matches_dense and the sp-mesh
    Llama tests."""
    b, s, h, kv, d = 2, 32, 4, 2, 16
    key = jax.random.PRNGKey(0)
    kq, kk, kvk = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, kv, d), jnp.float32)
    v = jax.random.normal(kvk, (b, s, kv, d), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh, scale=d**-0.5) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(causal_attention(q, k, v, d**-0.5) ** 2)

    # Jitted, as a train step runs it: un-jitted, reverse mode compiles the
    # shard_map program piece by piece (~10x the wall time, nothing gained).
    grads_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    grads_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for ring_grad, dense_grad in zip(grads_ring, grads_dense):
        np.testing.assert_allclose(
            np.asarray(ring_grad), np.asarray(dense_grad), rtol=3e-4, atol=3e-5
        )


def test_ring_attention_fully_masked_rows_are_zero() -> None:
    """A query row positioned before every key (packed padding) must output
    exactly 0, not mean(V) — regardless of ring layout / causal skipping."""
    from jax import shard_map

    from torchft_tpu.ops.ring_attention import ring_attention

    b, s, h, kv, d = 1, 16, 2, 1, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kv, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kv, d), jnp.float32)
    qpos = jnp.broadcast_to(jnp.arange(s), (b, s)).at[0, 0].set(-100)
    kpos = jnp.broadcast_to(jnp.arange(s), (b, s))
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    spec = P(None, "sp", None, None)
    fn = shard_map(
        lambda q_, k_, v_, qp, kp: ring_attention(
            q_, k_, v_, "sp", scale=d**-0.5, q_positions=qp, k_positions=kp
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec, P(None, "sp"), P(None, "sp")),
        out_specs=spec,
    )
    out = np.asarray(fn(q, k, v, qpos, kpos))
    assert np.all(out[0, 0] == 0.0)
    assert not np.all(out[0, 1] == 0.0)


def test_ring_attention_zigzag_matches_dense() -> None:
    """Load-balanced zigzag layout: natural-order inputs/outputs, balanced
    causal work per device, numerics identical to dense."""
    from torchft_tpu.ops.ring_attention import ring_attention_zigzag, zigzag_permutation

    b, s, h, kv, d = 2, 64, 4, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, s, kv, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, s, kv, d), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    zz = ring_attention_zigzag(q, k, v, mesh, scale=d**-0.5)
    dense = causal_attention(q, k, v, d**-0.5)
    np.testing.assert_allclose(np.asarray(zz), np.asarray(dense), rtol=3e-4, atol=3e-5)

    # Per-(q,kv) sub-chunk relevance counts are balanced across devices.
    sp = 4
    perm, inv = zigzag_permutation(s, sp)
    assert sorted(perm[inv].tolist()) == list(range(s))
    shard, half = s // sp, s // sp // 2
    counts = []
    for dev in range(sp):
        c = 0
        for qi in range(2):
            q_max = perm[dev * shard + qi * half : dev * shard + (qi + 1) * half].max()
            for src in range(sp):
                for ki in range(2):
                    lo = src * shard + ki * half
                    if perm[lo : lo + half].min() <= q_max:
                        c += 1
        counts.append(c)
    assert max(counts) - min(counts) <= 1, counts

    with pytest.raises(ValueError, match="divide"):
        zigzag_permutation(30, 4)


def test_ring_attention_zigzag_gradients_match_dense() -> None:
    """The balanced layout's backward pass (cond + sliced accumulators
    inside fori_loop) must match dense gradients.

    sp=2 deliberately: the reverse-mode shard_map program's compile time
    grows with ring hops and dominated the suite at sp=4 (~50s); two hops
    already exercise every backward mechanism (cond branches, sliced
    accumulators, the permuted layout), and the sp=4 forward is covered by
    test_ring_attention_zigzag_matches_dense."""
    from torchft_tpu.ops.ring_attention import ring_attention_zigzag

    b, s, h, kv, d = 2, 32, 4, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(keys[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, s, kv, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, s, kv, d), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))

    def loss_zz(q, k, v):
        return jnp.sum(ring_attention_zigzag(q, k, v, mesh, scale=d**-0.5) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(causal_attention(q, k, v, d**-0.5) ** 2)

    # Jitted for the same reason as test_ring_attention_gradients_match_dense.
    gz = jax.jit(jax.grad(loss_zz, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gz, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-5)


def test_blockwise_attention_matches_dense() -> None:
    """blockwise_attention (lax.scan over KV blocks, online softmax) is
    numerically equivalent to dense causal attention — forward and grad —
    including non-block-multiple sequence lengths and GQA."""
    from torchft_tpu.ops.ring_attention import blockwise_attention

    # ONE case carrying every property at once (GQA h != kv AND a
    # non-block-multiple sequence): the second shape only re-compiled the
    # same fwd+vjp programs for ~7s of suite time with no new mechanism.
    for (b, s, h, kv, d, blk) in [(2, 100, 4, 2, 16, 32)]:
        kq, kk, kvk = jax.random.split(jax.random.PRNGKey(s), 3)
        q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
        k = jax.random.normal(kk, (b, s, kv, d), jnp.float32)
        v = jax.random.normal(kvk, (b, s, kv, d), jnp.float32)
        dense = causal_attention(q, k, v, d**-0.5)
        block = blockwise_attention(q, k, v, block_size=blk)
        np.testing.assert_allclose(
            np.asarray(block), np.asarray(dense), rtol=2e-5, atol=2e-5
        )
        # All three gradients (the custom_vjp backward recomputes blocks).
        weights = jnp.cos(jnp.arange(d))
        g_dense = jax.grad(
            lambda q, k, v: (causal_attention(q, k, v, d**-0.5) * weights).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_block = jax.grad(
            lambda q, k, v: (
                blockwise_attention(q, k, v, block_size=blk) * weights
            ).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for dense_grad, block_grad, name in zip(g_dense, g_block, "qkv"):
            np.testing.assert_allclose(
                np.asarray(block_grad),
                np.asarray(dense_grad),
                rtol=3e-4,
                atol=3e-5,
                err_msg=f"d{name}",
            )
        with pytest.raises(ValueError, match="attention_impl"):
            from torchft_tpu.models.llama import LlamaConfig

            LlamaConfig(attention_impl="flashiest")


def test_llama_blockwise_impl_matches_dense_model() -> None:
    """The model under attention_impl='blockwise' produces the same logits
    as 'dense' (same params), and 'auto' flips to blockwise past
    blockwise_min_seq."""
    from torchft_tpu.models.llama import Llama, LlamaConfig

    base = dict(
        vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_hidden=64, max_seq_len=96, dtype=jnp.float32,
        attention_block_size=32,
    )
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 96), 0, 128)
    dense_model = Llama(LlamaConfig(**base, attention_impl="dense"))
    params = dense_model.init(jax.random.PRNGKey(1), tokens)
    dense_logits = dense_model.apply(params, tokens)
    block_model = Llama(LlamaConfig(**base, attention_impl="blockwise"))
    block_logits = block_model.apply(params, tokens)
    np.testing.assert_allclose(
        np.asarray(block_logits), np.asarray(dense_logits), rtol=3e-4, atol=3e-4
    )
    auto_model = Llama(
        LlamaConfig(**base, attention_impl="auto", blockwise_min_seq=64)
    )
    auto_logits = auto_model.apply(params, tokens)
    np.testing.assert_array_equal(
        np.asarray(auto_logits), np.asarray(block_logits)
    )


@pytest.mark.parametrize(
    "overrides",
    [
        {},
        # The combination every chip configuration runs (chipbench's cells,
        # chip_smoke.py): the Pallas kernel (interpreted off-TPU) inside a
        # remat'd scan cell, where "dots" also keeps the kernel's named
        # (out, lse).
        {"attention_impl": "flash", "scan_layers": True,
         "attention_block_size": 16},
    ],
    ids=["dense-loop", "flash-scan"],
)
def test_llama_remat_matches_baseline(overrides) -> None:
    """remat='full'/'dots' change only the backward's memory/recompute
    schedule: same params, logits AND gradients must match the unremat
    model (allclose; fp32 tiny config)."""
    cfg = replace(CONFIGS["tiny"], **overrides)
    tokens = jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % cfg.vocab_size
    base = Llama(cfg)
    params = base.init(jax.random.PRNGKey(0), tokens)

    def loss(model):
        return lambda p: cross_entropy_loss(model.apply(p, tokens), tokens)

    v0, g0 = jax.jit(jax.value_and_grad(loss(base)))(params)
    for mode in ("full", "dots"):
        model = Llama(replace(cfg, remat=mode))
        v1, g1 = jax.jit(jax.value_and_grad(loss(model)))(params)
        np.testing.assert_allclose(float(v1), float(v0), rtol=1e-6)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            ),
            g1, g0,
        )


@pytest.mark.parametrize("attention_impl", ["dense", "blockwise"])
@pytest.mark.parametrize("scan_layers", [False, True], ids=["loop", "scan"])
def test_dots_remat_without_flash_is_plain_checkpoint_dots(
    attention_impl, scan_layers, monkeypatch
) -> None:
    """The bypass: "dots" adds the flash kernel's two names to
    checkpoint_dots, and dense / blockwise attention never produce such a
    name — their gradient lowers to the same program text as under plain
    checkpoint_dots."""
    from torchft_tpu.models import llama

    cfg = replace(
        CONFIGS["tiny"], attention_impl=attention_impl,
        attention_block_size=8, scan_layers=scan_layers, remat="dots",
    )
    tokens = jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % cfg.vocab_size
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(0), tokens)

    def lowered() -> str:
        grad = jax.grad(
            lambda p: cross_entropy_loss(model.apply(p, tokens), tokens)
        )
        return jax.jit(grad).lower(params).as_text()

    with_names = lowered()
    monkeypatch.setattr(llama, "remat_policy", lambda remat, dots, *names: dots)
    assert lowered() == with_names


def test_llama_scan_layers_matches_loop() -> None:
    """scan_layers=True is the same function: stacking the loop model's
    per-layer params into the scan layout reproduces its logits exactly,
    and gradients through the scanned stack are finite."""
    cfg = CONFIGS["tiny"]
    tokens = jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % cfg.vocab_size
    loop_model = Llama(cfg)
    loop_params = loop_model.init(jax.random.PRNGKey(0), tokens)

    p = dict(loop_params["params"])
    layers = [p.pop(f"layer_{i}") for i in range(cfg.n_layers)]
    p["layers"] = {
        "block": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    }
    scan_cfg = replace(cfg, scan_layers=True)
    scan_model = Llama(scan_cfg)
    scan_params = {"params": p}

    loop_logits = loop_model.apply(loop_params, tokens)
    scan_logits = scan_model.apply(scan_params, tokens)
    np.testing.assert_allclose(
        np.asarray(scan_logits), np.asarray(loop_logits), rtol=2e-5, atol=2e-5
    )

    # Fresh init has the scanned structure; remat composes under the scan.
    remat_cfg = replace(cfg, scan_layers=True, remat="dots")
    remat_model = Llama(remat_cfg)
    fresh = remat_model.init(jax.random.PRNGKey(1), tokens)
    wq = fresh["params"]["layers"]["block"]["attn"]["wq"]["kernel"]
    assert wq.shape[0] == cfg.n_layers

    def loss(p):
        return cross_entropy_loss(remat_model.apply(p, tokens), tokens)

    value, grads = jax.jit(jax.value_and_grad(loss))(fresh)
    assert np.isfinite(float(value))
    for leaf in jax.tree_util.tree_leaves(grads):
        assert np.all(np.isfinite(np.asarray(leaf, dtype=np.float32)))


def test_sharding_plan_applies_to_scanned_params() -> None:
    """The plan's per-layer specs shift right over the scanned stack's
    leading layer axis (replicated) and the forward still jits."""
    cfg = LlamaConfig(
        vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_hidden=128, max_seq_len=64, dtype=jnp.float32, scan_layers=True,
    )
    model = Llama(cfg)
    tokens = jnp.zeros((1, 16), dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("fsdp", "tp"))
    sharded = apply_sharding_plan(params, mesh, sharding_plan())
    wq = sharded["params"]["layers"]["block"]["attn"]["wq"]["kernel"]
    assert wq.sharding.spec == P(None, "fsdp", "tp", None)
    scale = sharded["params"]["layers"]["block"]["attn_norm"]["scale"]
    assert scale.sharding.spec == P()
    with mesh:
        logits = jax.jit(model.apply)(sharded, tokens)
    assert logits.shape == (1, 16, cfg.vocab_size)


def test_all_fit_levers_compose_in_one_step() -> None:
    """scan_layers + dots-remat + fused CE + microbatch accumulation in a
    single jitted train step over the fsdp/tp mesh — the full 70B-class
    composition. Loss/grads stay finite and the update step runs; each
    lever alone is equivalence-tested elsewhere, this guards the
    cross-feature interactions (remat inside scan inside microbatch scan,
    custom-VJP CE under sharding)."""
    import optax

    from torchft_tpu.models.llama import apply_sharding_plan

    cfg = replace(
        CONFIGS["tiny"],
        scan_layers=True,
        remat="dots",
        loss_vocab_chunk=128,
    )
    model = Llama(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 17), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), tokens[:, :-1])
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("fsdp", "tp"))
    params = apply_sharding_plan(params, mesh, sharding_plan())

    def loss_fn(p, batch):
        return model.apply(p, batch[:, :-1], targets=batch[:, 1:])

    # The shipped fused step (Optimizer/LocalSGD's production path), not a
    # test-local variant.
    from torchft_tpu.optim import make_jit_fused_step

    tx = optax.adamw(1e-3)
    step = make_jit_fused_step(tx, loss_fn, num_microbatches=2)
    opt_state = tx.init(params)

    with mesh:
        loss, new_params, _ = step(params, opt_state, tokens)
    assert np.isfinite(float(loss))
    for leaf in jax.tree_util.tree_leaves(new_params):
        assert np.all(np.isfinite(np.asarray(leaf, dtype=np.float32)))

    # Cross-check: the microbatched loss the step returned equals the
    # full-batch fused loss (equal chunks -> mean-of-means == mean).
    full_loss = model.apply(params, tokens[:, :-1], targets=tokens[:, 1:])
    np.testing.assert_allclose(float(loss), float(full_loss), rtol=1e-5)


def _selected_attention_oracle(q, k, v, selection, scale):
    """Dense attention over the keys ``selection`` (b, s, s) marks."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    scores = jnp.einsum("bskgd,btkd->bkgst", q.reshape(b, s, kv, h // kv, d), k) * scale
    scores = jnp.where(selection[:, None, None] != 0, scores, -jnp.inf)
    out = jnp.einsum("bkgst,btkd->bskgd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(b, s, h, d)


@pytest.mark.parametrize("selected", [False, True], ids=["causal", "selection"])
def test_flash_shard_maps_itself_under_ambient_mesh(selected):
    """Under a bound mesh (jax.set_mesh — the sharded-train-step context)
    the flash dispatcher must shard_map the Pallas kernel over the
    batch/head axes itself: XLA SPMD refuses to partition Mosaic custom
    calls, so the bare kernel call fails to lower inside jit-with-mesh
    (test_mosaic_lowering.py's 8B gate pins the lowering half; this test
    pins numerics — the mapped kernel must match dense attention
    exactly where each (batch, head) shard computes independently). A
    selection of keys, one (b, s, s) operand for all heads, goes with the
    batch."""
    b, s, h, kv, d = 4, 128, 4, 2, 64
    kq, kk, kvk, ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, kv, d), jnp.float32)
    v = jax.random.normal(kvk, (b, s, kv, d), jnp.float32)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def selection_for(rows):
        """Half the earlier keys at random, and always the query's own."""
        if not selected:
            return None
        some = jax.random.bernoulli(ks, 0.5, (rows, s, s)) | jnp.eye(s, dtype=bool)
        return (some & causal).astype(jnp.int8)

    def dispatched(q, k, v, selection):
        with jax.set_mesh(jax.make_mesh((4, 2), ("fsdp", "tp"))):
            return jax.jit(
                lambda *qkv, selection: flash_under_mesh(
                    *qkv, scale=d**-0.5, selection=selection,
                    batch_axes=("dp", "fsdp"), tp_axis="tp",
                )
            )(q, k, v, selection=selection)

    def oracle(q, k, v, selection):
        if selection is None:
            return causal_attention(q, k, v, scale=d**-0.5)
        return _selected_attention_oracle(q, k, v, selection, d**-0.5)

    chosen = selection_for(b)
    np.testing.assert_allclose(
        np.asarray(dispatched(q, k, v, chosen)), np.asarray(oracle(q, k, v, chosen)),
        rtol=2e-5, atol=2e-5,
    )

    # Non-dividing dims must still compute correctly: the axes stay
    # manual (a bare pallas_call under the mesh is the lowering error
    # this wrapper avoids) but drop out of the specs, replicating the
    # kernel over them — 3 batch rows over fsdp=4 and 3 q-heads over
    # tp=2.
    q3 = jax.random.normal(kq, (3, s, 3, d), jnp.float32)
    k3 = jax.random.normal(kk, (3, s, 3, d), jnp.float32)
    chosen3 = selection_for(3)
    np.testing.assert_allclose(
        np.asarray(dispatched(q3, k3, k3, chosen3)), np.asarray(oracle(q3, k3, k3, chosen3)),
        rtol=2e-5, atol=2e-5,
    )


def test_flash_mesh_fallback_keeps_largest_dividing_subset(caplog):
    """The non-dividing batch fallback is per-axis: batch 2 on a
    dp=2 x fsdp=2 mesh keeps dp sharded (product 4 does not divide, dp=2
    does) instead of replicating over both, and the drop to replication
    over fsdp logs a once-per-shape warning."""
    import logging as _logging

    from torchft_tpu.ops import attention

    s, h, kv, d = 128, 4, 2, 64

    def dispatch(q, k, v):
        return flash_under_mesh(
            q, k, v, scale=d**-0.5, batch_axes=("dp", "fsdp"), tp_axis="tp"
        )

    kq, kk, kvk = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (2, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (2, s, kv, d), jnp.float32)
    v = jax.random.normal(kvk, (2, s, kv, d), jnp.float32)

    attention._FLASH_REPLICATION_WARNED.clear()
    mesh = jax.make_mesh((2, 2, 2), ("dp", "fsdp", "tp"))
    with caplog.at_level(_logging.WARNING, logger="torchft_tpu.ops.attention"):
        with jax.set_mesh(mesh):
            out = jax.jit(dispatch)(q, k, v)
            # Same shape again: the warning must not repeat.
            jax.jit(lambda q, k, v: dispatch(q, k, v))(q, k, v)
    ref = causal_attention(q, k, v, scale=d**-0.5)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    warnings = [r for r in caplog.records if "replicates its compute" in r.message]
    assert len(warnings) == 1, [r.message for r in caplog.records]
    assert "fsdp=2" in warnings[0].message


def test_largest_dividing_subset_selection():
    """The pure fallback helper: keeps the max-shard-count dividing subset
    in spec order; all-or-nothing only when nothing divides."""
    from torchft_tpu.ops.attention import largest_dividing_subset

    sizes = {"dp": 2, "fsdp": 4}
    assert largest_dividing_subset(("dp", "fsdp"), sizes, 8) == ("dp", "fsdp")
    assert largest_dividing_subset(("dp", "fsdp"), sizes, 4) == ("fsdp",)
    assert largest_dividing_subset(("dp", "fsdp"), sizes, 2) == ("dp",)
    assert largest_dividing_subset(("dp", "fsdp"), sizes, 3) == ()
    # Ties prefer more axes (finer layout): 4 rows on 2x2 -> both axes.
    assert largest_dividing_subset(
        ("dp", "fsdp"), {"dp": 2, "fsdp": 2}, 4
    ) == ("dp", "fsdp")
    # Order in the result is spec order regardless of subset enumeration.
    assert largest_dividing_subset(
        ("a", "b", "c"), {"a": 3, "b": 2, "c": 2}, 12
    ) == ("a", "b", "c")


def test_flash_dispatcher_is_inert_inside_callers_shard_map():
    """Inside a caller's shard_map the fsdp/tp axes are Manual and shapes
    are already per-shard local: the dispatcher must use the plain kernel
    call (a nested map over local shapes would mis-divide them — caught
    by comparing AxisType.Manual, which its first version string-compared
    wrong)."""
    b, s, h, kv, d = 8, 128, 4, 2, 64
    kq, kk, kvk = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, kv, d), jnp.float32)
    v = jax.random.normal(kvk, (b, s, kv, d), jnp.float32)

    mesh = jax.make_mesh((4, 2), ("fsdp", "tp"))
    # kv heads shard over tp like q heads — splitting only q heads would
    # break the GLOBAL GQA pairing inside each shard (the dispatcher's
    # own mapped path uses the same paired layout for exactly this
    # reason).
    spec = P("fsdp", None, "tp", None)
    out = jax.jit(
        jax.shard_map(
            lambda q, k, v: flash_under_mesh(q, k, v, scale=d**-0.5),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
    )(q, k, v)
    # Each (batch, head) shard attends independently over the full local
    # sequence, so the mapped result equals unsharded dense attention.
    ref = causal_attention(q, k, v, scale=d**-0.5)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


# ---------------------------------------------------------------------------
# models/decoder.py: the seam a new architecture is written against
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scan_layers", [False, True], ids=["loop", "scan"])
def test_llama_parameter_tree_is_the_golden(scan_layers, golden_param_tree) -> None:
    """As the model made it before the stack, norm and head were
    models/decoder.py's (tests/conftest.py ``golden_param_tree``)."""
    model = Llama(replace(CONFIGS["tiny"], scan_layers=scan_layers))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 64), jnp.int32))
    golden_param_tree("llama-scan" if scan_layers else "llama-loop", params)


@pytest.mark.parametrize("remat", ["none", "dots", "full"])
@pytest.mark.parametrize("scan_layers", [False, True], ids=["loop", "scan"])
def test_a_block_defined_here_runs_through_layer_stack(scan_layers, remat) -> None:
    """What a new architecture writes is its block: ``layer_stack`` gives a
    three-line block the scanned or looped, rematerialised stack, its leaves
    under ``layers/block/`` with a leading layer axis or under ``layer_<i>/``,
    and the same function either way."""
    from dataclasses import dataclass

    import flax.linen as nn

    from torchft_tpu.models.decoder import layer_stack, remat_policy

    @dataclass(frozen=True)
    class Config:
        n_layers: int = 3
        scan_layers: bool = False
        remat: str = "none"

    class Block(nn.Module):
        config: Config

        @nn.compact
        def __call__(self, x, positions):
            return x + nn.Dense(x.shape[-1], name="mix")(jnp.tanh(x)) * positions[..., None]

    class Model(nn.Module):
        config: Config

        @nn.compact
        def __call__(self, x, positions):
            policy = remat_policy(self.config.remat, jax.checkpoint_policies.checkpoint_dots)
            return layer_stack(Block, self.config, policy, x, positions)

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 8))
    positions = jnp.broadcast_to(jnp.arange(5) / 5.0, (2, 5))
    model = Model(Config(scan_layers=scan_layers, remat=remat))
    params = model.init(jax.random.PRNGKey(0), x, positions)
    shapes = {
        "/".join(str(k.key) for k in path): list(leaf.shape)
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
    }
    if scan_layers:
        assert shapes == {
            "params/layers/block/mix/kernel": [3, 8, 8], "params/layers/block/mix/bias": [3, 8],
        }
    else:
        assert shapes == {
            f"params/layer_{i}/mix/{leaf}": shape
            for i in range(3) for leaf, shape in (("kernel", [8, 8]), ("bias", [8]))
        }

    def value_and_grads(model, params):
        def loss(p):
            return jnp.sum(model.apply(p, x, positions) ** 2)

        return jax.jit(jax.value_and_grad(loss))(params)

    def looped(tree):
        """A scanned stack's tree in the looped stack's layout."""
        if not scan_layers:
            return tree
        stacked = tree["params"]["layers"]["block"]
        return {"params": {
            f"layer_{i}": jax.tree_util.tree_map(lambda a: a[i], stacked) for i in range(3)
        }}

    # The plain looped stack over the same weights is the same function.
    value, grads = value_and_grads(model, params)
    want, want_grads = value_and_grads(Model(Config()), looped(params))
    np.testing.assert_allclose(float(value), float(want), rtol=1e-6)
    for got, wanted in zip(*map(jax.tree_util.tree_leaves, (looped(grads), want_grads))):
        assert float(jnp.linalg.norm(wanted)) > 0
        np.testing.assert_allclose(np.asarray(got), np.asarray(wanted), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# The four models a loop over the stack does not concern (PR 62)
# ---------------------------------------------------------------------------

# model file -> (configuration, rehearsal overlay, sha256 of the seeded tree,
# sha256 of the jaxpr of the loss and its gradient), as commit 712ab83 (the
# parent of PR 62: no ``looped_stack``, no loss by token) made them; the
# PROGRAMS of keye and smallthinker as PR 63 made them (models/experts.py
# ``route`` names what it hands the layer: six ``name`` identities more in
# keye's jaxpr and no other primitive, its compiled step the parent's to the
# instruction; smallthinker's ``dots`` keeps more), their trees as before.
_UNLOOPED = {
    "llama": (
        "mistral-7b-v0.3-1chip", "rehearsal.json",
        "e18ffe1f23a55721232fec596784d8feae9b7ce01394ddeaf6d46d34eee1014a",
        "ff9db91479f3b49aa3bb1a579e5c266f3442f8bf5f7af16e74648df3dfa8e991",
    ),
    "keye": (
        "keye-vl2-30b-a3b-ep8-1chip", "rehearsal-keye.json",
        "1c29e353da905d45f9faf339e3523ac623284158c2a6c85a692f3fb92b6f47bf",
        "aad318734db84e0ba1424a775ecfa11f8c0c4549466db5cc5e28b547a5bc1eea",
    ),
    "smallthinker": (
        "smallthinker-21b-a3b-ep8-1chip", "rehearsal-smallthinker.json",
        "cefe89df40d01b356f0b994ca5b718b436dd434db87198adf16f901df90a840b",
        "a86d934e83c51297f4a2ccc2a154d0c17a86961b32298c4d2bfec8c885e10fc0",
    ),
    "granite": (
        "granite-4.0-h-micro-1chip", "rehearsal-granite.json",
        "5ff201b99d949a9c09bed1c7a99b82ebb6d13f50e3f6ceb06607d7338b9af6e0",
        "7b99f6045539718fffb0f96c2bbef019078cdc4d912535f6e16f9db3f83ebc3b",
    ),
}


@pytest.fixture(scope="module")
def unlooped_digests():
    """{model: (tree digest, program digest)} of the four models at their
    cells' rehearsal sizes, scanned as the cells run them, each traced once."""
    import hashlib
    import json
    import re
    from pathlib import Path

    from chipbench import spec

    root = Path(__file__).resolve().parents[1]
    found = {}
    for name, (config_name, overlay_name, _, _) in _UNLOOPED.items():
        config = json.loads((root / f"chipbench/configs/{config_name}.json").read_text())
        overlay = json.loads((root / f"chipbench/fixtures/{overlay_name}").read_text())
        config = {**config, **overlay["config"]}
        config["run"] = {**config["run"], **overlay["run"]}
        architecture = spec.load_module(root / f"chipbench/architectures/{config['model_type']}.py")
        model = architecture.build(config, 64)
        tokens = jnp.zeros((1, 65), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])
        tree = hashlib.sha256()
        for path, leaf in jax.tree_util.tree_leaves_with_path(params):
            tree.update(("/".join(str(k.key) for k in path) + str(leaf.shape) + str(leaf.dtype)).encode())
            tree.update(jax.device_get(leaf).tobytes())
        traced = jax.make_jaxpr(jax.value_and_grad(
            lambda p: model.apply(p, tokens[:, :-1], targets=tokens[:, 1:])
        ))(params)
        text = re.sub(r" at 0x[0-9a-f]+", "", str(traced))
        found[name] = (tree.hexdigest(), hashlib.sha256(text.encode()).hexdigest())
    return found


@pytest.mark.parametrize("what", ["tree", "program"])
@pytest.mark.parametrize("name", sorted(_UNLOOPED))
def test_a_model_whose_stack_runs_once_is_what_it_was_before_the_loop(name, what, unlooped_digests) -> None:
    """``layer_stack``, the head and the fused mean loss serve the four older
    models as they did: the same seeded leaves under the same paths, and the
    same traced loss and gradient, op for op (on the chip their lowered step
    programs were compared whole, parent against change: PERF.md, PR 62)."""
    golden = _UNLOOPED[name][2:]
    index = ["tree", "program"].index(what)
    assert unlooped_digests[name][index] == golden[index]
