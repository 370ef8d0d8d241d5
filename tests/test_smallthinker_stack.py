"""models/smallthinker.py's layer stack and attention at a small size (CPU): the
stack scanned by period against the looped one, and a stack of one kind against
the tree it always had (models/decoder.py ``layer_stack``); the window's edge;
the full layers' blindness to positions; and that the window reaches the flash
kernels. The model against the float32 reference is
tests/test_smallthinker_model.py's.

    JAX_PLATFORMS=cpu python -m pytest tests/test_smallthinker_stack.py -q
"""

from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from test_smallthinker_model import flat, program_loss, relative, toy  # noqa: F401
from torchft_tpu.models import decoder
from torchft_tpu.models.smallthinker import Block, SmallThinker, SmallThinkerConfig
from torchft_tpu.ops import attention


def restack(params, period: int, layers: int):
    """The looped tree ``layer_<i>`` from the tree scanned by period."""
    tree = dict(params["params"])
    stack = tree.pop("layers")
    for layer in range(layers):
        tree[f"layer_{layer}"] = jax.tree_util.tree_map(
            lambda a: a[layer // period], stack[f"block_{layer % period}"]
        )
    return {"params": tree}


@pytest.mark.parametrize("remat", ["none", "dots", "full"])
def test_the_stack_scanned_by_period_equals_the_looped_one(remat, toy):
    """One traced period for the whole depth: leaves under
    ``layers/block_<kind>`` with a leading axis of periods; the same numbers as
    eight inlined layers on the same weights, loss and gradient, whatever is
    rematerialised."""
    config, model, params, tokens = toy
    cfg = replace(model.config, remat=remat)
    assert cfg.period == 4 and cfg.n_layers == 8 and cfg.scan_layers
    stack = params["params"]["layers"]
    assert sorted(stack) == ["block_0", "block_1", "block_2", "block_3"]
    assert stack["block_2"]["attn"]["wq"]["kernel"].shape == (2, 64, 14, 32)
    scanned, looped = SmallThinker(cfg), SmallThinker(replace(cfg, scan_layers=False))
    unrolled = restack(params, 4, 8)
    assert jax.tree_util.tree_structure(unrolled) == jax.tree_util.tree_structure(
        jax.eval_shape(lambda: looped.init(jax.random.PRNGKey(0), tokens[:, :-1]))
    )
    loss_a, grad_a = jax.value_and_grad(lambda p: program_loss(scanned, p, tokens))(params)
    loss_b, grad_b = jax.value_and_grad(lambda p: program_loss(looped, p, tokens))(unrolled)
    assert abs(float(loss_a) - float(loss_b)) < 1e-5 * float(loss_b)
    grad_a = flat(restack(grad_a, 4, 8))
    for name, want in flat(grad_b).items():
        assert relative(grad_a[name], want) < 1e-4, name


def test_one_traced_period_serves_the_whole_depth(toy):
    """The scanned program holds a period's four attention calls once, however
    deep the stack; the looped one holds one a layer."""
    _, model, params, tokens = toy

    def softmaxes(cfg):
        text = str(jax.make_jaxpr(
            lambda p: SmallThinker(cfg).apply(p, tokens[:, :-1])
        )(params if cfg.scan_layers else restack(params, 4, 8)))
        return text.count("custom_jvp_call")  # jax.nn.softmax: dense attention, the router

    dense = replace(model.config, attention_impl="dense")
    assert softmaxes(replace(dense, scan_layers=False)) == 2 * softmaxes(dense)


def test_a_stack_of_one_kind_keeps_the_tree_it_had(toy):
    """A layout of one kind is a period of one: ``layers/block`` with a leading
    layer axis, scanned, and ``layer_<i>`` looped, name for name what
    ``layer_stack`` gave before it knew of periods; models/keye.py's and
    models/llama.py's trees are held by their own tests."""
    _, model, _, tokens = toy
    one_kind = replace(model.config, window_layout=(1,) * 8, rope_layout=(1,) * 8)
    assert one_kind.period == 1
    shapes = jax.eval_shape(
        lambda: SmallThinker(one_kind).init(jax.random.PRNGKey(0), tokens[:, :-1])
    )["params"]
    assert sorted(shapes["layers"]) == ["block"]
    assert shapes["layers"]["block"]["moe"]["w_gate"].shape == (8, 4, 64, 48)
    looped = jax.eval_shape(
        lambda: SmallThinker(replace(one_kind, scan_layers=False)).init(
            jax.random.PRNGKey(0), tokens[:, :-1])
    )["params"]
    assert sorted(k for k in looped if k.startswith("layer_")) == [f"layer_{i}" for i in range(8)]
    with pytest.raises(ValueError, match="whole periods"):
        decoder.layer_stack(Block, replace(model.config, n_layers=8), None, None, None, period=3)
    # Periods are found from the layouts: two kinds alternating, and none.
    assert replace(model.config, window_layout=(0, 1) * 4, rope_layout=(0, 1) * 4).period == 2
    assert replace(model.config, rope_layout=(0, 1, 1, 1, 0, 1, 1, 0)).period == 8


def attention_layer(windowed: bool, rotary: bool, window: int = 4096, seq: int = 4200):
    """One attention layer at the published window, narrow and with one head:
    (apply(x, positions), x)."""
    from torchft_tpu.models.smallthinker import Attention

    cfg = SmallThinkerConfig(
        vocab_size=32, dim=16, n_layers=1, n_heads=1, n_kv_heads=1, head_dim=8, moe_hidden=8,
        num_experts=2, experts_per_token=1, num_local_experts=2, window=window,
        window_layout=(int(windowed),), rope_layout=(int(rotary),), dtype=jnp.float32,
        attention_impl="blockwise", attention_block_size=1024,
    )
    layer = Attention(cfg, windowed, rotary)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, seq, 16))
    at = jnp.arange(seq)[None]
    params = layer.init(jax.random.PRNGKey(4), x, at)
    return (lambda x, positions=at: layer.apply(params, x, positions)), x


def test_the_windows_edge_is_4095_in_and_4096_out():
    """Query t of a windowed layer sees key u at ``t - u = 4095`` and not at
    4096: the window counts the query's own position, 4096 keys in all. Moved
    at the edge's two sides, key 100 reaches query 4195 and not query 4196;
    a full layer's query 4196 sees it."""
    apply, x = attention_layer(windowed=True, rotary=True)
    moved = x.at[0, 100].add(1.0)
    change = jnp.linalg.norm(apply(moved) - apply(x), axis=-1)[0]
    assert float(change[100 + 4095]) > 1e-6 and float(change[100]) > 1e-6
    assert float(change[100 + 4096]) == 0.0 and float(jnp.max(change[100 + 4096:])) == 0.0
    assert float(jnp.max(change[:100])) == 0.0
    full, x = attention_layer(windowed=False, rotary=False)
    change = jnp.linalg.norm(full(x.at[0, 100].add(1.0)) - full(x), axis=-1)[0]
    assert float(change[100 + 4096]) > 1e-7 and float(change[-1]) > 1e-7


def test_a_full_layer_is_blind_to_positions_and_a_windowed_one_is_not():
    """No positional encoding where ``rope_layout`` says 0: the layer's output
    does not move when every position shifts, nor when they are scaled; a
    rotary layer keeps to a shift (rotary is relative) and moves with a
    scaling."""
    full, x = attention_layer(windowed=False, rotary=False, window=64, seq=256)
    at = jnp.arange(256)[None]
    assert float(jnp.max(jnp.abs(full(x, at + 1000) - full(x)))) == 0.0
    assert float(jnp.max(jnp.abs(full(x, 3 * at) - full(x)))) == 0.0
    rotary, x = attention_layer(windowed=True, rotary=True, window=64, seq=256)
    assert relative(rotary(x, at + 1000), rotary(x)) < 1e-4
    assert relative(rotary(x, 3 * at), rotary(x)) > 1e-2


def test_the_flash_path_carries_the_window_into_the_kernels(toy, monkeypatch):
    """Steered onto the TPU's choice (``auto`` on a TPU at this length), a
    windowed layer's attention is the flash call with the window and a full
    layer's the call without one."""
    config, model, params, tokens = toy
    seen = []
    real = attention.flash_under_mesh

    def watched(q, k, v, **kwargs):
        seen.append(kwargs.get("window"))
        return real(q, k, v, **kwargs)

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "flash_under_mesh", watched)
    cfg = replace(model.config, attention_impl="auto", blockwise_min_seq=64, scan_layers=False)
    SmallThinker(cfg).apply(restack(params, 4, 8), tokens[:, :-1])
    assert seen == [None, 24, 24, 24] * 2
