"""models/granite.py against plain float32 mathematics, at a small size on
seeded weights (CPU): the model against the benchmark's float32 reference
(chipbench/architectures/granitemoehybrid.py, written from the recurrence's
closed form and sharing nothing with ops/ssd.py), loss and every leaf's
gradient; that each of the four multipliers, the gate's place before the norm,
the absence of positions and the attention's own scale is really in the
program; which layer of a period attends; the tied matrix's two uses; and the
sliced vocabulary.

    JAX_PLATFORMS=cpu python -m pytest tests/test_granite_model.py -q
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import reference, spec  # noqa: E402
from torchft_tpu.models.decoder import tied_head  # noqa: E402
from torchft_tpu.models.granite import Block, Granite, GraniteConfig, chunk_log_decay  # noqa: E402

ARCHITECTURE = spec.load_module(ROOT / "chipbench/architectures/granitemoehybrid.py")
SEQ = 96  # six chunks of 16, three blocks of the reference's head
# Float32 on both sides: they differ in the order of their sums.
TOLERANCE = 1e-5


def toy_config(**run) -> dict:
    """The cell's configuration file under its rehearsal overlay: every key the
    architecture file reads, at a toy size (one period of ten layers, 8 Mamba
    heads of 16 with a state of 16 in chunks of 16, 8 / 2 attention heads of 8
    at a scale that is not 8^-0.5)."""
    config = json.loads((ROOT / "chipbench/configs/granite-4.0-h-micro-1chip.json").read_text())
    overlay = json.loads((ROOT / "chipbench/fixtures/rehearsal-granite.json").read_text())
    config = {**config, **overlay["config"]}
    config["run"] = {**config["run"], **overlay["run"], **run}
    return config


def seeded(config):
    model = ARCHITECTURE.build(config, SEQ)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, SEQ + 1), 0, config["vocab_size"])
    params = jax.jit(model.init)(jax.random.PRNGKey(3), tokens[:, :-1])
    return config, model, params, tokens


@pytest.fixture(scope="module")
def toy():
    return seeded(toy_config())


@pytest.fixture(scope="module")
def short():
    """Three layers, Mamba-2, attention, Mamba-2 (a period of three), for what
    does not turn on the period of ten: a third of the program to compile."""
    return seeded({**toy_config(), "num_hidden_layers": 3, "layer_types": ["mamba", "attention", "mamba"]})


@pytest.fixture(autouse=True)
def small_reference_blocks(monkeypatch):
    """A toy sequence is still three blocks of the reference's head and
    attention and forty-eight of its scan."""
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)
    monkeypatch.setattr(reference, "HEAD_BLOCK", 32)


def program_loss(model, params, tokens):
    return model.apply(params, tokens[:, :-1], targets=tokens[:, 1:])


def relative(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def flat(tree) -> dict:
    return {
        "/".join(str(getattr(k, "key", k)) for k in path): leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


def reference_loss(config, params, tokens):
    count = tokens.shape[0] * (tokens.shape[1] - 1)
    with jax.default_matmul_precision("highest"):
        return sum(ARCHITECTURE.sequence_loss(params, seq, config) for seq in tokens) / count


def test_loss_and_every_leafs_gradient_agree_with_the_float32_reference(toy):
    """The chunked scan, its autodiff backward, the convolution, the gated
    norm, the attention layer and the tied head against the reference's closed
    form. Tolerances: 1e-5 of the loss and 1e-4 of a leaf's gradient's norm,
    float32 on both sides summing in another order. And the tied matrix's
    gradient has both its parts: the head's reaches every row, and the rows the
    batch gathers carry the gather's on top."""
    config, model, params, tokens = toy
    tokens = tokens[:1]  # one sequence: the reference writes each out, layer by layer
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: reference_loss(config, p, tokens)))(params)
    with jax.default_matmul_precision("highest"):
        got_loss, got = jax.jit(jax.value_and_grad(lambda p: program_loss(model, p, tokens)))(params)
    assert abs(float(got_loss) - float(want_loss)) / float(want_loss) < TOLERANCE
    rows = jnp.linalg.norm(got["params"]["tok_embed"]["embedding"], axis=-1)
    gathered = jnp.zeros(config["vocab_size"], bool).at[tokens[0, :-1]].set(True)
    assert bool(jnp.all(rows > 0))
    assert float(jnp.mean(rows[gathered])) > 2 * float(jnp.mean(rows[~gathered]))
    got, want = flat(got), flat(want)
    # nine Mamba layers of 12 leaves, one attention layer of 8, embedding, final norm
    assert set(got) == set(want) and len(got) == 9 * 12 + 8 + 2
    for name in sorted(want):
        assert float(jnp.linalg.norm(want[name])) > 0, name
        assert relative(got[name], want[name]) < 10 * TOLERANCE, name


def test_the_inlined_layout_is_the_same_model(toy):
    """``scan_layers`` false: leaves under ``layer_<i>``, read by the reference
    from that layout, and the loss the reference's."""
    _, _, _, tokens = toy
    config = toy_config(scan_layers=False)
    model = ARCHITECTURE.build(config, SEQ)
    params = jax.jit(model.init)(jax.random.PRNGKey(3), tokens[:, :-1])
    assert sorted(params["params"])[1:11] == sorted(f"layer_{i}" for i in range(10))
    want = reference.make_loss(ARCHITECTURE, config)(params, tokens)
    got = jax.jit(lambda p: program_loss(model, p, tokens))(params)
    assert abs(float(got) - float(want)) / float(want) < TOLERANCE


def test_the_harness_reference_program_agrees_with_the_model(short):
    """``reference.make_loss`` around the architecture's ``sequence_loss``, as
    the harness calls it, on a batch of two."""
    config, model, params, tokens = short
    want = reference.make_loss(ARCHITECTURE, config)(params, tokens)
    got = jax.jit(lambda p: program_loss(model, p, tokens))(params)
    assert abs(float(got) - float(want)) / float(want) < TOLERANCE


def _norm_before_gate(y, z, scale, eps):
    return reference.rms_norm(y, scale, eps) * jax.nn.silu(z)


def _with_rotary(monkeypatch):
    plain = reference.causal_attention

    def rotated(q, k, v):
        return plain(reference.rotary(q, 10000.0), reference.rotary(k, 10000.0), v)

    monkeypatch.setattr(reference, "causal_attention", rotated)


# What the reference is made to leave out, one at a time: (the layer kind it
# is seen in, or None for the whole model's loss; a key of the configuration
# put to what "not there" means, or a function swapped).
DROPPED = {
    "embedding_multiplier": (None, {"embedding_multiplier": 1}),
    "logits_scaling": (None, {"logits_scaling": 1}),
    "residual_multiplier": (0, {"residual_multiplier": 1.0}),
    "the-gate-comes-before-the-norm": (0, lambda patch: patch.setattr(
        ARCHITECTURE, "_gated_norm", _norm_before_gate
    )),
    "attention_multiplier-is-not-head_dim^-0.5": (5, {"attention_multiplier": 8**-0.5}),
    "no-positional-encoding": (5, _with_rotary),
}


@pytest.mark.parametrize("what", sorted(DROPPED))
def test_each_published_choice_is_in_the_program(what, short, monkeypatch):
    """The reference with ONE of the published choices dropped no longer
    agrees with the program, by a hundred tolerances or more, and with all of
    them it does. So none of them is a no-op of the toy, and the program has
    each. The embedding's and the logits' multipliers are seen in the whole
    model's loss; the others in ONE block's output on unit-scale rows (a Mamba
    block, kind 0, or the attention block, kind 5), because the mean loss of
    random weights on random tokens hardly sees a layer (the attention's scale
    moves it by 1e-5, rotary by 4e-7)."""
    config, model, params, tokens = short
    kind, change = DROPPED[what]
    if kind is None:
        tokens = tokens[:1]
        program = lambda: jax.jit(lambda p: program_loss(model, p, tokens))(params)
        plain = lambda cfg: jax.jit(lambda p: reference_loss(cfg, p, tokens))(params)
    else:
        config = toy_config()  # the period's own kinds: 0 is Mamba-2, 5 attends
        block = Block(ARCHITECTURE.build(config, SEQ).config, kind)
        rows = jax.random.normal(jax.random.PRNGKey(4), (1, SEQ, config["hidden_size"]))
        layer = block.init(jax.random.PRNGKey(5), rows, None)
        # The block's two branches, the stream it adds them to taken off.
        program = lambda: jax.jit(block.apply)(layer, rows, None)[0] - rows[0]

        def plain(cfg):
            with jax.default_matmul_precision("highest"):
                return ARCHITECTURE._block(
                    rows[0], ARCHITECTURE._weights(layer["params"]), cfg, cfg["layer_types"][kind]
                ) - rows[0]

    with jax.default_matmul_precision("highest"):
        got = program()
    assert relative(got, plain(config)) < 10 * TOLERANCE
    if callable(change):
        change(monkeypatch)
    else:
        config = {**config, **change}
    assert relative(got, plain(config)) > 100 * TOLERANCE


@pytest.mark.parametrize("scan_layers", [True, False], ids=["scanned-by-period", "inlined"])
def test_layer_five_of_a_period_attends_and_no_other(scan_layers):
    """Twenty layers, two periods: layers 5 and 15 hold attention's four
    matrices, every other layer a Mamba-2 mixer's leaves, and the decays are
    sown by the eighteen Mamba layers in order."""
    kinds = (("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 2
    cfg = GraniteConfig(
        vocab_size=64, dim=32, n_layers=20, layer_types=kinds, n_heads=4, n_kv_heads=2,
        mlp_hidden=48, mamba_heads=4, mamba_head_dim=16, mamba_state=8, mamba_chunk=8,
        dtype=jnp.float32, scan_layers=scan_layers,
    )
    assert cfg.period == 10 and cfg.head_dim == 8 and cfg.mamba_inner == 64
    model = Granite(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 24), 0, 64)
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(1), tokens)["params"]
    if scan_layers:
        layers = {kind: tree["layers"][f"block_{kind}"] for kind in range(10)}
        assert all(leaf.shape[0] == 2 for leaf in jax.tree_util.tree_leaves(tree["layers"]))
    else:
        layers = {i: tree[f"layer_{i}"] for i in range(20)}
    for i, layer in layers.items():
        attends = i % 10 == 5
        assert ("attn" in layer) == attends and ("mamba" in layer) != attends, i
        assert set(layer) == {"mixer_norm", "mlp_norm", "mlp", "attn" if attends else "mamba"}
    assert "lm_head" not in tree  # the head is the embedding


def test_the_decays_are_sown_by_the_mamba_layers_in_order(toy):
    """``ssd_chunk_log_decay`` through ``sown_by_layer``: nine rows for the
    nine Mamba layers of the period (the attention layer sows nothing), each
    the smallest and largest total log-decay of a chunk, both negative; the
    first layer's is what ops/ssd.py gives for that layer's own dt and A."""
    config, model, params, tokens = toy
    decays = jax.jit(lambda p: chunk_log_decay(model, p, tokens[:, :-1]))(params)
    assert decays.shape == (9, 2) and bool(jnp.all(decays[:, 0] <= decays[:, 1]))
    assert bool(jnp.all(decays[:, 1] < 0))
    assert len({float(d) for d in decays[:, 0]}) == 9  # each layer its own


def test_a_layout_that_is_not_whole_periods_is_refused():
    with pytest.raises(ValueError, match="layer_types"):
        GraniteConfig(n_layers=10, layer_types=("mamba",) * 9)
    with pytest.raises(ValueError, match="layer_types"):
        GraniteConfig(n_layers=2, layer_types=("mamba", "conv"))


class _Tied(nn.Module):
    """A gather, something in between, and the tied head."""

    @nn.compact
    def __call__(self, tokens, targets=None):
        embed = nn.Embed(40, 16, name="tok_embed")
        return tied_head(embed, jnp.tanh(embed(tokens)), targets, loss_vocab_chunk=16)


def test_the_tied_matrixs_gradient_is_the_sum_of_both_uses():
    """``tied_head`` against the same loss written with TWO matrices, one
    gathered from and one multiplied by: at equal matrices the tied gradient is
    the sum of the two, and neither part is nothing. The fused path (three
    slabs of 16, the last padded) forms no logits; without targets the logits
    are ``x E^T``."""
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 12), 0, 40)
    targets = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 40)
    model = _Tied()
    params = model.init(jax.random.PRNGKey(2), tokens)
    table = params["params"]["tok_embed"]["embedding"]

    def two(gathered, multiplied):
        logp = jax.nn.log_softmax(jnp.tanh(gathered[tokens]) @ multiplied.T, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))

    from_gather, from_head = jax.grad(two, argnums=(0, 1))(table, table)
    tied = jax.grad(lambda p: model.apply(p, tokens, targets))(params)["params"]["tok_embed"]["embedding"]
    assert float(jnp.linalg.norm(from_gather)) > 0 and float(jnp.linalg.norm(from_head)) > 0
    assert relative(tied, from_gather + from_head) < TOLERANCE
    assert relative(tied, from_head) > 1e-2
    logits = model.apply(params, tokens)
    assert relative(logits, jnp.tanh(table[tokens]) @ table.T) < TOLERANCE


def test_a_sliced_vocabulary_is_the_smaller_vocabulary_model(short):
    """A quarter of the rows of the tied matrix, ids drawn from the slice:
    the model of the small vocabulary IS the large model restricted to those
    rows: the same logits over the held rows, and its loss the cross-entropy
    over the slice alone."""
    config, model, params, _ = short
    held = config["vocab_size"] // 4
    small_config = {**config, "vocab_size": held}
    small = ARCHITECTURE.build(small_config, SEQ)
    sliced = jax.tree_util.tree_map(lambda a: a, params)
    sliced["params"] = {**params["params"], "tok_embed": {
        "embedding": params["params"]["tok_embed"]["embedding"][:held]
    }}
    tokens = jax.random.randint(jax.random.PRNGKey(11), (1, SEQ + 1), 0, held)
    full_logits = jax.jit(lambda p: model.apply(p, tokens[:, :-1]))(params)
    small_logits = jax.jit(lambda p: small.apply(p, tokens[:, :-1]))(sliced)
    assert small_logits.shape[-1] == held
    assert relative(small_logits, full_logits[..., :held]) < TOLERANCE
    logp = jax.nn.log_softmax(full_logits[..., :held], axis=-1)
    over_the_slice = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
    got = jax.jit(lambda p: program_loss(small, p, tokens))(sliced)
    assert abs(float(got) - float(over_the_slice)) / float(over_the_slice) < TOLERANCE
    want = jax.jit(lambda p: reference_loss(small_config, p, tokens))(sliced)
    assert abs(float(got) - float(want)) / float(got) < TOLERANCE


def test_a_mamba_layer_on_the_interpreted_kernels_is_the_layer_on_the_einsum_path(monkeypatch):
    """One Mamba-2 block at the smallest widths ops/ssd.py's Mosaic kernels
    take (two heads of 64, a state of 128, chunks of 128, 384 channels of
    convolution), float32: the loss and every leaf's gradient with the
    convolution and the scan in the Pallas interpreter against the same layer
    on the XLA path. The model has no switch for it, so the test binds
    ``interpret=True`` where the model looks the two functions up."""
    from functools import partial

    from torchft_tpu.ops import ssd

    cfg = GraniteConfig(
        vocab_size=64, dim=64, n_layers=1, layer_types=("mamba",), n_heads=4, n_kv_heads=2,
        mlp_hidden=96, mamba_heads=2, mamba_head_dim=64, mamba_state=128, mamba_chunk=128,
        dtype=jnp.float32,
    )
    model = Granite(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(11), (1, 257), 0, cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.PRNGKey(3), tokens[:, :-1])
    loss_and_gradient = lambda: jax.jit(jax.value_and_grad(lambda p: program_loss(model, p, tokens)))
    with jax.default_matmul_precision("highest"):
        want_loss, want = loss_and_gradient()(params)
        monkeypatch.setattr(ssd, "conv_silu", partial(ssd.conv_silu, interpret=True))
        monkeypatch.setattr(ssd, "ssd_scan", partial(ssd.ssd_scan, interpret=True))
        traced = str(jax.make_jaxpr(lambda p: program_loss(model, p, tokens))(params))
        assert ssd.CONV_FWD in traced and ssd.SSD_FWD in traced
        got_loss, got = loss_and_gradient()(params)
    assert abs(float(got_loss) - float(want_loss)) / float(want_loss) < TOLERANCE
    got, want = flat(got), flat(want)
    assert set(got) == set(want) and len(got) == 12 + 2
    for name in sorted(want):
        assert float(jnp.linalg.norm(want[name])) > 0, name
        assert relative(got[name], want[name]) < 10 * TOLERANCE, name


def test_the_model_file_asks_no_platform_and_ops_chooses_the_kernel():
    text = (ROOT / "torchft_tpu/models/granite.py").read_text()
    assert "on_tpu" not in text and "jax.devices" not in text and "interpret" not in text
    assert "from torchft_tpu.ops.attention import attend" in text
