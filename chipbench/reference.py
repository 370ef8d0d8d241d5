"""Plain float32 reference of the configurations' block: forward, loss, and
the first optimizer update.

Straightforward ``jax.numpy`` after the published description of
Mistral-7B-v0.3 (``MistralForCausalLM``: token embedding, pre-norm blocks of
RMSNorm -> rotary grouped-query causal attention -> residual, RMSNorm ->
SwiGLU -> residual, a final RMSNorm, an untied output head, mean token
cross-entropy). No kernels, no cache, no remat, no scan, no chunked loss;
every multiplication in float32 under ``jax.default_matmul_precision
("highest")``, because a TPU otherwise runs a float32 matmul in bf16 passes.

It reads the same weights as the system (the system's parameter tree, whose
names are the only thing it takes from the code under test) and nothing else
of it. Departures from the published model: none in the mathematics; the
rotary pairing is the rotate-half convention of the published implementation
(first half of a head paired with its second half), and there is no sliding
window (``sliding_window: null`` in v0.3).

To bound memory at the real widths the loss is taken one sequence at a time
(``lax.map`` over the batch): attention scores of one sequence are
heads x s x s float32, 0.5 GiB at s = 2048.

The update (``grad_sum`` + ``first_adamw_step``, as one program in
``make_loss_after_first_update``) is the float32 gradient of that loss and
AdamW's first step from zero moments written out by hand (Loshchilov & Hutter,
arXiv:1711.05101, with Adam's bias correction): after one step the corrected
moments are g and g*g, so the step is ``p - lr * (g / (|g| + eps) + wd * p)``,
stored in the configuration's parameter dtype. No optimizer library, no
moments: what the system's second loss is held to.
For the gradient alone each block is recomputed in the backward pass
(``jax.checkpoint``), which changes memory and no number.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def _rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (s, heads, head_dim). Angle of pair i at position p: p * theta^(-2i/hd)."""
    s, _, hd = x.shape
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    first, second = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate(
        [first * cos - second * sin, first * sin + second * cos], axis=-1
    )


class _Frozen(dict):
    """A configuration as a hashable static argument (for ``jax.checkpoint``)."""

    def __hash__(self) -> int:  # type: ignore[override]
        return id(self)


def _attention(x, w, config: Dict[str, Any]) -> jnp.ndarray:
    """x: (s, d). Grouped-query causal attention of one sequence."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, theta = config["head_dim"], float(config["rope_theta"])
    s = x.shape[0]
    q = jnp.einsum("sd,dhk->shk", x, w["wq"])
    k = jnp.einsum("sd,dhk->shk", x, w["wk"])
    v = jnp.einsum("sd,dhk->shk", x, w["wv"])
    q, k = _rotary(q, theta), _rotary(k, theta)
    # Each group of heads / kv query heads shares one key/value head.
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    scores = jnp.einsum("shk,thk->hst", q, k) * hd**-0.5
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hst,thk->shk", probs, v)
    return jnp.einsum("shk,hkd->sd", out, w["wo"])


def _block(x, w, config: Dict[str, Any]) -> jnp.ndarray:
    eps = float(config["rms_norm_eps"])
    x = x + _attention(_rms_norm(x, w["attn_norm"], eps), w, config)
    h = _rms_norm(x, w["mlp_norm"], eps)
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def _layer_weights(params: Dict[str, Any], layer: int) -> Dict[str, jnp.ndarray]:
    """One layer's matrices in float32, from either layout of the system's
    tree: stacked under ``layers/block`` (scanned) or ``layer_<i>``."""
    tree = params["params"]
    if "layers" in tree:
        block = jax.tree_util.tree_map(lambda a: a[layer], tree["layers"]["block"])
    else:
        block = tree[f"layer_{layer}"]
    f32 = lambda a: a.astype(jnp.float32)
    return {
        "wq": f32(block["attn"]["wq"]["kernel"]),
        "wk": f32(block["attn"]["wk"]["kernel"]),
        "wv": f32(block["attn"]["wv"]["kernel"]),
        "wo": f32(block["attn"]["wo"]["kernel"]),
        "attn_norm": f32(block["attn_norm"]["scale"]),
        "mlp_norm": f32(block["mlp_norm"]["scale"]),
        "w_gate": f32(block["mlp"]["w_gate"]["kernel"]),
        "w_up": f32(block["mlp"]["w_up"]["kernel"]),
        "w_down": f32(block["mlp"]["w_down"]["kernel"]),
    }


def sequence_loss(
    params: Dict[str, Any], tokens: jnp.ndarray, config: Dict[str, Any], recompute: bool = False
):
    """Sum of next-token cross-entropies of ONE sequence ``tokens`` (s + 1,)."""
    tree = params["params"]
    inputs, targets = tokens[:-1], tokens[1:]
    x = tree["tok_embed"]["embedding"].astype(jnp.float32)[inputs]
    block = jax.checkpoint(_block, static_argnums=2) if recompute else _block
    for layer in range(config["num_hidden_layers"]):
        x = block(x, _layer_weights(params, layer), _Frozen(config))
    x = _rms_norm(
        x, tree["final_norm"]["scale"].astype(jnp.float32), float(config["rms_norm_eps"])
    )
    if config.get("tie_word_embeddings"):
        head = tree["tok_embed"]["embedding"].astype(jnp.float32).T
    else:
        head = tree["lm_head"]["kernel"].astype(jnp.float32)
    logp = jax.nn.log_softmax(x @ head, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def make_loss(config: Dict[str, Any]):
    """``loss(params, tokens)``: mean next-token cross-entropy of a batch
    ``tokens`` (b, s + 1), float32 at the highest matmul precision."""

    @jax.jit
    def loss(params, tokens):
        with jax.default_matmul_precision("highest"):
            sums = jax.lax.map(lambda seq: sequence_loss(params, seq, config), tokens)
        return jnp.sum(sums) / (tokens.shape[0] * (tokens.shape[1] - 1))

    return loss


def grad_sum(params, tokens, config: Dict[str, Any]):
    """Float32 gradient of the SUM of token losses of ``tokens`` (n, s + 1)
    with respect to float32 ``params``, one sequence at a time."""
    grad = jax.grad(lambda p, seq: sequence_loss(p, seq, config, recompute=True))

    def add(total, seq):
        return jax.tree_util.tree_map(jnp.add, total, grad(params, seq)), None

    return jax.lax.scan(add, jax.tree_util.tree_map(jnp.zeros_like, params), tokens)[0]


def first_adamw_step(params, grad_total, count, config: Dict[str, Any]):
    """Float32 ``params`` after ONE AdamW step from zero moments on the mean
    gradient ``grad_total / count``, computed in float32 and then rounded to
    the dtype the configuration keeps its parameters in (``run.dtype``). The
    rounding belongs to the configuration, not to the code under test: a step
    of 3e-4 is two or three bf16 units of a weight near 0.02, and without it
    the second loss differs by 1e-3 from any bf16 training, with it by 5e-5
    (my chip run, PR 24)."""
    opt = config["optimizer"]
    lr, wd, eps = float(opt["learning_rate"]), float(opt["weight_decay"]), float(opt["eps"])
    stored_as = jnp.dtype(config["run"]["dtype"])

    def leaf(p, g_sum):
        g = g_sum / count
        return (p - lr * (g / (jnp.abs(g) + eps) + wd * p)).astype(stored_as)

    return jax.tree_util.tree_map(leaf, params, grad_total)


def make_loss_after_first_update(config: Dict[str, Any]):
    """``loss_after(params, first, then)``: the mean loss of the batch ``then``
    (b, s + 1) after one AdamW step on the mean loss of ``first`` (n, s + 1),
    which holds every sequence the step averages over. One program from the
    system's weights to a number: the float32 copy, the gradient and the
    stepped weights are its temporaries, so the reference leaves no array on
    the device and the run's ``peak_bytes_in_use`` is the job's own."""

    @jax.jit
    def loss_after(params, first, then):
        with jax.default_matmul_precision("highest"):
            p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
            count = first.shape[0] * (first.shape[1] - 1)
            stepped = first_adamw_step(p32, grad_sum(p32, first, config), count, config)
            sums = jax.lax.map(lambda seq: sequence_loss(stepped, seq, config), then)
        return jnp.sum(sums) / (then.shape[0] * (then.shape[1] - 1))

    return loss_after
