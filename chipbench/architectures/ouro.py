"""ouro: everything the benchmark knows of one block, the looped decoder of
ByteDance/Ouro-2.6B ("Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741): ONE stack of layers run ``total_ut_steps`` times on one set
of weights, an exit after every pass, the exits weighed token by token by a
learned gate. Found by a configuration's ``"model_type": "ouro"``; nothing
outside this file names the program's model class, a parameter path or a
width's key.

The equations the float32 reference is written from (``d`` hidden, ``T =
total_ut_steps``):

1. Block ``i``, four RMSNorms (eps ``rms_norm_eps``) in a sandwich: ``a = x +
   N2_i(Attn_i(N1_i(x)))``, ``y = a + N4_i(MLP_i(N3_i(a)))``.
2. ``Attn``: ``q, k, v = h Wq, h Wk, h Wv`` without bias, ``num_attention_heads``
   query and ``num_key_value_heads`` key-value heads of ``head_dim``, rotary
   over the whole head (rotate-half pairing, ``rope_theta``) on q and k,
   ``softmax(q k^T / sqrt(head_dim) + causal) v``, ``Wo``.
3. ``MLP``: ``W_down(silu(h W_gate) * (h W_up))``, ``intermediate_size`` wide.
4. The loop: ``h_0 = E[tokens]``; for ``t = 1 .. T``: ``h_t = N_f(M(h_{t-1}))``,
   ``M`` the whole stack, the SAME weights and the same final norm ``N_f`` in
   every pass; the normed state is both the exit's input and the next pass's.
5. Exit ``t``: logits ``h_t W_head``; gate ``lambda_t = sigmoid(h_t . w_g +
   b_g)``, one ``Linear(d, 1)`` shared by the passes. By token: ``p_1 =
   lambda_1``, ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for 1 < t < T, ``p_T
   = prod_{j<T} (1 - lambda_j)`` (the last pass takes what is left; ``lambda_T``
   takes no part).
6. Loss by token: ``sum_t p_t CE_t - beta H(p)``, ``CE_t`` the next-token
   cross-entropy of exit t, ``H(p) = -sum_t p_t log p_t``, ``beta =
   exit_entropy_beta``; the step's loss is its mean over tokens.

The reference is a Python loop over passes and layers, the gate's logarithms
from ``log_sigmoid`` as they are written above (the program takes a running sum
over the passes); it shares no function with torchft_tpu/models/ouro.py.
What is assumed, because the config does not say it (the sandwich, ``N_f``
between passes, ``beta``, the initialisers), is listed in the configuration
file under ``assumed``.

An architecture file gives ``build``, ``sequence_loss``, ``parameter_counts``,
``train_flops_per_token``; this one also what a device trace calls the attention
kernels (``ATTENTION_KERNEL``) and the operations they need
(``mixed_attention_flops``), which the accepted readers ``mixed_attn_time_pct``
and ``mixed_attn_mxu_pct`` ask for by these names, and which ops of a device
trace are the exits' (``exit_loss_seconds``, read by ``exit_loss_time_pct``).
It does NOT say ``FLASH_ATTENTION_IN_EVERY_LAYER``: ``flash_mxu_pct`` counts
``num_hidden_layers`` calls a step and this cell makes ``total_ut_steps`` times
that.
"""

from __future__ import annotations

import functools
import re
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench import reference


class _Seeded:
    """The program's model with the yardstick's one choice of initial scale
    laid over its own initialisers, as chipbench/architectures/smallthinker.py
    lays its two: ``init`` is the model's, then the output head times
    ``run.head_init_scale`` (the configuration file's ``assumed`` says what it
    is chosen for). The choice is the benchmark's, so it lives here and the
    model keeps the initialisers every decoder has; every other attribute
    (``apply``, ``config``) is the model's own."""

    def __init__(self, model, head: float) -> None:
        self._model, self._head = model, head

    def init(self, *args, **kwargs):
        variables = self._model.init(*args, **kwargs)
        head = jax.tree_util.tree_map(
            lambda leaf: (leaf.astype(jnp.float32) * self._head).astype(leaf.dtype),
            variables["params"]["lm_head"],
        )
        return {**variables, "params": {**variables["params"], "lm_head": head}}

    def __getattr__(self, name: str):
        return getattr(self._model, name)


def build(config: Dict[str, Any], seq: int):
    """The program's model for a configuration file as it is run: ``init(key,
    tokens)`` and ``apply(params, inputs, targets=...)``, the scalar loss."""
    from torchft_tpu.models.ouro import Ouro, OuroConfig

    run = config["run"]
    if config["hidden_size"] != config["num_attention_heads"] * config["head_dim"]:
        raise ValueError("models/ouro.py derives head_dim as hidden_size / heads")
    if config["tie_word_embeddings"] or config["hidden_act"] != "silu":
        raise ValueError("models/ouro.py: an untied head, SwiGLU")
    if config["use_sliding_window"] or config["sliding_window"] is not None:
        raise ValueError("models/ouro.py has no sliding window")
    if set(config["layer_types"]) != {"full_attention"} or config["rope_scaling"] is not None:
        raise ValueError("models/ouro.py: full attention in every layer, plain rotary")
    model = Ouro(OuroConfig(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        ffn_hidden=config["intermediate_size"],
        loops=config["total_ut_steps"],
        exit_entropy_coef=float(config["exit_entropy_beta"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(run["dtype"]),
        norm_dtype=jnp.dtype(run["norm_dtype"]),
        attention_impl=run["attention_impl"],
        remat=run["remat"],
        loss_vocab_chunk=run["loss_vocab_chunk"],
        scan_layers=run["scan_layers"],
    ))
    return _Seeded(model, float(run["head_init_scale"]))


# -- the float32 reference ----------------------------------------------------


def _attention(h, w, config: Dict[str, Any]):
    """h: (s, d). Rotary causal attention of one sequence."""
    theta = float(config["rope_theta"])
    group = config["num_attention_heads"] // config["num_key_value_heads"]
    q = reference.rotary(jnp.einsum("sd,dhk->shk", h, w["wq"]), theta)
    k = reference.rotary(jnp.einsum("sd,dhk->shk", h, w["wk"]), theta)
    v = jnp.einsum("sd,dhk->shk", h, w["wv"])
    out = reference.causal_attention(q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1))
    return jnp.einsum("shk,hkd->sd", out, w["wo"])


def _block(x, w, config: Dict[str, Any]):
    """Equation 1: a norm before each branch and one after it."""
    norm = functools.partial(reference.rms_norm, eps=float(config["rms_norm_eps"]))
    a = x + norm(_attention(norm(x, w["n1"]), w, config), w["n2"])
    h = norm(a, w["n3"])
    return a + norm((jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"], w["n4"])


def _layer_weights(tree: Dict[str, Any], layer: int) -> Dict[str, jnp.ndarray]:
    """One layer's leaves in float32 by the names the equations use, from
    either layout of the system's tree: stacked under ``layers/block`` or
    ``layer_<i>``. ONE set: every pass reads the same."""
    if "layers" in tree:
        block = jax.tree_util.tree_map(lambda a: a[layer], tree["layers"]["block"])
    else:
        block = tree[f"layer_{layer}"]
    f32 = lambda a: a.astype(jnp.float32)
    return {
        **{name: f32(block["attn"][name]["kernel"]) for name in ("wq", "wk", "wv", "wo")},
        **{name: f32(block["mlp"][name]["kernel"]) for name in ("w_gate", "w_up", "w_down")},
        "n1": f32(block["attn_norm"]["scale"]), "n2": f32(block["attn_post_norm"]["scale"]),
        "n3": f32(block["mlp_norm"]["scale"]), "n4": f32(block["mlp_post_norm"]["scale"]),
    }


def _token_losses(x, head, targets):
    """Cross-entropy of ``softmax(x @ head)`` against ``targets`` BY position:
    (s,). The head in blocks of ``reference.HEAD_BLOCK`` positions, each
    recomputed in the gradient, as ``reference.next_token_loss_sum`` does."""

    def rows(_first, x_rows, target_rows):
        logp = jax.nn.log_softmax(x_rows @ head, axis=-1)
        return -jnp.take_along_axis(logp, target_rows[:, None], axis=-1)[:, 0]

    return reference._in_blocks(rows, reference.HEAD_BLOCK, x, targets).reshape(targets.shape)


def exit_losses(params: Dict[str, Any], tokens: jnp.ndarray, config: Dict[str, Any], recompute: bool = False):
    """``(CE, gate logits)`` of ONE sequence ``tokens`` (s + 1,), each
    ``(total_ut_steps, s)``: every exit's cross-entropy by position and the
    gate's logit on every exit's state (equations 1 to 5, before the gate's
    weighing). ``recompute`` checkpoints by pass AND by layer: a pass keeps its
    input alone, and its backward keeps a layer's input alone."""
    tree = params["params"]
    f32 = lambda a: a.astype(jnp.float32)
    h = f32(tree["tok_embed"]["embedding"])[tokens[:-1]]
    head, final = f32(tree["lm_head"]["kernel"]), f32(tree["final_norm"]["scale"])
    gate, bias = f32(tree["exit_gate"]["kernel"])[:, 0], f32(tree["exit_gate"]["bias"])[0]
    layers = [_layer_weights(tree, layer) for layer in range(config["num_hidden_layers"])]
    block = functools.partial(_block, config=config)
    if recompute:
        block = jax.checkpoint(block)

    def one_pass(h, layers):
        for w in layers:
            h = block(h, w)
        return reference.rms_norm(h, final, float(config["rms_norm_eps"]))

    if recompute:
        one_pass = jax.checkpoint(one_pass)
    losses, logits = [], []
    for _ in range(config["total_ut_steps"]):
        h = one_pass(h, layers)
        losses.append(_token_losses(h, head, tokens[1:]))
        logits.append(h @ gate + bias)
    return jnp.stack(losses), jnp.stack(logits)


def exit_log_probs(logits: jnp.ndarray) -> jnp.ndarray:
    """Equation 5 in logarithms, term by term: ``logits`` (T, s) to ``log p_t``
    (T, s). ``log(1 - sigmoid(z)) = log_sigmoid(-z)``."""
    passes = logits.shape[0]
    out = []
    for t in range(passes):
        stayed = sum((jax.nn.log_sigmoid(-logits[j]) for j in range(t)), jnp.zeros_like(logits[0]))
        out.append(stayed if t == passes - 1 else stayed + jax.nn.log_sigmoid(logits[t]))
    return jnp.stack(out)


def sequence_loss(
    params: Dict[str, Any], tokens: jnp.ndarray, config: Dict[str, Any], recompute: bool = False
):
    """Equation 6 summed over the positions of ONE sequence ``tokens`` (s + 1,):
    everything the training loss sums for it; the caller takes the mean over
    the batch's tokens. ``recompute`` changes memory and no number."""
    losses, logits = exit_losses(params, tokens, config, recompute)
    log_p = exit_log_probs(logits)
    p = jnp.exp(log_p)
    entropy = -jnp.sum(p * log_p, axis=0)
    return jnp.sum(jnp.sum(p * losses, axis=0) - float(config["exit_entropy_beta"]) * entropy)


# -- what is counted -----------------------------------------------------------


def parameter_counts(config: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of the model as it is run (depth and the vocabulary's slice
    as the file has them), ONE copy of every layer however often it runs.
    ``matmul``: the matrices a token is multiplied by in ONE pass with one exit
    (the gather is no product, nor is the gate counted)."""
    d, ffn = config["hidden_size"], config["intermediate_size"]
    heads, kv, hd = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    layers, vocab = config["num_hidden_layers"], config["vocab_size"]
    attention = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    mlp = 3 * d * ffn
    return {
        "per_layer": attention + mlp + 4 * d,
        "layer_matrices": attention + mlp,
        "embedding": vocab * d,
        "head": vocab * d,
        "gate": d + 1,
        "total": layers * (attention + mlp + 4 * d) + 2 * vocab * d + d + d + 1,
        "matmul": layers * (attention + mlp) + vocab * d,
    }


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """Forward + backward operations one trained token requires: every pass
    multiplies the token by every layer's matrices and by the head, so 6 x
    total_ut_steps x N_matmul, and 12 x (heads x head_dim) x seq for each of
    the num_hidden_layers x total_ut_steps attention calls (the PaLM
    convention). The gate's d products a pass and what the program recomputes
    are not counted."""
    passes = config["total_ut_steps"]
    attention = 12.0 * config["num_hidden_layers"] * config["hidden_size"] * seq
    return passes * (6.0 * parameter_counts(config)["matmul"] + attention)


# What XLA calls the attention layers' two Mosaic calls in a device trace: the
# scope models/ouro.py traces ``attend`` under, dots and colons to underscores,
# and a number (forward and the one backward call alike).
ATTENTION_KERNEL = re.compile(r"tpuft__ouro_attention")


def mixed_attention_flops(config: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations attention NEEDS for one training step: seven matmuls (two
    forward, five backward with the one recomputation of the scores, as
    ``flops.flash_attention_flops`` counts them) of 2 x head_dim x heads over
    the s (s + 1) / 2 pairs the causal mask allows, in each of the
    num_hidden_layers x total_ut_steps layer passes. Needed pairs, not the
    blocks a kernel walks."""
    calls = config["num_hidden_layers"] * config["total_ut_steps"]
    width = config["head_dim"] * config["num_attention_heads"]
    return 7.0 * 2.0 * width * (seq * (seq + 1) / 2) * calls * batch


def _first_result(name: str):
    """``(dtype, sizes)`` of an op's first result as a reduced trace names it
    ("fusion.348 bf16[8192,4096]"), sizes of 1 dropped; ``("", [])`` without."""
    shape = re.search(r"([a-z]+[0-9]*)\[([0-9,]*)\]", name)
    if not shape:
        return "", []
    return shape.group(1), [n for n in map(int, shape.group(2).split(",") if shape.group(2) else ()) if n > 1]


def exit_loss_shapes(config: Dict[str, Any], batch: int, seq: int):
    """``(dtype, sizes)`` of the results only the exits' loss has, sizes of 1
    dropped and ``None`` for any dtype, with n = batch x seq tokens, c =
    ``run.loss_vocab_chunk`` and d hidden: a slab of logits, of its softmax or
    of its gradient, ``(n, c)``; that slab's part of the head's gradient, ``(d,
    c)`` (no layer's matrix is d x c: the unit's are d x intermediate_size);
    and the gradient to an exit's state summed over the slabs, ``(n, d)`` in
    FLOAT32, which is ops/cross_entropy.py's own statement (its backward
    carries ``dx`` wide from slab to slab) where every ``(n, d)`` result of a
    layer pass is in ``run.dtype``."""
    n, chunk = batch * seq, config["run"]["loss_vocab_chunk"]
    shapes = [(None, [n, chunk]), (None, [config["hidden_size"], chunk])]
    if config["run"]["dtype"] != "float32":
        shapes.append(("f32", [n, config["hidden_size"]]))
    return shapes


def is_exit_loss_op(name: str, config: Dict[str, Any], batch: int, seq: int) -> bool:
    """Whether an op a reduced trace (or a compiled program's text) calls
    ``name`` is one of the exits' by :func:`exit_loss_shapes`."""
    dtype, sizes = _first_result(name)
    return any(sizes == want and kind in (None, dtype) for kind, want in exit_loss_shapes(config, batch, seq))


def exit_loss_seconds(trace: Dict[str, Any], config: Dict[str, Any], batch: int, seq: int) -> float:
    """Device seconds of the exits' vocabulary products and loss in a reduced
    trace, forward and backward, all ``total_ut_steps`` of them. The fused loss
    (ops/cross_entropy.py) is plain XLA, so its ops are found by their first
    result (:func:`exit_loss_shapes`): in the backward the slab's product with
    the head and the softmax's gradient, the head's gradient by slab, and the
    gradient to the exit's state.

    NOT seen, and so counted as other time: the FORWARD's slab, which XLA
    compiles into one fusion that ends in the ``(n,)`` running maxima and sums
    (a layer's norms make ``(n,)`` results too: XLA fuses a projection into the
    mean square of the norm after it, so the shape is not the exits' alone),
    the exponentials, the exit's norm and gate, and the write of a slab into
    the head's whole ``(d, vocab)`` gradient. So the share reads LOW: of the
    43.6 ms a step that the exits' own loops took on the chip the shapes above
    hold 28.8 (the two slabs 18.2, the gradient to the state 10.6) and the
    forward's fusion 10.8 (my chip run, PR 62: PERF.md section 5), two thirds.
    The shapes are THIS path's: another slab width, a fusion XLA draws
    otherwise or a kernel in the path's place moves ops in or out of sight, and
    a reading is comparable with another reading of the same path only. The
    scope ``tpuft::exit`` names the same ops in the profile's ``op_name``, which
    ``trace_reduce`` does not keep (tests/test_tpu_aot_compile.py reads it in
    the compiled program's text to hold these shapes to that scope)."""
    return sum(s for name, s in trace.get("ops", []) if is_exit_loss_op(name, config, batch, seq))
