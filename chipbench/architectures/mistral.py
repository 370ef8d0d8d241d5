"""mistral: everything the benchmark knows of one block, the dense pre-norm
decoder of ``MistralForCausalLM`` (Mistral-7B-v0.3): token embedding, blocks of
RMSNorm -> rotary grouped-query causal attention -> residual, RMSNorm ->
SwiGLU -> residual, a final RMSNorm, an untied output head, mean token
cross-entropy. Found by a configuration's ``"model_type": "mistral"``; nothing
outside this file names the program's model class or a parameter path.

An architecture file gives four things:

    build(config, seq)                      the PROGRAM's model as it is run
    sequence_loss(params, tokens, config, recompute=False)
                                            the float32 reference of one sequence
    parameter_counts(config)                what is counted, and
    train_flops_per_token(config, seq)      what ``mfu_pct`` divides by

Departures of the reference from the published model: none in the
mathematics; the rotary pairing is the rotate-half convention of the published
implementation (first half of a head paired with its second half), and there
is no sliding window (``sliding_window: null`` in v0.3).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench import reference

# What flash_time_pct and flash_mxu_pct ask of a cell they list
# (``ARCHITECTURE_SAYS`` in their files; ``spec.problems`` reads this line):
# every Pallas call of the step programs ``build`` gives is causal flash
# attention, and every one of ``num_hidden_layers`` layers runs it at
# ``num_attention_heads x head_dim``.
FLASH_ATTENTION_IN_EVERY_LAYER = True


def build(config: Dict[str, Any], seq: int):
    """The program's model for a configuration file as it is run: an object
    with ``init(key, tokens)`` and ``apply(params, inputs, targets=...)``,
    which returns the scalar training loss. The sizes are the file's, the code
    is ``torchft_tpu/models/llama.py`` unchanged."""
    from torchft_tpu.models.llama import Llama, LlamaConfig

    if config["hidden_size"] != config["num_attention_heads"] * config["head_dim"]:
        raise ValueError("models/llama.py derives head_dim as hidden_size / heads")
    if config.get("sliding_window") is not None:
        raise ValueError("models/llama.py has no sliding window")
    run = config["run"]
    return Llama(LlamaConfig(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        ffn_hidden=config["intermediate_size"],
        max_seq_len=seq,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(run["dtype"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        attention_impl=run["attention_impl"],
        remat=run["remat"],
        loss_vocab_chunk=run["loss_vocab_chunk"],
        scan_layers=run["scan_layers"],
    ))


# -- the float32 reference ----------------------------------------------------


def _attention(x, w, config: Dict[str, Any]) -> jnp.ndarray:
    """x: (s, d). Grouped-query causal attention of one sequence."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    theta = float(config["rope_theta"])
    q = jnp.einsum("sd,dhk->shk", x, w["wq"])
    k = jnp.einsum("sd,dhk->shk", x, w["wk"])
    v = jnp.einsum("sd,dhk->shk", x, w["wv"])
    q, k = reference.rotary(q, theta), reference.rotary(k, theta)
    # Each group of heads / kv query heads shares one key/value head.
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    out = reference.causal_attention(q, k, v)
    return jnp.einsum("shk,hkd->sd", out, w["wo"])


def _block(x, w, config: Dict[str, Any]) -> jnp.ndarray:
    eps = float(config["rms_norm_eps"])
    x = x + _attention(reference.rms_norm(x, w["attn_norm"], eps), w, config)
    h = reference.rms_norm(x, w["mlp_norm"], eps)
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
    """Sum of next-token cross-entropies of ONE sequence ``tokens`` (s + 1,):
    everything the training loss sums for it; the caller takes the mean over
    the batch's tokens. This block has no other term. A term of a single
    sequence (a router's z-loss) would be added here, scaled as the program
    scales it against a token's loss; a term over the whole batch (a load
    balance from the batch's expert counts) cannot be: it needs the batch, so
    ``reference.make_loss`` and ``grad_sum`` would have to call a
    ``batch_loss`` of the architecture where it has one. ``recompute`` changes
    memory and no number."""
    tree = params["params"]
    inputs, targets = tokens[:-1], tokens[1:]
    x = tree["tok_embed"]["embedding"].astype(jnp.float32)[inputs]
    block = functools.partial(_block, config=config)
    if recompute:
        block = jax.checkpoint(block)
    for layer in range(config["num_hidden_layers"]):
        x = block(x, _layer_weights(params, layer))
    x = reference.rms_norm(
        x, tree["final_norm"]["scale"].astype(jnp.float32), float(config["rms_norm_eps"])
    )
    if config.get("tie_word_embeddings"):
        head = tree["tok_embed"]["embedding"].astype(jnp.float32).T
    else:
        head = tree["lm_head"]["kernel"].astype(jnp.float32)
    return reference.next_token_loss_sum(x, head, targets)


# -- what is counted -----------------------------------------------------------


def parameter_counts(config: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of the model as it is run (depth cut included)."""
    d, ffn = config["hidden_size"], config["intermediate_size"]
    heads, kv, hd = (
        config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"],
    )
    layers, vocab = config["num_hidden_layers"], config["vocab_size"]
    attention = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    mlp = 3 * d * ffn
    norms = 2 * d
    embedding = vocab * d
    head = 0 if config.get("tie_word_embeddings") else vocab * d
    return {
        "per_layer": attention + mlp + norms,
        "embedding": embedding,
        "head": head,
        "total": layers * (attention + mlp + norms) + embedding + head + d,
        # What a matrix multiplication touches every token: the embedding
        # table is a gather, the head (tied or not) is a matmul. For a routed
        # layer this would hold its active experts only.
        "matmul": layers * (attention + mlp) + vocab * d,
    }


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """Forward + backward operations one trained token requires:
    6 * N_matmul + 12 * L * d * s, the PaLM convention (attention scores and
    values counted over the whole sequence, not the causal half: the flash
    kernels skip the masked half, so the attention term, 5.5% of the total at
    s = 2048 and 19% at 8192, over-counts the work they need by up to a factor
    of two; ``flash_mxu_pct`` counts the half). Recomputation under remat is
    not counted."""
    counts = parameter_counts(config)
    attention = 12 * config["num_hidden_layers"] * config["hidden_size"] * seq
    return 6.0 * counts["matmul"] + attention
