"""smallthinker: everything the benchmark knows of one block, the decoder of
PowerInfer/SmallThinker-21BA3B-Instruct as ONE CHIP'S SHARE of an
expert-parallel deployment. Found by a configuration's ``"model_type":
"smallthinker"``; nothing outside this file names the program's model class, a
parameter path or a width's key.

The equations the float32 reference is written from (per layer ``l``, pre-norm,
``rms_norm_eps``; ``x`` is the residual stream as it ENTERS the layer):

1. ``r = x Wr``: one logit for each of ``router_width`` experts, from the
   block's input itself, ahead of attention and of any norm.
2. ``h = RMSNorm(x)``; ``q = h Wq`` (heads x head_dim), ``k = h Wk``, ``v = h
   Wv`` (kv heads x head_dim); no bias, no norm over the heads. Where
   ``rope_layout[l] == 1`` rotary (rotate-half, ``rope_theta``) on q and k over
   the whole head; where it is 0, nothing: no positional encoding at all.
3. Query t sees key u iff ``u <= t`` and, where ``sliding_window_layout[l] ==
   1``, ``t - u < sliding_window_size`` (the query's own position counts:
   ``sliding_window_size`` keys in all). ``a = softmax over the seen keys of
   (q . k) * head_dim ** -0.5``; ``x' = x + (a v) Wo``.
4. ``g = RMSNorm(x')``; ``E_t`` the ``moe_num_active_primary_experts`` largest
   of ``r_t``, weights ``softmax over E_t of r_t`` (the softmax over all
   logits renormalised over the chosen is the same number:
   ``moe_primary_router_apply_softmax`` with ``norm_topk_prob``);
   ``x'' = x' + sum over e in E_t that are held of weight_e * (relu(g Wg_e) *
   (g Wu_e)) Wd_e``. Held are the experts ``expert_share *
   moe_num_primary_experts ..``; what the others would add is left out.
5. Final RMSNorm, untied head over the held ``vocab_size`` rows, mean
   next-token cross-entropy. The config carries no coefficient for a router
   term, so none is built.

What is assumed, because the config does not say it, is listed in the
configuration file under ``assumed``: that the router reads the stream before
``input_layernorm``, ReGLU, no per-head norm, the window's count, and that the
secondary experts the family's description speaks of are not built.

An architecture file gives ``build``, ``sequence_loss``, ``parameter_counts``,
``train_flops_per_token``; this one also what a device trace calls its attention
kernels (``ATTENTION_KERNEL``, ``WINDOW_KERNEL``), what it calls the expert
layer's (``EXPERT_KERNEL``), the (query, key) pairs each kind of layer needs
(``attention_pairs``) and the operations attention needs for a step
(``mixed_attention_flops``, ``window_attention_flops``).
"""

from __future__ import annotations

import functools
import re
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench import reference

# What XLA calls the step programs' Mosaic calls in a device trace (the last
# scope a call is traced under, dots and colons to underscores, and a number):
# a layer without a window runs the flash kernels under the scope
# ``tpuft::full_attention`` (forward and the one backward call alike); a layer
# with one runs them under names of their own, ``window_attn_fwd`` and
# ``window_attn_bwd`` (torchft_tpu/ops/flash_attention.py WINDOW_FWD /
# WINDOW_BWD). The routed layer's calls are megablox's ``gmm`` / ``tgmm`` and
# the two sums by token, ``sum_by_token`` and its transpose (my chip run,
# PR 54: PERF.md section 5 has the kept trace). Differentiated on its own a
# call has a longer name that still holds these.
ATTENTION_KERNEL = re.compile(r"tpuft__full_attention|window_attn_(?:fwd|bwd)")
WINDOW_KERNEL = re.compile(r"window_attn_(?:fwd|bwd)")
EXPERT_KERNEL = re.compile(r"gmm|sum_by_token")


def _layouts(config: Dict[str, Any]):
    """(windowed, rotary) of the layers that are held: the first
    ``num_hidden_layers`` entries of the two published layouts."""
    layers = config["num_hidden_layers"]
    return config["sliding_window_layout"][:layers], config["rope_layout"][:layers]


class _Seeded:
    """The program's model with the yardstick's choice of initial scale laid
    over its own initialisers: ``init`` is the model's, then the embedding
    and the output head times ``run.embedding_init_scale`` and
    ``run.head_init_scale`` (the configuration file's ``assumed`` says what
    each is chosen for). The choice is the benchmark's, so it lives here and
    the model keeps the initialisers every decoder has; every other attribute
    (``apply``, ``config``) is the model's own."""

    def __init__(self, model, embedding: float, head: float) -> None:
        self._model, self._scales = model, {"tok_embed": embedding, "lm_head": head}

    def init(self, *args, **kwargs):
        variables = self._model.init(*args, **kwargs)
        scaled = {
            name: jax.tree_util.tree_map(
                lambda leaf, by=self._scales.get(name, 1.0): (
                    leaf.astype(jnp.float32) * by
                ).astype(leaf.dtype),
                group,
            )
            for name, group in variables["params"].items()
        }
        return {**variables, "params": scaled}

    def __getattr__(self, name: str):
        return getattr(self._model, name)


def build(config: Dict[str, Any], seq: int):
    """The program's model for a configuration file as it is run: ``init(key,
    tokens)`` and ``apply(params, inputs, targets=...)``, the scalar loss."""
    from torchft_tpu.models.smallthinker import SmallThinker, SmallThinkerConfig

    run = config["run"]
    if config["tie_word_embeddings"] or config["rope_scaling"] is not None:
        raise ValueError("models/smallthinker.py: an untied head, plain rotary")
    if not (config["moe_primary_router_apply_softmax"] and config["norm_topk_prob"]):
        raise ValueError("models/experts.py: a softmax router renormalised over its choices")
    windowed, rotary = _layouts(config)
    model = SmallThinker(SmallThinkerConfig(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        moe_hidden=config["moe_ffn_hidden_size"],
        num_experts=config["router_width"],
        experts_per_token=config["moe_num_active_primary_experts"],
        num_local_experts=config["moe_num_primary_experts"],
        expert_share=config["expert_share"],
        window=config["sliding_window_size"],
        window_layout=tuple(windowed),
        rope_layout=tuple(rotary),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(run["dtype"]),
        norm_dtype=jnp.dtype(run["norm_dtype"]),
        attention_impl=run["attention_impl"],
        remat=run["remat"],
        loss_vocab_chunk=run["loss_vocab_chunk"],
        scan_layers=run["scan_layers"],
        init_depth=config["published"]["num_hidden_layers"],
    ))
    return _Seeded(model, float(run["embedding_init_scale"]), float(run["head_init_scale"]))


# -- the float32 reference ----------------------------------------------------

# Queries of one block of attention, as a share of ``reference.QUERY_BLOCK``
# (which a rehearsal sets): a block of one key-value group's queries against
# the keys it may see is (group x block x keys) float32 scores, and at 7 x 512
# x 16,384 a quarter of a GiB, which the update's program has room for beside
# three float32 copies of the parameters.
ATTENTION_BLOCKS_IN_A_QUERY_BLOCK = 4


def _group_attention(q, k, v, window):
    """One key-value group of one sequence. q: (s, group, head_dim), k and v:
    (s, head_dim), positions already encoded. In blocks of queries: a full
    layer's block against the whole row of keys, a windowed layer's against
    the ``window + block`` keys that end with the block (the keys of its
    window and the block, no others)."""
    s, _, hd = q.shape
    block = max(1, reference.QUERY_BLOCK // ATTENTION_BLOCKS_IN_A_QUERY_BLOCK)

    def weigh(scores, seen, values):
        probs = jax.nn.softmax(jnp.where(seen[None], scores * hd**-0.5, -jnp.inf), axis=-1)
        return jnp.einsum("gst,tk->sgk", probs, values)

    if window is None or window >= s:
        def rows(first, q_rows):
            at = first + jnp.arange(q_rows.shape[0])
            seen = at[:, None] >= jnp.arange(s)[None, :]
            return weigh(jnp.einsum("sgk,tk->gst", q_rows, k), seen, v)
    else:
        # ``window`` rows of zeros in front, so that every block's span of
        # keys is a slice of one length; a key before the sequence is unseen.
        k_front = jnp.pad(k, ((window, 0), (0, 0)))
        v_front = jnp.pad(v, ((window, 0), (0, 0)))

        def rows(first, q_rows):
            n = q_rows.shape[0]
            span = window + n
            keys = jax.lax.dynamic_slice_in_dim(k_front, first, span)
            values = jax.lax.dynamic_slice_in_dim(v_front, first, span)
            at = first + jnp.arange(n)
            key_at = first - window + jnp.arange(span)
            apart = at[:, None] - key_at[None, :]
            seen = (apart >= 0) & (apart < window) & (key_at[None, :] >= 0)
            return weigh(jnp.einsum("sgk,tk->gst", q_rows, keys), seen, values)

    return reference._in_blocks(rows, block, q).reshape(q.shape)


def _attention(h, w, config: Dict[str, Any], windowed: bool, rotary: bool):
    """h: (s, d). Grouped-query attention of one sequence, one key-value group
    after the other."""
    theta = float(config["rope_theta"])
    q = jnp.einsum("sd,dhk->shk", h, w["wq"])
    k = jnp.einsum("sd,dhk->shk", h, w["wk"])
    v = jnp.einsum("sd,dhk->shk", h, w["wv"])
    if rotary:
        q, k = reference.rotary(q, theta), reference.rotary(k, theta)
    s, heads, hd = q.shape
    kv = k.shape[1]
    window = config["sliding_window_size"] if windowed else None
    by_group = jax.lax.map(
        lambda group: _group_attention(*group, window),
        (q.reshape(s, kv, heads // kv, hd).transpose(1, 0, 2, 3),
         k.transpose(1, 0, 2), v.transpose(1, 0, 2)),
    )  # (kv, s, group, head_dim)
    out = by_group.transpose(1, 0, 2, 3).reshape(s, heads, hd)
    return jnp.einsum("shk,hkd->sd", out, w["wo"])


def _experts(g, logits, w, config: Dict[str, Any]) -> jnp.ndarray:
    """The held experts' part of the routed layer for the normed rows ``g``
    under the router's ``logits`` (of the block's input), one expert at a time
    over every token, weighed by the token's gate for it (zero where the
    router did not choose it)."""
    local, share = config["moe_num_primary_experts"], config["expert_share"]
    top, chosen = jax.lax.top_k(logits, config["moe_num_active_primary_experts"])
    gates = jnp.zeros_like(logits).at[jnp.arange(g.shape[0])[:, None], chosen].set(
        jax.nn.softmax(top, axis=-1)
    )
    held = gates[:, share * local: (share + 1) * local]

    def add(out, expert):
        gate, w_gate, w_up, w_down = expert
        return out + gate[:, None] * ((jax.nn.relu(g @ w_gate) * (g @ w_up)) @ w_down), None

    return jax.lax.scan(add, jnp.zeros_like(g), (held.T, w["w_gate"], w["w_up"], w["w_down"]))[0]


def _block(x, w, config: Dict[str, Any], windowed: bool, rotary: bool):
    eps = float(config["rms_norm_eps"])
    logits = x @ w["router"]
    x = x + _attention(reference.rms_norm(x, w["attn_norm"], eps), w, config, windowed, rotary)
    return x + _experts(reference.rms_norm(x, w["mlp_norm"], eps), logits, w, config)


def _weights(block: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
    """A layer's matrices in float32 by the names the equations use, from the
    system's tree of one layer."""
    f32 = lambda a: a.astype(jnp.float32)
    attn, moe = block["attn"], block["moe"]
    return {
        "wq": f32(attn["wq"]["kernel"]), "wk": f32(attn["wk"]["kernel"]),
        "wv": f32(attn["wv"]["kernel"]), "wo": f32(attn["wo"]["kernel"]),
        "attn_norm": f32(block["attn_norm"]["scale"]),
        "mlp_norm": f32(block["mlp_norm"]["scale"]),
        "router": f32(moe["router"]["kernel"]),
        "w_gate": f32(moe["w_gate"]), "w_up": f32(moe["w_up"]), "w_down": f32(moe["w_down"]),
    }


def _layer(tree: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """Layer ``layer`` of the system's tree, whichever layout it has: inlined
    (``layer_<i>``), scanned as one kind (``layers/block``, a leading layer
    axis) or scanned by period (``layers/block_<kind>``, a leading axis of
    periods)."""
    if "layers" not in tree:
        return tree[f"layer_{layer}"]
    stack = tree["layers"]
    if "block" in stack:
        return jax.tree_util.tree_map(lambda a: a[layer], stack["block"])
    period = len(stack)
    return jax.tree_util.tree_map(lambda a: a[layer // period], stack[f"block_{layer % period}"])


def sequence_loss(
    params: Dict[str, Any], tokens: jnp.ndarray, config: Dict[str, Any], recompute: bool = False
):
    """Sum of next-token cross-entropies of ONE sequence ``tokens`` (s + 1,):
    everything the training loss sums for it. The layers are written out one
    after the other, each with its own kind; ``recompute`` changes memory and
    no number."""
    tree = params["params"]
    x = tree["tok_embed"]["embedding"].astype(jnp.float32)[tokens[:-1]]
    for layer, (windowed, rotary) in enumerate(zip(*_layouts(config))):
        block = functools.partial(
            _block, config=config, windowed=bool(windowed), rotary=bool(rotary)
        )
        if recompute:
            block = jax.checkpoint(block)
        x = block(x, _weights(_layer(tree, layer)))
    scale = tree["final_norm"]["scale"].astype(jnp.float32)
    x = reference.rms_norm(x, scale, float(config["rms_norm_eps"]))
    head = tree["lm_head"]["kernel"].astype(jnp.float32)
    return reference.next_token_loss_sum(x, head, tokens[1:])


# -- what is counted -----------------------------------------------------------


def parameter_counts(config: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of the model as it is run (depth, experts held and the
    vocabulary's slice as the file has them)."""
    d, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    heads, kv, hd = (
        config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"],
    )
    layers, vocab = config["num_hidden_layers"], config["vocab_size"]
    attention = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    router = d * config["router_width"]
    expert = 3 * d * f
    held = config["moe_num_primary_experts"] * expert
    per_layer = attention + router + held + 2 * d  # and the two norms' scales
    # An expert-equivalent a token: the router's choices that land on this
    # chip's share, exact as the mean over a layer's shares whatever the
    # routing and for ONE share an expectation under near-uniform routing.
    active = (
        config["moe_num_active_primary_experts"] * config["moe_num_primary_experts"]
        / config["router_width"]
    )
    return {
        "attention": attention,
        "per_layer": per_layer,
        "experts": layers * held,
        "embedding": vocab * d,
        "head": vocab * d,
        "total": layers * per_layer + 2 * vocab * d + d,
        "matmul": layers * (attention + router + active * expert) + vocab * d,
    }


def attention_pairs(seq: int, window=None) -> int:
    """(query, key) pairs one sequence's attention needs in one layer: every
    key up to the query's own, under a ``window`` the latest ``window`` of
    them."""
    full = seq if window is None else min(seq, window)
    return full * (full + 1) // 2 + (seq - full) * full


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """Forward + backward operations one trained token requires: 6 * N_matmul
    (attention, the router, the expectation of the held experts a token, the
    head; no embedding gather) + 12 * (heads x head_dim) * the keys a query
    may see at most, summed over the layers under each layer's own mask (the
    PaLM convention: the sequence in a full layer, the window in a windowed
    one, not the causal half). What the program computes beyond that is time,
    not need."""
    width = config["num_attention_heads"] * config["head_dim"]
    windowed, _ = _layouts(config)
    keys = sum(min(seq, config["sliding_window_size"]) if w else seq for w in windowed)
    return 6.0 * parameter_counts(config)["matmul"] + 12.0 * width * keys


def _attention_flops(config: Dict[str, Any], batch: int, seq: int, full_layers: bool) -> float:
    windowed, _ = _layouts(config)
    pairs = sum(
        attention_pairs(seq, config["sliding_window_size"] if w else None)
        for w in windowed if w or full_layers
    )
    return 7 * 2.0 * pairs * config["head_dim"] * config["num_attention_heads"] * batch


def mixed_attention_flops(config: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations attention NEEDS for one training step, both kinds of layer:
    seven matmuls (two forward, five backward with the one recomputation of
    the scores, as ``flops.flash_attention_flops`` counts them) of 2 x pairs x
    head_dim x heads over the pairs each layer's mask allows. Needed pairs,
    not the blocks a kernel walks."""
    return _attention_flops(config, batch, seq, full_layers=True)


def window_attention_flops(config: Dict[str, Any], batch: int, seq: int) -> float:
    """The same for the windowed layers alone."""
    return _attention_flops(config, batch, seq, full_layers=False)
