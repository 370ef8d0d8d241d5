"""KeyeVL2: everything the benchmark knows of one block, the language model of
Kwai-Keye/Keye-VL-2.0-30B-A3B as ONE CHIP'S SHARE of an expert-parallel
deployment. Found by a configuration's ``"model_type": "KeyeVL2"``; nothing
outside this file names the program's model class, a parameter path or a width's
key. The vision tower is not built; with text-only positions the three sections
of ``rope_scaling.mrope_section`` see one position, which is plain rotary.

The equations the float32 reference is written from (per layer, pre-norm,
``rms_norm_eps``, ``rope_theta``; ``h`` is the layer's normed input):

1. ``q = h Wq`` (heads x head_dim), ``k = h Wk``, ``v = h Wv`` (kv heads x
   head_dim); RMSNorm over each head's ``head_dim`` of q and of k; rotary
   (rotate-half) on both; scale ``head_dim ** -0.5``.
2. Indexer, from the same ``h``: ``qI = h WqI`` (indexer heads x indexer
   head_dim), ``kI = LayerNorm(h WkI)`` (one key head), rotary on both,
   ``w = h Ww * (indexer heads) ** -0.5 * (indexer head_dim) ** -0.5``;
   ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])`` for ``s <= t``.
3. ``S_t`` = the ``sa_config.topk`` largest ``I[t, s]`` with ``s <= t`` (all of
   them while there are no more), ties to the earlier key; one selection a
   query, shared by all heads. It carries no gradient.
4. ``a = softmax over S_t of (q . k)``; ``x <- x + (a v) Wo``.
5. ``h = RMSNorm(x)``; ``p = softmax(h Wr)`` over all ``num_experts``; ``E_t``
   its ``num_experts_per_tok`` largest, weights ``p / sum over E_t``;
   ``x <- x + sum over e in E_t that are held of weight_e *
   (silu(h Wg_e) * (h Wu_e)) Wd_e``. Held are the experts ``expert_share *
   num_local_experts ..``; what the others would add is left out.
6. Final RMSNorm, untied head over the held ``vocab_size`` rows, mean
   next-token cross-entropy.

Departures from the published mechanism, in program and reference alike:
(a) the published indexer is trained by a term of its own against the
attention's distribution, whose weight and schedule ``config.json`` does not
give; it is left out, so the language-model loss gives the indexer's three
matrices and its LayerNorm gradient zero (``jax.grad`` of this reference says
the same: top-k is piecewise constant) and AdamW's decay alone moves them;
(b) ``config.json`` has no router auxiliary coefficient: no load-balance term.
Assumed, since the config does not say: the LayerNorm on ``kI``, rotary on
``qI`` and ``kI`` over the whole indexer head, the scale of ``w``, and that
``q_chunk_size`` / ``kv_chunk_size`` are tiles of computation, not the unit of
selection (the other reading, a selection of whole chunks, exists).

An architecture file gives ``build``, ``sequence_loss``, ``parameter_counts``,
``train_flops_per_token``; this one also what a device trace calls its two
kernels (``EXPERT_KERNEL``, ``selected_attention_seconds``), the operations
attention under the selection needs (``selected_attention_flops``; the expert
product has no such count: what it needs follows the rows that arrive, which
no reader can see), and ``selections`` for the diagnostic that counts how far the program's
selection is from this reference's (scripts/keye_selection_check.py).
"""

from __future__ import annotations

import functools
import re
from pathlib import Path
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench import reference
from chipbench.spec import load_module

# A Pallas call of the step program whose name in the device trace holds this
# is the grouped expert product (torchft_tpu/ops/grouped_matmul.py): XLA names
# the Mosaic calls after megablox's jitted functions, ``gmm.<n>`` (forward and
# the rows' gradient) and ``tgmm.<n>`` (the weights' gradient) in the step
# program (my chip run, PR 46), and by longer names that still hold ``gmm``
# where the product is differentiated on its own.
EXPERT_KERNEL = re.compile(r"gmm")
# Every Mosaic call of the expert layer: the grouped product's, which
# ``expert_time_pct`` reads in this cell, and since PR 51 the two sums by token
# (``sum_by_token.<n>``, ``transpose_jvp_sum_by_token__.<n>``). A Pallas call of
# the step programs under neither name is selected attention's.
EXPERT_LAYER_KERNEL = re.compile(r"gmm|sum_by_token")
_SPARSE_FLASH = load_module(Path(__file__).parents[1] / "layer_metrics" / "sparse_flash_time_pct.py")


def build(config: Dict[str, Any], seq: int):
    """The program's model for a configuration file as it is run: ``init(key,
    tokens)`` and ``apply(params, inputs, targets=...)``, the scalar loss."""
    from torchft_tpu.models.keye import Keye, KeyeConfig

    run, sa = config["run"], config["sa_config"]
    if config["tie_word_embeddings"] or config["mlp_only_layers"] or config["decoder_sparse_step"] != 1:
        raise ValueError("models/keye.py: untied head, an expert layer in every block")
    return Keye(KeyeConfig(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        moe_hidden=config["moe_intermediate_size"],
        num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        num_local_experts=config["num_local_experts"],
        expert_share=config["expert_share"],
        indexer_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"],
        indexer_dtype=jnp.dtype(run["indexer_dtype"]),
        topk=sa["topk"],
        select_block=sa["q_chunk_size"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(run["dtype"]),
        norm_dtype=jnp.dtype(run["norm_dtype"]),
        remat=run["remat"],
        loss_vocab_chunk=run["loss_vocab_chunk"],
        scan_layers=run["scan_layers"],
        init_depth=config["published"]["num_hidden_layers"],
    ))


# -- the float32 reference ----------------------------------------------------


def _layer_norm(x, scale, bias, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _selection(first, qi_rows, w_rows, ki, topk: int):
    """(rows, s) bool: for the queries at ``first ..``, the ``topk`` earlier
    keys of highest index score, ties to the earlier key (``lax.top_k`` lists
    the lower index first among equals)."""
    s = ki.shape[0]
    dots = jnp.einsum("tje,se->tjs", qi_rows, ki)
    index = jnp.sum(w_rows[:, :, None] * jax.nn.relu(dots), axis=1)
    index = jnp.where(index == 0, 0.0, index)  # -0.0 and 0.0 tie
    at = first + jnp.arange(qi_rows.shape[0])
    causal = at[:, None] >= jnp.arange(s)[None, :]
    _, best = jax.lax.top_k(jnp.where(causal, index, -jnp.inf), min(topk, s))
    chosen = jnp.zeros(causal.shape, bool).at[jnp.arange(best.shape[0])[:, None], best].set(True)
    return chosen & causal  # a query with fewer earlier keys than topk takes them all


def _indexer(h, w, config: Dict[str, Any]):
    sa, eps = config["sa_config"], float(config["rms_norm_eps"])
    theta = float(config["rope_theta"])
    qi = jnp.einsum("sd,dje->sje", h, w["indexer_wq"])
    ki = _layer_norm(h @ w["indexer_wk"], w["indexer_k_scale"], w["indexer_k_bias"], eps)
    qi = reference.rotary(qi, theta)
    ki = reference.rotary(ki[:, None, :], theta)[:, 0, :]
    weights = (h @ w["indexer_weights"]) * (
        sa["indexer_num_heads"] ** -0.5 * sa["indexer_head_dim"] ** -0.5
    )
    return qi, ki, weights


def _attention(h, w, config: Dict[str, Any], watch: bool = False):
    """h: (s, d). Grouped-query attention of one sequence, each query over the
    keys its indexer selects; in blocks of ``reference.QUERY_BLOCK`` queries,
    each against the whole row of keys, as ``reference.causal_attention``.
    With ``watch`` also the selection itself, (s, s) bool."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    hd, topk = config["head_dim"], config["sa_config"]["topk"]
    q = jnp.einsum("sd,dhk->shk", h, w["wq"])
    k = jnp.einsum("sd,dhk->shk", h, w["wk"])
    v = jnp.einsum("sd,dhk->shk", h, w["wv"])
    q = reference.rotary(reference.rms_norm(q, w["q_norm"], eps), theta)
    k = reference.rotary(reference.rms_norm(k, w["k_norm"], eps), theta)
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    qi, ki, weights = _indexer(h, w, config)

    def rows(first, q_rows, qi_rows, w_rows):
        chosen = _selection(first, qi_rows, w_rows, ki, topk)
        scores = jnp.einsum("shk,thk->hst", q_rows, k) * hd**-0.5
        probs = jax.nn.softmax(jnp.where(chosen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hst,thk->shk", probs, v)

    chosen = None
    if watch:
        pick = lambda first, qi_rows, w_rows: _selection(first, qi_rows, w_rows, ki, topk)
        chosen = reference._in_blocks(pick, reference.QUERY_BLOCK, qi, weights)
        chosen = chosen.reshape(q.shape[0], q.shape[0])
    out = reference._in_blocks(rows, reference.QUERY_BLOCK, q, qi, weights).reshape(q.shape)
    return jnp.einsum("shk,hkd->sd", out, w["wo"]), chosen


def _experts(h, w, config: Dict[str, Any]) -> jnp.ndarray:
    """The held experts' part of the routed layer, one expert at a time over
    every token, weighed by the token's gate for it (zero where the router did
    not choose it)."""
    local, share = config["num_local_experts"], config["expert_share"]
    probs = jax.nn.softmax(h @ w["router"], axis=-1)  # over ALL experts
    top, chosen = jax.lax.top_k(probs, config["num_experts_per_tok"])
    gates = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], chosen].set(
        top / jnp.sum(top, axis=-1, keepdims=True)
    )
    held = gates[:, share * local: (share + 1) * local]

    def add(out, expert):
        gate, w_gate, w_up, w_down = expert
        return out + gate[:, None] * ((jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down), None

    # One program for the 16 experts (a loop in the compiled program, so that
    # the reference compiles in a minute and not in seven).
    return jax.lax.scan(add, jnp.zeros_like(h), (held.T, w["w_gate"], w["w_up"], w["w_down"]))[0]


def _block(x, w, config: Dict[str, Any], watch: bool = False):
    eps = float(config["rms_norm_eps"])
    attended, chosen = _attention(reference.rms_norm(x, w["attn_norm"], eps), w, config, watch)
    x = x + attended
    return x + _experts(reference.rms_norm(x, w["mlp_norm"], eps), w, config), chosen


def _weights(block: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
    """A layer's matrices in float32 by the names the equations use, from the
    system's tree of one layer (or of the stacked layers: the same names)."""
    f32 = lambda a: a.astype(jnp.float32)
    attn, moe = block["attn"], block["moe"]
    return {
        "wq": f32(attn["wq"]["kernel"]), "wk": f32(attn["wk"]["kernel"]),
        "wv": f32(attn["wv"]["kernel"]), "wo": f32(attn["wo"]["kernel"]),
        "q_norm": f32(attn["q_norm"]["scale"]), "k_norm": f32(attn["k_norm"]["scale"]),
        "indexer_wq": f32(attn["indexer"]["wq"]["kernel"]),
        "indexer_wk": f32(attn["indexer"]["wk"]["kernel"]),
        "indexer_weights": f32(attn["indexer"]["weights"]["kernel"]),
        "indexer_k_scale": f32(attn["indexer"]["k_norm"]["scale"]),
        "indexer_k_bias": f32(attn["indexer"]["k_norm"]["bias"]),
        "attn_norm": f32(block["attn_norm"]["scale"]),
        "mlp_norm": f32(block["mlp_norm"]["scale"]),
        "router": f32(moe["router"]["kernel"]),
        "w_gate": f32(moe["w_gate"]), "w_up": f32(moe["w_up"]), "w_down": f32(moe["w_down"]),
    }


def _hidden(params, tokens, config, recompute: bool, watch: bool = False):
    """The final normed hidden states (s, d) of one sequence, and with
    ``watch`` every layer's selection (layers, s, s). Either layout of the
    system's tree: stacked under ``layers/block`` (scanned) or ``layer_<i>``.
    The layers are written out one after the other (a loop over them in the
    compiled program compiles in a third of the time and needs 2.4 GiB more,
    which at this size the chip has not: chipless compiles, PR 46)."""
    tree = params["params"]
    x = tree["tok_embed"]["embedding"].astype(jnp.float32)[tokens[:-1]]
    block = functools.partial(_block, config=config, watch=watch)
    if recompute:
        block = jax.checkpoint(block)
    chosen = []
    for layer in range(config["num_hidden_layers"]):
        if "layers" in tree:
            one = jax.tree_util.tree_map(lambda a: a[layer], tree["layers"]["block"])
        else:
            one = tree[f"layer_{layer}"]
        x, selected = block(x, _weights(one))
        chosen.append(selected)
    chosen = jnp.stack(chosen) if watch else None
    scale = tree["final_norm"]["scale"].astype(jnp.float32)
    return reference.rms_norm(x, scale, float(config["rms_norm_eps"])), chosen


def sequence_loss(
    params: Dict[str, Any], tokens: jnp.ndarray, config: Dict[str, Any], recompute: bool = False
):
    """Sum of next-token cross-entropies of ONE sequence ``tokens`` (s + 1,):
    everything the training loss sums for it (this block has no other term: see
    the departures above). ``recompute`` changes memory and no number."""
    x, _ = _hidden(params, tokens, config, recompute)
    head = params["params"]["lm_head"]["kernel"].astype(jnp.float32)
    return reference.next_token_loss_sum(x, head, tokens[1:])


def selections(params: Dict[str, Any], tokens: jnp.ndarray, config: Dict[str, Any]) -> jnp.ndarray:
    """(layers, s, s) bool: the keys each query of ONE sequence ``tokens``
    (s + 1,) selects in every layer of this reference, on its own float32 hidden
    states. Call it under ``jax.default_matmul_precision("highest")``."""
    return _hidden(params, tokens, config, False, watch=True)[1]


# -- what is counted -----------------------------------------------------------


def parameter_counts(config: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of the model as it is run (depth, experts held and the
    vocabulary's slice as the file has them)."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    heads, kv, hd = (
        config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"],
    )
    sa = config["sa_config"]
    layers, vocab = config["num_hidden_layers"], config["vocab_size"]
    attention = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    indexer = d * sa["indexer_num_heads"] * sa["indexer_head_dim"] + d * sa["indexer_head_dim"] \
        + d * sa["indexer_num_heads"]
    router = d * config["num_experts"]
    expert = 3 * d * f
    held = config["num_local_experts"] * expert
    small = 2 * d + 2 * hd + 2 * sa["indexer_head_dim"]  # the norms' scales and one bias
    per_layer = attention + indexer + router + held + small
    # An expert-equivalent a token: the router's choices that land on this
    # chip's share. Over the eight shares of a layer it is exact whatever the
    # routing (every token's 8 choices land somewhere); for ONE share it is an
    # expectation under near-uniform routing, which seeded weights give and
    # training on random tokens leaves (PERF.md section 6, PR 46): this
    # share's own count runs from 0 to 2 as the cell trains.
    active = config["num_experts_per_tok"] * config["num_local_experts"] / config["num_experts"]
    return {
        "per_layer": per_layer,
        "experts": layers * held,
        "embedding": vocab * d,
        "head": vocab * d,
        "total": layers * per_layer + 2 * vocab * d + d,
        "matmul": layers * (attention + indexer + router + active * expert) + vocab * d,
    }


def selected_pairs(seq: int, topk: int) -> int:
    """Sum over the queries of one sequence of the keys each selects."""
    full = min(seq, topk)
    return full * (full + 1) // 2 + (seq - full) * topk


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """Forward + backward operations one trained token requires: 6 * N_matmul
    (attention, the indexer's three matrices, the router, the expectation of
    one held expert-equivalent a token, the head; no embedding gather) +
    12 * L * (heads x head_dim) * min(s, topk), the PaLM convention over the
    keys a query may select at most (not the causal half) + the index scores,
    forward only (they carry no gradient). What the program computes beyond
    that (pairs under the mask, a tile recomputed in the backward) is time, not
    need."""
    sa, layers = config["sa_config"], config["num_hidden_layers"]
    width = config["num_attention_heads"] * config["head_dim"]
    attention = 12 * layers * width * min(seq, sa["topk"])
    index = 2 * layers * sa["indexer_num_heads"] * sa["indexer_head_dim"] * (seq + 1) / 2
    return 6.0 * parameter_counts(config)["matmul"] + attention + index


def selected_attention_flops(config: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations attention under the selection needs for ONE training step:
    seven matmuls over the SELECTED pairs (two forward, five backward with the
    one recomputation of the scores, as ``flops.flash_attention_flops`` counts
    dense attention), each 2 x pairs x head_dim x heads, plus the index scores
    over the causal pairs, forward only."""
    sa = config["sa_config"]
    pairs = selected_pairs(seq, sa["topk"])
    attention = 7 * 2.0 * pairs * config["head_dim"] * config["num_attention_heads"]
    index = 2.0 * (seq * (seq + 1) // 2) * sa["indexer_num_heads"] * sa["indexer_head_dim"]
    return (attention + index) * config["num_hidden_layers"] * batch


def selected_attention_seconds(trace: Dict[str, Any], config: Dict[str, Any], seq: int) -> float:
    """Device seconds of attention under the learned key selection in a reduced
    trace: the index scores, the per-row threshold and attention over the
    selected keys, forward and backward, WHATEVER implements any of them (since
    PR 64; from PR 47 to PR 63 this read the selection's XLA ops alone while
    ``selected_attention_flops`` kept counting attention's matmuls). Two kinds
    of op, and no op is of both (a kernel's name matches no shape below):

    1. The step programs' Pallas calls that are not the expert layer's
       (``EXPERT_LAYER_KERNEL``): since PR 47 the flash kernels that attend
       with the selection as an operand, ``attn.<n>`` in a trace, the calls
       ``sparse_flash_time_pct`` reads; a kernel that one day makes the
       selection would be counted here by the same rule.
    2. The selection's plain tiled XLA ops (``ops/sparse_attention.py``
       ``select_keys``), found by what only that path produces: a result shaped
       by a tile of queries (``sa_config.q_chunk_size``), alone or against a
       group's key length (a multiple of the tile):
       ``[.., tile, keys]`` (index scores, the threshold's masks; before PR 47
       also attention scores, probabilities and their gradients), ``[tile,
       indexer heads, keys]``, ``[kv heads, keys, tile, group]``, ``[1, tile, n,
       128]`` (a tile of queries or outputs by head, or a row of keys folded to
       lanes), ``[kv heads, group, tile]`` (row maxima and sums, which XLA fuses
       with the products that feed them), ``[kv heads, tile, group, 128]`` and
       its transpose (a tile's output), ``s32[tile]`` / ``u32[tile]`` (the radix
       select's counters), and ``[1, keys, kv heads, 128]`` for keys SHORTER
       than the sequence (a group's slice of k or v, and the gradient into it).

    NOT seen, and so counted as other time: XLA ops on the whole sequence's k
    and v (``[1, seq, kv heads, 128]``, which the projections' own ops share),
    so the time share reads a little low and the share of the peak a little high
    (my chip run, PR 46: PERF.md section 5 has the table by shape). The shapes
    are THIS path's: another tiling or a fusion XLA draws otherwise moves XLA
    ops in or out of sight, while a kernel in the path's place stays in sight
    under 1. The scopes ``tpuft::indexer`` and ``tpuft::sparse_attention`` name
    the same ops in the profile's ``op_name``, which ``trace_reduce`` does not
    keep (PERF.md section 7)."""
    sa = config["sa_config"]
    tile = min(sa["q_chunk_size"], seq)
    lengths = list(range(tile, seq + 1, tile))
    keys = "(?:" + "|".join(map(str, lengths)) + ")"
    shorter = "(?:" + "|".join(map(str, lengths[:-1] or lengths)) + ")"
    kv = config["num_key_value_heads"]
    group = config["num_attention_heads"] // kv
    mine = re.compile("|".join([
        rf"\[(?:\d+,)*{tile},{keys}\]", rf"\[(?:1,)?{tile},{sa['indexer_num_heads']},{keys}\]",
        rf"\[{kv},{keys},{tile},{group}\]", rf"\[1,{tile},\d+,128\]",
        rf"\[{kv},{group},{tile}\]", rf"\[{kv},{tile},{group},128\]", rf"\[{kv},128,{group},{tile}\]",
        rf"\b[su]32\[{tile}\]", rf"\[1,{shorter},{kv},128\]",
    ]))
    selection = sum(s for name, s in trace.get("ops", []) if mine.search(name))
    return selection + _SPARSE_FLASH.kernel_seconds_but(trace, EXPERT_LAYER_KERNEL)
