"""granitemoehybrid: everything the benchmark knows of one block, the decoder of
ibm-granite/granite-4.0-h-micro: Mamba-2 state-space layers and attention
layers nine to one, a dense SwiGLU unit in every layer, four multipliers and a
head that is the embedding. Found by a configuration's ``"model_type":
"granitemoehybrid"``; nothing outside this file names the program's model
class, a parameter path or a width's key.

The equations the float32 reference is written from (HF ``GraniteMoeHybrid``,
whose Mamba layer is Bamba's Mamba-2; ``d`` hidden, ``I = mamba_expand x d``,
``H`` heads of ``P``, state ``N``, ``G`` groups):

1. ``x0 = embedding_multiplier * E[tokens]``.
2. A layer of either kind: ``a = x + residual_multiplier * mixer(RMSNorm(x))``;
   ``y = a + residual_multiplier * W_out(silu(g) * u)`` with ``[g, u] =
   W_in(RMSNorm(a))``, ``shared_intermediate_size`` each, gate first.
3. ``layer_types[l] == "attention"``: q, k, v, o without bias, heads of
   ``d / num_attention_heads``, NO positional encoding, ``softmax(q k^T *
   attention_multiplier + causal) v``.
4. ``layer_types[l] == "mamba"``: ``[z (I), xBC (I + 2GN), dt (H)] = W_inproj
   h``; ``xBC = silu(conv(xBC))``, depthwise and causal, ``out[t] = b + sum_j
   w[:, j] * in[t - (mamba_d_conv - 1) + j]``, zeros before the sequence;
   ``[x (I), B (GN), C (GN)] = xBC``; ``dt = softplus(dt + dt_bias)``, ``A =
   -exp(A_log)``, both by head. Per head, ``x_t`` in R^P: ``S_t = exp(dt_t A)
   S_{t-1} + dt_t x_t B_t^T``, ``S_{-1} = 0``; ``y_t = S_t C_t + D x_t``. Then
   ``y = RMSNorm over I (y * silu(z)) * w_norm`` (the gate BEFORE the norm, one
   group over all of I) and ``W_outproj``.
5. ``logits = RMSNorm(x_L) E^T / logits_scaling`` over the held rows of ``E``;
   mean next-token cross-entropy.

The reference computes 4 from the recurrence's closed form, never from the
chunked algorithm the program runs: unrolled, ``S_t = sum_{u <= t} exp(A (c_t -
c_u)) dt_u x_u B_u^T`` with ``c`` the running sum of ``dt``, so ``y_t = sum_{u
<= t} exp(A (c_t - c_u)) (C_t . B_u) dt_u x_u + D x_t``: a masked (s x s)
matrix a head, in blocks of rows against the whole row of earlier positions,
each block recomputed in the gradient. It shares no function with
torchft_tpu/ops/ssd.py.

What is assumed, because the config does not say it, is listed in the
configuration file under ``assumed``.

An architecture file gives ``build``, ``sequence_loss``, ``parameter_counts``,
``train_flops_per_token``; this one also what the state-space scan and its
convolution NEED for a training step (``ssd_flops``, ``ssd_bytes``), which
ops of a device trace are theirs (``ssd_seconds``), and for the attention
layers what a device trace calls their kernels (``ATTENTION_KERNEL``) and the
operations they need (``mixed_attention_flops``), which the readers
``mixed_attn_time_pct`` and ``mixed_attn_mxu_pct`` ask for by these names.
"""

from __future__ import annotations

import functools
import re
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench import reference


def _kinds(config: Dict[str, Any]):
    """The kind of every layer that is held: the first ``num_hidden_layers``
    entries of the published ``layer_types``."""
    return config["layer_types"][: config["num_hidden_layers"]]


def _mamba_sizes(config: Dict[str, Any]):
    """(inner width I, heads H, head width P, state N, groups G)."""
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    inner = config["mamba_expand"] * config["hidden_size"]
    if inner != heads * p:
        raise ValueError(f"{heads} heads of {p} are not mamba_expand x hidden_size = {inner}")
    return inner, heads, p, config["mamba_d_state"], config["mamba_n_groups"]


def build(config: Dict[str, Any], seq: int):
    """The program's model for a configuration file as it is run: ``init(key,
    tokens)`` and ``apply(params, inputs, targets=...)``, the scalar loss."""
    from torchft_tpu.models.granite import Granite, GraniteConfig

    run = config["run"]
    if not config["tie_word_embeddings"] or config["position_embedding_type"] != "nope":
        raise ValueError("models/granite.py: a tied head, no positional encoding")
    if config["num_local_experts"] or config["attention_bias"] or config["mamba_proj_bias"]:
        raise ValueError("models/granite.py: a dense unit, no bias but the convolution's")
    if not config["mamba_conv_bias"] or config["hidden_act"] != "silu":
        raise ValueError("models/granite.py: a convolution with bias, SwiGLU")
    inner, heads, p, state, groups = _mamba_sizes(config)
    return Granite(GraniteConfig(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        layer_types=tuple(_kinds(config)),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        mlp_hidden=config["shared_intermediate_size"],
        mamba_heads=heads,
        mamba_head_dim=p,
        mamba_state=state,
        mamba_groups=groups,
        mamba_conv=config["mamba_d_conv"],
        mamba_chunk=config["mamba_chunk_size"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(run["dtype"]),
        norm_dtype=jnp.dtype(run["norm_dtype"]),
        attention_impl=run["attention_impl"],
        remat=run["remat"],
        loss_vocab_chunk=run["loss_vocab_chunk"],
        scan_layers=run["scan_layers"],
        init_depth=config["published"]["num_hidden_layers"],
    ))


# -- the float32 reference ----------------------------------------------------

# Rows of one block of the state-space layer's masked matrix, as a share of
# ``reference.QUERY_BLOCK`` (which a rehearsal sets): a block of rows against
# the whole sequence is (heads x block x s) float32 decays, at 64 x 128 x 8192
# a quarter of a GiB, and the gradient holds a few of them at a time.
SCAN_BLOCKS_IN_A_QUERY_BLOCK = 16


def _attention(h, w, config: Dict[str, Any]):
    """h: (s, d). Grouped-query causal attention without positions, scale
    ``attention_multiplier``: ``reference.causal_attention`` scales by
    head_dim^-0.5, so q carries the rest."""
    q = jnp.einsum("sd,dhk->shk", h, w["wq"])
    k = jnp.einsum("sd,dhk->shk", h, w["wk"])
    v = jnp.einsum("sd,dhk->shk", h, w["wv"])
    group = q.shape[1] // k.shape[1]
    q = q * (float(config["attention_multiplier"]) * q.shape[-1] ** 0.5)
    out = reference.causal_attention(q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1))
    return jnp.einsum("shk,hkd->sd", out, w["wo"])


def _convolution(x, kernel, bias):
    """x: (s, channels); kernel (channels, width). ``out[t] = bias + sum_j
    kernel[:, j] x[t - (width - 1) + j]``, zeros before the sequence."""
    s, width = x.shape[0], kernel.shape[1]
    front = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    taps = jnp.stack([front[j: j + s] for j in range(width)], axis=-1)  # (s, channels, width)
    return bias + jnp.sum(taps * kernel, axis=-1)


def _selective_scan(x, dt, a, b_in, c_out, d_skip):
    """The recurrence's closed form for one sequence. x (s, H, P), dt (s, H),
    a (H,), b_in and c_out (s, G, N), d_skip (H,). Row t of head h: ``sum_{u <=
    t} exp(a_h (c_t - c_u)) (C_t . B_u) dt_u x_u + D x_t``, ``c`` the running
    sum of ``dt``; rows in blocks, each against every position of the
    sequence under the mask."""
    s, heads, _ = x.shape
    groups = b_in.shape[1]
    run = jnp.cumsum(dt, axis=0)  # (s, H)
    fed = x * dt[..., None]  # dt_u x_u
    block = max(1, reference.QUERY_BLOCK // SCAN_BLOCKS_IN_A_QUERY_BLOCK)

    def rows(first, run_rows, c_rows):
        at = first + jnp.arange(run_rows.shape[0])
        seen = at[:, None] >= jnp.arange(s)[None, :]  # (rows, s)
        apart = run_rows[:, None, :] - run[None, :, :]  # (rows, s, H): c_t - c_u
        decay = jnp.exp(jnp.where(seen[..., None], a * apart, -jnp.inf))
        alike = jnp.einsum("tgn,ugn->tug", c_rows, b_in)  # C_t . B_u, the heads of a group alike
        weights = decay * jnp.repeat(alike, heads // groups, axis=2)
        return jnp.einsum("tuh,uhp->thp", weights, fed)

    y = reference._in_blocks(rows, block, run, c_out).reshape(x.shape)
    return y + d_skip[:, None] * x


def _gated_norm(y, z, scale, eps: float):
    """The gate BEFORE the norm, one group over the whole inner width."""
    return reference.rms_norm(y * jax.nn.silu(z), scale, eps)


def _mamba(h, w, config: Dict[str, Any]):
    """h: (s, d), one sequence through a Mamba-2 mixer."""
    inner, heads, p, state, groups = _mamba_sizes(config)
    s = h.shape[0]
    parts = h @ w["in_proj"]
    z, xbc, dt = parts[:, :inner], parts[:, inner: -heads], parts[:, -heads:]
    xbc = jax.nn.silu(_convolution(xbc, w["conv_kernel"], w["conv_bias"]))
    x, b_in, c_out = (
        xbc[:, :inner], xbc[:, inner: inner + groups * state], xbc[:, inner + groups * state:],
    )
    y = _selective_scan(
        x.reshape(s, heads, p), jax.nn.softplus(dt + w["dt_bias"]), -jnp.exp(w["A_log"]),
        b_in.reshape(s, groups, state), c_out.reshape(s, groups, state), w["D"],
    )
    eps = float(config["rms_norm_eps"])
    return _gated_norm(y.reshape(s, inner), z, w["norm"], eps) @ w["out_proj"]


def _block(x, w, config: Dict[str, Any], kind: str):
    eps, by = float(config["rms_norm_eps"]), float(config["residual_multiplier"])
    mixer = _attention if kind == "attention" else _mamba
    x = x + by * mixer(reference.rms_norm(x, w["mixer_norm"], eps), w, config)
    gate, up = jnp.split(reference.rms_norm(x, w["mlp_norm"], eps) @ w["w_in"], 2, axis=-1)
    return x + by * ((jax.nn.silu(gate) * up) @ w["w_out"])


def _weights(block: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
    """A layer's leaves in float32 by the names the equations use, from the
    system's tree of one layer."""
    f32 = lambda a: a.astype(jnp.float32)
    out = {
        "mixer_norm": f32(block["mixer_norm"]["scale"]), "mlp_norm": f32(block["mlp_norm"]["scale"]),
        "w_in": f32(block["mlp"]["w_in"]["kernel"]), "w_out": f32(block["mlp"]["w_out"]["kernel"]),
    }
    if "attn" in block:
        out.update({name: f32(block["attn"][name]["kernel"]) for name in ("wq", "wk", "wv", "wo")})
        return out
    mamba = block["mamba"]
    vectors = ("conv_kernel", "conv_bias", "A_log", "dt_bias", "D")
    out.update({name: f32(mamba[name]) for name in vectors})
    out.update({
        "in_proj": f32(mamba["in_proj"]["kernel"]), "out_proj": f32(mamba["out_proj"]["kernel"]),
        "norm": f32(mamba["norm"]["scale"]),
    })
    return out


def _layer(tree: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """Layer ``layer`` of the system's tree, whichever layout it has: inlined
    (``layer_<i>``) or scanned by period (``layers/block_<kind>``, a leading
    axis of periods)."""
    if "layers" not in tree:
        return tree[f"layer_{layer}"]
    stack = tree["layers"]
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
    table = tree["tok_embed"]["embedding"].astype(jnp.float32)
    x = float(config["embedding_multiplier"]) * table[tokens[:-1]]
    for layer, kind in enumerate(_kinds(config)):
        block = functools.partial(_block, config=config, kind=kind)
        if recompute:
            block = jax.checkpoint(block)
        x = block(x, _weights(_layer(tree, layer)))
    scale = tree["final_norm"]["scale"].astype(jnp.float32)
    x = reference.rms_norm(x, scale, float(config["rms_norm_eps"]))
    return reference.next_token_loss_sum(x / float(config["logits_scaling"]), table.T, tokens[1:])


# -- what is counted -----------------------------------------------------------


def parameter_counts(config: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of the model as it is run (depth and the vocabulary's slice
    as the file has them). ``matmul``: the matrices a token is multiplied by,
    the tied matrix once, as the head (the gather is no product)."""
    d, f = config["hidden_size"], config["shared_intermediate_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    inner, mamba_heads, _, state, groups = _mamba_sizes(config)
    conv = inner + 2 * groups * state
    kinds = _kinds(config)
    n_mamba = sum(kind == "mamba" for kind in kinds)
    n_attention = len(kinds) - n_mamba
    hd = d // heads
    attention = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    mamba_matrices = d * (inner + conv + mamba_heads) + inner * d
    mamba = mamba_matrices + conv * config["mamba_d_conv"] + conv + 3 * mamba_heads + inner
    unit = 3 * d * f
    vocab = config["vocab_size"]
    return {
        "attention": attention,
        "mamba": mamba,
        "unit": unit,
        "attention_layer": attention + unit + 2 * d,
        "mamba_layer": mamba + unit + 2 * d,
        "embedding": vocab * d,
        "total": n_mamba * (mamba + unit + 2 * d) + n_attention * (attention + unit + 2 * d)
        + vocab * d + d,
        "matmul": n_mamba * (mamba_matrices + unit) + n_attention * (attention + unit) + vocab * d,
    }


def ssd_forward_flops_per_token(config: Dict[str, Any]) -> float:
    """Operations the chunked scan's matmuls need for one token of one layer,
    forward, at the PUBLISHED chunk Q whatever the program runs: the products
    inside a chunk over the causal pairs alone, Q (Q + 1) / 2 of them a chunk,
    so (Q + 1) x 2N / 2 a token for ``C B^T`` (once a group) and (Q + 1) x 2P /
    2 a head for the masked scores times x; 2 P N a head for the chunk's
    state and 2 P N for its contribution to the outputs."""
    _, heads, p, state, groups = _mamba_sizes(config)
    q = config["mamba_chunk_size"]
    return groups * (q + 1) * state + heads * ((q + 1) * p + 4 * p * state)


def ssd_flops(config: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations the scan NEEDS for one training step: forward, and twice
    that for the backward (a product's two gradients), in every Mamba layer.
    Needed work at the published chunk, not work done: a program that
    recomputes its forward in the backward, or computes the masked half of a
    chunk's scores, spends time and not need."""
    layers = sum(kind == "mamba" for kind in _kinds(config))
    return 3.0 * ssd_forward_flops_per_token(config) * layers * batch * seq


def ssd_bytes(config: Dict[str, Any], batch: int, seq: int) -> float:
    """Bytes the convolution and the scan must move for one training step, in
    the run dtype, every Mamba layer: what ONE kernel a direction that runs
    the convolution and the scan together would read and write, and nothing
    that only passes from the one to the other. Forward: xBC as the
    in-projection left it and dt are read, y is written. Backward: xBC and dt
    are read again (the convolution, its silu and the scan's products are
    redone from them), y's gradient is read, the gradients of xBC and dt are
    written. The convolution's output, the states, decays and scores stay on
    chip in such a kernel; what a program writes and reads back of them (this
    PR's does: the convolution's output, x and y by chunk, the decay matrices)
    is time, not need."""
    inner, heads, _, state, groups = _mamba_sizes(config)
    size = jnp.dtype(config["run"]["dtype"]).itemsize
    read = inner + 2 * groups * state + heads  # xBC and dt
    layers = sum(kind == "mamba" for kind in _kinds(config))
    return float(size * (3 * read + 2 * inner) * layers * batch * seq)


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """Forward + backward operations one trained token requires: 6 * N_matmul
    (the projections of both kinds of mixer, the unit, the tied matrix once;
    no gather), the scan's 3 x forward in every Mamba layer, and 12 * (heads x
    head_dim) * seq in every attention layer (the PaLM convention). What the
    program recomputes is time, not need."""
    kinds = _kinds(config)
    n_mamba = sum(kind == "mamba" for kind in kinds)
    return (
        6.0 * parameter_counts(config)["matmul"]
        + 3.0 * ssd_forward_flops_per_token(config) * n_mamba
        + 12.0 * config["hidden_size"] * seq * (len(kinds) - n_mamba)
    )


# What XLA calls the attention layers' two Mosaic calls in a device trace: the
# scope models/granite.py traces ``attend`` under, dots and colons to
# underscores, and a number (forward and the one backward call alike).
ATTENTION_KERNEL = re.compile(r"tpuft__nope_attention")


def mixed_attention_flops(config: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations attention NEEDS for one training step in the layers that
    attend (one in ten; the others are Mamba-2 and have none): seven matmuls
    (two forward, five backward with the one recomputation of the scores, as
    ``flops.flash_attention_flops`` counts them) of 2 x head_dim x heads over
    the s (s + 1) / 2 pairs the causal mask allows. Needed pairs, not the
    blocks a kernel walks."""
    layers = sum(kind == "attention" for kind in _kinds(config))
    return 7.0 * 2.0 * config["hidden_size"] * (seq * (seq + 1) / 2) * layers * batch


def _sizes(name: str):
    """The sizes of an op's first result, those of 1 dropped."""
    shape = re.search(r"\[([0-9,]*)\]", name)
    return [n for n in map(int, shape.group(1).split(",")) if n > 1] if shape and shape.group(1) else []


# A Mosaic call of the scan's, should a later PR bring one: found by name.
SSD_KERNEL = re.compile(r"ssd|mamba_conv")


def ssd_seconds(trace: Dict[str, Any], config: Dict[str, Any], batch: int, seq: int) -> float:
    """Device seconds of the state-space scan and its convolution in a
    reduced trace, forward and backward, NOT the projections. Since PR 58 the
    program's path on a TPU is four Mosaic calls (ops/ssd.py: ``mamba_conv_fwd``
    / ``mamba_conv_bwd`` / ``ssd_fwd`` / ``ssd_bwd``), found by NAME
    (``SSD_KERNEL``); what stays plain XLA around them, and the whole einsum
    path of PR 57 or of another platform, is found by the result shapes only
    that path produces. XLA orders and folds the leading
    dimensions (batch, chunks, heads) as it likes, so a shape is one of the
    path's where its ELEMENT COUNT is a family's and the family's telling
    sizes are among its dimensions, with b the batch, c = seq / Q chunks, H
    heads of P, N the state, G groups and W = I + 2 G N the convolution's
    channels:

    - ``b c H Q Q`` elements with two dimensions of Q: the decay matrices, the
      masked weights and their gradients; ``b c G Q Q`` with two of Q: a
      chunk's scores ``C B^T`` and their gradient;
    - ``b c H P N`` with P and N: the chunk states, the states entering a
      chunk and their gradients; ``b c c H`` with two of c: the decays between
      chunks;
    - ``b s H P`` with Q: x and y BY CHUNK and their gradients (the same
      elements as ``(b, s, I)`` are the projections' and the gated norm's, and
      are not taken); ``b s H`` with Q: the running sums and dt by chunk;
      ``b s G N`` with Q and N: B and C by chunk;
    - ``b s W`` and ``b (s + width - 1) W`` with W: the convolution's output,
      its padded input, and their gradients.

    NOT seen, and so counted as other time: an op whose first result has
    another shape, which is what XLA makes of a fusion that ends in the
    projections' own shapes (the gradient of xBC written straight into the
    in-projection's ``(b s, 2 I + 2 G N + H)`` gradient; y's cast fused into
    the gated norm, ``(b, s, I)``), and the few ops on ``(c, H)`` totals; so the
    time share reads a little low and the share of the roofline a little
    high. The shapes are THIS path's: another chunking, a fusion XLA draws
    otherwise or a kernel in the path's place moves ops in or out of sight,
    and a reading is comparable with another reading of the same path only. A
    Mosaic call is found by NAME instead (``SSD_KERNEL``). The scopes
    ``tpuft::ssd::*`` and ``tpuft::mamba::conv`` name the same ops in the
    profile's ``op_name``, which ``trace_reduce`` does not keep."""
    inner, heads, p, state, groups = _mamba_sizes(config)
    q = min(config["mamba_chunk_size"], seq)
    chunks = -(-seq // q)
    rows, width = batch * chunks * q, inner + 2 * groups * state
    families = [  # (elements, the sizes that must be among the dimensions)
        (rows * heads * q, [q, q]), (rows * groups * q, [q, q]),
        (batch * chunks * heads * p * state, [p, state]),
        (batch * chunks * chunks * heads, [chunks, chunks]),
        (rows * heads * p, [q]), (rows * heads, [q]), (rows * groups * state, [q, state]),
        (batch * seq * width, [width]),
        (batch * (seq + config["mamba_d_conv"] - 1) * width, [width]),
    ]

    def mine(name: str) -> bool:
        sizes = _sizes(name)
        count = 1
        for n in sizes:
            count *= n
        return any(
            count == elements and all(sizes.count(n) >= telling.count(n) for n in telling)
            for elements, telling in families
        )

    return sum(s for name, s in trace.get("ops", []) if SSD_KERNEL.search(name) or mine(name))
