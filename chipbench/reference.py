"""Plain float32 reference, the part that no architecture owns: the norm, the
rotary embedding, causal attention and the next-token loss of one sequence as
helpers a block may call, and the mean loss, its gradient and the first
optimizer update of a batch, given the architecture.

An architecture (``<dir>/architectures/<model_type>.py``, found by the
configuration's ``model_type``) writes its block after its published
description in straightforward ``jax.numpy`` and gives ``sequence_loss(params,
tokens, config, recompute)``: everything the training loss sums for one
sequence, from the system's parameter tree, whose names are the only thing it
takes from the code under test. No kernels, no cache, no scan, no chunked
vocabulary; every multiplication in float32 under
``jax.default_matmul_precision("highest")``, because a TPU otherwise runs a
float32 matmul in bf16 passes.

To bound memory at the real widths the loss is taken one sequence at a time
(``lax.map`` over the batch), and within a sequence of more than one block of
positions in blocks: attention in blocks of ``QUERY_BLOCK`` query positions,
each against the whole masked row of keys (heads x block x s float32 scores:
2 GiB at 32 x 2048 x 8192, where the whole matrix would be 8), and the
loss head in blocks of ``HEAD_BLOCK`` positions (block x vocabulary logits).
Softmax is by row, so the mathematics is that of the unblocked matrix and
nothing is approximated; in the gradient each block is recomputed
(``jax.checkpoint``). A sequence of at most one block runs the unblocked
program. The block sizes are constants of the reference, not keys of a
configuration (a rehearsal, which runs toy sequences, sets its own).

The update (``grad_sum`` + ``first_adamw_step``, as one program in
``make_loss_after_first_update``) is the float32 gradient of that loss and
AdamW's first step from zero moments written out by hand (Loshchilov & Hutter,
arXiv:1711.05101, with Adam's bias correction): after one step the corrected
moments are g and g*g, so the step is ``p - lr * (g / (|g| + eps) + wd * p)``,
stored in the configuration's parameter dtype. No optimizer library, no
moments: what the system's second loss is held to.
For the gradient alone each block of the model is recomputed in the backward
pass (``jax.checkpoint``), which changes memory and no number.
"""

from __future__ import annotations

from types import ModuleType
from typing import Any, Dict

import jax
import jax.numpy as jnp

QUERY_BLOCK = 2048
HEAD_BLOCK = 2048


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (s, heads, head_dim). Angle of pair i at position p: p * theta^(-2i/hd)."""
    s, _, hd = x.shape
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    first, second = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate(
        [first * cos - second * sin, first * sin + second * cos], axis=-1
    )


def _in_blocks(fn, block: int, *rows: jnp.ndarray) -> jnp.ndarray:
    """``fn(first, *rows)`` over ``rows`` (each (s, ...)) cut into blocks of
    ``block`` positions, one block at a time and recomputed in the gradient;
    ``first`` is the block's first position. One call where s <= block."""
    s = rows[0].shape[0]
    if s <= block:
        return fn(0, *rows)
    if s % block:
        raise ValueError(f"a sequence of {s} positions is not whole blocks of {block}")
    cut = [r.reshape(s // block, block, *r.shape[1:]) for r in rows]
    return jax.lax.map(
        lambda args: jax.checkpoint(fn)(*args), (jnp.arange(0, s, block), *cut)
    )


def causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Softmax attention of one sequence under the causal mask. q, k, v:
    (s, heads, head_dim), positions already encoded, shared key/value heads
    already repeated; scale head_dim^-0.5. Returns (s, heads, head_dim)."""
    s, _, hd = q.shape

    def rows(first, q_rows):
        scores = jnp.einsum("shk,thk->hst", q_rows, k) * hd**-0.5
        at = first + jnp.arange(q_rows.shape[0])
        causal = at[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hst,thk->shk", probs, v)

    return _in_blocks(rows, QUERY_BLOCK, q).reshape(q.shape)


def next_token_loss_sum(x: jnp.ndarray, head: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Sum over the positions of one sequence of the cross-entropy of
    ``softmax(x @ head)`` against ``targets``. x: (s, d); head: (d, vocab)."""

    def rows(_first, x_rows, target_rows):
        logp = jax.nn.log_softmax(x_rows @ head, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, target_rows[:, None], axis=-1))

    return jnp.sum(_in_blocks(rows, HEAD_BLOCK, x, targets))


def make_loss(architecture: ModuleType, config: Dict[str, Any]):
    """``loss(params, tokens)``: mean next-token cross-entropy of a batch
    ``tokens`` (b, s + 1), float32 at the highest matmul precision."""

    @jax.jit
    def loss(params, tokens):
        with jax.default_matmul_precision("highest"):
            sums = jax.lax.map(
                lambda seq: architecture.sequence_loss(params, seq, config), tokens
            )
        return jnp.sum(sums) / (tokens.shape[0] * (tokens.shape[1] - 1))

    return loss


def grad_sum(architecture: ModuleType, params, tokens, config: Dict[str, Any]):
    """Float32 gradient of the SUM of token losses of ``tokens`` (n, s + 1)
    with respect to float32 ``params``, one sequence at a time."""
    grad = jax.grad(
        lambda p, seq: architecture.sequence_loss(p, seq, config, recompute=True)
    )

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
    (my chip run, PR 24). The rounding is spelled ``lax.reduce_precision``
    before the cast: XLA may keep excess precision through a float32 -> bf16
    -> float32 pair of casts and did, where the caller's program let it see
    the pair (one sequence a batch: my chip run, PR 27), and then the weights
    were never rounded; ``reduce_precision`` it must honour."""
    opt = config["optimizer"]
    lr, wd, eps = float(opt["learning_rate"]), float(opt["weight_decay"]), float(opt["eps"])
    stored_as = jnp.dtype(config["run"]["dtype"])
    bits = jnp.finfo(stored_as)

    def leaf(p, g_sum):
        g = g_sum / count
        stepped = p - lr * (g / (jnp.abs(g) + eps) + wd * p)
        return jax.lax.reduce_precision(stepped, bits.nexp, bits.nmant).astype(stored_as)

    return jax.tree_util.tree_map(leaf, params, grad_total)


def make_loss_after_first_update(architecture: ModuleType, config: Dict[str, Any]):
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
            stepped = first_adamw_step(
                p32, grad_sum(architecture, p32, first, config), count, config
            )
            sums = jax.lax.map(
                lambda seq: architecture.sequence_loss(stepped, seq, config), then
            )
        return jnp.sum(sums) / (then.shape[0] * (then.shape[1] - 1))

    return loss_after
