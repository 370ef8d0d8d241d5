"""Memory-efficient fused linear + cross-entropy.

The LM head is the single largest activation in causal-LM training: at
batch 8 x seq 2048 x vocab 128k the f32 logits alone are 8 GiB, and the
softmax/backward temporaries double it — often more HBM than the whole
rest of the step. The reference stack inherits torch's materialized
``F.cross_entropy`` over full logits; this op is the TPU-first
alternative: ``lax.scan`` over vocab chunks with an online logsumexp
(the flash-attention trick applied to the vocab axis), so only one
``(..., chunk)`` logits slab is ever live.

A ``custom_vjp`` keeps the backward at the same footprint: the forward
saves ``(x, w, targets, lse)`` — inputs plus one f32 scalar per row; the
backward re-computes each chunk's logits from ``(x, w)``, forms
``softmax - onehot`` in the chunk, and accumulates ``dx`` and the
``dw`` slab in final layout — full logits are never materialized in
either direction (AD through the naive scan would stack per-chunk
residuals and reconstruct exactly the array this op exists to avoid).

FLOPs are identical to the dense path (the matmul is computed once per
direction either way); what changes is peak HBM and the fusion shape.

Two results of the one op: :func:`chunked_cross_entropy`, the MEAN token loss
(a scalar cotangent in the backward), and
:func:`chunked_cross_entropy_by_token`, every token's own loss (a cotangent BY
TOKEN: a model that weighs each token's loss at each of several exits by a
learned probability, models/ouro.py). ``by_token`` is static, so the mean's
program is the one it was before the second result existed.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["chunked_cross_entropy", "chunked_cross_entropy_by_token"]


def _chunk_logits(x2, w, start, chunk):
    """(n, d) @ (d, chunk) slice starting at vocab index ``start``."""
    wc = jax.lax.dynamic_slice_in_dim(w, start, chunk, axis=1)
    return jnp.dot(
        x2.astype(jnp.float32), wc.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _chunked_ce(x2, w, targets1, chunk, vocab_valid, by_token):
    loss, _ = _ce_fwd(x2, w, targets1, chunk, vocab_valid, by_token)
    return loss


def _col_mask(idx, chunk, vocab_valid):
    """(chunk,) validity of this slab's global vocab columns — the tail
    slab of a non-multiple vocab is zero-padded by the wrapper and masked
    out here."""
    return idx * chunk + jnp.arange(chunk) < vocab_valid


def _ce_fwd(x2, w, targets1, chunk, vocab_valid, by_token):
    n, d = x2.shape
    vocab = w.shape[1]
    n_chunks = vocab // chunk

    def body(carry, idx):
        m, s, tl = carry  # running max, sum exp, target logit
        logits = _chunk_logits(x2, w, idx * chunk, chunk)  # (n, chunk)
        logits = jnp.where(_col_mask(idx, chunk, vocab_valid), logits, -1e30)
        cmax = jnp.max(logits, axis=1)
        new_m = jnp.maximum(m, cmax)
        s = s * jnp.exp(m - new_m) + jnp.sum(
            jnp.exp(logits - new_m[:, None]), axis=1
        )
        # Gather this chunk's contribution to the target logit.
        local = targets1 - idx * chunk
        in_chunk = (local >= 0) & (local < chunk)
        picked = jnp.take_along_axis(
            logits, jnp.clip(local, 0, chunk - 1)[:, None], axis=1
        )[:, 0]
        tl = jnp.where(in_chunk, picked, tl)
        return (new_m, s, tl), None

    init = (
        jnp.full((n,), -jnp.inf, jnp.float32),
        jnp.zeros((n,), jnp.float32),
        jnp.zeros((n,), jnp.float32),
    )
    (m, s, tl), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    lse = m + jnp.log(s)
    loss = lse - tl if by_token else jnp.mean(lse - tl)
    return loss, (x2, w, targets1, lse)


def _ce_bwd(chunk, vocab_valid, by_token, residuals, g):
    x2, w, targets1, lse = residuals
    n, d = x2.shape
    vocab = w.shape[1]
    n_chunks = vocab // chunk
    # By token the cotangent is a row's own; of the mean, d(mean)/d(per-row loss).
    scale = g[:, None] if by_token else g / n

    def body(carry, idx):
        dx, dw = carry
        logits = _chunk_logits(x2, w, idx * chunk, chunk)
        logits = jnp.where(_col_mask(idx, chunk, vocab_valid), logits, -1e30)
        p = jnp.exp(logits - lse[:, None])  # softmax slab (n, chunk)
        local = targets1 - idx * chunk
        in_chunk = (local >= 0) & (local < chunk)
        onehot = (
            jax.nn.one_hot(jnp.clip(local, 0, chunk - 1), chunk, dtype=jnp.float32)
            * in_chunk[:, None]
        )
        dlogits = (p - onehot) * scale  # (n, chunk) f32
        wc = jax.lax.dynamic_slice_in_dim(w, idx * chunk, chunk, axis=1)
        dx = dx + jnp.dot(
            dlogits, wc.astype(jnp.float32).T, preferred_element_type=jnp.float32
        )
        dwc = jnp.dot(
            x2.astype(jnp.float32).T, dlogits, preferred_element_type=jnp.float32
        )  # (d, chunk)
        # In-place slab write into the final (d, vocab) layout — a stacked
        # (n_chunks, d, chunk) output would force a transient full-size
        # transpose copy on reshape (and see CLAUDE.md on
        # dynamic_update_slice for sliced accumulators under shard_map AD).
        dw = jax.lax.dynamic_update_slice_in_dim(dw, dwc, idx * chunk, axis=1)
        return (dx, dw), None

    (dx, dw), _ = jax.lax.scan(
        body,
        (jnp.zeros((n, d), jnp.float32), jnp.zeros((d, vocab), jnp.float32)),
        jnp.arange(n_chunks),
    )
    return dx.astype(x2.dtype), dw.astype(w.dtype), None


_chunked_ce.defvjp(_ce_fwd, _ce_bwd)


def _token_losses(x, w, targets, vocab_chunk, by_token):
    """What both results share: rows, clamped targets, the dense one-shot for
    a vocabulary of one slab, the padded tail slab."""
    d = x.shape[-1]
    vocab = w.shape[1]
    x2 = x.reshape(-1, d)
    targets1 = jnp.clip(targets.reshape(-1).astype(jnp.int32), 0, vocab - 1)
    if vocab_chunk is None or vocab_chunk >= vocab:
        logits = jnp.dot(
            x2.astype(jnp.float32), w.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        logp = jax.nn.log_softmax(logits, axis=-1)
        tl = jnp.take_along_axis(logp, targets1[:, None], axis=1)[:, 0]
        return -tl if by_token else -jnp.mean(tl)
    pad = (-vocab) % vocab_chunk
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
    return _chunked_ce(x2, w, targets1, vocab_chunk, vocab, by_token)


def chunked_cross_entropy(
    x: jnp.ndarray,
    w: jnp.ndarray,
    targets: jnp.ndarray,
    vocab_chunk: Optional[int] = 4096,
) -> jnp.ndarray:
    """Mean token cross-entropy of ``softmax((x @ w))`` against ``targets``
    without materializing the logits.

    Args:
        x: final hidden states ``(..., d)`` (any float dtype; matmuls run
           f32-accumulated).
        w: LM-head kernel ``(d, vocab)`` (for tied embeddings pass
           ``embedding.T``).
        targets: int targets, shape ``x.shape[:-1]``.
        vocab_chunk: vocab slab width. Non-multiple vocabs (Llama-3's
           128256) are handled by zero-padding the tail slab outside the
           custom VJP and masking the padded columns to ``-1e30`` inside
           (AD of the pad restores ``dw``'s true shape). ``None``
           disables chunking (dense one-shot — same math, for small
           vocabs).

    Matches ``cross_entropy_loss(x @ w, targets)`` (models/llama.py) to
    f32 tolerance in value and gradients; peak activation memory drops
    from O(n·vocab) to O(n·vocab_chunk).

    Targets must lie in ``[0, vocab)``; out-of-range values are clamped
    to the nearest valid index (once, here in the wrapper) so the
    chunked and dense paths return the SAME value for invalid input —
    previously the chunked path silently used a 0.0 target logit while
    the dense path clamped (round-3 advisor).
    """
    return _token_losses(x, w, targets, vocab_chunk, by_token=False)


def chunked_cross_entropy_by_token(
    x: jnp.ndarray,
    w: jnp.ndarray,
    targets: jnp.ndarray,
    vocab_chunk: Optional[int] = 4096,
) -> jnp.ndarray:
    """Every token's cross-entropy, float32 in ``targets``' shape: the vector
    :func:`chunked_cross_entropy` is the mean of, from the same slabs and
    under the same clamp. Its backward takes a cotangent by token, so a
    caller may weigh the tokens as it likes (their mean is
    :func:`chunked_cross_entropy` to the bit, value and gradients)."""
    return _token_losses(x, w, targets, vocab_chunk, by_token=True).reshape(targets.shape)
