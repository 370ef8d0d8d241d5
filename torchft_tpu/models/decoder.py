"""What every decoder here shares, written once: rotary, the norm, the output
head with its fused loss, the ``dots`` remat rule and the layer stack. A model
file (models/llama.py, models/keye.py) is a config, a block and a top-level
module of embedding, :func:`layer_stack`, final norm and head; its attention
asks ops/ for a kernel (ops/attention.py, ops/sparse_attention.py).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchft_tpu.ops.cross_entropy import chunked_cross_entropy

__all__ = ["apply_rope", "RMSNorm", "LMHead", "remat_policy", "layer_stack"]


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (batch, seq, heads, head_dim); positions: (batch, seq)."""
    head_dim = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (b, s, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    """float32 accumulation whatever ``dtype`` is; the scale is STORED in
    ``param_dtype`` (``KeyeConfig.norm_dtype`` says why that is an option)."""

    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), self.param_dtype)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (normed * scale.astype(jnp.float32)).astype(self.dtype)


class LMHead(nn.Module):
    """The output projection, param-compatible with ``nn.Dense`` (same
    ``lm_head/kernel`` path, lecun-normal init, dtype promotion): owning
    the kernel directly lets the fused loss path hand it to
    :func:`~torchft_tpu.ops.cross_entropy.chunked_cross_entropy` without
    ever forming the logits. With ``targets`` the mean token cross-entropy,
    in vocabulary slabs of ``loss_vocab_chunk`` (None = dense)."""

    dim: int
    vocab_size: int
    dtype: Any = jnp.bfloat16
    loss_vocab_chunk: Optional[int] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, targets: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (self.dim, self.vocab_size), self.dtype
        )
        if targets is None:
            return jnp.dot(x, kernel.astype(self.dtype))
        return chunked_cross_entropy(x, kernel, targets, self.loss_vocab_chunk)


def remat_policy(remat: str, dots: Any, *names: str):
    """The policy of ``remat`` for :func:`layer_stack`. ``dots`` keeps what
    the MXU produced, the ``dot_general`` results the model's ``dots`` policy
    picks (``jax.checkpoint_policies.checkpoint_dots`` or a narrower one), and
    the arrays tagged ``names``: a Pallas call is no ``dot_general``, so
    without the flash forward kernel's (out, logsumexp) by name the backward
    would run that whole kernel a second time. ``full`` (None) recomputes
    everything, that kernel included."""
    if remat != "dots":
        return None
    policies = jax.checkpoint_policies
    return policies.save_from_both_policies(dots, policies.save_only_these_names(*names))


class _ScanCell(nn.Module):
    """One block in ``(carry, broadcast) -> (carry, out)`` shape for
    ``nn.scan``; params live under ``<stack>/block`` with a leading layer
    axis added by the scan's ``variable_axes``."""

    block: Any  # the block's class
    config: Any

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray):
        return self.block(self.config, name="block")(x, positions), None


def layer_stack(block: Any, cfg: Any, policy: Any, x: jnp.ndarray, positions: jnp.ndarray):
    """``cfg.n_layers`` of ``block(cfg)(x, positions) -> x``, called inside the
    model's ``__call__``. ``cfg.scan_layers``: one ``lax.scan`` over the stack,
    one traced and compiled block for the whole depth (O(1) HLO size and
    compile time in depth), leaves under ``layers/block/...`` with a leading
    layer axis (as is what a block sows into ``intermediates``); otherwise
    inlined copies under ``layer_<i>/...``. ``cfg.remat`` other than ``none``
    rematerialises each block under ``policy`` (:func:`remat_policy`)."""
    if cfg.scan_layers:
        cell = _ScanCell
        if cfg.remat != "none":
            # prevent_cse is safe (and standard) under scan: the loop
            # boundary already blocks the CSE remat would otherwise fight.
            cell = nn.remat(cell, policy=policy, prevent_cse=False)
        stack = nn.scan(
            cell,
            variable_axes={"params": 0, "intermediates": 0},
            split_rngs={"params": True},
            length=cfg.n_layers,
            in_axes=nn.broadcast,
        )
        return stack(block, cfg, name="layers")(x, positions)[0]
    if cfg.remat != "none":
        block = nn.remat(block, policy=policy)
    for layer in range(cfg.n_layers):
        x = block(cfg, name=f"layer_{layer}")(x, positions)
    return x
