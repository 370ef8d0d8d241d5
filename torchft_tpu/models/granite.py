"""Hybrid state-space decoder (the ``GraniteMoeHybrid`` family, dense unit):
Mamba-2 layers and attention layers in a published order, four scalar
multipliers and a head that is the embedding.

``x0 = embedding_multiplier * E[tokens]``. Per layer, pre-norm, both kinds:

    a = x + residual_multiplier * mixer(RMSNorm(x))
    y = a + residual_multiplier * W_out(silu(g) * u),   [g, u] = W_in(RMSNorm(a))

- ATTENTION mixer (``layer_types[i] == "attention"``): grouped-query heads of
  ``dim / n_heads``, no bias, NO positional encoding, softmax scale
  ``attention_multiplier`` (not ``head_dim ** -0.5``); one call into
  ops/attention.py ``attend``.
- MAMBA mixer (Mamba-2): ``[z, xBC, dt] = W_inproj h`` (``inner``, ``inner + 2
  x groups x state``, ``heads`` wide, in that order); ``xBC = silu(conv(xBC))``,
  a depthwise causal convolution ``mamba_conv`` wide with bias; ``[x, B, C] =
  xBC``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` by head; the
  selective scan ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t
  + D x_t`` by chunks of ``mamba_chunk`` (ops/ssd.py ``conv_silu`` and
  ``ssd_scan``: Mosaic kernels on a TPU where the shapes fit them, XLA
  einsums elsewhere; ops/ssd.py chooses, nothing here does); then the
  gate BEFORE the norm, ``RMSNorm over inner (y * silu(z))``, and ``W_outproj``.
  No bias but the convolution's. The layer sows ``ssd_chunk_log_decay``, the
  smallest and largest total log-decay of a chunk over heads
  (:func:`chunk_log_decay` reads it by layer).

``logits = RMSNorm(x_L) E^T / logits_scaling`` over the held rows of ``E``
(models/decoder.py ``tied_head``: the fused loss takes the embedding's matrix,
and its gradient is the sum of the gather's and the head's).

The kinds repeat with a period (``[m m m m m A m m m m]``: ten), which
models/decoder.py ``layer_stack`` scans; the norm, the head's fused loss and
the remat rule are models/decoder.py's. ``dots`` keeps, by name, the flash
kernels' output and logsumexp and each layer's two widest projections (a Mamba
layer's in-projection, the unit's gate and up: 133 and 256 MiB a layer at 8192
positions, 3.1 GiB of the step's 4.6 GiB of temporaries, for two thirds of the
projections' operations not done twice) and no other ``dot_general``: the
convolution, the scan, the narrow projections and the attention's q, k, v come
again in a layer's backward, and nothing else of a Mamba layer outlives its
forward (the decay matrices of one layer are half a GiB at 8192 positions).
No sharding plan yet: the model runs on one device or replicated.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from torchft_tpu.models.decoder import (
    RMSNorm, into_residual, layer_stack, remat_policy, smallest_period, sown_by_layer, tied_head,
)
from torchft_tpu.ops import ssd
from torchft_tpu.ops.attention import attend
from torchft_tpu.ops.flash_attention import FLASH_LSE, FLASH_OUT

__all__ = ["GraniteConfig", "Granite", "chunk_log_decay"]

# checkpoint_name tags of a layer's two widest projections, which ``dots``
# keeps by name beside the flash kernels' pair (``Granite.__call__``).
MAMBA_IN = "mamba_in_proj"
UNIT_IN = "unit_w_in"

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclass(frozen=True)
class GraniteConfig:
    vocab_size: int = 100352
    dim: int = 2048
    n_layers: int = 40
    # A kind for every layer: "mamba" or "attention".
    layer_types: Tuple[str, ...] = _PERIOD * 4
    n_heads: int = 32  # of dim / n_heads each
    n_kv_heads: int = 8
    mlp_hidden: int = 8192
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_groups: int = 1
    mamba_conv: int = 4
    mamba_chunk: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # What the norms' scales and a Mamba layer's three per-head vectors
    # (A_log, dt_bias, D) are STORED in.
    norm_dtype: Any = jnp.float32
    # The path ops/attention.py ``attend`` takes, as models/llama.py.
    attention_impl: str = "auto"
    remat: str = "none"  # "none" | "full" | "dots", as models/llama.py
    loss_vocab_chunk: Optional[int] = None
    scan_layers: bool = False
    # The depth the projections into the residual stream are initialised for
    # (models/decoder.py ``into_residual``); None = ``n_layers``.
    init_depth: Optional[int] = None

    def __post_init__(self) -> None:
        if self.attention_impl not in ("auto", "dense", "blockwise", "flash"):
            raise ValueError(f"attention_impl={self.attention_impl!r}")
        if self.remat not in ("none", "full", "dots"):
            raise ValueError(f"remat={self.remat!r} is not one of ('none', 'full', 'dots')")
        if self.dim % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} / {self.n_kv_heads} heads over {self.dim}")
        if self.mamba_heads % self.mamba_groups:
            raise ValueError(f"{self.mamba_heads} heads over {self.mamba_groups} groups")
        if len(self.layer_types) != self.n_layers or set(self.layer_types) - {"mamba", "attention"}:
            raise ValueError(f"layer_types does not list {self.n_layers} mamba / attention layers")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def period(self) -> int:
        """The smallest number of layers the kinds repeat with."""
        return smallest_period(self.layer_types)


def _dense(cfg: GraniteConfig):
    return partial(nn.DenseGeneral, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.dtype)


def _a_log(key, shape, dtype):
    """Mamba-2's own: ``A = -uniform(1, 16)``."""
    return jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0)).astype(dtype)


def _dt_bias(key, shape, dtype):
    """Mamba-2's own: the inverse softplus of a step log-uniform in
    [1e-3, 1e-1]."""
    low, high = jnp.log(1e-3), jnp.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, minval=low, maxval=high))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class Attention(nn.Module):
    config: GraniteConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        dense = _dense(cfg)
        q = dense(features=(cfg.n_heads, cfg.head_dim), name="wq")(x)
        k = dense(features=(cfg.n_kv_heads, cfg.head_dim), name="wk")(x)
        v = dense(features=(cfg.n_kv_heads, cfg.head_dim), name="wv")(x)
        with jax.named_scope("tpuft::nope_attention"):
            # The flash kernels' blocks are the other models': 512 x 1024.
            out = attend(
                q, k, v, scale=cfg.attention_multiplier, impl=cfg.attention_impl, block_k=1024,
            )
        init = into_residual(cfg.init_depth or cfg.n_layers)
        return dense(features=cfg.dim, axis=(-2, -1), kernel_init=init, name="wo")(out)


class Mamba(nn.Module):
    config: GraniteConfig

    @nn.compact
    def __call__(self, h: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        dense = _dense(cfg)
        b, s, _ = h.shape
        heads, inner = cfg.mamba_heads, cfg.mamba_inner
        shared = cfg.mamba_groups * cfg.mamba_state  # B's width, and C's
        with jax.named_scope("tpuft::mamba::in_proj"):
            parts = dense(features=2 * inner + 2 * shared + heads, name="in_proj")(h)
            parts = checkpoint_name(parts, MAMBA_IN)
        z, xbc, dt = jnp.split(parts, [inner, 2 * inner + 2 * shared], axis=-1)
        kernel = self.param(
            "conv_kernel",
            # torch's Conv1d: uniform within 1 / sqrt(width) either way.
            nn.initializers.variance_scaling(1 / 3, "fan_in", "uniform", in_axis=-1, out_axis=-2),
            (xbc.shape[-1], cfg.mamba_conv), cfg.dtype,
        )
        bias = self.param("conv_bias", nn.initializers.zeros, (xbc.shape[-1],), cfg.dtype)
        with jax.named_scope("tpuft::mamba::conv"):
            xbc = ssd.conv_silu(xbc, kernel, bias)  # rounded once, to cfg.dtype
        x, b_in, c_out = jnp.split(xbc, [inner, inner + shared], axis=-1)
        a_log = self.param("A_log", _a_log, (heads,), cfg.norm_dtype)
        dt_bias = self.param("dt_bias", _dt_bias, (heads,), cfg.norm_dtype)
        d_skip = self.param("D", nn.initializers.ones, (heads,), cfg.norm_dtype)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
        a = -jnp.exp(a_log.astype(jnp.float32))
        decays = ssd.chunk_log_decay(dt, a, cfg.mamba_chunk)
        self.sow("intermediates", "ssd_chunk_log_decay", decays)
        by_group = (b, s, cfg.mamba_groups, cfg.mamba_state)
        y = ssd.ssd_scan(
            x.reshape(b, s, heads, cfg.mamba_head_dim), dt, a,
            b_in.reshape(by_group), c_out.reshape(by_group), d_skip, cfg.mamba_chunk,
        )
        with jax.named_scope("tpuft::mamba::gated_norm"):
            gated = y.reshape(b, s, inner).astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
            y = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.norm_dtype, name="norm")(gated)
        init = into_residual(cfg.init_depth or cfg.n_layers)
        with jax.named_scope("tpuft::mamba::out_proj"):
            return dense(features=cfg.dim, kernel_init=init, name="out_proj")(y)


class GatedUnit(nn.Module):
    """SwiGLU with its two input projections as ONE matrix, gate first."""

    config: GraniteConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        dense = _dense(cfg)
        both = checkpoint_name(dense(features=2 * cfg.mlp_hidden, name="w_in")(x), UNIT_IN)
        gate, up = jnp.split(both, 2, axis=-1)
        init = into_residual(cfg.init_depth or cfg.n_layers)
        return dense(features=cfg.dim, kernel_init=init, name="w_out")(nn.silu(gate) * up)


class Block(nn.Module):
    """Layer ``kind`` of the period (any layer of that kind: ``kind`` indexes
    ``layer_types``). Leaves under ``attn`` or ``mamba`` by kind."""

    config: GraniteConfig
    kind: int = 0

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        norm = partial(RMSNorm, cfg.norm_eps, cfg.dtype, cfg.norm_dtype)
        if cfg.layer_types[self.kind] == "attention":
            mixer = Attention(cfg, name="attn")
        else:
            mixer = Mamba(cfg, name="mamba")

        def add(x, branch):
            # The stream is float32 (``Granite.__call__`` says why), and 0.22
            # is no bfloat16 number (0.2197 is the nearest).
            return x + cfg.residual_multiplier * branch.astype(jnp.float32)

        x = add(x, mixer(norm(name="mixer_norm")(x)))
        return add(x, GatedUnit(cfg, name="mlp")(norm(name="mlp_norm")(x)))


class Granite(nn.Module):
    """``apply(params, tokens)`` returns logits over the held vocabulary;
    ``apply(params, tokens, targets=targets)`` the mean token cross-entropy,
    through the fused head where ``loss_vocab_chunk`` is set."""

    config: GraniteConfig

    @nn.compact
    def __call__(
        self, tokens: jnp.ndarray, positions: Optional[jnp.ndarray] = None,
        targets: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        cfg = self.config
        if positions is None:  # no layer reads them: ``layer_stack``'s signature
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        # The residual stream starts at unit variance, after the multiplier.
        embed = nn.Embed(
            cfg.vocab_size, cfg.dim, dtype=cfg.dtype, param_dtype=cfg.dtype,
            embedding_init=nn.initializers.normal(1.0 / cfg.embedding_multiplier),
            name="tok_embed",
        )
        # The residual stream is carried in float32 whatever ``dtype`` is: a
        # branch adds only 0.22 of its output to it, twenty times a period,
        # onto rows that start ON the bfloat16 grid (12 x a bfloat16 row), so
        # a bfloat16 running sum drops whole what is under half a unit of it,
        # and the stream's cotangent does the same on the way back. Measured
        # on the chip at 1 x 8192 against the float32 reference: the loss
        # 1.7e-5 to 2.3e-5 off in bfloat16 (2.65 times inside the 2^-14 a
        # benchmark cell is held to) and 9.0e-7 to 4.7e-6 in float32 (13
        # times), a leaf's gradient 1.1% and 0.67% off, the first update's
        # descent on the next batch 0.36% lower, for 5% of the step (PERF.md
        # section 6, PR 57: the first loss's margin is what it is kept for).
        # The norms read it in float32 anyway; every product takes ``dtype``.
        x = embed(tokens).astype(jnp.float32) * cfg.embedding_multiplier
        policy = remat_policy(
            cfg.remat, jax.checkpoint_policies.nothing_saveable, FLASH_OUT, FLASH_LSE,
            MAMBA_IN, UNIT_IN,
        )
        x = layer_stack(Block, cfg, policy, x, positions, period=cfg.period)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.norm_dtype, name="final_norm")(x)
        x = (x.astype(jnp.float32) / cfg.logits_scaling).astype(cfg.dtype)
        out = tied_head(embed, x, targets, cfg.loss_vocab_chunk)
        return out if targets is not None else out.astype(jnp.float32)


def chunk_log_decay(model: Granite, params: Any, tokens: jnp.ndarray) -> jnp.ndarray:
    """(Mamba layers, 2): the smallest and largest total log-decay of a chunk
    over heads, by Mamba layer in order, for ``tokens`` (b, s)."""
    return sown_by_layer(model, params, tokens, "mamba", "ssd_chunk_log_decay")
