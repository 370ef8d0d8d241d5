"""Sparse-expert decoder whose layers come in a period of kinds (the
``SmallThinker`` family), as ONE CHIP'S SHARE of an expert-parallel layer.

Per layer, pre-norm, ``x`` the block's input:

- router: the routed layer's scores are taken from ``x`` itself, the residual
  stream as it ENTERS the block, ahead of attention (so a layer's routing
  does not wait for its attention, and does not move with it).
- attention over ``RMSNorm(x)``: grouped-query heads of a ``head_dim`` that is
  its own number (not ``dim / n_heads``), no bias, no norm over the heads. A
  layer is one of two kinds, by ``window_layout`` and ``rope_layout``: FULL,
  causal over the whole sequence, with NO positional encoding (where
  ``rope_layout`` says 0), or WINDOWED, each query over the ``window`` latest
  keys up to its own, with rotary (rotate-half) on q and k. Both are one call
  into ops/attention.py ``attend``, the windowed one with ``window``: on a TPU
  the flash kernels, whose schedule skips the block pairs behind the window
  (ops/flash_attention.py), elsewhere the blockwise or dense path under the
  same mask.
- experts over ``RMSNorm(x + attention)``: models/experts.py
  ``RoutedExperts`` with the logits from above, ReLU in the gated unit
  (ReGLU), ``num_local_experts`` of ``num_experts`` held here.

The layouts list a kind for every layer; the smallest period they repeat
with is what models/decoder.py ``layer_stack`` scans (``[0, 1, 1, 1]`` x 13:
a period of four, one traced period for the whole depth; a layout of one kind
is the one-kind stack). Rotary, the norm (its scale stored in ``norm_dtype``),
the output head with its fused loss and the remat rule are models/decoder.py's
(``dots`` keeps the flash kernels' output and logsumexp, what the flash call
reads, the stream after attention and the router's decisions, so that a
layer's backward computes no projection, no rotary and no routing a second
time: ``SmallThinker.__call__``). No sharding plan yet: the model runs on one
device or replicated.
``router_load`` and ``dispatch_rows`` are models/experts.py's.
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
    LMHead, RMSNorm, apply_rope, into_residual, layer_stack, remat_policy, smallest_period,
)
from torchft_tpu.models.experts import (
    RoutedExperts, dispatch_rows, router_load, routing_saveable,
)
from torchft_tpu.ops.attention import attend
from torchft_tpu.ops.flash_attention import FLASH_LSE, FLASH_OUT

__all__ = ["SmallThinkerConfig", "SmallThinker", "router_load", "dispatch_rows"]

# checkpoint_name tags of what a layer's flash call reads (q and k AFTER
# rotary, v) and of the stream after the attention branch, which ``dots``
# keeps by name (``SmallThinker.__call__``).
ATTN_Q = "attn_q"
ATTN_K = "attn_k"
ATTN_V = "attn_v"
POST_ATTN = "post_attn"


@dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    dim: int = 2560
    n_layers: int = 52
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    moe_hidden: int = 768
    num_experts: int = 64  # the router's width
    experts_per_token: int = 6
    num_local_experts: int = 64  # held here
    expert_share: int = 0  # which share: experts share * local .. + local - 1
    # The keys a windowed layer's query sees, its own position counted.
    window: int = 4096
    # A kind for every layer: 1 = windowed / rotary, 0 = full / none.
    window_layout: Tuple[int, ...] = (0, 1, 1, 1) * 13
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1) * 13
    rope_theta: float = 1.5e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    norm_dtype: Any = jnp.float32  # what the norms' scales are STORED in
    # As models/llama.py: the path ops/attention.py ``attend`` takes ("ring"
    # takes no window), the flash kernels' blocks, and where "auto" leaves
    # the dense path.
    attention_impl: str = "auto"
    attention_block_size: int = 512
    attention_block_k: Optional[int] = 1024
    blockwise_min_seq: int = 2048
    remat: str = "none"  # "none" | "full" | "dots", as models/llama.py
    loss_vocab_chunk: Optional[int] = None
    scan_layers: bool = False
    # The depth the projections into the residual stream are initialised for
    # (models/decoder.py ``into_residual``); None = ``n_layers``. A model cut
    # in depth names the depth it was cut from.
    init_depth: Optional[int] = None

    def __post_init__(self) -> None:
        if self.attention_impl not in ("auto", "dense", "blockwise", "flash"):
            raise ValueError(f"attention_impl={self.attention_impl!r}")
        if self.remat not in ("none", "full", "dots"):
            raise ValueError(f"remat={self.remat!r} is not one of ('none', 'full', 'dots')")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} query heads over {self.n_kv_heads} key heads")
        if not len(self.window_layout) == len(self.rope_layout) == self.n_layers:
            raise ValueError(f"the layouts do not list {self.n_layers} layers")
        held = (self.expert_share + 1) * self.num_local_experts
        if self.expert_share < 0 or held > self.num_experts:
            raise ValueError(
                f"share {self.expert_share} of {self.num_local_experts} experts "
                f"is not inside {self.num_experts}"
            )

    @property
    def kinds(self) -> Tuple[Tuple[int, int], ...]:
        """(windowed, rotary) of every layer."""
        return tuple(zip(self.window_layout, self.rope_layout))

    @property
    def period(self) -> int:
        """The smallest number of layers the kinds repeat with."""
        return smallest_period(self.kinds)


def _dense(cfg: SmallThinkerConfig):
    return partial(nn.DenseGeneral, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.dtype)


class Attention(nn.Module):
    config: SmallThinkerConfig
    windowed: bool
    rotary: bool

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        dense = _dense(cfg)
        q = dense(features=(cfg.n_heads, cfg.head_dim), name="wq")(x)
        k = dense(features=(cfg.n_kv_heads, cfg.head_dim), name="wk")(x)
        v = dense(features=(cfg.n_kv_heads, cfg.head_dim), name="wv")(x)
        if self.rotary:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        q, k, v = checkpoint_name(q, ATTN_Q), checkpoint_name(k, ATTN_K), checkpoint_name(v, ATTN_V)
        scope = "tpuft::window_attention" if self.windowed else "tpuft::full_attention"
        with jax.named_scope(scope):
            out = attend(
                q, k, v, scale=cfg.head_dim**-0.5, impl=cfg.attention_impl,
                blockwise_min_seq=cfg.blockwise_min_seq,
                block_size=cfg.attention_block_size, block_k=cfg.attention_block_k,
                window=cfg.window if self.windowed else None,
            )
        init = into_residual(cfg.init_depth or cfg.n_layers)
        return dense(features=cfg.dim, axis=(-2, -1), kernel_init=init, name="wo")(out)


class Block(nn.Module):
    """Layer ``kind`` of the period (any layer of that kind: ``kind`` indexes
    the layouts)."""

    config: SmallThinkerConfig
    kind: int = 0

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        windowed, rotary = cfg.kinds[self.kind]
        norm = partial(RMSNorm, cfg.norm_eps, cfg.dtype, cfg.norm_dtype)
        moe = RoutedExperts(
            dim=cfg.dim, hidden=cfg.moe_hidden, num_experts=cfg.num_experts,
            experts_per_token=cfg.experts_per_token, num_local_experts=cfg.num_local_experts,
            expert_share=cfg.expert_share, activation=nn.relu, dtype=cfg.dtype,
            down_init=into_residual(
                cfg.init_depth or cfg.n_layers, in_axis=-2, out_axis=-1, batch_axis=0
            ),
            name="moe",
        )
        with jax.named_scope("tpuft::router"):
            logits = moe.logits(x)
        attention = Attention(cfg, bool(windowed), bool(rotary), name="attn")
        x = checkpoint_name(x + attention(norm(name="attn_norm")(x), positions), POST_ATTN)
        return x + moe(norm(name="mlp_norm")(x), logits)


class SmallThinker(nn.Module):
    """``apply(params, tokens)`` returns logits over the held vocabulary;
    ``apply(params, tokens, targets=targets)`` the mean token cross-entropy,
    through the fused head where ``loss_vocab_chunk`` is set."""

    config: SmallThinkerConfig

    @nn.compact
    def __call__(
        self, tokens: jnp.ndarray, positions: Optional[jnp.ndarray] = None,
        targets: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        cfg = self.config
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        # Unit-variance embeddings: the residual stream starts at the scale
        # the normed branches write at (``KeyeConfig.init_depth``).
        x = nn.Embed(
            cfg.vocab_size, cfg.dim, dtype=cfg.dtype, param_dtype=cfg.dtype,
            embedding_init=nn.initializers.normal(1.0), name="tok_embed",
        )(tokens)
        # ``dots`` keeps, beside the flash kernels' output and logsumexp, what
        # the backward would otherwise compute a second time and the chip has
        # room for since the lone FT step holds ONE copy of the state: what
        # the flash call reads (q and k after rotary, v: the three products
        # AND rotary stay out of the backward) and the stream after attention
        # (what the second norm reads, so ``wo``'s product is not needed
        # again), a quarter of a GiB a layer and sequence of 16,384 tokens;
        # and what the router decided (models/experts.py). By name and not
        # ``checkpoint_dots``: that keeps q and k BEFORE rotary and ``wo``'s
        # result short of its sum, and measured a sixtieth of a step slower
        # for the same bytes. The norms and the router's logits come again.
        policy = remat_policy(
            cfg.remat, routing_saveable, FLASH_OUT, FLASH_LSE, ATTN_Q, ATTN_K, ATTN_V, POST_ATTN
        )
        x = layer_stack(Block, cfg, policy, x, positions, period=cfg.period)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.norm_dtype, name="final_norm")(x)
        head = LMHead(cfg.dim, cfg.vocab_size, cfg.dtype, cfg.loss_vocab_chunk, name="lm_head")
        return head(x, targets) if targets is not None else head(x).astype(jnp.float32)
