"""Sparse-expert decoder with a learned key selection (the language model of
the ``KeyeVL2`` family), as ONE CHIP'S SHARE of an expert-parallel layer.

Per layer, pre-norm:

- attention: grouped-query heads of a ``head_dim`` that is its own number (not
  ``dim / n_heads``), an RMSNorm over each head of q and k, rotary (rotate-half;
  text-only positions, so the family's three rotary sections see one position
  and the embedding is the plain one). Each query attends to the ``topk``
  earlier keys an indexer scores highest (ops/sparse_attention.py
  ``selected_attention``: on a TPU the selection is made once as an int8
  array and the flash kernels attend under it, elsewhere plain tiled XLA
  does both): the indexer has ``indexer_heads`` query heads and one key head of
  ``indexer_head_dim``, a LayerNorm on its key, rotary on both, a learned
  weight a head, and runs in ``indexer_dtype`` (float32) whatever ``dtype`` is.
  The selection carries no gradient, so the language-model loss gives the
  indexer's leaves gradient zero; the term that trains an indexer in the
  published mechanism is not built.
- experts: models/experts.py ``RoutedExperts``, the routed layer as one
  chip's share (a softmax router over ALL ``num_experts``, the
  ``experts_per_token`` largest renormalised to one, ``num_local_experts``
  of them held here), its router on the layer's own normed input and SiLU
  in the gated unit.

Rotary, the norm (its scale stored in ``norm_dtype``: float32 unless a
configuration says otherwise), the output head with its fused loss and the
scanned, rematerialised layer stack are models/decoder.py's (the ``dots``
policy keeps the projections' products, the flash kernel's output and
logsumexp and the selection, not a tile's scores). ``router_load`` and
``dispatch_rows`` are models/experts.py's.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchft_tpu.models.decoder import (
    LMHead, RMSNorm, apply_rope, into_residual, layer_stack, remat_policy,
)
from torchft_tpu.models.experts import RoutedExperts, dispatch_rows, router_load
from torchft_tpu.ops.flash_attention import FLASH_LSE, FLASH_OUT
from torchft_tpu.ops.sparse_attention import SELECTION, selected_attention

__all__ = ["KeyeConfig", "Keye", "expert_layer", "router_load", "dispatch_rows"]


@dataclass(frozen=True)
class KeyeConfig:
    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    moe_hidden: int = 768
    num_experts: int = 128  # the router's width
    experts_per_token: int = 8
    num_local_experts: int = 128  # held here
    expert_share: int = 0  # which share: experts share * local .. + local - 1
    indexer_heads: int = 16
    indexer_head_dim: int = 64
    indexer_dtype: Any = jnp.float32
    topk: int = 2048
    # Tile of queries the index scores and the attention are computed in
    # (ops/sparse_attention.py).
    select_block: int = 512
    rope_theta: float = 1e7
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # What the norms' scales (and the indexer's LayerNorm) are STORED in. In
    # bfloat16 an optimizer step under half a unit of the stored value is lost:
    # 3e-4 never moves a scale of 1.0, and the norms stay where they started.
    norm_dtype: Any = jnp.float32
    remat: str = "none"  # "none" | "full" | "dots", as models/llama.py
    loss_vocab_chunk: Optional[int] = None
    scan_layers: bool = False
    # The depth the projections INTO the residual stream (``wo``, ``w_down``)
    # are initialised for: lecun-normal over sqrt(2 x depth), the scaled
    # initialisation of deep pre-norm stacks. None = ``n_layers``. A model cut
    # in depth names the depth it was cut from. Without the scaling a stack at
    # initialisation loses rank within two layers (attention's average over
    # thousands of keys passes what the tokens share and averages away what
    # tells them apart): near-tied index scores, one token to the router, and
    # a first optimizer step whose effect on the loss has either sign (PERF.md
    # section 6, PR 46). It says nothing of a stack once it trains.
    init_depth: Optional[int] = None

    def __post_init__(self) -> None:
        if self.remat not in ("none", "full", "dots"):
            raise ValueError(f"remat={self.remat!r} is not one of ('none', 'full', 'dots')")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} query heads over {self.n_kv_heads} key heads")
        held = (self.expert_share + 1) * self.num_local_experts
        if self.expert_share < 0 or held > self.num_experts:
            raise ValueError(
                f"share {self.expert_share} of {self.num_local_experts} experts "
                f"is not inside {self.num_experts}"
            )


def _dense(cfg: KeyeConfig, accumulate_as: Any = None):
    """A bias-free projection stored and multiplied in ``dtype``; with
    ``accumulate_as`` the products come out in that (wider) dtype unrounded."""
    into = {}
    if accumulate_as is not None:
        into["dot_general"] = partial(jax.lax.dot_general, preferred_element_type=accumulate_as)
    return partial(nn.DenseGeneral, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.dtype, **into)


class Indexer(nn.Module):
    """(qI, kI, w) in ``indexer_dtype`` from the layer's normed input: products
    of the stored weights accumulated in that dtype, never rounded to
    ``dtype`` on the way to the scores."""

    config: KeyeConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray):
        cfg = self.config
        wide = cfg.indexer_dtype
        heads, width = cfg.indexer_heads, cfg.indexer_head_dim
        dense = _dense(cfg, wide)
        x = jax.lax.stop_gradient(x)
        qi = dense(features=(heads, width), name="wq")(x).astype(wide)
        ki = dense(features=width, name="wk")(x).astype(wide)
        w = dense(features=heads, name="weights")(x).astype(wide)
        ki = nn.LayerNorm(epsilon=cfg.norm_eps, dtype=wide, param_dtype=cfg.norm_dtype, name="k_norm")(ki)
        qi = apply_rope(qi, positions, cfg.rope_theta)
        ki = apply_rope(ki[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
        return qi, ki, w * (heads**-0.5 * width**-0.5)


class SparseAttention(nn.Module):
    config: KeyeConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        dense = _dense(cfg)
        head_norm = partial(RMSNorm, cfg.norm_eps, cfg.dtype, cfg.norm_dtype)
        q = dense(features=(cfg.n_heads, cfg.head_dim), name="wq")(x)
        k = dense(features=(cfg.n_kv_heads, cfg.head_dim), name="wk")(x)
        v = dense(features=(cfg.n_kv_heads, cfg.head_dim), name="wv")(x)
        q = apply_rope(head_norm(name="q_norm")(q), positions, cfg.rope_theta)
        k = apply_rope(head_norm(name="k_norm")(k), positions, cfg.rope_theta)
        qi, ki, w = Indexer(cfg, name="indexer")(x, positions)
        watched = self.is_mutable_collection("intermediates")
        out, chosen = selected_attention(
            q, k, v, qi, ki, w, topk=cfg.topk, scale=cfg.head_dim**-0.5,
            block=cfg.select_block, return_selection=watched,
        )
        if watched:
            self.sow("intermediates", "selection", chosen)
        init = into_residual(cfg.init_depth or cfg.n_layers)
        return dense(features=cfg.dim, axis=(-2, -1), kernel_init=init, name="wo")(out)


def expert_layer(cfg: KeyeConfig, **module) -> RoutedExperts:
    """The routed layer of this configuration: SiLU, the projection back into
    the residual stream initialised for ``init_depth``."""
    depth = cfg.init_depth or cfg.n_layers
    return RoutedExperts(
        dim=cfg.dim, hidden=cfg.moe_hidden, num_experts=cfg.num_experts,
        experts_per_token=cfg.experts_per_token, num_local_experts=cfg.num_local_experts,
        expert_share=cfg.expert_share, activation=nn.silu, dtype=cfg.dtype,
        down_init=into_residual(depth, in_axis=-2, out_axis=-1, batch_axis=0), **module,
    )


class Block(nn.Module):
    config: KeyeConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        norm = partial(RMSNorm, cfg.norm_eps, cfg.dtype, cfg.norm_dtype)
        x = x + SparseAttention(cfg, name="attn")(norm(name="attn_norm")(x), positions)
        return x + expert_layer(cfg, name="moe")(norm(name="mlp_norm")(x))


class Keye(nn.Module):
    """``apply(params, tokens)`` returns logits over the held vocabulary;
    ``apply(params, tokens, targets=targets)`` the mean token cross-entropy,
    through the fused head where ``loss_vocab_chunk`` is set."""

    config: KeyeConfig

    @nn.compact
    def __call__(
        self, tokens: jnp.ndarray, positions: Optional[jnp.ndarray] = None,
        targets: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        cfg = self.config
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        # Unit-variance embeddings: the residual stream starts at the scale the
        # normed branches write at (see ``KeyeConfig.init_depth``).
        x = nn.Embed(
            cfg.vocab_size, cfg.dim, dtype=cfg.dtype, param_dtype=cfg.dtype,
            embedding_init=nn.initializers.normal(1.0), name="tok_embed",
        )(tokens)
        # ``dots`` keeps what the projections' matmuls produced, the flash
        # kernel's output and logsumexp and the selection by their names (one
        # int8 (s, s) a layer: dropped, the backward would score and select
        # again); but only the dots WITHOUT batch dimensions: a tile's index
        # scores and attention scores are batched dots, and kept they would be
        # the whole (heads, s, s) arrays the tiles exist to avoid (a policy
        # reaches through the tiles' own ``jax.checkpoint``: 36 GB at 6 layers
        # x 8192).
        policy = remat_policy(
            cfg.remat, jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            FLASH_OUT, FLASH_LSE, SELECTION,
        )
        x = layer_stack(Block, cfg, policy, x, positions)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.norm_dtype, name="final_norm")(x)
        head = LMHead(cfg.dim, cfg.vocab_size, cfg.dtype, cfg.loss_vocab_chunk, name="lm_head")
        return head(x, targets) if targets is not None else head(x).astype(jnp.float32)
