"""Llama-family transformer, TPU-first.

The flagship model family for the fault-tolerant training stack — the
reference composes with torchtitan's Llama 3 configs for its production
story (BASELINE.md: FT-DDP Llama-3 8B, FT-HSDP 70B, DiLoCo 8B), so this
module provides the same family natively: RMSNorm, rotary embeddings, GQA
attention, SwiGLU MLP, tied-or-untied output head.

TPU-first choices:
- bfloat16 activations/weights by default, float32 RMSNorm accumulation and
  logits — keeps matmuls on the MXU at full tile rate;
- static shapes everywhere; the causal mask is computed inline (no python
  control flow under jit);
- attention is one call of ops/attention.py ``attend``, which takes ring
  attention when a sequence-parallel axis is present in the ambient mesh,
  enabling context lengths sharded across devices;
- :func:`sharding_plan` gives PartitionSpecs for fsdp/tp axes (megatron
  layout: column-parallel qkv/up, row-parallel out/down) consumed by
  ``jax.jit`` via NamedSharding;
- ``remat`` ("full"/"dots") and ``scan_layers`` on the config: gradient
  checkpointing and a lax.scan'd layer stack, so 70B-class/long-context
  steps fit in HBM and compile in O(1) HLO size in depth.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from torchft_tpu.models.decoder import (
    LMHead, RMSNorm, apply_rope, layer_stack, remat_policy, tied_head,
)
from torchft_tpu.ops.attention import attend
from torchft_tpu.ops.flash_attention import FLASH_LSE, FLASH_OUT

__all__ = [
    "LlamaConfig",
    "Llama",
    "CONFIGS",
    "large_bench_config",
    "sharding_plan",
    "plan_shardings",
    "apply_sharding_plan",
    "cross_entropy_loss",
]


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_hidden: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False
    # "auto" | "dense" | "blockwise" | "flash" | "ring": the path
    # ops/attention.py ``attend`` takes, and what "auto" chooses where.
    attention_impl: str = "auto"
    sp_axis: str = "sp"
    attention_block_size: int = 512
    # KV-block length for the flash path only (the kernel's sequential
    # accumulation axis). scripts/flash_block_sweep.py on the TPU v5e (32/8
    # heads of 128, 8192 tokens a call, at 1 x 8192 and at 4 x 2048; PERF.md
    # section 5 has the table, PR 35's and PR 42's): 512x1024 is under every
    # smaller pair and under 512x2048; 1024x1024 is the one pair under it at
    # both lengths, with more VMEM than a call gets unasked at 8192 rows.
    # None = attention_block_size.
    attention_block_k: Optional[int] = 1024
    # The mesh axes the flash path maps its kernel over when the ambient
    # mesh binds them (ops/attention.py ``flash_under_mesh``): batch over
    # the data axes, q/kv heads over the tensor axis, the megatron layout
    # sharding_plan uses.
    flash_batch_axes: Tuple[str, ...] = ("dp", "fsdp")
    flash_tp_axis: Optional[str] = "tp"
    # The ring path's per-hop block compute through the fused Pallas kernel
    # (ops/flash_attention.py) instead of the jnp scan update.
    ring_use_flash: bool = False
    # auto picks blockwise over dense at/after this sequence length.
    blockwise_min_seq: int = 2048
    # Rematerialization (gradient checkpointing): trade FLOPs for HBM so
    # long-context / 70B-class steps fit. "full" recomputes each block in
    # the backward, the flash forward kernel included; "dots" keeps what
    # the MXU produced — every dot_general result
    # (jax.checkpoint_policies.checkpoint_dots) and the flash forward
    # kernel's output and logsumexp (a Pallas call, kept by the names
    # ops/flash_attention.py gives them: +(dim x 2 + heads x 4) bytes a
    # token and layer, 65 MiB at 8192 x 4096) — and recomputes the cheap
    # elementwise/VPU work. Usually the right TPU default when
    # activations don't fit.
    remat: str = "none"
    # Vocab slab width for the fused linear+CE loss path (``targets=`` in
    # __call__): the (b, s, vocab) logits — 8 GiB at 8x2048x128k f32 —
    # are never materialized (ops/cross_entropy.py). None = dense CE.
    loss_vocab_chunk: Optional[int] = None
    # lax.scan over the layer stack: one traced/compiled Block for the
    # whole depth instead of n_layers inlined copies — O(1) HLO size and
    # compile time in depth (matters at 80 layers). Params gain a leading
    # layer axis; sharding_plan/apply_sharding_plan handle both layouts.
    scan_layers: bool = False

    def __post_init__(self) -> None:
        valid = ("auto", "dense", "blockwise", "flash", "ring")
        if self.attention_impl not in valid:
            raise ValueError(
                f"attention_impl={self.attention_impl!r} is not one of {valid}"
            )
        if self.remat not in ("none", "full", "dots"):
            raise ValueError(
                f"remat={self.remat!r} is not one of ('none', 'full', 'dots')"
            )

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads


CONFIGS: Dict[str, LlamaConfig] = {
    # Test/bench-sized models.
    "tiny": LlamaConfig(
        vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_hidden=128, max_seq_len=256, dtype=jnp.float32,
    ),
    "small": LlamaConfig(
        vocab_size=8192, dim=512, n_layers=6, n_heads=8, n_kv_heads=4,
        ffn_hidden=1536, max_seq_len=2048,
    ),
    # Llama-3 family shapes (parity with the reference's torchtitan configs).
    "1b": LlamaConfig(
        vocab_size=128256, dim=2048, n_layers=16, n_heads=32, n_kv_heads=8,
        ffn_hidden=8192, max_seq_len=8192,
    ),
    "8b": LlamaConfig(
        vocab_size=128256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        ffn_hidden=14336, max_seq_len=8192,
    ),
    "70b": LlamaConfig(
        vocab_size=128256, dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
        ffn_hidden=28672, max_seq_len=8192,
    ),
}


def large_bench_config(**overrides) -> LlamaConfig:
    """The ~445M-parameter config of the probes — the ONE definition.

    Shared by the chipless HBM sizing probe (scripts/hbm_probe.py), the
    compile-cost bench (benchmarks/compile_bench.py base dims), and the
    Mosaic cross-lowering gate (tests/test_mosaic_lowering.py), so the
    three always size, compile and lower the same program — the config
    used to be copied verbatim into each file, and a retune in one
    silently drifted the others. No cell of the benchmark runs it
    (``chipbench/`` has its own configurations).

    The choices (their speed on today's v5e: not measured):

    - head geometry 8x128, not 16x64: identical params and FLOPs at
      dim 1024, but 64-wide heads half-fill the 128-lane MXU.
    - remat="dots" + batch 4: the 15.75 GiB HBM budget, sized by
      chipless compiles for a described v5e (scripts/hbm_probe.py) —
      batch 8 without remat needs ~29 GB. "dots" keeps the matmuls'
      results and the flash kernel's (out, logsumexp), so a layer step
      runs the forward kernel once, not again in the backward.
    - flash attention + scanned layers + fused CE: the long-sequence
      kernel path, O(1) HLO in depth, and no materialized logits.

    ``overrides`` are dataclasses.replace fields (the compile bench
    flips scan_layers/remat to measure their cost; the HBM probe sweeps
    remat and sequence length).
    """
    base = LlamaConfig(
        vocab_size=32768, dim=1024, n_layers=24, n_heads=8, n_kv_heads=4,
        ffn_hidden=4096, max_seq_len=2048, dtype=jnp.bfloat16,
        attention_impl="flash", scan_layers=True, loss_vocab_chunk=4096,
        remat="dots",
    )
    return replace(base, **overrides) if overrides else base


class Attention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        dense = partial(
            nn.DenseGeneral, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.dtype
        )
        q = dense(features=(cfg.n_heads, cfg.head_dim), name="wq")(x)
        k = dense(features=(cfg.n_kv_heads, cfg.head_dim), name="wk")(x)
        v = dense(features=(cfg.n_kv_heads, cfg.head_dim), name="wv")(x)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        out = attend(
            q, k, v, scale=cfg.head_dim**-0.5, impl=cfg.attention_impl,
            sp_axis=cfg.sp_axis, ring_use_flash=cfg.ring_use_flash,
            blockwise_min_seq=cfg.blockwise_min_seq,
            block_size=cfg.attention_block_size, block_k=cfg.attention_block_k,
            batch_axes=cfg.flash_batch_axes, tp_axis=cfg.flash_tp_axis,
        )
        return dense(features=cfg.dim, axis=(-2, -1), name="wo")(out)


class MLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.dtype)
        gate = dense(cfg.ffn_hidden, name="w_gate")(x)
        up = dense(cfg.ffn_hidden, name="w_up")(x)
        return dense(cfg.dim, name="w_down")(nn.silu(gate) * up)


class Block(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        x = x + Attention(cfg, name="attn")(
            RMSNorm(cfg.norm_eps, cfg.dtype, name="attn_norm")(x), positions
        )
        x = x + MLP(cfg, name="mlp")(RMSNorm(cfg.norm_eps, cfg.dtype, name="mlp_norm")(x))
        return x


class Llama(nn.Module):
    """Callable two ways: ``apply(params, tokens)`` returns logits;
    ``apply(params, tokens, targets=targets)`` returns the mean token
    cross-entropy directly — with ``config.loss_vocab_chunk`` set, via the
    fused linear+CE that never materializes the logits."""

    config: LlamaConfig

    @nn.compact
    def __call__(
        self,
        tokens: jnp.ndarray,
        positions: Optional[jnp.ndarray] = None,
        targets: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        cfg = self.config
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        embed = nn.Embed(
            cfg.vocab_size, cfg.dim, dtype=cfg.dtype, param_dtype=cfg.dtype,
            name="tok_embed",
        )
        x = embed(tokens)
        # ``dots`` keeps every dot_general result and the flash forward
        # kernel's output and logsumexp (models/decoder.py ``remat_policy``).
        policy = remat_policy(
            cfg.remat, jax.checkpoint_policies.checkpoint_dots, FLASH_OUT, FLASH_LSE
        )
        x = layer_stack(Block, cfg, policy, x, positions)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, name="final_norm")(x)
        if cfg.tie_embeddings:
            out = tied_head(embed, x, targets, cfg.loss_vocab_chunk)
            return out if targets is not None else out.astype(jnp.float32)
        head = LMHead(cfg.dim, cfg.vocab_size, cfg.dtype, cfg.loss_vocab_chunk, name="lm_head")
        return head(x, targets) if targets is not None else head(x).astype(jnp.float32)


def cross_entropy_loss(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    token_logp = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(token_logp)


def sharding_plan(
    fsdp_axis: Optional[str] = "fsdp", tp_axis: Optional[str] = "tp"
) -> Dict[str, Any]:
    """Regex -> PartitionSpec map for Llama params (megatron layout:
    column-parallel wq/wk/wv/w_gate/w_up, row-parallel wo/w_down; embeddings
    vocab-sharded on tp; everything else fsdp-sharded on dim 0)."""
    f, t = fsdp_axis, tp_axis
    return {
        r".*tok_embed/embedding": P(t, f),
        r".*lm_head/kernel": P(f, t),
        r".*(wq|wk|wv)/kernel": P(f, t, None),
        r".*wo/kernel": P(t, None, f),
        r".*(w_gate|w_up)/kernel": P(f, t),
        r".*w_down/kernel": P(t, f),
        r".*scale": P(),
    }


def plan_shardings(params: Any, mesh: Any, plan: Dict[str, Any]) -> Any:
    """Maps each param leaf (by its flattened path) to a NamedSharding from
    the plan; unmatched leaves replicate. Works on abstract leaves
    (ShapeDtypeStruct / eval_shape output) and abstract meshes too — only
    ``.ndim``/``.shape`` are read — so AOT lowering of a sharded train
    step (tests/test_mosaic_lowering.py's scale gate) can build the exact
    in_shardings the runtime path uses without materializing anything."""
    import re

    from jax.sharding import NamedSharding

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)

    def path_str(path: Tuple) -> str:
        return "/".join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in path
        )

    out = []
    for path, leaf in flat:
        name = path_str(path)
        spec = P()
        for pattern, candidate in plan.items():
            if re.fullmatch(pattern, name):
                spec = candidate
                break
        # Scanned stacks carry a leading layer axis (scan_layers=True):
        # the plan describes the per-layer shape, so shift it right and
        # replicate over the stack axis.
        if len(spec) and leaf.ndim == len(spec) + 1:
            spec = P(None, *spec)
        # Drop spec axes that don't divide the leaf's dims.
        fixed = []
        for dim, entry in enumerate(spec):
            if entry is None:
                fixed.append(None)
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = 1
            for axis in axes:
                size *= mesh.shape.get(axis, 1)
            fixed.append(entry if leaf.shape[dim] % size == 0 else None)
        out.append(NamedSharding(mesh, P(*fixed)))
    return jax.tree_util.tree_unflatten(treedef, out)


def apply_sharding_plan(params: Any, mesh: Any, plan: Dict[str, Any]) -> Any:
    """Places each param leaf onto its :func:`plan_shardings` sharding
    (one batched transfer — per-leaf puts would serialize hundreds of
    copies over a slow host↔device link)."""
    return jax.device_put(params, plan_shardings(params, mesh, plan))
