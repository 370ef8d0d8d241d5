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
- attention dispatches to ring attention (ops/ring_attention.py) when a
  sequence-parallel axis is present in the ambient mesh, enabling context
  lengths sharded across devices;
- :func:`sharding_plan` gives PartitionSpecs for fsdp/tp axes (megatron
  layout: column-parallel qkv/up, row-parallel out/down) consumed by
  ``jax.jit`` via NamedSharding;
- ``remat`` ("full"/"dots") and ``scan_layers`` on the config: gradient
  checkpointing and a lax.scan'd layer stack, so 70B-class/long-context
  steps fit in HBM and compile in O(1) HLO size in depth.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from torchft_tpu.utils.platform import on_tpu

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
    # "auto": ring attention iff an 'sp' axis is in the ambient mesh, else
    # for long sequences the fused Pallas flash kernel on real TPU /
    # blockwise elsewhere, else dense. Explicit options:
    # "dense", "blockwise" (O(s*block) memory, ops/ring_attention.py),
    # "flash" (fused Pallas TPU kernel forward + same flash backward,
    # ops/flash_attention.py; interpret-mode off-TPU), "ring".
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
    # Mosaic kernels cannot be auto-partitioned by XLA SPMD: under a
    # jit-with-mesh (fsdp/tp/dp sharded train step) the flash path must
    # shard_map ITSELF or lowering fails outright. These name the mesh
    # axes it maps over when the ambient mesh binds them (batch over the
    # data axes, q/kv heads over the tensor axis — the megatron layout
    # sharding_plan uses); axes that are absent, size-1, already manual,
    # or non-dividing are dropped per-call.
    flash_batch_axes: Tuple[str, ...] = ("dp", "fsdp")
    flash_tp_axis: Optional[str] = "tp"
    # Route the ring path's per-hop block compute through the fused Pallas
    # kernel (ops/flash_attention.py) instead of the jnp scan update.
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


def _rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (batch, seq, heads, head_dim); positions: (batch, seq)."""
    freqs = _rope_freqs(x.shape[-1], theta)  # (head_dim/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (b, s, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (normed * scale).astype(self.dtype)


def _sp_axis_in_mesh(axis: str) -> bool:
    """True when the ambient abstract mesh binds ``axis`` with size > 1.

    Reads only the public ``jax.sharding.get_abstract_mesh`` accessor, which
    sees every context the ring path can actually execute in: shard_map
    tracing (Manual axes — the only place ``lax.ppermute(axis_name=...)``
    is bound) and ``jax.set_mesh``/``use_mesh`` scopes. A legacy
    ``with mesh:`` block alone is invisible here, but it also cannot bind
    the collective axis name ring attention requires — under it ``auto``
    correctly computes local attention, and an explicit
    ``attention_impl='ring'`` fails loudly at trace time with an
    unbound-axis-name error (test_models.py asserts that loud path) rather
    than silently returning per-shard results."""
    abstract = jax.sharding.get_abstract_mesh()
    if abstract is None or axis not in getattr(abstract, "axis_names", ()):
        return False
    return abstract.shape[axis] > 1


def _largest_dividing_subset(
    axes: Tuple[str, ...], sizes: Dict[str, int], n: int
) -> Tuple[str, ...]:
    """The subset of ``axes`` with the largest shard-count product that
    divides ``n``, in the original axis order (the spec/flatten order).
    Ties prefer more axes (finer sharding layout), then earlier subsets.
    Brute force: flash_batch_axes is 2-3 names, never a search problem."""
    best: Tuple[str, ...] = ()
    best_size = 1
    for mask in range(1, 1 << len(axes)):
        subset = tuple(a for i, a in enumerate(axes) if mask & (1 << i))
        size = 1
        for a in subset:
            size *= sizes[a]
        if n % size == 0 and (
            size > best_size or (size == best_size and len(subset) > len(best))
        ):
            best, best_size = subset, size
    return best


# (shape, dropped-axes) combinations already warned about — the fallback
# fires on every traced call, and a sharded train step retraces per shape.
_FLASH_REPLICATION_WARNED: set = set()


def _warn_flash_replicated(
    dropped: Tuple[str, ...], kept: Tuple[str, ...], tp, dims, mesh
) -> None:
    """Once-per-shape warning when a usable mesh axis falls back to
    replication because the batch/head count doesn't divide it: the kernel
    still runs (inside the manual context), but the compute is replicated
    — and q/k/v all-gathered — across every dropped axis, a large silent
    performance cliff worth surfacing."""
    b, h, kv_heads = dims
    key = (dims, dropped, kept, tp)
    if key in _FLASH_REPLICATION_WARNED:
        return
    _FLASH_REPLICATION_WARNED.add(key)
    sizes = ", ".join(f"{a}={mesh.shape[a]}" for a in dropped)
    logging.getLogger(__name__).warning(
        "flash attention: batch=%d heads=%d/%d does not divide mesh axis(es) "
        "%s — the kernel replicates its compute (and all-gathers q/k/v) "
        "across them; kept batch axes %s, tp axis %s. Resize the batch/head "
        "counts or flash_batch_axes to restore full sharding.",
        b, h, kv_heads, sizes, kept or "()", tp,
    )


def _flash_under_ambient_mesh(cfg: LlamaConfig, q, k, v, scale: float):
    """Dispatches the fused Pallas kernel, shard_mapping it over the
    ambient mesh's data/tensor axes when one is bound.

    XLA SPMD cannot partition a Mosaic custom call ("Mosaic kernels
    cannot be automatically partitioned") — so inside a sharded train
    step (jit with a NamedSharding mesh: the FTMesh/HSDP path) a bare
    ``flash_attention`` fails to lower. Attention is embarrassingly
    parallel over (batch, head) in the non-SP case, so the wrapper maps
    batch over ``cfg.flash_batch_axes`` and heads over
    ``cfg.flash_tp_axis`` — the same layout ``sharding_plan`` gives the
    QKV projections, so no resharding is introduced. The map takes EVERY
    mesh axis that is not manual already: Mosaic refuses to lower while
    any axis of the mesh is left automatic, a size-1 one included (an
    fsdp=2 x tp=1 group failed on the chip exactly so). Axes already
    manual (the model is inside a caller's shard_map — shapes are
    already local and the kernel just works) are excluded; with none
    left the plain call is used. An axis that is not one of the
    configured ones, has size 1, or whose batch/head count doesn't
    divide is manual but drops out of the specs — the kernel then
    computes replicated over it, because a bare pallas_call under
    jit-with-mesh is the exact lowering error this wrapper exists to
    avoid, dividing or not. GQA inside each shard is preserved: h and
    kv_heads are divided by the same tp factor, so the group ratio is
    unchanged.

    The ambient mesh is read via ``jax.sharding.get_abstract_mesh`` —
    bind it with ``jax.set_mesh(mesh)`` (what the in-repo drills and
    examples do); a legacy ``with mesh:`` block alone is invisible
    here, leaving the bare kernel to fail lowering on a real pod with
    XLA's own "wrap the call in a shard_map" error."""
    from torchft_tpu.ops.flash_attention import flash_attention

    from jax.sharding import AxisType

    call = partial(
        flash_attention,
        scale=scale,
        block_q=cfg.attention_block_size,
        block_k=cfg.attention_block_k or cfg.attention_block_size,
    )
    mesh = jax.sharding.get_abstract_mesh()
    axis_types = dict(
        zip(getattr(mesh, "axis_names", ()), getattr(mesh, "axis_types", ()))
    )

    # Already-manual axes (the model is inside a caller's shard_map) must
    # not be wrapped again — shapes are already local there and a nested
    # map over local shapes mis-divides them. Every other axis becomes
    # manual, whatever its size.
    manual = {a for a, t in axis_types.items() if t != AxisType.Manual}
    if not manual:
        return call(q, k, v)

    def usable(axis: Optional[str]) -> bool:
        return axis in manual and mesh.shape[axis] > 1

    b, _, h, _ = q.shape
    kv_heads = k.shape[2]
    usable_batch = tuple(a for a in cfg.flash_batch_axes if usable(a))
    # Non-dividing fallback is PER-AXIS, not all-or-nothing: keep the
    # largest dividing subset (by total shard count) of the usable batch
    # axes instead of replicating over every one of them the moment the
    # product stops dividing — e.g. batch 4 on dp=2 x fsdp=4 still shards
    # over dp. Any axis left out replicates the attention compute (and
    # all-gathers q/k/v) across it — a silent performance cliff, so it
    # warns once per shape below.
    batch_axes = _largest_dividing_subset(
        usable_batch, {a: mesh.shape[a] for a in usable_batch}, b
    )
    tp = cfg.flash_tp_axis if usable(cfg.flash_tp_axis) else None
    if tp is not None and (h % mesh.shape[tp] or kv_heads % mesh.shape[tp]):
        tp = None
    dropped = tuple(a for a in usable_batch if a not in batch_axes)
    if usable(cfg.flash_tp_axis) and tp is None:
        dropped += (cfg.flash_tp_axis,)
    if dropped:
        _warn_flash_replicated(dropped, batch_axes, tp, (b, h, kv_heads), mesh)
    bspec = batch_axes if batch_axes else None
    spec = P(bspec, None, tp, None)
    return jax.shard_map(
        call,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names=manual,
    )(q, k, v)


def causal_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, scale: float
) -> jnp.ndarray:
    """Grouped-query causal attention; fp32 softmax on the VPU, matmuls in
    the input dtype on the MXU. Shapes: q (b,s,h,d); k,v (b,s,kv,d)."""
    b, s, h, d = q.shape
    kv_heads = k.shape[2]
    group = h // kv_heads
    q = q.reshape(b, s, kv_heads, group, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) * scale
    mask = jnp.tril(jnp.ones((s, s), dtype=bool))
    scores = jnp.where(mask[None, None, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, d)


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

        scale = cfg.head_dim**-0.5
        use_ring = cfg.attention_impl == "ring" or (
            cfg.attention_impl == "auto" and _sp_axis_in_mesh(cfg.sp_axis)
        )
        if use_ring:
            from torchft_tpu.ops.ring_attention import (
                ring_attention,
                ring_attention_flash,
            )

            ring = ring_attention_flash if cfg.ring_use_flash else ring_attention
            out = ring(q, k, v, axis_name=cfg.sp_axis, scale=scale)
        elif cfg.attention_impl == "flash" or (
            cfg.attention_impl == "auto"
            and x.shape[1] >= cfg.blockwise_min_seq
            and on_tpu()
        ):
            # On real TPU hardware, auto prefers the fused Pallas kernel for
            # long sequences: same O(s·block) memory as blockwise but one
            # Mosaic kernel instead of a jnp scan (re-verified against dense
            # on every live-chip bench via verify_on_chip). Under a sharded
            # train step the dispatcher shard_maps the kernel itself —
            # Mosaic custom calls cannot be auto-partitioned by XLA SPMD.
            out = _flash_under_ambient_mesh(cfg, q, k, v, scale)
        elif cfg.attention_impl == "blockwise" or (
            cfg.attention_impl == "auto" and x.shape[1] >= cfg.blockwise_min_seq
        ):
            from torchft_tpu.ops.ring_attention import blockwise_attention

            out = blockwise_attention(
                q, k, v, scale=scale, block_size=cfg.attention_block_size
            )
        else:
            out = causal_attention(q, k, v, scale)
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


def _remat_policy(remat: str):
    """``dots`` keeps what the MXU produced: every ``dot_general`` result
    and the flash forward kernel's (out, logsumexp), which is a Pallas call
    and so invisible to ``checkpoint_dots`` alone — without the names the
    backward would run the whole forward kernel a second time. ``full``
    (None) recomputes everything, that kernel included."""
    if remat != "dots":
        return None
    from torchft_tpu.ops.flash_attention import FLASH_LSE, FLASH_OUT

    policies = jax.checkpoint_policies
    return policies.save_from_both_policies(
        policies.checkpoint_dots,
        policies.save_only_these_names(FLASH_OUT, FLASH_LSE),
    )


class _ScanCell(nn.Module):
    """One Block in ``(carry, broadcast) -> (carry, out)`` shape for
    ``nn.scan``; params live under ``<stack>/block`` with a leading layer
    axis added by the scan's ``variable_axes={'params': 0}``."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray):
        return Block(self.config, name="block")(x, positions), None


class _LMHead(nn.Module):
    """The output projection, param-compatible with ``nn.Dense`` (same
    ``lm_head/kernel`` path, lecun-normal init, dtype promotion): owning
    the kernel directly lets the fused loss path hand it to
    :func:`~torchft_tpu.ops.cross_entropy.chunked_cross_entropy` without
    ever forming the logits."""

    config: LlamaConfig

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        targets: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        cfg = self.config
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (cfg.dim, cfg.vocab_size),
            cfg.dtype,
        )
        if targets is None:
            return jnp.dot(x, kernel.astype(cfg.dtype))
        from torchft_tpu.ops.cross_entropy import chunked_cross_entropy

        return chunked_cross_entropy(x, kernel, targets, cfg.loss_vocab_chunk)


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
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape
            )
        embed = nn.Embed(
            cfg.vocab_size, cfg.dim, dtype=cfg.dtype, param_dtype=cfg.dtype,
            name="tok_embed",
        )
        x = embed(tokens)
        if cfg.scan_layers:
            cell = _ScanCell
            if cfg.remat != "none":
                # prevent_cse is safe (and standard) under scan: the loop
                # boundary already blocks the CSE remat would otherwise fight.
                cell = nn.remat(
                    cell, policy=_remat_policy(cfg.remat), prevent_cse=False
                )
            stack = nn.scan(
                cell,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                length=cfg.n_layers,
                in_axes=nn.broadcast,
            )
            x, _ = stack(cfg, name="layers")(x, positions)
        else:
            block = Block
            if cfg.remat != "none":
                block = nn.remat(Block, policy=_remat_policy(cfg.remat))
            for layer in range(cfg.n_layers):
                x = block(cfg, name=f"layer_{layer}")(x, positions)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, name="final_norm")(x)
        if targets is not None:
            from torchft_tpu.ops.cross_entropy import chunked_cross_entropy

            if cfg.tie_embeddings:
                return chunked_cross_entropy(
                    x, embed.embedding.T, targets, cfg.loss_vocab_chunk
                )
            return _LMHead(cfg, name="lm_head")(x, targets)
        if cfg.tie_embeddings:
            logits = embed.attend(x)
        else:
            logits = _LMHead(cfg, name="lm_head")(x)
        return logits.astype(jnp.float32)


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
