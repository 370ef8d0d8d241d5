"""Causal attention, one entry: :func:`attend` chooses the kernel a model's
attention runs (ring, flash, blockwise or dense), :func:`flash_under_mesh` is
the one way into a Mosaic attention call (it shard_maps the kernel over an
ambient mesh, which XLA cannot partition), and :func:`causal_attention` is the
dense path and every kernel's oracle. Attention under a learned key selection
makes its choice in ops/sparse_attention.py ``selected_attention`` and comes
through :func:`flash_under_mesh` too.
"""

from __future__ import annotations

import logging
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from torchft_tpu.ops.flash_attention import flash_attention
from torchft_tpu.ops.ring_attention import blockwise_attention, ring_attention, ring_attention_flash
from torchft_tpu.utils.platform import on_tpu

__all__ = ["attend", "causal_attention", "flash_under_mesh"]


def causal_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, scale: float, window: Optional[int] = None
) -> jnp.ndarray:
    """Grouped-query causal attention; fp32 softmax on the VPU, matmuls in
    the input dtype on the MXU. Shapes: q (b,s,h,d); k,v (b,s,kv,d). With a
    ``window`` query t sees key u iff ``0 <= t - u < window``."""
    b, s, h, d = q.shape
    kv_heads = k.shape[2]
    group = h // kv_heads
    q = q.reshape(b, s, kv_heads, group, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) * scale
    mask = jnp.tril(jnp.ones((s, s), dtype=bool))
    if window is not None:
        mask &= ~jnp.tril(jnp.ones((s, s), dtype=bool), -window)
    scores = jnp.where(mask[None, None, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, d)


def sp_axis_in_mesh(axis: str) -> bool:
    """True when the ambient abstract mesh binds ``axis`` with size > 1.

    Reads only the public ``jax.sharding.get_abstract_mesh`` accessor, which
    sees every context the ring path can actually execute in: shard_map
    tracing (Manual axes — the only place ``lax.ppermute(axis_name=...)``
    is bound) and ``jax.set_mesh``/``use_mesh`` scopes. A legacy
    ``with mesh:`` block alone is invisible here, but it also cannot bind
    the collective axis name ring attention requires — under it ``auto``
    computes local attention, and an explicit ``impl='ring'`` fails loudly
    at trace time with an unbound-axis-name error (test_models.py asserts
    that) rather than silently returning per-shard results."""
    abstract = jax.sharding.get_abstract_mesh()
    if abstract is None or axis not in getattr(abstract, "axis_names", ()):
        return False
    return abstract.shape[axis] > 1


def largest_dividing_subset(axes: Tuple[str, ...], sizes: Dict[str, int], n: int) -> Tuple[str, ...]:
    """The subset of ``axes`` with the largest shard-count product that
    divides ``n``, in the original axis order (the spec/flatten order).
    Ties prefer more axes (finer sharding layout), then earlier subsets.
    Brute force: ``batch_axes`` is 2-3 names, never a search problem."""
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
        "counts or the batch axes to restore full sharding.",
        b, h, kv_heads, sizes, kept or "()", tp,
    )


def flash_under_mesh(
    q, k, v, *, scale: float, selection=None, window: Optional[int] = None,
    batch_axes: Tuple[str, ...] = ("dp", "fsdp"), tp_axis: Optional[str] = "tp",
    **blocks: int,
):
    """``flash_attention(q, k, v, scale=, selection=, window=, **blocks)``
    (``blocks``: its ``block_q`` and ``block_k``), shard_mapped over the
    ambient mesh's data/tensor axes when one is bound.

    XLA SPMD cannot partition a Mosaic custom call ("Mosaic kernels
    cannot be automatically partitioned") — so inside a sharded train
    step (jit with a NamedSharding mesh: the FTMesh/HSDP path) a bare
    ``flash_attention`` fails to lower. Attention is embarrassingly
    parallel over (batch, head) in the non-SP case, so the wrapper maps
    batch over ``batch_axes`` and heads over ``tp_axis`` — the megatron
    layout a sharding plan gives the QKV projections, so no resharding is
    introduced; a ``selection`` (b, s, s), one for all heads, goes with
    the batch. The map takes EVERY mesh axis that is not manual already:
    Mosaic refuses to lower while any axis of the mesh is left automatic,
    a size-1 one included (an fsdp=2 x tp=1 group failed on the chip
    exactly so). Axes already manual (the model is inside a caller's
    shard_map — shapes are already local and the kernel just works) are
    excluded; with none left the plain call is used. An axis that is not
    one of the named ones, has size 1, or whose batch/head count doesn't
    divide is manual but drops out of the specs — the kernel then computes
    replicated over it, because a bare pallas_call under jit-with-mesh is
    the exact lowering error this wrapper exists to avoid, dividing or
    not. GQA inside each shard is preserved: h and kv_heads are divided by
    the same tp factor, so the group ratio is unchanged.

    The ambient mesh is read via ``jax.sharding.get_abstract_mesh`` —
    bind it with ``jax.set_mesh(mesh)`` (what the in-repo drills and
    examples do); a legacy ``with mesh:`` block alone is invisible
    here, leaving the bare kernel to fail lowering on a real pod with
    XLA's own "wrap the call in a shard_map" error."""

    def call(q, k, v, selection=None):
        return flash_attention(
            q, k, v, scale=scale, selection=selection, window=window, **blocks
        )

    operands = (q, k, v) if selection is None else (q, k, v, selection)
    mesh = jax.sharding.get_abstract_mesh()
    axis_types = dict(zip(getattr(mesh, "axis_names", ()), getattr(mesh, "axis_types", ())))
    # Already-manual axes (the model is inside a caller's shard_map) must
    # not be wrapped again — shapes are already local there and a nested
    # map over local shapes mis-divides them. Every other axis becomes
    # manual, whatever its size.
    manual = {a for a, t in axis_types.items() if t != AxisType.Manual}
    if not manual:
        return call(*operands)

    def usable(axis: Optional[str]) -> bool:
        return axis in manual and mesh.shape[axis] > 1

    b, _, h, _ = q.shape
    kv_heads = k.shape[2]
    usable_batch = tuple(a for a in batch_axes if usable(a))
    # Non-dividing fallback is PER-AXIS, not all-or-nothing: keep the
    # largest dividing subset (by total shard count) of the usable batch
    # axes instead of replicating over every one of them the moment the
    # product stops dividing — e.g. batch 4 on dp=2 x fsdp=4 still shards
    # over dp. Any axis left out replicates the attention compute (and
    # all-gathers q/k/v) across it — a silent performance cliff, so it
    # warns once per shape below.
    kept = largest_dividing_subset(
        usable_batch, {a: mesh.shape[a] for a in usable_batch}, b
    )
    tp = tp_axis if usable(tp_axis) else None
    if tp is not None and (h % mesh.shape[tp] or kv_heads % mesh.shape[tp]):
        tp = None
    dropped = tuple(a for a in usable_batch if a not in kept)
    if usable(tp_axis) and tp is None:
        dropped += (tp_axis,)
    if dropped:
        _warn_flash_replicated(dropped, kept, tp, (b, h, kv_heads), mesh)
    bspec = kept if kept else None
    spec = P(bspec, None, tp, None)
    return jax.shard_map(
        call,
        mesh=mesh,
        in_specs=(spec, spec, spec, P(bspec, None, None))[: len(operands)],
        out_specs=spec,
        axis_names=manual,
    )(*operands)


def attend(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *, scale: float, impl: str = "auto",
    sp_axis: str = "sp", ring_use_flash: bool = False, blockwise_min_seq: int = 2048,
    block_size: int = 512, block_k: Optional[int] = None,
    batch_axes: Tuple[str, ...] = ("dp", "fsdp"), tp_axis: Optional[str] = "tp",
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Causal grouped-query attention of q (b, s, h, d) over k, v
    (b, s, kv, d), positions encoded, by the path ``impl`` names; with a
    ``window`` each query over the ``window`` latest keys up to its own
    (flash, blockwise and dense take it; the ring does not):

    - ``ring`` (ops/ring_attention.py over ``sp_axis``; its per-hop compute
      through the flash kernels where ``ring_use_flash``), which ``auto``
      takes iff the ambient mesh binds ``sp_axis``;
    - ``flash`` (:func:`flash_under_mesh` at blocks ``block_size`` x
      ``block_k``, None = ``block_size``; interpreted off a TPU), which
      ``auto`` takes on a TPU from ``blockwise_min_seq`` positions on: same
      O(s·block) memory as blockwise but one Mosaic kernel instead of a jnp
      scan (re-verified against dense on every live-chip bench via
      ``verify_on_chip``);
    - ``blockwise`` (O(s·block) memory in plain XLA), ``auto``'s choice at
      that length elsewhere;
    - ``dense`` (:func:`causal_attention`), ``auto``'s below it.
    """
    s = q.shape[1]
    if impl == "ring" or (impl == "auto" and sp_axis_in_mesh(sp_axis)):
        if window is not None:
            raise ValueError("ring attention takes no window")
        ring = ring_attention_flash if ring_use_flash else ring_attention
        return ring(q, k, v, axis_name=sp_axis, scale=scale)
    if impl == "flash" or (impl == "auto" and s >= blockwise_min_seq and on_tpu()):
        return flash_under_mesh(
            q, k, v, scale=scale, window=window, batch_axes=batch_axes, tp_axis=tp_axis,
            block_q=block_size, block_k=block_k or block_size,
        )
    if impl == "blockwise" or (impl == "auto" and s >= blockwise_min_seq):
        return blockwise_attention(q, k, v, scale=scale, block_size=block_size, window=window)
    return causal_attention(q, k, v, scale, window)
