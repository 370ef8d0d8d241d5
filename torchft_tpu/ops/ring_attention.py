"""Ring attention: causal attention over a sequence-parallel mesh axis.

Long-context is first-class in this framework even though the reference has
no context-parallel code (SURVEY.md §2.7: absent; the FT replica axis stays
orthogonal so a CP/ring axis fits inside the slice). Design follows the
blockwise/ring attention literature (Liu et al., https://arxiv.org/abs/2310.01889):

Each device in the ``sp`` axis holds one sequence shard of Q, K, V. K/V
blocks rotate around the ring via ``jax.lax.ppermute`` while every device
accumulates attention for its local Q block with an **online softmax**
(running max + normalizer, flash-attention style), so the full sequence
never materializes on one chip. Causality is enforced per ring step by
comparing global position ids — a shard attends to a rotated KV block only
where q_pos >= k_pos, which also makes the code correct for any sequence
layout (contiguous shards being the standard one).

Use inside shard_map/jit over a mesh with the ``sp`` axis, activations
sharded (batch, seq/sp, heads, head_dim). Compute rides the MXU per block;
ICI traffic is one KV block per step, overlapped by XLA with the block
matmuls.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = [
    "blockwise_attention",
    "ring_attention",
    "ring_attention_flash",
    "ring_attention_sharded",
    "ring_attention_zigzag",
    "zigzag_permutation",
]

_NEG_INF = -1e30


def _visible(q_pos, k_pos, window):
    """(b, sq, 1, 1, sk) bool: key u is no later than query t and, with a
    ``window``, fewer than ``window`` positions before it."""
    apart = q_pos[:, :, None, None, None] - k_pos[:, None, None, None, :]
    seen = apart >= 0
    return seen if window is None else seen & (apart < window)


def _block_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_pos: jnp.ndarray,
    k_pos: jnp.ndarray,
    scale: float,
    acc: jnp.ndarray,
    row_max: jnp.ndarray,
    row_sum: jnp.ndarray,
    window: Optional[int] = None,
):
    """One flash-style block update.

    q: (b, sq, kv, g, d); k/v: (b, sk, kv, d); positions (b, sq)/(b, sk).
    acc: (b, sq, kv, g, d) f32; row_max/row_sum: (b, sq, kv, g) f32.
    """
    scores = jnp.einsum("bskgd,btkd->bskgt", q, k).astype(jnp.float32) * scale
    scores = jnp.where(_visible(q_pos, k_pos, window), scores, _NEG_INF)

    block_max = jnp.max(scores, axis=-1)
    new_max = jnp.maximum(row_max, block_max)
    # Rescale the old accumulator to the new max.
    correction = jnp.exp(row_max - new_max)
    probs = jnp.exp(scores - new_max[..., None])
    new_sum = row_sum * correction + jnp.sum(probs, axis=-1)
    block_out = jnp.einsum("bskgt,btkd->bskgd", probs.astype(v.dtype), v).astype(
        jnp.float32
    )
    new_acc = acc * correction[..., None] + block_out
    return new_acc, new_max, new_sum


def blockwise_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: Optional[float] = None,
    block_size: int = 512,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Memory-bounded causal GQA attention on ONE device.

    The single-device sibling of :func:`ring_attention`: a ``lax.scan`` over
    KV blocks with the same online-softmax block update, so activation
    memory is O(s·block) instead of dense attention's O(s²) — in BOTH
    directions: a flash-style ``custom_vjp`` saves only (q, k, v, out,
    logsumexp) and recomputes each block's probabilities in the backward
    pass (a plain scan would stack per-block residuals and give the
    quadratic memory right back under AD). Static shapes, no
    data-dependent control flow; each block's matmuls ride the MXU.

    Shapes: q (b, s, h, d); k/v (b, s, kv_heads, d). The sequence is padded
    to a multiple of ``block_size``; padded KV positions are masked out by
    the causal position comparison (their positions sit beyond every real
    query). ``window``: a query sees the ``window`` latest keys up to its
    own; every block is still walked (this is the CPU path).
    """
    b, s, h, d = q.shape
    if scale is None:
        scale = d**-0.5
    return _blockwise_core(q, k, v, float(scale), int(block_size), window)


def _blockwise_blocks(k: jnp.ndarray, v: jnp.ndarray, block_size: int):
    """Pads K/V to a block multiple and returns (k_blocks, v_blocks,
    k_pos_blocks) with the block axis leading (scan xs layout)."""
    b, s = k.shape[0], k.shape[1]
    pad = (-s) % block_size
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_blocks = (s + pad) // block_size
    kv_heads, d = k.shape[2], k.shape[3]
    k_blocks = k.reshape(b, n_blocks, block_size, kv_heads, d).swapaxes(0, 1)
    v_blocks = v.reshape(b, n_blocks, block_size, kv_heads, d).swapaxes(0, 1)
    kp = jnp.broadcast_to(jnp.arange(s + pad), (b, s + pad))
    kp_blocks = kp.reshape(b, n_blocks, block_size).swapaxes(0, 1)
    return k_blocks, v_blocks, kp_blocks, n_blocks, pad


def _blockwise_fwd_impl(q, k, v, scale: float, block_size: int, window=None):
    b, s, h, d = q.shape
    kv_heads = k.shape[2]
    group = h // kv_heads
    q_pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    k_blocks, v_blocks, kp_blocks, _, _ = _blockwise_blocks(k, v, block_size)

    qg = q.reshape(b, s, kv_heads, group, d)
    acc = jnp.zeros((b, s, kv_heads, group, d), dtype=jnp.float32)
    row_max = jnp.full((b, s, kv_heads, group), _NEG_INF, dtype=jnp.float32)
    row_sum = jnp.zeros((b, s, kv_heads, group), dtype=jnp.float32)

    def scan_step(carry, blk):
        acc, row_max, row_sum = carry
        k_blk, v_blk, kp_blk = blk
        acc, row_max, row_sum = _block_attention(
            qg, k_blk, v_blk, q_pos, kp_blk, scale, acc, row_max, row_sum, window
        )
        return (acc, row_max, row_sum), None

    (acc, row_max, row_sum), _ = jax.lax.scan(
        scan_step, (acc, row_max, row_sum), (k_blocks, v_blocks, kp_blocks)
    )
    safe_sum = jnp.maximum(row_sum, 1e-30)
    out = (acc / safe_sum[..., None]).reshape(b, s, h, d).astype(q.dtype)
    lse = row_max + jnp.log(safe_sum)  # (b, s, kv, g) f32
    return out, lse


from functools import partial as _partial


@_partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _blockwise_core(q, k, v, scale: float, block_size: int, window=None):
    return _blockwise_fwd_impl(q, k, v, scale, block_size, window)[0]


def _blockwise_core_fwd(q, k, v, scale: float, block_size: int, window=None):
    out, lse = _blockwise_fwd_impl(q, k, v, scale, block_size, window)
    return out, (q, k, v, out, lse)


def _blockwise_core_bwd(scale: float, block_size: int, residuals, d_out, window=None):
    q, k, v, out, lse = residuals
    b, s, h, d = q.shape
    kv_heads = k.shape[2]
    group = h // kv_heads
    q_pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    k_blocks, v_blocks, kp_blocks, n_blocks, pad = _blockwise_blocks(
        k, v, block_size
    )

    qg = q.reshape(b, s, kv_heads, group, d).astype(jnp.float32)
    og = out.reshape(b, s, kv_heads, group, d).astype(jnp.float32)
    dog = d_out.reshape(b, s, kv_heads, group, d).astype(jnp.float32)
    # delta_i = sum_d dO_i . O_i  (flash-attention-2 backward identity).
    delta = jnp.sum(dog * og, axis=-1)  # (b, s, kv, g)

    def scan_step(dq_acc, blk):
        k_blk, v_blk, kp_blk = blk
        k32 = k_blk.astype(jnp.float32)
        v32 = v_blk.astype(jnp.float32)
        scores = jnp.einsum("bskgd,btkd->bskgt", qg, k32) * scale
        # p rebuilt from the saved logsumexp; masked entries exactly 0.
        seen = _visible(q_pos, kp_blk, window)
        p = jnp.where(seen, jnp.exp(scores - lse[..., None]), 0.0)
        dv_blk = jnp.einsum("bskgt,bskgd->btkd", p, dog)
        dp = jnp.einsum("bskgd,btkd->bskgt", dog, v32)
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bskgt,btkd->bskgd", ds, k32)
        dk_blk = jnp.einsum("bskgt,bskgd->btkd", ds, qg)
        return dq_acc, (dk_blk, dv_blk)

    # zeros_like, not a fresh constant: under a caller's shard_map (the
    # flash dispatcher maps itself over fsdp/tp) the carry must be varying
    # over the same manual axes as q, or the scan's carry types mismatch.
    dq_init = jnp.zeros_like(qg)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        scan_step, dq_init, (k_blocks, v_blocks, kp_blocks)
    )
    dk = dk_blocks.swapaxes(0, 1).reshape(b, n_blocks * block_size, kv_heads, d)
    dv = dv_blocks.swapaxes(0, 1).reshape(b, n_blocks * block_size, kv_heads, d)
    if pad:
        dk = dk[:, :s]
        dv = dv[:, :s]
    return (
        dq.reshape(b, s, h, d).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


_blockwise_core.defvjp(
    _blockwise_core_fwd,
    lambda scale, block_size, window, residuals, d_out: _blockwise_core_bwd(
        scale, block_size, residuals, d_out, window
    ),
)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    scale: Optional[float] = None,
    q_positions: Optional[jnp.ndarray] = None,
    k_positions: Optional[jnp.ndarray] = None,
    kv_sub_blocks: int = 1,
) -> jnp.ndarray:
    """Causal GQA attention with K/V rotating over ``axis_name``.

    Call from inside shard_map (or jit-with-sharding) where the seq dim of
    q/k/v is the per-device shard. Shapes: q (b, s_local, h, d);
    k/v (b, s_local, kv_heads, d). Positions default to contiguous shards
    ordered by the device's axis index.

    ``kv_sub_blocks``: each rotated KV block is processed in this many
    sequence sub-blocks, each causally skipped independently — with the
    zigzag layout (2 chunks per shard) this is what turns the skip into a
    balanced wall-clock saving.
    """
    axis_size = jax.lax.psum(1, axis_name)
    axis_index = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    kv_heads = k.shape[2]
    group = h // kv_heads
    if scale is None:
        scale = d**-0.5

    if q_positions is None:
        base = axis_index * s_local
        q_positions = jnp.broadcast_to(base + jnp.arange(s_local), (b, s_local))
    if k_positions is None:
        k_positions = q_positions

    qg = q.reshape(b, s_local, kv_heads, group, d)
    acc = jnp.zeros((b, s_local, kv_heads, group, d), dtype=jnp.float32)
    row_max = jnp.full((b, s_local, kv_heads, group), _NEG_INF, dtype=jnp.float32)
    row_sum = jnp.zeros((b, s_local, kv_heads, group), dtype=jnp.float32)
    # The constant-initialized carries must be marked varying over the ring
    # axis or the fori_loop carry types mismatch under shard_map's
    # varying-manual-axes checking.
    acc, row_max, row_sum = (
        jax.lax.pcast(x, (axis_name,), to="varying")
        for x in (acc, row_max, row_sum)
    )

    if s_local % kv_sub_blocks != 0:
        raise ValueError(
            f"kv_sub_blocks ({kv_sub_blocks}) must divide the shard ({s_local})"
        )
    sub = s_local // kv_sub_blocks

    def ring_step(step, carry):
        acc, row_max, row_sum, k_blk, v_blk, k_pos = carry

        # Causal skip, per (query sub-block, KV sub-block) pair: a pair
        # whose earliest KV position exceeds the sub-block's last query
        # position is fully masked — skip its matmuls while the block still
        # rotates. With the contiguous layout (kv_sub_blocks=1) this halves
        # attention FLOPs but latency stays bound by the busiest device
        # (ppermute is a barrier); the zigzag layout + sub_blocks=2 makes
        # every device's relevant-pair count equal, so the saving shows up
        # in wall-clock time.
        if kv_sub_blocks == 1:
            # Direct path: one causal-skip decision for the whole block
            # (avoids the sliced-accumulator machinery entirely).
            relevant = jnp.min(k_pos) <= jnp.max(q_positions)
            acc, row_max, row_sum = jax.lax.cond(
                relevant,
                lambda ops: _block_attention(
                    qg, ops[0], ops[1], q_positions, ops[2], scale, *ops[3:]
                ),
                lambda ops: (ops[3], ops[4], ops[5]),
                (k_blk, v_blk, k_pos, acc, row_max, row_sum),
            )
            return acc, row_max, row_sum, *_rotate(k_blk, v_blk, k_pos)
        for qi in range(kv_sub_blocks):
            q_sub = qg[:, qi * sub : (qi + 1) * sub]
            qp_sub = q_positions[:, qi * sub : (qi + 1) * sub]
            acc_sub = acc[:, qi * sub : (qi + 1) * sub]
            rm_sub = row_max[:, qi * sub : (qi + 1) * sub]
            rs_sub = row_sum[:, qi * sub : (qi + 1) * sub]
            q_sub_max = jnp.max(qp_sub)
            for ki in range(kv_sub_blocks):
                k_sub = k_blk[:, ki * sub : (ki + 1) * sub]
                v_sub = v_blk[:, ki * sub : (ki + 1) * sub]
                p_sub = k_pos[:, ki * sub : (ki + 1) * sub]
                relevant = jnp.min(p_sub) <= q_sub_max
                acc_sub, rm_sub, rs_sub = jax.lax.cond(
                    relevant,
                    lambda ops: _block_attention(
                        q_sub, ops[0], ops[1], qp_sub, ops[2], scale, *ops[3:]
                    ),
                    lambda ops: (ops[3], ops[4], ops[5]),
                    (k_sub, v_sub, p_sub, acc_sub, rm_sub, rs_sub),
                )
            # dynamic_update_slice (not .at[].set): scatter transposes break
            # shard_map AD's sharding inference here.
            acc = jax.lax.dynamic_update_slice_in_dim(acc, acc_sub, qi * sub, axis=1)
            row_max = jax.lax.dynamic_update_slice_in_dim(row_max, rm_sub, qi * sub, axis=1)
            row_sum = jax.lax.dynamic_update_slice_in_dim(row_sum, rs_sub, qi * sub, axis=1)
        return acc, row_max, row_sum, *_rotate(k_blk, v_blk, k_pos)

    def _rotate(k_blk, v_blk, k_pos):
        # Rotate KV to the next ring position (keeping the final, unused hop
        # is fine: the loop is static and XLA overlaps it).
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        return (
            jax.lax.ppermute(k_blk, axis_name, perm),
            jax.lax.ppermute(v_blk, axis_name, perm),
            jax.lax.ppermute(k_pos, axis_name, perm),
        )

    carry = (acc, row_max, row_sum, k, v, k_positions)
    carry = jax.lax.fori_loop(0, axis_size, ring_step, carry)
    acc, row_max, row_sum = carry[:3]

    # Fully-masked rows (possible with user-supplied positions, e.g. packed
    # padding) must yield 0: their row_max never left _NEG_INF, and the
    # softmax shift would otherwise turn the all-masked scores into uniform
    # weights (mean of V).
    masked = row_max <= _NEG_INF
    out = jnp.where(
        masked[..., None], 0.0, acc / jnp.maximum(row_sum[..., None], 1e-30)
    )
    return out.reshape(b, s_local, h, d).astype(q.dtype)


def _ring_flash_fwd_impl(
    q, k, v, q_pos, k_pos, axis_name, scale, block_q, block_k, interpret
):
    from torchft_tpu.ops.flash_attention import (
        flash_attention_partial,
        merge_attention_partials,
    )

    axis_size = jax.lax.psum(1, axis_name)
    b, s_local, h, d = q.shape
    out = jnp.zeros((b, s_local, h, d), jnp.float32)
    lse = jnp.full((b, s_local, h), _NEG_INF, jnp.float32)
    # Constant-initialized carries must be varying over the ring axis (see
    # ring_attention above).
    out, lse = (
        jax.lax.pcast(x, (axis_name,), to="varying") for x in (out, lse)
    )

    def ring_step(_, carry):
        out, lse, k_blk, v_blk, kp = carry
        # The fused kernel computes this hop's partial (normalized out +
        # logsumexp); fully-masked hops come back as (0, sentinel) and the
        # merge weights them out exactly. Block-granular causal skipping
        # happens inside the kernel from the position arrays, so zigzag
        # layouts balance without the sliced-accumulator machinery.
        o_p, l_p = flash_attention_partial(
            q, k_blk, v_blk, q_pos, kp,
            scale=scale, block_q=block_q, block_k=block_k, interpret=interpret,
        )
        out, lse = merge_attention_partials(
            out, lse, o_p.astype(jnp.float32), l_p
        )
        perm = [(r, (r + 1) % axis_size) for r in range(axis_size)]
        return (
            out,
            lse,
            jax.lax.ppermute(k_blk, axis_name, perm),
            jax.lax.ppermute(v_blk, axis_name, perm),
            jax.lax.ppermute(kp, axis_name, perm),
        )

    out, lse, *_ = jax.lax.fori_loop(
        0, axis_size, ring_step, (out, lse, k, v, k_pos)
    )
    return out.astype(q.dtype), lse


@_partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _ring_flash(
    q, k, v, q_pos, k_pos, axis_name, scale, block_q, block_k, interpret,
    pallas_bwd,
):
    return _ring_flash_fwd_impl(
        q, k, v, q_pos, k_pos, axis_name, scale, block_q, block_k, interpret
    )[0]


def _ring_flash_fwd(
    q, k, v, q_pos, k_pos, axis_name, scale, block_q, block_k, interpret,
    pallas_bwd,
):
    out, lse = _ring_flash_fwd_impl(
        q, k, v, q_pos, k_pos, axis_name, scale, block_q, block_k, interpret
    )
    return out, (q, k, v, q_pos, k_pos, out, lse)


def _ring_bwd_loop(axis_name, dq0, k, v, k_pos, per_hop):
    """The ring-backward scaffold shared by both per-hop engines: f32
    (dq, dk, dv) carries marked varying over the ring axis (fresh zeros —
    a zeros_like of the already-varying inputs would make the pcast a
    rejected varying→varying cast), with each KV block's (dk, dv) partial
    sums riding the rotation home. ``per_hop(k_blk, v_blk, kp)`` returns
    this hop's (dq_inc, dk_inc, dv_inc) in f32.
    (Ring cost: fwd rotates {k, v, pos}; bwd rotates {k, v, pos, dk, dv}.)
    """
    axis_size = jax.lax.psum(1, axis_name)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros_like(dk0)
    dq0, dk0, dv0 = (
        jax.lax.pcast(x, (axis_name,), to="varying") for x in (dq0, dk0, dv0)
    )

    def ring_step(_, carry):
        dq, k_blk, v_blk, kp, dk_blk, dv_blk = carry
        dq_inc, dk_inc, dv_inc = per_hop(k_blk, v_blk, kp)
        perm = [(r, (r + 1) % axis_size) for r in range(axis_size)]
        rotate = lambda x: jax.lax.ppermute(x, axis_name, perm)
        return (
            dq + dq_inc,
            rotate(k_blk),
            rotate(v_blk),
            rotate(kp),
            rotate(dk_blk + dk_inc),
            rotate(dv_blk + dv_inc),
        )

    dq, _, _, _, dk, dv = jax.lax.fori_loop(
        0, axis_size, ring_step, (dq0, k, v, k_pos, dk0, dv0)
    )
    return dq, dk, dv


def _ring_flash_bwd_pallas(
    axis_name, scale, block_q, block_k, interpret, residuals, d_out
):
    """Ring backward with the fused Pallas backward kernel as the per-hop
    block compute: each hop runs flash_attention_partial_bwd with the
    GLOBAL logsumexp (and the hop-invariant delta = rowsum(dO·O), computed
    once). The kernel's position-driven causal block skip gives zigzag
    layouts their balance on the backward too."""
    from torchft_tpu.ops.flash_attention import flash_attention_partial_bwd

    q, k, v, q_pos, k_pos, out, lse = residuals
    b, s_local, h, d = q.shape

    delta = jnp.sum(
        d_out.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # (b, s, h), hop-invariant

    def per_hop(k_blk, v_blk, kp):
        return flash_attention_partial_bwd(
            q, k_blk, v_blk, d_out, out, lse, q_pos, kp,
            scale, block_q, block_k, interpret, delta=delta,
        )

    dq0 = jnp.zeros((b, s_local, h, d), jnp.float32)
    dq, dk, dv = _ring_bwd_loop(axis_name, dq0, k, v, k_pos, per_hop)
    return (
        dq.astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
        None,
        None,
    )


def _ring_flash_bwd(
    axis_name, scale, block_q, block_k, interpret, pallas_bwd, residuals, d_out
):
    if pallas_bwd:
        return _ring_flash_bwd_pallas(
            axis_name, scale, block_q, block_k, interpret, residuals, d_out
        )
    return _ring_flash_bwd_scan(
        axis_name, scale, block_q, block_k, interpret, residuals, d_out
    )


def _ring_flash_bwd_scan(axis_name, scale, block_q, block_k, interpret, residuals, d_out):
    """True ring backward from the saved (out, lse) residuals — the
    flash-attention-2 identity with the GLOBAL logsumexp as XLA einsums,
    so no forward recompute is needed. The interpret/CPU engine; shares
    the rotation scaffold with the Pallas engine via _ring_bwd_loop."""
    q, k, v, q_pos, k_pos, out, lse = residuals
    b, s_local, h, d = q.shape
    kv_heads = k.shape[2]
    group = h // kv_heads

    qg = q.reshape(b, s_local, kv_heads, group, d).astype(jnp.float32)
    og = out.reshape(b, s_local, kv_heads, group, d).astype(jnp.float32)
    dog = d_out.reshape(b, s_local, kv_heads, group, d).astype(jnp.float32)
    lse_g = lse.reshape(b, s_local, kv_heads, group)
    # delta_i = dO_i . O_i (flash-attention-2 backward identity).
    delta = jnp.sum(dog * og, axis=-1)  # (b, s, kv, g)

    def per_hop(k_blk, v_blk, kp):
        k32 = k_blk.astype(jnp.float32)
        v32 = v_blk.astype(jnp.float32)
        scores = jnp.einsum("bskgd,btkd->bskgt", qg, k32) * scale
        mask = q_pos[:, :, None, None, None] >= kp[:, None, None, None, :]
        # p rebuilt from the merged global logsumexp; masked entries are
        # exactly 0 (fully-masked rows have the -1e30 sentinel, whose exp
        # overflow is discarded by the where).
        p = jnp.where(mask, jnp.exp(scores - lse_g[..., None]), 0.0)
        dv_inc = jnp.einsum("bskgt,bskgd->btkd", p, dog)
        dp = jnp.einsum("bskgd,btkd->bskgt", dog, v32)
        ds = p * (dp - delta[..., None]) * scale
        dq_inc = jnp.einsum("bskgt,btkd->bskgd", ds, k32)
        dk_inc = jnp.einsum("bskgt,bskgd->btkd", ds, qg)
        return dq_inc, dk_inc, dv_inc

    dq0 = jnp.zeros((b, s_local, kv_heads, group, d), jnp.float32)
    dq, dk, dv = _ring_bwd_loop(axis_name, dq0, k, v, k_pos, per_hop)
    return (
        dq.reshape(b, s_local, h, d).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
        None,
        None,
    )


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention_flash(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    scale: Optional[float] = None,
    q_positions: Optional[jnp.ndarray] = None,
    k_positions: Optional[jnp.ndarray] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    use_pallas_bwd: Optional[bool] = None,
) -> jnp.ndarray:
    """:func:`ring_attention` with the fused Pallas kernel as the per-hop
    block compute (ops/flash_attention.py): K/V still rotate over
    ``axis_name`` via ppermute, but each hop's online-softmax inner loop
    runs as one kernel with VMEM-resident accumulators, and hops merge by
    logsumexp. Default blocks follow the flash kernel's on-chip sweep
    (512x1024, see flash_attention's docstring); the kernel entry points
    clamp them to each hop's padded local lengths, so small shards are
    unaffected. Same shapes/semantics as :func:`ring_attention`. The
    backward is a true ring backward from the saved (out, lse); on TPU
    (``use_pallas_bwd=None`` → when the forward compiles) each hop runs
    the fused backward kernel (flash_attention_partial_bwd), with the
    einsum ring backward as the interpret/CPU fallback."""
    axis_index = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    if scale is None:
        scale = d**-0.5
    if q_positions is None:
        base = axis_index * s_local
        q_positions = jnp.broadcast_to(base + jnp.arange(s_local), (b, s_local))
    if k_positions is None:
        k_positions = q_positions
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    if use_pallas_bwd is None:
        use_pallas_bwd = not interpret
    return _ring_flash(
        q, k, v,
        q_positions.astype(jnp.int32), k_positions.astype(jnp.int32),
        axis_name, float(scale), int(block_q), int(block_k), bool(interpret),
        bool(use_pallas_bwd),
    )


def ring_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: jax.sharding.Mesh,
    axis_name: str = "sp",
    scale: Optional[float] = None,
    use_flash: bool = False,
) -> jnp.ndarray:
    """Convenience wrapper: shard_map ring_attention over ``mesh`` with the
    sequence dim split on ``axis_name`` (other dims replicated).
    ``use_flash`` selects the fused Pallas per-hop kernel."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis_name, None, None)
    ring = ring_attention_flash if use_flash else ring_attention

    def inner(q_, k_, v_):
        return ring(q_, k_, v_, axis_name=axis_name, scale=scale)

    return shard_map(
        inner, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


def zigzag_permutation(seq_len: int, sp: int):
    """Load-balanced ("zigzag") sequence layout for causal ring attention.

    With contiguous shards, causal skipping idles early-ring devices while
    late ones do full work each step (latency = busiest device). Splitting
    the sequence into ``2*sp`` chunks and giving device ``i`` chunks
    ``(i, 2*sp-1-i)`` equalizes the causally-relevant work per device, so
    the FLOP saving becomes wall-clock saving.

    Returns (perm, inv_perm): apply ``x[:, perm]`` before sharding over
    ``sp`` and pass the matching positions (``perm`` itself) to
    :func:`ring_attention`; apply ``out[:, inv_perm]`` to restore order.
    """
    import numpy as np

    if seq_len % (2 * sp) != 0:
        raise ValueError(f"seq_len {seq_len} must divide by 2*sp ({2 * sp})")
    chunk = seq_len // (2 * sp)
    order = []
    for device in range(sp):
        order.extend([device, 2 * sp - 1 - device])
    perm = np.concatenate(
        [np.arange(c * chunk, (c + 1) * chunk) for c in order]
    )
    inv = np.empty_like(perm)
    inv[perm] = np.arange(seq_len)
    return perm, inv


def ring_attention_zigzag(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: jax.sharding.Mesh,
    axis_name: str = "sp",
    scale: Optional[float] = None,
    use_flash: bool = False,
) -> jnp.ndarray:
    """Ring attention with the zigzag layout applied transparently: inputs
    and outputs are in natural sequence order; internally the sequence is
    permuted so every ring step does balanced causal work. ``use_flash``
    selects the fused Pallas per-hop kernel, whose in-kernel block-granular
    causal skip replaces the scan path's kv_sub_blocks slicing."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    sp = mesh.shape[axis_name]
    b, s = q.shape[0], q.shape[1]
    perm, inv = zigzag_permutation(s, sp)
    perm_j = jnp.asarray(perm)
    positions = jnp.broadcast_to(perm_j, (b, s))

    spec = P(None, axis_name, None, None)
    pos_spec = P(None, axis_name)

    def inner(q_, k_, v_, pos):
        if use_flash:
            return ring_attention_flash(
                q_, k_, v_, axis_name=axis_name, scale=scale,
                q_positions=pos, k_positions=pos,
            )
        return ring_attention(
            q_, k_, v_, axis_name=axis_name, scale=scale,
            q_positions=pos, k_positions=pos, kv_sub_blocks=2,
        )

    mapped = shard_map(
        inner, mesh=mesh, in_specs=(spec, spec, spec, pos_spec), out_specs=spec
    )
    out = mapped(q[:, perm_j], k[:, perm_j], v[:, perm_j], positions)
    return out[:, jnp.asarray(inv)]
