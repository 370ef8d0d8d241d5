"""Pallas TPU flash attention: the fused-kernel path for the hot op.

The scan-based :func:`torchft_tpu.ops.ring_attention.blockwise_attention`
already gives O(s·block) memory, but each block update is a separate XLA
fusion: scores, mask, softmax bookkeeping, and the PV matmul round-trip
through HBM between blocks. This module fuses the whole online-softmax
inner loop into ONE Pallas kernel so the accumulators (acc, running max,
running sum) live in VMEM for the duration and the two matmuls per block
ride the MXU back-to-back (pallas_guide.md: grid iterated sequentially on
TPU with the last axis minor, which makes cross-grid-step VMEM scratch the
canonical accumulation pattern).

Forward AND backward are fused Pallas kernels on TPU. The backward is the
standard FlashAttention-2 two-pass recompute from the saved (out, lse)
residuals: a dq kernel accumulating over KV blocks and a dkv kernel
accumulating over Q blocks, with the per-row ``delta = rowsum(dO*O)``
identity computed by XLA outside the kernels (it fuses into the
surrounding graph). The two residuals the forward kernel produced carry
``checkpoint_name`` tags, ``FLASH_OUT`` and ``FLASH_LSE``: a Pallas call is
no ``dot_general``, so a remat policy that keeps dot results alone would
drop them and run the whole forward kernel again in the backward. The
model's ``remat="dots"`` keeps both by name (models/llama.py
``_remat_policy``); ``remat="full"`` and any policy that does not name them
recompute the kernel, and without remat the names do nothing.
:func:`flash_attention_partial` is untagged: its VJP is the ring's own.
GQA is handled by emitting per-q-head dk/dv partials and summing over the
group axis outside — keeps every output block written exactly once per
grid pass (no cross-step output aliasing, which Mosaic cannot express).
The scan-based blockwise backward remains the
interpret/CPU fallback (``use_pallas_bwd`` selects; CPU tests run the
Pallas backward in interpret mode explicitly). Run :func:`verify_on_chip`
on the chip after any kernel change (the CLAUDE.md kernel-verification
gate — every chip_smoke.py run re-executes it, forward and backward). In
the CPU suite, tests/test_mosaic_lowering.py cross-lowers every kernel here
for a TPU target (block-layout violations, the class interpret mode cannot
see) and tests/test_tpu_aot_compile.py compiles them
at real widths for a described v5e (VMEM limits, misaligned slices) — both
without a chip.
Note "auto" attention (models/llama.py) SELECTS this kernel on real TPU
for long sequences, so a kernel edit reaches default-configured runs:
never ship one without the on-chip gate.

The reference has no attention code at all (SURVEY.md §2.7: long-sequence
scaling is delegated to torchtitan); this is part of the beyond-reference
long-context stack, sitting below ring attention (which shards the
sequence across chips) as the per-chip kernel.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from torchft_tpu.utils.platform import on_tpu

from torchft_tpu.ops.ring_attention import _blockwise_core_bwd

__all__ = [
    "FLASH_OUT",
    "FLASH_LSE",
    "flash_attention",
    "flash_attention_partial",
    "flash_attention_partial_bwd",
    "merge_attention_partials",
]

# checkpoint_name tags of the forward kernel's two residuals (module
# docstring: who keeps them).
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"

_NEG_INF = -1e30
_PAD_POS = 2**31 - 1  # position for padded rows: beyond every real query


def _out_struct(shape, dtype, inputs):
    """ShapeDtypeStruct carrying the union of the inputs' varying-mesh-axes
    (vma): under shard_map(check_vma=True) pallas_call outputs must declare
    how they vary over manual axes; outside shard_map the union is empty."""
    try:
        vma = frozenset().union(*(jax.typeof(x).vma for x in inputs))
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    except (AttributeError, TypeError):
        return jax.ShapeDtypeStruct(shape, dtype)


def _fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    qp_ref,
    kp_ref,
    o_ref,
    lse_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    nk: int,
):
    """One (batch, head, q-block, kv-block) grid step.

    Refs: q (block_q, d); k/v (block_k, d); positions qp (block_q, 1) and
    kp (1, block_k) int32 — explicit arrays, not iota, so permuted layouts
    (ring/zigzag shards) mask correctly; o (block_q, d); lse (block_q, 1) —
    scalars-per-row ride as a column, rank-1 tiled outputs fail Mosaic
    lowering (see ops/quantization.py). Scratch acc (block_q, d) f32,
    m/l (block_q, 1) f32 persist across the kv grid axis (innermost,
    sequential on TPU).
    """
    from jax.experimental import pallas as pl

    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = qp_ref[...]  # (block_q, 1)
    k_pos = kp_ref[...]  # (1, block_k)

    # Causal skip: a KV block whose earliest position is beyond this q
    # block's last position is fully masked — skip both matmuls (the grid
    # still visits the step, but the MXU does nothing).
    @pl.when(jnp.min(k_pos) <= jnp.max(q_pos))
    def _update():
        q = q_ref[...]
        k = k_ref[...]
        scores = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # (block_q, block_k) f32
        scores = jnp.where(q_pos >= k_pos, scores, _NEG_INF)

        m_prev = m_ref[...]  # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        correction = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)  # (block_q, block_k) f32
        l_ref[...] = l_ref[...] * correction + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype),
            v_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * correction + pv
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        # Rows whose running max never left the sentinel saw only masked
        # scores: their p = exp(score - m) degenerated to 1 (the classic
        # all-masked-row trap), so acc holds sum-of-V garbage — zero them
        # and pin lse to the sentinel so partial merges weight them out.
        empty = m_ref[...] <= _NEG_INF
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = jnp.where(empty, 0.0, acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[...] = jnp.where(empty, _NEG_INF, m_ref[...] + jnp.log(l))


def _flash_fwd(
    q, k, v, scale, block_q, block_k, interpret,
    q_positions=None, k_positions=None,
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk = k.shape[1]
    kv_heads = k.shape[2]
    group = h // kv_heads

    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(sq, dtype=jnp.int32), (b, sq))
    if k_positions is None:
        k_positions = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32), (b, sk))

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    # Padded positions are INT32_MAX: beyond every real query, so the
    # causal mask excludes padded KV rows for real queries; padded q rows
    # are sliced off below.
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        # Edge-pad (repeat the last real position), NOT _PAD_POS: padded q
        # rows are sliced off below so their mask content is irrelevant,
        # but an INT32_MAX in the block would defeat the kernel's causal
        # skip (max(q_pos) would dominate every KV block's min).
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad_q)), mode="edge")
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        k_positions = jnp.pad(
            k_positions, ((0, 0), (0, pad_k)), constant_values=_PAD_POS
        )
    nq = (sq + pad_q) // block_q
    nk = (sk + pad_k) // block_k
    # Positions ride as 3-D so each block is a 2-D tile (a column for q, a
    # row for k — so the in-kernel compare broadcasts without a transpose).
    qp = q_positions.astype(jnp.int32).reshape(b, sq + pad_q, 1)
    kp = k_positions.astype(jnp.int32).reshape(b, 1, sk + pad_k)

    # Kernels run on (b, heads, seq, d): Mosaic requires the last two BLOCK
    # dims be (mult-of-8, mult-of-128-or-whole-dim), so seq and head_dim must
    # be minor. The model-facing (b, seq, heads, d) layout would squeeze the
    # heads dim into second-to-last block position (block 1 vs array h — an
    # on-chip lowering error interpret mode never sees). The transposes are
    # plain XLA copies at the kernel boundary.
    qt = q.transpose(0, 2, 1, 3)  # (b, h, sq_p, d)
    kt = k.transpose(0, 2, 1, 3)  # (b, kv_heads, sk_p, d)
    vt = v.transpose(0, 2, 1, 3)

    kernel = partial(_fwd_kernel, scale=scale, nk=nk)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec(
                (None, None, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
            ),
            pl.BlockSpec(
                (None, None, block_k, d),
                lambda ib, ih, iq, ik: (ib, ih // group, ik, 0),
            ),
            pl.BlockSpec(
                (None, None, block_k, d),
                lambda ib, ih, iq, ik: (ib, ih // group, ik, 0),
            ),
            pl.BlockSpec(
                (None, block_q, 1), lambda ib, ih, iq, ik: (ib, iq, 0)
            ),
            pl.BlockSpec(
                (None, 1, block_k), lambda ib, ih, iq, ik: (ib, 0, ik)
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (None, None, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
            ),
            pl.BlockSpec(
                (None, None, block_q, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
            ),
        ],
        out_shape=[
            _out_struct((b, h, sq + pad_q, d), q.dtype, (q, k, v, qp, kp)),
            _out_struct((b, h, sq + pad_q, 1), jnp.float32, (q, k, v, qp, kp)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, qp, kp)
    out = out.transpose(0, 2, 1, 3)  # back to (b, sq_p, h, d)
    lse = lse[..., 0].transpose(0, 2, 1)  # (b, sq_p, h)
    if pad_q:
        out = out[:, :sq]
        lse = lse[:, :sq]
    # (b, sq, h) -> (b, sq, kv, group): head h is kv-head h // group, the
    # same layout blockwise_attention's backward expects for its residual.
    return out, lse.reshape(b, sq, kv_heads, group)


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, qp_ref, kp_ref,
    dq_ref, dq_acc_ref, *, scale: float, nk: int,
):
    """dQ pass: grid (b, h, nq, nk), KV axis innermost; dq accumulates in
    VMEM scratch across the KV blocks of one q block (FlashAttention-2
    backward, probabilities recomputed from the saved logsumexp)."""
    from jax.experimental import pallas as pl

    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    q_pos = qp_ref[...]  # (block_q, 1)
    k_pos = kp_ref[...]  # (1, block_k)

    @pl.when(jnp.min(k_pos) <= jnp.max(q_pos))
    def _update():
        q = q_ref[...]
        k = k_ref[...]
        scores = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # (block_q, block_k) f32
        # p from the saved lse; masked entries exactly 0 (also kills padded
        # q rows, whose position is -1 — below every key).
        p = jnp.where(q_pos >= k_pos, jnp.exp(scores - lse_ref[...]), 0.0)
        dp = jax.lax.dot_general(
            do_ref[...], v_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k) f32
        ds = p * (dp - dl_ref[...]) * scale
        dq_acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[...] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, qp_ref, kp_ref,
    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *, scale: float, nq: int,
):
    """dK/dV pass: grid (b, h, nk, nq), Q axis innermost; dk/dv accumulate
    in VMEM scratch across the q blocks of one KV block. Outputs are
    PER-Q-HEAD partials (b, sk, h, d) — the GQA group sum happens outside
    so every output block is written exactly once."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    q_pos = qp_ref[...]  # (block_q, 1)
    k_pos = kp_ref[...]  # (1, block_k)

    @pl.when(jnp.max(q_pos) >= jnp.min(k_pos))
    def _update():
        q = q_ref[...]
        k = k_ref[...]
        scores = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # (block_q, block_k) f32
        p = jnp.where(q_pos >= k_pos, jnp.exp(scores - lse_ref[...]), 0.0)
        do = do_ref[...]
        dv_acc_ref[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_k, d)
        dp = jax.lax.dot_general(
            do, v_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dl_ref[...]) * scale
        dk_acc_ref[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_k, d)

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[...] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc_ref[...].astype(dv_ref.dtype)


def flash_attention_partial_bwd(
    q, k, v, d_out, out, lse,
    q_positions, k_positions,
    scale, block_q, block_k, interpret,
    delta=None,
    out_dtype=None,
):
    """Fused Pallas backward PARTIAL over an arbitrary KV block: the ring
    backward building block (and, with arange positions, the full causal
    backward). Masking uses explicit global position arrays, so permuted
    (zigzag) ring layouts work; ``lse`` is the GLOBAL logsumexp per q-head
    (b, sq, h) f32 — with it, one call yields this KV block's exact (dk,
    dv) and this query shard's dq contribution, no forward recompute
    (FlashAttention-2 identity).

    ``delta = rowsum(dO*O)`` may be precomputed (ring callers reuse it
    across hops). Returns (dq_partial, dk, dv) in ``out_dtype`` (default
    f32 — ring callers accumulate partials across hops in f32 and cast
    once at the end; the single-block full-causal caller passes the input
    dtype so the kernels cast in VMEM and halve the gradient writeback for
    bf16 models). dk/dv are group-summed. Padding: q rows pad with
    position -1 (below every key → zero contribution to every gradient);
    KV rows pad with _PAD_POS (above every query → likewise zero)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk = k.shape[1]
    kv_heads = k.shape[2]
    group = h // kv_heads
    # Same rounding as every forward entry point — block_q to the 16
    # sublane tile, block_k to the 128 LANE tile (the kp position row rides
    # as a (1, block_k) tile whose last dim must be a 128-multiple or the
    # whole dim): ragged blocks pass interpret mode but fail Mosaic
    # lowering on real TPU.
    block_q = min(_next_multiple(int(block_q), 16), _next_multiple(sq, 16))
    block_k = min(_next_multiple(int(block_k), 128), _next_multiple(sk, 128))
    if out_dtype is None:
        out_dtype = jnp.float32

    if delta is None:
        # Cheap elementwise+reduce, XLA fuses it into the surrounding graph.
        delta = jnp.sum(
            d_out.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
        )  # (b, sq, h)

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        d_out = jnp.pad(d_out, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        lse = jnp.pad(lse, ((0, 0), (0, pad_q), (0, 0)))
        delta = jnp.pad(delta, ((0, 0), (0, pad_q), (0, 0)))
        q_positions = jnp.pad(
            q_positions, ((0, 0), (0, pad_q)), constant_values=-1
        )
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        k_positions = jnp.pad(
            k_positions, ((0, 0), (0, pad_k)), constant_values=_PAD_POS
        )
    nq = (sq + pad_q) // block_q
    nk = (sk + pad_k) // block_k
    qp = q_positions.astype(jnp.int32).reshape(b, sq + pad_q, 1)
    kp = k_positions.astype(jnp.int32).reshape(b, 1, sk + pad_k)
    # Same heads-major transposition as _flash_fwd (see comment there): the
    # kernels see (b, h, seq, d) / (b, h, seq, 1) so seq and d are the block
    # minor dims Mosaic requires.
    qt = q.transpose(0, 2, 1, 3)  # (b, h, sq_p, d)
    kt = k.transpose(0, 2, 1, 3)  # (b, kv_heads, sk_p, d)
    vt = v.transpose(0, 2, 1, 3)
    dot = d_out.transpose(0, 2, 1, 3)  # (b, h, sq_p, d)
    lse_col = lse.reshape(b, sq + pad_q, h, 1).transpose(0, 2, 1, 3)
    delta_col = delta.reshape(b, sq + pad_q, h, 1).transpose(0, 2, 1, 3)

    q_spec = pl.BlockSpec(
        (None, None, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
    )
    k_spec = pl.BlockSpec(
        (None, None, block_k, d), lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)
    )
    col_spec = pl.BlockSpec(
        (None, None, block_q, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
    )
    qp_spec = pl.BlockSpec((None, block_q, 1), lambda ib, ih, iq, ik: (ib, iq, 0))
    kp_spec = pl.BlockSpec((None, 1, block_k), lambda ib, ih, iq, ik: (ib, 0, ik))
    inputs = (qt, kt, vt, dot, lse_col, delta_col, qp, kp)

    dq = pl.pallas_call(
        partial(_bwd_dq_kernel, scale=scale, nk=nk),
        grid=(b, h, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, col_spec, col_spec, qp_spec, kp_spec],
        out_specs=[q_spec],
        out_shape=[_out_struct((b, h, sq + pad_q, d), out_dtype, inputs)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(*inputs)[0]
    dq = dq.transpose(0, 2, 1, 3)  # (b, sq_p, h, d)

    # dK/dV pass: swap the two inner grid axes (KV outer, Q innermost) so
    # the accumulators persist across q blocks. Index maps take (iq, ik) in
    # swapped positions.
    q_spec_t = pl.BlockSpec(
        (None, None, block_q, d), lambda ib, ih, ik, iq: (ib, ih, iq, 0)
    )
    k_spec_t = pl.BlockSpec(
        (None, None, block_k, d), lambda ib, ih, ik, iq: (ib, ih // group, ik, 0)
    )
    kh_spec_t = pl.BlockSpec(
        (None, None, block_k, d), lambda ib, ih, ik, iq: (ib, ih, ik, 0)
    )
    col_spec_t = pl.BlockSpec(
        (None, None, block_q, 1), lambda ib, ih, ik, iq: (ib, ih, iq, 0)
    )
    qp_spec_t = pl.BlockSpec((None, block_q, 1), lambda ib, ih, ik, iq: (ib, iq, 0))
    kp_spec_t = pl.BlockSpec((None, 1, block_k), lambda ib, ih, ik, iq: (ib, 0, ik))
    dk_h, dv_h = pl.pallas_call(
        partial(_bwd_dkv_kernel, scale=scale, nq=nq),
        grid=(b, h, nk, nq),
        in_specs=[
            q_spec_t, k_spec_t, k_spec_t, q_spec_t, col_spec_t, col_spec_t,
            qp_spec_t, kp_spec_t,
        ],
        out_specs=[kh_spec_t, kh_spec_t],
        out_shape=[
            _out_struct((b, h, sk + pad_k, d), out_dtype, inputs),
            _out_struct((b, h, sk + pad_k, d), out_dtype, inputs),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(*inputs)
    dk_h = dk_h.transpose(0, 2, 1, 3)  # (b, sk_p, h, d)
    dv_h = dv_h.transpose(0, 2, 1, 3)

    if pad_q:
        dq = dq[:, :sq]
    if pad_k:
        dk_h = dk_h[:, :sk]
        dv_h = dv_h[:, :sk]
    # GQA group sum of the per-q-head partials (one XLA reduction).
    dk = dk_h.reshape(b, sk, kv_heads, group, d).sum(axis=3)
    dv = dv_h.reshape(b, sk, kv_heads, group, d).sum(axis=3)
    return dq, dk, dv


def _flash_bwd(q, k, v, out, lse, d_out, scale, block_q, block_k, interpret):
    """Full-causal fused backward: the partial backward with arange
    positions and a single all-KV block set."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    q_positions = jnp.broadcast_to(jnp.arange(sq, dtype=jnp.int32), (b, sq))
    k_positions = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32), (b, sk))
    dq, dk, dv = flash_attention_partial_bwd(
        q, k, v, d_out, out, lse, q_positions, k_positions,
        scale, block_q, block_k, interpret,
        out_dtype=q.dtype,  # no cross-call accumulation: cast in VMEM
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, scale, block_q, block_k, interpret, pallas_bwd):
    return _flash_fwd(q, k, v, scale, block_q, block_k, interpret)[0]


def _flash_core_fwd(q, k, v, scale, block_q, block_k, interpret, pallas_bwd):
    out, lse = _flash_fwd(q, k, v, scale, block_q, block_k, interpret)
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, out, lse)


def _flash_core_bwd(
    scale, block_q, block_k, interpret, pallas_bwd, residuals, d_out
):
    q, k, v, out, lse = residuals
    if pallas_bwd:
        b, s, h, d = q.shape
        # Residual lse is (b, s, kv, group); the kernels index it per
        # q-head h = kvh * group + g — the exact inverse reshape.
        return _flash_bwd(
            q, k, v, out, lse.reshape(b, s, h), d_out,
            scale, block_q, block_k, interpret,
        )
    # Scan-based flash backward (recompute per KV block from the saved
    # logsumexp) — shared with blockwise_attention; the CPU/fallback path.
    return _blockwise_core_bwd(scale, block_k, residuals, d_out)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention_partial(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    k_positions: jnp.ndarray,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
):
    """One causal-attention PARTIAL over an arbitrary KV block: the ring
    attention building block. Masking uses the explicit global position
    arrays (so zigzag/permuted shard layouts work), and the result is
    returned with its logsumexp so partials from different KV shards merge
    exactly (see :func:`merge_attention_partials`).

    Shapes: q (b, sq, h, d); k/v (b, sk, kv_heads, d); positions (b, sq) /
    (b, sk). Returns (out (b, sq, h, d) in q.dtype, lse (b, sq, h) f32;
    fully-masked rows come back as out=0, lse≈-1e30). Forward-only — ring
    callers define their own VJP (ops/ring_attention.py: per-hop
    :func:`flash_attention_partial_bwd` on TPU, einsum ring backward as
    the interpret/CPU fallback).
    """
    b, sq, h, d = q.shape
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        interpret = not on_tpu()
    block_q = min(_next_multiple(int(block_q), 16), _next_multiple(sq, 16))
    block_k = min(_next_multiple(int(block_k), 128), _next_multiple(k.shape[1], 128))
    out, lse = _flash_fwd(
        q, k, v, float(scale), block_q, block_k, bool(interpret),
        q_positions=q_positions, k_positions=k_positions,
    )
    return out, lse.reshape(b, sq, h)


def merge_attention_partials(out_a, lse_a, out_b, lse_b):
    """Combines two normalized attention partials of the same queries over
    disjoint KV sets via their logsumexps (the flash/ring merge identity).
    out: (..., d) f32; lse: (...,) f32 with -1e30 as the empty sentinel."""
    m = jnp.maximum(lse_a, lse_b)
    # Guard the both-empty case: exp(-1e30 - -1e30) = 1 would resurrect
    # fully-masked rows with weight 1 each; keep them exactly empty.
    both_empty = m <= _NEG_INF
    wa = jnp.where(both_empty, 0.0, jnp.exp(lse_a - m))
    wb = jnp.where(both_empty, 0.0, jnp.exp(lse_b - m))
    l = wa + wb
    safe_l = jnp.maximum(l, 1e-30)
    out = (out_a * wa[..., None] + out_b * wb[..., None]) / safe_l[..., None]
    lse = jnp.where(both_empty, _NEG_INF, m + jnp.log(safe_l))
    return out, lse


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    use_pallas_bwd: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused causal GQA attention on one device: Pallas forward AND
    FlashAttention-2-style Pallas backward (dq + dkv kernels recomputing
    probabilities from the saved logsumexp).

    Shapes: q (b, s, h, d); k/v (b, s, kv_heads, d); h % kv_heads == 0.
    The sequence is padded to block multiples internally; outputs are
    returned in the original length. The default blocks (512x1024, up from
    128x128) were picked by an earlier on-chip sweep
    (scripts/flash_block_sweep.py) whose timings carried a per-dispatch
    cost this machine does not have; the choice stands until the sweep is
    re-run here, and its speed-ups are not measured. Oversized blocks clamp
    to the padded sequence below, so short sequences are unaffected. ``interpret=None`` auto-selects
    interpret mode off-TPU so the same call works in CPU tests.
    ``use_pallas_bwd=None`` picks the fused backward exactly when the
    forward compiles (on TPU); CPU tests pass True to exercise the
    backward kernels in interpret mode, and False forces the scan-based
    blockwise fallback.
    """
    b, s, h, d = q.shape
    kv_heads = k.shape[2]
    if h % kv_heads:
        raise ValueError(f"n_heads {h} not a multiple of kv_heads {kv_heads}")
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        # Same device-platform check as ops/quantization.py's *_device
        # helpers: only the default device's platform says whether Mosaic
        # can compile the kernel.
        interpret = not on_tpu()
    if use_pallas_bwd is None:
        use_pallas_bwd = not interpret
    # Align the block sizes themselves (not just the clamp bounds):
    # block_q to 16 — the bf16 sublane tile (and a multiple of f32's 8);
    # block_k to 128 — the LANE tile, because the kp position row rides as
    # a (1, block_k) block whose last dim must be a 128-multiple or the
    # whole padded dim. Then clamp oversized blocks to the padded sequence.
    # A ragged block would pass interpret-mode tests and fail Mosaic
    # lowering on the chip (tests/test_mosaic_lowering.py pins this).
    block_q = min(_next_multiple(int(block_q), 16), _next_multiple(s, 16))
    block_k = min(_next_multiple(int(block_k), 128), _next_multiple(s, 128))
    return _flash_core(
        q, k, v, float(scale), int(block_q), int(block_k), bool(interpret),
        bool(use_pallas_bwd),
    )


def _next_multiple(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def verify_on_chip() -> dict:
    """Compile (not interpret) the kernel on the attached accelerator and
    check it against dense attention — the CLAUDE.md 'verify kernels on the
    real chip' gate (chip_smoke.py runs it; by hand through the chip tool):

        python -c "from torchft_tpu.ops.flash_attention import verify_on_chip; print(verify_on_chip())"
    """
    import numpy as np

    from torchft_tpu.models.llama import causal_attention

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"no TPU attached (devices()[0] is {dev})")
    b, s, h, kv, d = 2, 256, 4, 2, 64
    kq, kk, kvk = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, kv, d), jnp.bfloat16)
    v = jax.random.normal(kvk, (b, s, kv, d), jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=128, block_k=128, interpret=False)
    ref = causal_attention(q, k, v, scale=d**-0.5)
    err = float(
        jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    )
    if err > 0.05:  # bf16 tolerance
        raise AssertionError(f"on-chip flash attention mismatch: max err {err}")

    # Backward: compile the fused dq/dkv kernels on-chip and check the
    # gradients against dense attention's.
    def loss_flash(q_, k_, v_):
        return jnp.sum(
            flash_attention(q_, k_, v_, interpret=False, use_pallas_bwd=True)
            .astype(jnp.float32) ** 2
        )

    def loss_dense(q_, k_, v_):
        return jnp.sum(
            causal_attention(q_, k_, v_, scale=d**-0.5).astype(jnp.float32) ** 2
        )

    grads_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    grads_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    err_bwd = max(
        float(jnp.max(jnp.abs(gf.astype(jnp.float32) - gd.astype(jnp.float32))))
        for gf, gd in zip(grads_flash, grads_dense)
    )
    # Gradients square the bf16 rounding; the scan-backward CPU tests hold
    # the same bound.
    if err_bwd > 0.25:
        raise AssertionError(f"on-chip flash BACKWARD mismatch: max err {err_bwd}")

    # The partial surface (ring building block): explicit PERMUTED position
    # arrays (the (1, block_k) row tile), sq != sk, ragged lengths, a
    # fully-masked hop, and the logsumexp merge — everything the ring path
    # lowers that the full-attention call above does not.
    sq = 200  # ragged: pads to 208
    pos = jax.random.permutation(jax.random.PRNGKey(3), s)[:sq]
    qp = jnp.broadcast_to(pos.astype(jnp.int32), (b, sq))
    kp_full = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    qs = jax.random.normal(kq, (b, sq, h, d), jnp.bfloat16)
    half = s // 2
    o1, l1 = flash_attention_partial(
        qs, k[:, :half], v[:, :half], qp, kp_full[:, :half], interpret=False
    )
    o2, l2 = flash_attention_partial(
        qs, k[:, half:], v[:, half:], qp, kp_full[:, half:], interpret=False
    )
    merged, lse_g = merge_attention_partials(
        o1.astype(jnp.float32), l1, o2.astype(jnp.float32), l2
    )
    # Reference: dense attention with the same permuted-position mask.
    qg = qs.astype(jnp.float32).reshape(b, sq, kv, h // kv, d)
    sc = jnp.einsum("bskgd,btkd->bskgt", qg, k.astype(jnp.float32)) * (d**-0.5)
    mask = qp[:, :, None, None, None] >= kp_full[:, None, None, None, :]
    sc = jnp.where(mask, sc, -1e30)
    pr = jax.nn.softmax(sc, axis=-1)
    ref_p = jnp.einsum("bskgt,btkd->bskgd", pr, v.astype(jnp.float32)).reshape(
        b, sq, h, d
    )
    err_p = float(jnp.max(jnp.abs(merged - ref_p)))
    if err_p > 0.05:
        raise AssertionError(
            f"on-chip flash PARTIAL/merge mismatch: max err {err_p}"
        )

    # The ring-backward building block: flash_attention_partial_bwd
    # compiled with PERMUTED positions, sq != sk, and the global (merged)
    # logsumexp — checked against the FlashAttention-2 einsum identity
    # (the _ring_flash_bwd_scan per-hop math, computed inline).
    d_out_p = jax.random.normal(jax.random.PRNGKey(7), merged.shape, jnp.float32)
    dq_pal, dk_pal, dv_pal = flash_attention_partial_bwd(
        qs, k[:, :half], v[:, :half], d_out_p.astype(qs.dtype),
        merged.astype(qs.dtype), lse_g,
        qp, kp_full[:, :half],
        d**-0.5, 128, 128, False,
    )
    group = h // kv
    qg2 = qs.astype(jnp.float32).reshape(b, sq, kv, group, d)
    dog = d_out_p.reshape(b, sq, kv, group, d)
    og = merged.reshape(b, sq, kv, group, d)
    delta = jnp.sum(dog * og, axis=-1)
    k32 = k[:, :half].astype(jnp.float32)
    v32 = v[:, :half].astype(jnp.float32)
    scores2 = jnp.einsum("bskgd,btkd->bskgt", qg2, k32) * (d**-0.5)
    mask2 = qp[:, :, None, None, None] >= kp_full[:, None, None, None, :half]
    lse_gg = lse_g.reshape(b, sq, kv, group)
    p2 = jnp.where(mask2, jnp.exp(scores2 - lse_gg[..., None]), 0.0)
    dv_ref = jnp.einsum("bskgt,bskgd->btkd", p2, dog)
    dp2 = jnp.einsum("bskgd,btkd->bskgt", dog, v32)
    ds2 = p2 * (dp2 - delta[..., None]) * (d**-0.5)
    dq_ref = jnp.einsum("bskgt,btkd->bskgd", ds2, k32).reshape(b, sq, h, d)
    dk_ref = jnp.einsum("bskgt,bskgd->btkd", ds2, qg2)
    err_pb = max(
        float(jnp.max(jnp.abs(dq_pal.astype(jnp.float32) - dq_ref))),
        float(jnp.max(jnp.abs(dk_pal.astype(jnp.float32) - dk_ref))),
        float(jnp.max(jnp.abs(dv_pal.astype(jnp.float32) - dv_ref))),
    )
    if err_pb > 0.25:
        raise AssertionError(
            f"on-chip flash PARTIAL BACKWARD mismatch: max err {err_pb}"
        )
    return {
        "device": str(dev),
        "max_err": err,
        "max_err_bwd": err_bwd,
        "max_err_partial": err_p,
        "ok": True,
    }
