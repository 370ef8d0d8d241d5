"""Cross-platform Mosaic lowering gates for every Pallas kernel.

Interpret mode skips Mosaic entirely, so a kernel whose block layout
violates TPU tiling (last two block dims must be multiple-of-8 /
multiple-of-128 or the whole array dim) passes every CPU test and then
fails its first real compile — exactly what happened to the first flash
kernels (heads squeezed into second-to-last block position: the chip's
compiler rejected all three).

jax's AOT path lowers for a TPU target WITHOUT a TPU attached
(``jit(f).trace(...).lower(lowering_platforms=("tpu",))`` — the
jax.export mechanism), and Pallas block-mapping validation runs during
that lowering. These tests pin the Mosaic-visible layout of each kernel
so the constraint class is caught in the default CPU suite, not on the
chip. Execution semantics (numerics) stay covered by the interpret-mode
tests plus verify_on_chip(); this file only proves the programs LOWER
for real TPU.

What lowering does not catch: it never asks the chip's compiler. A kernel
that wants more VMEM than it may use, a slice not aligned to the tiling,
a mesh axis left automatic around a Mosaic call, a program too large for
HBM — all of these lower cleanly and fail at compile. Those are
tests/test_tpu_aot_compile.py's (chipless COMPILES for a described v5e).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from torchft_tpu.ops import quantization
from torchft_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_partial,
    flash_attention_partial_bwd,
)


def _lower_tpu(fn, *args):
    """Lower ``fn`` for a TPU target on this CPU-only host; returns the
    Lowered object (raises ValueError on a Mosaic block-mapping error)."""
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# (b, s, h, kv_heads, d): verify_on_chip's GQA shape, kernel_bench's MHA
# shape, and a ragged sequence that exercises the padding path.
ATTN_SHAPES = [
    pytest.param(2, 256, 4, 2, 64, id="gqa-256x64"),
    pytest.param(4, 1024, 8, 8, 128, id="mha-1024x128"),
    pytest.param(1, 200, 4, 4, 64, id="ragged-200x64"),
]


@pytest.mark.parametrize("b,s,h,kv,d", ATTN_SHAPES)
def test_flash_forward_lowers_for_tpu(b, s, h, kv, d):
    q = _sds((b, s, h, d), jnp.bfloat16)
    k = _sds((b, s, kv, d), jnp.bfloat16)
    v = _sds((b, s, kv, d), jnp.bfloat16)
    _lower_tpu(lambda q, k, v: flash_attention(q, k, v, interpret=False), q, k, v)


@pytest.mark.parametrize("bq,bk", [(64, 64), (192, 192), (48, 512)])
def test_flash_forward_lowers_with_non128_blocks(bq, bk):
    # Public block sizes are rounded internally (block_q to the 16 sublane
    # tile, block_k to the 128 lane tile the kp row-tile needs) — a
    # non-128-multiple block_k must not reach Mosaic un-rounded.
    b, s, h, kv, d = 2, 256, 4, 2, 64
    q = _sds((b, s, h, d), jnp.bfloat16)
    k = _sds((b, s, kv, d), jnp.bfloat16)
    v = _sds((b, s, kv, d), jnp.bfloat16)
    _lower_tpu(
        lambda q, k, v: flash_attention(
            q, k, v, block_q=bq, block_k=bk, interpret=False
        ),
        q, k, v,
    )


def test_flash_backward_lowers_for_tpu():
    b, s, h, kv, d = 2, 256, 4, 2, 64
    q = _sds((b, s, h, d), jnp.bfloat16)
    k = _sds((b, s, kv, d), jnp.bfloat16)
    v = _sds((b, s, kv, d), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention(q, k, v, interpret=False, use_pallas_bwd=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)


def test_flash_partial_and_partial_bwd_lower_for_tpu():
    # The ring-attention building blocks: a KV block smaller than the
    # query shard, with explicit (permuted-layout-capable) positions.
    b, sq, sk, h, kv, d = 1, 256, 128, 4, 2, 64
    q = _sds((b, sq, h, d), jnp.bfloat16)
    k = _sds((b, sk, kv, d), jnp.bfloat16)
    v = _sds((b, sk, kv, d), jnp.bfloat16)
    qp = _sds((b, sq), jnp.int32)
    kp = _sds((b, sk), jnp.int32)

    _lower_tpu(
        lambda q, k, v, qp, kp: flash_attention_partial(
            q, k, v, qp, kp, interpret=False
        ),
        q, k, v, qp, kp,
    )

    out = _sds((b, sq, h, d), jnp.bfloat16)
    lse = _sds((b, sq, h), jnp.float32)
    _lower_tpu(
        lambda q, k, v, do, out, lse, qp, kp: flash_attention_partial_bwd(
            q, k, v, do, out, lse, qp, kp,
            scale=d**-0.5, block_q=128, block_k=128, interpret=False,
        ),
        q, k, v, out, out, lse, qp, kp,
    )


@pytest.mark.parametrize(
    "b,s,own,grid,tables",
    [
        pytest.param(1, 8192, True, (1, 32, 1, 72), [(4, 72)], id="cells-1x8192"),
        pytest.param(4, 2048, True, (4, 32, 1, 6), [(4, 6)], id="cells-4x2048"),
        # Chunk 0 (q blocks 0..63) needs 1056 pairs and one empty step for each
        # of the 32 KV blocks behind it, chunk 1 needs 2048 + 1056: both walk
        # the longer's 3104 steps.
        pytest.param(
            1, 65536, True, (1, 32, 2, 3104), [(4, 2 * 3104)], id="over-budget-1x65536"
        ),
        # With position arrays (a ring hop's) the walk is over every pair, as
        # it was: (batch, q heads, q chunks, KV blocks, q blocks of a chunk).
        pytest.param(
            1, 8192, False, (1, 32, 1, 8, 16), [(1, 3, 16), (1, 3, 8)],
            id="positions-1x8192",
        ),
        pytest.param(
            1, 65536, False, (1, 32, 2, 64, 64), [(1, 3, 128), (1, 3, 64)],
            id="positions-over-budget-1x65536",
        ),
    ],
)
def test_flash_backward_lowers_at_the_cells_geometry(b, s, own, grid, tables):
    # The benchmark cells' attention (32 q heads over 8 KV heads of 128) at
    # the default blocks, and a sequence over the VMEM a call may hold, which
    # the one backward call walks in two chunks of q blocks. A call whose
    # positions are the sequence's own steps the needed pairs alone: its grid
    # is (batch, q heads, q chunks, steps of a chunk) and its one table the
    # steps of every chunk.
    h, kv, d = 32, 8, 128
    q = _sds((b, s, h, d), jnp.bfloat16)
    k = _sds((b, s, kv, d), jnp.bfloat16)
    lse = _sds((b, s, h), jnp.float32)
    positions = [None, None] if own else [_sds((b, s), jnp.int32)] * 2
    traced = jax.jit(
        lambda q, k, v, do, out, lse, *positions: flash_attention_partial_bwd(
            q, k, v, do, out, lse, *(positions or (None, None)),
            scale=d**-0.5, block_q=512, block_k=1024, interpret=False,
            out_dtype=jnp.bfloat16,
        )
    ).trace(q, k, k, q, q, lse, *(p for p in positions if p is not None))
    (call,) = _pallas_calls(traced.jaxpr.jaxpr)
    assert call.params["grid_mapping"].grid == grid
    assert _kernel_refs(call)[: len(tables)] == [(shape, "int32") for shape in tables]
    traced.lower(lowering_platforms=("tpu",))


def _pallas_calls(jaxpr) -> list:
    """pallas_call equations of a jaxpr, nested bodies (shard_map, pjit)
    included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


def _kernel_refs(call) -> list:
    """(shape, dtype) of every ref the kernel body of a pallas_call sees: the
    scalar-prefetch tables, the operands' blocks, the outputs' blocks, the
    scratch."""
    return [(v.aval.shape, str(v.aval.dtype)) for v in call.params["jaxpr"].invars]


def _cell_gradient(kv, with_selection, h=32):
    """The traced gradient of ``flash_attention`` at 1 x 8192 with ``h`` (32) q
    heads of 128 over ``kv`` KV heads, default blocks; its two pallas_calls."""
    b, s, d = 1, 8192, 128
    q = _sds((b, s, h, d), jnp.bfloat16)
    k = _sds((b, s, kv, d), jnp.bfloat16)
    selection = _sds((b, s, s), jnp.int8)

    def loss(q, k, v, selection):
        out = flash_attention(
            q, k, v, interpret=False, use_pallas_bwd=True,
            selection=selection if with_selection else None,
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    traced = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, k, k, selection)
    forward, backward = _pallas_calls(traced.jaxpr.jaxpr)
    return traced, forward, backward


_BF16, _F32, _I32 = "bfloat16", "float32", "int32"
_TABLES = [((4, 72), _I32)]  # the step table of the 72 needed pairs of 16 x 8
_FWD_REST = [  # out and the logsumexp; acc, running max and sum
    ((512, 128), _BF16), ((512, 1), _F32),
    ((512, 128), _F32), ((512, 1), _F32), ((512, 1), _F32),
]
_BWD_REST = [  # dq (the head's rows), dk, dv; their three accumulators
    ((8192, 128), _BF16), ((1024, 128), _BF16), ((1024, 128), _BF16),
    ((8192, 128), _F32), ((1024, 128), _F32), ((1024, 128), _F32),
]
_QKV = [((512, 128), _BF16), ((1024, 128), _BF16), ((1024, 128), _BF16)]
_BWD_ROWS = [((512, 128), _BF16), ((512, 1), _F32), ((512, 1), _F32)]  # dO, lse, delta
_POSITIONS = [((512, 1), _I32), ((1, 1024), _I32)]
_SELECTION = [((512, 1024), "int8")]


def test_without_a_selection_the_calls_are_the_ones_the_mistral_cells_run():
    """The contract with every caller that passes no selection (the four
    Mistral cells, chip_smoke.py): operand for operand, the blocks and the
    scratch the two calls had before the argument existed (read off the
    commit before it, at the cells' 1 x 8192 geometry), and no int8 anywhere.
    The grid is the 72 needed pairs of the 128, and the one table their list
    (``flash_attention`` brings no position arrays)."""
    traced, forward, backward = _cell_gradient(kv=8, with_selection=False)
    assert forward.params["grid_mapping"].grid == (1, 32, 72)
    assert backward.params["grid_mapping"].grid == (1, 32, 1, 72)
    assert _kernel_refs(forward) == _TABLES + _QKV + _POSITIONS + _FWD_REST
    assert _kernel_refs(backward) == _TABLES + _QKV + _BWD_ROWS + _POSITIONS + _BWD_REST
    assert [(v.aval.shape, str(v.aval.dtype)) for v in forward.invars] == _TABLES + [
        ((1, 32, 8192, 128), _BF16), ((1, 8, 8192, 128), _BF16), ((1, 8, 8192, 128), _BF16),
        ((1, 8192, 1), _I32), ((1, 1, 8192), _I32),
    ]
    assert len(backward.invars) == 9
    # No int8 tensor among the lowered module's values (">" is no letter of
    # the base64 the Mosaic bodies are written in).
    assert "xi8>" not in traced.lower(lowering_platforms=("tpu",)).as_text()


def test_with_no_shared_head_the_calls_are_the_same_at_the_ouro_cells_geometry():
    """16 query heads over 16 key-value heads of 128 (the cell
    ``ouro-2.6b-1chip.ftddp-seq8k``, the first whose KV heads are not shared):
    the listed walk and the pair classes serve it unchanged. The grids count 16
    heads, the one table lists the same 72 pairs, every block and scratch is
    the Mistral cells', and k and v come 16 heads wide."""
    traced, forward, backward = _cell_gradient(kv=16, with_selection=False, h=16)
    assert forward.params["grid_mapping"].grid == (1, 16, 72)
    assert backward.params["grid_mapping"].grid == (1, 16, 1, 72)
    assert _kernel_refs(forward) == _TABLES + _QKV + _POSITIONS + _FWD_REST
    assert _kernel_refs(backward) == _TABLES + _QKV + _BWD_ROWS + _POSITIONS + _BWD_REST
    assert [v.aval.shape for v in forward.invars[1:4]] == [(1, 16, 8192, 128)] * 3
    assert "xi8>" not in traced.lower(lowering_platforms=("tpu",)).as_text()


def test_selected_flash_kernels_lower_for_tpu_at_the_keye_cells_geometry():
    """With the operand (32 q heads over 4 KV heads, as the cell
    ``keye-vl2-30b-a3b-1chip.ftddp-seq8k`` runs them): the same grids, the
    two position blocks gone and one (512, 1024) int8 block of the selection
    in their place, in the forward and in the one backward call, and the pair
    lowers for a TPU (the int8 block's (32, 128) tiling)."""
    traced, forward, backward = _cell_gradient(kv=4, with_selection=True)
    assert forward.params["grid_mapping"].grid == (1, 32, 72)
    assert backward.params["grid_mapping"].grid == (1, 32, 1, 72)
    assert _kernel_refs(forward) == _TABLES + _QKV + _SELECTION + _FWD_REST
    assert _kernel_refs(backward) == _TABLES + _QKV + _BWD_ROWS + _SELECTION + _BWD_REST
    assert (forward.invars[-1].aval.shape, backward.invars[-1].aval.shape) == ((1, 8192, 8192),) * 2
    assert "xi8>" in traced.lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("s, block_q", [(200, 64), (40, 512), (600, 48)])
def test_selected_flash_kernels_lower_with_ragged_lengths_and_blocks(s, block_q):
    """The selection's block has block_q rows of int8, whose sublane tile is
    32: a block_q the bf16 rule alone would leave at 48 is rounded to 64, and
    a sequence shorter than a block pads to a multiple of 32."""
    b, h, kv, d = 2, 4, 2, 64
    q = _sds((b, s, h, d), jnp.bfloat16)
    k = _sds((b, s, kv, d), jnp.bfloat16)

    def loss(q, k, v, selection):
        out = flash_attention(
            q, k, v, block_q=block_q, block_k=128, interpret=False, selection=selection
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, k, _sds((b, s, s), jnp.int8))


@pytest.mark.parametrize(
    "where", ["plain", "shard_map-batch", "shard_map-positions", "shard_map-batch-own"]
)
def test_flash_kernels_lower_with_their_schedule_tables(where):
    """The forward and the backward call each take the causal block
    schedule as two scalar-prefetch operands that their index maps and
    bodies read from SMEM, and lower for a TPU: bare; under a shard_map over the batch
    (tables of ``arange`` that vary
    over no axis beside data that does); and under a shard_map over the
    sequence with the positions as arguments (a ring hop: the tables vary
    with the shard). With position arrays the grids are over every pair,
    (2, 4, 8, 4) and (2, 4, 1, 4, 8) at these 8 x 4 blocks, whatever the
    arrays hold. Without them (``own``: the model's
    ``ops.attention.flash_under_mesh`` under its shard_map over the batch) each
    call takes its one step table, a constant that varies over no axis, and
    steps the 20 needed pairs."""
    from jax import shard_map
    from jax.sharding import AbstractMesh, PartitionSpec as P

    b, s, h, kv, d = 2, 1024, 4, 2, 64

    def both(q, k, v, do, qp, kp):
        out, lse = flash_attention_partial(
            q, k, v, qp, kp, block_q=128, block_k=256, interpret=False
        )
        return flash_attention_partial_bwd(
            q, k, v, do, out, lse, qp, kp,
            scale=d**-0.5, block_q=128, block_k=256, interpret=False,
        )

    def own(q, k, v, do):
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, block_q=128, block_k=256, interpret=False, use_pallas_bwd=True
            ),
            q, k, v,
        )
        return vjp(do)

    def arange(x):
        return jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32), x.shape[:2])

    data = [_sds((b, s, n, d), jnp.bfloat16) for n in (h, kv, kv, h)]
    positions = [_sds((b, s), jnp.int32)] * 2
    if where == "plain":
        fn, args = both, data + positions
    elif where == "shard_map-batch":
        fn = shard_map(
            lambda q, k, v, do: both(q, k, v, do, arange(q), arange(k)),
            mesh=AbstractMesh((2,), ("fsdp",)),
            in_specs=(P("fsdp"),) * 4, out_specs=(P("fsdp"),) * 3,
        )
        args = data
    elif where == "shard_map-batch-own":
        fn = shard_map(
            own, mesh=AbstractMesh((2,), ("fsdp",)),
            in_specs=(P("fsdp"),) * 4, out_specs=(P("fsdp"),) * 3,
        )
        args = data
    else:
        fn = shard_map(
            both, mesh=AbstractMesh((4,), ("sp",)),
            in_specs=(P(None, "sp"),) * 6, out_specs=(P(None, "sp"),) * 3,
        )
        args = data + positions
    traced = jax.jit(fn).trace(*args)
    calls = _pallas_calls(traced.jaxpr.jaxpr)
    assert len(calls) == 2
    tables, grids = {
        "plain": (2, [(2, 4, 8, 4), (2, 4, 1, 4, 8)]),
        "shard_map-batch": (2, [(1, 4, 8, 4), (1, 4, 1, 4, 8)]),
        "shard_map-positions": (2, [(2, 4, 2, 1), (2, 4, 1, 1, 2)]),
        "shard_map-batch-own": (1, [(1, 4, 20), (1, 4, 1, 20)]),
    }[where]
    assert [c.params["grid_mapping"].num_index_operands for c in calls] == [tables] * 2
    assert [c.params["grid_mapping"].grid for c in calls] == grids
    lowered = traced.lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") >= 2


@pytest.mark.parametrize("wire", ["fp8", "int8"])
@pytest.mark.parametrize("n_blocks", [3, 64, 1500, 2048])
def test_quant_kernels_lower_for_tpu(wire, n_blocks):
    # n_blocks=3 pins the rows_per_tile == whole-dim branch of the tiling
    # rule; 64 pins whole-dim above the old 8-row tiles; 1500 pins the
    # RAGGED 1024-row grid (a partial final tile — the common shape for
    # arbitrary gradient sizes) and 2048 the exact-multiple grid.
    x = _sds((n_blocks, quantization.BLOCK), jnp.float32)
    _lower_tpu(
        lambda x: quantization.quantize_blocks_pallas(
            x, interpret=False, wire=wire
        ),
        x,
    )

    pdtype = jnp.int8 if wire == "int8" else jnp.float8_e4m3fn
    payload = _sds((n_blocks, quantization.BLOCK), pdtype)
    scales = _sds((n_blocks,), jnp.float32)
    _lower_tpu(
        lambda p, s: quantization.dequantize_blocks_pallas(
            p, s, interpret=False
        ),
        payload,
        scales,
    )


def test_flagship_flash_train_step_lowers_for_tpu(monkeypatch):
    """Cross-lower the FULL ~445M train step (scan llama + dots-remat +
    Pallas flash fwd/bwd + fused CE + sgd update) for a TPU target — the
    integration-level version of the kernel gates above. The config is
    the shared ``large_bench_config()`` that scripts/hbm_probe.py sizes
    and benchmarks/compile_bench.py compiles, and the switches are those
    of every chip configuration (chipbench's cells, chip_smoke.py); a
    lowering regression anywhere in that stack fails here instead of on
    the chip. Everything is abstract (jax.eval_shape) —
    no 445M params materialize.
    """
    import optax

    from torchft_tpu.models import llama as llama_mod
    from torchft_tpu.ops import attention as attention_mod
    from torchft_tpu.ops import flash_attention as fa_mod
    from torchft_tpu.models.llama import Llama, LlamaConfig

    # flash_attention auto-selects interpret mode off-TPU; the gate must
    # lower the real Mosaic program, so pretend the chip is attached for
    # the trace (lowering still targets TPU via lowering_platforms).
    monkeypatch.setattr(fa_mod, "on_tpu", lambda: True)
    monkeypatch.setattr(attention_mod, "on_tpu", lambda: True)

    # The SHARED definition: the gate must lower exactly the program the
    # HBM probe sizes (a copied config drifted when the head geometry was
    # retuned — review finding, round 5).
    config = llama_mod.large_bench_config()
    seq = config.max_seq_len
    model = Llama(config)
    tx = optax.sgd(0.01, momentum=0.9)
    tokens = _sds((1, seq + 1), jnp.int32)
    params = jax.eval_shape(
        lambda key, t: model.init(key, t),
        jax.random.PRNGKey(0), _sds((1, seq), jnp.int32),
    )
    opt_state = jax.eval_shape(tx.init, params)

    def train_step(p, s, batch_tokens):
        def loss_fn(p):
            return model.apply(p, batch_tokens[:, :-1], targets=batch_tokens[:, 1:])

        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, s = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss

    lowered = _lower_tpu(train_step, params, opt_state, tokens)
    # The Mosaic kernels must actually be in the lowered program (the gate
    # would be vacuous if auto-selection fell back to the scan path).
    assert "tpu_custom_call" in lowered.as_text()


def test_ring_flash_under_sp_mesh_lowers_for_tpu():
    """The sequence-parallel path: shard_map(ring_attention_flash) over an
    AbstractMesh (no devices needed), forward and reverse, cross-lowered
    for TPU with the per-hop Pallas partials present in the module. This
    is the long-context stack's on-chip program — ppermute ring + flash
    partial kernels — gated without a chip."""
    from jax import shard_map
    from jax.sharding import AbstractMesh, PartitionSpec as P

    from torchft_tpu.ops.ring_attention import ring_attention_flash

    am = AbstractMesh((4,), ("sp",))
    b, s, h, kv, d = 1, 512, 4, 2, 64

    def f(q, k, v):
        return shard_map(
            lambda q, k, v: ring_attention_flash(
                q, k, v, axis_name="sp", interpret=False
            ),
            mesh=am,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"),
        )(q, k, v)

    args = (
        _sds((b, s, h, d), jnp.bfloat16),
        _sds((b, s, kv, d), jnp.bfloat16),
        _sds((b, s, kv, d), jnp.bfloat16),
    )
    lowered = _lower_tpu(f, *args)
    assert "tpu_custom_call" in lowered.as_text()

    def loss(q, k, v):
        return jnp.sum(f(q, k, v).astype(jnp.float32) ** 2)

    lowered_bwd = _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), *args)
    assert "tpu_custom_call" in lowered_bwd.as_text()


def test_lowering_gate_catches_bad_block_layout():
    """Meta-test: the gate actually fires on the exact constraint class the
    round-1..4 flash kernels violated (squeezed dim in second-to-last block
    position). If jax ever stops validating block mappings during
    cross-platform lowering, this fails and the gate must move on-chip."""
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def bad(x):
        return pl.pallas_call(
            kern,
            grid=(4,),
            in_specs=[pl.BlockSpec((None, 128, None, 64), lambda i: (0, 0, i, 0))],
            out_specs=pl.BlockSpec((None, 128, None, 64), lambda i: (0, 0, i, 0)),
            out_shape=jax.ShapeDtypeStruct((2, 256, 4, 64), jnp.bfloat16),
        )(x)

    x = _sds((2, 256, 4, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="last two dimensions"):
        _lower_tpu(bad, x)

def test_8b_sharded_flash_train_step_lowers_for_tpu(monkeypatch):
    """The SCALE gate: the reference's production story is Llama-3 8B
    FT-DDP / 70B HSDP (BASELINE.md); this cross-lowers the full 8B
    config's SHARDED train step — scan + dots-remat + fused CE + the
    Pallas flash kernel — over an abstract fsdp=4 x tp=2 mesh for a TPU
    target, with params/opt-state sharded by the same plan_shardings the
    runtime uses. Two distinct failure classes land here instead of on a
    real pod: Mosaic block-mapping violations at 8B shapes, and the
    "Mosaic kernels cannot be automatically partitioned" lowering error
    the flash path hits under jit-with-mesh unless it shard_maps itself
    (ops/attention.py flash_under_mesh — found by exactly this
    lowering, round 5). Everything is abstract: 8.03B params eval_shape
    only, and the scanned stack keeps the lowered module ~0.2 MB."""
    from dataclasses import replace

    import optax

    from jax.sharding import AbstractMesh, NamedSharding, PartitionSpec as P

    from torchft_tpu.models.llama import (
        CONFIGS, Llama, plan_shardings, sharding_plan,
    )
    from torchft_tpu.ops import attention as attention_mod
    from torchft_tpu.ops import flash_attention as fa_mod

    monkeypatch.setattr(fa_mod, "on_tpu", lambda: True)
    monkeypatch.setattr(attention_mod, "on_tpu", lambda: True)

    cfg = replace(
        CONFIGS["8b"], scan_layers=True, remat="dots", loss_vocab_chunk=4096,
        attention_impl="flash", max_seq_len=4096,
    )
    model = Llama(cfg)
    am = AbstractMesh((4, 2), ("fsdp", "tp"))
    B, S = 8, cfg.max_seq_len
    tokens = _sds((B, S + 1), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), _sds((B, S), jnp.int32))
    )
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n_params > 8e9  # the real 8B, not a stand-in
    tx = optax.sgd(0.01, momentum=0.9)
    opt_state = jax.eval_shape(tx.init, params)
    plan = sharding_plan("fsdp", "tp")
    p_sh = plan_shardings(params, am, plan)
    o_sh = plan_shardings(opt_state, am, plan)
    b_sh = NamedSharding(am, P("fsdp", None))

    def train_step(p, s, bt):
        def loss_fn(p):
            return model.apply(p, bt[:, :-1], targets=bt[:, 1:])

        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, s = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss

    with jax.sharding.use_abstract_mesh(am):
        lowered = (
            jax.jit(train_step, in_shardings=(p_sh, o_sh, b_sh))
            .trace(params, opt_state, tokens)
            .lower(lowering_platforms=("tpu",))
        )
    assert "tpu_custom_call" in lowered.as_text()


def test_windowed_flash_kernels_lower_for_tpu_at_the_smallthinker_cells_geometry():
    """With a window of 4,096 at 1 x 16,384 (28 q heads over 4 KV heads of
    128, as the cell ``smallthinker-21b-a3b-1chip.ftddp-seq16k`` runs its
    windowed layers): the grids are the 140 pairs of the 32 x 16 the window
    needs and the one table their list, the operands after it the causal
    call's, the calls carry names of their own, and the pair lowers for a TPU.
    The same call without the window steps the diagonal's 272 and has no name.
    Given position arrays, the windowed pair walks every pair as it did, its
    two schedule tables with their fourth row (the far edge)."""
    from torchft_tpu.ops.flash_attention import WINDOW_BWD, WINDOW_FWD, _flash_fwd

    b, s, h, kv, d = 1, 16384, 28, 4, 128
    q = _sds((b, s, h, d), jnp.bfloat16)
    k = _sds((b, s, kv, d), jnp.bfloat16)

    def gradient(window):
        def loss(q, k, v):
            out = flash_attention(q, k, v, interpret=False, use_pallas_bwd=True, window=window)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        traced = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, k, k)
        return (traced, *_pallas_calls(traced.jaxpr.jaxpr))

    traced, forward, backward = gradient(4096)
    assert forward.params["grid_mapping"].grid == (1, 28, 140)
    assert backward.params["grid_mapping"].grid == (1, 28, 1, 140)
    assert _kernel_refs(forward)[0] == _kernel_refs(backward)[0] == ((4, 140), _I32)
    assert _kernel_refs(forward)[1:] == _QKV + _POSITIONS + _FWD_REST
    assert [call.params["name"] for call in (forward, backward)] == [WINDOW_FWD, WINDOW_BWD]
    traced.lower(lowering_platforms=("tpu",))
    _, forward, backward = gradient(None)
    assert forward.params["grid_mapping"].grid == (1, 28, 272)
    assert backward.params["grid_mapping"].grid == (1, 28, 1, 272)
    assert _kernel_refs(forward)[0] == _kernel_refs(backward)[0] == ((4, 272), _I32)
    assert not {WINDOW_FWD, WINDOW_BWD} & {call.params["name"] for call in (forward, backward)}

    def hop(q, k, v, do, qp, kp):
        out, lse = _flash_fwd(q, k, v, d**-0.5, 512, 1024, False, qp, kp, window=4096)
        return flash_attention_partial_bwd(
            q, k, v, do, out, lse.reshape(b, s, h), qp, kp, d**-0.5, 512, 1024, False,
            window=4096,
        )

    positions = _sds((b, s), jnp.int32)
    traced = jax.jit(hop).trace(q, k, k, q, positions, positions)
    forward, backward = _pallas_calls(traced.jaxpr.jaxpr)
    assert forward.params["grid_mapping"].grid == (1, 28, 32, 16)
    assert backward.params["grid_mapping"].grid == (1, 28, 1, 16, 32)
    assert _kernel_refs(forward)[:2] == [((1, 4, 32), _I32), ((1, 4, 16), _I32)]
    assert _kernel_refs(backward)[:2] == [((1, 4, 32), _I32), ((1, 4, 16), _I32)]
    assert [call.params["name"] for call in (forward, backward)] == [WINDOW_FWD, WINDOW_BWD]
    traced.lower(lowering_platforms=("tpu",))


def _update_bodies(call) -> list:
    """The scores' shape of every update body a kernel traces, sorted: a
    ``pl.when`` is a ``cond`` of the kernel's jaxpr, and the bodies that run a
    pair's matmuls are the ones that hold a ``dot_general``, the first of
    them the scores'."""
    def first_dot(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                return eqn.outvars[0].aval.shape
            for sub in jax.core.jaxprs_in_params(eqn.params):
                if (shape := first_dot(sub)) is not None:
                    return shape
        return None

    bodies = []
    for eqn in call.params["jaxpr"].eqns:
        if eqn.primitive.name == "cond":
            shapes = [first_dot(branch.jaxpr) for branch in eqn.params["branches"]]
            bodies += [shape for shape in shapes if shape is not None]
    return sorted(bodies)


_WHOLE_STEP, _HALF_STEP = (512, 1024), (512, 512)


@pytest.mark.parametrize(
    "b, s, h, kv, window, selected, positions, halves, bodies",
    [
        # The cells' calls: masked and bare on the whole block, and the left
        # half alone where the diagonal enters a block at an even q block;
        # under the window the right half alone too, where its edge enters at
        # an odd one; under a selection every step is masked, so one body a span.
        pytest.param(
            1, 16384, 28, 4, None, False, False, 16, [_HALF_STEP] + [_WHOLE_STEP] * 2,
            id="cells-1x16384",
        ),
        pytest.param(
            1, 16384, 28, 4, 4096, False, False, 28, [_HALF_STEP] * 2 + [_WHOLE_STEP] * 2,
            id="cells-1x16384-window-4096",
        ),
        pytest.param(
            1, 8192, 32, 8, None, False, False, 8, [_HALF_STEP] + [_WHOLE_STEP] * 2,
            id="cells-1x8192",
        ),
        pytest.param(
            1, 8192, 32, 4, None, True, False, 8, [_HALF_STEP, _WHOLE_STEP],
            id="cells-1x8192-selection",
        ),
        pytest.param(
            4, 2048, 32, 8, None, False, False, 2, [_HALF_STEP] + [_WHOLE_STEP] * 2,
            id="cells-4x2048",
        ),
        # A ring hop's calls (position arrays): the walk over every pair and
        # its two bodies on the whole block, as before the halves existed.
        pytest.param(
            1, 8192, 32, 8, None, False, True, 0, [_WHOLE_STEP] * 2, id="positions-1x8192"
        ),
        pytest.param(
            1, 16384, 28, 4, 4096, False, True, 0, [_WHOLE_STEP] * 2,
            id="positions-1x16384-window-4096",
        ),
    ],
)
def test_a_call_traces_the_half_steps_its_table_holds_and_lowers(
    b, s, h, kv, window, selected, positions, halves, bodies
):
    """At the cells' real shapes, default blocks: a call without position
    arrays traces one update body for each class its step table holds (the
    scores of a half step are 512 x 512), the forward and the one backward
    alike, and lowers for a TPU (the static slices of k, v, dk, dv rows and of
    the key positions' or the selection's lanes are whole tiles); a call with
    them traces the two bodies it always did."""
    from torchft_tpu.ops import flash_attention as fa

    d = 128
    q = _sds((b, s, h, d), jnp.bfloat16)
    k = _sds((b, s, kv, d), jnp.bfloat16)
    at = [_sds((b, s), jnp.int32)] * 2 if positions else []
    selection = _sds((b, s, s), jnp.int8) if selected else None

    def both(q, k, v, do, selection, *at):
        qp, kp = at or (None, None)
        out, lse = fa._flash_fwd(
            q, k, v, d**-0.5, 512, 1024, False, qp, kp, selection=selection, window=window
        )
        return fa.flash_attention_partial_bwd(
            q, k, v, do, out, lse.reshape(b, s, h), qp, kp, d**-0.5, 512, 1024, False,
            out_dtype=jnp.bfloat16, selection=selection, window=window,
        )

    own = [jnp.arange(s, dtype=jnp.int32)[None]] * 2 if positions else [None, None]
    assert fa._class_counts(s, s, 512, 1024, *own, window=window)["halves"] == halves
    traced = jax.jit(both).trace(q, k, k, q, selection, *at)
    forward, backward = _pallas_calls(traced.jaxpr.jaxpr)
    assert _update_bodies(forward) == _update_bodies(backward) == bodies
    traced.lower(lowering_platforms=("tpu",))


# The granite cell's Mamba-2 geometry: 1 x 8192, 64 heads of 64, a state of
# 128 in one group, 4352 channels of convolution 4 wide, chunks of 256.
MAMBA = {"b": 1, "s": 8192, "heads": 64, "p": 64, "n": 128, "groups": 1, "width": 4, "chunk": 256}


def _mamba_operands(b, s, heads, p, n, groups, dtype=jnp.bfloat16, **_):
    return (
        _sds((b, s, heads, p), dtype), _sds((b, s, heads), jnp.float32), _sds((heads,), jnp.float32),
        _sds((b, s, groups, n), dtype), _sds((b, s, groups, n), dtype), _sds((heads,), dtype),
    )


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_the_scans_kernels_lower_for_tpu_at_the_granite_cells_geometry(direction):
    """``ssd_fwd`` alone (the primal call keeps no states) and the pair a
    gradient traces (``ssd_fwd`` with the entering states, ``ssd_bwd``)."""
    from torchft_tpu.ops import ssd

    operands = _mamba_operands(**MAMBA)
    assert ssd.scan_kernel_fits(operands[0], operands[3], MAMBA["chunk"])
    scan = lambda *z: ssd.ssd_scan(*z, chunk=MAMBA["chunk"], interpret=False)
    if direction == "forward":
        text = _lower_tpu(scan, *operands).as_text()
        assert text.count("tpu_custom_call") == 1 and ssd.SSD_FWD in text
        return
    loss = lambda *z: jnp.sum(scan(*z).astype(jnp.float32) ** 2)
    text = _lower_tpu(jax.grad(loss, argnums=tuple(range(6))), *operands).as_text()
    assert text.count("tpu_custom_call") == 2 and ssd.SSD_FWD in text and ssd.SSD_BWD in text


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_the_convolutions_kernels_lower_for_tpu_at_the_granite_cells_geometry(direction):
    from torchft_tpu.ops import ssd

    channels = MAMBA["heads"] * MAMBA["p"] + 2 * MAMBA["groups"] * MAMBA["n"]
    operands = (
        _sds((MAMBA["b"], MAMBA["s"], channels), jnp.bfloat16),
        _sds((channels, MAMBA["width"]), jnp.bfloat16), _sds((channels,), jnp.bfloat16),
    )
    assert channels == 4352 and ssd.conv_kernel_fits(*operands[:2])
    conv = lambda *z: ssd.conv_silu(*z, interpret=False)
    if direction == "forward":
        text = _lower_tpu(conv, *operands).as_text()
        assert text.count("tpu_custom_call") == 1 and ssd.CONV_FWD in text
        return
    loss = lambda *z: jnp.sum(conv(*z).astype(jnp.float32) ** 2)
    text = _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), *operands).as_text()
    assert text.count("tpu_custom_call") == 2 and ssd.CONV_FWD in text and ssd.CONV_BWD in text


@pytest.mark.parametrize(
    "sizes",
    [
        pytest.param({"heads": 4, "p": 32, "s": 256, "chunk": 128}, id="four-heads-of-32-a-slab"),
        pytest.param({"heads": 2, "p": 128, "s": 256, "chunk": 128}, id="a-head-of-128"),
        pytest.param({"heads": 8, "p": 64, "groups": 2, "s": 512, "chunk": 256}, id="two-groups"),
        pytest.param({"heads": 4, "p": 64, "s": 256, "chunk": 128, "dtype": jnp.float32}, id="float32"),
    ],
)
def test_the_scans_kernels_lower_at_the_other_shapes_they_take(sizes):
    """Every shape ``scan_kernel_fits`` admits has to lower: head widths on
    either side of a slab of 128 lanes, more than one group, float32."""
    from torchft_tpu.ops import ssd

    sizes = {**MAMBA, **sizes}
    operands = _mamba_operands(**sizes)
    assert ssd.scan_kernel_fits(operands[0], operands[3], sizes["chunk"])
    loss = lambda *z: jnp.sum(ssd.ssd_scan(*z, chunk=sizes["chunk"], interpret=False).astype(jnp.float32))
    text = _lower_tpu(jax.grad(loss, argnums=tuple(range(6))), *operands).as_text()
    assert text.count("tpu_custom_call") == 2
