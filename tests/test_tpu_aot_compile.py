"""Chipless COMPILES for the TPU: ask the chip's compiler before the chip.

tests/test_mosaic_lowering.py only *lowers* the Pallas kernels (block-mapping
validation). This file goes one step further and compiles — for a v5e that is
described, not attached (``jax.experimental.topologies``) — the kernels of
chip_smoke.py's main path at its real widths, and its whole plain step. That
catches what lowering cannot: a kernel that wants more VMEM than it may use,
a slice not aligned to the tiling, a program the TPU compiler refuses. Every
case asserts the Mosaic call (``tpu_custom_call``) is in the compiled text.

Nothing runs, so this says nothing about results or speed: a compile that
passes is a rehearsal, not a chip run. Skipped where the topology cannot be
described (no TPU compiler beside this jax). The persistent compile cache is
off around the module: such compiles write entries a later chipless run
cannot read back, and warns about.
"""

from __future__ import annotations

import os
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

# chip_smoke.py's shapes: CONFIGS["1b"] head geometry at batch 4 x seq 2048,
# the sp=4 ring hop of its 8192-token ring phase, and its largest DiLoCo
# fragment (one 128256 x 2048 vocabulary matrix) in 256-element blocks.
B, S, H, KV, D = 4, 2048, 32, 8, 64
RING_B, RING_HOP = 2, 2048
FRAGMENT_BLOCKS = 128256 * 2048 // 256


@pytest.fixture(scope="module", autouse=True)
def _compile_cache_off():
    from jax.experimental.compilation_cache import compilation_cache as cc

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def v5e():
    """The four devices of a described (not attached) v5e 2x2."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip, with why
        pytest.skip(f"v5e topology cannot be described: {type(e).__name__}: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def chip(v5e):
    """One of them, as a single-device sharding."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e[0])


def _sds(shape, dtype, chip):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _sds_tree(tree, chip):
    return jax.tree_util.tree_map(lambda a: _sds(a.shape, a.dtype, chip), tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _mosaic_calls(compiled) -> list:
    """(name, the limits it states, the bytes it used) of every Mosaic call
    in a compiled program's text."""
    calls = []
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        stated, used = (
            [int(size) for size in re.findall(r'"size":"(\d+)"', configs)]
            for configs in re.findall(r'"(?:used_)?scoped_memory_configs":\[([^\]]*)\]', line)
        )
        calls.append((line.split(" = ")[0].strip().lstrip("%"), stated, used))
    return calls


def _qkv(chip, b=B, sq=S, sk=S):
    return (
        _sds((b, sq, H, D), jnp.bfloat16, chip),
        _sds((b, sk, KV, D), jnp.bfloat16, chip),
        _sds((b, sk, KV, D), jnp.bfloat16, chip),
    )


def test_flash_forward_compiles_at_smoke_widths(chip) -> None:
    from torchft_tpu.ops.flash_attention import flash_attention

    _compile(lambda q, k, v: flash_attention(q, k, v, interpret=False), *_qkv(chip))


def test_flash_fused_backward_compiles_at_smoke_widths(chip) -> None:
    from torchft_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, interpret=False, use_pallas_bwd=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    _compile(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(chip))


def test_flash_partial_pair_compiles_at_ring_hop(chip) -> None:
    """The ring building blocks: one hop's partial forward and its backward
    from the merged logsumexp, with explicit position arrays."""
    from torchft_tpu.ops.flash_attention import (
        flash_attention_partial,
        flash_attention_partial_bwd,
    )

    q, k, v = _qkv(chip, b=RING_B, sq=RING_HOP, sk=RING_HOP)
    pos = _sds((RING_B, RING_HOP), jnp.int32, chip)
    _compile(
        lambda q, k, v, qp, kp: flash_attention_partial(
            q, k, v, qp, kp, interpret=False
        ),
        q, k, v, pos, pos,
    )
    lse = _sds((RING_B, RING_HOP, H), jnp.float32, chip)
    _compile(
        lambda q, k, v, d_out, out, lse, qp, kp: flash_attention_partial_bwd(
            q, k, v, d_out, out, lse, qp, kp, D**-0.5, 512, 1024, False
        ),
        q, k, v, q, q, lse, pos, pos,
    )


def test_ring_flash_compiles_over_sp4(v5e) -> None:
    """What ``chip_smoke.py --chips 4`` runs last, at its size: ring attention
    over an sp=4 mesh of the described chips with the Pallas hops compiled,
    forward and gradient. Inside the shard_map the hops' positions, and so
    the kernels' schedule tables, differ by shard."""
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchft_tpu.ops.ring_attention import ring_attention_flash

    mesh = Mesh(np.array(v5e[:4]), ("sp",))
    spec = P(None, "sp", None, None)

    def ring(q, k, v):
        return shard_map(
            lambda q, k, v: ring_attention_flash(
                q, k, v, axis_name="sp", interpret=False
            ),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
        )(q, k, v)

    q, k, v = (
        jax.ShapeDtypeStruct(
            (RING_B, 4 * RING_HOP, heads, D), jnp.bfloat16,
            sharding=NamedSharding(mesh, spec),
        )
        for heads in (H, KV, KV)
    )
    forward = _compile(ring, q, k, v)
    assert forward.as_text().count('custom_call_target="tpu_custom_call"') == 1
    backward = _compile(
        jax.grad(
            lambda q, k, v: jnp.sum(ring(q, k, v).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2),
        ),
        q, k, v,
    )
    # The forward's hop loop, then the backward's: a hop's dq, dk and dv
    # come from one call.
    assert backward.as_text().count('custom_call_target="tpu_custom_call"') == 2


# The benchmark cells' attention (chipbench/configs/mistral-7b-v0.3-1chip.json:
# 32 q heads over 8 KV heads of 128) at their traffic: the call fits the VMEM
# it gets without asking and states no limit. A ring hop of the same 8192 rows,
# whose gradients leave in float32 and whose positions are arguments, a hop of
# a 128k sequence over sp=4, and the cells' rows in 1024 x 1024 blocks (the
# sweep's best pair, whose probabilities outgrow the registers by 3 MiB) need
# more and state it. 65,536 rows are over the most a call holds and are walked
# in two chunks.
CELL_H, CELL_KV, CELL_D = 32, 8, 128


@pytest.mark.parametrize(
    "batch, seq, blocks, hop, chunks, states_limit",
    [
        pytest.param(1, 8192, (512, 1024), False, 1, False, id="cells-1x8192"),
        pytest.param(4, 2048, (512, 1024), False, 1, False, id="cells-4x2048"),
        pytest.param(
            1, 8192, (1024, 1024), False, 1, True, id="cells-1x8192-1024x1024"
        ),
        pytest.param(1, 8192, (512, 1024), True, 1, True, id="ring-hop-8192-f32"),
        pytest.param(1, 32768, (512, 1024), True, 1, True, id="ring-hop-32768-f32"),
        pytest.param(1, 65536, (512, 1024), False, 2, True, id="over-budget-1x65536"),
    ],
)
def test_flash_backward_compiles_at_the_cells_geometry(
    chip, batch, seq, blocks, hop, chunks, states_limit
) -> None:
    """The one backward call under the VMEM limit it works out from its
    shapes, at the default 512 x 1024 blocks and at 1024 x 1024."""
    from torchft_tpu.ops import flash_attention as fa

    out_dtype = jnp.float32 if hop else jnp.bfloat16
    tile = (*blocks, CELL_D, 2, jnp.dtype(out_dtype).itemsize)
    nc, nqc = fa._q_chunks(seq, fa._MAX_VMEM_BYTES, *tile)
    need = fa._bwd_vmem_bytes(nqc * blocks[0], *tile)
    assert nc == chunks and need <= fa._MAX_VMEM_BYTES
    assert (need > fa._SCOPED_VMEM_BYTES) == states_limit
    q = _sds((batch, seq, CELL_H, CELL_D), jnp.bfloat16, chip)
    k = _sds((batch, seq, CELL_KV, CELL_D), jnp.bfloat16, chip)
    lse = _sds((batch, seq, CELL_H), jnp.float32, chip)
    pos = _sds((batch, seq), jnp.int32, chip)

    def backward(q, k, v, d_out, out, lse, qp, kp):
        if not hop:
            qp = kp = None
        return fa.flash_attention_partial_bwd(
            q, k, v, d_out, out, lse, qp, kp, CELL_D**-0.5, *blocks, False,
            out_dtype=out_dtype,
        )

    compiled = _compile(backward, q, k, k, q, q, lse, pos, pos)
    # What the call states (nothing: XLA sees a call like any other and tiles
    # the program's other fusions as it did) and what Mosaic used of it.
    ((_, stated, used),) = _mosaic_calls(compiled)
    assert stated == ([need] if states_limit else [])
    assert used and used[0] <= (need if states_limit else fa._SCOPED_VMEM_BYTES)


@pytest.mark.parametrize(
    "batch, seq, heads, kv_heads, window, selected, halves",
    [
        pytest.param(1, 16384, 28, 4, None, False, 16, id="cells-1x16384"),
        pytest.param(1, 16384, 28, 4, 4096, False, 28, id="cells-1x16384-window-4096"),
        pytest.param(1, 8192, 32, 8, None, False, 8, id="cells-1x8192"),
        pytest.param(1, 8192, 32, 4, None, True, 8, id="cells-1x8192-selection"),
        pytest.param(4, 2048, 32, 8, None, False, 2, id="cells-4x2048"),
    ],
)
def test_the_half_steps_compile_at_the_cells_geometries(
    chip, batch, seq, heads, kv_heads, window, selected, halves
) -> None:
    """The forward and the one backward call of every cell's attention, whose
    step tables hold pairs computed by one half of their KV block: the static
    half slices of k, v, the mask's lanes and the dk / dv accumulators
    compile for a described v5e, each call uses no more VMEM than it may (the
    16 MiB it gets unasked, or the limit the backward states), and the
    backward's estimate from its shapes is still over the compiler's own
    account."""
    from torchft_tpu.ops import flash_attention as fa

    assert fa._class_counts(seq, seq, 512, 1024, window=window)["halves"] == halves
    need = fa._bwd_vmem_bytes(seq, 512, 1024, CELL_D, 2, 2, selected)
    selection = _sds((batch, seq, seq), jnp.int8, chip) if selected else None

    def loss(q, k, v, selection):
        out = fa.flash_attention(q, k, v, interpret=False, selection=selection, window=window)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2)),
        _sds((batch, seq, heads, CELL_D), jnp.bfloat16, chip),
        _sds((batch, seq, kv_heads, CELL_D), jnp.bfloat16, chip),
        _sds((batch, seq, kv_heads, CELL_D), jnp.bfloat16, chip),
        selection,
    )
    forward, backward = sorted(_mosaic_calls(compiled), key=lambda call: call[2][0])
    # (Beside a call that states a limit, the text gives one that states none
    # the 16 MiB it gets unasked.)
    assert forward[1] in ([], [fa._SCOPED_VMEM_BYTES]) and forward[2][0] <= fa._SCOPED_VMEM_BYTES
    assert backward[1] == ([need] if need > fa._SCOPED_VMEM_BYTES else [])
    assert backward[2][0] <= need


@pytest.mark.parametrize(
    "seq, steps",
    [
        pytest.param(131072, 16512, id="listed-131072"),
        pytest.param(262144, 512 * 256, id="dense-262144"),
    ],
)
def test_the_step_table_fits_smem_where_the_call_lists_its_pairs(chip, seq, steps) -> None:
    """A call without position arrays steps the needed pairs alone, its table
    a scalar-prefetch operand of 16 bytes a step that rides in SMEM whole:
    264 KB at 131,072 rows, which the compiler takes, forward and backward
    (the backward in chunks, each as long as the longest). At 262,144 the
    table would be the 1,052,672 bytes the compiler refuses (the v5e's SMEM is
    1 MiB); over ``_MAX_TABLE_BYTES`` the call walks every pair, by shape
    alone, and compiles as it did."""
    from torchft_tpu.ops import flash_attention as fa

    assert fa._class_counts(seq, seq, 512, 1024)["steps"] == steps
    assert (16 * steps <= fa._MAX_TABLE_BYTES) == (seq == 131072)
    q = _sds((1, seq, 1, CELL_D), jnp.bfloat16, chip)
    lse = _sds((1, seq, 1), jnp.float32, chip)
    _compile(lambda q, k, v: fa.flash_attention(q, k, v, interpret=False), q, q, q)
    _compile(
        lambda q, k, v, d_out, out, lse: fa.flash_attention_partial_bwd(
            q, k, v, d_out, out, lse, None, None, CELL_D**-0.5, 512, 1024, False,
            out_dtype=jnp.bfloat16,
        ),
        q, q, q, q, q, lse,
    )


@pytest.mark.parametrize("wire", ["fp8", "int8"])
def test_codec_pair_compiles_at_fragment_size(chip, wire) -> None:
    from torchft_tpu.ops import quantization as q

    blocks = _sds((FRAGMENT_BLOCKS, q.BLOCK), jnp.float32, chip)
    _compile(lambda x: q.quantize_blocks_pallas(x, wire=wire), blocks)
    payload, scales = jax.eval_shape(
        lambda x: q.quantize_blocks_pallas(x, wire=wire), blocks
    )
    _compile(
        q.dequantize_blocks_pallas,
        _sds(payload.shape, payload.dtype, chip),
        _sds(scales.shape, scales.dtype, chip),
    )


# The benchmark cell's layer stack as models/llama.py shapes it (two scanned
# layers of chipbench/configs/mistral-7b-v0.3-1chip.json): 310.4M values, of
# which the two DenseGeneral kernels that end in (heads, 128) are merged in
# bf16 and the float32 norm scale rides the flat tail.
CELL_FRAGMENT = (
    ((2, 4096, 14336), jnp.bfloat16),
    ((2, 14336, 4096), jnp.bfloat16),
    ((2, 4096, 32, 128), jnp.bfloat16),
    ((2, 4096, 8, 128), jnp.bfloat16),
    ((2, 32, 128, 4096), jnp.bfloat16),
    ((2, 4096), jnp.float32),
)


def test_fragment_sync_programs_compile_at_the_cells_geometry(chip, monkeypatch) -> None:
    """DiLoCo's two sync programs for the cell's leaves: each holds its
    Mosaic calls (one a leaf that goes leaf-wise, one for the flat tail),
    and neither copies the fragment through flat float32 arrays. The flat
    formulation read 34.6 + 44.4 bytes accessed a value by the compiler's
    count here, with 8.3 and 7.4 bytes a value of temporaries (two flat
    float32 copies of the fragment); a later edit that brings those copies
    back fails this test."""
    import optax

    from torchft_tpu import local_sgd
    from torchft_tpu.ops import quantization as q

    monkeypatch.setattr(q, "on_tpu", lambda: True)
    leaves = [_sds(shape, dtype, chip) for shape, dtype in CELL_FRAGMENT]
    values = sum(leaf.size for leaf in leaves)
    assert q.tree_codec_elements(leaves) == {"leaf": values - 2 * 4096, "flat": 2 * 4096}
    outer_tx = optax.sgd(0.7, momentum=0.9, nesterov=True)
    quantize_pseudograd, apply_outer = local_sgd._device_sync_programs(
        leaves, outer_tx, 0.0
    )
    payload, scales = jax.eval_shape(quantize_pseudograd, leaves, leaves)
    assert payload.shape == (-(-values // q.BLOCK), q.BLOCK)
    programs = {
        "quantize_pseudograd": quantize_pseudograd.lower(leaves, leaves).compile(),
        "apply_outer": apply_outer.lower(
            _sds(payload.shape, payload.dtype, chip),
            _sds(scales.shape, scales.dtype, chip),
            leaves, leaves, _sds_tree(jax.eval_shape(outer_tx.init, leaves), chip),
        ).compile(),
    }
    accessed = 0.0
    for name, compiled in programs.items():
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == len(leaves), name
        temporaries = compiled.memory_analysis().temp_size_in_bytes / values
        assert temporaries <= 4.5, f"{name}: {temporaries:.2f} bytes a value of temporaries"
        accessed += compiled.cost_analysis()["bytes accessed"] / values
    assert accessed <= 34, f"{accessed:.1f} bytes accessed a value by the two programs"


@pytest.mark.slow  # 10-15 s: the tier-1 gate (-m 'not slow') is near its limit
def test_plain_step_compiles_at_smoke_config(chip, monkeypatch) -> None:
    """The whole plain SGD-momentum step chip_smoke.py runs, with its state
    inside one chip's HBM. ops/ asks ``on_tpu()`` and would see the CPU
    here: the test steers it, the program grows no option for it."""
    import optax

    import chip_smoke
    import torchft_tpu.models.llama as llama
    import torchft_tpu.ops.attention as attention
    import torchft_tpu.ops.flash_attention as flash

    for module in (attention, flash):
        monkeypatch.setattr(module, "on_tpu", lambda: True)
    config, batch, seq = chip_smoke.smoke_config(rehearse=False)
    assert replace(config, n_layers=16, max_seq_len=8192) == replace(
        llama.CONFIGS["1b"], attention_impl="flash", scan_layers=True,
        remat="dots", loss_vocab_chunk=4096,
    ), "chip_smoke cut a width of CONFIGS['1b']"
    model = llama.Llama(config)
    tx = optax.sgd(0.01, momentum=0.9)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((batch, seq), jnp.int32))
    )
    compiled = (
        chip_smoke.make_plain_step(tx, chip_smoke.make_loss_fn(model))
        .lower(
            _sds_tree(params, chip),
            _sds_tree(jax.eval_shape(tx.init, params), chip),
            _sds((batch, seq + 1), jnp.int32, chip),
        )
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )
    # The described-topology compile does not refuse an oversized program
    # by itself: hold the bytes against the v5e's 15.75 GiB here.
    assert total < 15.75 * 2**30, f"plain step needs {total / 2**30:.2f} GiB"


# The cells' own sizes (chipbench/traffic/ftddp-seq8k.json and ftddp.json over
# the Mistral configuration file), and twins at toy widths for tier-1: the
# same head_dim, GQA ratio, block sizes and fused-CE chunk, one eighth of the
# widths and of the sequence. The last column is the step program's
# temporaries as the commit before the kernels took their schedule tables
# (8c1185e) compiled it here, in bytes.
_TOY_WIDTHS = {
    "hidden_size": 256, "num_attention_heads": 2, "num_key_value_heads": 1,
    "intermediate_size": 512, "vocab_size": 4096,
}


@pytest.mark.parametrize(
    "widths, batch, seq, temp_before",
    [
        pytest.param({}, 1, 8192, 1090004992, marks=pytest.mark.slow, id="mistral-1x8192"),  # 30 s
        pytest.param({}, 4, 2048, 1058031104, marks=pytest.mark.slow, id="mistral-4x2048"),  # 25 s
        pytest.param(_TOY_WIDTHS, 1, 1024, 0, id="toy-1x1024"),
        pytest.param(_TOY_WIDTHS, 4, 256, 0, id="toy-4x256"),
    ],
)
def test_dots_step_runs_one_flash_forward_a_layer(
    chip, monkeypatch, widths, batch, seq, temp_before
) -> None:
    """The FT-DDP fused step of ``mistral7b-1chip.ftddp-seq8k`` and of
    ``mistral7b-1chip.ftddp`` (2 scanned layers, bf16, ``dots``, fused CE
    4096, AdamW) compiled twice: as the model builds it, and with ``dots``
    meaning plain ``checkpoint_dots`` again. The layers stay one loop either
    way, so the compiled text holds one Mosaic call for each kernel of a
    layer body: forward and the one backward (dq, dk and dv from a single
    recomputation of the scores) — and under plain ``checkpoint_dots`` the
    forward a second time, in the backward's loop. Keeping (out, lse) may
    cost the program's temporaries no more than those two arrays for each
    layer, and the kernels' schedule tables (two small int32 arrays a call,
    constants where the positions are ``arange``) no more than 1 MiB over
    what the program needed before it had them. No Mosaic call of the step
    states a VMEM limit (ops/flash_attention.py ``_SCOPED_VMEM_BYTES``)."""
    import json
    from pathlib import Path

    import torchft_tpu.models.llama as llama
    import torchft_tpu.ops.attention as attention
    import torchft_tpu.ops.flash_attention as flash
    from chipbench.architectures import mistral
    from chipbench.model import System
    from torchft_tpu.optim import make_jit_fused_step

    for module in (attention, flash):
        monkeypatch.setattr(module, "on_tpu", lambda: True)
    config = json.loads(
        (Path(__file__).parent.parent / "chipbench/configs/mistral-7b-v0.3-1chip.json")
        .read_text()
    )
    config.update(widths)
    assert config["run"]["remat"] == "dots" and config["run"]["scan_layers"]
    # The benchmark's own model, loss and AdamW for the cell's traffic.
    system = System(config, mistral, {"batch": batch, "seq": seq}, seed=0)
    params = jax.eval_shape(system.init_params)
    opt_state = jax.eval_shape(system.tx.init, params)

    def compiled():
        program = (
            make_jit_fused_step(system.tx, system.loss_fn, donate_state=True)
            .lower(
                _sds_tree(params, chip), _sds_tree(opt_state, chip),
                _sds((batch, seq + 1), jnp.int32, chip),
            )
            .compile()
        )
        calls = [
            line for line in program.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
        ]
        # No call of a cell's step program states a VMEM limit: one that did
        # made XLA tile the loss head's backward matmul 3 ms a step slower.
        assert all('"scoped_memory_configs":[]' in line for line in calls)
        return len(calls), program.memory_analysis().temp_size_in_bytes

    calls, temp = compiled()
    monkeypatch.setattr(llama, "remat_policy", lambda remat, dots, *names: dots)
    calls_plain_dots, temp_plain_dots = compiled()
    assert (calls, calls_plain_dots) == (2, 3)
    heads, layers = config["num_attention_heads"], config["num_hidden_layers"]
    # bf16 out, f32 lse
    kept = layers * batch * seq * (config["hidden_size"] * 2 + heads * 4)
    assert temp - temp_plain_dots <= 1.1 * kept, (temp, temp_plain_dots, kept)
    assert temp <= temp_before + 2**20, (temp, temp_before)


def test_sharded_step_with_size_one_mesh_axis_compiles(v5e, monkeypatch) -> None:
    """The layout of chip_smoke.py's two-chip replica groups: an fsdp=2 x
    tp=1 mesh. Mosaic refuses to lower while ANY mesh axis is left
    automatic, a size-1 one included — the flash dispatcher must take them
    all. (This failed on the chip before it did in any CPU test: interpret
    mode has no such rule. A tiny model shows it as well as a large one.)"""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import torchft_tpu.models.llama as llama
    import torchft_tpu.ops.attention as attention
    import torchft_tpu.ops.flash_attention as flash

    for module in (attention, flash):
        monkeypatch.setattr(module, "on_tpu", lambda: True)
    mesh = Mesh(np.array(v5e[:2]).reshape(2, 1), ("fsdp", "tp"))
    config = replace(
        llama.CONFIGS["tiny"], attention_impl="flash", dtype=jnp.bfloat16,
        n_layers=1, max_seq_len=128,
    )
    model = llama.Llama(config)
    batch, seq = 4, 128
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((batch, seq), jnp.int32))
    )
    shardings = llama.plan_shardings(params, mesh, llama.sharding_plan("fsdp", "tp"))
    params = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        params, shardings,
    )
    tokens = jax.ShapeDtypeStruct(
        (batch, seq), jnp.int32, sharding=NamedSharding(mesh, P("fsdp", None))
    )

    def loss_fn(p, t):
        return jnp.sum(model.apply(p, t) ** 2)

    with jax.set_mesh(mesh):
        compiled = jax.jit(jax.value_and_grad(loss_fn)).lower(params, tokens).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_grouped_expert_product_compiles_at_the_keye_cells_geometry(chip) -> None:
    """The expert layer's three grouped products and their gradients at the
    cell ``keye-vl2-30b-a3b-1chip.ftddp-seq8k``'s shapes: the dropless row
    buffer of 8192 tokens x 8 choices, 16 held experts of 2048 x 768, a 17th
    group of rows that belong elsewhere: megablox's ``gmm`` and ``tgmm``."""
    from torchft_tpu.ops.grouped_matmul import grouped_matmul

    rows, d, f, held = 8192 * 8, 2048, 768, 16

    def loss(x, w_gate, w_up, w_down, sizes):
        product = lambda a, w: grouped_matmul(a, w, sizes, use_pallas=True)
        hidden = jax.nn.silu(product(x, w_gate)) * product(x, w_up)
        return jnp.sum(product(hidden, w_down).astype(jnp.float32))

    text = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3)),
        _sds((rows, d), jnp.bfloat16, chip), _sds((held, d, f), jnp.bfloat16, chip),
        _sds((held, d, f), jnp.bfloat16, chip), _sds((held, f, d), jnp.bfloat16, chip),
        _sds((held + 1,), jnp.int32, chip),
    ).as_text()
    # Three forward, up to three for the rows' gradient, three (tgmm) for the
    # weights'; by whatever name, each holds "gmm" (what the benchmark's
    # ``expert_time_pct`` finds them by).
    calls = re.findall(r"%(\S+) = \S+ custom-call\([^\n]*tpu_custom_call", text)
    assert len(calls) >= 8 and all("gmm" in name for name in calls), calls
    assert sum("tgmm" in name for name in calls) == 3


def test_selected_attention_fits_the_chip_at_the_keye_cells_geometry(chip) -> None:
    """One layer's attention under the selection, forward and backward, at
    1 x 8192 with 32 / 4 heads of 128, 16 indexer heads of 64 and top-2048:
    plain XLA (no Mosaic call), and its temporaries are a tile's, not the
    (heads, s, s) scores: under 4 GiB where those would be 8."""
    from torchft_tpu.ops.sparse_attention import sparse_attention

    s, h, kv, d, j, e = 8192, 32, 4, 128, 16, 64

    def loss(q, k, v, qi, ki, w):
        out, _ = sparse_attention(q, k, v, qi, ki, w, topk=2048, scale=d**-0.5)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        _sds((1, s, h, d), jnp.bfloat16, chip), _sds((1, s, kv, d), jnp.bfloat16, chip),
        _sds((1, s, kv, d), jnp.bfloat16, chip), _sds((1, s, j, e), jnp.float32, chip),
        _sds((1, s, e), jnp.float32, chip), _sds((1, s, j), jnp.float32, chip),
    ).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30


def test_selected_flash_kernels_compile_at_the_keye_cells_geometry(chip) -> None:
    """The forward and the one backward call with the selection as an operand,
    at 1 x 8192 with 32 / 4 heads of 128 and the default 512 x 1024 blocks:
    neither states a VMEM limit (a stated one retiles other fusions of the
    step: ops/flash_attention.py ``_SCOPED_VMEM_BYTES``), both fit the 16 MiB
    a call gets unasked, and the backward's estimate, which knows of the
    operand's block, is over what the compiler used and not over those
    16 MiB: exactly at them, so a block of this call that grows brings a
    stated limit and fails here."""
    from torchft_tpu.ops import flash_attention as fa

    s, h, kv, d = 8192, 32, 4, 128
    need = fa._bwd_vmem_bytes(s, 512, 1024, d, 2, 2, True)
    assert fa._bwd_vmem_bytes(s, 512, 1024, d, 2, 2) < need <= fa._SCOPED_VMEM_BYTES

    def loss(q, k, v, selection):
        out = fa.flash_attention(q, k, v, interpret=False, selection=selection)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2)),
        _sds((1, s, h, d), jnp.bfloat16, chip), _sds((1, s, kv, d), jnp.bfloat16, chip),
        _sds((1, s, kv, d), jnp.bfloat16, chip), _sds((1, s, s), jnp.int8, chip),
    )
    calls = _mosaic_calls(compiled)
    assert len(calls) == 2 and all(stated == [] for _, stated, _ in calls), calls
    assert max(used[0] for _, _, used in calls) <= need, calls


def test_selection_kernel_compiles_at_the_keye_cells_geometry(chip) -> None:
    """The key selection as one Mosaic call (ops/key_selection.py) at 1 x 8192
    with 16 indexer heads of 64 and top-2048: it lowers through Mosaic under
    its own name, states no VMEM limit (a stated one retiles other fusions of
    the step: ops/flash_attention.py ``_SCOPED_VMEM_BYTES``) and uses no more
    than the 16 MiB a call gets unasked; around it XLA only transposes the
    two operands, with no product of its own."""
    from torchft_tpu.ops import flash_attention as fa
    from torchft_tpu.ops import key_selection as ks

    s, j, e = 8192, 16, 64
    assert ks.fits(s, e)
    compiled = _compile(
        lambda qi, ki, w: ks.key_selection(qi, ki, w, topk=2048),
        _sds((1, s, j, e), jnp.float32, chip), _sds((1, s, e), jnp.float32, chip),
        _sds((1, s, j), jnp.float32, chip),
    )
    ((name, stated, used),) = _mosaic_calls(compiled)
    assert ks.KERNEL_NAME in name and stated == [], (name, stated)
    assert used[0] <= fa._SCOPED_VMEM_BYTES, used
    assert " convolution(" not in compiled.as_text()


# The cell ``keye-vl2-30b-a3b-1chip.ftddp-seq8k``'s own size, and a twin at toy
# widths for tier-1: the same head_dim, GQA group of 8, block sizes and four
# key groups, an eighth of the sequence.
_KEYE_TOY = {
    "hidden_size": 256, "num_attention_heads": 8, "num_key_value_heads": 1,
    "moe_intermediate_size": 128, "num_experts": 16, "num_experts_per_tok": 2,
    "num_local_experts": 2, "vocab_size": 2048, "num_hidden_layers": 2,
    "sa_config": {
        "indexer_head_dim": 64, "indexer_num_heads": 2, "indexer_num_kv_heads": 1,
        "kv_chunk_size": 128, "q_chunk_size": 128, "topk": 256,
    },
}


@pytest.mark.parametrize(
    "widths, seq",
    [
        pytest.param({}, 8192, marks=pytest.mark.slow, id="keye-1x8192"),  # 2 x 40 s
        pytest.param(_KEYE_TOY, 1024, id="toy-1x1024"),
    ],
)
def test_dots_step_of_the_keye_cell_selects_once_and_runs_one_flash_pair_a_layer(
    chip, monkeypatch, widths, seq
) -> None:
    """The FT-DDP fused step of the KeyeVL2 cell (scanned layers, bf16,
    ``dots``, AdamW) compiled for a described v5e as the model builds it on a
    TPU: the layers stay one loop, so the compiled text holds one Mosaic call
    for each kernel of a layer body: beside the expert layer's (``gmm`` and the
    sum by token, ``sum_by_token``: one a rung in each of its two
    conditionals), the flash forward and the ONE backward, each with the
    selection as its operand, and neither stating a VMEM limit; and the
    selection is ONE Mosaic call (``key_selection``) a traced layer, in the
    forward's loop body alone, with no index-score product left in XLA. With
    a policy that keeps the dots and none of the three names, the backward's
    loop holds the forward call and the selection's call a second time: what
    the names are kept for."""
    import json
    from pathlib import Path

    import torchft_tpu.models.keye as keye
    import torchft_tpu.ops.flash_attention as flash
    import torchft_tpu.ops.grouped_matmul as grouped
    import torchft_tpu.ops.sparse_attention as sparse
    from chipbench import spec
    from chipbench.model import System
    from torchft_tpu.ops.key_selection import KERNEL_NAME
    from torchft_tpu.optim import make_jit_fused_step

    for module in (sparse, flash, grouped):
        monkeypatch.setattr(module, "on_tpu", lambda: True)
    root = Path(__file__).parent.parent
    config = json.loads(
        (root / "chipbench/configs/keye-vl2-30b-a3b-ep8-1chip.json").read_text()
    )
    config.update(widths)
    assert config["run"]["remat"] == "dots" and config["run"]["scan_layers"]
    architecture = spec.load_module(root / "chipbench/architectures/KeyeVL2.py")
    system = System(config, architecture, {"batch": 1, "seq": seq}, seed=0)
    params = jax.eval_shape(system.init_params)
    opt_state = jax.eval_shape(system.tx.init, params)

    def compiled():
        program = (
            make_jit_fused_step(system.tx, system.loss_fn, donate_state=True)
            .lower(
                _sds_tree(params, chip), _sds_tree(opt_state, chip),
                _sds((1, seq + 1), jnp.int32, chip),
            )
            .compile()
        )
        calls = _mosaic_calls(program)
        assert all(stated == [] for _, stated, _ in calls), calls
        sums = [name for name, _, _ in calls if "sum_by_token" in name]
        assert len(sums) == 6 and not any(architecture.EXPERT_KERNEL.search(name) for name in sums)
        selections = [name for name, _, _ in calls if KERNEL_NAME in name]
        assert not any(architecture.EXPERT_LAYER_KERNEL.search(name) for name in selections)
        flash_calls = [
            name for name, _, _ in calls
            if not architecture.EXPERT_KERNEL.search(name) and name not in sums + selections
        ]
        text = program.as_text().splitlines()
        assert not any("tpuft::indexer" in line and " convolution(" in line for line in text)
        made = [
            line for line in text
            if 'custom_call_target="tpu_custom_call"' in line and KERNEL_NAME in line.split(" = ")[0]
        ]
        assert len(made) == len(selections) and all("tpuft::indexer" in line for line in made)
        return len(flash_calls), len(made), sum("rematted_computation" in line for line in made)

    assert compiled() == (2, 1, 0)
    monkeypatch.setattr(keye, "remat_policy", lambda remat, dots, *names: dots)
    assert compiled() == (3, 2, 1)


def _computations(text: str) -> dict:
    """{computation: its instruction lines} of a compiled program's text."""
    found, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            name = head.group(1)
            found[name] = []
        elif name is not None and line.startswith("  "):
            found[name].append(line)
    return found


def _reached_from(computations: dict, root: str) -> set:
    """``root`` and every computation its instructions call, however deep."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in computations[name]:
            todo += [c for c in re.findall(r"%([\w.\-]+)", line.split(" = ", 1)[-1]) if c in computations]
    return seen


def test_dots_step_of_a_keye_share_keeps_every_worst_case_row_inside_the_last_rung(
    chip, monkeypatch
) -> None:
    """The FT-DDP fused step of a small KeyeVL2 (two scanned layers, ``dots``,
    2 of 16 experts held, 1,024 tokens of 8 choices: the expert dispatch's
    rungs are 2,048, 4,096 and the worst case 8,192) compiled for a described
    v5e. The program holds two conditionals, one in the forward's loop body
    and one in the backward's (the forward that ``dots`` runs again needs no
    product: its residuals are the layer's arguments), each over the three
    rungs; every op whose result has the worst case's 8,192 rows and a feature
    width lies inside a conditional's LAST branch; no conditional hands out an
    array with a rung's row count (nothing a rung sizes crosses from forward
    to backward); a rung's layer step is 12 ``gmm`` / ``tgmm`` calls, 3 in
    the forward's branch and 9 in the backward's, not 15, and two sums by
    token, one Mosaic call each (``sum_by_token``, which the expert kernel's
    name does not find); no branch holds a scatter into an array of feature
    width; and nothing a branch computes, here or at the cell's own shapes,
    has a result shape that ``selected_attention_seconds`` takes for the
    selection's under the cell's ``sa_config``."""
    import json
    from pathlib import Path

    import torchft_tpu.ops.flash_attention as flash
    import torchft_tpu.ops.grouped_matmul as grouped
    import torchft_tpu.ops.sparse_attention as sparse
    from chipbench import spec
    from chipbench.model import System
    from torchft_tpu.optim import make_jit_fused_step

    for module in (sparse, flash, grouped):
        monkeypatch.setattr(module, "on_tpu", lambda: True)
    root = Path(__file__).parent.parent
    config = json.loads(
        (root / "chipbench/configs/keye-vl2-30b-a3b-ep8-1chip.json").read_text()
    )
    cell = dict(config)
    config.update({**_KEYE_TOY, "num_experts_per_tok": 8})
    seq, k, widths = 1024, 8, (config["hidden_size"], config["moe_intermediate_size"])
    rungs = grouped.dispatch_rungs(seq, k, config["num_local_experts"], config["num_experts"])
    assert rungs == (2048, 4096, 8192)
    architecture = spec.load_module(root / "chipbench/architectures/KeyeVL2.py")
    system = System(config, architecture, {"batch": 1, "seq": seq}, seed=0)
    params = jax.eval_shape(system.init_params)
    text = (
        make_jit_fused_step(system.tx, system.loss_fn, donate_state=True)
        .lower(
            _sds_tree(params, chip), _sds_tree(jax.eval_shape(system.tx.init, params), chip),
            _sds((1, seq + 1), jnp.int32, chip),
        )
        .compile()
        .as_text()
    )
    computations = _computations(text)
    conditionals = [
        line for lines in computations.values() for line in lines if " conditional(" in line
    ]
    assert len(conditionals) == 2
    inside_last, expert_calls, sums, inside = set(), [], [], set()
    for line in conditionals:
        result = line.split(" = ", 1)[1].split(" conditional(")[0]
        handed_out = {int(n) for dims in re.findall(r"\[([\d,]+)\]", result) for n in dims.split(",")}
        assert not handed_out & set(rungs), result
        branches = re.search(r"branch_computations=\{([^}]*)\}", line).group(1)
        branches = [name.strip().lstrip("%") for name in branches.split(",")]
        assert len(branches) == len(rungs)
        reached = [_reached_from(computations, name) for name in branches]
        inside_last |= reached[-1]
        expert_calls.append([
            sum(
                'custom_call_target="tpu_custom_call"' in line
                and bool(architecture.EXPERT_KERNEL.search(line.split(" = ")[0]))
                for name in names for line in computations[name]
            )
            for names in reached
        ])
        inside |= set().union(*reached)
        sums.append([
            sum(
                'custom_call_target="tpu_custom_call"' in line
                and "sum_by_token" in line.split(" = ")[0]
                and not architecture.EXPERT_KERNEL.search(line.split(" = ")[0])
                for name in names for line in computations[name]
            )
            for names in reached
        ])
    assert sorted(expert_calls) == [[3, 3, 3], [9, 9, 9]]
    assert sums == [[1, 1, 1], [1, 1, 1]]
    feature_wide = re.compile(r" = \S*\[(?:\d+,)*(?:%d|%d)\]\S* scatter\(" % widths)
    scatters = [
        line[:160] for name in inside for line in computations[name] if feature_wide.search(line)
    ]
    assert not scatters, scatters
    # What the selection's reader would take for its own: the branches' ops
    # here, and every array the pair of functions and their backward rules
    # make at the cell's shapes and rungs.
    from chipbench import trace_reduce

    ops = [(trace_reduce.short_name(line.strip()), 1.0) for name in inside for line in computations[name]]
    tokens, dim = 8192, cell["hidden_size"]

    def both_ways(flat, x, w, t):
        rows, gather_back = jax.vjp(lambda flat: grouped.rows_of(flat, t), flat)
        out, sum_back = jax.vjp(lambda x, w: grouped.sum_by_token(x, w, t, tokens), x, w)
        return rows, out, gather_back(x), sum_back(out)

    def eqns_of(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns_of(inner)

    for rows in grouped.dispatch_rungs(
        tokens, cell["num_experts_per_tok"], cell["num_local_experts"], cell["num_experts"]
    ):
        made = jax.make_jaxpr(both_ways)(*(jax.ShapeDtypeStruct(*a) for a in (
            ((tokens, dim), jnp.bfloat16), ((rows, dim), jnp.bfloat16), ((rows,), jnp.float32),
            ((rows,), jnp.int32),
        )))
        ops += [
            (f"{eqn.primitive.name} {v.aval.str_short(short_dtypes=True)}".replace("i32", "s32"), 1.0)
            for eqn in eqns_of(made.jaxpr) for v in eqn.outvars if hasattr(v.aval, "shape")
        ]
    made = {name.split(" ", 1)[1] for name, _ in ops}
    assert len(ops) > 300 and {"s32[16384]", "f32[8192,2048]", "bf16[4,16384,2048]"} <= made
    assert architecture.selected_attention_seconds({"ops": ops}, cell, tokens) == 0.0
    worst_case = re.compile(r" = \S*\[%d,(?:%d|%d)\]" % (rungs[-1], *widths))
    rows = [
        (name, line) for name, lines in computations.items() for line in lines
        if worst_case.search(line)
    ]
    assert rows and all(name in inside_last for name, _ in rows), [
        line[:160] for name, line in rows if name not in inside_last
    ]


def test_windowed_flash_kernels_compile_at_the_smallthinker_cells_geometry(chip) -> None:
    """The forward and the one backward call under a window of 4,096 at
    1 x 16,384 with 28 / 4 heads of 128 and the default 512 x 1024 blocks,
    compiled for a described v5e: the two calls carry their own names in the
    compiled text (what a device trace will call them), the forward fits the
    16 MiB a call gets unasked, and the backward, whose float32 dq holds the
    head's 16,384 rows, states the limit it works out from its shapes."""
    from torchft_tpu.ops import flash_attention as fa

    s, h, kv, d = 16384, 28, 4, 128
    need = fa._bwd_vmem_bytes(s, 512, 1024, d, 2, 2)
    assert fa._SCOPED_VMEM_BYTES < need <= fa._MAX_VMEM_BYTES
    assert fa._q_chunks(s, fa._MAX_VMEM_BYTES, 512, 1024, d, 2, 2) == (1, 32)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, interpret=False, window=4096)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2)),
        _sds((1, s, h, d), jnp.bfloat16, chip), _sds((1, s, kv, d), jnp.bfloat16, chip),
        _sds((1, s, kv, d), jnp.bfloat16, chip),
    )
    calls = _mosaic_calls(compiled)
    # Differentiated on its own a call's name is wrapped (``jvp_..._``); in the
    # model's step it is the bare name and a number (the test below).
    (forward,) = [call for call in calls if fa.WINDOW_FWD in call[0]]
    (backward,) = [call for call in calls if fa.WINDOW_BWD in call[0]]
    assert len(calls) == 2 and backward[1] == [need]
    assert forward[2][0] <= fa._SCOPED_VMEM_BYTES and backward[2][0] <= need


# The cell ``smallthinker-21b-a3b-1chip.ftddp-seq16k``'s own size, and a twin
# at toy widths for tier-1: the same head_dim, GQA group of 7, block sizes,
# period of four kinds and a window of a quarter of the sequence.
_SMALLTHINKER_TOY = {
    "hidden_size": 256, "num_attention_heads": 7, "num_key_value_heads": 1,
    "moe_ffn_hidden_size": 128, "router_width": 16, "moe_num_active_primary_experts": 3,
    "moe_num_primary_experts": 2, "vocab_size": 2048, "sliding_window_size": 1024,
}


def _op_names(text: str, *opcodes: str) -> list:
    """The ``op_name`` metadata (the path through the traced program: scopes,
    modules, ``rematted_computation`` for what a backward computes again) of
    every instruction with one of ``opcodes`` in a compiled program's text."""
    pattern = re.compile(rf" (?:{'|'.join(opcodes)})\(.*op_name=\"([^\"]*)\"")
    return [m.group(1) for m in map(pattern.search, text.splitlines()) if m]


@pytest.mark.parametrize(
    "widths, seq, remat",
    [
        pytest.param({}, 16384, "dots", marks=pytest.mark.slow, id="smallthinker-1x16384"),  # 2 min
        pytest.param(_SMALLTHINKER_TOY, 4096, "dots", id="toy-1x4096"),
        pytest.param(_SMALLTHINKER_TOY, 4096, "full", id="toy-1x4096-full"),
    ],
)
def test_dots_step_of_the_smallthinker_cell_is_one_traced_period_and_fits(
    chip, monkeypatch, widths, seq, remat
) -> None:
    """The FT-DDP fused step of the smallthinker cell (eight layers scanned as
    two periods of four kinds, bf16, ``dots``, AdamW) compiled for a described
    v5e as the model builds it on a TPU: the period stays one loop body, so the
    compiled text holds the period's Mosaic calls once: one forward and one
    backward call of the full layer under its scope's name, three forward and
    three backward calls under the window's own names, and the routed layer's
    beside them; the names are the ones the architecture file's patterns find.
    ``dots`` keeps what the backward would otherwise compute a second time
    (PR 63): each kind's q / k / v / o products are in the text once as the
    forward wrote them, and what the backward recomputes
    (``rematted_computation``) holds none of them, no sort (the router's
    ``argsort``; the top-k is a sort to this compiler) and no tally; under
    ``full`` (the third case: the count can fail) it holds all of them and the
    forward kernels besides.
    At the cell's own size the program's arguments, results and temporaries
    come to 16.85 GiB by this compiler's sum, which is NOT what the chip holds:
    the parent's program sums to 13.40 here and held 10.67 GiB on the chip,
    this one 12.20 (``hbm_held_gib``; PERF.md section 6, PR 63: a kept stack
    costs the sum twice its bytes and the chip less than once). The bound is
    this program's own and guards against one more kept stack, 0.6 GiB."""
    import json
    from pathlib import Path

    import torchft_tpu.ops.attention as attention
    import torchft_tpu.ops.flash_attention as flash
    import torchft_tpu.ops.grouped_matmul as grouped
    from chipbench import spec
    from chipbench.model import System
    from torchft_tpu.optim import make_jit_fused_step

    for module in (attention, flash, grouped):
        monkeypatch.setattr(module, "on_tpu", lambda: True)
    root = Path(__file__).parent.parent
    config = json.loads(
        (root / "chipbench/configs/smallthinker-21b-a3b-ep8-1chip.json").read_text()
    )
    config.update(widths)
    assert config["run"]["remat"] == "dots" and config["run"]["scan_layers"]
    config["run"]["remat"] = remat
    architecture = spec.load_module(root / "chipbench/architectures/smallthinker.py")
    system = System(config, architecture, {"batch": 1, "seq": seq}, seed=0)
    assert system.model.config.period == 4 and system.model.config.n_layers == 8
    params = jax.eval_shape(system.init_params)
    assert sorted(params["params"]["layers"]) == [f"block_{kind}" for kind in range(4)]
    opt_state = jax.eval_shape(system.tx.init, params)
    program = (
        make_jit_fused_step(system.tx, system.loss_fn, donate_state=True)
        .lower(
            _sds_tree(params, chip), _sds_tree(opt_state, chip),
            _sds((1, seq + 1), jnp.int32, chip),
        )
        .compile()
    )
    names = [name for name, _, _ in _mosaic_calls(program)]
    attention_calls = [n for n in names if architecture.ATTENTION_KERNEL.search(n)]
    window_calls = [n for n in names if architecture.WINDOW_KERNEL.search(n)]
    again = remat == "full"  # the forward kernels come a second time
    assert len(attention_calls) == 8 + 4 * again and len(window_calls) == 6 + 3 * again, names
    assert sum(n.startswith(flash.WINDOW_FWD) for n in window_calls) == 3 + 3 * again
    assert all(
        architecture.EXPERT_KERNEL.search(n) for n in names if n not in attention_calls
    ), names
    assert not [n for n in attention_calls if architecture.EXPERT_KERNEL.search(n)]
    # The products by where the traced program made them (a dot is a
    # convolution to this compiler).
    text = program.as_text()
    products = _op_names(text, "convolution")
    projections = tuple(
        f"block_{kind}/attn/{w}/dot_general" for kind in range(4) for w in ("wq", "wk", "wv", "wo")
    )
    forward = [n for n in products if "/jvp(SmallThinker)/" in n]
    assert all(sum(n.endswith(p) for n in forward) == 1 for p in projections), forward
    twice = [n for n in products if "rematted_computation/" in n and n.endswith(projections)]
    decided_twice = [
        n for n in _op_names(text, "sort", "scatter")
        if "rematted_computation/" in n and "/moe/" in n
    ]
    if again:
        assert len(twice) == 16 and len(decided_twice) == 12, (twice, decided_twice)
    else:
        assert not twice and not decided_twice, (twice, decided_twice)
    if not widths:
        memory = program.memory_analysis()
        total = (
            memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes - memory.alias_size_in_bytes
        )
        assert total < 17.25 * 2**30, total / 2**30


@pytest.mark.slow  # four minutes: two float32 programs of eight written-out layers
def test_the_smallthinker_cells_reference_programs_fit_the_chip(chip) -> None:
    """The float32 reference's loss and its update at the cell's own size
    (1 x 16,384, eight layers, attention in blocks of 512 queries a key-value
    group), compiled for a described v5e: 3.13 and 14.06 GiB (PERF.md
    section 6, PR 54), both inside the chip's 15.75 with the bf16 weights
    they are given."""
    import json
    from pathlib import Path

    from chipbench import reference, spec
    from chipbench.model import System

    root = Path(__file__).parent.parent
    config = json.loads(
        (root / "chipbench/configs/smallthinker-21b-a3b-ep8-1chip.json").read_text()
    )
    architecture = spec.load_module(root / "chipbench/architectures/smallthinker.py")
    system = System(config, architecture, {"batch": 1, "seq": 16384}, seed=0)
    params = _sds_tree(jax.eval_shape(system.init_params), chip)
    tokens = _sds((1, 16385), jnp.int32, chip)

    def total(compiled):
        memory = compiled.memory_analysis()
        return (
            memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes - memory.alias_size_in_bytes
        )

    loss = reference.make_loss(architecture, config).lower(params, tokens).compile()
    assert total(loss) < 4 * 2**30
    update = reference.make_loss_after_first_update(architecture, config)
    assert total(update.lower(params, tokens, tokens).compile()) < 14.5 * 2**30


@pytest.mark.parametrize("kernels", ["scan", "convolution"])
def test_the_mamba_kernels_compile_at_the_granite_cells_geometry(chip, kernels) -> None:
    """ops/ssd.py's four Mosaic calls at the cell's geometry (1 x 8192, 64
    heads of 64, a state of 128 in one group, 4352 channels 4 wide, chunks of
    256, bf16), forward and backward, compiled for a described v5e: each under
    its name, stating no VMEM limit (PR 42: a stated limit moves other
    fusions) and using under the 16 MiB a call gets without asking."""
    from torchft_tpu.ops import ssd

    b, s, heads, p, n, groups, chunk = 1, 8192, 64, 64, 128, 1, 256
    if kernels == "scan":
        operands = (
            _sds((b, s, heads, p), jnp.bfloat16, chip), _sds((b, s, heads), jnp.float32, chip),
            _sds((heads,), jnp.float32, chip), _sds((b, s, groups, n), jnp.bfloat16, chip),
            _sds((b, s, groups, n), jnp.bfloat16, chip), _sds((heads,), jnp.bfloat16, chip),
        )
        call = lambda *z: ssd.ssd_scan(*z, chunk=chunk, interpret=False)
        names = (ssd.SSD_FWD, ssd.SSD_BWD)
    else:
        channels = heads * p + 2 * groups * n
        operands = (
            _sds((b, s, channels), jnp.bfloat16, chip), _sds((channels, 4), jnp.bfloat16, chip),
            _sds((channels,), jnp.bfloat16, chip),
        )
        call = lambda *z: ssd.conv_silu(*z, interpret=False)
        names = (ssd.CONV_FWD, ssd.CONV_BWD)
    loss = lambda *z: jnp.sum(call(*z).astype(jnp.float32) ** 2)
    calls = _mosaic_calls(_compile(jax.grad(loss, argnums=tuple(range(len(operands)))), *operands))
    assert len(calls) == 2 and all(name in call for name, (call, _, _) in zip(names, calls)), calls
    for name, stated, used in calls:
        assert not stated and 0 < max(used) <= 16 * 2**20, (name, stated, used)


# The cell ``granite-4.0-h-micro-1chip.ftddp-seq8k``'s own size, and a twin at
# toy widths for the slow marker's other side: the same head widths (64 and
# 64), state, chunk and period of ten.
_GRANITE_TOY = {
    "hidden_size": 256, "intermediate_size": 512, "shared_intermediate_size": 512,
    "num_attention_heads": 4, "num_key_value_heads": 1, "mamba_n_heads": 8, "vocab_size": 2048,
}


@pytest.mark.parametrize(
    "widths, seq",
    [
        pytest.param({}, 8192, id="granite-1x8192"),  # under a minute
        pytest.param(_GRANITE_TOY, 2048, id="toy-1x2048"),
    ],
)
def test_dots_step_of_the_granite_cell_recomputes_its_period_and_fits(
    chip, monkeypatch, widths, seq
) -> None:
    """The FT-DDP fused step of the granite cell (one period of ten layers,
    nine Mamba-2 and one attention, bf16, ``dots``, AdamW) compiled for a
    described v5e as the model builds it on a TPU. The scan of ONE period is
    inlined by XLA, and the remat barrier must survive that: at the cell's own
    size the program's arguments, results and temporaries come to under 14.5 of
    the chip's 15.75 GiB (13.49: PERF.md section 6, PR 57; 17.55 where CSE
    merges the recomputation with the forward). The one attention layer's two
    Mosaic calls are the only ones, under its scope's name; the scan's scopes
    reach the compiled text; and the architecture file's shapes find the scan's
    ops in it and none of the projections'."""
    import json
    from pathlib import Path

    import torchft_tpu.ops.attention as attention
    import torchft_tpu.ops.flash_attention as flash
    import torchft_tpu.ops.ssd as ssd
    from chipbench import spec
    from chipbench.model import System
    from torchft_tpu.optim import make_jit_fused_step

    for module in (attention, flash, ssd):
        monkeypatch.setattr(module, "on_tpu", lambda: True)
    root = Path(__file__).parent.parent
    config = json.loads((root / "chipbench/configs/granite-4.0-h-micro-1chip.json").read_text())
    config.update(widths)
    assert config["run"]["remat"] == "dots" and config["run"]["scan_layers"]
    architecture = spec.load_module(root / "chipbench/architectures/granitemoehybrid.py")
    system = System(config, architecture, {"batch": 1, "seq": seq}, seed=0)
    assert system.model.config.period == system.model.config.n_layers == 10
    params = jax.eval_shape(system.init_params)
    assert sorted(params["params"]["layers"]) == sorted(f"block_{kind}" for kind in range(10))
    opt_state = jax.eval_shape(system.tx.init, params)
    program = (
        make_jit_fused_step(system.tx, system.loss_fn, donate_state=True)
        .lower(
            _sds_tree(params, chip), _sds_tree(opt_state, chip),
            _sds((1, seq + 1), jnp.int32, chip),
        )
        .compile()
    )
    calls = _mosaic_calls(program)
    count = lambda name: sum(name in call for call, _, _ in calls)
    mamba_layers = system.model.config.layer_types.count("mamba")
    assert mamba_layers == 9 and len(calls) == 6 * mamba_layers + 2, [name for name, _, _ in calls]
    assert count("tpuft__nope_attention") == 2
    assert count(ssd.CONV_FWD) == count(ssd.SSD_FWD) == 2 * mamba_layers
    assert count(ssd.CONV_BWD) == count(ssd.SSD_BWD) == mamba_layers
    for name, stated, used in calls:  # inside the VMEM a call gets without asking
        assert not stated and max(used, default=0) <= 16 * 2**20, (name, stated, used)
    text = program.as_text()
    for scope in ("mamba::in_proj", "mamba::conv", "mamba::gated_norm", "mamba::out_proj"):
        assert f"tpuft::{scope}" in text, scope
    assert "tpuft::ssd::" not in text  # the einsum path's four parts
    # The reader on the program's own instructions, a second each: every
    # named call, none of the projections' fusions.
    by_kind = {"calls": [[name, 1.0] for name, _, _ in calls if "tpuft__" not in name], "projections": []}
    for line in text.splitlines():
        found = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])", line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        if not found or not op_name or " fusion(" not in line and " convolution(" not in line:
            continue
        if re.search(r"tpuft::mamba::(?:in|out)_proj", op_name.group(1)):
            by_kind["projections"].append([f"{found.group(1)} {found.group(2)}", 1.0])
    seen = lambda rows: architecture.ssd_seconds({"ops": rows}, config, 1, seq)
    assert seen(by_kind["calls"]) == 6.0 * mamba_layers and by_kind["projections"]
    assert seen(by_kind["projections"]) == 0.0
    if not widths:
        memory = program.memory_analysis()
        total = (
            memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes - memory.alias_size_in_bytes
        )
        assert total < 14.5 * 2**30, total / 2**30


@pytest.mark.slow  # a minute and a half: two float32 programs of ten written-out layers
def test_the_granite_cells_reference_programs_fit_the_chip(chip) -> None:
    """The float32 reference's loss and its update at the cell's own size
    (1 x 8192, ten layers, the scan's masked matrix in blocks of 128 rows),
    compiled for a described v5e: 3.97 and 9.64 GiB (PERF.md section 6,
    PR 57), both inside the chip's 15.75 with the bf16 weights they are given."""
    import json
    from pathlib import Path

    from chipbench import reference, spec
    from chipbench.model import System

    root = Path(__file__).parent.parent
    config = json.loads((root / "chipbench/configs/granite-4.0-h-micro-1chip.json").read_text())
    architecture = spec.load_module(root / "chipbench/architectures/granitemoehybrid.py")
    system = System(config, architecture, {"batch": 1, "seq": 8192}, seed=0)
    params = _sds_tree(jax.eval_shape(system.init_params), chip)
    tokens = _sds((1, 8193), jnp.int32, chip)

    def total(compiled):
        memory = compiled.memory_analysis()
        return (
            memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes - memory.alias_size_in_bytes
        )

    loss = reference.make_loss(architecture, config).lower(params, tokens).compile()
    assert total(loss) < 4.5 * 2**30
    update = reference.make_loss_after_first_update(architecture, config)
    assert total(update.lower(params, tokens, tokens).compile()) < 10.5 * 2**30


def test_dots_step_of_the_ouro_cell_is_one_traced_layer_for_32_passes_and_fits(chip, monkeypatch) -> None:
    """The FT-DDP fused step of the cell ``ouro-2.6b-1chip.ftddp-seq8k`` (eight
    layers run four times on one set of weights, bf16, ``dots``, AdamW with a
    float32 first moment, state donated) compiled for a described v5e as the
    model builds it on a TPU. The layers are one scan and the passes a scan
    around it, so the program holds ONE flash forward and ONE backward call for
    its 32 layer passes, under the model's scope; what ``dots`` keeps (the flash
    pair and the unit's output) brings arguments, results and temporaries to
    13.49 of the chip's 15.75 GiB (PERF.md section 6, PR 62; with gate and up
    kept as well it does not compile: 15.86); the three scopes reach the
    compiled text; and the architecture file's shapes find the exits' slabs in
    it under ``tpuft::exit`` and nothing of a layer pass."""
    import json
    from pathlib import Path

    import torchft_tpu.ops.attention as attention
    import torchft_tpu.ops.flash_attention as flash
    from chipbench import spec
    from chipbench.model import System
    from torchft_tpu.optim import make_jit_fused_step

    for module in (attention, flash):
        monkeypatch.setattr(module, "on_tpu", lambda: True)
    root = Path(__file__).parent.parent
    config = json.loads((root / "chipbench/configs/ouro-2.6b-1chip.json").read_text())
    assert config["run"]["remat"] == "dots" and config["run"]["scan_layers"]
    architecture = spec.load_module(root / "chipbench/architectures/ouro.py")
    system = System(config, architecture, {"batch": 1, "seq": 8192}, seed=0)
    params = jax.eval_shape(system.init_params)
    leaves = jax.tree_util.tree_leaves(params)
    assert sum(leaf.size for leaf in leaves) == architecture.parameter_counts(config)["total"] == 461_443_073
    assert params["params"]["layers"]["block"]["mlp"]["w_up"]["kernel"].shape == (8, 2048, 5632)  # ONE copy
    opt_state = jax.eval_shape(system.tx.init, params)
    state_bytes = sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree_util.tree_leaves((params, opt_state))
    )
    assert 3.43 * 2**30 < state_bytes < 3.45 * 2**30  # 8 bytes a parameter: bf16, float32 mu, bf16 nu
    program = (
        make_jit_fused_step(system.tx, system.loss_fn, donate_state=True)
        .lower(
            _sds_tree(params, chip), _sds_tree(opt_state, chip),
            _sds((1, 8193), jnp.int32, chip),
        )
        .compile()
    )
    calls = _mosaic_calls(program)
    assert len(calls) == 2 and all("tpuft__ouro_attention" in name for name, _, _ in calls), calls
    for name, stated, used in calls:  # inside the VMEM a call gets without asking
        assert not stated and max(used, default=0) <= 16 * 2**20, (name, stated, used)
    text = program.as_text()
    for scope in ("tpuft::ouro_attention", "tpuft::loop_pass", "tpuft::exit"):
        assert scope in text, scope
    # The reader's shapes on the program's own instructions: found under the
    # exits' scope, never under a layer pass's; the float32 (n, d) result, the
    # gradient to an exit's state summed over the slabs, among them.
    found = {"exit": 0, "pass": 0, "state": 0}
    for line in text.splitlines():
        match = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])", line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        if not match or not op_name or " fusion(" not in line and " convolution(" not in line:
            continue
        if architecture.is_exit_loss_op(match.group(2), config, 1, 8192):
            found["pass" if "tpuft::loop_pass" in op_name.group(1) else "exit"] += 1
            found["state"] += match.group(2) == "f32[8192,2048]"
            assert "tpuft::loop_pass" in op_name.group(1) or "tpuft::exit" in op_name.group(1), line[:300]
    assert found["exit"] >= 5 and found["pass"] == 0 and found["state"] >= 1, found
    memory = program.memory_analysis()
    total = (
        memory.argument_size_in_bytes + memory.output_size_in_bytes
        + memory.temp_size_in_bytes - memory.alias_size_in_bytes
    )
    assert 13.0 * 2**30 < total < 14.0 * 2**30, total / 2**30
