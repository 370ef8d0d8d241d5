"""Quantization + quantized collective tests: fp8 + int8 (parity targets:
quantization_test.py + collectives_test.py; the dual wire format mirrors
the reference's fp8-on-SM90+/int8-below split) and the beyond-reference
packed int4 wire format (half the bytes, opt-in)."""

from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from test_process_group import fresh_prefix, make_group, run_on_all, store_server  # noqa: F401

from torchft_tpu.ops import quantization as q
from torchft_tpu.parallel.collectives import (
    allreduce_quantized,
    reduce_scatter_quantized,
)
from torchft_tpu.parallel.process_group import ReduceOp


# -- kernels (numpy reference) ------------------------------------------------


@pytest.mark.parametrize("wire", ["fp8", "int8", "int4"])
@pytest.mark.parametrize(
    "shape", [(7,), (256,), (1000,), (33, 17), (4, 4, 4)]
)
def test_quantize_roundtrip_accuracy(shape, wire) -> None:
    rng = np.random.default_rng(0)
    x = rng.normal(size=shape).astype(np.float32) * 10
    payload, scales = q.quantize_blocks(x, wire=wire)
    assert payload.dtype == q._WIRE_NP_DTYPES[wire]
    if wire == "int4":  # two values per byte
        assert payload.shape[1] == q.BLOCK // 2
    restored = q.dequantize_blocks(payload, scales, x.shape, x.dtype)
    if wire in ("int8", "int4"):
        # Round-to-nearest guarantee: error <= scale/2 per element.
        bound = np.max(scales) / 2 * 1.001
        assert float(np.max(np.abs(restored - x))) <= bound
    else:
        # fp8 e4m3 has ~2 decimal digits; blockwise scales keep it low.
        np.testing.assert_allclose(restored, x, rtol=0.07, atol=0.1)


def test_quantize_zero_block() -> None:
    x = np.zeros(512, dtype=np.float32)
    payload, scales = q.quantize_blocks(x)
    restored = q.dequantize_blocks(payload, scales, x.shape, x.dtype)
    np.testing.assert_array_equal(restored, x)


@pytest.mark.parametrize("wire", ["fp8", "int8", "int4"])
def test_reduce_quantized_matches_float_sum(wire) -> None:
    rng = np.random.default_rng(1)
    chunks = [rng.normal(size=(4, q.BLOCK)).astype(np.float32) for _ in range(3)]
    quantized = [q.quantize_blocks(c, wire=wire) for c in chunks]
    out_payload, out_scales = q.reduce_quantized(
        [p for p, _ in quantized], [s for _, s in quantized]
    )
    total = sum(
        q._decode_payload_np(p) * s[:, None] for p, s in quantized
    )
    restored = q._decode_payload_np(out_payload) * out_scales[:, None]
    if wire == "int4":
        # Analytic round-trip bound: one requant at out_scale resolution.
        bound = float(np.max(out_scales)) / 2 * 1.001
        assert float(np.max(np.abs(restored - total))) <= bound
    else:
        np.testing.assert_allclose(restored, total, rtol=0.07, atol=0.1)


@pytest.mark.parametrize("wire", ["fp8", "int8", "int4"])
def test_pack_unpack_roundtrip(wire) -> None:
    rng = np.random.default_rng(2)
    x = rng.normal(size=(5, q.BLOCK)).astype(np.float32)
    payload, scales = q.quantize_blocks(x.reshape(-1), wire=wire)
    buf = q.pack_arrays(payload, scales)
    payload2, scales2 = q.unpack_arrays(buf, payload.shape[0], wire=wire)
    assert payload2.dtype == payload.dtype
    np.testing.assert_array_equal(payload.view(np.uint8), payload2.view(np.uint8))
    np.testing.assert_array_equal(scales, scales2)


# -- pallas kernels (interpret mode on CPU) -----------------------------------


@pytest.mark.parametrize("wire", ["fp8", "int8"])
@pytest.mark.parametrize("n_blocks", [8, 1500])
def test_pallas_quantize_matches_numpy(wire, n_blocks) -> None:
    # n_blocks=8 is a single whole-dim tile; 1500 forces the ragged
    # 1024-row grid (partial final tile) the retiled kernels use for
    # arbitrary gradient sizes -- numeric proof that padded rows never
    # bleed into real rows' scales/payload (the lowering gate only proves
    # the shape compiles).
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    x = rng.normal(size=(n_blocks, q.BLOCK)).astype(np.float32) * 5
    payload_np, scales_np = q.quantize_blocks(x.reshape(-1), wire=wire)
    payload_pl, scales_pl = q.quantize_blocks_pallas(
        jnp.asarray(x), interpret=True, wire=wire
    )
    np.testing.assert_allclose(scales_pl, scales_np, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(payload_pl).astype(np.float32),
        payload_np.astype(np.float32),
        atol=1e-6,
    )
    restored = q.dequantize_blocks_pallas(payload_pl, scales_pl, interpret=True)
    np.testing.assert_allclose(np.asarray(restored), x, rtol=0.07, atol=0.1)


# -- quantized collectives over a real PG -------------------------------------


@pytest.mark.parametrize("world_size", [2, 4])
def test_allreduce_quantized_sum_avg(store_server, world_size) -> None:
    pgs = make_group(store_server, world_size)
    rng = np.random.default_rng(4)
    inputs = [
        [rng.normal(size=(40, 13)).astype(np.float32), rng.normal(size=300).astype(np.float32)]
        for _ in range(world_size)
    ]
    try:
        for op in (ReduceOp.SUM, ReduceOp.AVG):
            results = run_on_all(
                pgs, lambda pg, i: allreduce_quantized(inputs[i], op, pg).wait()
            )
            expected = [
                sum(inputs[r][idx] for r in range(world_size)) for idx in range(2)
            ]
            if op == ReduceOp.AVG:
                expected = [e / world_size for e in expected]
            for r in results:
                for idx in range(2):
                    assert r[idx].shape == expected[idx].shape
                    assert r[idx].dtype == expected[idx].dtype
                    # Two quantization passes: tolerance ~ 2x single pass.
                    np.testing.assert_allclose(
                        r[idx], expected[idx], rtol=0.2, atol=0.3
                    )
            # Bitwise identical across ranks.
            for idx in range(2):
                assert all(
                    r[idx].tobytes() == results[0][idx].tobytes() for r in results
                )
    finally:
        for pg in pgs:
            pg.shutdown()


def test_reduce_scatter_quantized(store_server) -> None:
    pgs = make_group(store_server, 2)
    rng = np.random.default_rng(5)
    inputs = [[rng.normal(size=1024).astype(np.float32)] for _ in range(2)]
    try:
        results = run_on_all(
            pgs,
            lambda pg, i: reduce_scatter_quantized(inputs[i], ReduceOp.SUM, pg).wait(),
        )
        total = inputs[0][0] + inputs[1][0]
        blocks = total.reshape(-1, q.BLOCK)
        # rank 0 gets blocks [0:2], rank 1 gets [2:4]
        for rank, result in enumerate(results):
            expected = blocks[rank * 2 : (rank + 1) * 2].reshape(-1)
            np.testing.assert_allclose(result[0], expected, rtol=0.2, atol=0.3)
    finally:
        for pg in pgs:
            pg.shutdown()


def test_manager_allreduce_quantized_path() -> None:
    """manager.allreduce(should_quantize=True) routes through the fp8 path."""
    from test_manager import make_manager, make_quorum
    from torchft_tpu.parallel.process_group import ProcessGroupDummy

    manager, client, _, _ = make_manager(pg=ProcessGroupDummy(), min_replica_size=1)
    client._quorum.return_value = make_quorum(replica_world_size=1, max_world_size=1)
    manager.start_quorum()
    x = np.linspace(-3, 3, 512, dtype=np.float32)
    out = manager.allreduce(x, should_quantize=True).wait()
    np.testing.assert_allclose(out, x, rtol=0.1, atol=0.1)


# -- int8 wire format (reference parity: fp8 on SM90+, int8 below) -----------


def test_default_wire_env(monkeypatch) -> None:
    monkeypatch.delenv(q.WIRE_DTYPE_ENV, raising=False)
    assert q.default_wire() == "fp8"
    monkeypatch.setenv(q.WIRE_DTYPE_ENV, "int8")
    assert q.default_wire() == "int8"
    payload, _ = q.quantize_blocks(np.ones(16, np.float32))
    assert payload.dtype == np.int8
    monkeypatch.setenv(q.WIRE_DTYPE_ENV, "fp4")
    with pytest.raises(ValueError, match="fp4"):
        q.default_wire()


def test_wire_of() -> None:
    assert q.wire_of(np.zeros(4, np.int8)) == "int8"
    assert q.wire_of(np.zeros(4, q._FP8)) == "fp8"
    assert q.wire_of(np.zeros(4, np.uint8)) == "int4"
    with pytest.raises(TypeError):
        q.wire_of(np.zeros(4, np.float32))


def test_allreduce_quantized_int8_wire(store_server) -> None:
    from torchft_tpu.parallel.collectives import allreduce_quantized

    pgs = make_group(store_server, 2)
    rng = np.random.default_rng(6)
    inputs = [[rng.normal(size=512).astype(np.float32)] for _ in range(2)]
    try:
        results = run_on_all(
            pgs,
            lambda pg, i: allreduce_quantized(
                inputs[i], ReduceOp.AVG, pg, wire_dtype="int8"
            ).wait(),
        )
        expected = (inputs[0][0] + inputs[1][0]) / 2
        for r in results:
            np.testing.assert_allclose(r[0], expected, rtol=0.1, atol=0.15)
        assert results[0][0].tobytes() == results[1][0].tobytes()
    finally:
        for pg in pgs:
            pg.shutdown()


def test_device_codec_int8_through_wire_allreduce(store_server) -> None:
    """A device codec built with wire='int8' flows through
    allreduce_quantized_wire end to end — the wire format is read from the
    payload dtype, not the env."""
    import jax.numpy as jnp

    from torchft_tpu.ops.quantization import make_tree_fp8_codec
    from torchft_tpu.parallel.collectives import allreduce_quantized_wire

    leaves = [jnp.linspace(-2, 2, 300, dtype=jnp.float32).reshape(30, 10)]
    quantize, dequantize = make_tree_fp8_codec(leaves, wire="int8")
    payload, scales = quantize(leaves)
    assert np.asarray(payload).dtype == np.int8

    pgs = make_group(store_server, 2)
    try:
        results = run_on_all(
            pgs,
            lambda pg, i: allreduce_quantized_wire(
                payload, scales, ReduceOp.AVG, pg
            ).wait(),
        )
        for out_payload, out_scales in results:
            assert out_payload.dtype == np.int8
            restored = dequantize(
                jnp.asarray(out_payload), jnp.asarray(out_scales)
            )
            np.testing.assert_allclose(
                np.asarray(restored[0]), np.asarray(leaves[0]), rtol=0.05, atol=0.05
            )
    finally:
        for pg in pgs:
            pg.shutdown()


def test_unpack_rejects_cross_format_buffer() -> None:
    """A peer that quantized with a different TPUFT_WIRE_DTYPE must be a
    hard error at decode, never a silent bit reinterpretation."""
    x = np.linspace(-1, 1, q.BLOCK, dtype=np.float32)
    payload, scales = q.quantize_blocks(x, wire="fp8")
    buf = q.pack_arrays(payload, scales)
    with pytest.raises(ValueError, match="wire format mismatch"):
        q.unpack_arrays(buf, payload.shape[0], wire="int8")
    with pytest.raises(ValueError, match="unknown wire format tag"):
        q.unpack_arrays(np.full(64, 255, np.uint8), 0)


def test_int4_pack_unpack_exact() -> None:
    """Nibble packing is lossless over the full [-7, 7] code space."""
    vals = np.tile(np.arange(-7, 8, dtype=np.int8), 35)[: 2 * q.BLOCK].reshape(
        2, q.BLOCK
    )
    packed = q._pack_int4_np(vals)
    assert packed.shape == (2, q.BLOCK // 2) and packed.dtype == np.uint8
    np.testing.assert_array_equal(q._unpack_int4_np(packed), vals)


def test_allreduce_quantized_int4_wire(store_server) -> None:
    """End-to-end int4 allreduce: half the wire bytes of int8, bitwise
    agreement across ranks, error within the 4-bit analytic bound."""
    from torchft_tpu.parallel.collectives import allreduce_quantized

    pgs = make_group(store_server, 2)
    rng = np.random.default_rng(7)
    inputs = [[rng.normal(size=512).astype(np.float32)] for _ in range(2)]
    p8, s8 = q.quantize_blocks(inputs[0][0], wire="int8")
    p4, s4 = q.quantize_blocks(inputs[0][0], wire="int4")
    assert p4.nbytes * 2 == p8.nbytes
    try:
        results = run_on_all(
            pgs,
            lambda pg, i: allreduce_quantized(
                inputs[i], ReduceOp.AVG, pg, wire_dtype="int4"
            ).wait(),
        )
        expected = (inputs[0][0] + inputs[1][0]) / 2
        # Per-element bound: input rounding (scale_i/2 each, averaged) +
        # the requant of the reduced chunk.
        bound = (float(np.max(s4)) + float(np.max(s4))) / 2 / 2 + float(
            np.max(s4)
        )
        for r in results:
            assert float(np.max(np.abs(r[0] - expected))) <= bound
        assert results[0][0].tobytes() == results[1][0].tobytes()
    finally:
        for pg in pgs:
            pg.shutdown()


def test_device_codec_int4_roundtrip_and_host_compat() -> None:
    """The jnp int4 device codec round-trips within the analytic bound and
    its packed payload decodes identically through the HOST kernels (one
    wire format across device/host paths)."""
    import jax.numpy as jnp

    from torchft_tpu.ops.quantization import (
        dequantize_blocks_device,
        make_tree_fp8_codec,
    )

    rng = np.random.default_rng(8)
    leaves = [
        rng.normal(size=(37, 11)).astype(np.float32),
        rng.normal(size=600).astype(np.float32) * 5,
    ]
    quantize, dequantize = make_tree_fp8_codec(
        [jnp.asarray(l) for l in leaves], wire="int4"
    )
    payload, scales = quantize([jnp.asarray(l) for l in leaves])
    assert np.dtype(payload.dtype) == np.uint8
    restored = dequantize(payload, scales)
    bound = float(np.max(np.asarray(scales))) / 2 * 1.001
    flat_in = np.concatenate([l.reshape(-1) for l in leaves])
    flat_out = np.concatenate([np.asarray(r).reshape(-1) for r in restored])
    assert float(np.max(np.abs(flat_out - flat_in))) <= bound

    # Host-side decode of the device payload matches the device decode.
    host = q.dequantize_blocks(
        np.asarray(payload), np.asarray(scales, dtype=np.float32),
        (flat_in.size,), np.float32,
    )
    dev = np.asarray(dequantize_blocks_device(payload, scales))[: flat_in.size]
    np.testing.assert_allclose(host, dev, rtol=0, atol=1e-7)


def test_device_codec_int4_through_wire_allreduce(store_server) -> None:
    """The packed-int4 device codec flows through allreduce_quantized_wire
    end to end (format read from the uint8 payload dtype)."""
    import jax.numpy as jnp

    from torchft_tpu.ops.quantization import make_tree_fp8_codec
    from torchft_tpu.parallel.collectives import allreduce_quantized_wire

    leaves = [jnp.linspace(-2, 2, 300, dtype=jnp.float32).reshape(30, 10)]
    quantize, dequantize = make_tree_fp8_codec(leaves, wire="int4")
    payload, scales = quantize(leaves)
    assert np.asarray(payload).dtype == np.uint8

    pgs = make_group(store_server, 2)
    try:
        results = run_on_all(
            pgs,
            lambda pg, i: allreduce_quantized_wire(
                payload, scales, ReduceOp.AVG, pg
            ).wait(),
        )
        for out_payload, out_scales in results:
            assert out_payload.dtype == np.uint8
            restored = dequantize(
                jnp.asarray(out_payload), jnp.asarray(out_scales)
            )
            # Both ranks contributed the identical tensor, so AVG is the
            # tensor itself up to two 4-bit roundings.
            bound = 2.0 * float(np.max(np.asarray(scales)))
            assert (
                float(np.max(np.abs(np.asarray(restored[0]) - np.asarray(leaves[0]))))
                <= bound
            )
        assert results[0][0].tobytes() == results[1][0].tobytes()
    finally:
        for pg in pgs:
            pg.shutdown()


# -- the leaf-layout codec: a leaf is quantized in the layout it has ----------

LEAF_KERNELS = (
    "quantize_leaf_pallas", "dequantize_leaf_pallas",
    "quantize_blocks_pallas", "dequantize_blocks_pallas",
)


@pytest.fixture
def interpreted_kernels(monkeypatch):
    """The TPU branches of the device codec on the CPU: ``on_tpu()`` answers
    yes and every Pallas kernel runs interpreted."""
    import functools

    monkeypatch.setattr(q, "on_tpu", lambda: True)
    for name in LEAF_KERNELS:
        monkeypatch.setattr(
            q, name, functools.partial(getattr(q, name), interpret=True)
        )


def _leaf_case(name: str):
    """(backup, local) leaves of one case; values of mixed magnitude."""
    import jax.numpy as jnp

    rng = np.random.default_rng(11)

    def pair(shape, dtype, scale=1.0):
        backup = jnp.asarray(rng.normal(0, scale, shape), dtype)
        local = jnp.asarray(rng.normal(0, scale, shape), dtype)
        return backup, local

    if name == "2d":
        pairs = [pair((64, 512), jnp.bfloat16)]
    elif name == "stacked-3d":  # three row tiles of 32, three chunks of one block
        pairs = [pair((2, 96, 768), jnp.bfloat16), pair((3, 32, 256), jnp.float32, 1e-3)]
    elif name == "merged-heads":  # 256-blocks span two (heads, 128) rows
        pairs = [pair((2, 64, 4, 128), jnp.bfloat16)]
    elif name == "one-row":  # no 32 rows: the flat path, whole blocks or not
        pairs = [pair((1024,), jnp.float32), pair((1, 256), jnp.bfloat16)]
    elif name == "zero-block":
        backup, local = pair((32, 768), jnp.float32)
        local = local.at[3, 256:512].set(backup[3, 256:512])  # difference 0
        pairs = [(backup, local)]
    elif name == "larger-tiles-first":  # the second leaf's run leads the wire
        pairs = [pair((32, 256), jnp.bfloat16), pair((128, 2048), jnp.bfloat16)]
    elif name == "flat-tail":
        pairs = [
            pair((7, 100), jnp.bfloat16),
            pair((32, 512), jnp.bfloat16),
            pair((2, 32, 256), jnp.float32, 30.0),
            pair((5,), jnp.float32, 1e-2),
        ]
    else:
        raise KeyError(name)
    return [b for b, _ in pairs], [l for _, l in pairs]


LEAF_CASES = [
    "2d", "stacked-3d", "merged-heads", "one-row", "zero-block",
    "larger-tiles-first", "flat-tail",
]


def _wire_index(view):
    """Where block (l, row, segment) of a leaf sits in its run of the wire,
    written out by hand: tiles row-major over (leading, row tile, chunk),
    and inside a tile all rows of its first segment, then the next."""
    *lead, rows, cols = view.shape
    segments = cols // q.BLOCK
    tiles, chunks = rows // view.tile_rows, segments // view.chunk_segments
    index = np.empty((int(np.prod(lead, dtype=int)), rows, segments), np.int64)
    for l in range(index.shape[0]):
        for r in range(rows):
            for seg in range(segments):
                tile = (l * tiles + r // view.tile_rows) * chunks + seg // view.chunk_segments
                index[l, r, seg] = (
                    (tile * view.chunk_segments + seg % view.chunk_segments)
                    * view.tile_rows + r % view.tile_rows
                )
    return index.reshape(-1)


@pytest.mark.parametrize("impl", ["jnp", "pallas-interpret"])
@pytest.mark.parametrize("wire", ["fp8", "int8"])
@pytest.mark.parametrize("case", LEAF_CASES)
def test_leaf_layout_codec_matches_flat_blocks(case, wire, impl, request) -> None:
    """The tree codec reading the leaves as they lie gives, block for block
    and bit for bit, the scales, payload and decoded leaves of the flat
    device codec on the flattened float32 pseudogradient (the formulation it
    replaces), which are the host codec's ``quantize_blocks`` to a rounding
    of the scale (XLA divides by the format's maximum through a reciprocal).
    Only where a block sits on the wire differs: a leaf of 32-row tiles of
    whole blocks has a run of its own, larger tiles first, the rest follow
    flat."""
    import jax
    import jax.numpy as jnp

    if impl == "pallas-interpret":
        request.getfixturevalue("interpreted_kernels")
    backup, local = _leaf_case(case)
    quantize, dequantize = q.make_tree_fp8_codec(backup, wire=wire)
    payload, scales = quantize(backup, local)

    # The flat formulation, jitted as local_sgd.py's program was, a leaf.
    def flat_codec(b, l):
        flat = (b.astype(jnp.float32) - l.astype(jnp.float32)).reshape(-1)
        blocks, block_scales = q.quantize_blocks_device(flat, wire=wire)
        return flat, blocks, block_scales, q.dequantize_blocks_device(blocks, block_scales)

    # Larger tiles first, written out here as the codec's rule is.
    views = q.tree_codec_views([b.shape for b in backup], wire)
    wire_order = sorted(
        (i for i, v in enumerate(views) if v is not None),
        key=lambda i: -views[i].tile_blocks,
    )
    assert [(i, at) for i, _, at in q.tree_codec_runs([b.shape for b in backup], wire)] == [
        (i, sum(views[k].n_blocks for k in wire_order[:n])) for n, i in enumerate(wire_order)
    ]
    at = 0
    for i in wire_order:
        flat, blocks, block_scales, values = jax.jit(flat_codec)(backup[i], local[i])
        index = at + _wire_index(views[i])
        np.testing.assert_array_equal(np.asarray(scales)[index], np.asarray(block_scales))
        np.testing.assert_array_equal(
            np.asarray(payload).astype(np.float32)[index],
            np.asarray(blocks).astype(np.float32),
        )
        host_scales = q.quantize_blocks(np.asarray(flat), wire=wire)[1]
        np.testing.assert_allclose(np.asarray(block_scales), host_scales, rtol=2e-7)
        at += views[i].n_blocks
    tail = [i for i, v in enumerate(views) if v is None]
    assert (case in ("one-row", "flat-tail")) == bool(tail)
    if tail:
        flat, blocks, block_scales, tail_values = jax.jit(flat_codec)(
            jnp.concatenate([backup[i].astype(jnp.float32).reshape(-1) for i in tail]),
            jnp.concatenate([local[i].astype(jnp.float32).reshape(-1) for i in tail]),
        )
        np.testing.assert_array_equal(np.asarray(scales)[at:], np.asarray(block_scales))
        np.testing.assert_array_equal(
            np.asarray(payload).astype(np.float32)[at:],
            np.asarray(blocks).astype(np.float32),
        )
        at += blocks.shape[0]
    assert payload.shape == (at, q.BLOCK) and scales.shape == (at,)
    if case == "zero-block":
        zero = _wire_index(views[0])[3 * 3 + 1]  # row 3, second block of three
        assert np.asarray(scales)[zero] == 1.0
        assert not np.asarray(payload).astype(np.float32)[zero].any()

    restored = dequantize(payload, scales)
    for i, (leaf, got) in enumerate(zip(backup, restored)):
        assert got.shape == leaf.shape and got.dtype == leaf.dtype
        if i in tail:
            continue
        values = jax.jit(flat_codec)(backup[i], local[i])[3]
        want = values[: leaf.size].reshape(leaf.shape).astype(leaf.dtype)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    offset = 0
    for i in tail:
        leaf = backup[i]
        want = tail_values[offset : offset + leaf.size].reshape(leaf.shape)
        np.testing.assert_array_equal(
            np.asarray(restored[i]), np.asarray(want.astype(leaf.dtype))
        )
        offset += leaf.size


@pytest.mark.parametrize("tile", [(32, 1), (32, 4), (96, 2)], ids=str)
@pytest.mark.parametrize("with_minus", [True, False])
def test_leaf_kernels_fill_their_run_of_the_payload(tile, with_minus) -> None:
    """The kernels at tiles other than the tuned one (the sweep's
    parameters), with leading dimensions on the grid and a run that starts
    past another leaf's: the blocks land in wire order from ``base_block``
    on, every other row of the payload is left as it was, and the pair
    round-trips bit for bit as the flat kernels do."""
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    shape = (3, 96, 1024)
    view = q.leaf_block_view(shape, *tile)
    assert (view.tile_rows, view.chunk_segments) == tile and view.shape == shape
    x = jnp.asarray(rng.normal(0, 4, shape), jnp.bfloat16)
    y = jnp.asarray(rng.normal(0, 4, shape), jnp.bfloat16) if with_minus else None
    base = 2 * view.tile_blocks
    total = base + view.n_blocks + 64
    before = jnp.full((total, q.BLOCK), 0.5, jnp.float8_e4m3fn)
    payload, scales = q.quantize_leaf_pallas(
        x, y, view, total, base, before, interpret=True, wire="fp8"
    )
    assert scales.shape == (view.n_blocks // view.tile_blocks, *tile)
    got = np.asarray(payload).astype(np.float32)
    assert (got[:base] == 0.5).all() and (got[base + view.n_blocks :] == 0.5).all()

    data = x.astype(jnp.float32) - (y.astype(jnp.float32) if with_minus else 0)
    flat_payload, flat_scales = q.quantize_blocks_pallas(
        data.reshape(-1, q.BLOCK), interpret=True, wire="fp8"
    )
    index = _wire_index(view)
    np.testing.assert_array_equal(
        np.asarray(scales).transpose(0, 2, 1).reshape(-1)[index], np.asarray(flat_scales)
    )
    np.testing.assert_array_equal(
        got[base + index], np.asarray(flat_payload).astype(np.float32)
    )
    values = q.dequantize_leaf_pallas(payload, scales, view, base, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(values).reshape(-1, q.BLOCK),
        np.asarray(q.dequantize_blocks_pallas(flat_payload, flat_scales, interpret=True)),
    )
    np.testing.assert_allclose(np.asarray(values), np.asarray(data), rtol=0.07, atol=0.1)
    # The jnp order (the codec off the TPU) is the kernels' order.
    np.testing.assert_array_equal(
        np.asarray(q._wire_order(data, view)), np.asarray(data).reshape(-1, q.BLOCK)[np.argsort(index)]
    )
    np.testing.assert_array_equal(
        np.asarray(q._leaf_order(q._wire_order(data, view), view)), np.asarray(data)
    )


@pytest.mark.parametrize(
    "shape, view",
    [
        ((2, 4096, 14336), ((2, 4096, 14336), 256, 8)),
        ((2, 4096, 8, 128), ((2, 4096, 1024), 512, 4)),
        ((2, 32, 128, 4096), ((2, 32, 128, 4096), 128, 8)),
        ((32768, 4096), ((32768, 4096), 256, 8)),
        ((96, 768), ((96, 768), 32, 1)),
        ((2, 4096, 100), None),  # a row of 409,600 is 1600 blocks, but 2 rows are no tile
        ((64, 4, 64), ((64, 256), 64, 1)),
        ((4096,), None),
        ((3, 256), None),
        ((7, 100), None),
        ((0, 256), None),
        ((), None),
    ],
)
def test_leaf_block_view(shape, view) -> None:
    got = q.leaf_block_view(shape)
    assert got == (view and q.LeafView(*view))
    if got:
        assert got.n_blocks * q.BLOCK == int(np.prod(shape))
        assert got.n_blocks == int(np.prod(got.grid)) * got.tile_blocks
    elements = q.tree_codec_elements([np.zeros(shape, np.float32)], wire="fp8")
    size = int(np.prod(shape))
    assert elements == ({"leaf": size, "flat": 0} if view else {"leaf": 0, "flat": size})
    # Packed int4 has no leaf-layout kernel: everything rides the flat path.
    assert q.tree_codec_elements([np.zeros(shape, np.float32)], wire="int4") == {
        "leaf": 0, "flat": size
    }
