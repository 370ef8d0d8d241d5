"""Block quantization (fp8/int8/int4) for bandwidth-compressed collectives.

Role-equivalent of the reference's Triton kernels
(/root/reference/torchft/quantization.py): rowwise/blockwise max-abs scales,
8-bit payloads, and a fused dequantize-reduce-requantize used inside the
quantized allreduce. Like the reference — which emits fp8e4nv on SM90+ and
int8 on older GPUs — the wire formats share one layout:

- ``"fp8"`` (float8_e4m3): wider per-block dynamic range;
- ``"int8"``: symmetric round-to-nearest, finer resolution near the block
  max and universally fast integer hardware;
- ``"int4"`` (beyond reference): symmetric [-7, 7] nibbles packed two per
  byte — HALF the wire bytes of the 8-bit formats. The cross-DCN outer
  syncs (DiLoCo pseudogradients) are the intended user; at 4 bits the
  per-block resolution is coarse, so it is opt-in, never the default.

Select per call or globally via ``TPUFT_WIRE_DTYPE``. The TPU build
provides a numpy/jnp implementation (works everywhere; used for the
host-side TCP collective wire format) and Pallas TPU kernels for the
device-side hot path (``*_pallas``), exercised in interpret mode on CPU
tests and compiled on real TPU. int4 uses the jnp device path on every
backend (nibble packing is plain XLA integer ops; no Pallas kernel).

Layout: arrays are flattened, padded to a multiple of ``block``, and viewed
as ``(n_blocks, block)``; each block carries one float32 scale. (The tree
codec of the device pipelines cuts the same blocks out of a leaf where it
lies, without flattening it: ``make_tree_fp8_codec``; only where a block
sits in the payload differs.) The wire
payload is ``scales || payload``, mirroring the reference's interleaved
[scales||payload] slices. The 8-bit formats are 1 byte/element and int4
is a packed uint8 ``(n_blocks, block // 2)``; ``payload_cols()`` gives the
per-block wire width, and the payload dtype rides in the arrays so every
consumer (dequantize, reduce, unpack) dispatches on it.
"""

from __future__ import annotations

import os
from typing import NamedTuple, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np

from torchft_tpu.utils.platform import on_tpu

__all__ = [
    "BLOCK",
    "FP8_MAX",
    "INT8_MAX",
    "INT4_MAX",
    "WIRE_DTYPE_ENV",
    "default_wire",
    "wire_of",
    "payload_cols",
    "quantize_blocks",
    "dequantize_blocks",
    "reduce_quantized",
    "pack_arrays",
    "unpack_arrays",
    "quantize_blocks_pallas",
    "dequantize_blocks_pallas",
    "LeafView",
    "leaf_block_view",
    "quantize_leaf_pallas",
    "dequantize_leaf_pallas",
]

BLOCK = 256
FP8_MAX = 448.0  # float8_e4m3fn dynamic range
INT8_MAX = 127.0
INT4_MAX = 7.0  # symmetric nibbles: [-7, 7], -8 never produced
_FP8 = ml_dtypes.float8_e4m3fn
WIRE_DTYPE_ENV = "TPUFT_WIRE_DTYPE"

# int4's payload is nibble-packed into uint8 — a dtype neither 8-bit
# format uses, so dtype-dispatch (wire_of) stays unambiguous.
_WIRE_NP_DTYPES = {
    "fp8": np.dtype(_FP8),
    "int8": np.dtype(np.int8),
    "int4": np.dtype(np.uint8),
}
_WIRE_QMAX = {"fp8": FP8_MAX, "int8": INT8_MAX, "int4": INT4_MAX}


def payload_cols(wire: str, block: int = BLOCK) -> int:
    """Per-block wire payload width in bytes (int4 packs two per byte)."""
    if wire == "int4" and block % 2:
        raise ValueError(f"int4 requires an even block size, got {block}")
    return block // 2 if wire == "int4" else block


def _pack_int4_np(v: np.ndarray) -> np.ndarray:
    """(n, block) int8 in [-7, 7] -> (n, block//2) uint8, low nibble first."""
    u = v.astype(np.uint8) & 0xF
    return (u[:, 0::2] | (u[:, 1::2] << 4)).astype(np.uint8)


def _unpack_int4_np(p: np.ndarray) -> np.ndarray:
    """Inverse of :func:`_pack_int4_np` with 4-bit sign extension."""
    lo = p & 0xF
    hi = (p >> 4) & 0xF
    out = np.empty((p.shape[0], p.shape[1] * 2), np.uint8)
    out[:, 0::2] = lo
    out[:, 1::2] = hi
    return ((out.astype(np.int16) ^ 8) - 8).astype(np.int8)


def _resolve_wire(wire: "Optional[str]") -> str:
    """Validates an explicit wire choice; None means the env default."""
    if wire is None:
        return default_wire()
    if wire not in _WIRE_NP_DTYPES:
        raise ValueError(
            f"wire={wire!r} is not one of {sorted(_WIRE_NP_DTYPES)}"
        )
    return wire


def default_wire() -> str:
    """The process-wide wire format: ``TPUFT_WIRE_DTYPE`` or ``"fp8"``."""
    wire = os.environ.get(WIRE_DTYPE_ENV, "fp8")
    if wire not in _WIRE_NP_DTYPES:
        raise ValueError(
            f"{WIRE_DTYPE_ENV}={wire!r} is not one of {sorted(_WIRE_NP_DTYPES)}"
        )
    return wire


def wire_of(payload) -> str:
    """Wire format of an existing payload array, by dtype."""
    dtype = np.dtype(payload.dtype)
    for name, np_dtype in _WIRE_NP_DTYPES.items():
        if dtype == np_dtype:
            return name
    raise TypeError(f"array dtype {dtype} is not a known wire payload format")


def _as_blocks(flat: np.ndarray, block: int = BLOCK) -> np.ndarray:
    pad = (-flat.size) % block
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, dtype=flat.dtype)])
    return flat.reshape(-1, block)


def quantize_blocks(
    array: np.ndarray, block: int = BLOCK, wire: Optional[str] = None
) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (payload (n_blocks, payload_cols(wire)), scales f32
    (n_blocks,)) — 1 byte/element for fp8/int8, nibble-packed uint8 at
    block//2 bytes for int4."""
    wire = _resolve_wire(wire)
    flat = np.ascontiguousarray(array).astype(np.float32).reshape(-1)
    blocks = _as_blocks(flat, block)
    maxabs = np.max(np.abs(blocks), axis=1)
    scales = np.where(maxabs > 0, maxabs / _WIRE_QMAX[wire], 1.0).astype(np.float32)
    scaled = blocks / scales[:, None]
    if wire == "int8":
        scaled = np.rint(scaled)
    elif wire == "int4":
        payload_cols(wire, block)  # validates even block
        return _pack_int4_np(np.rint(scaled).astype(np.int8)), scales
    payload = scaled.astype(_WIRE_NP_DTYPES[wire])
    return payload, scales


def _decode_payload_np(payload: np.ndarray) -> np.ndarray:
    """Payload -> f32 block values (unpacks int4 by dtype dispatch)."""
    if payload.dtype == np.uint8:
        payload = _unpack_int4_np(payload)
    return payload.astype(np.float32)


def dequantize_blocks(
    payload: np.ndarray, scales: np.ndarray, shape: Tuple[int, ...], dtype: np.dtype
) -> np.ndarray:
    """Inverse of :func:`quantize_blocks` (drops padding)."""
    blocks = _decode_payload_np(payload) * scales[:, None]
    size = int(np.prod(shape))
    return blocks.reshape(-1)[:size].reshape(shape).astype(dtype)


def reduce_quantized(
    payloads: Sequence[np.ndarray], scales: Sequence[np.ndarray]
) -> Tuple[np.ndarray, np.ndarray]:
    """Fused dequantize-sum-requantize over per-rank quantized chunks
    (reference fused_reduce_fp8): accumulates in float32, emits a fresh
    payload + scales for the reduced result in the inputs' wire format."""
    wire = wire_of(payloads[0])
    acc = _decode_payload_np(payloads[0]) * scales[0][:, None]
    for payload, scale in zip(payloads[1:], scales[1:]):
        acc += _decode_payload_np(payload) * scale[:, None]
    maxabs = np.max(np.abs(acc), axis=1)
    out_scales = np.where(maxabs > 0, maxabs / _WIRE_QMAX[wire], 1.0).astype(
        np.float32
    )
    out = acc / out_scales[:, None]
    if wire == "int8":
        out = np.rint(out)
    elif wire == "int4":
        return _pack_int4_np(np.rint(out).astype(np.int8)), out_scales
    out_payload = out.astype(_WIRE_NP_DTYPES[wire])
    return out_payload, out_scales


_WIRE_TAGS = {"fp8": 0, "int8": 1, "int4": 2}
_TAG_WIRES = {tag: name for name, tag in _WIRE_TAGS.items()}

# One leading byte identifies the payload format on the wire. The 8-bit
# formats are byte-identical in size, so without it a cross-rank
# TPUFT_WIRE_DTYPE disagreement would decode peers' fp8 bits as int8 and
# silently corrupt the reduction; the tag turns that into a hard error.
WIRE_HEADER_BYTES = 1


def pack_arrays(payload: np.ndarray, scales: np.ndarray) -> np.ndarray:
    """Packs [format tag || scales || payload] into one uint8 wire buffer."""
    tag = np.array([_WIRE_TAGS[wire_of(payload)]], dtype=np.uint8)
    return np.concatenate(
        [tag,
         scales.astype(np.float32).view(np.uint8).reshape(-1),
         payload.view(np.uint8).reshape(-1)]
    )


def unpack_arrays(
    buf: np.ndarray, n_blocks: int, block: int = BLOCK, wire: Optional[str] = None
) -> Tuple[np.ndarray, np.ndarray]:
    """Inverse of :func:`pack_arrays`. The embedded format tag is
    authoritative; passing ``wire`` asserts the peer used the expected
    format (raising on cross-rank TPUFT_WIRE_DTYPE disagreement)."""
    tag_wire = _TAG_WIRES.get(int(buf[0]))
    if tag_wire is None:
        raise ValueError(f"unknown wire format tag {int(buf[0])} in buffer")
    if wire is not None and wire != tag_wire:
        raise ValueError(
            f"wire format mismatch: peer sent {tag_wire!r}, this rank expects "
            f"{wire!r} — TPUFT_WIRE_DTYPE must agree across all replicas"
        )
    body = buf[WIRE_HEADER_BYTES:]
    scale_bytes = n_blocks * 4
    scales = body[:scale_bytes].view(np.float32).copy()
    cols = payload_cols(tag_wire, block)
    payload = (
        body[scale_bytes : scale_bytes + n_blocks * cols]
        .view(_WIRE_NP_DTYPES[tag_wire])
        .reshape(n_blocks, cols)
        .copy()
    )
    return payload, scales


# ---------------------------------------------------------------------------
# Pallas TPU kernels (device-side hot path)
# ---------------------------------------------------------------------------

# Grid tile height shared by the paired quantize/dequantize kernels (they
# must stay in sync — a mismatch silently changes the partial-final-tile
# shape between the two directions). Rows are independent, so the limits
# are VMEM (1024 x 256 f32 = 1 MB/tile, double-buffered — well inside the
# ~16 MB budget) and Mosaic tiling (1024 is a multiple of the 8-bit
# payload's 32-row tile; a smaller n_blocks rides whole-dim via min()).
# The original 8-row tiles made a 256 MB codec run a 32k-step grid of
# per-step overhead; 1024-row tiles cut the grid 128x
# (scripts/codec_block_sweep.py is the sweep).
_ROWS_PER_TILE = 1024


def quantize_blocks_pallas(
    x,
    block: int = BLOCK,
    interpret: bool = False,
    wire: Optional[str] = None,
    rows_per_tile: Optional[int] = None,
):
    """Device-side blockwise 8-bit quantization (fp8 or int8).

    ``x``: float array, flattened/padded by the caller to (n_blocks, block).
    Returns (payload, scales f32). One grid row per block tile keeps the
    VPU busy while scales stay in SMEM-sized slices. ``rows_per_tile``
    overrides the tuned grid tile height (:data:`_ROWS_PER_TILE`) — the
    free parameter ``scripts/codec_block_sweep.py`` sweeps on-chip.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    wire = _resolve_wire(wire)
    if wire == "int4":
        raise ValueError(
            "int4 has no Pallas kernel — use quantize_blocks_device (jnp path)"
        )
    qmax = _WIRE_QMAX[wire]
    out_dtype = jnp.int8 if wire == "int8" else jnp.float8_e4m3fn
    n_blocks = x.shape[0]
    rows_per_tile = min(
        n_blocks, rows_per_tile if rows_per_tile else _ROWS_PER_TILE
    )

    def kernel(x_ref, payload_ref, scales_ref):
        block_data = x_ref[:].astype(jnp.float32)
        maxabs = jnp.max(jnp.abs(block_data), axis=1, keepdims=True)
        scale = jnp.where(maxabs > 0, maxabs / qmax, 1.0)
        scales_ref[:] = scale
        scaled = block_data / scale
        if wire == "int8":
            scaled = jnp.round(scaled)
        payload_ref[:] = scaled.astype(out_dtype)

    grid = ((n_blocks + rows_per_tile - 1) // rows_per_tile,)
    payload, scales = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows_per_tile, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows_per_tile, block), lambda i: (i, 0)),
            # Scales ride as a (n_blocks, 1) column so the block layout obeys
            # TPU tiling (rank-1 dynamic slices are not 128-aligned here).
            pl.BlockSpec((rows_per_tile, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, block), out_dtype),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return payload, scales.reshape(n_blocks)


def dequantize_blocks_pallas(
    payload, scales, interpret: bool = False, rows_per_tile: Optional[int] = None
):
    """Device-side blockwise fp8/int8 dequantization to float32.
    ``rows_per_tile`` as in :func:`quantize_blocks_pallas` (the paired
    kernels need not share a height — the wire format is tile-agnostic)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if payload.dtype == jnp.uint8:
        raise ValueError(
            "packed int4 has no Pallas kernel — use dequantize_blocks_device"
        )
    n_blocks, block = payload.shape
    rows_per_tile = min(
        n_blocks, rows_per_tile if rows_per_tile else _ROWS_PER_TILE
    )

    def kernel(payload_ref, scales_ref, out_ref):
        out_ref[:] = payload_ref[:].astype(jnp.float32) * scales_ref[:]

    grid = ((n_blocks + rows_per_tile - 1) // rows_per_tile,)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows_per_tile, block), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_tile, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows_per_tile, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks, block), jnp.float32),
        interpret=interpret,
    )(payload, scales.reshape(n_blocks, 1))


# The leaf-layout kernels: a float array is read as ``(..., rows, cols)`` in
# the layout it has, with ``cols`` a multiple of BLOCK, so that a block is
# BLOCK consecutive values of a row (exactly the block a row-major flatten
# forms), and no float32 copy of it is made outside VMEM. A grid step covers a
# tile of ``tile_rows`` rows by ``chunk_segments`` blocks and reads or writes
# that tile's blocks as one run of rows of the fragment's ``(n_blocks, BLOCK)``
# payload, segment by segment, so the payload is never laid out again either.
# Both are powers of two (rows in steps of the 8-bit payload's 32-row tile),
# which keeps every later leaf's run aligned behind the earlier ones. About
# _LEAF_TILE_VALUES values a step: measured, scripts/codec_block_sweep.py and
# PERF.md section 5.
_LEAF_TILE_VALUES = 512 * 1024
_LEAF_CHUNK_SEGMENTS = 8
_LEAF_MIN_ROWS = 32


class LeafView(NamedTuple):
    """How one leaf rides the wire in its own layout: the shape ``(..., rows,
    cols)`` it is read under, and the tile of a grid step. The tile's
    ``tile_rows * chunk_segments`` blocks are consecutive on the wire,
    segment-major (all rows of its first block column, then the next): an
    order that is a function of the leaf's shape alone, so every replica
    forms it alike."""

    shape: Tuple[int, ...]
    tile_rows: int
    chunk_segments: int

    @property
    def tile_blocks(self) -> int:
        return self.tile_rows * self.chunk_segments

    @property
    def n_blocks(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64)) // BLOCK

    @property
    def grid(self) -> Tuple[int, ...]:
        *lead, rows, cols = self.shape
        return (*lead, rows // self.tile_rows, cols // (self.chunk_segments * BLOCK))


def leaf_block_view(
    shape: Sequence[int],
    tile_rows: Optional[int] = None,
    chunk_segments: Optional[int] = None,
) -> Optional[LeafView]:
    """The :class:`LeafView` of an array of ``shape``: ``cols`` is the product
    of the fewest trailing dimensions that is a multiple of BLOCK (so no
    block straddles a row; a reshape to the view moves nothing unless it
    merges trailing dimensions), and ``rows``, the dimension before them, is
    a multiple of the payload's 32-row tile. ``None`` where the shape gives
    neither (the flat path takes the leaf). ``tile_rows`` and
    ``chunk_segments`` override the tuned tile (the sweep's parameters)."""
    if 0 in shape:
        return None
    cols = 1
    for k in range(len(shape) - 1, 0, -1):
        cols *= int(shape[k])
        if cols % BLOCK == 0:
            break
    else:
        return None
    rows = int(shape[k - 1])
    if rows % _LEAF_MIN_ROWS:
        return None
    segments = cols // BLOCK
    if not chunk_segments:
        chunk_segments = 1
        while chunk_segments < _LEAF_CHUNK_SEGMENTS and segments % (2 * chunk_segments) == 0:
            chunk_segments *= 2
    if not tile_rows:
        most = max(_LEAF_MIN_ROWS, _LEAF_TILE_VALUES // (chunk_segments * BLOCK))
        tile_rows = _LEAF_MIN_ROWS
        while 2 * tile_rows <= most and rows % (2 * tile_rows) == 0:
            tile_rows *= 2
    return LeafView(
        tuple(int(d) for d in shape[: k - 1]) + (rows, cols), tile_rows, chunk_segments
    )


def _leaf_specs(view: LeafView, base_block: int):
    """BlockSpecs of a leaf-layout call: the leaf's tile, the tile's run of
    payload rows (``base_block``: the leaf's first block on the wire) and
    the tile's scales."""
    from jax.experimental import pallas as pl

    grid = view.grid
    lead = len(grid) - 2
    base = base_block // view.tile_blocks

    def step(*at):  # the grid step's number, row-major over the grid
        n = 0
        for index, size in zip(at, grid):
            n = n * size + index
        return n

    values = pl.BlockSpec(
        (None,) * lead + (view.tile_rows, view.chunk_segments * BLOCK), lambda *at: at
    )
    payload = pl.BlockSpec((view.tile_blocks, BLOCK), lambda *at: (base + step(*at), 0))
    scales = pl.BlockSpec(
        (None, view.tile_rows, view.chunk_segments), lambda *at: (step(*at), 0, 0)
    )
    return values, payload, scales


def quantize_leaf_pallas(
    x,
    y,
    view: LeafView,
    payload_blocks: int,
    base_block: int = 0,
    payload=None,
    interpret: bool = False,
    wire: Optional[str] = None,
):
    """Blockwise 8-bit quantization (fp8 or int8) of ``x - y`` (of ``x``
    where ``y`` is None), formed in float32 in VMEM from operands of
    ``view.shape`` and any float dtype, read as they lie. The leaf's blocks
    land in rows ``[base_block, base_block + view.n_blocks)`` of the
    ``(payload_blocks, BLOCK)`` payload: of ``payload``, updated in place,
    or of a new buffer whose other rows are left for later calls to fill.
    Returns it and float32 scales ``(steps, tile_rows, chunk_segments)``;
    each block's scale and values have :func:`quantize_blocks_pallas`'
    bits."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    wire = _resolve_wire(wire)
    if wire == "int4":
        raise ValueError("int4 has no Pallas kernel: it rides the flat jnp path")
    qmax = _WIRE_QMAX[wire]
    out_dtype = jnp.int8 if wire == "int8" else jnp.float8_e4m3fn
    rows, segments = view.tile_rows, view.chunk_segments
    operands = [x] if y is None else [x, y]

    def kernel(x_ref, *refs):
        y_ref, (payload_ref, scales_ref) = None if y is None else refs[0], refs[-2:]
        for j in range(segments):
            seg = slice(j * BLOCK, (j + 1) * BLOCK)
            data = x_ref[:, seg].astype(jnp.float32)
            if y_ref is not None:
                data = data - y_ref[:, seg].astype(jnp.float32)
            maxabs = jnp.max(jnp.abs(data), axis=1, keepdims=True)
            scale = jnp.where(maxabs > 0, maxabs / qmax, 1.0)
            scales_ref[:, j : j + 1] = scale
            scaled = data / scale
            if wire == "int8":
                scaled = jnp.round(scaled)
            payload_ref[j * rows : (j + 1) * rows, :] = scaled.astype(out_dtype)

    values_spec, payload_spec, scales_spec = _leaf_specs(view, base_block)
    in_specs = [values_spec for _ in operands]
    aliases = {}
    if payload is not None:
        # The payload so far rides through untouched: it is not fetched, and
        # the output IS its buffer.
        aliases = {len(operands): 0}
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(payload)
    steps = int(np.prod(view.grid))
    return pl.pallas_call(
        kernel,
        grid=view.grid,
        in_specs=in_specs,
        out_specs=[payload_spec, scales_spec],
        out_shape=[
            jax.ShapeDtypeStruct((payload_blocks, BLOCK), out_dtype),
            jax.ShapeDtypeStruct((steps, rows, segments), jnp.float32),
        ],
        input_output_aliases=aliases,
        interpret=interpret,
    )(*operands)


def dequantize_leaf_pallas(
    payload, scales, view: LeafView, base_block: int = 0, interpret: bool = False
):
    """Inverse of :func:`quantize_leaf_pallas`: float32 values of
    ``view.shape`` from the leaf's rows of the whole payload, read where
    they lie, and its scales ``(steps, tile_rows, chunk_segments)`` (the
    caller rounds to the leaf's dtype)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if payload.dtype == jnp.uint8:
        raise ValueError("packed int4 has no Pallas kernel: it rides the flat jnp path")
    rows, segments = view.tile_rows, view.chunk_segments

    def kernel(payload_ref, scales_ref, out_ref):
        for j in range(segments):
            out_ref[:, j * BLOCK : (j + 1) * BLOCK] = (
                payload_ref[j * rows : (j + 1) * rows, :].astype(jnp.float32)
                * scales_ref[:, j : j + 1]
            )

    values_spec, payload_spec, scales_spec = _leaf_specs(view, base_block)
    return pl.pallas_call(
        kernel,
        grid=view.grid,
        in_specs=[payload_spec, scales_spec],
        out_specs=values_spec,
        out_shape=jax.ShapeDtypeStruct(view.shape, jnp.float32),
        interpret=interpret,
    )(payload, scales)


def quantize_blocks_device(x, block: int = BLOCK, wire: Optional[str] = None):
    """Device-side quantization of a flat array: pads to a block multiple,
    returns (payload (n_blocks, payload_cols(wire)), scales f32
    (n_blocks,)). Uses the Pallas kernel on TPU (fp8/int8), a jitted jnp
    path elsewhere and for packed int4."""
    import jax
    import jax.numpy as jnp

    wire = _resolve_wire(wire)
    flat = x.reshape(-1)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, dtype=flat.dtype)])
    blocks = flat.reshape(-1, block).astype(jnp.float32)
    if on_tpu() and wire != "int4":
        return quantize_blocks_pallas(blocks, block, wire=wire)
    maxabs = jnp.max(jnp.abs(blocks), axis=1)
    scales = jnp.where(maxabs > 0, maxabs / _WIRE_QMAX[wire], 1.0).astype(
        jnp.float32
    )
    scaled = blocks / scales[:, None]
    if wire == "int8":
        scaled = jnp.round(scaled)
    elif wire == "int4":
        # Nibble-pack on device: plain XLA integer ops, no Pallas kernel.
        u = jnp.round(scaled).astype(jnp.int8).astype(jnp.uint8) & 0xF
        return (u[:, 0::2] | (u[:, 1::2] << 4)).astype(jnp.uint8), scales
    payload = scaled.astype(jnp.int8 if wire == "int8" else jnp.float8_e4m3fn)
    return payload, scales


def dequantize_blocks_device(payload, scales):
    """Device-side dequantization to a flat f32 array (padding retained)."""
    import jax
    import jax.numpy as jnp

    if payload.dtype == jnp.uint8:  # packed int4: unpack with sign extension
        lo = payload & 0xF
        hi = (payload >> 4) & 0xF
        both = jnp.stack([lo, hi], axis=-1).reshape(payload.shape[0], -1)
        vals = (both.astype(jnp.int16) ^ 8) - 8
        out = vals.astype(jnp.float32) * scales[:, None]
    elif on_tpu():
        out = dequantize_blocks_pallas(payload, scales)
    else:
        out = payload.astype(jnp.float32) * scales[:, None]
    return out.reshape(-1)


def _wire_order(values, view: LeafView):
    """``values`` of ``view.shape`` as the leaf's ``(n_blocks, BLOCK)`` run
    of the wire: tile by tile, and inside a tile segment-major."""
    tiles, chunks = view.grid[-2:]
    return (
        values.reshape(-1, tiles, view.tile_rows, chunks, view.chunk_segments, BLOCK)
        .transpose(0, 1, 3, 4, 2, 5)
        .reshape(-1, BLOCK)
    )


def _leaf_order(blocks, view: LeafView):
    """Inverse of :func:`_wire_order`: ``(n_blocks, BLOCK)`` to ``view.shape``."""
    tiles, chunks = view.grid[-2:]
    return (
        blocks.reshape(-1, tiles, chunks, view.chunk_segments, view.tile_rows, BLOCK)
        .transpose(0, 1, 4, 2, 3, 5)
        .reshape(view.shape)
    )


def quantize_leaf_device(
    x, y, view: LeafView, payload_blocks: int, base_block: int, payload=None,
    wire: Optional[str] = None,
):
    """``x - y`` (``x`` where ``y`` is None) of one leaf, quantized into its
    run of the fragment's payload: the Pallas kernel on TPU, the same
    arithmetic and order in jnp elsewhere (fp8 / int8; int4 rides the flat
    path). Returns the payload and the leaf's scales in wire order."""
    import jax.numpy as jnp

    wire = _resolve_wire(wire)
    if on_tpu():
        payload, scales = quantize_leaf_pallas(
            x, y, view, payload_blocks, base_block, payload, wire=wire
        )
        return payload, scales.transpose(0, 2, 1).reshape(-1)
    data = x.astype(jnp.float32)
    if y is not None:
        data = data - y.astype(jnp.float32)
    run, scales = quantize_blocks_device(_wire_order(data, view), wire=wire)
    if payload is None:
        payload = jnp.zeros((payload_blocks, run.shape[1]), run.dtype)
    return payload.at[base_block : base_block + view.n_blocks].set(run), scales


def dequantize_leaf_device(payload, scales, view: LeafView, base_block: int):
    """Inverse of :func:`quantize_leaf_device`: float32 of ``view.shape`` from
    the whole payload and the whole scales."""
    import jax.numpy as jnp

    scales = scales[base_block : base_block + view.n_blocks]
    if on_tpu():
        tiled = scales.reshape(-1, view.chunk_segments, view.tile_rows).transpose(0, 2, 1)
        return dequantize_leaf_pallas(payload, tiled, view, base_block)
    run = payload[base_block : base_block + view.n_blocks].astype(jnp.float32)
    return _leaf_order(run * scales[:, None], view)


def tree_codec_views(shapes, wire: Optional[str] = None):
    """Per leaf shape, the :class:`LeafView` under which
    :func:`make_tree_fp8_codec` quantizes it in its own layout, or None where
    the leaf joins the flat tail: its shape gives no rows of whole blocks
    (:func:`leaf_block_view`), or the wire is packed int4 (no leaf-layout
    kernel). What the code observes; there is no knob."""
    if _resolve_wire(wire) == "int4":
        return [None for _ in shapes]
    return [leaf_block_view(shape) for shape in shapes]


def tree_codec_runs(shapes, wire: Optional[str] = None):
    """The wire order of the leaves that go in their own layout: ``(leaf
    index, its LeafView, its first block)`` a run, larger tiles first, which
    keeps every run aligned to its own tile (tiles are powers of two)."""
    views = tree_codec_views(shapes, wire)
    runs, base = [], 0
    for i in sorted(
        (i for i, view in enumerate(views) if view is not None),
        key=lambda i: -views[i].tile_blocks,
    ):
        runs.append((i, views[i], base))
        base += views[i].n_blocks
    return runs


def tree_codec_elements(leaves, wire: Optional[str] = None) -> dict:
    """Elements of ``leaves`` by the path :func:`make_tree_fp8_codec` gives
    them: ``{"leaf": ..., "flat": ...}``."""
    out = {"leaf": 0, "flat": 0}
    shapes = [tuple(leaf.shape) for leaf in leaves]
    for shape, view in zip(shapes, tree_codec_views(shapes, wire)):
        out["flat" if view is None else "leaf"] += int(np.prod(shape, dtype=np.int64))
    return out


def make_tree_fp8_codec(leaves, wire: Optional[str] = None):
    """Builds a jitted (quantize, dequantize) pair for a fixed list of float
    array leaves. ``quantize(leaves_in, minus=None)`` emits one (payload
    ``(n_blocks, payload_cols)``, scales ``(n_blocks,)``) of ``leaves_in``,
    or of ``leaves_in - minus`` formed in float32; ``dequantize`` inverts it
    back to per-leaf arrays with the original shapes/dtypes. A leaf whose
    shape gives rows of whole blocks (:func:`tree_codec_views`) is read and
    written in the layout it has, straight into and out of its run of the
    payload; those runs come first (:func:`tree_codec_runs`), and the other
    leaves are concatenated flat in
    float32 as before and their blocks appended. Shared by the DDP and DiLoCo
    quantized device pipelines; ``wire`` picks the payload format (default:
    ``TPUFT_WIRE_DTYPE``/fp8 — the name keeps the historical "fp8" even
    though int8 is also valid)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    wire = _resolve_wire(wire)
    for leaf in leaves:
        if np.dtype(leaf.dtype).kind not in ("f", "V"):
            raise TypeError(
                f"quantized sync requires float leaves, got {leaf.dtype}; "
                "use the unquantized path for integer state"
            )
    shapes = [tuple(leaf.shape) for leaf in leaves]
    dtypes = [leaf.dtype for leaf in leaves]
    runs = tree_codec_runs(shapes, wire)
    tail = sorted(set(range(len(shapes))) - {i for i, _, _ in runs})
    tail_offsets = np.cumsum([0] + [int(np.prod(shapes[i])) for i in tail])
    tail_base = sum(view.n_blocks for _, view, _ in runs)
    payload_blocks = tail_base + -(-int(tail_offsets[-1]) // BLOCK)

    def quantize(leaves_in, minus=None):
        payload, scales = None, []
        for i, view, base in runs:
            payload, scale = quantize_leaf_device(
                leaves_in[i].reshape(view.shape),
                None if minus is None else minus[i].reshape(view.shape),
                view, payload_blocks, base, payload, wire=wire,
            )
            scales.append(scale)
        if tail:
            flat = [leaves_in[i].astype(jnp.float32).reshape(-1) for i in tail]
            if minus is not None:
                flat = [
                    f - minus[i].astype(jnp.float32).reshape(-1)
                    for f, i in zip(flat, tail)
                ]
            blocks, scale = quantize_blocks_device(jnp.concatenate(flat), wire=wire)
            payload = blocks if payload is None else payload.at[tail_base:].set(blocks)
            scales.append(scale)
        return payload, jnp.concatenate(scales)

    def dequantize(payload, scales):
        out = [None] * len(shapes)
        for i, view, base in runs:
            values = dequantize_leaf_device(payload, scales, view, base)
            out[i] = values.astype(dtypes[i]).reshape(shapes[i])
        if tail:
            flat = dequantize_blocks_device(payload[tail_base:], scales[tail_base:])
            for slot, i in enumerate(tail):
                out[i] = (
                    flat[tail_offsets[slot] : tail_offsets[slot + 1]]
                    .reshape(shapes[i])
                    .astype(dtypes[i])
                )
        return out

    return jax.jit(quantize), jax.jit(dequantize)


def verify_on_chip() -> dict:
    """Compile (not interpret) the Pallas codec kernels on the attached TPU
    — every wire format — and check them against the host reference codec:
    the CLAUDE.md 'verify kernels on the real chip' gate, automated like
    flash_attention.verify_on_chip:

        python -c "from torchft_tpu.ops.quantization import verify_on_chip; print(verify_on_chip())"
    """
    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"no TPU attached (devices()[0] is {dev})")

    # Ragged length forces the padding path; mixed magnitudes + an all-zero
    # block exercise the scale selection. The second, larger length lands
    # on 1200 blocks — past _ROWS_PER_TILE with a partial final grid tile —
    # so the retiled kernels' ragged-grid branch is numerically verified on
    # the compiled Mosaic path too, not just interpret mode + the chipless
    # lowering gate.
    rng = np.random.default_rng(0)
    host_small = np.concatenate(
        [
            rng.normal(0, 3.0, 700).astype(np.float32),
            np.zeros(BLOCK, np.float32),
            rng.normal(0, 1e-4, 500).astype(np.float32),
        ]
    )
    host_ragged = rng.normal(0, 2.0, 1200 * BLOCK - 37).astype(np.float32)
    result: dict = {"ok": True}
    for label, host in (("small", host_small), ("ragged", host_ragged)):
        _verify_roundtrips(host, result, label)
    _verify_leaf_layout(result)
    return result


# The benchmark cell's leaves (chipbench/configs/mistral-7b-v0.3-1chip.json
# through models/llama.py, two scanned layers): an MLP matrix, the output
# projection with its heads leading, a DenseGeneral kernel whose (heads, 128)
# tail is merged, and a float32 norm scale that joins the flat tail.
_CELL_LEAVES = (
    ((2, 4096, 14336), "bfloat16"),
    ((2, 32, 128, 4096), "bfloat16"),
    ((2, 4096, 8, 128), "bfloat16"),
    ((2, 4096), "float32"),
)


def _verify_leaf_layout(result: dict) -> None:
    """The leaf-layout kernels compiled at the cell's shapes, through the
    tree codec (each leaf's run written into the shared payload in place):
    every leaf decodes as accurately as the host codec on its float32
    difference, and its run of the device payload decodes with the HOST
    kernels to the device's own values."""
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    pairs = [
        tuple(
            jnp.asarray(rng.standard_normal(shape, np.float32) * 0.02, dtype)
            for _ in range(2)
        )
        for shape, dtype in _CELL_LEAVES
    ]
    backup, local = [b for b, _ in pairs], [l for _, l in pairs]
    for wire in ("fp8", "int8"):
        quantize, dequantize = make_tree_fp8_codec(backup, wire=wire)
        payload, scales = quantize(backup, local)
        out = dequantize(payload, scales)
        host_payload = np.asarray(payload).view(_WIRE_NP_DTYPES[wire])
        host_scales = np.asarray(scales)
        runs = tree_codec_runs([b.shape for b in backup], wire)
        worst = 0.0
        for i, view, base in runs:
            diff = np.asarray(backup[i], np.float32) - np.asarray(local[i], np.float32)
            ref = dequantize_blocks(*quantize_blocks(diff, wire=wire), diff.shape, np.float32)
            got = np.asarray(out[i], np.float32)
            err_chip = float(np.max(np.abs(got - diff)))
            # The host's decode, rounded to the leaf's dtype as the device's is.
            err_host = float(np.max(np.abs(
                np.asarray(jnp.asarray(ref).astype(backup[i].dtype), np.float32) - diff
            )))
            if err_chip > max(err_host * 1.5, 1e-6):
                raise AssertionError(
                    f"leaf {backup[i].shape} {wire}: on-chip error {err_chip} vs host {err_host}"
                )
            run = slice(base, base + view.n_blocks)
            mixed = _leaf_order(
                _decode_payload_np(host_payload[run]) * host_scales[run, None], view
            ).reshape(diff.shape)
            mixed = np.asarray(jnp.asarray(mixed).astype(backup[i].dtype), np.float32)
            if float(np.max(np.abs(mixed - got))) > 1e-6:
                raise AssertionError(
                    f"leaf {backup[i].shape} {wire}: device run diverges from host decode"
                )
            worst = max(worst, err_chip)
        result[f"{wire}_leaf_max_err"] = worst
        result[f"{wire}_leaf_runs"] = [list(view) for _, view, _ in runs]


def _verify_roundtrips(host, result: dict, label: str) -> None:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    x = jnp.asarray(host)
    for wire in _WIRE_NP_DTYPES:
        payload, scales = jax.jit(
            functools.partial(quantize_blocks_device, wire=wire)
        )(x)
        out = jax.jit(dequantize_blocks_device)(payload, scales)[: host.size]

        ref_payload, ref_scales = quantize_blocks(host, wire=wire)
        ref = dequantize_blocks(ref_payload, ref_scales, host.shape, host.dtype)

        # The kernel must round-trip as accurately as the host codec (both
        # are bounded by the 8-bit format's per-block resolution).
        err_chip = float(np.max(np.abs(np.asarray(out) - host)))
        err_host = float(np.max(np.abs(ref - host)))
        if err_chip > max(err_host * 1.5, 1e-6):
            raise AssertionError(
                f"on-chip {wire} codec error {err_chip} vs host {err_host}"
            )
        # Wire-format compatibility: the device payload must dequantize with
        # the HOST kernels too (the mixed device/host paths share one
        # format).
        mixed = dequantize_blocks(
            np.asarray(payload).view(_WIRE_NP_DTYPES[wire]),
            np.asarray(scales).astype(np.float32),
            host.shape,
            host.dtype,
        )
        err_mixed = float(np.max(np.abs(mixed - np.asarray(out))))
        if err_mixed > 1e-6:
            raise AssertionError(
                f"device {wire} payload diverges from host decode: {err_mixed}"
            )
        # Per-pass keys so the committed artifact records BOTH passes (the
        # ragged multi-tile pass used to overwrite the small mixed-
        # magnitude one); the unlabeled legacy key stays as the worst case
        # across passes so existing artifact readers keep a meaningful
        # number.
        result[f"{wire}_max_err_{label}"] = err_chip
        result[f"{wire}_host_err_{label}"] = err_host
        result[f"{wire}_max_err"] = max(result.get(f"{wire}_max_err", 0.0), err_chip)
        result[f"{wire}_host_err"] = max(result.get(f"{wire}_host_err", 0.0), err_host)
