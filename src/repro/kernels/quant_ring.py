"""Pallas kernels for the fused quantized-ring hop (``repro.dist.compression``).

The compressed ring's hop payload is a narrow-dtype tensor plus (for the
scaled formats) its quantization scales. The XLA reference path computes one
*global* amax scale per message and pays two ``ppermute`` collectives per hop
(payload + f32 scale). These kernels implement the fused single-message
layout instead:

  * :func:`quantize_pack_pallas` — blockwise symmetric int8 quantization in
    one VMEM pass: each grid step loads a tile of ``block``-sized sub-block
    rows, computes every row's amax scale and emits the int8 payload plus
    the f32 scale per row. Per-block scales tighten the round-off bound
    from ``max|x| / 254`` (global) to ``max|x_block| / 254``, and the scales
    travel *with* the payload (bitcast into an int8 trailer by the caller)
    so each hop pays the per-message latency ``gamma`` exactly once.
  * :func:`dequant_accumulate_pallas` — the receive side, fused:
    ``recv_int8 * scale + acc`` per sub-block without materializing the
    dequantized f32 intermediate in HBM (it exists only as the VMEM
    register value feeding the add). With ``acc=None`` it degenerates to a
    plain blockwise dequantize (the Share-Only phase's unpack).
  * :func:`dequant_add_quantize_pallas` — the steady-state Share-Reduce hop
    in ONE pass: dequantize the received payload, add the local chunk, and
    re-quantize the partial sum for the next hop without the f32 partial
    ever leaving VMEM. Composition-equivalent to ``quantize_pack(
    dequant_accumulate(...))`` (asserted in tests/test_kernels.py) but one
    kernel launch and one HBM round-trip cheaper per hop.

Both kernels run natively on TPU and in ``interpret=True`` mode on CPU, so
the whole test suite exercises them (the ``repro.kernels.ops`` convention).
The grid walks row tiles with Pallas' automatic input double-buffering:
while tile ``k`` is being quantized/accumulated in VMEM, tile ``k+1``'s
HBM->VMEM copy is already in flight — the intra-message half of the hop
overlap that ``repro.dist.compression`` builds its double-buffered hop
schedule on. A tile is the whole ``n_blocks`` dimension when it fits
``_TILE_BUDGET_BYTES`` (a conservative slice of the ~16 MB VMEM, so in/out
tiles double-buffer comfortably); otherwise it is a multiple of
``ROW_ALIGN`` rows, the TPU's native int8 tile height. A dividing tile is
preferred; when ``n_blocks`` has no aligned divisor under the budget the
grid is ``cdiv(n_blocks, rows)`` and the last tile is ragged. Every row is
quantized on its own, so the rows past the end of a ragged tile never touch
a real row, and their writes are dropped.

Scales live in VMEM as an ``(n_blocks, 1)`` column, so a scale tile is
``(rows, 1)``: row-aligned like its payload tile, and whole in the lane
dimension. The public functions take and return ``(n_blocks,)`` scales.

Three wire dtypes share the pipeline:

  * ``int8`` (default) — symmetric blockwise quantization, scale =
    ``max|block| / 127``, values rounded to integers;
  * ``float8_e4m3fn`` — same per-block f32 scales (scale =
    ``max|block| / 448``) but the scaled values keep a 3-bit mantissa
    instead of rounding to integers, so small elements within a block lose
    far less relative precision. Same 1 byte/element payload and the same
    f32-trailer message layout as int8 (``HopMessageLayout`` applies
    unchanged: the caller bitcasts the fp8 payload to int8 for the wire);
  * ``bfloat16`` — no scales at all (bf16 carries f32's exponent range):
    :func:`cast_pack_bf16_pallas` / :func:`bf16_add_cast_pallas` /
    :func:`bf16_accumulate_pallas` move 2 bytes/element with a trailer-free
    hop message.

Arrays are 2-D ``(n_blocks, block)``; the ring layer owns flattening,
padding and the wire format (payload ++ scale trailer).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

QMAX = 127.0      # symmetric int8 range
FP8_MAX = 448.0   # float8_e4m3fn finfo max (no inf: overflow saturates here)
FP8_DTYPE = jnp.float8_e4m3fn


def wire_qmax(wire_dtype) -> float:
    """Symmetric clip range of a quantized wire dtype (scale denominator)."""
    dt = jnp.dtype(wire_dtype)
    if dt == jnp.dtype(jnp.int8):
        return QMAX
    if dt == jnp.dtype(FP8_DTYPE):
        return FP8_MAX
    raise ValueError(f"unsupported quantized wire dtype {dt}; "
                     "expected int8 or float8_e4m3fn")

# bytes each f32 scale occupies after the bitcast into the message trailer;
# the kernels own this constant (the trailer is *their* output layout) and
# repro.dist.compression re-exports it for the wire accounting
SCALE_BYTES = 4


@dataclasses.dataclass(frozen=True)
class HopMessageLayout:
    """The fused ring's wire-message layout for one hop chunk.

    A hop message is ``[int8 payload: n_blocks * block][trailer: n_blocks
    scales, each bitcast to scale_bytes int8 bytes]`` — the layout
    ``pack_hop_message`` emits and ``unpack_hop_message`` inverts. This is
    the single source of truth the wire accounting
    (``compressed_wire_bytes`` / ``rar_compressed_bytes_per_worker``) and
    the static verifier (``repro.analysis.collectives``) both derive message
    sizes from, so kernel layout and scheduler pricing cannot drift apart
    silently.
    """

    n_blocks: int
    block: int
    scale_bytes: int = SCALE_BYTES

    @property
    def payload_bytes(self) -> int:
        return self.n_blocks * self.block

    @property
    def trailer_bytes(self) -> int:
        return self.n_blocks * self.scale_bytes

    @property
    def message_bytes(self) -> int:
        return self.payload_bytes + self.trailer_bytes


def hop_message_layout(chunk_elems: int, *, block: int) -> HopMessageLayout:
    """Layout of one hop message for a ``chunk_elems``-element ring chunk.

    The chunk is padded up to whole ``block``-sized sub-blocks; the
    effective block never exceeds the chunk itself (tiny chunks quantize as
    one sub-block).
    """
    c = max(int(chunk_elems), 1)
    b = max(1, min(int(block), c))
    c_pad = -(-c // b) * b
    return HopMessageLayout(n_blocks=c_pad // b, block=b)

# per-tile working set cap: f32 in + int8 out (+ f32 acc on the receive
# side) double-buffered must fit VMEM with headroom
_TILE_BUDGET_BYTES = 2 * 1024 * 1024

# row (second-minor) alignment of a tile that is not the whole array: 32
# rows is one native int8/fp8 tile, and a multiple of the bf16 (16) and f32
# (8) tile heights, so one alignment serves every operand of every kernel
ROW_ALIGN = 32


def _rows_per_tile(nb: int, block: int, rows: Optional[int],
                   bytes_per_elem: int) -> int:
    """Rows of one tile: the whole ``nb`` when it fits the VMEM budget,
    else the largest ``ROW_ALIGN`` multiple under the budget, preferring
    one that divides ``nb`` (the grid is ``cdiv(nb, rows)`` either way).

    An explicit ``rows`` override must divide ``nb`` and be ``nb`` itself
    or a multiple of ``ROW_ALIGN``: the TPU compiler refuses any other
    tile height.
    """
    if rows is not None:
        if nb % rows:
            raise ValueError(f"rows_per_tile={rows} must divide n_blocks={nb}")
        if rows != nb and rows % ROW_ALIGN:
            raise ValueError(f"rows_per_tile={rows} must be n_blocks={nb} "
                             f"or a multiple of {ROW_ALIGN}")
        return int(rows)
    cap = _TILE_BUDGET_BYTES // max(block * bytes_per_elem, 1)
    if nb <= max(cap, ROW_ALIGN):
        return nb
    top = max(ROW_ALIGN, cap - cap % ROW_ALIGN)
    for r in range(top, 0, -ROW_ALIGN):
        if nb % r == 0:
            return r
    return top


def _tiled_call(kernel, operands, out_shapes, *, rows: int,
                interpret: bool):
    """``pallas_call`` over row tiles of ``(n_blocks, block)`` operands.

    ``operands`` and ``out_shapes`` hold ``(n_blocks, block)`` payloads and
    ``(n_blocks, 1)`` scale columns; each gets a ``(rows, block)`` or
    ``(rows, 1)`` tile. Outputs carry the operands' varying manual axes, so
    the call type-checks inside a ``shard_map`` with ``check_vma=True``.
    Interpret mode runs the TPU interpreter: the generic HLO interpreter
    refuses operands that vary over a manual mesh axis.
    """
    nb = operands[0].shape[0]
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))

    def spec(shape):
        return pl.BlockSpec((rows, shape[1]), lambda i: (i, 0))

    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(nb, rows),),
        in_specs=[spec(x.shape) for x in operands],
        out_specs=[spec(o.shape) for o in out_shapes],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype, vma=vma)
                   for o in out_shapes],
        interpret=pltpu.InterpretParams() if interpret else False,
    )(*operands)


def _quantize_rows(y: jax.Array, qmax: float, wire_dtype):
    """Per-row amax scale ``(rows, 1)`` + quantized payload of a 2-D f32
    tile.

    Integer wire dtypes round to the nearest step; float wire dtypes (fp8)
    keep the scaled value's mantissa and let the dtype cast do the rounding.
    """
    amax = jnp.max(jnp.abs(y), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    v = y / scale
    if jnp.issubdtype(jnp.dtype(wire_dtype), jnp.integer):
        v = jnp.round(v)
    return jnp.clip(v, -qmax, qmax).astype(wire_dtype), scale


def _scale_column(scales: jax.Array) -> jax.Array:
    """``(n_blocks,)`` scales as the kernels' ``(n_blocks, 1)`` column."""
    return scales.astype(jnp.float32).reshape(-1, 1)


def _make_quantize_pack_kernel(qmax: float, wire_dtype):
    def kernel(x_ref, q_ref, scale_ref):
        """One tile: per-row amax -> scale, emit payload + f32 scales."""
        q, scale = _quantize_rows(x_ref[...].astype(jnp.float32), qmax,
                                  wire_dtype)
        q_ref[...] = q
        scale_ref[...] = scale
    return kernel


def quantize_pack_pallas(x: jax.Array, *, interpret: bool = False,
                         rows_per_tile: Optional[int] = None,
                         wire_dtype=jnp.int8):
    """Blockwise symmetric quantization of a ``(n_blocks, block)`` array.

    Returns ``(q, scales)``: ``q`` has ``x``'s shape in ``wire_dtype`` (int8
    or float8_e4m3fn), ``scales`` is f32 ``(n_blocks,)`` with
    ``scales[i] = max|x[i]| / qmax`` (1.0 for all-zero sub-blocks, so
    dequantization is well defined). Error bound per element: ``scales[i]/2``
    for int8; relative ~2^-3 within the block for fp8.
    """
    qmax = wire_qmax(wire_dtype)
    nb, block = x.shape
    rows = _rows_per_tile(nb, block, rows_per_tile, bytes_per_elem=5)
    q, scales = _tiled_call(
        _make_quantize_pack_kernel(qmax, wire_dtype), [x],
        [jax.ShapeDtypeStruct((nb, block), jnp.dtype(wire_dtype)),
         jax.ShapeDtypeStruct((nb, 1), jnp.float32)],
        rows=rows, interpret=interpret)
    return q, scales.reshape(nb)


def _make_dequant_add_quantize_kernel(qmax: float, wire_dtype):
    def kernel(q_ref, scale_ref, acc_ref, q_out, s_out):
        """One tile of the steady-state hop: requantize(acc + q * scale)."""
        y = (acc_ref[...].astype(jnp.float32)
             + q_ref[...].astype(jnp.float32) * scale_ref[...])
        q, scale = _quantize_rows(y, qmax, wire_dtype)
        q_out[...] = q
        s_out[...] = scale
    return kernel


def dequant_add_quantize_pallas(q: jax.Array, scales: jax.Array,
                                acc: jax.Array, *, interpret: bool = False,
                                rows_per_tile: Optional[int] = None):
    """The fused ring's intermediate hop: ``Q(acc + dequant(q, scales))``.

    One VMEM pass per sub-block row — the f32 partial sum is never
    materialized in HBM. The wire dtype (int8 or fp8) is inherited from
    ``q``. Returns ``(q', scales')`` for the next hop's wire message.
    """
    wire_dtype = q.dtype
    qmax = wire_qmax(wire_dtype)
    nb, block = q.shape
    rows = _rows_per_tile(nb, block, rows_per_tile, bytes_per_elem=6)
    q2, s2 = _tiled_call(
        _make_dequant_add_quantize_kernel(qmax, wire_dtype),
        [q, _scale_column(scales), acc],
        [jax.ShapeDtypeStruct((nb, block), wire_dtype),
         jax.ShapeDtypeStruct((nb, 1), jnp.float32)],
        rows=rows, interpret=interpret)
    return q2, s2.reshape(nb)


def _dequant_accumulate_kernel(q_ref, scale_ref, acc_ref, out_ref):
    """One tile: out = acc + q * scale, f32 intermediate stays in VMEM."""
    q = q_ref[...].astype(jnp.float32)
    out_ref[...] = acc_ref[...].astype(jnp.float32) + q * scale_ref[...]


def _dequant_kernel(q_ref, scale_ref, out_ref):
    """One tile: out = q * scale (Share-Only unpack, no accumulator)."""
    out_ref[...] = q_ref[...].astype(jnp.float32) * scale_ref[...]


def dequant_accumulate_pallas(q: jax.Array, scales: jax.Array,
                              acc: Optional[jax.Array] = None, *,
                              interpret: bool = False,
                              rows_per_tile: Optional[int] = None
                              ) -> jax.Array:
    """Fused dequantize(+accumulate) of a ``(n_blocks, block)`` int8 payload.

    ``acc`` (same shape, any float dtype) is added in the same VMEM pass;
    ``acc=None`` returns the plain blockwise dequantization. Output is f32.
    """
    nb, block = q.shape
    rows = _rows_per_tile(nb, block, rows_per_tile,
                          bytes_per_elem=9 if acc is not None else 5)
    operands = [q, _scale_column(scales)]
    kernel = _dequant_kernel
    if acc is not None:
        operands.append(acc)
        kernel = _dequant_accumulate_kernel
    (out,) = _tiled_call(kernel, operands,
                         [jax.ShapeDtypeStruct((nb, block), jnp.float32)],
                         rows=rows, interpret=interpret)
    return out


# ---------------------------------------------------------------------------
# bf16 wire format: trailer-free 2-byte payload (no scales)
# ---------------------------------------------------------------------------

def _cast_bf16_kernel(x_ref, out_ref):
    """One tile: round-to-nearest bf16 cast (the bf16 wire's 'quantize')."""
    out_ref[...] = x_ref[...].astype(jnp.float32).astype(jnp.bfloat16)


def cast_pack_bf16_pallas(x: jax.Array, *, interpret: bool = False,
                          rows_per_tile: Optional[int] = None) -> jax.Array:
    """bf16 wire payload of a ``(n_blocks, block)`` array — the bf16 ring's
    analogue of :func:`quantize_pack_pallas`, minus the scales (bf16 keeps
    f32's exponent, so no per-block normalization is needed and the hop
    message is the bare payload)."""
    nb, block = x.shape
    rows = _rows_per_tile(nb, block, rows_per_tile, bytes_per_elem=6)
    (out,) = _tiled_call(_cast_bf16_kernel, [x],
                         [jax.ShapeDtypeStruct((nb, block), jnp.bfloat16)],
                         rows=rows, interpret=interpret)
    return out


def _bf16_add_cast_kernel(recv_ref, acc_ref, out_ref):
    """One tile of the steady-state bf16 hop: bf16(acc + recv)."""
    y = (acc_ref[...].astype(jnp.float32)
         + recv_ref[...].astype(jnp.float32))
    out_ref[...] = y.astype(jnp.bfloat16)


def bf16_add_cast_pallas(recv: jax.Array, acc: jax.Array, *,
                         interpret: bool = False,
                         rows_per_tile: Optional[int] = None) -> jax.Array:
    """The bf16 ring's intermediate hop: accumulate in f32 inside VMEM, emit
    the next hop's bf16 payload — one pass, no HBM f32 intermediate."""
    nb, block = recv.shape
    rows = _rows_per_tile(nb, block, rows_per_tile, bytes_per_elem=8)
    (out,) = _tiled_call(_bf16_add_cast_kernel, [recv, acc],
                         [jax.ShapeDtypeStruct((nb, block), jnp.bfloat16)],
                         rows=rows, interpret=interpret)
    return out


def _bf16_accumulate_kernel(recv_ref, acc_ref, out_ref):
    """One tile: out = acc + recv in f32."""
    out_ref[...] = (acc_ref[...].astype(jnp.float32)
                    + recv_ref[...].astype(jnp.float32))


def _bf16_upcast_kernel(recv_ref, out_ref):
    """One tile: out = f32(recv) (Share-Only unpack, no accumulator)."""
    out_ref[...] = recv_ref[...].astype(jnp.float32)


def bf16_accumulate_pallas(recv: jax.Array,
                           acc: Optional[jax.Array] = None, *,
                           interpret: bool = False,
                           rows_per_tile: Optional[int] = None) -> jax.Array:
    """f32 upcast(+accumulate) of a ``(n_blocks, block)`` bf16 payload —
    the bf16 analogue of :func:`dequant_accumulate_pallas` (``acc=None``
    returns the plain upcast)."""
    nb, block = recv.shape
    rows = _rows_per_tile(nb, block, rows_per_tile,
                          bytes_per_elem=10 if acc is not None else 6)
    operands = [recv]
    kernel = _bf16_upcast_kernel
    if acc is not None:
        operands.append(acc)
        kernel = _bf16_accumulate_kernel
    (out,) = _tiled_call(kernel, operands,
                         [jax.ShapeDtypeStruct((nb, block), jnp.float32)],
                         rows=rows, interpret=interpret)
    return out
