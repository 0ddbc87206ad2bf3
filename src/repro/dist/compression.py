"""Gradient compression for the ring: int8 quantization + error feedback.

The ring's wire term d(w-1)/w * 2/b is bandwidth-bound for large models, so
shrinking elements 4x (f32 -> int8 + f32 scales) shifts the paper's Eq. (1)
toward compute. Two collectives:

  * ``compressed_ring_all_reduce`` — every hop's payload is quantized
    (per-hop rounding error, no state). Share-Reduce re-quantizes partial
    sums each hop; Share-Only forwards each reduced chunk's int8 payload
    verbatim, so gather adds no extra error beyond one quantization.
  * ``ef_compressed_all_reduce`` — error feedback (Karimireddy et al.):
    each worker adds its residual before compressing and carries the new
    residual, recovering exact-SGD convergence rates. The tensor is
    quantized exactly once on the send side: the ring's first Share-Reduce
    hop forwards that int8 payload verbatim instead of re-quantizing the
    dequantized values (re-quantization would both waste a pass and add
    rounding the residual does not track).

Both take a ``fused=`` switch selecting between two executions:

**XLA reference path** (``fused=False``): flat global-amax ``quantize`` per
message, and each hop pays the per-message latency gamma *twice* — one
``ppermute`` for the int8 payload, a second for the f32 scale —
``2 * (2(w-1))`` collectives per all-reduce.

**Fused Pallas path** (``fused=True``): the single-ppermute hop layout.
Each hop's wire message is ONE int8 buffer::

    [ int8 payload: n_blocks * block ][ trailer: n_blocks f32 scales,
                                        bitcast to 4 int8 bytes each ]

``repro.kernels.quant_ring.quantize_pack_pallas`` emits payload + per-block
scales in one VMEM pass (blockwise scales tighten the error bound from
``max|chunk|/254`` to ``max|block|/254``), and the receive side is the fused
``dequant_accumulate_pallas`` — ``recv_int8 * scale + chunk`` without
materializing the dequantized f32 intermediate in HBM. One ``ppermute`` per
hop: gamma is paid once, ``2(w-1)`` collectives per all-reduce — exactly
half the reference path (pinned by the trace-count test in
tests/test_wire_cost.py).

The fused Share-Reduce is also a *double-buffered hop schedule*: the only
work on the critical path between receiving hop s and sending hop s+1 is
the one-pass ``dequant_add_quantize_pallas`` hop kernel on the received
sub-blocks — the f32 partial sums never round-trip through the (w, chunk)
HBM accumulator that the XLA path scatter-updates every hop (each chunk
index is touched exactly once per worker, so the original local chunk is
read directly at its hop), and the kernel's sub-block grid double-buffers
tile k+1's VMEM copy against tile k's compute. In the Share-Only phase the
forwarded buffer *is* the received buffer, so nothing but the ppermute
chain sits on the wire path: the gathered chunks' dequantization runs as
one batched kernel with no send-side consumer, overlapping the remaining
hops' transfers on an async backend.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.collectives import (
    _all_gather_chunks,
    _as_chunks,
    _ring_perm,
    flatten_for_ring,
    unflatten_from_ring,
)
from repro.kernels.quant_ring import (
    FP8_DTYPE,
    SCALE_BYTES,  # noqa: F401  (re-export: the wire accounting's name for it)
    bf16_accumulate_pallas,
    bf16_add_cast_pallas,
    cast_pack_bf16_pallas,
    dequant_accumulate_pallas,
    dequant_add_quantize_pallas,
    hop_message_layout,
    quantize_pack_pallas,
)
from repro.kernels.ops import interpret_mode

QMAX = 127.0  # symmetric int8 range

# default sub-block size of the fused path: the per-block f32 scale costs
# 4/block of the payload on the wire (0.1% at 4096 — negligible next to the
# halved message count), while a 4096-element block's amax scale is still
# vastly tighter than the XLA path's whole-chunk amax; full lanes on TPU
DEFAULT_BLOCK = 4096


def quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization: (int8 values, f32 scale).

    scale = max|x| / 127 so the round-off error is bounded by scale/2.
    """
    flat = x.reshape(-1).astype(jnp.float32)
    amax = jnp.max(jnp.abs(flat))
    scale = jnp.where(amax > 0, amax / QMAX, 1.0)
    q = jnp.clip(jnp.round(flat / scale), -QMAX, QMAX).astype(jnp.int8)
    return q, scale


def dequantize(qx: Tuple[jax.Array, jax.Array], size: int,
               shape: Tuple[int, ...]) -> jax.Array:
    """Inverse of :func:`quantize`; size/shape restore the original layout."""
    q, scale = qx
    return (q.astype(jnp.float32) * scale)[:size].reshape(shape)


def quantization_error(x: jax.Array) -> jax.Array:
    """Residual x - Q(x) — the quantity error feedback carries forward."""
    return x.astype(jnp.float32) - dequantize(quantize(x), x.size, x.shape)


def _interpret_default(interpret: Optional[bool]) -> bool:
    """An explicit ``interpret`` wins; otherwise the repo's backend rule
    (:func:`repro.kernels.ops.interpret_mode`)."""
    if interpret is None:
        return interpret_mode()
    return bool(interpret)


# ---------------------------------------------------------------------------
# fused single-ppermute wire format: payload ++ bitcast scale trailer
# ---------------------------------------------------------------------------

def pack_hop_message(q: jax.Array, scales: jax.Array) -> jax.Array:
    """Pack ``(n_blocks, block)`` quantized payload + ``(n_blocks,)`` f32
    scales into one int8 wire buffer: payload first (fp8 payloads bitcast to
    int8 bytes), then each scale bitcast to 4 int8 bytes."""
    if q.dtype != jnp.int8:
        q = lax.bitcast_convert_type(q, jnp.int8)
    trailer = lax.bitcast_convert_type(scales, jnp.int8).reshape(-1)
    return jnp.concatenate([q.reshape(-1), trailer])


def unpack_hop_message(msg: jax.Array, n_blocks: int, block: int,
                       wire_dtype=jnp.int8) -> Tuple[jax.Array, jax.Array]:
    """Inverse of :func:`pack_hop_message` for a given payload dtype."""
    n = n_blocks * block
    q = msg[:n].reshape(n_blocks, block)
    if jnp.dtype(wire_dtype) != jnp.dtype(jnp.int8):
        q = lax.bitcast_convert_type(q, wire_dtype)
    scales = lax.bitcast_convert_type(
        msg[n:].reshape(n_blocks, SCALE_BYTES), jnp.float32)
    return q, scales


def _fused_chunk_layout(n: int, w: int, block: int) -> Tuple[int, int, int]:
    """(chunk elements, sub-blocks per chunk, total pad) for a flat size n.

    Chunks are padded so each splits into whole ``block``-sized sub-blocks;
    the effective block never exceeds the chunk itself. Derived from the
    kernels' :func:`repro.kernels.quant_ring.hop_message_layout` so the ring
    and the kernel layout cannot disagree on the wire format.
    """
    c = -(-n // max(w, 1))                 # ceil(n / w)
    layout = hop_message_layout(c, block=block)
    c_pad = layout.n_blocks * layout.block
    return c_pad, layout.n_blocks, w * c_pad - n


# ---------------------------------------------------------------------------
# the compressed ring collective
# ---------------------------------------------------------------------------

def compressed_ring_all_reduce(
    x: jax.Array, axis_name: str, *, fused: bool = False,
    block: int = DEFAULT_BLOCK, interpret: Optional[bool] = None,
) -> jax.Array:
    """Ring all-reduce with int8-quantized hop payloads (stateless).

    ``fused=False`` is the XLA reference path (global-amax scale, payload
    and scale each ppermuted); ``fused=True`` runs the Pallas blockwise
    single-ppermute pipeline (module docstring). ``block`` is the fused
    path's sub-block size; ``interpret`` overrides the TPU-native/interpret
    autodetection of the Pallas kernels.
    """
    w = lax.axis_size(axis_name)
    if w == 1:
        return x
    if fused:
        return _fused_ring_all_reduce(x, axis_name, block=block,
                                      interpret=_interpret_default(interpret))
    return _xla_ring_all_reduce(x, axis_name)


def _xla_ring_all_reduce(x: jax.Array, axis_name: str,
                         first_hop: Optional[Tuple[jax.Array, jax.Array]] = None,
                         ) -> jax.Array:
    """Reference path: two ppermutes per hop (int8 payload + f32 scale).

    ``first_hop = (q_chunks, scale)`` lets error feedback forward its
    already-quantized payload on the first Share-Reduce hop (``q_chunks`` is
    the (w, chunk) int8 mirror of the input's chunk layout, ``scale`` its
    global f32 scale) instead of re-quantizing the dequantized tensor.
    """
    w = lax.axis_size(axis_name)
    chunks, pad = _as_chunks(x.astype(jnp.float32), w)
    idx = lax.axis_index(axis_name)
    perm = _ring_perm(w)

    # Share-Reduce: quantize each hop's partial sum before sending.
    for s in range(w - 1):
        if s == 0 and first_hop is not None:
            q, scale = jnp.take(first_hop[0], idx, axis=0), first_hop[1]
        else:
            q, scale = quantize(jnp.take(chunks, (idx - s) % w, axis=0))
        q = lax.ppermute(q, axis_name, perm)
        scale = lax.ppermute(scale, axis_name, perm)
        chunks = chunks.at[(idx - s - 1) % w].add(q.astype(jnp.float32) * scale)

    # Share-Only: quantize the owned reduced chunk once, forward int8+scale
    # verbatim (each chunk pays exactly one gather-phase quantization).
    own = (idx + 1) % w
    q_own, s_own = quantize(jnp.take(chunks, own, axis=0))
    qchunks = lax.dynamic_update_index_in_dim(
        jnp.zeros(chunks.shape, jnp.int8), q_own, own, 0)
    scales = lax.dynamic_update_index_in_dim(
        jnp.zeros((w,), jnp.float32), s_own, own, 0)
    qchunks = _all_gather_chunks(qchunks, axis_name, idx, perm)
    scales = _all_gather_chunks(scales[:, None], axis_name, idx, perm)[:, 0]

    flat = (qchunks.astype(jnp.float32) * scales[:, None]).reshape(-1)
    if pad:
        flat = flat[: flat.size - pad]
    return unflatten_from_ring(flat.astype(x.dtype), x.shape)


def _fused_ring_all_reduce(
    x: jax.Array, axis_name: str, *, block: int, interpret: bool,
    first_hop: Optional[jax.Array] = None, wire_dtype=jnp.int8,
) -> jax.Array:
    """Fused path: one packed ppermute per hop, Pallas quantize/accumulate.

    ``first_hop`` is an optional pre-packed wire message for the first
    Share-Reduce send (error feedback's already-quantized chunk).
    ``wire_dtype`` selects the quantized payload element type (int8 or
    float8_e4m3fn — both 1 byte/element, identical wire layout).
    """
    w = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    perm = _ring_perm(w)
    flat = flatten_for_ring(x).astype(jnp.float32)
    c_pad, nb, pad = _fused_chunk_layout(flat.size, w, block)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(w, nb, c_pad // nb)

    b = c_pad // nb

    def quant_pack(blocks2d: jax.Array) -> jax.Array:
        q, scales = quantize_pack_pallas(blocks2d, interpret=interpret,
                                         wire_dtype=wire_dtype)
        return pack_hop_message(q, scales)

    # Share-Reduce: each hop receives ONE packed message, and the whole
    # send-critical path is the one-pass dequant-add-requantize kernel on
    # the received sub-blocks (no HBM scatter into `chunks` — chunk
    # (idx-s-1) is read exactly once, at its own hop; the f32 partial sum
    # never leaves VMEM). The last hop's fused accumulate produces the
    # owned reduced chunk.
    if first_hop is not None:
        send = first_hop
    else:
        send = quant_pack(jnp.take(chunks, idx, axis=0))
    reduced_own = None
    for s in range(w - 1):
        recv = lax.ppermute(send, axis_name, perm)  # the hop's ONE collective
        local = jnp.take(chunks, (idx - s - 1) % w, axis=0)
        q, scales = unpack_hop_message(recv, nb, b, wire_dtype)
        if s < w - 2:
            q2, s2 = dequant_add_quantize_pallas(q, scales, local,
                                                 interpret=interpret)
            send = pack_hop_message(q2, s2)
        else:
            reduced_own = dequant_accumulate_pallas(q, scales, local,
                                                    interpret=interpret)

    # Share-Only: quantize the owned chunk once; every hop forwards the
    # received buffer verbatim, so nothing but the ppermute chain is on the
    # wire path — the dequantization of all w gathered chunks happens in
    # one batched kernel call that overlaps the tail of the ring on an
    # async backend (and each chunk still pays exactly one gather-phase
    # quantization; the owner reads back its own quantized payload so every
    # worker ends with bit-identical values).
    send = quant_pack(reduced_own)
    msgs = [send]
    for s in range(w - 1):
        recv = lax.ppermute(send, axis_name, perm)
        msgs.append(recv)
        send = recv
    # unpack message by message: a (w, message) stack sliced and reshaped
    # to (w * nb, b) compiles in time linear in the payload on TPU (minutes
    # at full width), one 1-D -> 2-D reshape per message does not
    parts = [unpack_hop_message(m, nb, b, wire_dtype) for m in msgs]
    q_all = jnp.concatenate([q for q, _ in parts])
    scales_all = jnp.concatenate([sc for _, sc in parts])
    deq = dequant_accumulate_pallas(q_all, scales_all, None,
                                    interpret=interpret)
    out = _gathered_chunk_order(deq.reshape(w, nb, b), idx, w)

    flat = out.reshape(-1)
    if pad:
        flat = flat[: flat.size - pad]
    return unflatten_from_ring(flat.astype(x.dtype), x.shape)


def _gathered_chunk_order(msgs: jax.Array, idx: jax.Array,
                          w: int) -> jax.Array:
    """Reorder the Share-Only phase's ``(w, ...)`` messages by chunk index.

    Message ``k`` carries chunk ``(idx + 1 - k) % w`` (the owned chunk
    first, then one chunk further back per hop), so chunk ``c`` is message
    ``(idx + 1 - c) % w``. A gather, not a scatter into zeros: the result
    is varying over the ring axis either way, and the gather needs no
    unvarying base array.
    """
    return jnp.take(msgs, (idx + 1 - jnp.arange(w)) % w, axis=0)


def _bf16_fused_ring_all_reduce(
    x: jax.Array, axis_name: str, *, block: int, interpret: bool,
) -> jax.Array:
    """bf16 wire ring: one trailer-free bf16 ppermute per hop.

    Same single-collective hop schedule as the fused int8/fp8 ring, but the
    wire message is the bare 2-byte payload — bf16 keeps f32's exponent so
    there are no scales to carry. Share-Reduce accumulates in f32 inside the
    :func:`repro.kernels.quant_ring.bf16_add_cast_pallas` kernel; Share-Only
    forwards received buffers verbatim and upcasts all gathered chunks in one
    batched kernel, mirroring the int8 path.
    """
    w = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    perm = _ring_perm(w)
    flat = flatten_for_ring(x).astype(jnp.float32)
    c_pad, nb, pad = _fused_chunk_layout(flat.size, w, block)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    b = c_pad // nb
    chunks = flat.reshape(w, nb, b)

    def cast_pack(blocks2d: jax.Array) -> jax.Array:
        return cast_pack_bf16_pallas(blocks2d, interpret=interpret).reshape(-1)

    # Share-Reduce: each hop's ONE collective carries the bf16 payload; the
    # send-critical path is the one-pass add-and-downcast kernel.
    send = cast_pack(jnp.take(chunks, idx, axis=0))
    reduced_own = None
    for s in range(w - 1):
        recv = lax.ppermute(send, axis_name, perm).reshape(nb, b)
        local = jnp.take(chunks, (idx - s - 1) % w, axis=0)
        if s < w - 2:
            send = bf16_add_cast_pallas(recv, local,
                                        interpret=interpret).reshape(-1)
        else:
            reduced_own = bf16_accumulate_pallas(recv, local,
                                                 interpret=interpret)

    # Share-Only: downcast the owned reduced chunk once, forward verbatim,
    # upcast every gathered chunk in one batched kernel call.
    send = cast_pack(reduced_own)
    msgs = [send]
    for s in range(w - 1):
        recv = lax.ppermute(send, axis_name, perm)
        msgs.append(recv)
        send = recv
    stacked = jnp.concatenate([m.reshape(nb, b) for m in msgs])
    deq = bf16_accumulate_pallas(stacked, None, interpret=interpret)
    out = _gathered_chunk_order(deq.reshape(w, nb, b), idx, w)

    flat = out.reshape(-1)
    if pad:
        flat = flat[: flat.size - pad]
    return unflatten_from_ring(flat.astype(x.dtype), x.shape)


# wire-format name -> quantized payload dtype (None = trailer-free bf16)
FUSED_WIRES = ("int8", "fp8", "bf16")
_FUSED_WIRE_DTYPES = {"int8": jnp.int8, "fp8": FP8_DTYPE}


def fused_wire_all_reduce(
    x: jax.Array, axis_name: str, *, wire: str = "int8",
    block: int = DEFAULT_BLOCK, interpret: Optional[bool] = None,
) -> jax.Array:
    """Single-ppermute-per-hop fused ring with a selectable wire format.

    ``wire``:

      * ``"int8"`` — the PR-5 layout: blockwise int8 payload + f32 scale
        trailer (identical to ``compressed_ring_all_reduce(fused=True)``);
      * ``"fp8"`` — float8_e4m3fn payload (bitcast to int8 bytes on the
        wire) + the same per-block f32 scale trailer; byte-identical message
        size to int8, tighter relative error for small in-block elements;
      * ``"bf16"`` — trailer-free 2-byte bf16 payload, no scales.

    All three issue ``2(w-1)`` collectives; per-hop message sizes are priced
    by :func:`fused_wire_bytes` / ``rar_model.wire_formula``.
    """
    if wire not in FUSED_WIRES:
        raise ValueError(f"unknown fused wire format {wire!r}; "
                         f"expected one of {FUSED_WIRES}")
    w = lax.axis_size(axis_name)
    if w == 1:
        return x
    interp = _interpret_default(interpret)
    if wire == "bf16":
        return _bf16_fused_ring_all_reduce(x, axis_name, block=block,
                                           interpret=interp)
    return _fused_ring_all_reduce(x, axis_name, block=block, interpret=interp,
                                  wire_dtype=_FUSED_WIRE_DTYPES[wire])


# ---------------------------------------------------------------------------
# error feedback
# ---------------------------------------------------------------------------

def ef_compressed_all_reduce(
    g: jax.Array, residual: Optional[jax.Array], axis_name: str, *,
    fused: bool = False, block: int = DEFAULT_BLOCK,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Error-feedback compressed all-reduce.

    corrected = g + residual; the worker quantizes corrected exactly once,
    keeps residual' = corrected - Q(corrected) for the next step, and the
    ring's first Share-Reduce hop forwards that int8 payload verbatim (no
    re-quantization of the dequantized values — the skipped pass used to add
    rounding the residual cannot see). Returns (sum-reduced compressed
    gradient, new residual). The residual covers this worker's own
    compression; the ring's per-hop re-quantization of partial sums adds
    noise no residual tracks (small: bounded by hops * max|partial|/254;
    ``fused=True`` tightens it to per-``block`` amax).
    """
    corrected = g.astype(jnp.float32)
    if residual is not None:
        corrected = corrected + residual.astype(jnp.float32)
    w = lax.axis_size(axis_name)
    if w == 1:
        # no hops — still quantize once so the residual semantics (and the
        # fused mode's blockwise rounding) match the w >= 2 ring exactly
        if fused:
            interp = _interpret_default(interpret)
            c_pad, nb, pad = _fused_chunk_layout(corrected.size, 1, block)
            flat = corrected.reshape(-1)
            if pad:
                flat = jnp.pad(flat, (0, pad))
            q, scales = quantize_pack_pallas(
                flat.reshape(nb, c_pad // nb), interpret=interp)
            deq = dequant_accumulate_pallas(q, scales, None, interpret=interp)
            compressed = deq.reshape(-1)[: corrected.size].reshape(
                corrected.shape)
        else:
            compressed = dequantize(quantize(corrected), corrected.size,
                                    corrected.shape)
        return compressed.astype(g.dtype), corrected - compressed

    if fused:
        interp = _interpret_default(interpret)
        c_pad, nb, pad = _fused_chunk_layout(corrected.size, w, block)
        flat = flatten_for_ring(corrected)
        if pad:
            flat = jnp.pad(flat, (0, pad))
        blocks2d = flat.reshape(w * nb, c_pad // nb)
        q, scales = quantize_pack_pallas(blocks2d, interpret=interp)
        deq = dequant_accumulate_pallas(q, scales, None, interpret=interp)
        compressed = unflatten_from_ring(deq.reshape(-1)[: corrected.size],
                                         corrected.shape)
        idx = lax.axis_index(axis_name)
        first = pack_hop_message(
            lax.dynamic_slice_in_dim(q, idx * nb, nb, axis=0),
            lax.dynamic_slice_in_dim(scales, idx * nb, nb, axis=0))
        reduced = _fused_ring_all_reduce(compressed, axis_name, block=block,
                                         interpret=interp, first_hop=first)
    else:
        q_flat, scale = quantize(corrected)
        compressed = dequantize((q_flat, scale), corrected.size,
                                corrected.shape)
        q_chunks, _ = _as_chunks(q_flat, w)
        reduced = _xla_ring_all_reduce(compressed, axis_name,
                                       first_hop=(q_chunks, scale))
    new_residual = corrected - compressed
    return reduced.astype(g.dtype), new_residual


# ---------------------------------------------------------------------------
# wire-cost accounting (the executable side of the scheduler's Eq. (1))
# ---------------------------------------------------------------------------

def compressed_ring_ppermutes(w: int, *, fused: bool = False) -> int:
    """ppermute collectives one compressed all-reduce issues per worker.

    The XLA path pays gamma twice per hop (payload + f32 scale are separate
    collectives): 4(w-1). The fused path packs the scales into the payload
    trailer: one collective per hop, 2(w-1) — exactly half. Asserted against
    the traced collective in tests/test_wire_cost.py.
    """
    if w <= 1:
        return 0
    return (2 if fused else 4) * (w - 1)


def compressed_wire_bytes(d: float, w: int, *, scale_bytes: int = SCALE_BYTES,
                          fused: bool = False,
                          block: int = DEFAULT_BLOCK) -> float:
    """Per-worker wire bytes of one int8 ring all-reduce.

    XLA path: 2(w-1) hops, each sending a ceil(d/w)-byte int8 payload plus a
    separate ``scale_bytes`` f32 scale message (the chunk is zero-padded to
    split evenly, and the pad bytes do cross the wire). Fused path: 2(w-1)
    hops of ONE packed message — the block-padded payload plus one f32 scale
    per ``block`` sub-block bitcast into the trailer. Both are ~3.9x below
    the f32 ring's 2d(w-1)/w * 4 for d >> w * block; asserted against the
    traced collective payloads in tests/test_wire_cost.py.
    """
    if w <= 1:
        return 0.0
    if fused:
        c_pad, nb, _ = _fused_chunk_layout(int(d), w, block)
        return 2.0 * (w - 1.0) * (c_pad + float(scale_bytes) * nb)
    c = -(-int(d) // w)  # ceil(d / w): the executed (padded) chunk size
    return 2.0 * (w - 1.0) * (float(c) + float(scale_bytes))


def fused_wire_bytes(d: float, w: int, *, wire: str = "int8",
                     scale_bytes: int = SCALE_BYTES,
                     block: int = DEFAULT_BLOCK) -> float:
    """Per-worker wire bytes of one :func:`fused_wire_all_reduce`.

    All fused wires pay 2(w-1) hops of one message each. int8/fp8 messages
    are the block-padded 1-byte payload plus one bitcast f32 scale per
    sub-block; bf16 messages are the bare 2-byte payload (no trailer). The
    scheduler-side mirror is ``rar_model.rar_compressed_bytes_per_worker``
    with the matching ``payload_elem_bytes``/``trailer`` arguments — both
    are asserted against traced collectives in tests/test_wire_cost.py.
    """
    if wire not in FUSED_WIRES:
        raise ValueError(f"unknown fused wire format {wire!r}; "
                         f"expected one of {FUSED_WIRES}")
    if w <= 1:
        return 0.0
    c_pad, nb, _ = _fused_chunk_layout(int(d), w, block)
    if wire == "bf16":
        per_hop = 2.0 * c_pad
    else:
        per_hop = c_pad + float(scale_bytes) * nb
    return 2.0 * (w - 1.0) * per_hop
