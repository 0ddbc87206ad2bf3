"""Ring all-reduce collectives — the executable form of the paper's §III.

Each collective runs inside ``shard_map`` over a named mesh axis and moves
data exclusively via ``lax.ppermute`` along the ring, mirroring the paper's
RAR structure exactly:

  * Share-Reduce phase (``ring_reduce_scatter``): w-1 steps; at step s worker
    i forwards its partial sum of chunk (i - s) mod w to worker i+1 and
    accumulates the chunk arriving from worker i-1. After w-1 steps worker i
    owns the fully reduced chunk (i + 1) mod w.
  * Share-Only phase: another w-1 steps circulating the reduced chunks so
    every worker ends with the full gradient.

Total wire traffic per worker: 2 * d * (w-1)/w elements — exactly the
``rar_ring_bytes_per_worker`` term (with ``elem_bytes=1``) the GADGET
scheduler prices in :mod:`repro.core.rar_model`. ``ring_wire_elements`` below
is asserted against it in the tests.

The int8-compressed variants live in :mod:`repro.dist.compression`. Their
fused hop layout rides the same ``_ring_perm`` schedule but each hop's wire
message is ONE int8 buffer — blockwise-quantized payload followed by a
trailer of per-block f32 scales bitcast to int8 — so a hop pays exactly one
``ppermute`` (the XLA reference layout pays two: payload + scale). Blockwise
scales bound the per-element rounding error by ``max|x_block| / 254``
instead of the flat quantizer's ``max|x| / 254``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax


def _ring_perm(w: int, reverse: bool = False):
    """ppermute pairs for a unidirectional ring (src, dst)."""
    if reverse:
        return [(i, (i - 1) % w) for i in range(w)]
    return [(i, (i + 1) % w) for i in range(w)]


def flatten_for_ring(x: jax.Array) -> jax.Array:
    """``x`` as a flat vector, behind an optimization barrier.

    The rings slice a flattened leaf by ring position. Left to itself,
    XLA's TPU compiler folds the flattening reshape of a multi-dimensional
    leaf into those dynamic slices, and its compile time then grows with
    the leaf's row count: minutes for qwen3-0.6b's 151936 x 1024
    embedding. The barrier keeps the relayout a plain copy.
    """
    return lax.optimization_barrier(x.reshape(-1))


def unflatten_from_ring(flat: jax.Array, shape) -> jax.Array:
    """Inverse of :func:`flatten_for_ring`: ``flat`` in ``shape``."""
    return lax.optimization_barrier(flat).reshape(shape)


def _as_chunks(x: jax.Array, w: int) -> Tuple[jax.Array, int]:
    """Flatten and pad x so it splits into w equal ring chunks."""
    flat = flatten_for_ring(x)
    pad = (-flat.size) % w
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(w, -1), pad


def _effective_index(axis_name: str, w: int, reverse: bool) -> jax.Array:
    """Ring position in forward-ring coordinates.

    A reversed ring (worker i sends to i-1) is the forward ring under the
    relabeling j = -i mod w, so one schedule serves both directions.
    """
    idx = lax.axis_index(axis_name)
    return (w - idx) % w if reverse else idx


def ring_reduce_scatter(x: jax.Array, axis_name: str) -> jax.Array:
    """Share-Reduce phase only: returns worker i's reduced chunk (i+1) mod w.

    Output is the flat chunk of size ceil(d / w); callers all-gather or keep
    it sharded (e.g. for sharded optimizer updates). Forward ring only — a
    reversed ring would land chunks at relabeled offsets, breaking the
    chunk-index contract above.
    """
    w = lax.axis_size(axis_name)
    chunks, _ = _as_chunks(x, w)
    if w == 1:
        return chunks.reshape(-1)
    idx = lax.axis_index(axis_name)
    chunks = _reduce_scatter_chunks(chunks, axis_name, idx, _ring_perm(w))
    return jnp.take(chunks, (idx + 1) % w, axis=0)


def _reduce_scatter_chunks(chunks: jax.Array, axis_name: str, idx: jax.Array,
                           perm) -> jax.Array:
    """In-place Share-Reduce over a (w, chunk) array; chunk (idx+1)%w ends
    fully reduced on this worker."""
    w = chunks.shape[0]
    for s in range(w - 1):
        send = jnp.take(chunks, (idx - s) % w, axis=0)
        recv = lax.ppermute(send, axis_name, perm)
        chunks = chunks.at[(idx - s - 1) % w].add(recv)
    return chunks


def _all_gather_chunks(chunks: jax.Array, axis_name: str, idx: jax.Array,
                       perm) -> jax.Array:
    """Share-Only phase: circulate reduced chunks until all w are present."""
    w = chunks.shape[0]
    for s in range(w - 1):
        send = jnp.take(chunks, (idx + 1 - s) % w, axis=0)
        recv = lax.ppermute(send, axis_name, perm)
        chunks = chunks.at[(idx - s) % w].set(recv)
    return chunks


def _ring_all_reduce_flat(x: jax.Array, axis_name: str,
                          reverse: bool) -> jax.Array:
    w = lax.axis_size(axis_name)
    chunks, pad = _as_chunks(x, w)
    if w > 1:
        idx = _effective_index(axis_name, w, reverse)
        perm = _ring_perm(w, reverse)
        chunks = _reduce_scatter_chunks(chunks, axis_name, idx, perm)
        chunks = _all_gather_chunks(chunks, axis_name, idx, perm)
    flat = chunks.reshape(-1)
    return flat[: flat.size - pad] if pad else flat


def ring_all_reduce(x: jax.Array, axis_name: str, *,
                    reverse: bool = False) -> jax.Array:
    """Paper-faithful ring all-reduce: 2(w-1) ppermute steps, sum semantics."""
    if lax.axis_size(axis_name) == 1:
        return x
    return unflatten_from_ring(_ring_all_reduce_flat(x, axis_name, reverse),
                               x.shape)


def bidirectional_ring_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Counter-rotating half-rings: each half of the gradient takes one
    direction, so both link directions carry d(w-1)/w elements concurrently
    (2x the busy links of the unidirectional ring at the same total wire)."""
    w = lax.axis_size(axis_name)
    if w == 1:
        return x
    flat = flatten_for_ring(x)
    half = (flat.size + 1) // 2
    fwd = _ring_all_reduce_flat(flat[:half], axis_name, reverse=False)
    bwd = _ring_all_reduce_flat(flat[half:], axis_name, reverse=True)
    return unflatten_from_ring(jnp.concatenate([fwd, bwd]), x.shape)


def psum_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """XLA-native all-reduce baseline (same semantics, compiler-chosen algo)."""
    return lax.psum(x, axis_name)


def ring_wire_elements(d: float, w: int) -> float:
    """Per-worker wire traffic of one ring all-reduce, in elements.

    The paper's 2d(w-1)/w: (w-1) Share-Reduce sends + (w-1) Share-Only sends
    of d/w elements each. Must agree with
    ``repro.core.rar_model.rar_ring_bytes_per_worker(d, w, elem_bytes=1)`` —
    the scheduler's cost model and this executable layer share the formula.
    """
    if w <= 1:
        return 0.0
    return 2.0 * float(d) * (w - 1.0) / float(w)
