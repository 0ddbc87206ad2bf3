"""Compute/communication structuring: gradient accumulation and bucketing.

``microbatch_grads`` trades activation memory for sequential microbatch
passes (lax.scan keeps the HLO small); ``bucketed_psum`` coalesces many
small gradient tensors into a few large all-reduces — the ring's per-hop
latency gamma is paid per collective, so fewer, larger payloads sit closer
to the bandwidth-bound regime Eq. (1) assumes.

``bucketed_ring_reduce`` is the overlap pipeline's reduction: the same
order-preserving bucketing, but each bucket is reduced through a registered
``repro.dist.registry`` ring variant (e.g. the fused int8 single-ppermute
pipeline) instead of ``lax.psum``, and buckets are assigned in
*reverse-autodiff order* — reverse-mode AD materializes the last layer's
gradients first, so the bucket holding the tree's last leaves completes
first and its ring is issued first, overlapping the earlier layers' still-
running backward compute on an async backend. The bucket plan
(:func:`plan_buckets` / :func:`plan_bucket_sizes`) is shared with the
static collective verifier so the traced per-bucket ppermute chains and the
scheduler's wire pricing cannot drift apart.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.collectives import flatten_for_ring, unflatten_from_ring


def microbatch_grads(loss_fn: Callable, params, batch,
                     n_microbatches: int = 1) -> Tuple[jax.Array, Any]:
    """Mean loss and grads of ``loss_fn(params, batch)`` accumulated over
    ``n_microbatches`` equal slices of the batch's leading dim.

    Exactly matches the full-batch value when the loss is a batch mean
    (equal microbatch sizes), to float tolerance. Raises ``ValueError`` for
    splits that cannot be even: a leading dim smaller than
    ``n_microbatches`` or not divisible by it.
    """
    if n_microbatches <= 1:
        return jax.value_and_grad(loss_fn)(params, batch)

    def split(x):
        b = x.shape[0]
        if n_microbatches > b:
            raise ValueError(
                f"n_microbatches={n_microbatches} exceeds the batch's "
                f"leading dim {b}: each microbatch needs at least one "
                "sample")
        if b % n_microbatches:
            raise ValueError(
                f"batch leading dim {b} is not divisible by "
                f"n_microbatches={n_microbatches}: microbatches must be "
                "equal-sized for the accumulated mean to equal the "
                "full-batch mean")
        return x.reshape((n_microbatches, b // n_microbatches) + x.shape[1:])

    mb = jax.tree.map(split, batch)
    zero = (jnp.zeros((), jnp.float32),
            jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params))

    def body(carry, b):
        loss, grads = jax.value_and_grad(loss_fn)(params, b)
        acc_loss, acc_grads = carry
        return (acc_loss + loss.astype(jnp.float32),
                jax.tree.map(jnp.add, acc_grads, grads)), None

    (loss, grads), _ = lax.scan(body, zero, mb)
    inv = 1.0 / n_microbatches
    return loss * inv, jax.tree.map(lambda g: (g * inv).astype(g.dtype), grads)


# ---------------------------------------------------------------------------
# bucket planning (shared with repro.analysis.collectives' step pricing)
# ---------------------------------------------------------------------------

def plan_buckets(sizes: Sequence[int], n_buckets: int, *,
                 reverse: bool = False) -> List[List[int]]:
    """Greedy order-preserving partition of leaf ``sizes`` into contiguous
    buckets of roughly equal element count.

    Returns lists of *original* indices. ``reverse=True`` walks the leaves
    last-to-first (reverse-autodiff order) so the bucket containing the last
    leaves is planned — and its ring launched — first. The bucket count is
    clamped to ``[1, len(sizes)]``. This is the single bucketing rule:
    :func:`bucketed_psum`, :func:`bucketed_ring_reduce` and the collective
    verifier's overlap-mode pricing all call it, so the executed buckets and
    the priced buckets cannot disagree.
    """
    if not sizes:
        return []
    idx = list(range(len(sizes)))
    if reverse:
        idx.reverse()
    n_buckets = max(1, min(int(n_buckets), len(sizes)))
    total = sum(sizes)
    target = max(1, -(-total // n_buckets))  # ceil

    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_size = 0
    for i in idx:
        cur.append(i)
        cur_size += sizes[i]
        if cur_size >= target and len(buckets) < n_buckets - 1:
            buckets.append(cur)
            cur, cur_size = [], 0
    if cur:
        buckets.append(cur)
    return buckets


def plan_bucket_sizes(sizes: Sequence[int], n_buckets: int, *,
                      reverse: bool = True) -> List[int]:
    """Element count of each planned bucket (the reduced payload sizes a
    traced ``bucketed_ring_reduce`` must show, in launch order)."""
    return [sum(sizes[i] for i in bucket)
            for bucket in plan_buckets(sizes, n_buckets, reverse=reverse)]


def even_bucket_sizes(d: int, n: int) -> List[int]:
    """Even contiguous split of ``d`` flat elements into ``n`` segments
    (first ``d % n`` segments one element larger) — the segment rule of
    :func:`segmented_ring_reduce` and the variant-level bucketed pricing in
    ``repro.dist.registry``."""
    n = max(1, min(int(n), int(d))) if d > 0 else 1
    base, rem = divmod(int(d), n)
    return [base + (1 if i < rem else 0) for i in range(n)]


def segmented_ring_reduce(x: jax.Array, ring: Callable[[jax.Array], jax.Array],
                          n_segments: int) -> jax.Array:
    """Reduce a flat array as ``n_segments`` contiguous even segments, each
    through its own ``ring`` call (one ppermute chain per segment)."""
    flat = flatten_for_ring(x)
    parts = []
    off = 0
    for seg in even_bucket_sizes(flat.size, n_segments):
        parts.append(ring(flat[off: off + seg]))
        off += seg
    return unflatten_from_ring(jnp.concatenate(parts), x.shape)


# ---------------------------------------------------------------------------
# bucketed reductions
# ---------------------------------------------------------------------------

def _bucketed_reduce(grads, n_buckets: int, reduce_flat: Callable,
                     *, reverse: bool):
    """Shared driver: plan buckets, concat per dtype, reduce, split back."""
    leaves, treedef = jax.tree.flatten(grads)
    if not leaves:
        return grads
    sizes = [leaf.size for leaf in leaves]
    out = [None] * len(leaves)
    for bucket in plan_buckets(sizes, n_buckets, reverse=reverse):
        by_dtype: Dict[Any, list] = {}
        for i in bucket:
            by_dtype.setdefault(leaves[i].dtype, []).append(i)
        for dtype, idxs in by_dtype.items():
            flat = jnp.concatenate([flatten_for_ring(leaves[i])
                                    for i in idxs])
            red = reduce_flat(flat)
            off = 0
            for i in idxs:
                n = leaves[i].size
                out[i] = unflatten_from_ring(red[off: off + n],
                                             leaves[i].shape)
                off += n
    return jax.tree.unflatten(treedef, out)


def bucketed_psum(grads, axis_name: str, *, n_buckets: int = 4):
    """psum a gradient tree as ~``n_buckets`` flat fused payloads.

    Leaves are packed into contiguous buckets of roughly equal element
    count (order-preserving), concatenated per dtype, reduced with one
    ``lax.psum`` each, then split and reshaped back. Semantically identical
    to leaf-wise psum.
    """
    return _bucketed_reduce(grads, n_buckets,
                            lambda flat: lax.psum(flat, axis_name),
                            reverse=False)


def bucketed_ring_reduce(grads, axis_name: str, *,
                         variant: Union[str, Any] = "int8-fused",
                         n_buckets: int = 4):
    """Sum-reduce a gradient tree as per-bucket ring all-reduces.

    Each bucket's concatenated payload goes through one call of the named
    ``repro.dist.registry.RING_VARIANTS`` entry (its own ppermute chain), so
    a later bucket's ring can launch while earlier gradients are still being
    produced. Buckets are assigned in reverse-autodiff order
    (``plan_buckets(reverse=True)``): reverse-mode AD finishes the *last*
    leaves' gradients first, so their bucket's ring is issued first.
    Semantically equivalent to applying the variant leaf-wise (up to the
    variant's own quantization error being computed over bucket-concatenated
    blocks). Returns the **sum** across the axis, like the raw variants —
    callers divide by world size for the mean.
    """
    from repro.dist.registry import RingVariant, variant_by_name

    if isinstance(variant, str):
        variant = variant_by_name(variant)
    elif not isinstance(variant, RingVariant):
        raise TypeError("variant must be a registered variant name or a "
                        f"RingVariant, got {type(variant).__name__}")
    ring = variant.build(axis_name)
    return _bucketed_reduce(grads, n_buckets, ring, reverse=True)
