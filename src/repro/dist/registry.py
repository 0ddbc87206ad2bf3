"""Registry of every traceable ring-all-reduce variant and train-step mode.

Before this module, the set of ring collectives lived implicitly in
hand-written test lists (tests/test_wire_cost.py picked two) and the set of
train-step modes in ``train_step.RING_MODES`` plus string comparisons. The
static collective verifier (``repro.analysis.collectives``) needs the full
set *enumerable*: every entry here is traced under ``AbstractMesh`` across a
world-size sweep and checked against the scheduler's wire pricing, so adding
a ring variant without registering it — or registering one whose wire cost
the scheduler cannot price — fails CI instead of silently drifting.

Two registries:

  * :data:`RING_VARIANTS` — the raw collectives: unary ``grads -> reduced``
    callables built per axis name, each annotated with the ``rar_model``
    wire layout it must price as (``compression``), the number of distinct
    ring directions its hops may use, and whether it is a half-split
    bidirectional ring, a reduce-scatter (single phase), or a segmented
    bucket pipeline (``n_buckets`` independent ppermute chains).
  * :data:`STEP_MODES` — the full ``make_ring_train_step`` modes
    ``RingWorkerGroup`` accepts, annotated the same way. Most modes reduce
    *per gradient leaf* (plus one loss ``pmean``), so per-mode expectations
    compose the per-leaf variant expectation over a model's leaf sizes; the
    overlap mode reduces *per bucket* (``spec.n_buckets``), so its
    expectation composes over ``repro.dist.overlap.plan_bucket_sizes`` of
    the leaf sizes instead.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.rar_model import wire_formula
from repro.dist import collectives
from repro.dist.compression import (
    compressed_ring_all_reduce,
    ef_compressed_all_reduce,
    fused_wire_all_reduce,
)
from repro.dist.overlap import even_bucket_sizes, segmented_ring_reduce

__all__ = ["RingVariant", "StepModeSpec", "RING_VARIANTS", "STEP_MODES",
           "variant_by_name"]

BuildFn = Callable[[str], Callable[[jax.Array], jax.Array]]


@dataclasses.dataclass(frozen=True)
class RingVariant:
    """One registered ring collective and its priced wire layout.

    ``directions`` is the number of distinct ``ppermute`` permutations the
    traced jaxpr may contain: 1 for a unidirectional ring, 2 (mutually
    inverse) for the bidirectional split, 0 for psum-based variants with no
    explicit ring. ``halves`` marks the bidirectional collective (the flat
    tensor splits into two half-rings, each priced independently);
    ``reduce_scatter`` marks the single-phase collective (Share-Reduce
    only: half the hops and bytes of a full all-reduce); ``n_buckets``
    marks a segmented bucket pipeline — the flat input splits into that
    many contiguous even segments (``overlap.even_bucket_sizes``), each
    reduced by its own ppermute chain and priced independently. ``source``
    is the repo-relative file the variant's implementation lives in
    (verifier findings point at it).
    """

    name: str
    build: BuildFn
    compression: Optional[str] = None
    directions: int = 1
    collective: str = "ppermute"
    halves: bool = False
    reduce_scatter: bool = False
    n_buckets: Optional[int] = None
    source: str = "src/repro/dist/collectives.py"

    def expected_messages(self, w: int, d: Optional[int] = None) -> int:
        """ppermute count one traced call must contain at world size w.

        ``d`` only matters for bucketed variants (the segment count is
        clamped to the flat size).
        """
        if self.collective != "ppermute" or w <= 1:
            return 0
        per_ring = wire_formula(self.compression).messages(w)
        if self.halves:
            return 2 * per_ring
        if self.reduce_scatter:
            return per_ring // 2
        if self.n_buckets:
            segs = (len(even_bucket_sizes(d, self.n_buckets))
                    if d is not None else self.n_buckets)
            return segs * per_ring
        return per_ring

    def expected_bytes(self, d: int, w: int) -> float:
        """Total wire bytes the traced ppermutes must carry for a flat
        ``d``-element input (executed layout: padded chunks included)."""
        if self.collective != "ppermute" or w <= 1:
            return 0.0
        f = wire_formula(self.compression)
        if self.halves:
            hi = (d + 1) // 2
            return (f.bytes_per_worker(hi, w)
                    + f.bytes_per_worker(d - hi, w))
        if self.n_buckets:
            return sum(f.bytes_per_worker(seg, w)
                       for seg in even_bucket_sizes(d, self.n_buckets))
        total = f.bytes_per_worker(d, w)
        return total / 2.0 if self.reduce_scatter else total


def _ef_build(axis_name: str, *, fused: bool) -> Callable:
    def run(g: jax.Array) -> jax.Array:
        reduced, _ = ef_compressed_all_reduce(
            g, jnp.zeros_like(g), axis_name, fused=fused)
        return reduced
    return run


def _bucketed_f32_build(axis_name: str, *, n_buckets: int) -> Callable:
    def run(g: jax.Array) -> jax.Array:
        return segmented_ring_reduce(
            g, partial(collectives.ring_all_reduce, axis_name=axis_name),
            n_buckets)
    return run


# segment count of the registered variant-level bucket pipeline (the step
# mode's bucket count lives on StepModeSpec.n_buckets instead)
BUCKETED_VARIANT_SEGMENTS = 3


RING_VARIANTS: Tuple[RingVariant, ...] = (
    RingVariant(
        name="f32",
        build=lambda ax: partial(collectives.ring_all_reduce, axis_name=ax)),
    RingVariant(
        name="f32-reverse",
        build=lambda ax: partial(collectives.ring_all_reduce, axis_name=ax,
                                 reverse=True)),
    RingVariant(
        name="bidir",
        build=lambda ax: partial(collectives.bidirectional_ring_all_reduce,
                                 axis_name=ax),
        directions=2, halves=True),
    RingVariant(
        name="reduce-scatter",
        build=lambda ax: partial(collectives.ring_reduce_scatter,
                                 axis_name=ax),
        reduce_scatter=True),
    RingVariant(
        name="psum",
        build=lambda ax: partial(collectives.psum_all_reduce, axis_name=ax),
        directions=0, collective="psum"),
    RingVariant(
        name="f32-bucketed",
        build=partial(_bucketed_f32_build, n_buckets=BUCKETED_VARIANT_SEGMENTS),
        n_buckets=BUCKETED_VARIANT_SEGMENTS,
        source="src/repro/dist/overlap.py"),
    RingVariant(
        name="int8",
        build=lambda ax: partial(compressed_ring_all_reduce, axis_name=ax),
        compression="int8",
        source="src/repro/dist/compression.py"),
    RingVariant(
        name="int8-fused",
        build=lambda ax: partial(compressed_ring_all_reduce, axis_name=ax,
                                 fused=True),
        compression="int8-fused",
        source="src/repro/dist/compression.py"),
    RingVariant(
        name="bf16-fused",
        build=lambda ax: partial(fused_wire_all_reduce, axis_name=ax,
                                 wire="bf16"),
        compression="bf16-fused",
        source="src/repro/dist/compression.py"),
    RingVariant(
        name="fp8-fused",
        build=lambda ax: partial(fused_wire_all_reduce, axis_name=ax,
                                 wire="fp8"),
        compression="fp8-fused",
        source="src/repro/dist/compression.py"),
    RingVariant(
        name="ef-int8",
        build=partial(_ef_build, fused=False),
        compression="int8",
        source="src/repro/dist/compression.py"),
    RingVariant(
        name="ef-int8-fused",
        build=partial(_ef_build, fused=True),
        compression="int8-fused",
        source="src/repro/dist/compression.py"),
)


def variant_by_name(name: str) -> RingVariant:
    for v in RING_VARIANTS:
        if v.name == name:
            return v
    raise KeyError(f"no registered ring variant {name!r}; registered: "
                   f"{[v.name for v in RING_VARIANTS]}")


@dataclasses.dataclass(frozen=True)
class StepModeSpec:
    """Wire annotation of one ``make_ring_train_step`` mode.

    The step applies the mode's per-leaf reduction to every gradient leaf
    and one ``pmean`` to the scalar loss, so a traced step must show
    ``sum(leaf expectations) + 1 psum``. For ``collective == "psum"`` the
    expectation is instead ``n_leaves + 1`` psums and no ppermutes. A mode
    with ``n_buckets`` set reduces *per bucket* instead of per leaf
    (``overlap.bucketed_ring_reduce`` with the reverse-autodiff bucket
    plan): the expectation composes the leaf variant over
    ``plan_bucket_sizes(leaf_sizes, n_buckets, reverse=True)``.
    """

    mode: str
    compression: Optional[str] = None
    directions: int = 1
    collective: str = "ppermute"
    halves: bool = False
    n_buckets: Optional[int] = None

    def leaf_variant(self) -> RingVariant:
        """The registered raw collective this mode applies per leaf (per
        bucket for overlap modes)."""
        return variant_by_name({
            "ring": "f32", "bidir": "bidir", "psum": "psum",
            "compressed": "int8", "compressed-fused": "int8-fused",
            "compressed-fused-overlap": "int8-fused",
            "bf16-fused": "bf16-fused", "fp8-fused": "fp8-fused",
        }[self.mode])

    @property
    def wire_dtype(self) -> str:
        """Wire payload element dtype name (part of the compiled-step cache
        key: two modes sharing a dtype still differ by mode, but the dtype
        is the recompile-relevant axis a wire-format change moves)."""
        return {
            None: "float32",
            "int8": "int8",
            "int8-fused": "int8",
            "fp8-fused": "float8_e4m3fn",
            "bf16-fused": "bfloat16",
        }[self.compression]


# default bucket count of the overlap step mode; the executed bucketing
# clamps to the model's leaf count (overlap.plan_buckets), and the verifier
# prices with the identical clamped plan
DEFAULT_OVERLAP_BUCKETS = 4


STEP_MODES: Dict[str, StepModeSpec] = {
    "ring": StepModeSpec(mode="ring"),
    "bidir": StepModeSpec(mode="bidir", directions=2, halves=True),
    "psum": StepModeSpec(mode="psum", directions=0, collective="psum"),
    "compressed": StepModeSpec(mode="compressed", compression="int8"),
    "compressed-fused": StepModeSpec(mode="compressed-fused",
                                     compression="int8-fused"),
    "compressed-fused-overlap": StepModeSpec(
        mode="compressed-fused-overlap", compression="int8-fused",
        n_buckets=DEFAULT_OVERLAP_BUCKETS),
    "bf16-fused": StepModeSpec(mode="bf16-fused", compression="bf16-fused"),
    "fp8-fused": StepModeSpec(mode="fp8-fused", compression="fp8-fused"),
}
