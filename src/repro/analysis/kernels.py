"""Static Pallas-kernel checker for ``repro.kernels.quant_ring``.

``python -m repro.analysis.kernels`` validates (shape, block) configurations
of the fused quantized-ring kernels *without a TPU* — the checks are pure
arithmetic over the same constants the kernels use (imported from
``quant_ring``, not copied, so they cannot drift):

  * **tile shape** — an explicit ``rows_per_tile`` override must divide
    ``n_blocks`` and be ``n_blocks`` or a multiple of ``ROW_ALIGN`` (one
    that is not is rejected, exactly as ``_rows_per_tile`` rejects it at
    trace time); the resolver's own choice may leave a ragged last tile;
  * **tile budget** — the per-tile VMEM working set
    (``rows * block * bytes_per_elem + rows * SCALE_BYTES`` for the scale
    rows) must fit ``_TILE_BUDGET_BYTES``. ``_rows_per_tile`` itself does
    NOT enforce this when the smallest legal tile already exceeds the
    budget (a huge ``block`` resolves to ``ROW_ALIGN`` rows, or to the
    whole ``n_blocks`` below that, and over-commits VMEM) — the checker
    closes that gap;
  * **VMEM bound** — the double-buffered working set (Pallas pipelines the
    next tile's copy while the current one computes) must fit the ~16 MB
    VMEM of a TPU core;
  * **lane alignment** — ``block % 128 != 0`` wastes vector lanes on the
    last tile column (a warning, not a rejection: interpret mode and the
    wire format are still correct);
  * **scale-trailer consistency** — the wire message the kernels feed
    (int8 payload ++ bitcast f32 trailer, ``SCALE_BYTES`` per sub-block)
    must agree with both ``repro.dist.compression.compressed_wire_bytes``
    and the scheduler's ``repro.core.rar_model`` pricing, and
    ``SCALE_BYTES`` must equal the f32 itemsize the bitcast assumes.

``--execute`` additionally runs each *accepted* small config through the
real kernels in ``interpret=True`` mode and checks the packed message
length — still no TPU required.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import List, Optional, Tuple

from repro.kernels.quant_ring import _TILE_BUDGET_BYTES, _rows_per_tile

__all__ = ["KernelSpec", "CheckResult", "check_spec", "default_suite", "main"]

# one TPU core's VMEM; the double-buffered working set must fit with the
# same margin the kernels assume (_TILE_BUDGET_BYTES is carved out of this)
VMEM_BYTES = 16 * 1024 * 1024
LANE = 128  # TPU vector-lane width: the trailing dim tiles in multiples

# per-element VMEM bytes of each kernel's tile working set — MUST match the
# bytes_per_elem each quant_ring entry point passes to _rows_per_tile
# (asserted against the resolved tiling in tests/test_analysis.py):
#   quantize_pack        f32 in (4) + int8 out (1)            = 5
#   dequant_add_quantize int8 in (1) + f32 acc (4) + int8 out = 6
#   dequant_accumulate   int8 in (1) + f32 acc (4) + f32 out  = 9
#   dequant              int8 in (1) + f32 out (4)            = 5
BYTES_PER_ELEM = {
    "quantize_pack": 5,
    "dequant_add_quantize": 6,
    "dequant_accumulate": 9,
    "dequant": 5,
}


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One (shape, block) configuration of a quant_ring kernel.

    ``scale_bytes`` overrides the trailer bytes-per-scale the spec claims to
    put on the wire (default: the kernels' ``SCALE_BYTES``). Any value other
    than the f32 itemsize the bitcast trailer emits must be rejected by the
    trailer-consistency check — the must-reject suite pins this with the
    same :data:`repro.analysis.fixtures.TRAILER_MISMATCH_SCALE_BYTES` layout
    the collective verifier's broken-trailer ring uses.
    """

    n_blocks: int
    block: int
    kernel: str = "quantize_pack"
    rows_per_tile: Optional[int] = None
    scale_bytes: Optional[int] = None

    def __str__(self) -> str:
        rows = "" if self.rows_per_tile is None else \
            f", rows={self.rows_per_tile}"
        sb = "" if self.scale_bytes is None else \
            f", scale_bytes={self.scale_bytes}"
        return f"{self.kernel}(n_blocks={self.n_blocks}, " \
               f"block={self.block}{rows}{sb})"


@dataclasses.dataclass(frozen=True)
class CheckResult:
    spec: KernelSpec
    ok: bool
    rows: Optional[int]          # resolved rows_per_tile (None if rejected)
    tile_bytes: int              # single-tile VMEM working set
    errors: Tuple[str, ...]
    warnings: Tuple[str, ...]


def check_spec(spec: KernelSpec) -> CheckResult:
    """Statically validate one kernel configuration (no jax import)."""
    errors: List[str] = []
    warnings: List[str] = []
    bpe = BYTES_PER_ELEM.get(spec.kernel)
    if bpe is None:
        return CheckResult(spec, False, None, 0,
                           (f"unknown kernel {spec.kernel!r} (known: "
                            f"{sorted(BYTES_PER_ELEM)})",), ())
    if spec.n_blocks < 1 or spec.block < 1:
        return CheckResult(spec, False, None, 0,
                           ("n_blocks and block must be >= 1",), ())

    rows: Optional[int]
    try:
        # the real resolver — an explicit override that does not divide
        # n_blocks, or is not a legal TPU tile height, raises here exactly
        # as it would at pallas_call trace time
        rows = _rows_per_tile(spec.n_blocks, spec.block, spec.rows_per_tile,
                              bytes_per_elem=bpe)
    except ValueError as exc:
        return CheckResult(spec, False, None, 0, (str(exc),), ())

    # tile working set: payload/acc/out rows plus the f32 scale row(s),
    # which BlockSpec also stages per tile
    tile_bytes = rows * spec.block * bpe + rows * _scale_bytes()
    if tile_bytes > _TILE_BUDGET_BYTES:
        errors.append(
            f"tile working set {tile_bytes} B exceeds _TILE_BUDGET_BYTES="
            f"{_TILE_BUDGET_BYTES} B (rows={rows}); _rows_per_tile cannot "
            f"shrink below one sub-block row — reduce block")
    if 2 * tile_bytes > VMEM_BYTES:
        errors.append(
            f"double-buffered working set {2 * tile_bytes} B exceeds "
            f"VMEM ({VMEM_BYTES} B)")
    if spec.block % LANE:
        warnings.append(
            f"block={spec.block} is not a multiple of the {LANE}-wide "
            "vector lane — last-column lanes idle on TPU")

    errors.extend(_check_trailer_consistency(spec))
    return CheckResult(spec, not errors, rows, tile_bytes,
                       tuple(errors), tuple(warnings))


def _scale_bytes() -> int:
    from repro.dist.compression import SCALE_BYTES
    return SCALE_BYTES


def _check_trailer_consistency(spec: KernelSpec) -> List[str]:
    """The payload ++ scale-trailer layout vs the two byte formulas.

    A hop message for ``(n_blocks, block)`` is ``n_blocks * block`` int8
    payload bytes plus ``SCALE_BYTES`` per sub-block, and the fused ring
    pays ``2 * (w - 1)`` such messages per all-reduce. Both
    ``compressed_wire_bytes`` (the executable accounting) and
    ``rar_compressed_bytes_per_worker`` (the scheduler's Eq. (1) pricing)
    must reproduce that total for a gradient that shards evenly.
    """
    import numpy as np

    from repro.core.rar_model import rar_compressed_bytes_per_worker
    from repro.dist.compression import SCALE_BYTES, compressed_wire_bytes

    errors: List[str] = []
    f32_bytes = np.dtype(np.float32).itemsize
    scale_bytes = SCALE_BYTES if spec.scale_bytes is None else \
        int(spec.scale_bytes)
    if scale_bytes != f32_bytes:
        errors.append(
            f"trailer scale_bytes={scale_bytes} != f32 itemsize "
            f"{f32_bytes} — the bitcast trailer the kernels emit does not "
            "match this wire layout")

    nb, block = spec.n_blocks, spec.block
    message = nb * block + scale_bytes * nb  # payload ++ trailer
    for w in (2, 4):
        d = w * nb * block  # shards into w chunks of exactly (nb, block)
        expect = 2 * (w - 1) * message
        wire = float(compressed_wire_bytes(d, w, fused=True, block=block))
        if wire != float(expect):
            errors.append(
                f"trailer drift (w={w}): kernels send "
                f"2*(w-1)*({nb}*{block} + {SCALE_BYTES}*{nb}) = {expect} B "
                f"but compressed_wire_bytes prices {wire!r} B")
        model = float(rar_compressed_bytes_per_worker(
            float(d), w, fused=True, block=block))
        if abs(model - expect) > 1e-6 * expect:
            errors.append(
                f"pricing drift (w={w}): rar_model prices {model!r} B but "
                f"the fused ring sends {expect} B")
    return errors


def execute_spec(spec: KernelSpec) -> Optional[str]:
    """Run an accepted config through the real kernel in interpret mode.

    Returns an error string, or None on success. Small shapes only — the
    caller gates on payload size.
    """
    import numpy as np

    from repro.dist.compression import SCALE_BYTES, pack_hop_message
    from repro.kernels import quant_ring as qr

    rng = np.random.default_rng(0)
    x = rng.standard_normal((spec.n_blocks, spec.block)).astype(np.float32)
    import jax.numpy as jnp

    xj = jnp.asarray(x)
    q, scales = qr.quantize_pack_pallas(
        xj, interpret=True, rows_per_tile=spec.rows_per_tile)
    if spec.kernel == "dequant_add_quantize":
        q, scales = qr.dequant_add_quantize_pallas(
            q, scales, xj, interpret=True,
            rows_per_tile=spec.rows_per_tile)
    elif spec.kernel in ("dequant_accumulate", "dequant"):
        acc = xj if spec.kernel == "dequant_accumulate" else None
        out = qr.dequant_accumulate_pallas(
            q, scales, acc, interpret=True,
            rows_per_tile=spec.rows_per_tile)
        if out.shape != x.shape:
            return f"dequant output shape {out.shape} != {x.shape}"
        return None
    msg = pack_hop_message(q, scales)
    expect = spec.n_blocks * spec.block + SCALE_BYTES * spec.n_blocks
    if msg.size != expect:
        return (f"packed message is {msg.size} B, expected payload+trailer "
                f"= {expect} B")
    return None


def default_suite() -> List[Tuple[KernelSpec, bool]]:
    """(spec, expected-to-pass) pairs exercised by the CLI and CI.

    Covers each kernel's byte budget, an explicit rows override, and three
    configurations the checker must *reject*: a non-dividing override, a
    block so large that one sub-block row overflows the tile budget (the
    gap ``_rows_per_tile`` itself does not police), and the shared
    trailer-layout mismatch fixture (a 2-byte-per-scale trailer the
    collective verifier's broken-trailer ring also seeds — one defect,
    caught by both analyses).
    """
    from repro.analysis.fixtures import trailer_mismatch_kernel_spec

    return [
        (KernelSpec(64, 4096), True),
        (KernelSpec(512, 256, kernel="dequant_add_quantize",
                    rows_per_tile=128), True),
        (KernelSpec(7, 4096, kernel="dequant_accumulate"), True),
        (KernelSpec(48, 512, rows_per_tile=5), False),   # 5 does not divide 48
        (KernelSpec(4, 1 << 20), False),                 # one row > 2 MB tile
        (trailer_mismatch_kernel_spec(), False),         # 2 B scale trailer
    ]


def _parse_spec(text: str) -> KernelSpec:
    """``n_blocks,block[,kernel[,rows]]`` from the --check flag."""
    parts = text.split(",")
    if len(parts) < 2:
        raise argparse.ArgumentTypeError(
            f"--check wants n_blocks,block[,kernel[,rows]], got {text!r}")
    kernel = parts[2] if len(parts) > 2 and parts[2] else "quantize_pack"
    rows = int(parts[3]) if len(parts) > 3 and parts[3] else None
    return KernelSpec(int(parts[0]), int(parts[1]), kernel, rows)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis.kernels",
        description="static Pallas-kernel checker for repro.kernels "
                    "(module docstring has the rule list)")
    parser.add_argument("--check", action="append", type=_parse_spec,
                        metavar="NB,BLOCK[,KERNEL[,ROWS]]", default=None,
                        help="check this config instead of the default "
                             "suite (repeatable); exit 1 if any fails")
    parser.add_argument("--execute", action="store_true",
                        help="also run accepted small configs through the "
                             "real kernels in interpret mode (no TPU)")
    args = parser.parse_args(argv)

    failures = 0
    if args.check:
        suite = [(s, True) for s in args.check]
    else:
        suite = default_suite()
    for spec, expect_ok in suite:
        result = check_spec(spec)
        verdict = "OK" if result.ok else "REJECT"
        detail = f"rows={result.rows}, tile={result.tile_bytes} B" \
            if result.rows is not None else ""
        print(f"kernels: {verdict:6s} {spec}  {detail}")
        for w in result.warnings:
            print(f"kernels:   warning: {w}")
        for e in result.errors:
            print(f"kernels:   {e}")
        if result.ok != expect_ok:
            print(f"kernels:   EXPECTED {'OK' if expect_ok else 'REJECT'}")
            failures += 1
            continue
        if args.execute and result.ok and \
                spec.n_blocks * spec.block <= (1 << 20):
            err = execute_spec(spec)
            if err is None:
                print("kernels:   interpret-mode execution OK")
            else:
                print(f"kernels:   interpret-mode execution FAILED: {err}")
                failures += 1
    status = "OK" if not failures else f"{failures} unexpected outcome(s)"
    print(f"kernels: {len(suite)} config(s) -> {status}")
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
