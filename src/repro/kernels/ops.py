"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile natively; on CPU (this container) they run in
interpret mode, which executes the kernel body with numpy-level semantics —
correctness-equivalent, used by the test suite. The model code defaults to
the XLA chunked implementations (sharding-friendly under GSPMD); flip
``use_pallas=True`` per-call or via config on real TPU runs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quant_ring import (
    dequant_accumulate_pallas,
    quantize_pack_pallas,
)
from repro.kernels.rwkv6_wkv import wkv6_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


def interpret_mode() -> bool:
    """The one backend rule for every Pallas call in the repo: compile
    natively on a TPU, run the interpreter anywhere else."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128):
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128):
    return ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=chunk,
                           interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6(r, k, v, logw, u, *, chunk: int = 32):
    return wkv6_pallas(r, k, v, logw, u, chunk=chunk,
                       interpret=interpret_mode())


@jax.jit
def quantize_blockwise(x):
    """Blockwise int8 quantization of a ``(n_blocks, block)`` array:
    returns ``(q int8, scales f32[n_blocks])`` with per-block amax scales."""
    return quantize_pack_pallas(x, interpret=interpret_mode())


@jax.jit
def dequant_accumulate(q, scales, acc=None):
    """Fused ``acc + q * scale`` per block (f32 out); ``acc=None`` is a
    plain blockwise dequantize."""
    return dequant_accumulate_pallas(q, scales, acc,
                                     interpret=interpret_mode())
