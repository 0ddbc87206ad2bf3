"""Pallas kernel tests: interpret-mode execution vs pure-jnp oracles,
with hypothesis shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quant_ring import (
    bf16_add_cast_pallas,
    dequant_accumulate_pallas,
    dequant_add_quantize_pallas,
    quantize_pack_pallas,
)
from repro.kernels.ref import (
    dequant_accumulate_reference,
    mha_reference,
    quantize_block_reference,
    ssd_reference,
    wkv6_reference,
)
from repro.kernels.rwkv6_wkv import wkv6_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.models.rwkv import DECAY_CLAMP, wkv6_chunked
from repro.models.ssm import ssd_chunked


def rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 96),
                                           (False, None)])
def test_flash_attention_matches_ref(dtype, causal, window):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    b, s, hq, hkv, d = 2, 192, 4, 2, 64
    q = rand(keys[0], (b, s, hq, d), dtype)
    k = rand(keys[1], (b, s, hkv, d), dtype)
    v = rand(keys[2], (b, s, hkv, d), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=64, block_k=64, interpret=True)
    ref = mha_reference(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype] * 10)


@given(
    s=st.integers(16, 300),
    hq_mult=st.integers(1, 4),
    hkv=st.integers(1, 3),
    d=st.sampled_from([32, 64]),
    block=st.sampled_from([32, 64, 128]),
)
@settings(max_examples=12, deadline=None)
def test_flash_attention_shape_sweep(s, hq_mult, hkv, d, block):
    hq = hkv * hq_mult
    keys = jax.random.split(jax.random.PRNGKey(s), 3)
    q = rand(keys[0], (1, s, hq, d))
    k = rand(keys[1], (1, s, hkv, d))
    v = rand(keys[2], (1, s, hkv, d))
    out = flash_attention_pallas(q, k, v, causal=True, block_q=block,
                                 block_k=block, interpret=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5,
                               rtol=1e-3)


def test_flash_attention_ops_wrapper_runs():
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = rand(keys[0], (1, 128, 4, 32))
    k = rand(keys[1], (1, 128, 4, 32))
    v = rand(keys[2], (1, 128, 4, 32))
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    assert out.shape == q.shape and bool(jnp.isfinite(out).all())


# ---------------------------------------------------------------------------
# SSD (mamba2)
# ---------------------------------------------------------------------------

def ssd_inputs(key, b=2, s=96, h=3, p=16, n=8, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    x = rand(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(rand(ks[1], (b, s, h))).astype(jnp.float32)
    A = -jnp.exp(rand(ks[2], (h,), scale=0.5)).astype(jnp.float32)
    Bm = rand(ks[3], (b, s, n), dtype, scale=0.5)
    Cm = rand(ks[4], (b, s, n), dtype, scale=0.5)
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("chunk", [16, 32, 96])
def test_ssd_pallas_matches_sequential_ref(chunk):
    x, dt, A, Bm, Cm = ssd_inputs(jax.random.PRNGKey(0))
    out = ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    ref, _ = ssd_reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-3)


def test_ssd_model_chunked_matches_ref():
    x, dt, A, Bm, Cm = ssd_inputs(jax.random.PRNGKey(1))
    y, state = ssd_chunked(x, dt, A, Bm, Cm, chunk=32)
    ref_y, ref_state = ssd_reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref_y),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(state), np.asarray(ref_state),
                               atol=1e-4, rtol=1e-3)


@given(
    s=st.integers(8, 200),
    h=st.integers(1, 4),
    p=st.sampled_from([8, 16]),
    n=st.sampled_from([8, 16]),
    chunk=st.sampled_from([16, 64]),
)
@settings(max_examples=10, deadline=None)
def test_ssd_shape_sweep(s, h, p, n, chunk):
    x, dt, A, Bm, Cm = ssd_inputs(jax.random.PRNGKey(s), b=1, s=s, h=h, p=p, n=n)
    out = ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    ref, _ = ssd_reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-3)


# ---------------------------------------------------------------------------
# RWKV6 WKV
# ---------------------------------------------------------------------------

def wkv_inputs(key, b=2, s=80, h=3, p=16, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    r = rand(ks[0], (b, s, h, p), dtype, scale=0.5)
    k = rand(ks[1], (b, s, h, p), dtype, scale=0.5)
    v = rand(ks[2], (b, s, h, p), dtype, scale=0.5)
    # negative log-decay within the model's clamp
    logw = -jnp.minimum(jnp.exp(rand(ks[3], (b, s, h, p), scale=0.7)),
                        DECAY_CLAMP).astype(jnp.float32)
    u = rand(ks[4], (h, p), scale=0.3)
    return r, k, v, logw, u


@pytest.mark.parametrize("chunk", [8, 32])
def test_wkv_pallas_matches_sequential_ref(chunk):
    r, k, v, logw, u = wkv_inputs(jax.random.PRNGKey(0))
    out = wkv6_pallas(r, k, v, logw, u, chunk=chunk, interpret=True)
    ref, _ = wkv6_reference(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-3)


def test_wkv_model_chunked_matches_ref():
    r, k, v, logw, u = wkv_inputs(jax.random.PRNGKey(1))
    y, state = wkv6_chunked(r, k, v, logw, u)
    ref_y, ref_state = wkv6_reference(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref_y),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(state), np.asarray(ref_state),
                               atol=1e-4, rtol=1e-3)


@given(
    s=st.integers(4, 120),
    h=st.integers(1, 3),
    p=st.sampled_from([8, 16, 32]),
    chunk=st.sampled_from([8, 16, 32]),
)
@settings(max_examples=10, deadline=None)
def test_wkv_shape_sweep(s, h, p, chunk):
    r, k, v, logw, u = wkv_inputs(jax.random.PRNGKey(s), b=1, s=s, h=h, p=p)
    out = wkv6_pallas(r, k, v, logw, u, chunk=chunk, interpret=True)
    ref, _ = wkv6_reference(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-3)


# ---------------------------------------------------------------------------
# quantized-ring hop kernels (repro.kernels.quant_ring)
# ---------------------------------------------------------------------------

def _assert_quant_equiv(q, s, q_ref, s_ref):
    """Pallas-interpret vs XLA oracle: scales may differ by 1 ULP (different
    division lowering), which can shift a boundary value's int8 code by 1."""
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-6)
    assert int(np.abs(np.asarray(q, np.int32)
                      - np.asarray(q_ref, np.int32)).max()) <= 1
    np.testing.assert_allclose(np.asarray(q, np.float32) * np.asarray(s)[:, None],
                               np.asarray(q_ref, np.float32)
                               * np.asarray(s_ref)[:, None],
                               atol=float(np.abs(np.asarray(s)).max()))


@pytest.mark.parametrize("nb,block", [(1, 128), (3, 512), (16, 64), (7, 33)])
def test_quantize_pack_matches_xla_reference(nb, block):
    x = rand(jax.random.PRNGKey(0), (nb, block), scale=3.0)
    q, s = quantize_pack_pallas(x, interpret=True)
    q_ref, s_ref = quantize_block_reference(x)
    _assert_quant_equiv(q, s, q_ref, s_ref)
    # per-element round-off bounded by half the block's scale
    err = np.abs(np.asarray(q, np.float32) * np.asarray(s)[:, None]
                 - np.asarray(x))
    assert (err <= np.asarray(s)[:, None] / 2 + 1e-7).all()


def test_quantize_pack_all_zero_blocks():
    """All-zero sub-blocks quantize to scale 1.0 / payload 0 (well-defined
    dequantization), including when only some rows are zero."""
    x = jnp.zeros((4, 256), jnp.float32).at[2].set(1.0)
    q, s = quantize_pack_pallas(x, interpret=True)
    assert np.asarray(s)[0] == 1.0 and np.asarray(s)[3] == 1.0
    assert np.asarray(s)[2] == pytest.approx(1.0 / 127.0)
    assert (np.asarray(q)[[0, 1, 3]] == 0).all()
    back = dequant_accumulate_pallas(q, s, None, interpret=True)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=1e-7)


@pytest.mark.parametrize("with_acc", [False, True])
def test_dequant_accumulate_matches_xla_reference(with_acc):
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    x = rand(keys[0], (6, 384), scale=2.0)
    q, s = quantize_block_reference(x)
    acc = rand(keys[1], (6, 384)) if with_acc else None
    out = dequant_accumulate_pallas(q, s, acc, interpret=True)
    ref = dequant_accumulate_reference(q, s, acc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6,
                               atol=1e-6)


def test_dequant_add_quantize_matches_two_pass_composition():
    """The one-pass hop kernel == quantize_pack(dequant_accumulate(...))."""
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    x = rand(keys[0], (8, 256), scale=2.0)
    acc = rand(keys[1], (8, 256), scale=2.0)
    q, s = quantize_pack_pallas(x, interpret=True)
    q1, s1 = dequant_add_quantize_pallas(q, s, acc, interpret=True)
    two_pass = dequant_accumulate_pallas(q, s, acc, interpret=True)
    q2, s2 = quantize_pack_pallas(two_pass, interpret=True)
    _assert_quant_equiv(q1, s1, q2, s2)


@given(
    nb=st.integers(1, 12),
    block=st.sampled_from([16, 33, 128, 512]),
    scale=st.sampled_from([1e-3, 1.0, 50.0]),
)
@settings(max_examples=12, deadline=None)
def test_quant_ring_kernels_shape_sweep(nb, block, scale):
    x = rand(jax.random.PRNGKey(nb * block), (nb, block), scale=scale)
    q, s = quantize_pack_pallas(x, interpret=True)
    q_ref, s_ref = quantize_block_reference(x)
    _assert_quant_equiv(q, s, q_ref, s_ref)
    out = dequant_accumulate_pallas(q, s, x, interpret=True)
    ref = dequant_accumulate_reference(q, s, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_quant_ring_rows_per_tile_validation():
    x = rand(jax.random.PRNGKey(5), (96, 128))
    with pytest.raises(ValueError, match="must divide"):
        quantize_pack_pallas(x, interpret=True, rows_per_tile=40)
    # a tile the TPU compiler would refuse: neither the whole dimension
    # nor a multiple of the native int8 tile height
    with pytest.raises(ValueError, match="multiple of 32"):
        quantize_pack_pallas(x, interpret=True, rows_per_tile=48)
    # a valid explicit tiling matches the default
    q1, s1 = quantize_pack_pallas(x, interpret=True, rows_per_tile=32)
    q2, s2 = quantize_pack_pallas(x, interpret=True)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


@pytest.mark.parametrize("kernel", ["quantize_pack", "dequant_add_quantize",
                                    "dequant_accumulate", "dequant",
                                    "bf16_add_cast"])
def test_quant_ring_ragged_last_tile_bit_identical(kernel, monkeypatch):
    """A row count with no aligned divisor under the VMEM budget runs a
    cdiv grid with a ragged last tile; every row must come out exactly as
    the single-tile call computes it."""
    from repro.kernels import quant_ring

    nb, block = 100, 128
    x = rand(jax.random.PRNGKey(11), (nb, block), scale=3.0)
    acc = rand(jax.random.PRNGKey(12), (nb, block))
    q, s = quantize_pack_pallas(x, interpret=True)

    def run():
        if kernel == "quantize_pack":
            return quantize_pack_pallas(x, interpret=True)
        if kernel == "dequant_add_quantize":
            return dequant_add_quantize_pallas(q, s, acc, interpret=True)
        if kernel == "dequant_accumulate":
            return (dequant_accumulate_pallas(q, s, acc, interpret=True),)
        if kernel == "dequant":
            return (dequant_accumulate_pallas(q, s, None, interpret=True),)
        return (bf16_add_cast_pallas(x.astype(jnp.bfloat16), acc,
                                     interpret=True),)

    whole = run()
    # budget for exactly 32 rows of the widest (10 B/elem) working set:
    # 100 rows then tile as 32 + 32 + 32 + a ragged 4
    monkeypatch.setattr(quant_ring, "_TILE_BUDGET_BYTES", 32 * block * 10)
    assert quant_ring._rows_per_tile(nb, block, None, 10) == 32
    tiled = run()
    for a, b in zip(whole, tiled):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_quant_ring_ops_wrappers_run():
    x = rand(jax.random.PRNGKey(3), (4, 128))
    q, s = ops.quantize_blockwise(x)
    assert q.shape == x.shape and q.dtype == jnp.int8 and s.shape == (4,)
    out = ops.dequant_accumulate(q, s, x)
    assert out.shape == x.shape and bool(jnp.isfinite(out).all())
    deq = ops.dequant_accumulate(q, s)
    np.testing.assert_allclose(np.asarray(out) - np.asarray(deq),
                               np.asarray(x), atol=1e-6)


# ---------------------------------------------------------------------------
# quant ring: fp8 + bf16 wire-format kernels
# ---------------------------------------------------------------------------

def test_quantize_pack_fp8_wire():
    """fp8 payloads: same blockwise scale rule (amax -> FP8_MAX), dtype cast
    does the rounding (no integer round), dequant bounded by the e4m3
    mantissa budget."""
    from repro.kernels.quant_ring import FP8_DTYPE, FP8_MAX

    x = rand(jax.random.PRNGKey(4), (6, 256), scale=3.0)
    q, s = quantize_pack_pallas(x, interpret=True, wire_dtype=FP8_DTYPE)
    assert q.dtype == FP8_DTYPE and s.shape == (6,)
    amax = np.abs(np.asarray(x)).max(axis=1)
    np.testing.assert_allclose(np.asarray(s), amax / FP8_MAX, rtol=1e-6)
    back = np.asarray(q, np.float32) * np.asarray(s)[:, None]
    # e4m3: 3-bit mantissa -> relative half-step 2^-4 per element
    err = np.abs(back - np.asarray(x))
    assert (err <= np.abs(np.asarray(x)) * 2.0 ** -4 + 1e-6).all()


def test_fp8_dequant_add_quantize_composition():
    """The fp8 one-pass hop == quantize_pack(dequant_accumulate(...)) with
    the wire dtype inherited from the payload."""
    from repro.kernels.quant_ring import FP8_DTYPE

    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    x = rand(keys[0], (8, 128), scale=2.0)
    acc = rand(keys[1], (8, 128), scale=2.0)
    q, s = quantize_pack_pallas(x, interpret=True, wire_dtype=FP8_DTYPE)
    q1, s1 = dequant_add_quantize_pallas(q, s, acc, interpret=True)
    assert q1.dtype == FP8_DTYPE
    two_pass = dequant_accumulate_pallas(q, s, acc, interpret=True)
    q2, s2 = quantize_pack_pallas(two_pass, interpret=True,
                                  wire_dtype=FP8_DTYPE)
    np.testing.assert_array_equal(np.asarray(q1, np.float32),
                                  np.asarray(q2, np.float32))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


def test_fp8_all_zero_blocks_well_defined():
    from repro.kernels.quant_ring import FP8_DTYPE

    x = jnp.zeros((3, 128), jnp.float32).at[1].set(2.0)
    q, s = quantize_pack_pallas(x, interpret=True, wire_dtype=FP8_DTYPE)
    assert np.asarray(s)[0] == 1.0 and np.asarray(s)[2] == 1.0
    back = dequant_accumulate_pallas(q, s, None, interpret=True)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=1e-7)


def test_wire_qmax_rejects_unquantized_dtypes():
    from repro.kernels.quant_ring import FP8_DTYPE, wire_qmax

    assert wire_qmax(jnp.int8) == 127.0
    assert wire_qmax(FP8_DTYPE) == 448.0
    with pytest.raises(ValueError, match="unsupported quantized wire"):
        wire_qmax(jnp.bfloat16)


def test_bf16_cast_pack_and_accumulate_match_jnp():
    from repro.kernels.quant_ring import (
        bf16_accumulate_pallas,
        bf16_add_cast_pallas,
        cast_pack_bf16_pallas,
    )

    keys = jax.random.split(jax.random.PRNGKey(6), 2)
    x = rand(keys[0], (5, 384), scale=4.0)
    acc = rand(keys[1], (5, 384), scale=4.0)
    wire = cast_pack_bf16_pallas(x, interpret=True)
    assert wire.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(wire, np.float32),
                                  np.asarray(x.astype(jnp.bfloat16),
                                             np.float32))
    # steady-state hop: f32 accumulate in VMEM, bf16 back out
    hop = bf16_add_cast_pallas(wire, acc, interpret=True)
    ref = (acc.astype(jnp.float32)
           + wire.astype(jnp.float32)).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(hop, np.float32),
                                  np.asarray(ref, np.float32))
    # final accumulate -> f32; acc=None is a plain upcast
    out = bf16_accumulate_pallas(wire, acc, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(acc.astype(jnp.float32) + wire.astype(jnp.float32)),
        rtol=1e-6)
    up = bf16_accumulate_pallas(wire, None, interpret=True)
    np.testing.assert_array_equal(np.asarray(up),
                                  np.asarray(wire, np.float32))
