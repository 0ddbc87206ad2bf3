"""Distribution layer tests.

Ring-collective correctness needs >1 device; those tests run a subprocess
with XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main test
process keeps seeing 1 device (per the dry-run isolation rule).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.compression import (
    compressed_wire_bytes,
    dequantize,
    quantization_error,
    quantize,
)
from repro.dist.collectives import ring_wire_elements
from repro.dist.overlap import bucketed_psum, microbatch_grads

REPO_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "src")


def run_multidevice(snippet: str) -> str:
    """Run a python snippet in a subprocess with 8 host devices."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import jax.numpy as jnp
        import numpy as np
        from functools import partial
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.dist.collectives import (
            ring_all_reduce, ring_reduce_scatter, bidirectional_ring_all_reduce)
        from repro.dist.compression import compressed_ring_all_reduce, \\
            ef_compressed_all_reduce
        mesh = jax.make_mesh((8,), ("d",))
    """) + textwrap.dedent(snippet)
    env = dict(os.environ, PYTHONPATH=REPO_SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


@pytest.mark.slow
def test_ring_all_reduce_matches_psum():
    out = run_multidevice("""
        x = jnp.arange(8 * 37, dtype=jnp.float32).reshape(8, 37)
        f = shard_map(lambda a: ring_all_reduce(a, "d"), mesh=mesh,
                      in_specs=P("d", None), out_specs=P("d", None))
        got = f(x)
        want = jnp.tile(x.sum(axis=0, keepdims=True), (8, 1))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
        print("RING_OK")
    """)
    assert "RING_OK" in out


@pytest.mark.slow
def test_bidirectional_ring_matches_psum():
    out = run_multidevice("""
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 101), jnp.float32)
        f = shard_map(lambda a: bidirectional_ring_all_reduce(a, "d"),
                      mesh=mesh, in_specs=P("d", None), out_specs=P("d", None))
        got = f(x)
        want = jnp.tile(x.sum(axis=0, keepdims=True), (8, 1))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        print("BIDIR_OK")
    """)
    assert "BIDIR_OK" in out


@pytest.mark.slow
def test_ring_reduce_scatter_chunks():
    out = run_multidevice("""
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 64), jnp.float32)
        f = shard_map(lambda a: ring_reduce_scatter(a, "d"), mesh=mesh,
                      in_specs=P("d", None), out_specs=P("d"))
        got = np.asarray(f(x)).reshape(8, 8)  # row i = worker i's chunk
        total = np.asarray(x.sum(axis=0)).reshape(8, 8)
        for i in range(8):
            np.testing.assert_allclose(got[i], total[(i + 1) % 8],
                                       rtol=1e-5, atol=1e-5)
        print("RS_OK")
    """)
    assert "RS_OK" in out


@pytest.mark.slow
def test_compressed_ring_close_to_exact():
    out = run_multidevice("""
        x = jax.random.normal(jax.random.PRNGKey(2), (8, 512), jnp.float32)
        f = shard_map(lambda a: compressed_ring_all_reduce(a, "d"), mesh=mesh,
                      in_specs=P("d", None), out_specs=P("d", None))
        got = np.asarray(f(x))
        want = np.asarray(x.sum(axis=0))
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert rel < 0.15, rel  # int8 per-hop rounding, no EF
        print("CRING_OK", rel)
    """)
    assert "CRING_OK" in out


def test_quantize_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,), jnp.float32)
    qx = quantize(x)
    back = dequantize(qx, x.size, x.shape)
    err = jnp.abs(back - x).max()
    assert float(err) <= float(jnp.abs(x).max()) / 127.0 + 1e-6
    res = quantization_error(x)
    np.testing.assert_allclose(np.asarray(x - res), np.asarray(back), rtol=1e-6)


def test_wire_cost_formulas():
    # paper: 2d(w-1)/w elements; int8 ring ~3.88x cheaper than f32
    assert ring_wire_elements(1000, 4) == pytest.approx(1500.0)
    ratio = (ring_wire_elements(10_000, 8) * 4) / compressed_wire_bytes(10_000, 8)
    assert 3.5 < ratio < 4.0


def test_microbatch_grads_matches_full_batch():
    def loss_fn(p, b):
        pred = b["x"] @ p["w"]
        return jnp.mean((pred - b["y"]) ** 2)

    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (16, 4))}
    batch = {"x": jax.random.normal(key, (32, 16)),
             "y": jax.random.normal(key, (32, 4))}
    l1, g1 = jax.value_and_grad(loss_fn)(params, batch)
    l2, g2 = microbatch_grads(loss_fn, params, batch, n_microbatches=4)
    assert float(l1) == pytest.approx(float(l2), rel=1e-5)
    np.testing.assert_allclose(np.asarray(g1["w"]), np.asarray(g2["w"]),
                               rtol=1e-5)


def test_bucketed_psum_single_device_identity():
    # on 1 device psum over a size-1 axis is identity; checks bucketing logic
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    mesh = jax.make_mesh((1,), ("d",))
    grads = {"a": jnp.ones((8, 8)), "b": jnp.ones((128,)), "c": jnp.ones((2, 2))}
    f = shard_map(lambda g: bucketed_psum(g, "d", n_buckets=2), mesh=mesh,
                  in_specs=P(), out_specs=P())
    out = f(grads)
    for k in grads:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(grads[k]))


def test_microbatch_grads_rejects_nondivisible_split():
    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["w"]) ** 2)

    params = {"w": jnp.ones((4, 2))}
    batch = {"x": jnp.ones((10, 4))}
    with pytest.raises(ValueError, match="not divisible"):
        microbatch_grads(loss_fn, params, batch, n_microbatches=3)


def test_microbatch_grads_rejects_more_microbatches_than_batch():
    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["w"]) ** 2)

    params = {"w": jnp.ones((4, 2))}
    batch = {"x": jnp.ones((8, 4))}
    with pytest.raises(ValueError, match="exceeds the batch's leading dim"):
        microbatch_grads(loss_fn, params, batch, n_microbatches=16)


# ---------------------------------------------------------------------------
# bucketed reductions: psum coalescing + the overlap ring pipeline
# ---------------------------------------------------------------------------

def _traced_psum_count(fn, grads):
    """psum equations in the shard_mapped jaxpr (no devices needed)."""
    from jax.sharding import AbstractMesh, PartitionSpec as P
    from repro.analysis.collectives import collect_collectives

    mesh = AbstractMesh((8,), ("d",))
    templates = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), grads)
    closed = jax.make_jaxpr(jax.shard_map(
        fn, mesh=mesh, in_specs=P(), out_specs=P(),
        check_vma=False))(templates)
    return sum(s.repeat for s in collect_collectives(closed)
               if s.primitive == "psum")


def test_bucketed_psum_bucket_count_matches_traced_psums():
    """The coalescing promise, pinned at the jaxpr level: one psum per
    (bucket, dtype) — never one per leaf."""
    from repro.dist.overlap import plan_buckets

    grads = {f"p{i}": jnp.ones((sz,), jnp.float32)
             for i, sz in enumerate([40, 24, 100, 8, 60])}
    sizes = [leaf.size for leaf in jax.tree.leaves(grads)]
    for n_buckets in (1, 2, 3, 5, 9):
        n = _traced_psum_count(
            lambda g, nb=n_buckets: bucketed_psum(g, "d", n_buckets=nb),
            grads)
        assert n == len(plan_buckets(sizes, n_buckets))


def test_bucketed_psum_mixed_dtypes_split_per_bucket():
    """A mixed-dtype bucket issues one psum per dtype present (payloads are
    concatenated per dtype — no silent upcast on the wire)."""
    grads = {"a": jnp.ones((64,), jnp.float32),
             "b": jnp.ones((64,), jnp.bfloat16),
             "c": jnp.ones((16,), jnp.float32)}
    # plan over sizes [64, 64, 16] at n_buckets=2: bucket {a, b} (2 dtypes)
    # + bucket {c} (1 dtype) -> 3 psums
    n = _traced_psum_count(lambda g: bucketed_psum(g, "d", n_buckets=2),
                           grads)
    assert n == 3


@pytest.mark.slow
def test_bucketed_psum_matches_leafwise_psum_multidevice():
    out = run_multidevice("""
        from repro.dist.overlap import bucketed_psum
        key = jax.random.PRNGKey(7)
        ks = jax.random.split(key, 4)
        grads = {
            "w1": jax.random.normal(ks[0], (8, 33, 7), jnp.float32),
            "w2": jax.random.normal(ks[1], (8, 129), jnp.float32),
            "b16": jax.random.normal(ks[2], (8, 65), jnp.float32
                                     ).astype(jnp.bfloat16),
            "tiny": jax.random.normal(ks[3], (8, 3), jnp.float32),
        }
        f = shard_map(lambda g: bucketed_psum(g, "d", n_buckets=2),
                      mesh=mesh, in_specs=P("d"), out_specs=P("d"))
        ref = shard_map(
            lambda g: jax.tree.map(lambda x: jax.lax.psum(x, "d"), g),
            mesh=mesh, in_specs=P("d"), out_specs=P("d"))
        got, want = f(grads), ref(grads)
        for k in grads:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]))
        print("BUCKET_PSUM_OK")
    """)
    assert "BUCKET_PSUM_OK" in out


def test_bucketed_psum_more_buckets_than_leaves():
    """n_buckets beyond the leaf count clamps — at most one bucket per
    leaf, and every leaf is covered exactly once."""
    from repro.dist.overlap import plan_buckets

    grads = {"a": jnp.ones((7,)), "b": jnp.ones((7,))}
    n = _traced_psum_count(lambda g: bucketed_psum(g, "d", n_buckets=64),
                           grads)
    assert n == 2
    assert plan_buckets([7, 7], 64) == [[0], [1]]
    # unequal leaves may merge below the clamp, but coverage is exact
    plan = plan_buckets([5, 7, 100], 64)
    assert sorted(i for b in plan for i in b) == [0, 1, 2]
    assert len(plan) <= 3


def test_bucketed_psum_single_leaf_and_empty_tree():
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    mesh = jax.make_mesh((1,), ("d",))
    single = {"only": jnp.arange(12.0)}
    out = shard_map(lambda g: bucketed_psum(g, "d", n_buckets=4), mesh=mesh,
                    in_specs=P(), out_specs=P())(single)
    np.testing.assert_array_equal(np.asarray(out["only"]),
                                  np.asarray(single["only"]))
    assert bucketed_psum({}, "d", n_buckets=4) == {}


def test_plan_buckets_reverse_autodiff_order():
    """reverse=True packs from the LAST leaf backwards: the bucket holding
    the tree's last leaves (first gradients out of reverse-mode AD) is
    planned — and launched — first."""
    from repro.dist.overlap import plan_buckets, plan_bucket_sizes

    sizes = [10, 10, 10, 100]
    fwd = plan_buckets(sizes, 2)
    rev = plan_buckets(sizes, 2, reverse=True)
    assert fwd == [[0, 1, 2, 3]] or len(fwd) == 2  # greedy fwd packing
    assert rev[0] == [3]          # the last (largest) leaf rings first
    assert sorted(i for b in rev for i in b) == [0, 1, 2, 3]
    assert plan_bucket_sizes(sizes, 2) == [100, 30]


def test_bucketed_ring_reduce_single_device_identity():
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.dist.overlap import bucketed_ring_reduce

    mesh = jax.make_mesh((1,), ("d",))
    grads = {"a": jnp.arange(24.0).reshape(4, 6), "b": jnp.arange(5.0)}
    out = shard_map(
        lambda g: bucketed_ring_reduce(g, "d", n_buckets=2), mesh=mesh,
        in_specs=P(), out_specs=P())(grads)
    for k in grads:  # w=1: the fused ring passes through bit-identically
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(grads[k]))


def test_bucketed_ring_reduce_rejects_bad_variant():
    from repro.dist.overlap import bucketed_ring_reduce

    with pytest.raises(KeyError, match="no registered ring variant"):
        bucketed_ring_reduce({"a": jnp.ones(4)}, "d", variant="nope")
    with pytest.raises(TypeError, match="registered variant name"):
        bucketed_ring_reduce({"a": jnp.ones(4)}, "d", variant=42)


def test_bucketed_ring_reduce_traced_bytes_match_wire_formula():
    """The tentpole pricing pin: the overlap reduction's traced per-bucket
    ppermute chains carry exactly wire_formula('int8-fused') bytes over the
    reverse-autodiff bucket plan — and one chain per bucket."""
    from jax.sharding import AbstractMesh, PartitionSpec as P
    from repro.analysis.collectives import collect_collectives
    from repro.core.rar_model import wire_formula
    from repro.dist.overlap import bucketed_ring_reduce, plan_bucket_sizes

    grads = {f"p{i}": jnp.ones((sz,), jnp.float32)
             for i, sz in enumerate([300, 40, 4000, 50, 600])}
    sizes = [leaf.size for leaf in jax.tree.leaves(grads)]
    formula = wire_formula("int8-fused")
    w, n_buckets = 4, 3
    mesh = AbstractMesh((w,), ("d",))
    templates = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), grads)
    closed = jax.make_jaxpr(jax.shard_map(
        lambda g: bucketed_ring_reduce(g, "d", n_buckets=n_buckets),
        mesh=mesh, in_specs=P(), out_specs=P(),
        check_vma=False))(templates)
    sites = [s for s in collect_collectives(closed)
             if s.primitive == "ppermute"]
    payloads = plan_bucket_sizes(sizes, n_buckets, reverse=True)
    assert sum(s.repeat for s in sites) == \
        sum(formula.messages(w) for _ in payloads)
    assert sum(s.nbytes * s.repeat for s in sites) == pytest.approx(
        sum(formula.bytes_per_worker(d, w) for d in payloads))


@pytest.mark.slow
def test_bucketed_ring_reduce_matches_psum_multidevice():
    out = run_multidevice("""
        from repro.dist.overlap import bucketed_ring_reduce
        key = jax.random.PRNGKey(11)
        ks = jax.random.split(key, 3)
        grads = {
            "w1": jax.random.normal(ks[0], (8, 41, 9), jnp.float32),
            "w2": jax.random.normal(ks[1], (8, 517), jnp.float32),
            "b": jax.random.normal(ks[2], (8, 13), jnp.float32),
        }
        f = shard_map(lambda g: bucketed_ring_reduce(g, "d", n_buckets=2),
                      mesh=mesh, in_specs=P("d"), out_specs=P("d"))
        got = jax.jit(f)(grads)
        for k in grads:
            want = np.asarray(grads[k].sum(axis=0))
            g = np.asarray(got[k])
            rel = np.abs(g - want).max() / (np.abs(want).max() + 1e-9)
            assert rel < 0.15, (k, rel)  # int8 per-hop rounding, no EF
            assert (g == g[0]).all()     # replicas agree bit-for-bit
        print("BUCKET_RING_OK")
    """)
    assert "BUCKET_RING_OK" in out


@pytest.mark.slow
@pytest.mark.parametrize("wire,tol", [("bf16", 0.02), ("fp8", 0.25)])
def test_fused_wire_all_reduce_close_to_exact(wire, tol):
    """bf16 and fp8 wire formats through the fused single-ppermute ring:
    correct sums within each format's rounding budget (bf16 keeps the f32
    exponent; fp8 e4m3 re-rounds a 3-bit mantissa every hop)."""
    out = run_multidevice(f"""
        from functools import partial
        from repro.dist.compression import fused_wire_all_reduce
        x = jax.random.normal(jax.random.PRNGKey(5), (8, 513), jnp.float32)
        f = shard_map(partial(fused_wire_all_reduce, axis_name="d",
                              wire="{wire}", block=128),
                      mesh=mesh, in_specs=P("d", None), out_specs=P("d", None))
        got = np.asarray(jax.jit(f)(x))
        want = np.asarray(x.sum(axis=0))
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert rel < {tol}, rel
        assert (got == got[0]).all()
        print("WIRE_OK", rel)
    """)
    assert "WIRE_OK" in out


def test_fused_wire_all_reduce_rejects_unknown_wire():
    from repro.dist.compression import fused_wire_all_reduce

    with pytest.raises(ValueError, match="unknown fused wire"):
        fused_wire_all_reduce(jnp.ones(8), "d", wire="int4")


def test_error_feedback_convergence():
    """EF-compressed 'all-reduce' on 1 worker == quantize w/ residual carry:
    SGD on a quadratic still converges (the EF guarantee)."""
    w = jnp.array([5.0, -3.0, 2.0])
    x = jnp.zeros(3)
    residual = jnp.zeros(3)
    for _ in range(300):
        grad = 2 * (x - w)
        corrected = grad + residual
        q = dequantize(quantize(corrected), corrected.size, corrected.shape)
        residual = corrected - q
        x = x - 0.05 * q
    assert float(jnp.abs(x - w).max()) < 1e-2


@pytest.mark.slow
def test_measured_ring_timings_calibrate_bandwidth():
    """ROADMAP loop closure: time real ring_all_reduce runs and feed the fit
    back into an Eq. (1) profile (repro.cluster.calibrate)."""
    out = run_multidevice("""
        from repro.cluster.calibrate import (
            calibrate_profile, fit_comm_model, measure_ring_timings)
        from repro.core.rar_model import profile_from_arch

        samples = measure_ring_timings(worlds=(2, 4, 8),
                                       n_elements=(1 << 12, 1 << 14, 1 << 16),
                                       repeats=2)
        assert len(samples) == 9, samples
        fit = fit_comm_model(samples)
        assert fit.bandwidth > 0 and fit.n_samples == 9
        prof = profile_from_arch(n_params=1e6, tokens_per_batch=256)
        cal = calibrate_profile(prof, samples)
        assert cal.bandwidth > 0 and cal.bandwidth != prof.bandwidth
        assert float(cal.iteration_time(8)) > 0.0
        print(f"CALIB_OK b={fit.bandwidth:.3e}")
    """)
    assert "CALIB_OK" in out


# ---------------------------------------------------------------------------
# compressed ring: fused single-ppermute path + EF first-hop fix
# ---------------------------------------------------------------------------

def test_pack_unpack_hop_message_roundtrip():
    from repro.dist.compression import pack_hop_message, unpack_hop_message

    key = jax.random.PRNGKey(0)
    q = jax.random.randint(key, (5, 64), -127, 128, jnp.int8)
    scales = jnp.abs(jax.random.normal(key, (5,), jnp.float32)) + 1e-3
    msg = pack_hop_message(q, scales)
    assert msg.dtype == jnp.int8 and msg.size == 5 * 64 + 5 * 4
    q2, s2 = unpack_hop_message(msg, 5, 64)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(scales), np.asarray(s2))


def test_fused_chunk_layout_edges():
    """Chunk/sub-block layout for sizes not divisible by w or w*block."""
    from repro.dist.compression import _fused_chunk_layout

    # divisible: no padding
    assert _fused_chunk_layout(8 * 512, 8, 512) == (512, 1, 0)
    # chunk smaller than block: block clamps to the chunk
    c_pad, nb, pad = _fused_chunk_layout(40, 8, 512)
    assert (c_pad, nb) == (5, 1) and pad == 0
    # ragged: chunks pad up to whole sub-blocks
    c_pad, nb, pad = _fused_chunk_layout(1000, 8, 64)
    assert c_pad == 128 and nb == 2 and pad == 8 * 128 - 1000
    # n < w: degenerate one-element blocks
    c_pad, nb, pad = _fused_chunk_layout(3, 8, 512)
    assert (c_pad, nb, pad) == (1, 1, 5)


@pytest.mark.parametrize("fused", [False, True])
def test_compressed_ring_w1_passthrough(fused):
    """A 1-worker ring is a no-op: the input comes back bit-identical (and
    no quantization is applied at all)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.dist.compression import compressed_ring_all_reduce

    mesh = jax.make_mesh((1,), ("d",))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 257), jnp.float32)
    f = shard_map(lambda a: compressed_ring_all_reduce(a, "d", fused=fused),
                  mesh=mesh, in_specs=P("d", None), out_specs=P("d", None))
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x))


@pytest.mark.parametrize("fused", [False, True])
def test_ef_w1_quantizes_once(fused):
    """On one worker EF reduces to Q(g + residual): the result is the
    dequantized payload and the residual is exactly the rounding error —
    with the *same* quantizer as the w >= 2 ring (blockwise when fused, so
    an elastic shrink to w=1 does not change the rounding semantics)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.dist.compression import ef_compressed_all_reduce
    from repro.kernels.ref import (
        dequant_accumulate_reference,
        quantize_block_reference,
    )

    mesh = jax.make_mesh((1,), ("d",))
    g = jax.random.normal(jax.random.PRNGKey(1), (1, 300), jnp.float32)
    res = jax.random.normal(jax.random.PRNGKey(2), (1, 300), jnp.float32) * .1

    def f(gg, rr):
        return ef_compressed_all_reduce(gg, rr, "d", fused=fused, block=50)

    out, new_res = shard_map(f, mesh=mesh, in_specs=(P("d", None),) * 2,
                             out_specs=(P("d", None),) * 2)(g, res)
    corrected = np.asarray(g + res)
    if fused:
        back = dequant_accumulate_reference(
            *quantize_block_reference(jnp.asarray(corrected.reshape(6, 50))))
        back = np.asarray(back).reshape(corrected.shape)
    else:
        back = np.asarray(dequantize(quantize(jnp.asarray(corrected)),
                                     corrected.size, corrected.shape))
    np.testing.assert_allclose(np.asarray(out), back, atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_res),
                               corrected - np.asarray(out), atol=1e-6)


@pytest.mark.slow
def test_fused_ring_close_to_exact_nondivisible():
    """Fused single-ppermute ring on a size divisible by neither w nor
    w*block: correct sum, every worker bit-identical."""
    out = run_multidevice("""
        from functools import partial
        x = jax.random.normal(jax.random.PRNGKey(2), (8, 513), jnp.float32)
        f = shard_map(partial(compressed_ring_all_reduce, axis_name="d",
                              fused=True, block=128),
                      mesh=mesh, in_specs=P("d", None), out_specs=P("d", None))
        got = np.asarray(jax.jit(f)(x))
        want = np.asarray(x.sum(axis=0))
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert rel < 0.15, rel  # int8 per-hop rounding, no EF
        assert (got == got[0]).all()  # single gather-phase quantization
        print("FUSED_RING_OK", rel)
    """)
    assert "FUSED_RING_OK" in out


@pytest.mark.slow
def test_ef_fused_close_to_exact():
    out = run_multidevice("""
        from functools import partial
        x = jax.random.normal(jax.random.PRNGKey(3), (8, 700), jnp.float32)

        def f(a):
            r, res = ef_compressed_all_reduce(a, jnp.zeros_like(a), "d",
                                              fused=True, block=256)
            return r
        got = np.asarray(jax.jit(shard_map(
            f, mesh=mesh, in_specs=P("d", None),
            out_specs=P("d", None)))(x))
        want = np.asarray(x.sum(axis=0))
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert rel < 0.15, rel
        print("EF_FUSED_OK", rel)
    """)
    assert "EF_FUSED_OK" in out


@pytest.mark.slow
def test_ef_first_hop_bitexact_no_double_quantization():
    """The EF pin: the ring's first Share-Reduce hop forwards EF's already-
    quantized payload verbatim. Inputs are integer multiples of a power-of-
    two scale (amax = 127 * 2^-3), so every op on the fixed path is exact in
    f32 and the executed collective must match a numpy reference of the
    skip-requantization semantics BIT FOR BIT — while the old behaviour
    (re-quantizing the dequantized tensor per chunk on hop 0) provably
    diverges on the same inputs."""
    out = run_multidevice("""
        S0 = np.float32(0.125)                   # power-of-two global scale
        rng = np.random.default_rng(0)
        n, c = 64, 32                            # w=2 ring, chunk=32
        k = rng.integers(-100, 101, size=(2, n)).astype(np.float32)
        k[:, 0] = 127.0                          # pin global amax in chunk 0
        g = (k * S0).astype(np.float32)          # exactly representable

        def ref_new(g0, g1):
            # skip-requantization semantics, all-f32, same op order
            q = [np.round(gg / S0).astype(np.float32) for gg in (g0, g1)]
            red = {}
            for i in (0, 1):
                peer = 1 - i
                red[i] = (gg := g0 if i == 0 else g1).reshape(2, c)[peer] \\
                    + q[peer].reshape(2, c)[peer] * S0
            final = np.zeros((2, c), np.float32)
            for i in (0, 1):
                amax = np.float32(np.abs(red[i]).max())
                scale = amax / np.float32(127.0) if amax > 0 else np.float32(1)
                qq = np.clip(np.round(red[i] / scale), -127, 127)
                final[1 - i] = qq.astype(np.float32) * scale
            return final.reshape(-1)

        def ref_old(g0, g1):
            # the removed behaviour: hop-0 re-quantizes dequantized chunks
            q = [np.round(gg / S0).astype(np.float32) for gg in (g0, g1)]
            red = {}
            for i in (0, 1):
                peer = 1 - i
                v = q[peer].reshape(2, c)[peer] * S0
                amax = np.float32(np.abs(v).max())
                scale = amax / np.float32(127.0) if amax > 0 else np.float32(1)
                payload = np.clip(np.round(v / scale), -127, 127)
                red[i] = (g0 if i == 0 else g1).reshape(2, c)[peer] \\
                    + payload.astype(np.float32) * scale
            final = np.zeros((2, c), np.float32)
            for i in (0, 1):
                amax = np.float32(np.abs(red[i]).max())
                scale = amax / np.float32(127.0) if amax > 0 else np.float32(1)
                qq = np.clip(np.round(red[i] / scale), -127, 127)
                final[1 - i] = qq.astype(np.float32) * scale
            return final.reshape(-1)

        want = ref_new(g[0], g[1])
        assert np.abs(want - ref_old(g[0], g[1])).max() > 0, \\
            "inputs must distinguish the fixed path from the old one"

        mesh2 = jax.make_mesh((2,), ("e",))

        def f(a):
            r, res = ef_compressed_all_reduce(a, jnp.zeros_like(a), "e")
            return r
        got = np.asarray(jax.jit(shard_map(
            f, mesh=mesh2, in_specs=P("e", None),
            out_specs=P("e", None)))(jnp.asarray(g)))
        np.testing.assert_array_equal(got[0], want)
        np.testing.assert_array_equal(got[1], want)
        print("EF_BITEXACT_OK")
    """)
    assert "EF_BITEXACT_OK" in out
