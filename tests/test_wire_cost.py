"""Wire-cost consistency: the executable ring (repro.dist) and the
scheduler's analytical model (repro.core.rar_model) must price one
all-reduce identically — 2d(w-1)/w elements per worker for the f32 ring,
and the compressed formulas must agree with the *traced* collective
(ppermute counts and payload bytes read off the jaxpr via AbstractMesh, so
no devices are needed)."""

from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.core.rar_model import (
    compressed_rar_allreduce_time,
    compressed_ring_messages,
    rar_allreduce_time,
    rar_compressed_bytes_per_worker,
    rar_ring_bytes_per_worker,
)
from repro.dist.collectives import ring_wire_elements
from repro.dist.compression import (
    compressed_ring_all_reduce,
    compressed_ring_ppermutes,
    compressed_wire_bytes,
)


@pytest.mark.parametrize("d", [1, 1_000, 123_457, 7_000_000])
@pytest.mark.parametrize("w", [1, 2, 3, 4, 8, 16, 50])
def test_ring_wire_elements_matches_rar_model(d, w):
    assert ring_wire_elements(d, w) == pytest.approx(
        rar_ring_bytes_per_worker(d, w, elem_bytes=1))
    # and in f32 bytes, the unit used by the simulator
    assert ring_wire_elements(d, w) * 4 == pytest.approx(
        rar_ring_bytes_per_worker(d, w, elem_bytes=4))


@pytest.mark.parametrize("w", [2, 4, 8, 32])
def test_wire_term_drives_allreduce_time(w):
    """rar_allreduce_time's bandwidth term is exactly the one-directional
    wire volume (half of 2d(w-1)/w) over b, plus the reduction term."""
    d, b, g = 1e6, 1e9, 1e12
    expected = (ring_wire_elements(d, w) / 2.0) * (2.0 / b) + d * (
        w - 1.0) / w / g
    assert rar_allreduce_time(w, d, b, g) == pytest.approx(expected, rel=1e-9)


def test_single_worker_rings_are_free():
    assert ring_wire_elements(5e6, 1) == 0.0
    assert compressed_wire_bytes(5e6, 1) == 0.0
    assert compressed_wire_bytes(5e6, 1, fused=True) == 0.0
    assert compressed_ring_ppermutes(1) == 0
    assert compressed_ring_ppermutes(1, fused=True) == 0
    assert rar_allreduce_time(1, 5e6, 1e9, 1e12) == 0.0
    assert compressed_rar_allreduce_time(1, 5e6, 1e9, 1e12) == 0.0


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("d,w", [(10_000, 8), (1_000_000, 16), (4096, 4)])
def test_int8_ring_close_to_4x_cheaper(d, w, fused):
    ratio = (ring_wire_elements(d, w) * 4
             / compressed_wire_bytes(d, w, fused=fused))
    # fused pays block-padding + one scale per block instead of one per hop
    assert 3.0 < ratio < 4.0


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("d,w", [(10_000, 8), (123_457, 4), (1 << 20, 16)])
def test_compressed_formulas_match_rar_model(d, w, fused):
    """Scheduler-side (core) and executable-side (dist) compressed formulas
    are the same function — the Eq. (1) pricing cannot drift from the ring."""
    assert rar_compressed_bytes_per_worker(d, w, fused=fused) == pytest.approx(
        compressed_wire_bytes(d, w, fused=fused))
    assert compressed_ring_messages(w, fused=fused) == \
        compressed_ring_ppermutes(w, fused=fused)


def test_compressed_allreduce_time_terms():
    """Bytes over byte-rate + reduction + per-message gamma, and the fused
    layout halves the message count (the gamma term)."""
    d, w, b, g = 1 << 20, 8, 1e9, 1e12
    gamma = 1e-5
    for fused in (False, True):
        t = compressed_rar_allreduce_time(w, d, b, g, fused=fused,
                                          message_overhead=gamma)
        expected = (compressed_wire_bytes(d, w, fused=fused) / (b * 4)
                    + d * (w - 1) / w / g
                    + compressed_ring_ppermutes(w, fused=fused) * gamma)
        assert t == pytest.approx(expected, rel=1e-12)
    slow = compressed_rar_allreduce_time(w, d, b, g, message_overhead=gamma)
    fast = compressed_rar_allreduce_time(w, d, b, g, fused=True,
                                         message_overhead=gamma)
    n_slow = compressed_ring_messages(w)
    n_fast = compressed_ring_messages(w, fused=True)
    assert n_fast * 2 == n_slow
    # gamma savings: exactly (n_slow - n_fast) * gamma up to the (small)
    # fused block-padding cost on the wire term
    assert slow - fast == pytest.approx(
        (n_slow - n_fast) * gamma
        - (compressed_wire_bytes(d, w, fused=True)
           - compressed_wire_bytes(d, w)) / (b * 4), rel=1e-9)


# ---------------------------------------------------------------------------
# agreement with the executed collective: trace the ring over an abstract
# 8-way mesh and read the ppermutes straight off the jaxpr
# ---------------------------------------------------------------------------

def _ppermute_stats(jaxpr):
    """(count, payload bytes) of every ppermute in a jaxpr, recursively."""
    count, nbytes = 0, 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "ppermute":
            count += 1
            aval = eqn.invars[0].aval
            nbytes += aval.size * aval.dtype.itemsize
        for v in eqn.params.values():
            sub = v.jaxpr if hasattr(v, "jaxpr") else v
            if hasattr(sub, "eqns"):
                c, b = _ppermute_stats(sub)
                count += c
                nbytes += b
    return count, nbytes


def _traced_ring_stats(d: int, w: int, fused: bool):
    mesh = AbstractMesh((w,), ("d",))
    fn = jax.shard_map(
        partial(compressed_ring_all_reduce, axis_name="d", fused=fused,
                interpret=True),
        mesh=mesh, in_specs=P("d"), out_specs=P("d"), check_vma=False)
    jaxpr = jax.make_jaxpr(fn)(jnp.zeros((w * d,), jnp.float32))
    return _ppermute_stats(jaxpr.jaxpr)


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("d,w", [(10_000, 8), (4096, 4), (777, 3)])
def test_wire_formulas_agree_with_traced_collective(d, w, fused):
    """compressed_wire_bytes / compressed_ring_ppermutes describe exactly
    what the executed collective puts on the wire."""
    count, nbytes = _traced_ring_stats(d, w, fused)
    assert count == compressed_ring_ppermutes(w, fused=fused)
    assert nbytes == pytest.approx(compressed_wire_bytes(d, w, fused=fused))


def test_fused_ring_halves_ppermutes_per_hop():
    """The acceptance pin: over the same 2(w-1) hops the fused path issues
    exactly half the ppermutes of the XLA compressed ring (one packed
    message per hop instead of payload + scale)."""
    w, d = 8, 10_000
    n_xla, _ = _traced_ring_stats(d, w, fused=False)
    n_fused, _ = _traced_ring_stats(d, w, fused=True)
    hops = 2 * (w - 1)
    assert n_xla == 2 * hops
    assert n_fused == hops
    assert n_fused * 2 == n_xla
