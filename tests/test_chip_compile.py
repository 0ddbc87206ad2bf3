"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: misaligned tiles, 1-D
blocks, programs that do not fit the chip. These tests compile the main
path's Pallas wire kernels and the full-width one-chip train step for a
described ``v5e:2x2`` and check what the compiler returns. Nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.dist.compression import DEFAULT_BLOCK, _fused_chunk_layout
from repro.kernels.quant_ring import (
    bf16_accumulate_pallas,
    bf16_add_cast_pallas,
    cast_pack_bf16_pallas,
    dequant_accumulate_pallas,
    dequant_add_quantize_pallas,
    quantize_pack_pallas,
)

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def qwen3():
    from repro.configs import get_arch
    from repro.models.model import build_model

    return build_model(get_arch("qwen3-0.6b"))


def _wire_layouts(model, w=4):
    """(n_blocks, block) of the compressed-fused ring at world size ``w``
    for the model's largest and smallest gradient leaves: the per-hop
    Share-Reduce layout, and the Share-Only batched unpack of all ``w``
    gathered chunks."""
    sizes = [int(np.prod(x.shape))
             for x in jax.tree.leaves(model.abstract_params())]
    out = []
    for n in (max(sizes), min(sizes)):
        c_pad, nb, _ = _fused_chunk_layout(n, w, DEFAULT_BLOCK)
        out += [(nb, c_pad // nb), (w * nb, c_pad // nb)]
    return out


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _int8_kernels(nb, block):
    """The four int8 wire kernels: (name, fn, operand shapes)."""
    f32, i8 = jnp.float32, jnp.int8
    x, q, s = ((nb, block), f32), ((nb, block), i8), ((nb,), f32)
    return [
        ("quantize_pack",
         lambda a: quantize_pack_pallas(a, interpret=False), [x]),
        ("dequant_add_quantize",
         lambda a, b, c: dequant_add_quantize_pallas(a, b, c,
                                                     interpret=False),
         [q, s, x]),
        ("dequant_accumulate",
         lambda a, b, c: dequant_accumulate_pallas(a, b, c, interpret=False),
         [q, s, x]),
        ("dequant",
         lambda a, b: dequant_accumulate_pallas(a, b, None, interpret=False),
         [q, s]),
    ]


def _assert_native(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_wire_layouts_cover_largest_and_smallest_leaf(qwen3):
    layouts = _wire_layouts(qwen3)
    # embed/lm_head (151936 x 1024) and ln_f (1024) at w=4
    assert layouts[0] == (9496, 4096)
    assert layouts[1] == (4 * 9496, 4096)
    assert layouts[2] == (1, 256)
    assert layouts[3] == (4, 256)


@pytest.mark.parametrize("kernel", ["quantize_pack", "dequant_add_quantize",
                                    "dequant_accumulate", "dequant"])
@pytest.mark.parametrize("leaf", ["largest", "smallest"])
def test_int8_wire_kernels_compile_at_qwen3_layouts(kernel, leaf, qwen3,
                                                    one_chip):
    layouts = _wire_layouts(qwen3)
    for nb, block in layouts[:2] if leaf == "largest" else layouts[2:]:
        (name, fn, shapes), = [k for k in _int8_kernels(nb, block)
                               if k[0] == kernel]
        _assert_native(_compile(fn, one_chip, *shapes))


@pytest.mark.parametrize("kernel", ["quantize_pack", "dequant_add_quantize",
                                    "dequant_accumulate", "dequant"])
def test_int8_wire_kernels_compile_where_a_divisor_tile_was_refused(
        kernel, one_chip):
    """9497 rows has no row tile that is both a divisor and a multiple of
    8: the tiles were refused until a ragged last tile was allowed."""
    (name, fn, shapes), = [k for k in _int8_kernels(9497, 4096)
                           if k[0] == kernel]
    _assert_native(_compile(fn, one_chip, *shapes))


def test_dequant_accumulate_compiles_with_column_scales(one_chip):
    """A 1-D (64,) scale block was refused (neither whole nor a multiple
    of 128 lanes)."""
    fn = lambda q, s, a: dequant_accumulate_pallas(q, s, a, interpret=False)
    _assert_native(_compile(fn, one_chip, ((64, 4096), jnp.int8),
                            ((64,), jnp.float32), ((64, 4096), jnp.float32)))


@pytest.mark.parametrize("kernel", ["cast_pack", "add_cast", "accumulate"])
def test_bf16_wire_kernels_compile_at_largest_leaf(kernel, qwen3, one_chip):
    nb, block = _wire_layouts(qwen3)[0]
    f32, bf16 = ((nb, block), jnp.float32), ((nb, block), jnp.bfloat16)
    fn, shapes = {
        "cast_pack": (lambda a: cast_pack_bf16_pallas(a, interpret=False),
                      [f32]),
        "add_cast": (lambda a, b: bf16_add_cast_pallas(a, b,
                                                       interpret=False),
                     [bf16, f32]),
        "accumulate": (lambda a, b: bf16_accumulate_pallas(
            a, b, interpret=False), [bf16, f32]),
    }[kernel]
    _assert_native(_compile(fn, one_chip, *shapes))


def test_full_width_ring_step_fits_one_chip(qwen3, topo, monkeypatch):
    """The one-chip qwen3-0.6b ring step as RingWorkerGroup compiles it
    (bf16 params, adamw, global batch 8 x 1024): params and optimizer
    state are donated, and the whole program fits v5e's HBM."""
    from repro.training import elastic
    from repro.training.optimizer import make_optimizer

    # the group builds its mesh from jax.devices(): hand it the described
    # chip for this test only
    monkeypatch.setattr(elastic.jax, "devices", lambda: list(topo.devices))
    opt = make_optimizer(qwen3.cfg.optimizer)
    group = elastic.RingWorkerGroup(qwen3, opt, global_batch=8, lr=1e-3,
                                    mode="ring")
    prog = group._program(1)

    def shaped(tree, sharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    params = qwen3.abstract_params(dtype=jnp.bfloat16)
    opt_state = jax.eval_shape(opt.init, params)
    tokens = jax.ShapeDtypeStruct((8, 1024), jnp.int32)
    compiled = prog.step_fn.lower(
        shaped(params, prog.replicated), shaped(opt_state, prog.replicated),
        shaped({"tokens": tokens, "labels": tokens},
               prog.batch_sharding)).compile()
    mem = compiled.memory_analysis()
    state_bytes = sum(x.size * x.dtype.itemsize for x in
                      jax.tree.leaves((params, opt_state)))
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes >= state_bytes - 1024  # donated
    assert total < V5E_HBM_BYTES, (
        f"{total / 2 ** 30:.2f} GiB does not fit a 16 GiB v5e")


@pytest.mark.parametrize("variant", ["ring", "bidir", "compressed-fused",
                                     "bf16-fused"])
def test_ring_flatten_sits_behind_barriers(variant, topo, monkeypatch):
    """Each ring variant flattens a 2-D leaf and restores its shape behind
    optimization barriers at w=4, so the TPU compiler cannot fold the
    relayout into the ring's dynamic slices (that fold made the full-width
    w=4 ring step compile for minutes)."""
    import repro.dist.compression as compression
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.dist import collectives

    monkeypatch.setattr(compression, "interpret_mode", lambda: False)
    reduce = {
        "ring": collectives.ring_all_reduce,
        "bidir": collectives.bidirectional_ring_all_reduce,
        "compressed-fused": lambda g, a: compression.
        compressed_ring_all_reduce(g, a, fused=True),
        "bf16-fused": lambda g, a: compression.fused_wire_all_reduce(
            g, a, wire="bf16"),
    }[variant]
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    fn = jax.shard_map(lambda g: reduce(g, "data"), mesh=mesh,
                       in_specs=P(), out_specs=P("data"))
    x = jax.ShapeDtypeStruct((64, 1024), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P()))
    hlo = jax.jit(fn).lower(x).as_text()
    assert hlo.count("optimization_barrier") >= 2
