"""The dense family's per-lane decode against the vmap reference.

``DenseLM.decode_step_lanes`` reads each layer's cache once and writes only
the active lanes' new K/V rows; ``BaseModel.decode_step_lanes`` vmaps the
single-request ``decode_step`` over lanes and selects the whole new cache.
On the same inputs the two must give the same logits (to float tolerance),
the same written rows, and the input cache, bit for bit, everywhere else:
at every other position and in every inactive lane.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models.model import BaseModel, build_model

MAX_SEQ = 24
# lane positions: the first slot, mid-cache, the last slot (max_seq - 1),
# and for the 8-slot window every wrap of the ring
POSITIONS = np.array([0, 5, MAX_SEQ - 1, 11, 3, 17, 8, 14], np.int32)
ACTIVE = np.array([1, 1, 1, 0, 1, 0, 1, 1], bool)


def _model(arch, window):
    cfg = get_arch(arch).reduced()
    if window is not None:
        cfg = dataclasses.replace(cfg, sliding_window=window)
    return build_model(cfg)


def _filled_cache(model, key):
    """A cache whose every slot holds a distinct value, so a stray write
    anywhere shows."""
    abstract = model.abstract_cache(len(POSITIONS), MAX_SEQ)
    leaves, tree = jax.tree.flatten(abstract)
    keys = jax.random.split(key, len(leaves))
    return tree.unflatten([jax.random.normal(k, x.shape).astype(x.dtype)
                           for k, x in zip(keys, leaves)])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("arch, window", [("qwen3-0.6b", None),
                                          ("h2o-danube-1.8b", 8)],
                         ids=["qwen3", "h2o-danube-swa8"])
def test_dense_lane_decode_matches_vmap_reference(arch, window, dtype):
    model = _model(arch, window)
    params = model.init(jax.random.PRNGKey(0), dtype=dtype)
    cache = _filled_cache(model, jax.random.PRNGKey(1))
    old = jax.tree.map(np.asarray, cache)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (len(POSITIONS), 1),
                                0, model.cfg.vocab)
    args = (params, cache, tokens, jnp.asarray(POSITIONS),
            jnp.asarray(ACTIVE))

    logits, new = jax.jit(model.decode_step_lanes)(*args)
    ref_logits, ref = jax.jit(
        lambda *a: BaseModel.decode_step_lanes(model, *a))(*args)

    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(ref_logits, np.float32),
                               rtol=tol, atol=tol)
    sc = old["k"].shape[2]
    slot = POSITIONS % sc
    for name in ("k", "v"):
        got, want, before = (np.asarray(new[name]), np.asarray(ref[name]),
                             old[name])
        written = np.zeros(before.shape[1:3], bool)  # (lanes, slots)
        written[np.nonzero(ACTIVE)[0], slot[ACTIVE]] = True
        np.testing.assert_array_equal(got[:, written], want[:, written],
                                      err_msg=f"{name}: written rows")
        assert not np.array_equal(got[:, written], before[:, written])
        np.testing.assert_array_equal(got[:, ~written], before[:, ~written],
                                      err_msg=f"{name}: unwritten slots")
        np.testing.assert_array_equal(want[:, ~written], before[:, ~written])
