"""What the train and serve drivers share: the program's model for a
configuration file, the seeded weights, the plain reference beside the
configuration, and per-leaf norm gaps."""

from __future__ import annotations

import importlib.util
import os
from typing import Dict, List, Tuple

import numpy as np

from harness import BENCH_DIR

# program ArchConfig field <- configuration file key
ARCH_KEYS = {
    "n_layers": "num_hidden_layers", "d_model": "hidden_size",
    "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
    "head_dim": "head_dim", "d_ff": "intermediate_size",
    "vocab": "vocab_size", "qk_norm": "qk_norm", "rope_theta": "rope_theta",
}


def reference_module(config: Dict):
    """``bench/configs/<reference>.py``, the plain reference that the
    configuration file names."""
    path = os.path.join(BENCH_DIR, "configs", config["reference"] + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + config["reference"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program_model(config: Dict, arch_override=None):
    """The program's model for a configuration file; every size the file
    states has to be the size the program runs."""
    from repro.configs import get_arch
    from repro.models.model import build_model

    arch = arch_override or get_arch(config["program_arch"])
    wrong = {f: (getattr(arch, f), config[k]) for f, k in ARCH_KEYS.items()
             if getattr(arch, f) != config[k]}
    if wrong:
        raise ValueError(f"the program's {arch.name} differs from the "
                         f"configuration file: {wrong}")
    return build_model(arch)


def seeded_params(config: Dict, model, key):
    """The weights from the seed, made on the device in one jitted call, in
    the layout the program's model takes."""
    import functools

    import jax
    import jax.numpy as jnp

    ref = reference_module(config)
    params = jax.jit(functools.partial(ref.make_params, config,
                                       dtype=jnp.bfloat16))(key)
    want = jax.tree.map(lambda s: (s.shape, str(s.dtype)),
                        model.abstract_params(dtype=jnp.bfloat16))
    got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    if want != got:
        raise ValueError("seeded weights do not match the program's "
                         f"parameter layout: {want} != {got}")
    return params


def leaf_paths(tree) -> List[str]:
    import jax

    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_leaves_with_path(tree)]


def small_leaves(ref_grad: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is under a thousandth of the median
    leaf's: nought to rounding, so Adam moves them by round-off alone."""
    med = float(np.median(list(ref_grad.values())))
    return sorted(k for k, v in ref_grad.items() if v < 1e-3 * med)


def worst_leaf_gap(got: Dict[str, float], ref: Dict[str, float],
                   skip: List[str]) -> Tuple[float, str]:
    """Largest gap between two per-leaf norms, each against the larger of
    the reference leaf's norm and the median leaf's, over the leaves not in
    ``skip``; and the leaf where it is."""
    med = float(np.median(list(ref.values())))
    worst, where = 0.0, ""
    for k, r in ref.items():
        if k in skip:
            continue
        gap = abs(got[k] - r) / max(r, med)
        if gap > worst or not np.isfinite(gap):
            worst, where = gap, k
    return worst, where
