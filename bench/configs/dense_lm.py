"""Plain reference of a dense decoder-only LM, and the weights both sides
start from.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``: RMSNorm (eps from the config), grouped-query
attention with optional per-head RMSNorm on queries and keys, rotary
embeddings on two halves of each head, causal softmax scaled by
1/sqrt(head_dim), a SwiGLU MLP, and an untied output head. Nothing here
comes from the program under test; the weights are made here from the
seed and handed to the program.

``quant="fp8"`` is the control: the same computation with both operands of
every matrix product rounded to float8 e4m3 under a per-tensor scale (and
their cotangents to e5m2, likewise scaled), the next precision below the
configuration's bfloat16.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
FP8_MAX = 448.0       # largest float8_e4m3fn
E5M2_MAX = 57344.0    # largest float8_e5m2


def padded_vocab(c: Dict) -> int:
    return (c["vocab_size"] + 127) // 128 * 128


def param_shapes(c: Dict) -> Dict:
    """Leaf shapes, in the layout the program's dense model takes."""
    d, f = c["hidden_size"], c["intermediate_size"]
    n, h = c["num_hidden_layers"], c["num_attention_heads"]
    kv, hd, vp = c["num_key_value_heads"], c["head_dim"], padded_vocab(c)
    blocks = {
        "ln1": (n, d), "ln2": (n, d),
        "wq": (n, d, h, hd), "wk": (n, d, kv, hd), "wv": (n, d, kv, hd),
        "wo": (n, h, hd, d),
        "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d),
    }
    if c["qk_norm"]:
        blocks.update(q_norm=(n, hd), k_norm=(n, hd))
    return {"embed": (vp, d), "blocks": blocks, "ln_f": (d,),
            "lm_head": (d, vp)}


def _fan_in(name: str, shape) -> int:
    if name == "wo":
        return shape[-3] * shape[-2]
    return shape[-2] if name != "embed" else 1


def make_params(c: Dict, key, dtype=jnp.bfloat16) -> Dict:
    """Seeded weights: N(0, 1/fan_in) matrices, N(0, 0.02^2) embeddings,
    unit norm gains. Call under ``jax.jit`` to build them on the device in
    one program."""
    shapes = param_shapes(c)
    flat = sorted(list(shapes["blocks"].items())
                  + [(k, v) for k, v in shapes.items() if k != "blocks"])
    keys = jax.random.split(key, len(flat))
    out: Dict = {"blocks": {}}
    for (name, shape), k in zip(flat, keys):
        if name.startswith("ln") or name.endswith("_norm"):
            leaf = jnp.ones(shape, dtype)
        else:
            std = 0.02 if name == "embed" else 1.0 / math.sqrt(
                _fan_in(name, shape))
            leaf = (jax.random.normal(k, shape, jnp.float32) * std
                    ).astype(dtype)
        (out if name in shapes else out["blocks"])[name] = leaf
    return out


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _to_fp8(x, dtype, top):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    """An operand rounded to float8 e4m3 under its own scale; its cotangent
    is rounded to e5m2 under its own, as float8 training does."""
    return _to_fp8(x, jnp.float8_e4m3fn, FP8_MAX)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_to_fp8(g, jnp.float8_e5m2, E5M2_MAX),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(eq: str, a, b, quant: Optional[str]):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * freqs     # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(c: Dict, lp: Dict, h, quant):
    """One decoder layer over one sequence ``h`` (S, d)."""
    eps = c["rms_norm_eps"]
    s = h.shape[0]
    pos = jnp.arange(s)
    x = _rms(h, lp["ln1"].astype(jnp.float32), eps)
    q = _mm("sd,dhk->shk", x, lp["wq"], quant)
    k = _mm("sd,dhk->shk", x, lp["wk"], quant)
    v = _mm("sd,dhk->shk", x, lp["wv"], quant)
    if c["qk_norm"]:
        q = _rms(q, lp["q_norm"].astype(jnp.float32), eps)
        k = _rms(k, lp["k_norm"].astype(jnp.float32), eps)
    q = _rope(q, pos, c["rope_theta"])
    k = _rope(k, pos, c["rope_theta"])
    group = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = _mm("qhk,shk->hqs", q, k, quant) / math.sqrt(q.shape[-1])
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    o = _mm("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v, quant)
    h = h + _mm("qhk,hkd->qd", o, lp["wo"], quant)
    x = _rms(h, lp["ln2"].astype(jnp.float32), eps)
    g = _mm("sd,df->sf", x, lp["w_gate"], quant)
    u = _mm("sd,df->sf", x, lp["w_up"], quant)
    return h + _mm("sf,fd->sd", jax.nn.silu(g) * u, lp["w_down"], quant)


def hidden(c: Dict, params: Dict, tokens, quant: Optional[str] = None):
    """Final normed hidden states of one sequence: (S,) -> (S, d)."""
    h = params["embed"][tokens].astype(jnp.float32)

    def body(h, lp):
        return _layer(c, lp, h, quant), None

    h, _ = lax.scan(jax.checkpoint(body), h, params["blocks"])
    return _rms(h, params["ln_f"].astype(jnp.float32), c["rms_norm_eps"])


def logits(c: Dict, params: Dict, h, quant: Optional[str] = None):
    """Logits over the real vocabulary for hidden states (S, d)."""
    return _mm("sd,dv->sv", h, params["lm_head"][:, :c["vocab_size"]], quant)


def sequence_loss(c: Dict, params: Dict, tokens, labels,
                  quant: Optional[str] = None):
    """Mean next-token cross-entropy of one sequence (labels pre-shifted)."""
    lg = logits(c, params, hidden(c, params, tokens, quant), quant)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)
