"""Model protocol + dispatcher.

Every family implements:

  param_specs()                       -> SpecTree (shapes/dtypes/logical axes)
  init(key)                           -> params
  forward(params, batch)              -> logits (B, S, V) [+ aux dict]
  loss(params, batch)                 -> scalar (next-token CE + aux)
  cache_specs(batch, max_seq)         -> SpecTree for the decode cache
  decode_step(params, cache, tokens, cur_index) -> (logits, cache)
  input_specs(shape)                  -> dict of ShapeDtypeStruct (dry-run)

Params/caches are plain nested dicts; logical sharding axes live in the spec
trees and are resolved to mesh axes by ``repro.dist.sharding``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models.module import ParamSpec, SpecTree, abstract_from_specs, init_from_specs


class BaseModel:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    # -- to be provided by families -----------------------------------------
    def param_specs(self) -> SpecTree:
        raise NotImplementedError

    def forward(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        raise NotImplementedError

    def cache_specs(self, batch_size: int, max_seq: int) -> SpecTree:
        raise NotImplementedError

    def decode_step(self, params, cache, tokens, cur_index):
        raise NotImplementedError

    # -- shared --------------------------------------------------------------
    def init(self, key: jax.Array, dtype=None):
        return init_from_specs(self.param_specs(), key, dtype=dtype)

    def abstract_params(self, dtype=None):
        return abstract_from_specs(self.param_specs(), dtype=dtype)

    def abstract_cache(self, batch_size: int, max_seq: int):
        return abstract_from_specs(self.cache_specs(batch_size, max_seq))

    def loss(self, params, batch) -> jax.Array:
        logits, aux = self.forward(params, batch)
        ce = cross_entropy(logits, batch["labels"])
        return ce + 0.01 * aux.get("moe_aux", 0.0)

    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input (dry-run)."""
        b, s = shape.global_batch, shape.seq_len
        tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
        if shape.kind in ("train", "prefill"):
            out = {"tokens": tok}
            if shape.kind == "train":
                out["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
            out.update(self.extra_input_specs(b))
            return out
        # decode: one new token against a max_seq cache
        return {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}

    def extra_input_specs(self, batch_size: int) -> Dict[str, Any]:
        """Modality-frontend stub inputs (patch/frame embeddings)."""
        return {}

    def steady_decode_cache(self, params, cache):
        """Cast cache leaves to the dtypes one ``decode_step`` application
        emits (its dtype fixed point).

        Some families return a cache leaf wider than its spec (e.g. the
        Mamba2 conv window comes back f32 against a bf16 spec). A loop that
        feeds the cache straight back (the retired token-by-token serve
        loop) silently re-traces once and then *carries* the wider dtype;
        a ``lax.scan`` or a fixed-shape compiled step must instead pick one
        dtype up front — coercing back to the spec dtype every step would
        round the recurrent state each token and drift off the loop's
        numerics. Casting the initial (zero) cache up front is lossless and
        makes every later ``astype`` a no-op.
        """
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cache)
        batch = jax.tree.leaves(cache)[0].shape[CACHE_BATCH_AXIS]
        _, evolved = jax.eval_shape(
            self.decode_step, params, abstract,
            jax.ShapeDtypeStruct((batch, 1), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32))
        return jax.tree.map(lambda x, s: x.astype(s.dtype), cache, evolved)

    def decode_step_lanes(self, params, cache, tokens, positions, active):
        """Per-lane decode: every batch lane advances at its *own* position.

        ``decode_step`` takes one scalar ``cur_index`` shared by the whole
        batch — fine for lock-step generation, useless for continuous
        batching where lane b holds a request ``positions[b]`` tokens deep.
        This wrapper vmaps the family's own ``decode_step`` over the cache's
        batch axis (:data:`CACHE_BATCH_AXIS` — axis 1 of every leaf across
        all families), so each lane runs the unmodified single-request
        semantics at its private position. Recurrent state (SSM, conv, WKV)
        is rewritten whole every token, so the new cache is selected whole:
        an inactive lane (``active[b]`` false) keeps its old cache, and its
        garbage decode never lands. ``DenseLM`` overrides this with a step
        that writes only the new K/V rows.

        tokens ``(B, 1)`` int32, positions ``(B,)`` int32, active ``(B,)``
        bool -> (logits ``(B, 1, Vp)``, cache).
        """

        def one(lane_cache, tok, pos):
            c = jax.tree.map(lambda x: jnp.expand_dims(x, CACHE_BATCH_AXIS),
                             lane_cache)
            logits, new_c = self.decode_step(params, c, tok[None, :], pos)
            return logits[0], jax.tree.map(
                lambda x: jnp.squeeze(x, CACHE_BATCH_AXIS), new_c)

        logits, new_cache = jax.vmap(
            one, in_axes=(CACHE_BATCH_AXIS, 0, 0),
            out_axes=(0, CACHE_BATCH_AXIS),
        )(cache, tokens, positions)

        def keep(n, o):
            mask = active.reshape((1, -1) + (1,) * (n.ndim - 2))
            return jnp.where(mask, n, o).astype(o.dtype)

        return logits, jax.tree.map(keep, new_cache, cache)


# Every family lays its decode cache out as (layers, batch, ...): the batch
# ("lane") axis is uniformly axis 1 of every leaf — KV (dense/moe/hybrid/
# encdec self+cross), SSM/conv state (mamba2), and wkv/shift state (rwkv6).
# The lane helpers below and decode_step_lanes all key off this single
# constant, so a family with a different layout fails loudly in one place.
CACHE_BATCH_AXIS = 1


def cache_lane(cache, lane):
    """Read-only view of one lane (batch index kept, size 1) of a cache."""
    return jax.tree.map(
        lambda x: jax.lax.dynamic_slice_in_dim(x, lane, 1,
                                               axis=CACHE_BATCH_AXIS),
        cache)


def set_cache_lane(cache, lane_cache, lane):
    """Write a single-lane cache (batch size 1 at the lane axis) into
    ``cache`` at batch index ``lane``; dtypes follow the destination."""
    return jax.tree.map(
        lambda full, one: jax.lax.dynamic_update_slice_in_dim(
            full, one.astype(full.dtype), lane, axis=CACHE_BATCH_AXIS),
        cache, lane_cache)


def zero_cache_lane(cache, lane):
    """Zero one lane of every cache leaf — the evict/admit barrier.

    Attention caches are self-masking (``kpos <= cur_index`` hides stale
    keys), but recurrent state (SSM/conv/wkv/token-shift) is *not*: a new
    request prefilling into a lane still holding its predecessor's state
    would be conditioned on a conversation it never saw.
    """
    return jax.tree.map(
        lambda x: jax.lax.dynamic_update_slice_in_dim(
            x, jnp.zeros_like(
                jax.lax.dynamic_slice_in_dim(x, lane, 1,
                                             axis=CACHE_BATCH_AXIS)),
            lane, axis=CACHE_BATCH_AXIS),
        cache)


def masked_lm_head(h, w, vocab: int):
    """Logits over the padded vocab with pad slots masked to -inf (exact CE
    under Megatron-style vocab padding)."""
    logits = jnp.einsum("bsd,dv->bsv", h, w)
    vp = w.shape[-1]
    if vp == vocab:
        return logits
    mask = jnp.arange(vp) < vocab
    return jnp.where(mask[None, None, :], logits, jnp.float32(-1e30).astype(logits.dtype))


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy; labels are pre-shifted by the pipeline."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def build_model(cfg: ArchConfig) -> BaseModel:
    from repro.models import encdec, moe_model, rwkv, ssm, transformer

    if cfg.family in ("dense", "vlm"):
        return transformer.DenseLM(cfg)
    if cfg.family == "moe":
        return moe_model.MoeLM(cfg)
    if cfg.family == "hybrid":
        return ssm.Zamba2LM(cfg)
    if cfg.family == "ssm":
        return ssm.Mamba2LM(cfg)
    if cfg.family == "rwkv":
        return rwkv.Rwkv6LM(cfg)
    if cfg.family == "encdec":
        return encdec.WhisperLM(cfg)
    raise ValueError(f"unknown family {cfg.family!r}")
