"""Dense decoder-only transformer (GQA / RoPE / SwiGLU / qk-norm / SWA).

Covers: qwen3-0.6b, granite-3-2b, h2o-danube-1.8b (SWA), phi3-medium-14b,
and internvl2-26b (vlm: precomputed patch embeddings prepended — the vision
frontend is a stub per the assignment spec).

Layers are stacked along a leading "layers" dim and executed with
``jax.lax.scan`` (small HLO, fast SPMD compile); per-layer remat when
``cfg.remat``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import constrain
from repro.models import layers as L
from repro.models.model import BaseModel, masked_lm_head
from repro.models.module import ParamSpec


def _attn_specs(cfg: ArchConfig, n_layers: int, prefix_axes=("layers",)) -> Dict[str, ParamSpec]:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    lead = (n_layers,) if prefix_axes else ()
    out = {
        "wq": ParamSpec(lead + (d, h, hd), prefix_axes + ("embed", "heads", "head_dim")),
        "wk": ParamSpec(lead + (d, kv, hd), prefix_axes + ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec(lead + (d, kv, hd), prefix_axes + ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec(lead + (h, hd, d), prefix_axes + ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        out["q_norm"] = ParamSpec(lead + (hd,), prefix_axes + ("head_dim",), init="ones")
        out["k_norm"] = ParamSpec(lead + (hd,), prefix_axes + ("head_dim",), init="ones")
    return out


def _mlp_specs(cfg: ArchConfig, n_layers: int, prefix_axes=("layers",)) -> Dict[str, ParamSpec]:
    d, f = cfg.d_model, cfg.d_ff
    lead = (n_layers,) if prefix_axes else ()
    return {
        "w_gate": ParamSpec(lead + (d, f), prefix_axes + ("embed", "mlp")),
        "w_up": ParamSpec(lead + (d, f), prefix_axes + ("embed", "mlp")),
        "w_down": ParamSpec(lead + (f, d), prefix_axes + ("mlp", "embed")),
    }


class DenseLM(BaseModel):
    """Decoder-only LM; family == "vlm" adds patch-embedding inputs."""

    def param_specs(self):
        cfg = self.cfg
        nl = cfg.n_layers
        block = {
            "ln1": ParamSpec((nl, cfg.d_model), ("layers", "embed"), init="ones"),
            "ln2": ParamSpec((nl, cfg.d_model), ("layers", "embed"), init="ones"),
            **_attn_specs(cfg, nl),
            **_mlp_specs(cfg, nl),
        }
        out = {
            "embed": ParamSpec((cfg.padded_vocab, cfg.d_model),
                               ("vocab", "embed"), init="embed", scale=0.02),
            "blocks": block,
            "ln_f": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
            "lm_head": ParamSpec((cfg.d_model, cfg.padded_vocab), ("embed", "vocab")),
        }
        return out

    # -- blocks ---------------------------------------------------------------
    def _attn(self, lp, x, positions):
        cfg = self.cfg
        q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", x, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"])
        if cfg.qk_norm:
            q = L.rms_norm(q, lp["q_norm"])
            k = L.rms_norm(k, lp["k_norm"])
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        q = constrain(q, ("batch", "seq", "act_heads", None))
        o = L.attention(q, k, v, causal=True, window=cfg.sliding_window)
        return jnp.einsum("bshk,hkd->bsd", o, lp["wo"])

    def _block_train(self, lp, h, positions):
        cfg = self.cfg
        x = L.rms_norm(h, lp["ln1"])
        h = h + self._attn(lp, x, positions)
        x = L.rms_norm(h, lp["ln2"])
        mlp = L.swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
        h = h + mlp
        return constrain(h, ("batch", "seq", "act_embed"))

    def _embed_inputs(self, params, batch):
        cfg = self.cfg
        h = params["embed"][batch["tokens"]]
        if cfg.family == "vlm" and "patch_embeds" in batch:
            h = jnp.concatenate([batch["patch_embeds"].astype(h.dtype), h], axis=1)
        return h

    def forward(self, params, batch):
        cfg = self.cfg
        h = self._embed_inputs(params, batch)
        h = constrain(h, ("batch", "seq", "act_embed"))
        positions = jnp.arange(h.shape[1])

        def body(carry, lp):
            return self._block_train(lp, carry, positions), None

        step = jax.checkpoint(body) if cfg.remat else body
        h, _ = jax.lax.scan(step, h, params["blocks"])
        h = L.rms_norm(h, params["ln_f"])
        if cfg.family == "vlm" and "patch_embeds" in batch:
            h = h[:, batch["patch_embeds"].shape[1]:]  # logits for text positions
        logits = masked_lm_head(h, params["lm_head"], cfg.vocab)
        logits = constrain(logits, ("batch", "seq", "act_vocab"))
        return logits, {}

    # -- decode ----------------------------------------------------------------
    def cache_len(self, max_seq: int) -> int:
        cfg = self.cfg
        if cfg.sliding_window is not None:
            return min(max_seq, cfg.sliding_window)
        return max_seq

    def cache_specs(self, batch_size: int, max_seq: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        sc = self.cache_len(max_seq)
        shape = (cfg.n_layers, batch_size, sc, cfg.n_kv_heads, cfg.head_dim)
        axes = ("layers", "batch", "seq", "kv_heads", "head_dim")
        return {
            "k": ParamSpec(shape, axes, dtype=dtype, init="zeros"),
            "v": ParamSpec(shape, axes, dtype=dtype, init="zeros"),
        }

    def _decode_layer(self, lp, h, positions, attend):
        """One layer for one new token a lane. ``attend(q, k, v)`` reads
        the layer's cache and returns (attention output, what the layer
        scan emits)."""
        cfg = self.cfg
        x = L.rms_norm(h, lp["ln1"])
        q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", x, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"])
        if cfg.qk_norm:
            q = L.rms_norm(q, lp["q_norm"])
            k = L.rms_norm(k, lp["k_norm"])
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        o, out = attend(q, k, v)
        h = h + jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
        x = L.rms_norm(h, lp["ln2"])
        return h + L.swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), out

    def decode_step(self, params, cache, tokens, cur_index):
        """One token: update each layer's KV cache, return logits.

        SWA archs use a ring-buffer cache of window length (sub-quadratic
        memory — this is what makes long_500k feasible for h2o-danube).
        """
        cfg = self.cfg
        h = params["embed"][tokens]  # (B, 1, D)
        positions = jnp.full((1,), cur_index, dtype=jnp.int32)
        sc = cache["k"].shape[2]
        write_at = cur_index % sc if cfg.sliding_window is not None else cur_index
        # ring buffer: all slots valid once full; mask by recency
        valid_to = (jnp.minimum(cur_index, sc - 1)
                    if cfg.sliding_window is not None else cur_index)

        def body(h, xs):
            lp, k_cache, v_cache = xs

            def attend(q, k, v):
                kc = jax.lax.dynamic_update_slice(
                    k_cache, k.astype(k_cache.dtype), (0, write_at, 0, 0))
                vc = jax.lax.dynamic_update_slice(
                    v_cache, v.astype(v_cache.dtype), (0, write_at, 0, 0))
                return L.decode_attention(q, kc, vc, valid_to), (kc, vc)

            return self._decode_layer(lp, h, positions, attend)

        h, (new_k, new_v) = jax.lax.scan(
            body, h, (params["blocks"], cache["k"], cache["v"]))
        h = L.rms_norm(h, params["ln_f"])
        logits = masked_lm_head(h, params["lm_head"], cfg.vocab)
        return logits, {"k": new_k, "v": new_v}

    def decode_step_lanes(self, params, cache, tokens, positions, active):
        """Per-lane decode that writes only each active lane's new K/V row.

        Lane b decodes at its own ``positions[b]``. Each layer's cache is a
        read-only scan input: attention takes the slots the lane has seen
        plus the new token's own key and value in one softmax, which equals
        :meth:`decode_step`'s write-then-attend. The scan emits only the new
        rows, ``(layers, B, kv_heads, head_dim)``; one scatter after it puts
        each active lane's row at its slot. A KV cache is append-only, so
        that row is the whole update: with the cache donated the step reads
        it once and writes ``layers x lanes`` rows in place, and an
        inactive lane's cache stays bit-identical.

        SWA: the row goes to slot ``position % cache_len``; the slot it
        overwrites (the token leaving the window) is masked out.
        """
        cfg = self.cfg
        h = params["embed"][tokens]  # (B, 1, D)
        sc = cache["k"].shape[2]
        kpos = jnp.arange(sc)[None, :]
        seen = kpos < positions[:, None]
        write_at = positions
        if cfg.sliding_window is not None:
            write_at = positions % sc
            seen &= kpos != write_at[:, None]

        def body(h, xs):
            lp, k_cache, v_cache = xs

            def attend(q, k, v):
                k = k.astype(k_cache.dtype)
                v = v.astype(v_cache.dtype)
                o = L.decode_attention_lanes(q, k_cache, v_cache, k, v, seen)
                return o, (k[:, 0], v[:, 0])

            return self._decode_layer(lp, h, positions[:, None], attend)

        h, rows = jax.lax.scan(
            body, h, (params["blocks"], cache["k"], cache["v"]))
        h = L.rms_norm(h, params["ln_f"])
        logits = masked_lm_head(h, params["lm_head"], cfg.vocab)
        lanes = jnp.arange(tokens.shape[0])
        slot = jnp.where(active, write_at, sc)  # out of bounds: dropped
        new = {name: cache[name].at[:, lanes, slot].set(row, mode="drop")
               for name, row in zip(("k", "v"), rows)}
        return logits, new

    def extra_input_specs(self, batch_size: int):
        if self.cfg.family == "vlm":
            return {"patch_embeds": jax.ShapeDtypeStruct(
                (batch_size, self.cfg.n_patches, self.cfg.d_model), jnp.bfloat16)}
        return {}
