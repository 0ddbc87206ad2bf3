"""Mixture-of-experts LM: phi3.5-moe (16e top-2) and arctic-480b
(128e top-2 with a *dense residual* MLP in parallel — Snowflake's
dense+MoE hybrid design)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import constrain
from repro.models import layers as L
from repro.models.model import BaseModel, masked_lm_head
from repro.models.module import ParamSpec
from repro.models.transformer import DenseLM, _attn_specs, _mlp_specs


class MoeLM(DenseLM):
    """DenseLM with the FFN replaced (or paralleled) by a routed MoE."""

    def param_specs(self):
        cfg = self.cfg
        nl = cfg.n_layers
        d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_dff or cfg.d_ff
        block = {
            "ln1": ParamSpec((nl, d), ("layers", "embed"), init="ones"),
            "ln2": ParamSpec((nl, d), ("layers", "embed"), init="ones"),
            **_attn_specs(cfg, nl),
            "router": ParamSpec((nl, d, e), ("layers", "embed", "experts"),
                                scale=0.02),
            "we_gate": ParamSpec((nl, e, d, f),
                                 ("layers", "experts", "embed", "moe_mlp")),
            "we_up": ParamSpec((nl, e, d, f),
                               ("layers", "experts", "embed", "moe_mlp")),
            "we_down": ParamSpec((nl, e, f, d),
                                 ("layers", "experts", "moe_mlp", "embed")),
        }
        if cfg.dense_residual:
            block.update(_mlp_specs(cfg, nl))  # arctic's parallel dense MLP
        return {
            "embed": ParamSpec((cfg.padded_vocab, d), ("vocab", "embed"),
                               init="embed", scale=0.02),
            "blocks": block,
            "ln_f": ParamSpec((d,), ("embed",), init="ones"),
            "lm_head": ParamSpec((d, cfg.padded_vocab), ("embed", "vocab")),
        }

    def _ffn(self, lp, x):
        cfg = self.cfg
        y, aux = L.moe_ffn(
            x, lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"],
            top_k=cfg.top_k, capacity_factor=cfg.moe_capacity,
        )
        if cfg.dense_residual:
            y = y + L.swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
        return y, aux

    def _block_train(self, lp, h, positions):
        x = L.rms_norm(h, lp["ln1"])
        h = h + self._attn(lp, x, positions)
        x = L.rms_norm(h, lp["ln2"])
        y, aux = self._ffn(lp, x)
        h = h + y
        return constrain(h, ("batch", "seq", "act_embed")), aux

    def forward(self, params, batch):
        cfg = self.cfg
        h = self._embed_inputs(params, batch)
        h = constrain(h, ("batch", "seq", "act_embed"))
        positions = jnp.arange(h.shape[1])

        def body(carry, lp):
            h, aux_sum = carry
            h, aux = self._block_train(lp, h, positions)
            return (h, aux_sum + aux), None

        step = jax.checkpoint(body) if cfg.remat else body
        (h, aux_sum), _ = jax.lax.scan(step, (h, jnp.float32(0.0)),
                                       params["blocks"])
        h = L.rms_norm(h, params["ln_f"])
        logits = masked_lm_head(h, params["lm_head"], cfg.vocab)
        logits = constrain(logits, ("batch", "seq", "act_vocab"))
        return logits, {"moe_aux": aux_sum / cfg.n_layers}

    def decode_step(self, params, cache, tokens, cur_index):
        cfg = self.cfg
        h = params["embed"][tokens]
        positions = jnp.full((1,), cur_index, dtype=jnp.int32)

        def body(h, xs):
            lp, k_cache, v_cache = xs
            x = L.rms_norm(h, lp["ln1"])
            q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"])
            k = jnp.einsum("bsd,dhk->bshk", x, lp["wk"])
            v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"])
            if cfg.qk_norm:
                q = L.rms_norm(q, lp["q_norm"])
                k = L.rms_norm(k, lp["k_norm"])
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
            k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, cur_index, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, cur_index, 0, 0))
            o = L.decode_attention(q, k_cache, v_cache, cur_index)
            h = h + jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
            x = L.rms_norm(h, lp["ln2"])
            y, _ = self._ffn(lp, x)
            h = h + y
            return h, (k_cache, v_cache)

        h, (new_k, new_v) = jax.lax.scan(
            body, h, (params["blocks"], cache["k"], cache["v"]))
        h = L.rms_norm(h, params["ln_f"])
        logits = masked_lm_head(h, params["lm_head"], cfg.vocab)
        return logits, {"k": new_k, "v": new_v}

    # routing capacity is per call: lanes decoded together would compete
    # for experts' capacity, so each lane decodes alone (the vmap path)
    decode_step_lanes = BaseModel.decode_step_lanes
