"""Shared building blocks for the model zoo (pure JAX, GSPMD-friendly).

Attention defaults to a *flash-style chunked* implementation (lax.scan over
KV chunks with online softmax) so that 32k prefill never materializes an
S x S score tensor — the same algorithm as the Pallas kernel in
``repro.kernels.flash_attention``, expressed in XLA ops so it shards and
differentiates under GSPMD on any backend. The Pallas kernel is the TPU
hot-path; equivalence is asserted in tests.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (reference + flash-style chunked)
# ---------------------------------------------------------------------------

def _expand_kv(k: jax.Array, n_q_heads: int) -> jax.Array:
    """GQA: repeat kv heads to match query heads. (B,S,Hkv,D)->(B,S,Hq,D)."""
    n_kv = k.shape[-2]
    if n_kv == n_q_heads:
        return k
    return jnp.repeat(k, n_q_heads // n_kv, axis=-2)


def attention_reference(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, causal: bool = True, window: Optional[int] = None,
    q_offset: int | jax.Array = 0,
) -> jax.Array:
    """Dense O(S^2) attention. q: (B,Sq,Hq,D), k/v: (B,Skv,Hkv,D).

    GQA via grouped einsum (no materialized kv repeat); bf16 inputs with f32
    accumulation, bf16 probs for the PV matmul (same mixed-precision recipe
    as the chunked/Pallas paths)."""
    b, sq, hq, d = q.shape
    scale = 1.0 / math.sqrt(d)
    # NOTE (perf log, measured): grouped-GQA einsum here REGRESSES training
    # 4-14x — a (B,S,Hkv,G,D) layout cannot shard 16-way when Hkv < 16, so
    # GSPMD replicates the score tensors. Repeating kv keeps the q-head dim
    # shardable; the repeat itself is activation-sized (cheap vs scores).
    # Decode keeps the grouped form (there the cache dominates).
    with jax.named_scope("flash_attention"):
        k = _expand_kv(k, hq)
        v = _expand_kv(v, hq)
        qs = (q * scale).astype(q.dtype)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qs, k,
                            preferred_element_type=jnp.float32)
        skv = k.shape[1]
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(skv)
        mask = jnp.ones((sq, skv), dtype=bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window is not None:
            mask &= qpos[:, None] - kpos[None, :] < window
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v,
                         preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def attention_chunked(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, causal: bool = True, window: Optional[int] = None,
    chunk: int = 1024, q_offset: int | jax.Array = 0,
) -> jax.Array:
    """Flash-style streaming attention: scan over KV chunks, online softmax.

    Never materializes more than (B, Sq, Hq, chunk) scores. GQA is handled by
    a grouped einsum (no kv repeat is ever materialized). Score dots take
    bf16 inputs with f32 accumulation; the probability matrix is cast to the
    input dtype for the PV matmul (flash-standard mixed precision). Matches
    :func:`attention_reference` to float tolerance (tested).

    The whole body runs under ``jax.named_scope("flash_attention")`` so the
    HLO cost model can attribute its HBM traffic — on TPU these intermediates
    live in VMEM inside ``repro.kernels.flash_attention`` (the roofline's
    kernel-adjusted memory term; EXPERIMENTS.md §Perf).
    """
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    if skv % chunk != 0:
        pad = chunk - skv % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        skv_padded = skv + pad
    else:
        skv_padded = skv
    n_chunks = skv_padded // chunk
    # repeat kv so the q-head dim stays shardable (see attention_reference)
    k = _expand_kv(k, hq)
    v = _expand_kv(v, hq)
    kc = k.reshape(b, n_chunks, chunk, hq, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, hq, d).transpose(1, 0, 2, 3, 4)
    scale = 1.0 / math.sqrt(d)
    qs = (q * scale).astype(q.dtype)
    qpos = jnp.arange(sq) + q_offset  # (Sq,)

    def body(carry, inputs):
        m, l, acc = carry
        idx, k_j, v_j = inputs
        with jax.named_scope("flash_attention"):
            kpos = idx * chunk + jnp.arange(chunk)
            s = jnp.einsum("bqhd,bkhd->bqhk", qs, k_j,
                           preferred_element_type=jnp.float32)
            mask = kpos[None, :] < skv  # padding mask
            if causal:
                mask = mask & (qpos[:, None] >= kpos[None, :])
            if window is not None:
                mask = mask & (qpos[:, None] - kpos[None, :] < window)
            s = jnp.where(mask[None, :, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqhk,bkhd->bqhd", p.astype(q.dtype), v_j,
                preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, sq, hq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, hq), jnp.float32)
    acc0 = jnp.zeros((b, sq, hq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (jnp.arange(n_chunks), kc, vc))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, causal: bool = True, window: Optional[int] = None,
    q_offset: int | jax.Array = 0, chunk: int = 1024,
) -> jax.Array:
    """Dispatch: dense for short sequences, chunked-streaming for long."""
    if k.shape[1] <= 2048:
        return attention_reference(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset)
    return attention_chunked(q, k, v, causal=causal, window=window,
                             chunk=chunk, q_offset=q_offset)


def decode_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
    cur_index: jax.Array, *, window: Optional[int] = None,
) -> jax.Array:
    """One-token attention over a (possibly ring-buffered) KV cache.

    q: (B,1,Hq,D); caches: (B,S_cache,Hkv,D); cur_index: scalar — number of
    valid tokens already in the cache (the new token's position). GQA via
    grouped einsum — the kv repeat is never materialized (perf iteration 2,
    EXPERIMENTS.md §Perf).
    """
    b, sq, hq, d = q.shape
    hkv = k_cache.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    with jax.named_scope("flash_attention"):
        qg = (q.astype(jnp.float32) * scale).reshape(b, sq, hkv, g, d)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg.astype(q.dtype), k_cache,
                       preferred_element_type=jnp.float32)
        s_cache = k_cache.shape[1]
        kpos = jnp.arange(s_cache)
        mask = kpos <= cur_index
        if window is not None:
            mask &= kpos > cur_index - window
        s = jnp.where(mask[None, None, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bqhgk,bkhd->bqhgd", p.astype(q.dtype), v_cache,
                         preferred_element_type=jnp.float32)
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def decode_attention_lanes(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
    k_new: jax.Array, v_new: jax.Array, seen: jax.Array,
) -> jax.Array:
    """One-token attention per lane over a read-only KV cache plus the
    token's own key and value.

    q, k_new, v_new: (B,1,H,D); caches: (B,S_cache,Hkv,D); seen: (B,S_cache)
    bool, the cache slots each lane attends besides its new token. One
    softmax spans both parts, so the result equals writing the new row into
    the cache first and attending to it (:func:`decode_attention`) while
    the cache itself is never written here.

    The cache is read as it lies, as ``S_cache * Hkv`` rows of ``D`` per
    lane (a reshape that moves nothing). A grouped einsum would batch over
    kv heads, which sit between the slot and head_dim axes, and the TPU
    compiler copies each layer's cache into a head-major layout to do that.
    Here every query head scores every row and the rows of other kv heads
    are masked out of the softmax, so their probabilities are exactly zero
    in the value product: Hkv times the multiply-adds of the grouped form,
    which decode can spare (reading the cache bounds it), no copy, and no
    kv repeat.
    """
    b, _, hq, d = q.shape
    sc, hkv = k_cache.shape[1:3]
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    own = (jnp.arange(hq) // g)[:, None] == jnp.arange(hkv)[None, :]  # (Hq,Hkv)
    with jax.named_scope("flash_attention"):
        qs = (q[:, 0].astype(jnp.float32) * scale).astype(q.dtype)  # (B,Hq,D)
        s = jnp.einsum("bhd,brd->bhr", qs, k_cache.reshape(b, sc * hkv, d),
                       preferred_element_type=jnp.float32)
        mask = own[None, :, None, :] & seen[:, None, :, None]
        s = jnp.where(mask.reshape(b, hq, sc * hkv), s, NEG_INF)
        s_own = jnp.einsum("bjgd,bjd->bjg", qs.reshape(b, hkv, g, d),
                           k_new[:, 0], preferred_element_type=jnp.float32
                           ).reshape(b, hq)
        m = jnp.maximum(s.max(axis=-1), s_own)
        p = jnp.exp(s - m[..., None])
        p_own = jnp.exp(s_own - m)
        total = p.sum(axis=-1) + p_own
        out = jnp.einsum("bhr,brd->bhd", (p / total[..., None]).astype(q.dtype),
                         v_cache.reshape(b, sc * hkv, d),
                         preferred_element_type=jnp.float32)
        out += jnp.einsum("bjg,bjd->bjgd",
                          (p_own / total).reshape(b, hkv, g).astype(q.dtype),
                          v_new[:, 0], preferred_element_type=jnp.float32
                          ).reshape(b, hq, d)
    return out[:, None].astype(q.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def gelu_mlp(x: jax.Array, w_in: jax.Array, b_in: jax.Array,
             w_out: jax.Array, b_out: jax.Array) -> jax.Array:
    return jax.nn.gelu(x @ w_in + b_in) @ w_out + b_out


# ---------------------------------------------------------------------------
# MoE: top-k routing with sort-based capacity dispatch (no S x E x C tensor)
# ---------------------------------------------------------------------------

def moe_ffn(
    x: jax.Array,
    router: jax.Array,        # (D, E)
    w_gate: jax.Array,        # (E, D, F)
    w_up: jax.Array,          # (E, D, F)
    w_down: jax.Array,        # (E, F, D)
    *,
    top_k: int,
    capacity_factor: float = 1.25,
) -> Tuple[jax.Array, jax.Array]:
    """Sparse MoE via sort + fixed-capacity grouped matmul.

    Dispatch/combine are gathers & scatters (zero FLOPs); expert compute is a
    dense (E, C, D) x (E, D, F) einsum whose FLOPs equal active-expert FLOPs
    (times the modest capacity padding). Tokens overflowing an expert's
    capacity are dropped (standard Switch behaviour). Returns (y, aux_loss).
    """
    b, s, d = x.shape
    e = router.shape[-1]
    t = b * s
    xf = x.reshape(t, d)
    logits = (xf.astype(jnp.float32) @ router.astype(jnp.float32))  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_ids = jax.lax.top_k(probs, top_k)                  # (T, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch): E * sum_e f_e * p_e
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[gate_ids.reshape(-1)].add(1.0) / (t * top_k)
    aux = e * jnp.sum(me * ce)

    capacity = int(math.ceil(t * top_k / e * capacity_factor))
    capacity = max(capacity, top_k)

    expert_flat = gate_ids.reshape(-1)                 # (T*k,)
    token_flat = jnp.repeat(jnp.arange(t), top_k)      # (T*k,)
    weight_flat = gate_w.reshape(-1)
    order = jnp.argsort(expert_flat)
    sorted_experts = expert_flat[order]
    sorted_tokens = token_flat[order]
    sorted_weights = weight_flat[order]
    counts = jnp.bincount(sorted_experts, length=e)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(t * top_k) - starts[sorted_experts]
    keep = rank < capacity
    slot = jnp.where(keep, sorted_experts * capacity + rank, e * capacity)

    buf = jnp.zeros((e * capacity + 1, d), x.dtype)
    buf = buf.at[slot].set(xf[sorted_tokens])
    xe = buf[: e * capacity].reshape(e, capacity, d)
    # NOTE (§Perf, refuted hypothesis): forcing this buffer expert-sharded
    # via with_sharding_constraint makes GSPMD *replicate* the expert
    # matmuls (5x flops, 4.5x wire). Its own choice — dispatch buffer sharded
    # on D, partial-sum AR per expert matmul — measures best; leave it.

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", xe, w_up)
    ye = jnp.einsum("ecf,efd->ecd", h, w_down)

    # combine: scatter-add from the expert-sharded (E*C, D) buffer into the
    # token layout. Under GSPMD with experts sharded over "model", each shard
    # contributes partial sums and the compiler inserts ONE (B,S,D)
    # all-reduce — instead of all-gathering the (E,C,D) buffer (which is
    # top_k * capacity_factor bigger). Perf iteration: EXPERIMENTS.md §Perf.
    # token/weight targets per slot (cheap int/f32 scatters):
    token_for_slot = jnp.full((e * capacity + 1,), t, jnp.int32)
    token_for_slot = token_for_slot.at[slot].set(sorted_tokens.astype(jnp.int32))
    weight_for_slot = jnp.zeros((e * capacity + 1,), jnp.float32)
    weight_for_slot = weight_for_slot.at[slot].set(
        sorted_weights.astype(jnp.float32))
    y_flat = ye.reshape(e * capacity, d)
    contrib = y_flat * weight_for_slot[: e * capacity, None].astype(x.dtype)
    y = jnp.zeros((t + 1, d), x.dtype).at[
        token_for_slot[: e * capacity]].add(contrib)
    return y[:t].reshape(b, s, d), aux
