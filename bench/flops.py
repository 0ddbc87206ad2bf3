"""Operations and bytes that the algorithm needs, counted from shapes.

These are the yardstick's own counts, independent of how the program
computes: padded vocabulary slots, masked cache lanes, positions past a
request's length and recomputed (rematerialized) work are not counted.
A config here is the dict in ``bench/configs/<name>.json``.
"""

from __future__ import annotations

from typing import Dict, Sequence


def _attn_dims(c: Dict) -> tuple:
    return (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"])


def layer_matmul_params(c: Dict) -> int:
    """Weights one decoder layer multiplies each token by."""
    d, h, kv, hd = _attn_dims(c)
    f = c["intermediate_size"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def matmul_params(c: Dict) -> int:
    """Weights each token is multiplied by: every layer plus the output
    head over the real vocabulary (the embedding is a gather)."""
    return (c["num_hidden_layers"] * layer_matmul_params(c)
            + c["hidden_size"] * c["vocab_size"])


def attention_flops_per_query(c: Dict, keys: float) -> float:
    """Scores and weighted values of one query against ``keys`` keys, over
    every layer."""
    _, h, _, hd = _attn_dims(c)
    return 4.0 * h * hd * keys * c["num_hidden_layers"]


def train_flops_per_token(c: Dict, seq: int) -> float:
    """Forward and backward of causal LM training at sequence ``seq``:
    three times the forward; a query at position i sees i + 1 keys, so the
    mean query sees (seq + 1) / 2."""
    fwd = 2.0 * matmul_params(c) + attention_flops_per_query(
        c, (seq + 1) / 2.0)
    return 3.0 * fwd


def decode_step_work(c: Dict, positions: Sequence[int],
                     weight_bytes: int = 2, cache_bytes: int = 2
                     ) -> Dict[str, float]:
    """One decode step over the live lanes, each at its position (the
    index the new token is written at, so it attends ``position + 1``
    keys). Bytes: every weight read once, the live lanes' cached keys and
    values read, the new keys and values written."""
    d, h, kv, hd = _attn_dims(c)
    n_layers = c["num_hidden_layers"]
    lanes = len(positions)
    keys = sum(p + 1 for p in positions)
    flops = 2.0 * matmul_params(c) * lanes + attention_flops_per_query(
        c, keys)
    weights = (matmul_params(c) + lanes * d) * weight_bytes
    kv_row = 2 * kv * hd * cache_bytes * n_layers   # K and V, every layer
    read = kv_row * (keys - lanes)                  # positions before the new
    written = kv_row * lanes
    return {"flops": flops, "bytes": weights + read + written}


def roofline_seconds(flops: float, nbytes: float,
                     peaks: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
