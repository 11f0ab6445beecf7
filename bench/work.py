"""Work that the algorithm needs, counted from shapes.

These count what the computation requires, not what today's program
does: a decode step needs every weight read once and the live tokens'
keys and values, not the whole pool copied or the block table's dead
slots walked. So an implementation that stops doing unneeded work reads
higher against these counts, and none can read above the peak.

All counts are for bf16 storage (2 bytes) and multiply-adds as 2 FLOPs.
"""

from __future__ import annotations

import json
from typing import Dict, Tuple

from .spec import BENCH_DIR

BYTES = 2


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a kind not in the table is an error."""
    with open(BENCH_DIR / "peaks.json", encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]


def layer_matmul_params(m: Dict) -> int:
    d, H, Hkv = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"]
    D, F = m["head_dim"], m["intermediate_size"]
    return d * H * D + 2 * d * Hkv * D + H * D * d + 3 * d * F


def unembed_params(m: Dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def weight_bytes(m: Dict) -> int:
    """Bytes a decode step must read: every layer's matrices and norm
    scales, the unembedding (the embedding itself when tied), the final
    norm. The embedding rows a batch looks up are negligible."""
    d, L = m["hidden_size"], m["num_hidden_layers"]
    norms = 0 if m["norm_kind"] == "layernorm_np" else (2 * L + 1) * d
    return BYTES * (L * layer_matmul_params(m) + unembed_params(m) + norms)


def kv_token_bytes(m: Dict) -> int:
    """K and V of one token in every layer."""
    return BYTES * 2 * m["num_hidden_layers"] * m["num_key_value_heads"] \
        * m["head_dim"]


def attn_flops(m: Dict, live: int) -> int:
    """q.K and p.V of one query over ``live`` keys, all layers."""
    return 4 * m["num_attention_heads"] * m["head_dim"] * live \
        * m["num_hidden_layers"]


def decode_token_flops(m: Dict, live: int) -> int:
    """One sequence's token in a decode step whose KV holds ``live``
    tokens (this one included)."""
    mm = m["num_hidden_layers"] * layer_matmul_params(m) + unembed_params(m)
    return 2 * mm + attn_flops(m, live)


def decode_window(m: Dict, steps: int, lives) -> Tuple[float, float]:
    """(FLOPs, bytes) the decode steps of a window need: ``steps``
    executions, one token per entry of ``lives`` (its live length).
    Weights are read once per step; each token reads its live K/V and
    writes its own."""
    flops = sum(decode_token_flops(m, n) for n in lives)
    kv = kv_token_bytes(m)
    nbytes = steps * weight_bytes(m) + sum(n * kv + kv for n in lives)
    return float(flops), float(nbytes)


def paged_attention(m: Dict, lives) -> Tuple[float, float]:
    """(FLOPs, bytes) the attention kernel needs over live tokens only:
    per layer and token, q.K and p.V, the live K and V read, q read and
    the output written."""
    L, H, D = m["num_hidden_layers"], m["num_attention_heads"], \
        m["head_dim"]
    kv = kv_token_bytes(m)
    flops = sum(attn_flops(m, n) for n in lives)
    nbytes = sum(n * kv + 2 * BYTES * L * H * D for n in lives)
    return float(flops), float(nbytes)


def prefill_flops(m: Dict, prompt: int) -> int:
    """A prompt's prefill: every matmul over every position, causal
    attention (position t attends to t + 1 keys), and the unembedding of
    the last position only."""
    L = m["num_hidden_layers"]
    causal = prompt * (prompt + 1) // 2
    attn = 4 * m["num_attention_heads"] * m["head_dim"] * causal * L
    return 2 * prompt * L * layer_matmul_params(m) + attn \
        + 2 * unembed_params(m)


def least_seconds(flops: float, nbytes: float,
                  pk: Dict[str, float]) -> Tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    tc, tm = flops / pk["bf16_flop_per_s"], nbytes / pk["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
