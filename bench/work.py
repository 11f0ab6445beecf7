"""Work that the algorithm needs, counted from shapes, where no layer
stack shapes it: the chip's peaks, the roofline's least time, and the
per-token K/V, attention and unembedding counts that every family that
pages its K/V through the one ``paged_attention`` kernel shares. What a
layer stack needs (its matrices, its experts) each family counts itself
(``bench/families/<family>.py``: ``decode_work``, ``prefill_flops``).

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


def unembed_params(m: Dict) -> int:
    """The unembedding's matrix (the embedding itself when tied)."""
    return m["hidden_size"] * m["vocab_size"]


def kv_token_bytes(m: Dict) -> int:
    """K and V of one token in every layer."""
    return BYTES * 2 * m["num_hidden_layers"] * m["num_key_value_heads"] \
        * m["head_dim"]


def attn_flops(m: Dict, live: int) -> int:
    """q.K and p.V of one query over ``live`` keys, all layers."""
    return 4 * m["num_attention_heads"] * m["head_dim"] * live \
        * m["num_hidden_layers"]


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


def least_seconds(flops: float, nbytes: float,
                  pk: Dict[str, float]) -> Tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    tc, tm = flops / pk["bf16_flop_per_s"], nbytes / pk["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
