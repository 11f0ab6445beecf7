"""A configuration file as the harness uses it: one flat dict of sizes.

The file keeps the published keys at its top level; ``program`` holds
what the program needs besides (head size, norm and MLP kinds), and
``serving`` the pool. ``rehearsal`` swaps in smoke sizes for the CPU
rehearsal (``--rehearse``), which no chip run uses.
"""

from __future__ import annotations

from typing import Any, Dict

_KEYS = ("num_hidden_layers", "hidden_size", "intermediate_size",
         "num_attention_heads", "num_key_value_heads", "vocab_size",
         "rope_theta", "tie_word_embeddings", "max_position_embeddings")


def flat(cfg: Dict[str, Any], rehearse: bool = False) -> Dict[str, Any]:
    out = {k: cfg[k] for k in _KEYS}
    out.update(cfg["program"])
    out.update(cfg["serving"])
    out["norm_eps"] = float(cfg["program"].get(
        "norm_eps", cfg.get("rms_norm_eps", 1e-5)))
    out["name"] = cfg["name"]
    out["reference"] = cfg["reference"]
    out["prompt_divisor"] = out["output_divisor"] = 1
    if rehearse:
        out.update(cfg["rehearsal"])
        out["name"] = cfg["name"] + "-rehearsal"
    return out


def program_config(m: Dict[str, Any]):
    """The program's ``ModelConfig`` for these sizes."""
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=m["name"], family="dense",
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        rope_kind=m["rope_kind"], rope_theta=float(m["rope_theta"]),
        norm_kind=m["norm_kind"], norm_eps=m["norm_eps"],
        mlp_kind=m["mlp_kind"], tie_embeddings=m["tie_word_embeddings"],
        max_seq_len=m["max_pages_per_seq"] * m["page_tokens"])


def pool_config(m: Dict[str, Any]):
    from repro.serving import PoolConfig

    return PoolConfig(num_pages=m["pool_pages"],
                      page_tokens=m["page_tokens"],
                      max_pages_per_seq=m["max_pages_per_seq"])
