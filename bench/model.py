"""A configuration file as the harness uses it: one flat dict of sizes.

The file keeps the published keys at its top level, of which its
family's ``KEYS`` are kept; ``family`` names the layer stack
(``bench/families/<family>.py``, ``dense`` where absent); ``program``
holds what the program needs besides (head size, norm and MLP kinds), and
``serving`` the pool. ``rehearsal`` swaps in smoke sizes for the CPU
rehearsal (``--rehearse``), which no chip run uses.
"""

from __future__ import annotations

from typing import Any, Dict

from . import spec


def family(c: Dict[str, Any]):
    """The family module of a configuration file or a flat dict."""
    return spec.family(c.get("family", "dense"))


def flat(cfg: Dict[str, Any], rehearse: bool = False) -> Dict[str, Any]:
    out = {k: cfg[k] for k in family(cfg).KEYS}
    out.update(cfg["program"])
    out.update(cfg["serving"])
    out["norm_eps"] = float(cfg["program"].get(
        "norm_eps", cfg.get("rms_norm_eps", 1e-5)))
    out["name"] = cfg["name"]
    out["family"] = cfg.get("family", "dense")
    out["reference"] = cfg["reference"]
    out["prompt_divisor"] = out["output_divisor"] = 1
    if rehearse:
        out.update(cfg["rehearsal"])
        out["name"] = cfg["name"] + "-rehearsal"
    return out


def pool_config(m: Dict[str, Any]):
    from repro.serving import PoolConfig

    return PoolConfig(num_pages=m["pool_pages"],
                      page_tokens=m["page_tokens"],
                      max_pages_per_seq=m["max_pages_per_seq"])
