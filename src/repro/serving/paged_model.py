"""PagedLM — decode path over the RPCool KV pool.

A vLLM-lite forward for uniform GQA decoder stacks (dense / vlm
families): prefill reuses the standard stack (and on TPU the
flash_prefill kernel); decode projects q/k/v per layer and attends
through the **paged_attention kernel**, dereferencing block-table
pointers under the sandbox contract. The per-layer python loop is fine
at serving scale (the engine demos run ≤ 8-layer configs; the full-size
decode path for the dry-run uses the scan-based dense-cache model).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.paged_attention.ops import paged_attention
from ..models.attention import _project_kv, _project_q
from ..models.config import ModelConfig
from ..models.layers import apply_norm, apply_rope, embed_tokens, mlp_apply, unembed
from ..models.model import Model

Params = Dict[str, Any]


def _layer_params(stack: Params, layer: int) -> Params:
    """Slice layer ``layer`` out of the stacked pos0 params."""
    return jax.tree.map(lambda x: x[layer], stack["pos0"])


def _write_token(pool, layer: int, page, slot, new):
    """Write one token per sequence into ``pool[layer]`` (P, Hkv, T, D).

    ``new``: (B, Hkv, D) at ``(page[b], :, slot[b])``. The write goes
    through whole pages: read each page, set its row, store the page
    back. A scatter of single rows would make the TPU compiler relayout
    the whole pool around every layer's write; whole (T, D) tiles keep
    the pool in the layout the attention kernel reads. A row whose
    ``page`` lies past the pool writes nothing."""
    pages = pool[layer, page]                               # (B, Hkv, T, D)
    b = jnp.arange(page.shape[0])
    pages = pages.at[b, :, slot].set(new.astype(pool.dtype))
    return pool.at[layer, page].set(pages, mode="drop")


def check_paged_compatible(cfg: ModelConfig) -> None:
    pattern = cfg.block_pattern()
    if len(pattern) != 1 or pattern[0].kind != "attn" or pattern[0].moe:
        raise ValueError(
            f"{cfg.name}: PagedLM serves uniform dense-attention stacks; "
            "MoE/SSM/hybrid archs use the dense-cache decode path")


@partial(jax.jit, static_argnames=("cfg", "backend"))
def paged_decode_step(cfg: ModelConfig, params: Params, tokens, pos,
                      block_tab, seq_lens, k_pool, v_pool, perm_bits,
                      sandbox, bitmap, backend: Optional[str] = None):
    """One decode step over the pool.

    tokens: (B,) i32; pos: (B,) i32 (position being generated);
    block_tab: (B, MAXP); seq_lens: (B,) valid length AFTER this token.
    A row with ``seq_lens`` 0 is empty (batch padding): it attends to
    nothing and writes nothing, whatever its block table holds.
    k_pool/v_pool: (L, P, Hkv, T, D). Returns (logits, k_pool, v_pool,
    oob_total) — pools updated with this token's KV.
    """
    spec = cfg.block_pattern()[0]
    T = k_pool.shape[3]
    B = tokens.shape[0]

    x = embed_tokens(tokens[:, None], params["embed"], cfg.embed_scale,
                     cfg.d_model)
    page = jnp.take_along_axis(block_tab, (pos // T)[:, None], axis=1)[:, 0]
    page = jnp.where(seq_lens > 0, page, k_pool.shape[1])   # empty: no write
    slot = pos % T
    oob_total = jnp.zeros((B,), jnp.int32)

    for l in range(cfg.num_layers):
        lp = _layer_params(params["stack"], l)
        h = apply_norm(x, lp.get("norm_in"), cfg.norm_kind, cfg.norm_eps)
        q = _project_q(h, lp["attn"], cfg)              # (B, 1, Hq, D)
        k_new, v_new = _project_kv(h, lp["attn"], cfg)  # (B, 1, Hkv, D)
        if cfg.rope_kind in ("rope", "mrope"):
            # text-only decode: M-RoPE with equal t/h/w streams ≡ RoPE
            q = apply_rope(q, pos[:, None], spec.rope_theta)
            k_new = apply_rope(k_new, pos[:, None], spec.rope_theta)

        # write this token's KV into its page slot (the pool is the heap)
        k_pool = _write_token(k_pool, l, page, slot, k_new[:, 0])
        v_pool = _write_token(v_pool, l, page, slot, v_new[:, 0])

        out, oob = paged_attention(
            q[:, 0], k_pool, v_pool, block_tab, seq_lens,
            perm_bits, sandbox, bitmap, backend=backend, layer=l)
        oob_total = oob_total + oob
        a = jnp.einsum("bhk,hkd->bd", out, lp["attn"]["wo"])[:, None]
        x = x + a
        h = apply_norm(x, lp.get("norm_mlp"), cfg.norm_kind, cfg.norm_eps)
        x = x + mlp_apply(h, lp["mlp"], cfg.mlp_kind)

    x = apply_norm(x, params.get("norm_f"), cfg.norm_kind, cfg.norm_eps)
    logits = unembed(x, params["embed"])[:, 0].astype(jnp.float32)
    return logits, k_pool, v_pool, oob_total


def prefill_kv(model: Model, params: Params, tokens) -> Tuple[Any, Any, Any]:
    """Run prefill through the standard stack; returns (last_logits,
    k (L,B,S,Hkv,D), v). The engine slices [:, b] per request for
    PagedKVPool.write_prefill.

    ``Model.prefill`` is eager — called bare it re-traces (and
    re-compiles the layer scan) on EVERY admission, which turns a
    sub-millisecond prompt pass into ~1s of XLA time per request and
    serialises the continuous-batching ramp-up. One jit wrapper per
    model instance fixes that; jax's own cache then keys on the prompt
    shape.
    """
    fn = getattr(model, "_prefill_kv_jit", None)
    if fn is None:
        def _run(params, tokens):
            logits, cache = model.prefill(params, {"tokens": tokens},
                                          cache_len=tokens.shape[1])
            kv = cache["pos0"]["self"]
            return logits, kv["k"], kv["v"]
        fn = jax.jit(_run)
        model._prefill_kv_jit = fn
    return fn(params, tokens)
