"""Pure-jnp oracle for paged decode attention with sandbox semantics."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PERM_SEALED = 1


def paged_attention_ref(q, k_pool, v_pool, block_tab, seq_lens, perm_bits,
                        sandbox, bitmap, layer: int = 0):
    """Same contract as the kernel. All math in fp32.

    q: (B, Hq, D); pools: (L, P, Hkv, T, D); block_tab: (B, MAXP).
    Returns (out (B, Hq, D), oob (B,) i32).
    """
    k_pool, v_pool = k_pool[layer], v_pool[layer]
    B, Hq, D = q.shape
    P, Hkv, T, _ = k_pool.shape
    MAXP = block_tab.shape[1]
    qpk = Hq // Hkv
    scale = 1.0 / math.sqrt(D)

    sb_lo, sb_hi, sb_on = sandbox[0], sandbox[1], sandbox[2]
    n_needed = (seq_lens + T - 1) // T                     # (B,)
    page_idx = jnp.arange(MAXP)[None, :]                   # (1, MAXP)
    in_use = page_idx < n_needed[:, None]                  # (B, MAXP)

    clamped = jnp.clip(block_tab, 0, P - 1)
    in_bounds = (block_tab >= sb_lo) & (block_tab < sb_hi)
    allowed = bitmap[clamped] > 0
    sealed = (perm_bits[clamped] & PERM_SEALED) > 0
    ok = in_bounds & allowed & sealed
    valid_page = in_use & jnp.where(sb_on > 0, ok, in_bounds)
    oob = jnp.sum(in_use & ~valid_page, axis=1).astype(jnp.int32)

    # gather pages: (B, MAXP, Hkv, T, D) → (B, MAXP·T, Hkv, D)
    k = k_pool[clamped].astype(jnp.float32).transpose(0, 1, 3, 2, 4)
    v = v_pool[clamped].astype(jnp.float32).transpose(0, 1, 3, 2, 4)
    k = k.reshape(B, MAXP * T, Hkv, D)
    v = v.reshape(B, MAXP * T, Hkv, D)

    tok_pos = (page_idx[..., None] * T + jnp.arange(T)[None, None, :])
    tok_ok = (tok_pos < seq_lens[:, None, None]) & valid_page[..., None]
    tok_ok = tok_ok.reshape(B, MAXP * T)

    qg = q.astype(jnp.float32).reshape(B, Hkv, qpk, D)
    s = jnp.einsum("bgpd,btgd->bgpt", qg, k) * scale
    s = jnp.where(tok_ok[:, None, None, :], s, -jnp.inf)
    # rows with zero valid tokens → zero output
    any_valid = jnp.any(tok_ok, axis=-1)[:, None, None]
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(any_valid[..., None], w, 0.0)
    out = jnp.einsum("bgpt,btgd->bgpd", w, v).reshape(B, Hq, D)
    return out.astype(q.dtype), oob
