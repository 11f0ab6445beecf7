"""Paged decode attention with RPCool sandbox checks — Pallas TPU kernel.

The block table IS the RPC argument: a pointer-rich structure in shared
memory (§4.1). The kernel dereferences each "pointer" (pool page id) under
the sandbox contract (§4.4):

  * bounds check   — page must lie inside the sandboxed pool range;
  * bitmap check   — the sandbox permission bitmap must allow the page
                     (the MPK key check);
  * seal check     — the page must be SEALED (in-flight RPC args are
                     immutable, §4.5) — the receiver-side verification of
                     Fig. 8 step 4, done per dereference;

A violating dereference is *masked* (contributes nothing to the softmax)
and counted in the ``oob`` output — the kernel-space analogue of the
SIGSEGV→RPC-error path (a TPU kernel cannot trap).

Layout / tiling:
  q          (B, Hq, D)            — one decode token per sequence; the
                                     wrapper views it as (B, Hkv, qpk, D)
                                     so the kernel never reshapes
  k_pool     (L, P, Hkv, T, D)     — the shared KV heap of every layer
  v_pool     (L, P, Hkv, T, D)       (P pages × T tok), head-major so one
                                     KV head of a page is one (T, D)
                                     tile; the static ``layer`` picks the
                                     slice in the index map, so the
                                     layer is never copied out
  block_tab  (B, MAXP) int32       — scalar-prefetched (SMEM): drives the
                                     K/V BlockSpec index_map (the pointer
                                     dereference happens at DMA-issue time)
  seq_lens   (B,) int32            — valid tokens per sequence
  perm_bits  (P,) int32            — heap permission words (bit0 = SEALED)
  sandbox    (3,) int32            — lo page, hi page, enforce?
  bitmap     (P,) int32            — sandbox permission bitmap
  oob        (B,) int32 output     — whole array in SMEM

Grid: (B, MAXP). The page axis is innermost so the online-softmax scratch
(m, l, acc) carries across pages of one sequence in VMEM. A live grid step
(``p < ceil(seq_len / T)``) DMAs one (Hkv, T, D) K page + V page into VMEM
and runs two matmuls batched over the KV heads: (Hkv, qpk, D)·(Hkv, T, D)ᵀ
and (Hkv, qpk, T)·(Hkv, T, D). m and l are kept lane-broadcast over 128
lanes. A dead step costs only the grid step itself and its scalar
sandbox checks: its index map repeats the row's last live page
(``live_slot``), so the pipeline issues no new K/V DMA and never fetches
the page a dead slot names, and its body skips the math. A row of length
0 reads as empty (zeros out, no ``oob``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_PAGE_TOKENS = 64

PERM_SEALED = 1  # bit0 — mirrors repro.core.heap.PERM_SEALED

_LANES = 128


def live_slot(p, seq_len, page_tokens: int):
    """The block-table slot grid step ``p`` reads K/V from: ``p`` while
    it is live, else the row's last live slot, and slot 0 for a row of
    length 0. Consecutive dead steps thus keep one block index, so the
    pipeline fetches nothing for them."""
    n_live = (seq_len + page_tokens - 1) // page_tokens
    return jnp.minimum(p, jnp.maximum(n_live - 1, 0))


def _kernel(
    # scalar-prefetch refs (SMEM)
    block_tab_ref, seq_lens_ref, perm_ref, sandbox_ref, bitmap_ref,
    # array refs (VMEM blocks)
    q_ref, k_ref, v_ref,
    # outputs
    out_ref, oob_ref,
    # scratch
    m_ref, l_ref, acc_ref,
    *,
    page_tokens: int,
    head_dim: int,
    max_pages: int,
):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        oob_ref[b] = 0

    page_id = block_tab_ref[b, p]
    seq_len = seq_lens_ref[b]
    sb_lo, sb_hi, sb_on = sandbox_ref[0], sandbox_ref[1], sandbox_ref[2]

    # ---- the sandboxed dereference (§4.4) --------------------------------
    n_pages_needed = (seq_len + page_tokens - 1) // page_tokens
    in_use = p < n_pages_needed
    in_bounds = (page_id >= sb_lo) & (page_id < sb_hi)
    clamped = jnp.clip(page_id, 0, bitmap_ref.shape[0] - 1)
    allowed = bitmap_ref[clamped] > 0
    sealed = (perm_ref[clamped] & PERM_SEALED) > 0
    ok = in_bounds & allowed & sealed
    valid_page = in_use & jnp.where(sb_on > 0, ok, in_bounds)

    # SIGSEGV analogue: count violating dereferences of in-use entries
    oob_ref[b] += jnp.where(in_use & ~valid_page, 1, 0).astype(jnp.int32)

    scale = 1.0 / math.sqrt(head_dim)

    # ---- online softmax over this page, every KV head at once -----------
    # A masked page contributes nothing (pexp 0, alpha 1), so skipping
    # dead and violating pages leaves every output bit as it was.
    @pl.when(valid_page)
    def _update():
        tok_pos = p * page_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page_tokens), 2)
        tok_ok = tok_pos < seq_len                           # (1, 1, T)
        q = q_ref[...].astype(jnp.float32)                   # (Hkv, qpk, D)
        k = k_ref[...].astype(jnp.float32)                   # (Hkv, T, D)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale      # (Hkv, qpk, T)
        s = jnp.where(tok_ok, s, -jnp.inf)
        m_prev = m_ref[:, :, :1]                             # (Hkv, qpk, 1)
        l_prev = l_ref[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # guard: all -inf rows (nothing valid yet) — m stays -inf, alpha 1
        finite = m_new > -jnp.inf
        alpha = jnp.where(m_prev > -jnp.inf, jnp.exp(m_prev - m_new), 0.0)
        alpha = jnp.where(finite, alpha, 1.0)
        pexp = jnp.where(finite, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            pexp, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)              # (Hkv, qpk, D)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(p == max_pages - 1)
    def _finish():
        l = l_ref[:, :, :1]
        safe_l = jnp.where(l > 0, l, 1.0)
        out_ref[...] = (acc_ref[...] / safe_l).astype(out_ref.dtype)


def paged_attention_pallas(
    q, k_pool, v_pool, block_tab, seq_lens, perm_bits, sandbox, bitmap,
    *, layer: int = 0, interpret: bool = False,
):
    """q: (B, Hq, D); pools: (L, P, Hkv, T, D); block_tab: (B, MAXP) i32.

    Attends over layer ``layer`` of the pools. Returns (out (B, Hq, D),
    oob (B,) i32).
    """
    B, Hq, D = q.shape
    _, P, Hkv, T, _ = k_pool.shape
    MAXP = block_tab.shape[1]
    qpk = Hq // Hkv

    def q_map(b, p, *refs):
        return (b, 0, 0, 0)

    def kv_map(b, p, block_tab, seq_lens, perm, sandbox, bitmap):
        page = block_tab[b, live_slot(p, seq_lens[b], T)]
        return (layer, jnp.clip(page, 0, P - 1), 0, 0, 0)

    kernel = functools.partial(
        _kernel, page_tokens=T, head_dim=D, max_pages=MAXP)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, MAXP),
        in_specs=[
            pl.BlockSpec((None, Hkv, qpk, D), q_map),
            pl.BlockSpec((None, None, Hkv, T, D), kv_map),
            pl.BlockSpec((None, None, Hkv, T, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, Hkv, qpk, D), q_map),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((Hkv, qpk, _LANES), jnp.float32),
            pltpu.VMEM((Hkv, qpk, _LANES), jnp.float32),
            pltpu.VMEM((Hkv, qpk, D), jnp.float32),
        ],
    )

    out, oob = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, qpk, D), q.dtype),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ],
        interpret=interpret,
        name="paged_attention",
    )(block_tab, seq_lens, perm_bits, sandbox, bitmap,
      q.reshape(B, Hkv, qpk, D), k_pool, v_pool)
    return out.reshape(B, Hq, D), oob
