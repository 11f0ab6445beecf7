"""Scope page gather/scatter — Pallas TPU kernel.

The fallback transport (§5.6) and ``copy_from`` deep copies move *pages*:
gather the scope's pages from the pool into a contiguous wire buffer (for
the pod-axis ``ppermute``) and scatter them back into the destination
pool. The page list is a scalar-prefetched "pointer" array, exactly like
the paged-attention block table — the same sandbox clamp applies.

This is also the measured ``memcpy`` baseline of Table 1b: copying N
pages costs O(N·page_bytes) HBM traffic, while seal+sandbox costs O(1)
permission-word updates — the crossover the paper reports at 2 pages.

Grid: (n_pages,); one page per step. A pool is ``(N, *row)``: page ``i``
is the whole trailing block ``pool[i]``. The kernel views it as
``(N, R, C)`` (C the last dim, R the product of the others) and moves one
``(R, C)`` block per step — full trailing dims, so the block obeys the
TPU tiling rule at any row shape. A KV pool ``(L, P, Hkv, T, D)`` is
passed as its ``(L·P, Hkv·T, D)`` view.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _copy_kernel(pages_ref, src_ref, out_ref):
    out_ref[...] = src_ref[...]


def _scatter_kernel(pages_ref, buf_ref, pool_in_ref, out_ref):
    out_ref[...] = buf_ref[...]


def _rows(a):
    """``(N, *row)`` → ``(N, R, C)``."""
    return a.reshape(a.shape[0], math.prod(a.shape[1:-1]), a.shape[-1])


def gather_pages_pallas(pool, pages, *, interpret: bool = False):
    """pool: (N, *row); pages: (n,) i32 → (n, *row)."""
    N = pool.shape[0]
    n = pages.shape[0]
    view = _rows(pool)
    blk = (None,) + view.shape[1:]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(blk, lambda i, pages: (
                jnp.clip(pages[i], 0, N - 1), 0, 0)),
        ],
        out_specs=pl.BlockSpec(blk, lambda i, pages: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n,) + view.shape[1:], pool.dtype),
        interpret=interpret,
        name="gather_pages",
    )(pages, view)
    return out.reshape((n,) + pool.shape[1:])


def scatter_pages_pallas(pool, pages, buf, *, interpret: bool = False):
    """Write buf (n, *row) into pool rows `pages`; returns the updated pool.

    Uses input_output_aliasing so the pool is updated in place on TPU (the
    destination pool is the resident shared heap — no reallocation).
    """
    N = pool.shape[0]
    n = pages.shape[0]
    view = _rows(pool)
    blk = (None,) + view.shape[1:]

    row = lambda i, pages: (jnp.clip(pages[i], 0, N - 1), 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(blk, lambda i, pages: (i, 0, 0)),  # wire buffer
            pl.BlockSpec(blk, row),                         # aliased pool
        ],
        out_specs=pl.BlockSpec(blk, row),
    )
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(view.shape, pool.dtype),
        input_output_aliases={2: 0},  # pool (input 2, after scalars) ↔ out
        interpret=interpret,
        name="scatter_pages",
    )(pages, _rows(buf), view)
    return out.reshape(pool.shape)
