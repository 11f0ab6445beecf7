"""Dispatching wrapper for paged decode attention.

backend="tpu"       → compiled Pallas kernel
backend="interpret" → Pallas interpret mode (kernel body on CPU, tests)
backend="ref"       → pure-jnp oracle (CPU runs, logit cross-checks)
default (None)      → ``repro.kernels.default_backend()``: tpu on a TPU,
                      ref on the CPU, an error anywhere else
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from .. import default_backend
from .kernel import paged_attention_pallas
from .ref import paged_attention_ref


@partial(jax.jit, static_argnames=("backend", "layer"))
def paged_attention(q, k_pool, v_pool, block_tab, seq_lens, perm_bits,
                    sandbox, bitmap, backend: Optional[str] = None,
                    layer: int = 0):
    """RPCool-sandboxed paged decode attention.

    q: (B, Hq, D); k/v_pool: (L, P, Hkv, T, D), attended at ``layer``;
    block_tab: (B, MAXP) i32; seq_lens: (B,) i32; perm_bits/bitmap: (P,)
    i32; sandbox: (3,) i32 [lo, hi, enforce].
    Returns (out (B, Hq, D), oob (B,) i32) where oob counts
    sandbox-violating page dereferences (≠0 ⇒ the RPC must be failed with
    E_SANDBOX, per §4.4).
    """
    backend = backend or default_backend()
    if backend == "ref":
        return paged_attention_ref(q, k_pool, v_pool, block_tab, seq_lens,
                                   perm_bits, sandbox, bitmap, layer=layer)
    return paged_attention_pallas(
        q, k_pool, v_pool, block_tab, seq_lens, perm_bits, sandbox, bitmap,
        layer=layer, interpret=(backend == "interpret"))
