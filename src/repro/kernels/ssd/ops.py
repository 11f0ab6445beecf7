"""Dispatching wrapper: Pallas intra-chunk kernel + XLA inter-chunk scan.

Drop-in for ``models.ssm.ssd_chunked`` (same signature/semantics).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .. import default_backend
from .kernel import ssd_intra_chunk_pallas


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, init_state=None,
                backend: Optional[str] = None):
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm/Cm: (B,S,G,N), G=1.

    Returns (y (B,S,H,P), final_state (B,H,N,P)).
    """
    backend = backend or default_backend()
    if backend == "ref":
        from .ref import ssd_chunked_ref

        return ssd_chunked_ref(x, dt, A, Bm, Cm, chunk, init_state)

    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nc = Sp // Q

    y_diag, states, decay = ssd_intra_chunk_pallas(
        x, dt, A, Bm[:, :, 0], Cm[:, :, 0], chunk=Q,
        interpret=(backend == "interpret"))

    # inter-chunk state recurrence (tiny — O(nc) steps of (B,H,N,P))
    s0 = (init_state.astype(jnp.float32) if init_state is not None
          else jnp.zeros((B, H, N, P), jnp.float32))

    def step(s_prev, inp):
        dec, st = inp
        return s_prev * dec[:, :, None, None] + st, s_prev

    s_final, s_prevs = jax.lax.scan(
        step, s0, (decay.swapaxes(0, 1), states.swapaxes(0, 1)))
    s_prevs = s_prevs.swapaxes(0, 1)                    # (B,nc,H,N,P)

    # off-diagonal contribution: carried state read through C with decay
    dA = dt.astype(jnp.float32) * A.astype(jnp.float32)[None, None, :]
    cum = jnp.cumsum(dA.reshape(B, nc, Q, H), axis=2)
    y_off = jnp.einsum("bcin,bchnp,bcih->bcihp",
                       Cm[:, :, 0].astype(jnp.float32).reshape(B, nc, Q, N),
                       s_prevs, jnp.exp(cum))
    y = (y_diag.reshape(B, nc, Q, H, P) + y_off).reshape(B, Sp, H, P)
    return y[:, :S].astype(x.dtype), s_final
