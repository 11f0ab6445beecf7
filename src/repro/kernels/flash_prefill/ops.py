"""Dispatching wrapper for the flash prefill kernel."""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from .. import default_backend
from .kernel import flash_prefill_pallas
from .ref import flash_prefill_ref


@partial(jax.jit,
         static_argnames=("window", "softcap", "bq", "bk", "backend"))
def flash_prefill(q, k, v, window: int = 0, softcap: float = 0.0,
                  bq: int = 512, bk: int = 512,
                  backend: Optional[str] = None):
    backend = backend or default_backend()
    if backend == "ref":
        return flash_prefill_ref(q, k, v, window=window, softcap=softcap)
    return flash_prefill_pallas(q, k, v, window=window, softcap=softcap,
                                bq=bq, bk=bk,
                                interpret=(backend == "interpret"))
