"""Dispatching wrapper for page gather/scatter."""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from .. import default_backend
from .kernel import gather_pages_pallas, scatter_pages_pallas
from .ref import gather_pages_ref, scatter_pages_ref


@partial(jax.jit, static_argnames=("backend",))
def gather_pages(pool, pages, backend: Optional[str] = None):
    backend = backend or default_backend()
    if backend == "ref":
        return gather_pages_ref(pool, pages)
    return gather_pages_pallas(pool, pages,
                               interpret=(backend == "interpret"))


@partial(jax.jit, static_argnames=("backend",), donate_argnums=(0,))
def scatter_pages(pool, pages, buf, backend: Optional[str] = None):
    backend = backend or default_backend()
    if backend == "ref":
        return scatter_pages_ref(pool, pages, buf)
    return scatter_pages_pallas(pool, pages, buf,
                                interpret=(backend == "interpret"))
