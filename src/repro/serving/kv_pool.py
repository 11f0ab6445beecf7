"""PagedKVPool — the KV cache as an RPCool shared-memory heap.

The pool is the TPU-resident instantiation of the paper's shared heap:
  * a page = one KV block (page_tokens × kv_heads × head_dim × 2 (K,V) ×
    num_layers) — the natural protection granule on TPU (DESIGN.md §2);
  * page accounting, ownership, permissions, leases and quotas all run
    through the SharedHeap/Orchestrator machinery from repro.core —
    the pool *is* a heap, not a lookalike;
  * block tables are GlobalAddr-style pointers (page indices) — the
    pointer-rich RPC argument of the serving data plane;
  * seals: prefill write-protects a request's pages before the handoff
    RPC; the paged-attention kernel *verifies the seal on every
    dereference* (Fig. 8 step 4, done in silicon);
  * sandbox bitmap: pages owned by the connection — a wild block-table
    entry pointing at another request's pages is masked + flagged.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.errors import AllocationError, ChannelError, Overloaded
from ..core.heap import SharedHeap
from ..core.orchestrator import Orchestrator
from ..core.seal import SealManager
from ..models.config import ModelConfig

# back-off hint carried by pool-pressure sheds (§5.4 retry-after): long
# enough for a decode step or two to retire pages, short enough that a
# retrying client wastes no meaningful time
POOL_RETRY_AFTER_S = 0.02


@dataclass
class PoolConfig:
    num_pages: int = 256
    page_tokens: int = 16
    max_pages_per_seq: int = 32


class PagedKVPool:
    def __init__(self, orch: Orchestrator, cfg: ModelConfig,
                 pool_cfg: PoolConfig, owner_pid: int,
                 pod: Optional[str] = None):
        self.cfg = cfg
        self.pc = pool_cfg
        L = cfg.num_layers
        T, P = pool_cfg.page_tokens, pool_cfg.num_pages
        Hkv, D = cfg.num_kv_heads, cfg.head_dim

        # page byte size for quota accounting (K+V, all layers)
        page_bytes = 2 * L * T * Hkv * D * 2
        self.page_bytes = page_bytes
        self.heap = orch.create_heap(P, page_size=page_bytes,
                                     name="kv_pool")
        orch.map_heap(owner_pid, self.heap)
        self.seals = SealManager(self.heap, capacity=4 * P)
        # NOTE: the seal descriptor ring consumed heap pages 0..r-1; those
        # pages exist in the device pool too but are never handed to
        # requests (state == USED, owner 0).
        # head-major pages: one KV head of a page is one (T, D) tile
        self.k = jnp.zeros((L, P, Hkv, T, D), jnp.bfloat16)
        self.v = jnp.zeros((L, P, Hkv, T, D), jnp.bfloat16)
        self.owner_pid = owner_pid
        self.orch = orch
        self.pod = pod
        if pod is not None:
            # publish as the pod's KV pool: cross-pod byref arguments
            # resolve their destination pages against this registry
            orch.register_pool(pod, self)
        # byref data-plane accounting: bytes bulk-migrated into/out of
        # this pool by cross-pod pool-page RPCs (zero on the CXL route —
        # that is the paper's claim, and what the tests assert)
        self.byref_bytes_in = 0
        self.byref_bytes_out = 0

    # -- allocation (pointer minting) -----------------------------------
    def pages_owned(self, conn_id: int) -> int:
        """Pool pages ``conn_id`` currently owns (quota accounting)."""
        return int(((self.heap.owner == conn_id)
                    & (self.heap.state == 1)).sum())

    def _check_page_quota(self, conn_id: int, n_pages: int) -> None:
        quota = self.orch.page_quota(conn_id)
        if quota is None:
            return
        owned = self.pages_owned(conn_id)
        if owned + n_pages > quota:
            raise Overloaded(
                f"conn {conn_id}: admit needs {n_pages} pages but "
                f"{owned}/{quota} of its page quota are in use (§5.4)",
                retry_after_s=POOL_RETRY_AFTER_S)

    def alloc_seq(self, n_tokens: int, conn_id: int) -> List[int]:
        n_pages = max(1, -(-n_tokens // self.pc.page_tokens))
        if n_pages > self.pc.max_pages_per_seq:
            raise ValueError("sequence exceeds max_pages_per_seq")
        self._check_page_quota(conn_id, n_pages)
        # pages need not be contiguous: one-page extents (block tables
        # chase pointers anyway — that is the point of the paper).
        # A mid-sequence allocation failure must hand the partial list
        # back: the caller never sees these pages, so anything already
        # minted would otherwise leak until the pool starves.
        pages: List[int] = []
        try:
            for _ in range(n_pages):
                pages.append(self.heap.alloc_pages(1, owner=conn_id))
        except AllocationError:
            for p in pages:
                self.heap.free_extent(p, 1)
            raise
        return pages

    def extend_seq(self, pages: List[int], n_tokens: int,
                   conn_id: int) -> List[int]:
        need = max(1, -(-n_tokens // self.pc.page_tokens))
        if need > self.pc.max_pages_per_seq:
            raise ValueError("sequence exceeds max_pages_per_seq")
        if need > len(pages):
            self._check_page_quota(conn_id, need - len(pages))
        grown = 0
        try:
            while len(pages) < need:
                pages.append(self.heap.alloc_pages(1, owner=conn_id))
                grown += 1
        except AllocationError:
            # same audit as alloc_seq: a failed growth leaves the input
            # list exactly as it was — the pages this call minted go back
            for _ in range(grown):
                self.heap.free_extent(pages.pop(), 1)
            raise
        return pages

    def free_seq(self, pages: List[int]) -> None:
        for p in pages:
            self.heap.free_extent(p, 1)

    # -- seal protocol around the handoff RPC -----------------------------
    def seal_seq(self, pages: List[int], holder: int) -> List[int]:
        return [self.seals.seal((p, 1), holder=holder) for p in pages]

    def complete_and_release(self, seal_idxs: List[int], holder: int,
                             batched: bool = True) -> None:
        for idx in seal_idxs:
            self.seals.mark_complete(idx)
            if batched:
                self.seals.release_batched(idx, holder=holder)
            else:
                self.seals.release(idx, holder=holder)

    # -- device-side permission state for the kernel -----------------------
    def perm_bits(self) -> jnp.ndarray:
        return jnp.asarray(self.heap.perm.astype(np.int32))

    def sandbox_bitmap(self, conn_id: int) -> jnp.ndarray:
        """Pages this connection may dereference (the MPK key check)."""
        allowed = (self.heap.owner == conn_id) & (self.heap.state == 1)
        return jnp.asarray(allowed.astype(np.int32))

    def sandbox_desc(self, enforce: bool = True) -> jnp.ndarray:
        return jnp.asarray(
            [0, self.pc.num_pages, 1 if enforce else 0], jnp.int32)

    # -- data plane ----------------------------------------------------------
    def write_prefill(self, cache_k, cache_v, pages: List[int],
                      n_tokens: int) -> None:
        """Scatter a prefill's contiguous (L, S, Hkv, D) KV into pages."""
        T = self.pc.page_tokens
        nP = len(pages)
        pad = nP * T - n_tokens
        if pad:
            cache_k = jnp.pad(cache_k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            cache_v = jnp.pad(cache_v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        L = cache_k.shape[0]
        # (L, nP·T, Hkv, D) → (L, nP, Hkv, T, D)
        kb = cache_k.reshape(L, nP, T, *cache_k.shape[2:]).swapaxes(2, 3)
        vb = cache_v.reshape(L, nP, T, *cache_v.shape[2:]).swapaxes(2, 3)
        idx = jnp.asarray(pages, jnp.int32)
        self.k = self.k.at[:, idx].set(kb.astype(self.k.dtype))
        self.v = self.v.at[:, idx].set(vb.astype(self.v.dtype))

    def stats(self) -> Dict[str, int]:
        return self.heap.stats()


def transfer_pages_cross_pod(src_pool: "PagedKVPool",
                             dst_pool: "PagedKVPool",
                             src_pages: List[int], dst_pages: List[int],
                             backend: Optional[str] = None,
                             link=None) -> int:
    """The RDMA/DCN fallback data plane (§4.7): when prefill and decode
    live in different pods (no shared ICI domain), the block-table RPC
    degrades to gather(src pages) → wire → scatter(dst pages). Returns
    the bytes moved — the number the zero-copy path avoids entirely.

    On hardware the wire hop is a ``ppermute`` over the ``pod`` mesh axis
    (see launch/collectives.kv_handoff_lowering, which the dry-run lowers
    to count collective bytes); here the copy itself is executed.
    """
    import jax.numpy as jnp

    from ..kernels.scope_copy.ops import gather_pages, scatter_pages

    L, P = src_pool.k.shape[:2]
    sp = jnp.asarray(src_pages, jnp.int32)
    dp = jnp.asarray(dst_pages, jnp.int32)
    # (L, P, Hkv, T, D) → (L·P, Hkv·T, D): one page row per (layer, page)
    flat = lambda a: a.reshape(L * P, -1, a.shape[-1])

    moved = 0
    for name in ("k", "v"):
        src = flat(getattr(src_pool, name))
        dst = flat(getattr(dst_pool, name))
        # page ids offset per layer into the flattened (L·P, W) pool
        for l in range(L):
            wire = gather_pages(src, sp + l * P, backend=backend)
            dst = scatter_pages(dst, dp + l * P, wire, backend=backend)
            moved += wire.size * wire.dtype.itemsize
        setattr(dst_pool, name,
                dst.reshape(getattr(dst_pool, name).shape))
    src_pool.byref_bytes_out += moved
    dst_pool.byref_bytes_in += moved
    if link is not None:
        # ride the fallback plane's one-sided primitive: the whole
        # gather→wire→scatter lands as ONE asynchronous bulk put with a
        # completion word, charged to the same link accounting the RPC
        # flights use (cMPI framing, not per-message ping-pong)
        link.put_bytes(moved, to=1)
    return moved


class PoolPages:
    """A KV-pool page set passed *by reference* as an RPC argument.

    The argument form behind ``@method(byref=True)`` (§4.7 behind the
    §5.6 identical-surface contract): the stub resolves it per dispatch
    against the route the connection actually took —

    * same pod (CXL ring): the raw page indices travel as the pointer
      set; zero KV bytes move (the paper's headline handoff);
    * cross pod (fallback link): destination pages are minted in the
      target pod's registered pool (``orch.register_pool``) and the KV
      migrates in ONE bulk ``scope_copy`` gather→wire→scatter transfer
      (``transfer_pages_cross_pod`` — the cMPI-style amortization, not
      per-message ping-pong), then the *destination* indices travel.

    Either way the handler receives a plain page-index list in its own
    pod's pool. ``last_moved_bytes`` records what the most recent
    resolution copied (0 on the pointer route) — the byte-accounting
    hook the tests and the serve benchmark read.
    """

    __slots__ = ("pool", "pages", "backend", "last_moved_bytes")

    def __init__(self, pool: PagedKVPool, pages: List[int],
                 backend: Optional[str] = None):
        self.pool = pool
        self.pages = list(pages)
        self.backend = backend
        self.last_moved_bytes = 0

    def _server_pid(self, conn) -> int:
        # RoutedConnection wraps the live target; bare connections carry
        # server_pid directly
        target = getattr(conn, "target", None) or conn
        pid = getattr(target, "server_pid", None)
        if pid is None:
            raise ChannelError(
                "byref argument needs a connection with a server pid "
                "(Connection / FallbackConnection / RoutedConnection)")
        return pid

    def __byref_resolve__(self, conn) -> List[int]:
        transport = getattr(conn, "transport", None)
        if transport in (None, "cxl"):
            # shared coherence domain: pointer passing, nothing copied
            self.last_moved_bytes = 0
            return list(self.pages)
        orch = self.pool.orch
        pod = orch.pod_of(self._server_pid(conn))
        if pod is None:
            raise ChannelError(
                "cross-pod byref dispatch but the serving pid has no "
                "pod assignment — cannot locate the destination pool")
        dst_pool: PagedKVPool = orch.pool_of_pod(pod)
        # mint the destination block table (owned by the decode pod's
        # pool owner so its sandbox bitmap admits the kernel reads),
        # then one bulk transfer for the whole page set
        dst_pages = dst_pool.alloc_seq(
            len(self.pages) * dst_pool.pc.page_tokens, dst_pool.owner_pid)
        target = getattr(conn, "target", None) or conn
        try:
            self.last_moved_bytes = transfer_pages_cross_pod(
                self.pool, dst_pool, self.pages, dst_pages,
                backend=self.backend,
                link=getattr(target, "link", None))
        except BaseException:
            dst_pool.free_seq(dst_pages)
            raise
        return dst_pages
