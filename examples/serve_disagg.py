"""Disaggregated prefill→decode serving over RPCool (the paper, on TPU).

Walks the full handoff explicitly — what ServeEngine does per request:

  1. prefill worker leases pool pages from the orchestrator (quota'd),
  2. runs prefill, writes KV into the pages,
  3. builds the block table (pointers!) in an RPCool scope, seals it,
  4. RPC → decode worker: payload is ~48 bytes of pointers, not MBs of KV,
  5. decode worker verifies the seal and decodes via the paged-attention
     kernel, which bounds+seal-checks every pointer dereference,
  6. retire: batched seal release, pages freed, leases dropped.

Also demos the cross-pod fallback: the same handoff when the workers do
NOT share a pod — pages are gathered/copied/scattered (§4.7), and we
print the byte ratio the zero-copy path saves.

Run:  PYTHONPATH=src python examples/serve_disagg.py
"""

import dataclasses

import jax
import numpy as np

from repro.configs import get_config
from repro.models import build_model
from repro.serving import PoolConfig, ServeEngine
from repro.serving.kv_pool import PagedKVPool, transfer_pages_cross_pod
from repro.core.orchestrator import Orchestrator


def main() -> None:
    cfg = dataclasses.replace(
        get_config("yi-9b"), name="disagg-demo", num_layers=2,
        d_model=128, num_heads=4, num_kv_heads=2, head_dim=32,
        d_ff=512, vocab_size=4096)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    eng = ServeEngine(cfg, params,
                      PoolConfig(num_pages=64, page_tokens=8,
                                 max_pages_per_seq=8))

    rng = np.random.default_rng(0)
    rids = [eng.submit(list(rng.integers(1, cfg.vocab_size, size=6)),
                       max_new=8) for _ in range(4)]
    eng.run_until_drained()
    for r in rids:
        print(f"req {r}: {eng.result(r)}")
    print(f"\nzero-copy handoffs: {eng.handoff_bytes} bytes total "
          f"(block-table pointers only)")

    # ---- the cross-pod fallback for the same KV ---------------------------
    orch = Orchestrator()
    pc = PoolConfig(num_pages=64, page_tokens=8, max_pages_per_seq=8)
    pod0 = PagedKVPool(orch, cfg, pc, owner_pid=1)
    pod1 = PagedKVPool(orch, cfg, pc, owner_pid=2)
    pages = [5, 6]
    moved = transfer_pages_cross_pod(pod0, pod1, pages, [10, 11])
    print(f"cross-pod fallback for {len(pages)} pages: {moved:,} bytes "
          f"copied vs {8*len(pages)} pointer bytes in-pod "
          f"({moved/(8*len(pages)):,.0f}× more traffic)")


if __name__ == "__main__":
    main()
