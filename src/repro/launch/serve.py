"""End-to-end serving driver: continuous batching over the RPCool pool.

Serves a small GQA LM with batched requests through the full RPCool
path: pool pages leased from the orchestrator, prefill→decode handoff as
a sealed zero-copy RPC, sandboxed paged-attention decode, adaptive
busy-wait scheduling (§5.8).

CPU-runnable:  PYTHONPATH=src python -m repro.launch.serve \
                   --requests 16 --max-new 12

The kernels take their default backend: the compiled Pallas kernels on a
TPU, the jnp reference on the CPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import numpy as np

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is the cache (JAX reads it
    itself) and nothing here overrides it. Otherwise the cache is the
    fixed ``<repo>/.jax_cache``: the path is part of the cache key, so a
    directory that moved between runs would never hit."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--max-active", type=int, default=8)
    ap.add_argument("--pool-pages", type=int, default=256)
    ap.add_argument("--sleep-us", type=float, default=None,
                    help="fixed busy-wait sleep (default: §5.8 adaptive)")
    args = ap.parse_args()

    enable_compile_cache()
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import PoolConfig, ServeEngine

    cfg = dataclasses.replace(
        get_config("yi-9b"), name="serve-demo", num_layers=args.layers,
        d_model=args.d_model, num_heads=max(4, args.d_model // 64),
        num_kv_heads=max(2, args.d_model // 128), head_dim=64,
        d_ff=4 * args.d_model, vocab_size=8192)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    print(f"serving {cfg.name}: {cfg.param_count()/1e6:.1f}M params")

    eng = ServeEngine(
        cfg, params,
        PoolConfig(num_pages=args.pool_pages, page_tokens=16,
                   max_pages_per_seq=16),
        max_active=args.max_active, sleep_us=args.sleep_us)

    rng = np.random.default_rng(0)
    t0 = time.time()
    rids = []
    for _ in range(args.requests):
        prompt = list(rng.integers(1, cfg.vocab_size,
                                   size=int(rng.integers(4, 24))))
        rids.append(eng.submit(prompt, max_new=args.max_new))
    eng.run_until_drained()
    dt = time.time() - t0

    total_tokens = sum(len(eng.result(r)) for r in rids)
    print(f"{len(rids)} requests, {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens/dt:.1f} tok/s)")
    print(f"decode steps: {eng.decode_steps}  "
          f"handoff bytes (pointers only): {eng.handoff_bytes}  "
          f"sandbox violations: {eng.oob_events}")
    print(f"pool: {eng.pool.stats()}")
    for r in rids[:4]:
        print(f"  req {r}: {eng.result(r)}")


if __name__ == "__main__":
    main()
