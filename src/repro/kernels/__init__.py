"""Pallas TPU kernels for the compute hot-spots.

Each kernel ships three files:
  kernel.py — ``pl.pallas_call`` body with explicit BlockSpec VMEM tiling
  ops.py    — jit'd dispatching wrapper (pallas on TPU, interpret for
              tests, pure-jnp reference on the CPU — ``default_backend``)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

Kernels:
  paged_attention — decode attention dereferencing block-table "pointers"
                    into the shared KV pool under an RPCool sandbox
                    (bounds+seal checked per dereference — §4.4/§4.5 in
                    silicon) with online softmax accumulation.
  flash_prefill   — chunked causal flash attention (GQA, sliding window,
                    logit softcap) for 32k-token prefill.
  ssd             — Mamba-2 SSD intra-chunk kernel (decay-masked matmuls
                    on the MXU) + host-level inter-chunk scan.
  scope_copy      — page gather/scatter between pool and contiguous
                    buffers (fallback transport / memcpy baseline).
"""

import jax


def default_backend() -> str:
    """Backend for a kernel call that names none: the compiled kernel on
    a TPU, the pure-jnp reference on the CPU. A failed device query
    raises, and so does any other platform — no path falls back to the
    reference by swallowing an error."""
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return "tpu"
    if platform == "cpu":
        return "ref"
    raise RuntimeError(f"no kernel backend for platform {platform!r}")
