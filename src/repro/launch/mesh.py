"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run pins the device count via XLA_FLAGS
before any jax initialization).

Single pod:  (16, 16)    axes ("data", "model")      — 256 chips (v5e pod)
Multi-pod:   (2, 16, 16) axes ("pod", "data", "model") — 512 chips

Axis roles:
  pod    DP across pods (DCN); gradient all-reduce crosses it once/step,
         optionally int8-compressed (training/grad_comp). Also the PP
         axis when pipeline mode is enabled.
  data   DP within the pod (ICI); also context-parallel KV for batch-1
         long-context decode (hillclimb variant).
  model  TP/EP/SP: attention heads, MoE experts, d_ff, vocab.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
