"""GPipe-style pipeline parallelism over the ``pod`` axis (optional).

At 2 pods the default policy is DP over ``pod`` (gradient all-reduce of
N_params bytes once per step beats activation ppermute per microbatch for
every assigned config — see EXPERIMENTS.md §Perf napkin math). PP exists
for the regimes where it wins: models whose per-pod parameter shards do
not fit (≫52B dense), or DCN-starved clusters.

Implementation: ``shard_map`` over ``pod``; each pod holds
``num_blocks/n_stages`` of the super-block stack; microbatches stream
with ``jax.lax.ppermute`` boundary handoffs in a scan (GPipe fill/drain
schedule, bubble fraction (n_stages−1)/(n_micro+n_stages−1)).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import shard_map

Params = Any


def pipeline_apply(fn_stage: Callable, x, stage_params, *, mesh,
                   axis: str = "pod", n_micro: int = 4):
    """Run ``fn_stage(x, params)`` as a GPipe pipeline over ``axis``.

    x: (B, ...) global batch (microbatched internally).
    stage_params: params pytree whose leaves carry a leading stage dim
    sharded over ``axis`` (each pod sees its own stage slice).
    Returns the final stage's outputs gathered to all pods.
    """
    n_stages = mesh.shape[axis]

    def per_pod(x_local, params_local):
        # params_local leaves: (1, ...) — this pod's stage
        params_here = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)

        B = x_local.shape[0]
        mb = B // n_micro
        micros = x_local.reshape((n_micro, mb) + x_local.shape[1:])

        n_ticks = n_micro + n_stages - 1
        # carries become pod-varying through ppermute; the shard_map below
        # runs with the vma check off (check_vma=False), so no lax.pcast
        buf = jnp.zeros((mb,) + x_local.shape[1:], x_local.dtype)
        outs = jnp.zeros_like(micros)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (when in fill window)
            inject = jnp.logical_and(stage == 0, t < n_micro)
            mb_in = micros[jnp.clip(t, 0, n_micro - 1)]
            cur = jnp.where(inject, mb_in, buf)
            # every stage runs its slice
            y = fn_stage(cur, params_here)
            # pass downstream (ring; last stage's output wraps but is
            # ignored by stage 0's inject)
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            # last stage records microbatch (t - (n_stages-1)); masked
            # write (lax.cond branches disagree on varying axes under
            # shard_map — a where-select does not)
            out_idx = t - (n_stages - 1)
            is_out = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
            sel = jnp.arange(n_micro) == jnp.clip(out_idx, 0, n_micro - 1)
            outs = jnp.where((is_out & sel)[:, None, None], y[None], outs)
            return (nxt, outs), None

        (buf, outs), _ = jax.lax.scan(
            tick, (buf, outs), jnp.arange(n_ticks))
        # broadcast final outputs from the last stage to every pod
        # (ppermute needs unique sources; a masked psum broadcasts)
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, 0.0), axis)
        return outs.reshape(x_local.shape)

    return shard_map(
        per_pod, mesh=mesh,
        in_specs=(P(), P(axis)),
        out_specs=P(),
        check_vma=False,
    )(x, stage_params)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
