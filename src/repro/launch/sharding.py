"""Rule construction: logical axes → mesh axes, per (arch × shape × mesh).

Divisibility-driven: a logical axis maps to ``model`` only when the
corresponding dimension divides the model-axis size (XLA NamedSharding
requires even shards). Fallbacks:

  heads/kv_heads not divisible → attention stays head-replicated and the
      KV cache shards its *sequence* dim instead (``kv_seq``→model) — the
      context-sharded decode of long-KV serving;
  vocab not divisible (mamba2 50280, granite 49155, whisper 51865) → the
      embedding table shards its d_model dim (``vocab_embed``→model) and
      the chunked-CE path bounds the unsharded-logit transient;
  batch not divisible (long_500k B=1) → batch replicated; KV sharding
      carries the memory instead.

ZeRO-1: optimizer moments extend the param spec with the DP axes folded
into the largest still-unsharded divisible dim.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from ..models.config import ModelConfig, ShapeConfig
from ..sharding import auto_axes, logical_to_spec

Params = Any


def rules_for(cfg: ModelConfig, shape: ShapeConfig, mesh) -> Dict[str, Any]:
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_n = axes.get("model", 1)
    dp_axes = tuple(a for a in ("pod", "data") if a in axes)
    dp_n = int(np.prod([axes[a] for a in dp_axes])) if dp_axes else 1

    div = lambda n: bool(n) and n % model_n == 0

    batch_rule = dp_axes if shape.global_batch % dp_n == 0 else None
    kv_heads_sharded = div(cfg.num_kv_heads)

    r: Dict[str, Any] = {
        "batch": batch_rule,
        "layer": None,
        "embed": None,
        "head_dim": None,
        "seq": None,
        "heads": "model" if div(cfg.num_heads) else None,
        # heads can't carry TP (28 ∤ 16, 8 < 16): shard attention q-rows
        "attn_q": None if div(cfg.num_heads) else "model",
        "kv_heads": "model" if kv_heads_sharded else None,
        # decode KV cache: shard seq when heads can't carry the model axis
        "kv_seq": None if kv_heads_sharded else "model",
        "mlp": "model" if div(cfg.d_ff) or div(cfg.moe_d_ff) else None,
        "vocab": "model" if div(cfg.vocab_size) else None,
        "vocab_embed": None if div(cfg.vocab_size) else "model",
        "expert": "model" if div(cfg.num_experts) else None,
        "ssm_inner": "model" if div(cfg.d_inner) else None,
        "ssm_heads": "model" if div(cfg.ssm_heads) else None,
        "ssm_state": "model" if div(cfg.ssm_state) else None,
    }
    return r


def spec_tree(axes_tree, rules):
    """Logical-axes tree → PartitionSpec tree."""
    import jax

    from ..models.transformer import is_axes_leaf

    return jax.tree.map(lambda a: logical_to_spec(a or (), rules),
                        axes_tree, is_leaf=is_axes_leaf)


def sharding_tree(axes_tree, rules, mesh):
    import jax
    from jax.sharding import NamedSharding

    from ..models.transformer import is_axes_leaf

    mesh = auto_axes(mesh)
    return jax.tree.map(
        lambda a: NamedSharding(mesh, logical_to_spec(a or (), rules)),
        axes_tree, is_leaf=is_axes_leaf)


def zero_sharding_tree(param_shapes, axes_tree, rules, mesh):
    """ZeRO-1 shardings for optimizer moments: param spec + DP axes folded
    into the largest unsharded divisible dim."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..models.transformer import is_axes_leaf

    mesh = auto_axes(mesh)
    mesh_axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh_axes)
    dp_n = int(np.prod([mesh_axes[a] for a in dp_axes])) if dp_axes else 1

    def one(shape_struct, la):
        base = logical_to_spec(la or (), rules)
        spec = list(base) + [None] * (len(shape_struct.shape) - len(base))
        if dp_axes:
            # largest unsharded dim divisible by the full DP product
            cands = [(d, s) for d, s in enumerate(shape_struct.shape)
                     if spec[d] is None and s % dp_n == 0 and s > 0]
            if cands:
                d = max(cands, key=lambda t: t[1])[0]
                spec[d] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
        return NamedSharding(mesh, P(*spec))

    return _map2(one, param_shapes, axes_tree)


def _map2(fn, shapes_tree, axes_tree):
    """tree.map over (shapes, axes) where axes leaves are tuples."""
    import jax

    from ..models.transformer import is_axes_leaf

    # map over the axes tree (its leaves mark the structure) pairing with
    # the shapes tree
    flat_axes, treedef = jax.tree.flatten(axes_tree, is_leaf=is_axes_leaf)
    flat_shapes = treedef.flatten_up_to(shapes_tree)
    return jax.tree.unflatten(
        treedef, [fn(s, a) for s, a in zip(flat_shapes, flat_axes)])


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStructs + shardings) per (arch × shape)
# ---------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, shape: ShapeConfig, rules, mesh):
    """Returns (batch_structs, batch_shardings) for the mode's step inputs.

    train/prefill: {"tokens","labels"[,"positions"][,"frames"]}
    decode: handled by decode_input_specs (needs the cache tree).
    """
    import jax
    from jax.sharding import NamedSharding

    mesh = auto_axes(mesh)
    B, S = shape.global_batch, shape.seq_len
    i32 = jax.numpy.int32
    structs: Dict[str, Any] = {
        "tokens": jax.ShapeDtypeStruct((B, S), i32),
    }
    ax: Dict[str, Any] = {"tokens": ("batch", "seq")}
    if shape.mode == "train":
        structs["labels"] = jax.ShapeDtypeStruct((B, S), i32)
        ax["labels"] = ("batch", "seq")
    if cfg.rope_kind == "mrope":
        structs["positions"] = jax.ShapeDtypeStruct((3, B, S), i32)
        ax["positions"] = (None, "batch", "seq")
    if cfg.encoder_layers:
        structs["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_seq, cfg.d_model), jax.numpy.bfloat16)
        ax["frames"] = ("batch", None, "embed")
    shardings = {
        k: NamedSharding(mesh, logical_to_spec(a, rules))
        for k, a in ax.items()
    }
    return structs, shardings
