"""Logical-axis sharding rules (MaxText-style).

Model code annotates activations/params with *logical* axis names; the
launcher installs a rule set mapping logical names → mesh axes. With no
rules installed (CPU unit tests) every annotation is the identity, so the
same model code runs everywhere.

Baseline rules (see launch/sharding.py for the per-shape variants):

  batch    → ("pod", "data")   DP hierarchically across pods then ICI
  seq      → None              (SP variant maps it to "model" between blocks)
  embed    → None              residual stream replicated across model axis
  heads    → "model"           Megatron TP for attention
  kv_heads → "model"           (capped by kv head count — rule may be None)
  mlp      → "model"           Megatron TP for FFN
  vocab    → "model"           vocab-sharded embedding/logits
  expert   → "model"           EP: experts sharded over the model axis
  kv_seq   → context-parallel KV for long_500k decode
  layer    → None              stacked-block leading axis, never sharded
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

_state = threading.local()

Rule = Union[None, str, Tuple[str, ...]]


def current_rules() -> Optional[Dict[str, Rule]]:
    return getattr(_state, "rules", None)


def current_mesh():
    return getattr(_state, "mesh", None)


def auto_axes(mesh):
    """``mesh`` with every axis ``Auto``.

    ``jax.make_mesh`` makes ``Explicit`` axes, under which every op must
    be told its output sharding. The model code here annotates logical
    axes and leaves the rest to the compiler's sharding propagation,
    which needs ``Auto`` axes; every mesh the rules are applied on passes
    through here."""
    from jax.sharding import AxisType, Mesh

    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def shard_map(f, *, mesh, **kw):
    """``jax.shard_map`` over ``mesh`` with ``Auto`` axes, so it takes
    arrays that are not yet placed on the mesh."""
    import jax

    return jax.shard_map(f, mesh=auto_axes(mesh), **kw)


@contextlib.contextmanager
def use_rules(rules: Dict[str, Rule], mesh=None):
    old_r = getattr(_state, "rules", None)
    old_m = getattr(_state, "mesh", None)
    _state.rules = rules
    _state.mesh = None if mesh is None else auto_axes(mesh)
    try:
        yield
    finally:
        _state.rules = old_r
        _state.mesh = old_m


def logical_to_spec(axes: Sequence[Optional[str]],
                    rules: Optional[Dict[str, Rule]] = None):
    """Map a tuple of logical axis names to a jax PartitionSpec."""
    from jax.sharding import PartitionSpec as P

    rules = rules if rules is not None else current_rules()
    if rules is None:
        return P()
    out = []
    used = set()
    for name in axes:
        r = rules.get(name) if name is not None else None
        # an axis may appear at most once in a spec; drop duplicates
        if r is None:
            out.append(None)
            continue
        rt = (r,) if isinstance(r, str) else tuple(r)
        rt = tuple(a for a in rt if a not in used)
        used.update(rt)
        if not rt:
            out.append(None)
        elif len(rt) == 1:
            out.append(rt[0])
        else:
            out.append(rt)
    return P(*out)


def shard(x, axes: Sequence[Optional[str]]):
    """Annotate an intermediate with logical axes (no-op without rules).

    Divisibility-safe: a dim that does not divide its mapped mesh extent
    (e.g. a size-1 decode query dim under an ``attn_q``→model rule) is
    silently left unsharded instead of failing the lowering.
    """
    rules = current_rules()
    if rules is None:
        return x
    import jax
    from jax.sharding import PartitionSpec as P

    spec = logical_to_spec(axes, rules)
    mesh = current_mesh()
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        safe = []
        for d, entry in enumerate(spec):
            if entry is None or d >= x.ndim:
                safe.append(None)
                continue
            names = (entry,) if isinstance(entry, str) else tuple(entry)
            total = 1
            for nm in names:
                total *= sizes.get(nm, 1)
            safe.append(entry if total and x.shape[d] % total == 0 else None)
        spec = P(*safe)
    return jax.lax.with_sharding_constraint(x, _named(spec))


def _named(spec):
    from jax.sharding import NamedSharding

    mesh = current_mesh()
    if mesh is None:
        import jax

        mesh = getattr(jax.sharding, "get_abstract_mesh", lambda: None)()
        if mesh is None:
            return spec
    return NamedSharding(mesh, spec)
