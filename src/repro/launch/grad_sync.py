import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

"""Cross-pod gradient sync lowering: dense vs int8+EF compressed.

Hillclimb #2 artifact generator (EXPERIMENTS.md §Perf): on the multipod
mesh, the data-parallel gradient reduction crosses DCN once per step.
This driver lowers three variants of the pod-axis sync for an arch's
full gradient tree and reports HLO collective bytes:

  dense_f32   psum of fp32 grads        (naive)
  dense_bf16  psum of bf16-cast grads   (standard)
  int8_ef     compressed_psum           (ours: 1 B/elem wire + EF state)

Usage: python -m repro.launch.grad_sync --arch jamba-v0.1-52b
"""

import argparse
import json


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.sharding import shard_map
    from repro.configs import get_config
    from repro.launch.dryrun import collective_bytes
    from repro.launch.mesh import make_production_mesh
    from repro.launch.sharding import rules_for, spec_tree
    from repro.models import build_model
    from repro.models.config import SHAPES
    from repro.training.grad_comp import compressed_psum

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="jamba-v0.1-52b")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=True)
    rules = rules_for(cfg, SHAPES["train_4k"], mesh)
    axes = model.axes()
    specs = spec_tree(axes, rules)  # grads sharded like params (model axis)
    grad_shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
        model.param_shapes())

    def padd(spec):
        # pod-axis shard_map spec: grads replicated over pod (per-pod copy)
        return P(*spec)

    in_specs = jax.tree.map(padd, specs,
                            is_leaf=lambda x: isinstance(x, P))

    def lower(fn):
        sm = shard_map(
            fn, mesh=mesh, in_specs=(in_specs,), out_specs=in_specs,
            check_vma=False)
        return jax.jit(sm).lower(grad_shapes).compile()

    results = {}

    def dense_f32(g):
        return jax.tree.map(
            lambda x: jax.lax.psum(x, "pod") / 2.0, g)

    def dense_bf16(g):
        return jax.tree.map(
            lambda x: jax.lax.psum(x.astype(jnp.bfloat16), "pod")
            .astype(jnp.float32) / 2.0, g)

    def int8_ef(g):
        e = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), g)
        ghat, _ = compressed_psum(g, e, "pod", n_shards=2)
        return ghat

    for name, fn in (("dense_f32", dense_f32), ("dense_bf16", dense_bf16),
                     ("int8_ef", int8_ef)):
        compiled = lower(fn)
        cb = collective_bytes(compiled.as_text())
        results[name] = cb
        print(f"{name}: all-reduce bytes/device = "
              f"{cb['all-reduce']/1e9:.3f} GB  (ops={cb['count']})")

    if args.out:
        with open(args.out, "w") as f:
            json.dump({"arch": args.arch, "results": results}, f, indent=1)


if __name__ == "__main__":
    main()
