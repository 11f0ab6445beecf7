import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first: jax locks the device count at first
initialization, and the production meshes need 512 placeholder devices.

Per cell this driver:
  1. builds the model + sharding rules (launch.sharding.rules_for),
  2. jits the mode's step (train_step / prefill / decode_step) with full
     in/out NamedShardings,
  3. ``.lower(**ShapeDtypeStructs)`` — no allocation — and ``.compile()``,
  4. prints ``compiled.memory_analysis()`` (fits?) and
     ``compiled.cost_analysis()`` (FLOPs/bytes for §Roofline),
  5. parses the post-SPMD HLO for collective-operand bytes,
  6. writes artifacts/dryrun/<mesh>/<arch>__<shape>.json.

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all [--mesh both] [--jobs N]
"""

import argparse
import json
import re
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, Optional

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")

# long_500k only runs for sub-quadratic archs (DESIGN.md §Arch-applicability)
SKIPS = {
    ("qwen2-vl-7b", "long_500k"): "full attention — quadratic at 512k",
    ("yi-9b", "long_500k"): "full attention — quadratic at 512k",
    ("yi-6b", "long_500k"): "full attention — quadratic at 512k",
    ("olmo-1b", "long_500k"): "full attention — quadratic at 512k",
    ("qwen3-moe-30b-a3b", "long_500k"): "full attention — quadratic at 512k",
    ("granite-moe-1b-a400m", "long_500k"): "full attention — quadratic at 512k",
    ("whisper-base", "long_500k"): "full attention (448-pos decoder in reality)",
}

ARCHES = [
    "mamba2-1.3b", "qwen2-vl-7b", "gemma3-12b", "yi-9b", "yi-6b",
    "olmo-1b", "qwen3-moe-30b-a3b", "granite-moe-1b-a400m",
    "whisper-base", "jamba-v0.1-52b",
]
SHAPE_NAMES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]

_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"%?([\w.\-]+) = \(?([a-z0-9_]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def _nbytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes of every collective in post-SPMD HLO."""
    sizes: Dict[str, int] = {}
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "count": 0}
    for line in hlo_text.splitlines():
        m = _SHAPE_RE.search(line)
        if m:
            sizes[m.group(1)] = _nbytes(m.group(2), m.group(3))
        c = _COLL_RE.search(line)
        if c and "-done" not in line:
            kind = c.group(1)
            # operand list inside the call parens
            args = line.split(c.group(0), 1)[1]
            ops = re.findall(r"%?([\w.\-]+)", args.split(")")[0])
            b = sum(sizes.get(o, 0) for o in ops)
            if b == 0:
                # fall back to the result size on this line
                if m:
                    b = sizes.get(m.group(1), 0)
            out[kind] += b
            out["count"] += 1
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             extra: Optional[Dict[str, Any]] = None,
             costing: bool = False,
             rules_override: Optional[Dict[str, Any]] = None,
             variant: str = ""
             ) -> Dict[str, Any]:
    """variant: comma-joined hillclimb levers —
      train : remat_dots | accum8
      decode: uniform_pos | kv8
    """

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import build_model
    from repro.models.config import SHAPES
    from repro.sharding import use_rules
    from repro.launch.mesh import make_production_mesh
    from repro.launch.sharding import input_specs, rules_for, \
        sharding_tree, zero_sharding_tree
    from repro.models.transformer import stack_cache_axes
    from repro.training import AdamWConfig, init_opt_state, make_train_step
    from jax.sharding import NamedSharding, PartitionSpec as P

    import dataclasses

    from repro.costing import costing_mode

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    rules = rules_for(cfg, shape, mesh)
    if rules_override:
        rules.update(rules_override)

    if costing:
        # Extrapolation costing: cost_analysis counts while-loop bodies
        # once, so exact totals come from two SMALL unrolled compiles —
        # blocks are identical, so cost(nb) is affine in nb:
        #   total = c1 + (nb − 1) · (c2 − c1)        [+ encoder term]
        return _cost_by_extrapolation(
            arch, shape_name, mesh_kind, cfg, shape, mesh, rules, extra,
            variant)

    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mode": shape.mode, "costing": costing, "variant": variant,
        "devices": int(mesh.devices.size),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "rules": {k: (list(v) if isinstance(v, tuple) else v)
                  for k, v in rules.items()},
    }
    if extra:
        rec.update(extra)
    rec.update(_compile_metrics(cfg, shape, mesh, rules, variant,
                                verbose=True))
    return rec


def _compile_metrics(cfg, shape, mesh, rules, variant: str = "",
                     verbose: bool = False) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import build_model
    from repro.sharding import use_rules
    from repro.launch.sharding import (
        input_specs,
        sharding_tree,
        zero_sharding_tree,
    )
    from repro.models.transformer import stack_cache_axes
    from repro.training import AdamWConfig, init_opt_state, make_train_step

    model = build_model(cfg)
    rec: Dict[str, Any] = {}
    v = set(variant.split(",")) if variant else set()

    t0 = time.time()
    params_shapes = model.param_shapes()
    axes = model.axes()
    p_shard = sharding_tree(axes, rules, mesh)

    repl = NamedSharding(mesh, P())
    B, S = shape.global_batch, shape.seq_len

    with use_rules(rules, mesh):
        if shape.mode == "train":
            opt_shapes = jax.eval_shape(init_opt_state, params_shapes)
            opt_shard = {
                "m": zero_sharding_tree(params_shapes, axes, rules, mesh),
                "v": zero_sharding_tree(params_shapes, axes, rules, mesh),
                "step": repl,
            }
            batch_structs, batch_shard = input_specs(cfg, shape, rules, mesh)
            accum = 32 if "accum32" in v else (8 if "accum8" in v else 1)
            step = make_train_step(
                model, AdamWConfig(),
                remat="dots" if "remat_dots" in v else True,
                grad_accum=accum)
            jitted = jax.jit(
                step,
                in_shardings=(p_shard, opt_shard, batch_shard),
                out_shardings=(p_shard, opt_shard, None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_shapes, opt_shapes, batch_structs)
        elif shape.mode == "prefill":
            batch_structs, batch_shard = input_specs(cfg, shape, rules, mesh)

            def prefill_fn(params, batch):
                return model.prefill(params, batch, cache_len=S)

            jitted = jax.jit(prefill_fn,
                             in_shardings=(p_shard, batch_shard))
            lowered = jitted.lower(params_shapes, batch_structs)
        else:  # decode
            kv_dtype = jnp.int8 if "kv8" in v else jnp.bfloat16
            cache_shapes = jax.eval_shape(
                lambda: model.empty_cache(B, S, kv_dtype=kv_dtype))
            c_axes = stack_cache_axes(cfg)
            c_shard = sharding_tree(c_axes, rules, mesh)
            from repro.sharding import logical_to_spec

            tok_shard = NamedSharding(
                mesh, logical_to_spec(("batch",), rules))
            tok = jax.ShapeDtypeStruct((B,), jnp.int32)
            if "uniform_pos" in v:
                pos = jax.ShapeDtypeStruct((), jnp.int32)
                pos_shard = repl
            else:
                pos = jax.ShapeDtypeStruct((B,), jnp.int32)
                pos_shard = tok_shard
            jitted = jax.jit(
                model.decode_step,
                in_shardings=(p_shard, tok_shard, pos_shard, c_shard),
                out_shardings=(None, c_shard),
                donate_argnums=(3,),
            )
            lowered = jitted.lower(params_shapes, tok, pos, cache_shapes)

        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    mem = compiled.memory_analysis()
    if verbose:
        print(mem)
    if mem is not None:
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            val = getattr(mem, f, None)
            if val is not None:
                rec[f] = int(val)

    cost = compiled.cost_analysis()
    if verbose:
        print({k: val for k, val in (cost or {}).items()
               if k in ("flops", "bytes accessed")})
    if cost:
        rec["flops"] = float(cost.get("flops", 0.0))
        rec["bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
        rec["transcendentals"] = float(cost.get("transcendentals", 0.0))

    hlo = compiled.as_text()
    rec["collectives"] = collective_bytes(hlo)
    rec["hlo_lines"] = hlo.count("\n")
    return rec


def _cost_by_extrapolation(arch, shape_name, mesh_kind, cfg, shape, mesh,
                           rules, extra, variant) -> Dict[str, Any]:
    """Exact totals from small unrolled compiles (blocks are identical):

        cost(nb, ne) = outside + nb·block + ne·enc_block

    Solved from compiles at (1,1) and (2,2) for enc-dec (the two unknown
    slopes scale together here since we extrapolate each count with its
    own delta), or (1,·),(2,·) otherwise.
    """
    import dataclasses

    from repro.costing import costing_mode

    pattern = len(cfg.block_pattern())
    nb = cfg.num_blocks
    ne = cfg.encoder_layers

    def reduced(k: int):
        kw = {"num_layers": k * pattern}
        if ne:
            kw["encoder_layers"] = k
        return dataclasses.replace(cfg, **kw)

    fields = ("flops", "bytes_accessed", "transcendentals")

    with costing_mode():
        c1 = _compile_metrics(reduced(1), shape, mesh, rules, variant)
        c2 = _compile_metrics(reduced(2), shape, mesh, rules, variant)

    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mode": shape.mode, "costing": True, "variant": variant,
        "cost_method": "extrapolated(nb=1,2 unrolled)",
        "devices": int(mesh.devices.size),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "lower_s": c1.get("lower_s", 0) + c2.get("lower_s", 0),
        "compile_s": c1.get("compile_s", 0) + c2.get("compile_s", 0),
    }
    if extra:
        rec.update(extra)
    for f in fields:
        a, b = c1.get(f, 0.0), c2.get(f, 0.0)
        rec[f] = a + (nb - 1) * (b - a)
    coll = {}
    for k in c1.get("collectives", {}):
        a = c1["collectives"].get(k, 0)
        b = c2["collectives"].get(k, 0)
        coll[k] = int(a + (nb - 1) * (b - a))
    rec["collectives"] = coll
    print({k: rec.get(k) for k in ("flops", "bytes_accessed")})
    print("collectives:", coll)
    return rec


def cell_path(arch, shape_name, mesh_kind, tag="") -> str:
    d = os.path.join(ARTIFACT_DIR, mesh_kind + (f"_{tag}" if tag else ""))
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{arch}__{shape_name}.json")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--tag", default="", help="artifact subdir suffix")
    ap.add_argument("--costing", action="store_true",
                    help="unrolled-scan costing pass (exact FLOPs/bytes)")
    ap.add_argument("--variant", default="",
                    help="hillclimb levers, comma-joined (see run_cell)")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    if not args.tag:
        parts = []
        if args.variant:
            parts.append(args.variant.replace(",", "+"))
        if args.costing:
            parts.append("cost")
        args.tag = "_".join(parts)

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    if not args.all:
        assert args.arch and args.shape
        if (args.arch, args.shape) in SKIPS:
            print(f"SKIP {args.arch} {args.shape}: "
                  f"{SKIPS[(args.arch, args.shape)]}")
            rec = {"arch": args.arch, "shape": args.shape,
                   "mesh": meshes[0], "skipped": True,
                   "reason": SKIPS[(args.arch, args.shape)]}
            with open(cell_path(args.arch, args.shape, meshes[0],
                                args.tag), "w") as f:
                json.dump(rec, f, indent=1)
            return 0
        for mesh_kind in meshes:
            try:
                rec = run_cell(args.arch, args.shape, mesh_kind,
                               costing=args.costing, variant=args.variant)
                status = "OK"
            except Exception as e:
                rec = {"arch": args.arch, "shape": args.shape,
                       "mesh": mesh_kind, "error": repr(e),
                       "trace": traceback.format_exc()}
                status = "FAIL"
            with open(cell_path(args.arch, args.shape, mesh_kind,
                                args.tag), "w") as f:
                json.dump(rec, f, indent=1)
            print(f"[{status}] {args.arch} {args.shape} {mesh_kind} "
                  f"lower={rec.get('lower_s')}s compile={rec.get('compile_s')}s "
                  f"flops={rec.get('flops', 0):.3g}")
            if status == "FAIL":
                print(rec["trace"])
                return 1
        return 0

    # orchestrator: one subprocess per cell (isolates device state, allows
    # parallelism across compiles)
    jobs = []
    for mesh_kind in meshes:
        for arch in ARCHES:
            for shape_name in SHAPE_NAMES:
                out = cell_path(arch, shape_name, mesh_kind, args.tag)
                if os.path.exists(out) and not args.force:
                    with open(out) as f:
                        old = json.load(f)
                    if "error" not in old:
                        continue
                jobs.append((arch, shape_name, mesh_kind))

    print(f"{len(jobs)} cells to run")
    running: list = []
    failed = []
    done = 0
    while jobs or running:
        while jobs and len(running) < args.jobs:
            arch, shape_name, mesh_kind = jobs.pop(0)
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name,
                   "--mesh", mesh_kind]
            if args.tag:
                cmd += ["--tag", args.tag]
            if args.costing:
                cmd += ["--costing"]
            # the cell compiles on 512 host placeholder devices: pin the
            # child to the CPU so it never reaches for an attached chip
            p = subprocess.Popen(cmd, stdout=subprocess.DEVNULL,
                                 stderr=subprocess.DEVNULL,
                                 env={**os.environ, "JAX_PLATFORMS": "cpu"})
            running.append((p, arch, shape_name, mesh_kind, time.time()))
        for item in list(running):
            p, arch, shape_name, mesh_kind, t0 = item
            if p.poll() is not None:
                running.remove(item)
                done += 1
                dt = time.time() - t0
                ok = p.returncode == 0
                if not ok:
                    failed.append((arch, shape_name, mesh_kind))
                print(f"[{done}] {'OK ' if ok else 'FAIL'} "
                      f"{arch} {shape_name} {mesh_kind} ({dt:.0f}s)",
                      flush=True)
        time.sleep(0.5)
    if failed:
        print("FAILED CELLS:", failed)
        return 1
    print("ALL CELLS PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
