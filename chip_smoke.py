"""Chip smoke: serve yi-9b at published widths on one TPU through RPCool.

One process drives the serving path a user calls: a ``ServeEngine`` with
the compiled Pallas paged-attention kernel (``backend="tpu"``), clients
calling ``decode.generate_stream`` through ``router.stub(...,
DecodeService)``. The model is the ``serve_stage()`` preset of
``repro.configs.yi_9b``: one chip's stage of a two-stage pipeline, 24 of
48 layers at full width, with random weights drawn from ``--seed``.

Phases, each with its compile seconds:

  init        parameters drawn on the device (one jitted init)
  solo        every stream alone, one after another (the reference)
  concurrent  all streams at once, drained round-robin as the serve
              benchmark does, batched by the engine's StreamScheduler
  check       one decode step on the same inputs through the compiled
              kernel and through the jnp reference

The script fails (non-zero exit) if JAX finds no TPU, or if any check
fails: a sandbox violation (``oob_events``), a concurrent stream that
differs from its solo run, a lost token, a non-finite logit, a
kernel-vs-reference logit gap above ``LOGIT_RTOL`` of the largest
reference logit, or a decode step without a ``tpu_custom_call``. Its last
line on success is ``{"ok": true, "device": {...}}``.

    python chip_smoke.py               # on a TPU host
    python chip_smoke.py --rehearse    # tiny CPU rehearsal: smoke config,
                                       # interpret-mode kernels

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` where set, else
``<repo>/.jax_cache``; a second run reads it back.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# bf16 carries 8 bits of mantissa (ulp 2^-8 ≈ 0.4%). The kernel and the
# reference both compute attention in f32 from the same bf16 pool and
# round their output to bf16, so they part by a few ulps per layer; 24
# layers may compound that. 5% of the largest reference logit (≈ 13
# ulps) holds that and still fails a wrong page, mask or softmax, which
# move logits by their own scale.
LOGIT_RTOL = 0.05


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="draws the weights and the prompts")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the smoke config with "
                         "interpret-mode kernels; never a fallback")
    return ap.parse_args(argv)


class _CompileClock:
    """Sums JAX's backend-compile time (persistent-cache reads included)
    and counts persistent-cache hits, read per phase."""

    def __init__(self):
        import jax

        self.secs = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return self.secs, self.hits


def _setup(args):
    """(cfg, pool config, streams, backend, prompt lengths, max_new);
    the engine batches as many streams as the smoke runs."""
    if args.rehearse:
        from repro.configs import get_smoke_config
        from repro.serving import PoolConfig

        cfg = get_smoke_config("yi_9b")
        pool = PoolConfig(num_pages=64, page_tokens=8, max_pages_per_seq=8)
        return cfg, pool, 3, "interpret", (13, 20), 4
    from repro.configs.yi_9b import serve_stage

    cfg, pool, max_active = serve_stage()
    # a few hundred tokens, off the 16-token page grid; three lengths
    # keep prefill to three compiles
    return cfg, pool, max_active, "tpu", (200, 256, 301), 32


def run_smoke(args, out=print):
    """Drive the serving path once; return ``(checks, device)``.

    ``checks`` maps a check name to ``(passed, detail)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.serve import _run_concurrent, _run_sequential
    from repro.models import build_model
    from repro.serving import ServeEngine
    from repro.serving.paged_model import paged_decode_step

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    out(f"device: {device['kind']} ({device['platform']}) "
        f"x{device['count']}")
    clock = _CompileClock()
    cfg, pool_cfg, n, backend, lens, max_new = _setup(args)
    out(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} head_dim={cfg.head_dim} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"params={cfg.param_count()} ({2 * cfg.param_count() / 1e9:.2f} GB "
        f"bf16)")
    out(f"pool: {pool_cfg.num_pages} pages x {pool_cfg.page_tokens} tokens, "
        f"max {pool_cfg.max_pages_per_seq} pages/seq; backend={backend}; "
        f"{n} streams x {max_new} new tokens")

    phases = {}

    def phase(name, t0, c0):
        secs, hits = clock.mark()
        phases[name] = (time.perf_counter() - t0, secs - c0[0],
                        hits - c0[1])
        wall, comp, hit = phases[name]
        out(f"phase {name}: wall {wall:.2f}s compile {comp:.2f}s "
            f"cache_hits {hit}")

    t0, c0 = time.perf_counter(), clock.mark()
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    phase("init", t0, c0)

    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(lens[i % len(lens)]))
               .tolist() for i in range(n)]
    checks = {}
    eng = ServeEngine(cfg, params, pool_cfg, max_active=n,
                      backend=backend, serve_threaded=True)
    try:
        t0, c0 = time.perf_counter(), clock.mark()
        solo, _ = _run_sequential(eng, prompts, max_new)
        phase("solo", t0, c0)

        t0, c0 = time.perf_counter(), clock.mark()
        conc, conc_s = _run_concurrent(eng, prompts, max_new)
        phase("concurrent", t0, c0)
        produced = sum(len(o) for o in conc)
        out(f"tokens produced: {produced} concurrent in {conc_s:.2f}s "
            f"(smoke figure, not a metric: {produced / conc_s:.1f} tok/s), "
            f"{sum(len(o) for o in solo)} solo; "
            f"peak batch {eng.peak_stream_batch}")
        checks["no_lost_tokens"] = (
            all(len(o) == max_new for o in conc + solo),
            f"{produced}/{n * max_new} concurrent")
        same = sum(c == s for c, s in zip(conc, solo))
        checks["streams_match_solo"] = (
            same == n, f"{same}/{n} concurrent streams equal their solo run")

        # one decode step, same inputs, compiled kernel vs jnp reference
        t0, c0 = time.perf_counter(), clock.mark()
        slots = [eng.scheduler.admit(p, max_new) for p in prompts]
        try:
            batch = eng.scheduler.batch_inputs(slots)
            lowered = paged_decode_step.lower(cfg, params, *batch,
                                              backend=backend)
            compiled = lowered.compile()
            # keep logits and oob only: each step returns fresh pools,
            # and two spare pools do not fit beside the live one
            res = compiled(params, *batch)
            logits_k, oob_k = np.asarray(res[0][:n]), int(jnp.sum(res[3][:n]))
            del res
            res = paged_decode_step(cfg, params, *batch, backend="ref")
            logits_r, oob_r = np.asarray(res[0][:n]), int(jnp.sum(res[3][:n]))
            del res
            oob_step = oob_k + oob_r
        finally:
            for s in slots:
                eng.scheduler.release(s)
        phase("check", t0, c0)
        finite = bool(np.isfinite(logits_k).all()
                      and np.isfinite(logits_r).all())
        scale = float(np.abs(logits_r).max())
        diff = float(np.abs(logits_k - logits_r).max())
        agree = int((logits_k.argmax(-1) == logits_r.argmax(-1)).sum())
        checks["logits_finite"] = (finite, f"{logits_k.shape} logits")
        checks["kernel_vs_ref_logits"] = (
            finite and diff <= LOGIT_RTOL * scale,
            f"max |diff| {diff:.6g} <= {LOGIT_RTOL} x max |ref| {scale:.6g}"
            f" = {LOGIT_RTOL * scale:.6g}; argmax agrees on {agree}/{n}")
        oob = eng.oob_events + oob_step
        checks["oob_events_zero"] = (
            oob == 0, f"oob_events {eng.oob_events} in serving, "
                      f"{oob_step} in the check step")
        if backend == "tpu":
            has_kernel = "tpu_custom_call" in compiled.as_text()
            checks["tpu_custom_call_in_step"] = (
                has_kernel, "compiled paged_decode_step holds the Pallas "
                            "kernel" if has_kernel else "no Pallas kernel")
    finally:
        eng.shutdown()

    stats = dev.memory_stats() or {}
    out("peak_bytes_in_use: "
        f"{stats.get('peak_bytes_in_use', 'not reported')} of bytes_limit "
        f"{stats.get('bytes_limit', 'not reported')}")
    out("compile seconds: " + ", ".join(
        f"{k} {v[1]:.2f}" for k, v in phases.items())
        + f"; total {sum(v[1] for v in phases.values()):.2f}")
    return checks, device


def main(argv=None) -> int:
    args = _parse(argv)
    # libtpu writes its logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(HERE, "src"))
    sys.path.insert(0, HERE)
    try:
        import repro.serving  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax

    platform = jax.devices()[0].platform
    if not args.rehearse and platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {platform!r}); "
              "--rehearse runs the CPU rehearsal", file=sys.stderr)
        return 2
    if not args.rehearse:
        from repro.launch.serve import enable_compile_cache

        print(f"compile cache: {enable_compile_cache()}")
    checks, device = run_smoke(args)
    for name, (ok, detail) in checks.items():
        print(f"check {name}: {'PASS' if ok else 'FAIL'} ({detail})")
    ok = all(passed for passed, _ in checks.values())
    result = {"ok": ok, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
