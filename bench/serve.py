"""The system under test, built and warmed: a ``ServeEngine`` on the
bench's weights, with the client stubs the window drives.

Warm-up runs every shape the cell's traffic can produce, so nothing
compiles in the window:

* prefill and the pool write of each (prompt length, page count) pair
  of the mix's support (``prefill_kv`` jits on the prompt's shape and
  ``write_prefill`` runs eagerly on the page count);
* the decode step, and the eager slicing of its outputs, at every live
  batch size from 1 to ``max_active``;
* one whole stream through the RPC stub path.
"""

from __future__ import annotations

import os
import sys
import time
from typing import Dict, List, Tuple

from . import model, spec, traffic

CLIENT_PID0 = 40


def compile_cache() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` where set, else the fixed
    ``<checkout>/.jax_cache``; every program is cached, however quick."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        spec.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def start(chips: int, rehearse: bool):
    """(device, compile cache, error): the program importable, the chip
    checked, the compile cache placed. ``error`` is set when JAX finds
    no TPU, or fewer chips than the cell asks for (never in the CPU
    rehearsal, which caches nothing)."""
    if str(spec.ROOT / "src") not in sys.path:
        sys.path.insert(0, str(spec.ROOT / "src"))
    import jax

    devs = jax.devices()
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": min(chips, len(devs))}
    if rehearse:
        return device, "off (rehearsal)", None
    if d.platform != "tpu":
        return device, None, f"JAX finds no TPU (platform {d.platform!r})"
    if len(devs) < chips:
        return device, None, \
            f"the cell needs {chips} chips, JAX finds {len(devs)}"
    return device, compile_cache(), None


def make_stub(eng, pid: int):
    """A client stub that polls its chunk chain with 200 µs naps, as the
    repository's serve benchmark does (copied, so a change there cannot
    move this harness)."""
    from repro.core.channel import BusyWaitPolicy
    from repro.serving.engine import DecodeService

    stub = eng.router.stub(eng.endpoint_name, DecodeService, pid=pid,
                           pod="pod0")
    stub.connection.wait_policy = BusyWaitPolicy(fixed_sleep_us=200.0)
    return stub


def page_pairs(m: Dict, pairs: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """(prompt length, page count) pairs, one length pair for each."""
    T = m["page_tokens"]
    seen, out = set(), []
    for p, o in pairs:
        key = (p, -(-(p + o) // T))
        if key not in seen:
            seen.add(key)
            out.append((p, o))
    return out


def warm(eng, m: Dict, pairs: List[Tuple[int, int]], stub) -> int:
    """Run every shape the window can meet; returns decode steps run."""
    sch = eng.scheduler
    V = m["vocab_size"]
    prompt = lambda n: [1 + (i * 7919) % (V - 1) for i in range(n)]
    for p, o in page_pairs(m, pairs):
        sch.release(sch.admit(prompt(p), o))
    # decode at every live batch size: slot i joins before step i
    p0 = min(p for p, _ in pairs)
    o_max = max(o for _, o in pairs)
    if o_max <= m["max_active"]:
        raise ValueError("warm-up needs an output length above max_active")
    slots = []
    steps = 0
    try:
        for _ in range(m["max_active"]):
            slots.append(sch.admit(prompt(p0), o_max))
            with eng._decode_lock:
                sch.step_batch()
            steps += 1
    finally:
        for s in slots:
            sch.release(s)
    # and one stream through the RPC path, drained to its end
    p, o = min(pairs)
    list(stub.generate_stream.stream(prompt(p), o, timeout=120.0,
                                     window=o + 4))
    return steps


def mix_pairs(m: Dict, mix: Dict) -> List[Tuple[int, int]]:
    return traffic.pairs(mix, m["prompt_divisor"], m["output_divisor"])


def prepare(m: Dict, mix: Dict, seed: int, backend: str):
    """(engine, stubs, seconds by phase): built on the seed's weights and
    warmed for every shape of the mix."""
    import jax

    from repro.serving import ServeEngine

    fam = model.family(m)
    t = [time.perf_counter()]
    params = fam.make(fam.Dims.of(m), seed)
    jax.block_until_ready(params)
    eng = ServeEngine(fam.program_config(m), params, model.pool_config(m),
                      max_active=m["max_active"], backend=backend,
                      serve_threaded=True)
    t.append(time.perf_counter())
    stubs = [make_stub(eng, CLIENT_PID0 + i)
             for i in range(2 * m["max_active"] + 2)]
    t.append(time.perf_counter())
    warm(eng, m, mix_pairs(m, mix), stubs[0])
    t.append(time.perf_counter())
    names = ("weights and engine", "stubs", "warm-up")
    return eng, stubs, {n: b - a for n, a, b in zip(names, t, t[1:])}


def release(eng, loop=None) -> None:
    """Stop the engine and drop the device arrays it holds, so that the
    reference that follows finds the chip's memory free."""
    import gc

    eng.shutdown()
    eng.pool.k = eng.pool.v = eng.params = None
    if loop is not None:
        loop.free, loop.open = [], {}
    gc.collect()
