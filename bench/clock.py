"""Compile accounting from ``jax.monitoring`` events, and the pauses of
Python's garbage collector.

Copied from the compile clock of ``chip_smoke.py`` and extended: besides
the seconds XLA spent compiling and the persistent-cache hits, it counts
every program JAX lowered (each new jitted shape or eager op, whether it
then compiles or is read back from the cache). A lowering inside the
measured window means a shape set-up did not warm.
"""

from __future__ import annotations

import gc
import threading
import time

_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.lowerings = 0
        self.hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        with self._lock:
            if event == _COMPILE:
                self.compile_s += secs
                self.compiles += 1
            elif event == _LOWER:
                self.lowerings += 1

    def _event(self, event, **_):
        if event == _HIT:
            with self._lock:
                self.hits += 1

    def mark(self) -> dict:
        with self._lock:
            return {"compile_s": self.compile_s, "compiles": self.compiles,
                    "lowerings": self.lowerings, "cache_hits": self.hits}

    def since(self, mark: dict) -> dict:
        now = self.mark()
        return {k: now[k] - mark[k] for k in now}


class GcClock:
    """Python's garbage collections between ``start`` and ``stop``: how
    many, their seconds in all, and the longest with its generation. A
    collection stops every Python thread, the client's and the server's
    alike, so a long one reads as a host stall in the window."""

    def __init__(self):
        self.count = 0
        self.total_s = self.longest_s = 0.0
        self.longest_gen = -1
        self._t = 0.0

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
            return
        d = time.perf_counter() - self._t
        self.count += 1
        self.total_s += d
        if d > self.longest_s:
            self.longest_s, self.longest_gen = d, info["generation"]

    def start(self) -> None:
        gc.callbacks.append(self._cb)

    def stop(self) -> None:
        gc.callbacks.remove(self._cb)

    def line(self) -> str:
        return (f"gc in window: {self.count} collections, "
                f"{1e3 * self.total_s:.3f} ms in all, longest "
                f"{1e3 * self.longest_s:.3f} ms (generation "
                f"{self.longest_gen})")
