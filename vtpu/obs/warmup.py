"""Where an engine's warm-up goes: seconds by kind, from JAX's own events.

``ServingEngine._warm_executables`` runs every program once before the loop
serves. ``WarmupClock.timing()`` listens to ``jax.monitoring`` on the
warming thread meanwhile and sums what JAX reports: tracing and lowering
(both happen again for every program even when the persistent compilation
cache then answers), the backend's compile call, and inside it the cache's
retrieval. What is left of the wall time is the warm runs themselves and
host work. ``stats()["warmup_s"]`` is the snapshot, frozen when warm-up ends:
what an operator looks at when a pod is slow to turn ready.
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax

_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_lower",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace_lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


class WarmupClock:
    """Seconds of one thread's JAX work by kind, while ``timing()`` is open."""

    def __init__(self):
        self._s = {"trace_lower": 0.0, "backend": 0.0, "cache_load": 0.0}
        self._open = {kind: [] for kind in self._s}  # (start, seconds)
        self._programs = 0
        self._wall_s = 0.0
        self._thread = None

    def _note(self, event: str, duration: float, **_kw) -> None:
        kind = _KINDS.get(event)
        if kind is None or threading.get_ident() != self._thread:
            return
        # An event arrives when its span ends, an inner one (a jitted
        # helper traced inside a step) before the one around it: take back
        # what the new span holds, so nothing counts twice.
        start = time.perf_counter() - duration
        spans = self._open[kind]
        while spans and spans[-1][0] >= start:
            self._s[kind] -= spans.pop()[1]
        spans.append((start, duration))
        self._s[kind] += duration
        if kind == "backend":
            self._programs += 1

    @contextlib.contextmanager
    def timing(self):
        self._thread = threading.get_ident()
        jax.monitoring.register_event_duration_secs_listener(self._note)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self._wall_s += time.perf_counter() - t0
            jax.monitoring.unregister_event_duration_listener(self._note)

    def snapshot(self) -> dict:
        """{total, trace_lower, compile, cache_load, run, programs}: the
        backend's call holds the cache's retrieval, so ``compile`` is what
        is left of it (a real compile, or the key's hashing on a hit), and
        ``run`` what is left of the wall time."""
        s = self._s
        parts = {
            "trace_lower": s["trace_lower"],
            "compile": max(s["backend"] - s["cache_load"], 0.0),
            "cache_load": s["cache_load"],
        }
        parts["run"] = max(self._wall_s - sum(parts.values()), 0.0)
        out = {"total": self._wall_s, **parts}
        return {**{k: round(v, 4) for k, v in out.items()},
                "programs": self._programs}
