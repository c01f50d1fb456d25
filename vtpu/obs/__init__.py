"""Serving-engine observability: request-lifecycle tracing, tick-phase
profiling, and the unified ``vtpu_serving_*`` Prometheus exporter.

All host-side (nothing here ever touches the device — the overhead
contract tests/test_obs.py holds is that tracing adds no fetch and no
host sync; its cost in tokens/sec is not measured). What the DEVICE did
is named by ``jax.named_scope``s from one vocabulary, ``vtpu.ops.SCOPES``
(docs/design.md, observability: a new step or kernel takes a name from it
or adds one), and by the programs' names: ``jit_step`` decodes,
``jit_admit_step`` admits, ``jit_prefill_chunk_into_slot`` is a chunk.

- trace.py:    a lock-light bounded ring of structured lifecycle events
               (submit .. retire) stamped ``time.monotonic_ns`` off the
               tick hot path, with derived per-request spans, JSONL export
               and a Chrome ``trace_event`` dump that loads in Perfetto.
- fleettrace.py: the fleet half of the plane — stitched cross-engine
               request journeys (token-conservation contract), the fleet
               control-event ring, the DEAD-engine flight recorder, and
               the merged multi-pid Chrome dump.
- tickprof.py: per-tick decode-loop phase attribution (admission head,
               dispatch, fetch, deliver, swap drain, idle wait) into
               bounded histograms, opened by one context manager,
               ``TickProfiler.phase(name, ticks=1, **ids)``, that also
               holds a profiler span ``vtpu.tick.<phase>`` (ids: ``tick``
               and the caller's) on the profiler's own clock. The only
               record of the host's share of a tick: the EMA keys
               (``host_ms_per_tick``, ``host_ms_per_token``,
               ``admission_stall_ms``) are gone; ``host_ms_per_tick()``
               here reads the totals. Each histogram also counts the
               samples judged long and their excess (``long_count``,
               ``long_ms``; the last 64 in ``stats()["tick_long"]``): what
               a window lost in a phase.
- pauses.py:   the process's pause watch (``stats()["pauses"]``): a thread
               whose late wakes are the pauses of the whole process, each
               classed by the CPU time and the collector's time inside it,
               and every collection by generation; one watch a process,
               acquired by ``ServingEngine.start()``.
- warmup.py:   ``stats()["warmup_s"]``: the warm-up's seconds by kind
               (trace_lower, compile, cache_load, run) from JAX's own
               monitoring events.
- export.py:   the ``vtpu_serving_*`` Prometheus family set over
               ``ServingEngine.stats()`` + the span/phase histograms,
               registered into the monitor's collector so ONE scrape
               endpoint serves libvtpu and engine telemetry.
- summary.py:  the shared one-line stdout summary helper every benchmark's
               final line goes through (the PR-3 driver-artifact
               convention).
"""

from vtpu.obs.fleettrace import FleetTrace
from vtpu.obs.summary import print_summary, summary_line
from vtpu.obs.tickprof import BoundedHistogram, TickProfiler
from vtpu.obs.trace import RequestTrace, pct

try:  # the exporter needs prometheus_client; tracing/profiling do not —
    # the serving engine must stay importable without the monitor's deps
    from vtpu.obs.export import ServingCollector, serving_families
except ImportError:  # pragma: no cover
    ServingCollector = None  # type: ignore[assignment]
    serving_families = None  # type: ignore[assignment]

__all__ = [
    "BoundedHistogram",
    "FleetTrace",
    "RequestTrace",
    "ServingCollector",
    "TickProfiler",
    "pct",
    "print_summary",
    "serving_families",
    "summary_line",
]
