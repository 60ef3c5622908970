"""repro.telemetry — device-side metrics, host-side spans, unified report
(DESIGN.md §9).

Three layers:

  metrics   the ``Metrics`` pytree carried through the jitted scan in
            ``BrainState.stats`` — per-phase counters (phase B's live
            rows, query slots, restart rounds run and frontier overflow
            among them), per-chunk ring buffers, fixed-size histograms;
            per-rank resolution preserved. ``last_chunk_counters(k)``
            reads the ring of the newest state a Simulator produced;
  trace     ``span(name)`` wall-clock records, timed on the profiler's
            clock (``time.time_ns()``), + jax.profiler trace annotations
            (a step marker per ``Simulator.run``); ``profile(log_dir)``
            guards a Perfetto capture;
  report    the single JSON schema all benchmarks emit and
            ``benchmarks/check_regression.py`` gates on.
"""
from repro.telemetry.metrics import (COUNTER_KEYS, GAUGE_KEYS, HIST_BUCKETS,
                                     LEGACY_KEYS, LIFECYCLE_KEYS, PHASE_OF,
                                     Metrics, Recorder, init_metrics,
                                     last_chunk_counters, metrics_specs,
                                     publish_latest)
from repro.telemetry.trace import (Span, clear, export, profile, span, spans)
from repro.telemetry import report

__all__ = [
    "COUNTER_KEYS", "GAUGE_KEYS", "HIST_BUCKETS", "LEGACY_KEYS",
    "LIFECYCLE_KEYS", "PHASE_OF", "Metrics", "Recorder", "init_metrics",
    "last_chunk_counters", "metrics_specs", "publish_latest", "Span",
    "clear", "export", "profile", "span", "spans", "report",
]
