"""Host-side tracing: wall-clock spans + jax.profiler integration.

``span(name)`` is a context manager that (a) records a wall-clock span
(start, duration, nesting depth, parent) into a process-wide ring and
(b) opens a ``jax.profiler.TraceAnnotation`` so the same region shows up
as a named slice in a captured Perfetto/XPlane trace. With ``step_num``
it opens a ``jax.profiler.StepTraceAnnotation`` instead, which the
profiler shows as a step marker. Each record keeps its start and end in
ns on ``time.time_ns()``, the wall clock the profiler stamps host events
with, so a span read from ``spans()`` can be set directly against the
device ops and idle gaps of a captured trace. The Simulator wraps
``from_config`` / ``init`` / ``step`` / ``run`` / ``lower`` / ``save`` /
``restore`` in spans; ``run``, ``step`` and ``step_with`` open theirs as
step markers numbered by the first chunk they advance. Phase-level
device-side annotation uses ``jax.named_scope`` inside the traced chunk
(sim/phases.py, and the ``repro.bh.*``, ``repro.conn.accept`` and
``repro.act.input`` scopes within phase B and the activity step).

``profile(log_dir)`` guards ``jax.profiler.trace``: a failure to start
(no backend support, a trace already active) degrades to a no-op with a
warning instead of killing the run — profiling is opt-in observability,
never a correctness dependency.
"""
from __future__ import annotations

import contextlib
import threading
import time
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax

_MAX_SPANS = 4096
_records: "deque[Span]" = deque(maxlen=_MAX_SPANS)
_records_lock = threading.Lock()
_tls = threading.local()


@dataclass
class Span:
    """One completed (or in-flight) wall-clock span."""
    name: str
    start_s: float              # perf_counter at entry
    duration_ms: float = -1.0   # -1 while still open
    depth: int = 0
    parent: Optional[str] = None
    attrs: Dict[str, Any] = field(default_factory=dict)
    start_ns: int = 0           # time.time_ns() at entry: the profiler's clock
    end_ns: int = 0             # time.time_ns() at exit; 0 while still open
    step_num: Optional[int] = None   # set on a step marker

    def asdict(self) -> dict:
        return {"name": self.name, "start_s": self.start_s,
                "duration_ms": self.duration_ms, "depth": self.depth,
                "parent": self.parent, "attrs": dict(self.attrs),
                "start_ns": self.start_ns, "end_ns": self.end_ns,
                "step_num": self.step_num}


def _stack() -> List[Span]:
    if not hasattr(_tls, "stack"):
        _tls.stack = []
    return _tls.stack


@contextlib.contextmanager
def span(name: str, step_num: Optional[int] = None, **attrs):
    """Record a named wall-clock span (and a profiler TraceAnnotation, or
    with ``step_num`` a StepTraceAnnotation: a step marker). Yields the
    Span record; callers may add ``attrs`` to it."""
    stack = _stack()
    rec = Span(name=name, start_s=time.perf_counter(), depth=len(stack),
               parent=stack[-1].name if stack else None, attrs=dict(attrs),
               start_ns=time.time_ns(), step_num=step_num)
    stack.append(rec)
    annotation = jax.profiler.TraceAnnotation(name) if step_num is None \
        else jax.profiler.StepTraceAnnotation(name, step_num=int(step_num))
    try:
        with annotation:
            yield rec
    finally:
        rec.end_ns = time.time_ns()
        stack.pop()
        rec.duration_ms = (time.perf_counter() - rec.start_s) * 1e3
        with _records_lock:
            _records.append(rec)


def spans(name: Optional[str] = None) -> List[Span]:
    """Completed spans so far (oldest first), optionally filtered by name."""
    with _records_lock:
        out = list(_records)
    return out if name is None else [s for s in out if s.name == name]


def clear() -> None:
    with _records_lock:
        _records.clear()


def export() -> List[dict]:
    """JSON-serializable span records for telemetry.report."""
    return [s.asdict() for s in spans()]


@contextlib.contextmanager
def profile(log_dir: Optional[str]):
    """``jax.profiler.trace(log_dir)``, degraded to a no-op on None or on
    any start failure (warning, not an exception)."""
    if log_dir is None:
        yield
        return
    try:
        jax.profiler.start_trace(log_dir)
    except Exception as e:  # already tracing / unsupported backend
        warnings.warn(f"telemetry: profiler trace not captured: {e}")
        yield
        return
    try:
        yield
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            warnings.warn(f"telemetry: profiler trace not finalized: {e}")
