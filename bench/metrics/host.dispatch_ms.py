"""Host time to enqueue a chunk: the mean duration of the window's
``sim.run`` spans (one per chunk) in ``telemetry.spans()``. None where the
program recorded fewer such spans than the window has chunks."""


def read(run):
    if run.trace is None:
        return None
    try:
        from repro import telemetry
    except ImportError:
        return None
    spans = telemetry.spans("sim.run")[-run.chunks:]
    if len(spans) < run.chunks:
        return None
    return sum(s.duration_ms for s in spans) / len(spans)
