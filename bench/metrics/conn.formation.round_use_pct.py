"""Share of the restart iterations phase B runs that a live query needed over
the window's chunks: 100 x ``bh_restarts`` / ``bh_rounds_run`` (every row
runs the restart cap), counted on the device where phase B runs
(``telemetry.last_chunk_counters``). None where the program has no such
counters."""


def read(run):
    if run.trace is None:
        return None
    try:
        from repro import telemetry
    except ImportError:
        return None
    last = getattr(telemetry, "last_chunk_counters", None)
    counters = last(run.chunks) if last else None
    if not counters or "bh_rounds_run" not in counters:
        return None
    den = float(counters["bh_rounds_run"].sum())
    if not den:
        return None
    return 100.0 * float(counters["bh_restarts"].sum()) / den
