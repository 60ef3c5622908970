"""Share of phase B's query slots that hold a request over the window's
chunks: 100 x ``bh_queries_live`` / ``bh_query_slots``, counted on the
device where phase B runs and read from the per-chunk ring
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
    if not counters or "bh_query_slots" not in counters:
        return None
    den = float(counters["bh_query_slots"].sum())
    if not den:
        return None
    return 100.0 * float(counters["bh_queries_live"].sum()) / den
