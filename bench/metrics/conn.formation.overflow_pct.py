"""Share of phase B's live queries whose frontier overflowed over the
window's chunks: 100 x ``bh_frontier_overflow`` / ``bh_queries_live``,
counted on the device where phase B runs
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
    if not counters or "bh_queries_live" not in counters:
        return None
    den = float(counters["bh_queries_live"].sum())
    if not den:
        return None
    return 100.0 * float(counters["bh_frontier_overflow"].sum()) / den
