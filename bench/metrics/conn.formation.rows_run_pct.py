"""Share of phase B's restart iterations under the cap that the traversal
lowering computed over the window's chunks: 100 x ``bh_rows_run`` /
``bh_rounds_run``. A lowering that runs every row through the restart cap
reads 100; one that runs only the rows still searching, in whole row
blocks, reads less. Counted on the device where phase B runs
(``telemetry.last_chunk_counters``). None where the program has no such
counter."""


def read(run):
    if run.trace is None:
        return None
    try:
        from repro import telemetry
    except ImportError:
        return None
    last = getattr(telemetry, "last_chunk_counters", None)
    counters = last(run.chunks) if last else None
    if not counters or "bh_rows_run" not in counters:
        return None
    den = float(counters["bh_rounds_run"].sum())
    if not den:
        return None
    return 100.0 * float(counters["bh_rows_run"].sum()) / den
