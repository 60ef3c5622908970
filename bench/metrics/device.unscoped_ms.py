"""Device time per chunk outside ``repro.activity`` and
``repro.connectivity``: the health verdict (``repro.health``), the metrics
ring, scan plumbing and the benchmark's own state snapshot."""


def read(run):
    if run.trace is None:
        return None
    ns = sum(v for k, v in run.trace.scope_ns.items()
             if k.split("/")[0] not in ("repro.activity",
                                        "repro.connectivity"))
    return ns / 1e6 / run.chunks
