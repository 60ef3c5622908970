"""Device time per chunk under ``repro.conn.formation`` (phase B: the
Barnes-Hut search on the owning rank and the acceptance of requests)."""


def read(run):
    if run.trace is None:
        return None
    ns = sum(v for k, v in run.trace.scope_ns.items()
             if "repro.conn.formation" in k.split("/"))
    return ns / 1e6 / run.chunks
