"""Device time per chunk in the connectivity update outside phase B:
``repro.conn.retraction``, ``repro.conn.tree_build``, ``repro.conn.phase_a``,
``repro.conn.exchange`` and the update's own glue under
``repro.connectivity``."""


def read(run):
    if run.trace is None:
        return None
    ns = sum(v for k, v in run.trace.scope_ns.items()
             if k.split("/")[0] == "repro.connectivity"
             and "repro.conn.formation" not in k.split("/"))
    return ns / 1e6 / run.chunks
