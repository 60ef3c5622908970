"""Device time per chunk of phase B's restart loop itself: the own time of
``repro.bh.search`` under ``repro.conn.formation`` (loop control and the
carry selects of ``traverse.bh_search``, not the expansion and sampling it
runs). None where the program has no such scope."""


def read(run):
    if run.trace is None:
        return None
    ns = [v for k, v in run.trace.scope_ns.items()
          if "repro.conn.formation" in k.split("/")
          and k.rsplit("/", 1)[-1] == "repro.bh.search"]
    return sum(ns) / 1e6 / run.chunks if ns else None
