"""Device time per chunk of the acceptance of phase B's requests by their
targets (``repro.conn.accept`` under ``repro.conn.formation``). None where
the program has no such scope."""


def read(run):
    if run.trace is None:
        return None
    ns = [v for k, v in run.trace.scope_ns.items()
          if "repro.conn.formation" in k.split("/")
          and k.rsplit("/", 1)[-1] == "repro.conn.accept"]
    return sum(ns) / 1e6 / run.chunks if ns else None
