"""Device time per chunk of phase B's frontier expansion: the set-up and the
rounds of ``traverse.expand_and_sample`` (``repro.bh.expand`` under
``repro.conn.formation``). None where the program has no such scope."""


def read(run):
    if run.trace is None:
        return None
    ns = [v for k, v in run.trace.scope_ns.items()
          if "repro.conn.formation" in k.split("/")
          and k.rsplit("/", 1)[-1] == "repro.bh.expand"]
    return sum(ns) / 1e6 / run.chunks if ns else None
