"""Device time per chunk under ``repro.activity`` (the Delta electrical
steps of ``sim/phases.activity_phase``)."""


def read(run):
    if run.trace is None:
        return None
    ns = sum(v for k, v in run.trace.scope_ns.items()
             if k.split("/")[0] == "repro.activity")
    return ns / 1e6 / run.chunks
