"""Device time per chunk of the synaptic input of the activity steps: local
spike hits, remote spike reconstruction, the weight gather and the input
sum of ``activity_fused.step_core`` (``repro.act.input`` under
``repro.activity``). None where the program has no such scope."""


def read(run):
    if run.trace is None:
        return None
    ns = [v for k, v in run.trace.scope_ns.items()
          if "repro.activity" in k.split("/")
          and k.rsplit("/", 1)[-1] == "repro.act.input"]
    return sum(ns) / 1e6 / run.chunks if ns else None
