"""Unified telemetry export: one JSON schema for every bench script and
the regression gate (DESIGN.md §9).

A report merges the three telemetry sources:

  * measured device counters/histograms (``telemetry.metrics.Metrics``,
    via ``counters_block``) — per-rank values preserved next to totals;
  * host-side span timings (``telemetry.trace.export``), with compile and
    steady-state explicitly separated by the bench harness
    (``benchmarks/_util.measure`` / ``brain_sim_timed``);
  * analytic bytes from ``launch/roofline.py`` and the kernels' closed-form
    traffic models, carried in each case's ``metrics``.

Schema (``repro.telemetry/v1``)::

    {"schema": "repro.telemetry/v1", "bench": "<family>", "smoke": bool,
     "mesh": {"num_ranks": R, "backend": "cpu"},
     "cases": {"<case>": {"params": {...},     # shapes: n_per_rank, ...
                          "metrics": {...}}},  # flat floats: compile_ms,
                                               # steady_us_per_*, ratios
     "counters": {...}?, "histograms": {...}?, "spans": [...]?,
     "lifecycle": {...}?}                      # runner fault-tolerance
                                               # counters (saves/restores/
                                               # rollbacks/restarts/degrades)

``normalize`` also reads the PRE-schema flat ``BENCH_*.json`` layouts, so
the regression gate compares old committed baselines and new smoke runs
interchangeably (the satellite contract: old keys stay readable).
"""
from __future__ import annotations

import json
from typing import Any, Dict, Optional

import numpy as np

SCHEMA = "repro.telemetry/v1"

# params are case *shape*, never regression-checked as metrics
PARAM_KEYS = ("n_per_rank", "num_ranks", "s_max", "delta", "chunks",
              "phase_b_queries")


def timing(compile_ms: float, steady_us: float, unit: str = "chunk") -> dict:
    """The compile/steady split every bench emits (satellite 2)."""
    return {"compile_ms": float(compile_ms),
            f"steady_us_per_{unit}": float(steady_us)}


def counters_block(metrics) -> dict:
    """Serialize a (host or device) ``telemetry.metrics.Metrics``:
    summed totals AND the per-rank vectors (nothing collapsed), plus the
    health gauges (``health_flags`` reduces with max — it is a psum'd
    replicated bitmask, not a per-rank total)."""
    tot, per_rank = {}, {}
    for k, v in metrics.counters.items():
        a = np.asarray(v)
        tot[k] = float(a.sum())
        per_rank[k] = [float(x) for x in a.reshape(-1)]
    out = {"total": tot, "per_rank": per_rank}
    gauges = getattr(metrics, "gauges", None)
    if gauges:
        out["gauges"] = {
            k: float(np.asarray(v).max() if k == "health_flags"
                     else np.asarray(v).sum())
            for k, v in gauges.items()}
    return out


def lifecycle_block(lifecycle: dict) -> dict:
    """Serialize the runner lifecycle counters (checkpoint saves/
    restores, rollbacks, restarts, degrade events) — host-side ints from
    ``Simulator.lifecycle`` / ``Simulator.stats()``."""
    return {k: int(v) for k, v in lifecycle.items()}


def service_block(stats: dict, handles=None) -> dict:
    """Serialize a multi-tenant service run (repro.service): the service
    lifecycle counters (admissions, completions, quarantines, rollbacks,
    sheds, ...) plus a per-terminal-status census of the submitted
    requests."""
    out = {"lifecycle": {k: int(v) for k, v in stats.items()}}
    if handles is not None:
        census: Dict[str, int] = {}
        for h in handles:
            s = h.status.value
            census[s] = census.get(s, 0) + 1
        out["requests"] = census
    return out


def quality_block(metrics: dict) -> dict:
    """Serialize workload *function* metrics (repro.workloads — engram
    recall overlap/selectivity, assimilation error): quality reported in
    the same schema as the perf counters, so every bench row can carry
    both speed and function (DESIGN.md §13). The same values also appear
    as case metrics — the regression gate compares cases."""
    return {k: float(v) for k, v in metrics.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def histograms_block(metrics) -> dict:
    return {k: np.asarray(v).sum(axis=0).tolist()
            for k, v in metrics.hists.items()}


def roofline_block(hlo_text: str, num_ranks: int) -> dict:
    """Analytic bytes/FLOPs of one compiled sim chunk
    (``launch/roofline.py`` over the post-SPMD optimized HLO): collective
    wire bytes by kind, dot FLOPs, materialized HBM bytes, and the
    roofline terms priced on the TPU v5e peaks (a model of that target,
    not a measurement) — the third telemetry source next to the measured
    counters and the wall-clock spans."""
    from repro.launch import roofline as rl
    ana = rl.analyze_hlo(hlo_text, num_ranks)
    mat = rl.materialized_bytes(hlo_text)
    terms = rl.roofline_terms(ana["dot_flops"], mat,
                              ana["collective_bytes_total"],
                              device_kind=rl.V5E)
    return {"collective_wire_bytes": ana["collective_wire_bytes"],
            "collective_bytes_total": ana["collective_bytes_total"],
            "dot_flops": ana["dot_flops"],
            "materialized_hbm_bytes": mat,
            "target_device_kind": rl.V5E,
            "terms": terms}


def make_report(bench: str, cases: Dict[str, dict], *, smoke: bool = False,
                mesh: Optional[dict] = None, counters: Optional[dict] = None,
                histograms: Optional[dict] = None,
                spans: Optional[list] = None,
                roofline: Optional[dict] = None,
                lifecycle: Optional[dict] = None,
                service: Optional[dict] = None,
                quality: Optional[dict] = None) -> dict:
    rep = {"schema": SCHEMA, "bench": bench, "smoke": bool(smoke),
           "cases": cases}
    if service is not None:
        rep["service"] = service
    if quality is not None:
        rep["quality"] = quality_block(quality)
    if mesh is not None:
        rep["mesh"] = mesh
    if counters is not None:
        rep["counters"] = counters
    if histograms is not None:
        rep["histograms"] = histograms
    if spans is not None:
        rep["spans"] = spans
    if roofline is not None:
        rep["roofline"] = roofline
    if lifecycle is not None:
        rep["lifecycle"] = lifecycle_block(lifecycle)
    return rep


def case(params: dict, metrics: dict) -> dict:
    return {"params": {k: _num(v) for k, v in params.items()},
            "metrics": {k: _num(v) for k, v in metrics.items()}}


def _num(v):
    if isinstance(v, (bool, str)):
        return v
    try:
        return float(v)
    except (TypeError, ValueError):
        return v


def write(path: str, report: dict) -> None:
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------- normalize
def _split_case(d: dict) -> dict:
    params = {k: d[k] for k in PARAM_KEYS if k in d}
    metrics = {k: float(v) for k, v in d.items()
               if k not in params and isinstance(v, (int, float))
               and not isinstance(v, bool)}
    return {"params": params, "metrics": metrics}


def normalize(obj: dict, bench: Optional[str] = None) -> dict:
    """Canonical view ``{"bench", "smoke", "cases": {name: {"params",
    "metrics"}}}`` of either a v1 report or a pre-schema flat
    ``BENCH_*.json`` (old-activity: flat case at top level; old
    connectivity/spikes: {"smoke": bool, "<case>": {...}})."""
    if obj.get("schema") == SCHEMA:
        return {"bench": obj.get("bench", bench), "smoke": obj.get("smoke",
                False), "cases": obj["cases"]}
    if "n_per_rank" in obj:                       # old flat single-case
        name = f"n{int(obj['n_per_rank'])}"
        return {"bench": bench, "smoke": bool(obj.get("smoke", False)),
                "cases": {name: _split_case(obj)}}
    cases = {k: _split_case(v) for k, v in obj.items()
             if isinstance(v, dict)}
    return {"bench": bench, "smoke": bool(obj.get("smoke", False)),
            "cases": cases}
