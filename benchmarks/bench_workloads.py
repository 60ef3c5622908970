"""Workload quality bench: function next to speed (DESIGN.md §13).

Three cases through the ``repro.workloads`` subsystem:

  * ``engram``       train/lesion/recall pattern completion grown from an
                     empty connectome — quality metrics ``recall_overlap``
                     (gated: must not regress) and ``engram_selectivity``;
  * ``engram_conn``  the same protocol started from a generated
                     hemibrain-shaped surrogate via
                     ``Simulator.from_connectome`` (heavy-tailed degrees
                     through the full rewiring path);
  * ``assim``        the rate-assimilation loop — ``assim_final_abs_err``
                     (convergence, gated) and ``dyn_compile_count``
                     (retrace-free dynamic params: gated exactly).

Writes a ``repro.telemetry/v1`` report; ``--smoke`` to
``BENCH_workloads_smoke.json`` (CI candidate), ``--json`` to the
committed ``BENCH_workloads.json``. The committed baseline is captured
at smoke scale in the CI gate environment (4 host devices) so the smoke
run pairs with it at matched params and the quality rules apply tightly.
"""
import dataclasses
import os
import sys
import time


from benchmarks._util import ROOT, emit, num_ranks


def bench(n):
    from repro import telemetry
    from repro.configs.msp_brain import SMOKE_CONFIG
    from repro.workloads import assimilate as was
    from repro.workloads import datasets as wds
    from repro.workloads import engram as weng

    r = num_ranks()
    cfg = dataclasses.replace(SMOKE_CONFIG, neurons_per_rank=n,
                              requests_cap_factor=1000)
    spec = weng.EngramSpec()
    cases = {}

    with telemetry.span("bench.workloads.engram", n=n):
        t0 = time.perf_counter()
        m, sim = weng.run_engram(cfg, spec=spec)
        m["engram_wall_ms"] = (time.perf_counter() - t0) * 1e3
        m["synapses_formed"] = sim.stats()["synapses_formed"]
    params = {"num_ranks": r, "n_per_rank": n,
              "chunks": spec.total_chunks}
    cases[f"engram_r{r}_n{n}"] = telemetry.report.case(params, m)
    device_metrics = sim.metrics()
    emit(f"workloads_engram_r{r}_n{n}", m["engram_wall_ms"] * 1e3,
         f"recall_overlap={m['recall_overlap']:.3f} "
         f"selectivity={m['engram_selectivity']:.3f}")

    with telemetry.span("bench.workloads.engram_conn", n=n):
        ds = wds.generate_hemibrain_surrogate(
            r * n, n, max_degree=cfg.max_synapses,
            fraction_excitatory=cfg.fraction_excitatory)
        t0 = time.perf_counter()
        mc, simc = weng.run_engram(cfg, spec=spec, dataset=ds)
        mc["engram_wall_ms"] = (time.perf_counter() - t0) * 1e3
        mc["initial_synapses"] = float(ds.num_edges)
    cases[f"engram_conn_r{r}_n{n}"] = telemetry.report.case(params, mc)
    emit(f"workloads_engram_conn_r{r}_n{n}", mc["engram_wall_ms"] * 1e3,
         f"recall_overlap={mc['recall_overlap']:.3f} "
         f"edges={ds.num_edges}")

    with telemetry.span("bench.workloads.assim", n=n):
        t0 = time.perf_counter()
        res, _ = was.run_assimilation(cfg)
        wall_ms = (time.perf_counter() - t0) * 1e3
    ma = {"assim_final_abs_err": res.final_abs_err,
          "assim_first_abs_err": float(res.abs_err[0]),
          "dyn_compile_count": float(res.compile_count),
          "assim_wall_ms": wall_ms}
    assert res.compile_count == 1, res.compile_count
    cases[f"assim_r{r}_n{n}"] = telemetry.report.case(
        {"num_ranks": r, "n_per_rank": n,
         "chunks": res.target.shape[0]}, ma)
    emit(f"workloads_assim_r{r}_n{n}", wall_ms * 1e3,
         f"final_abs_err={res.final_abs_err:.5f} "
         f"compiles={res.compile_count}")
    return cases, device_metrics


def main():
    smoke = "--smoke" in sys.argv
    write_json = smoke or "--json" in sys.argv
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    n = int(args[0]) if args else 64
    import jax
    from repro import telemetry
    r = num_ranks()
    cases, device_metrics = bench(n)
    if write_json:
        out = "BENCH_workloads_smoke.json" if smoke \
            else "BENCH_workloads.json"
        quality = {f"{cname}/{k}": c["metrics"][k]
                   for cname, c in cases.items()
                   for k in ("recall_overlap", "engram_selectivity",
                             "assim_final_abs_err")
                   if k in c["metrics"]}
        rep = telemetry.report.make_report(
            "workloads", cases, smoke=smoke,
            mesh={"num_ranks": r, "backend": jax.default_backend()},
            counters=telemetry.report.counters_block(device_metrics),
            quality=quality,
            spans=telemetry.export())
        telemetry.report.write(os.path.join(ROOT, out), rep)


if __name__ == "__main__":
    main()
