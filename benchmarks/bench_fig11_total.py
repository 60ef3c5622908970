"""Paper Fig. 11 / §V-E: total simulation time, all-old vs all-new algorithm
pairs, largest feasible local configuration.

Emits CSV and — with ``--json`` or ``--smoke`` — a ``repro.telemetry/v1``
report with the compile/steady split and the all-new run's device
counters/histograms: ``--smoke`` (small n, for CI) writes
``BENCH_fig11_smoke.json``, otherwise ``BENCH_fig11.json`` (the committed
baseline the regression gate compares against).
"""
import os
import sys

from benchmarks._util import ROOT, brain_sim_timed, emit, num_ranks


def main():
    smoke = "--smoke" in sys.argv
    write_json = smoke or "--json" in sys.argv
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    n = int(args[0]) if args else (64 if smoke else 512)
    import jax
    from repro import telemetry
    r = num_ranks()
    levels, frontier, s_max = (3, 32, 8) if smoke else (4, 64, 32)
    metrics, sims = {}, {}
    for conn, spike, tag in (("old", "old", "old"), ("new", "new", "new")):
        with telemetry.span(f"bench.fig11.{tag}", n=n):
            timing, sims[tag] = brain_sim_timed(dict(
                neurons_per_rank=n, local_levels=levels,
                frontier_cap=frontier, max_synapses=s_max,
                connectivity_alg=conn, spike_alg=spike,
                requests_cap_factor=1), chunks=2)
        metrics[f"{tag}_compile_ms"] = timing.compile_ms
        metrics[f"{tag}_steady_us_per_chunk"] = timing.steady_us
    metrics["walltime_reduction_pct"] = 100 * (
        1 - metrics["new_steady_us_per_chunk"]
        / metrics["old_steady_us_per_chunk"])
    emit(f"fig11_total_old_r{r}_n{n}", metrics["old_steady_us_per_chunk"],
         f"compile_ms={metrics['old_compile_ms']:.0f}")
    emit(f"fig11_total_new_r{r}_n{n}", metrics["new_steady_us_per_chunk"],
         f"walltime_reduction={metrics['walltime_reduction_pct']:.1f}%")
    if write_json:
        device_metrics = sims["new"].metrics()
        # analytic bytes/FLOPs of the all-new chunk's compiled HLO — the
        # roofline source merged next to the measured counters
        roofline = telemetry.report.roofline_block(
            sims["new"].lower().compile().as_text(), r)
        params = {"num_ranks": r, "n_per_rank": n, "s_max": s_max,
                  "chunks": 3}
        rep = telemetry.report.make_report(
            "fig11", {f"r{r}_n{n}": telemetry.report.case(params, metrics)},
            smoke=smoke,
            mesh={"num_ranks": r, "backend": jax.default_backend()},
            counters=telemetry.report.counters_block(device_metrics),
            histograms=telemetry.report.histograms_block(device_metrics),
            spans=telemetry.export(),
            roofline=roofline)
        out = "BENCH_fig11_smoke.json" if smoke else "BENCH_fig11.json"
        telemetry.report.write(os.path.join(ROOT, out), rep)


if __name__ == "__main__":
    main()
