"""Paper Fig. 4: spike-transmission cost. Two sweeps:

  * spike_alg old vs new — per-step spiked-ID exchange vs Delta-periodic
    rate exchange (the chunk is dominated by the activity phase);
  * rate_exchange dense vs sparse (spike_alg='new') — the replicated (R, n)
    rates all-gather vs the demand-driven subscription push (DESIGN.md §7),
    with the measured exchanged-rate-record counters next to wall time.

Exchange volume comes from ``stats['rates_sent']`` (rate records actually
shipped: dense = n*(R-1) per rank per Delta, sparse = the subscribed
pushes), so the byte drop R*n*4 -> |subs|*4 is measured, not modeled. The
sparse exchange additionally ships one 4B subscription-request id per
pushed rate (``stats['subscription_requests']``) — reported separately and
folded into ``total_bytes_ratio`` so the sparse win is not overstated.

Writes a ``repro.telemetry/v1`` report — device counters/histograms of the
sparse run and host-side spans included — with ``--json`` to
``BENCH_spikes.json`` at the repo root (the recorded perf-trajectory
baseline: r=4, n=1024); ``--smoke`` runs a small n for CI and writes
``BENCH_spikes_smoke.json`` instead, so reproducing the CI step locally
cannot clobber the committed baseline. Compile (warmup chunk) and
steady-state per-chunk time are reported separately.
"""
import os
import sys

from benchmarks._util import (PAPER_BYTES, ROOT, brain_sim_timed, emit,
                              num_ranks)


def bench(n, chunks=2):
    import numpy as np
    from repro import telemetry
    from repro.core.spikes import NO_SUB
    r = num_ranks()
    base = dict(neurons_per_rank=n, local_levels=3, frontier_cap=32,
                max_synapses=16, connectivity_alg="new", rate_period=100,
                requests_cap_factor=max(r, 4), subs_cap_factor=max(r, 4))
    runs = {"old": dict(base, spike_alg="old"),
            "dense": dict(base, rate_exchange="dense"),
            "sparse": dict(base, rate_exchange="sparse")}
    sims, metrics = {}, {}
    for name, cfg in runs.items():
        with telemetry.span(f"bench.spikes.{name}", n=n):
            timing, sims[name] = brain_sim_timed(cfg, chunks=chunks)
        metrics[f"{name}_compile_ms"] = timing.compile_ms
        metrics[f"{name}_steady_us_per_chunk"] = timing.steady_us

    chunks_total = chunks + 1   # the warmup chunk also accumulates
    states = {name: sim.state for name, sim in sims.items()}
    for name in ("dense", "sparse"):
        sent = float(states[name].stats["rates_sent"].sum())
        metrics[f"{name}_rate_records_per_delta"] = sent / chunks_total
        metrics[f"{name}_rate_bytes_per_delta"] = \
            sent / chunks_total * PAPER_BYTES["rate"]
    subs = np.asarray(states["sparse"].subs)
    metrics["subs_per_rank_mean"] = float((subs != NO_SUB).sum()) / r
    metrics["dense_table_bytes_per_rank"] = r * n * PAPER_BYTES["rate"]
    metrics["subscription_overflow"] = \
        float(states["sparse"].stats["subscription_overflow"].sum())
    # the 4B request ids shipped alongside the pushed rates (dense: none)
    reqs = float(states["sparse"].stats["subscription_requests"].sum())
    metrics["sparse_request_bytes_per_delta"] = \
        reqs / chunks_total * PAPER_BYTES["rate"]
    metrics["rate_bytes_ratio"] = metrics["dense_rate_bytes_per_delta"] / \
        max(metrics["sparse_rate_bytes_per_delta"], 1.0)
    metrics["total_bytes_ratio"] = metrics["dense_rate_bytes_per_delta"] / \
        max(metrics["sparse_rate_bytes_per_delta"]
            + metrics["sparse_request_bytes_per_delta"], 1.0)
    # the whole point: the push must ship strictly less than the broadcast
    if r > 1:
        assert metrics["total_bytes_ratio"] > 1.0, metrics["total_bytes_ratio"]
    params = {"num_ranks": r, "n_per_rank": n,
              "delta": base["rate_period"], "chunks": chunks_total}
    return params, metrics, sims["sparse"].metrics()


def bench_connectome(n, chunks=2):
    """The dense-vs-sparse exchange sweep on a generated hemibrain-shaped
    surrogate (``--connectome``): the same byte counters, but measured on
    a heavy-tailed degree distribution through ``from_connectome`` (whose
    sparse registry is sized from the measured unique-remote-source
    count, not the near-uniform synthetic default). CSV-only — the
    surrogate's subscription footprint is not comparable to the
    committed synthetic baseline, so it is reported, not gated."""
    import time

    import jax
    import numpy as np
    from repro import telemetry
    from repro.configs.msp_brain import BrainConfig
    from repro.core.spikes import NO_SUB
    from repro.sim import Simulator
    from repro.workloads import datasets as wds
    r = num_ranks()
    base = dict(neurons_per_rank=n, local_levels=3, frontier_cap=32,
                max_synapses=16, connectivity_alg="new", rate_period=100,
                requests_cap_factor=max(r, 4), subs_cap_factor=max(r, 4))
    ds = wds.generate_hemibrain_surrogate(r * n, n,
                                          max_degree=base["max_synapses"])
    metrics = {"edges": float(ds.num_edges),
               "max_out_degree": float(ds.out_degrees().max())}
    states = {}
    for name in ("dense", "sparse"):
        cfg = BrainConfig(**dict(base, rate_exchange=name))
        with telemetry.span(f"bench.spikes.conn.{name}", n=n):
            sim = Simulator.from_connectome(cfg, ds)
            t0 = time.perf_counter()
            st = sim.step()
            jax.block_until_ready(st.positions)
            metrics[f"{name}_compile_ms"] = (time.perf_counter() - t0) * 1e3
            t0 = time.perf_counter()
            for _ in range(chunks):
                st = sim.step()
            jax.block_until_ready(st.positions)
            metrics[f"{name}_steady_us_per_chunk"] = \
                (time.perf_counter() - t0) / chunks * 1e6
        states[name] = sim.state
    chunks_total = chunks + 1
    for name in ("dense", "sparse"):
        sent = float(states[name].stats["rates_sent"].sum())
        metrics[f"{name}_rate_bytes_per_delta"] = \
            sent / chunks_total * PAPER_BYTES["rate"]
    subs = np.asarray(states["sparse"].subs)
    metrics["subs_per_rank_mean"] = float((subs != NO_SUB).sum()) / r
    metrics["subscription_overflow"] = \
        float(states["sparse"].stats["subscription_overflow"].sum())
    reqs = float(states["sparse"].stats["subscription_requests"].sum())
    metrics["sparse_request_bytes_per_delta"] = \
        reqs / chunks_total * PAPER_BYTES["rate"]
    metrics["total_bytes_ratio"] = metrics["dense_rate_bytes_per_delta"] / \
        max(metrics["sparse_rate_bytes_per_delta"]
            + metrics["sparse_request_bytes_per_delta"], 1.0)
    emit(f"fig4_spikes_conn_dense_r{r}_n{n}",
         metrics["dense_steady_us_per_chunk"],
         f"rateB/Delta={metrics['dense_rate_bytes_per_delta']:.0f} "
         f"edges={ds.num_edges}")
    emit(f"fig4_spikes_conn_sparse_r{r}_n{n}",
         metrics["sparse_steady_us_per_chunk"],
         f"rate+reqB/Delta={metrics['sparse_rate_bytes_per_delta']:.0f}"
         f"+{metrics['sparse_request_bytes_per_delta']:.0f} "
         f"({metrics['total_bytes_ratio']:.1f}x less, "
         f"overflow={metrics['subscription_overflow']:.0f})")
    return metrics


def main():
    smoke = "--smoke" in sys.argv
    write_json = smoke or "--json" in sys.argv
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    n = int(args[0]) if args else (64 if smoke else 256)
    import jax
    from repro import telemetry
    r = num_ranks()
    if "--connectome" in sys.argv:
        bench_connectome(n)
        return
    params, m, device_metrics = bench(n)
    emit(f"fig4_spikes_old_r{r}_n{n}", m["old_steady_us_per_chunk"],
         f"compile_ms={m['old_compile_ms']:.0f}")
    emit(f"fig4_spikes_new_dense_r{r}_n{n}", m["dense_steady_us_per_chunk"],
         f"speedup={m['old_steady_us_per_chunk'] / m['dense_steady_us_per_chunk']:.2f}x "
         f"rateB/Delta={m['dense_rate_bytes_per_delta']:.0f}")
    emit(f"fig4_spikes_new_sparse_r{r}_n{n}", m["sparse_steady_us_per_chunk"],
         f"rate+reqB/Delta={m['sparse_rate_bytes_per_delta']:.0f}"
         f"+{m['sparse_request_bytes_per_delta']:.0f} "
         f"({m['total_bytes_ratio']:.1f}x less)")
    if write_json:
        # smoke output goes to its own file: reproducing the CI smoke step
        # locally must not clobber the committed r=4/n=1024 baseline
        out = "BENCH_spikes_smoke.json" if smoke else "BENCH_spikes.json"
        rep = telemetry.report.make_report(
            "spikes", {f"r{r}_n{n}": telemetry.report.case(params, m)},
            smoke=smoke,
            mesh={"num_ranks": r, "backend": jax.default_backend()},
            counters=telemetry.report.counters_block(device_metrics),
            histograms=telemetry.report.histograms_block(device_metrics),
            spans=telemetry.export())
        telemetry.report.write(os.path.join(ROOT, out), rep)


if __name__ == "__main__":
    main()
