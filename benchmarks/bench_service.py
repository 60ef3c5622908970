"""Multi-tenant service throughput + isolation smoke (DESIGN.md §12).

Per tenant count B: spins up a ``SimulationService`` over a shared
compiled ``SlotBatch``, drives B same-budget tenants to completion, and
measures

  * ``requests_per_s``        completed requests / steady wall time;
  * ``p50_us_per_chunk`` /    per-tick (== per-chunk-boundary) service
    ``p99_us_per_chunk``      latency distribution, compile tick excluded;
  * ``isolation_overhead_x``  per-tenant chunk wall time vs a solo
                              ``Simulator`` chunk — the price of
                              co-tenancy (vmapped lanes + per-slot
                              verdicts + host bookkeeping).

Then the chaos smoke: B=4 tenants with one NaN-poisoned via
``chaos.poison_slot_nan`` — ASSERTS the poisoned slot quarantines + rolls
back and every tenant still completes (the bit-identity proof lives in
tests/test_service.py; the bench gate only needs recovery + counts).

With ``--smoke`` writes ``BENCH_service_smoke.json`` for the regression
gate (rules ``requests_per_s``, ``isolation_overhead_x``,
``*_us_per_*``), otherwise ``BENCH_service.json`` — the committed
baseline, which includes the smoke-scale cases so the gate pairs by
exact name at matched params (same reasoning as bench_connectivity).
"""
import os
import sys
import time

import numpy as np

from benchmarks._util import ROOT, emit, num_ranks


def _drive(svc, handles):
    """Tick to idle; returns (compile_ms, tick_times_s) with the first
    (trace+compile) tick split out of the steady distribution."""
    t0 = time.perf_counter()
    more = svc.tick()
    compile_ms = (time.perf_counter() - t0) * 1e3
    ticks = []
    while more:
        t0 = time.perf_counter()
        more = svc.tick()
        ticks.append(time.perf_counter() - t0)
    assert all(h.result is not None for h in handles)
    return compile_ms, ticks


def _bench_case(cfg, batch, tenants, chunks, solo_us):
    from repro.service import ServiceConfig, SimRequest, SimulationService
    svc = SimulationService(
        cfg, ServiceConfig(num_slots=tenants, queue_cap=2 * tenants),
        batch=batch)
    handles = [svc.submit(SimRequest(seed=100 + i, chunks=chunks))
               for i in range(tenants)]
    compile_ms, ticks = _drive(svc, handles)
    assert svc.stats()["requests_completed"] == tenants
    tick_us = np.array(ticks) * 1e6
    metrics = {
        "compile_ms": compile_ms,
        "requests_per_s": tenants / max(sum(ticks), 1e-9),
        "p50_us_per_chunk": float(np.percentile(tick_us, 50)),
        "p99_us_per_chunk": float(np.percentile(tick_us, 99)),
        "isolation_overhead_x":
            float(np.percentile(tick_us, 50)) / tenants / solo_us,
    }
    return metrics


def _solo_us_per_chunk(cfg, chunks):
    """Steady per-chunk wall time of a solo Simulator (the denominator
    of isolation_overhead_x)."""
    from repro.sim import Simulator
    sim = Simulator(cfg)
    sim.run(1)                        # compile
    best = float("inf")
    for _ in range(chunks):
        t0 = time.perf_counter()
        sim.run(1)
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _chaos_smoke(cfg, batch):
    """One poisoned tenant among 4: assert quarantine + rollback + full
    recovery. Returns (service stats, handles) for the report."""
    from repro import telemetry
    from repro.runtime import chaos
    from repro.service import (RequestStatus, ServiceConfig, SimRequest,
                               SimulationService)
    with telemetry.span("bench.service.chaos", tenants=4):
        svc = SimulationService(cfg, ServiceConfig(num_slots=4),
                                batch=batch)
        svc.chaos_hooks.append(chaos.poison_slot_nan(1, after_chunk=1))
        handles = [svc.submit(SimRequest(seed=200 + i, chunks=3))
                   for i in range(4)]
        svc.run_until_idle()
        stats = svc.stats()
        assert stats["quarantines"] >= 1, \
            "slot poisoning did not trigger a quarantine"
        assert stats["slot_rollbacks"] >= 1, \
            "quarantine did not roll the slot back"
        assert all(h.result.status is RequestStatus.DONE
                   for h in handles), "a tenant failed to recover"
    return stats, handles


def main():
    smoke = "--smoke" in sys.argv
    import jax
    from repro import telemetry
    from repro.configs.msp_brain import BrainConfig
    from repro.service import SlotBatch

    r = num_ranks()
    # smoke-scale cases always run (the committed baseline carries them
    # too, so the gate pairs by exact name at matched params); the full
    # run adds a larger-n case for the record
    sizes = [(32, 3, (2, 4))]
    if not smoke:
        sizes.append((64, 4, (4,)))

    cases, chaos_stats, chaos_handles = {}, None, None
    for n, chunks, tenant_counts in sizes:
        cfg = BrainConfig(neurons_per_rank=n, local_levels=3,
                          frontier_cap=32, max_synapses=8, rate_period=10,
                          requests_cap_factor=100, subs_cap_factor=100)
        solo_us = _solo_us_per_chunk(cfg, chunks)
        for b in tenant_counts:
            batch = SlotBatch(cfg, b)
            with telemetry.span("bench.service.case", tenants=b, n=n):
                m = _bench_case(cfg, batch, b, chunks, solo_us)
            m["solo_us_per_chunk"] = solo_us
            cases[f"b{b}_r{r}_n{n}"] = telemetry.report.case(
                {"tenants": b, "num_ranks": r, "n_per_rank": n,
                 "chunks": chunks}, m)
            emit(f"service_b{b}_r{r}_n{n}", m["p50_us_per_chunk"],
                 f"req_per_s={m['requests_per_s']:.2f} "
                 f"overhead_x={m['isolation_overhead_x']:.2f}")
            if b == 4 and n == 32:
                chaos_stats, chaos_handles = _chaos_smoke(cfg, batch)

    rep = telemetry.report.make_report(
        "service", cases, smoke=smoke,
        mesh={"num_ranks": r, "backend": jax.default_backend()},
        spans=telemetry.export(),
        service=telemetry.report.service_block(chaos_stats,
                                               chaos_handles))
    out = "BENCH_service_smoke.json" if smoke else "BENCH_service.json"
    telemetry.report.write(os.path.join(ROOT, out), rep)


if __name__ == "__main__":
    main()
