"""Fault-tolerant runner overhead + chaos smoke (DESIGN.md §10).

Runs ``SimulationRunner`` under injected faults — one simulated
preemption and one NaN poisoning — on whatever devices exist (CI sets 4
host devices), ASSERTS full recovery (the resumed run must finish with a
clean health verdict and the expected rollback/restart counts), and
measures the checkpoint save/restore wall-times the runner adds per
interval. With ``--smoke`` writes ``BENCH_runner_smoke.json`` for the
regression gate (rule ``*_ms_per_ckpt``), otherwise ``BENCH_runner.json``
(the committed baseline); the report carries the lifecycle counters
through the ``repro.telemetry/v1`` schema.
"""
import os
import sys
import tempfile
import time

from benchmarks._util import ROOT, emit, num_ranks


def _timed_ms(fn, iters=3):
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, (time.perf_counter() - t0) * 1e3)
    return best


def main():
    smoke = "--smoke" in sys.argv
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    n = int(args[0]) if args else (64 if smoke else 256)
    import jax
    from repro import telemetry
    from repro.configs.msp_brain import BrainConfig
    from repro.runtime import chaos
    from repro.runtime.sim_runner import SimRunnerConfig, SimulationRunner

    r = num_ranks()
    cfg = BrainConfig(neurons_per_rank=n, local_levels=3, frontier_cap=32,
                      max_synapses=8, rate_period=10,
                      requests_cap_factor=100, subs_cap_factor=100)
    chunks = 4
    metrics = {}
    with tempfile.TemporaryDirectory() as d:
        ck = os.path.join(d, "ck")
        # ---- chaos smoke: poison once, then preempt; a fresh runner
        # must resume and finish with a clean verdict ------------------
        with telemetry.span("bench.runner.chaos", n=n):
            runner = SimulationRunner(SimRunnerConfig(ck, ckpt_every=1),
                                      cfg=cfg)
            runner.chaos_hooks.append(
                chaos.poison_nan_once(field="v", after_chunk=1))
            runner.chaos_hooks.append(chaos.preempt_after(2))
            status = runner.run(chunks)
            assert status == "preempted", status
            assert runner.sim.lifecycle["rollbacks"] >= 1, \
                "NaN poisoning did not trigger a rollback"
            resumed = SimulationRunner(SimRunnerConfig(ck, ckpt_every=1),
                                       cfg=cfg)
            assert resumed.run(
                chunks - int(jax.device_get(
                    resumed.sim.state.chunk))) == "done"
            sim = resumed.sim
            assert int(jax.device_get(sim.state.chunk)) == chunks
            assert sim.health()["health_flags"] == 0, "unclean recovery"
            assert sim.lifecycle["restarts"] >= 1
        lifecycle = dict(sim.lifecycle)

        # ---- checkpoint save/restore wall time per interval ----------
        ck2 = os.path.join(d, "ck2")
        metrics["save_ms_per_ckpt"] = _timed_ms(lambda: sim.save(ck2))
        metrics["restore_ms_per_ckpt"] = _timed_ms(
            lambda: sim.restore(ck2))
        metrics["probe_ms_per_ckpt"] = _timed_ms(
            lambda: sim.probe_health())

    emit(f"runner_save_r{r}_n{n}", metrics["save_ms_per_ckpt"] * 1e3,
         f"restore_ms={metrics['restore_ms_per_ckpt']:.1f}")
    emit(f"runner_chaos_r{r}_n{n}", 0.0,
         f"rollbacks={lifecycle['rollbacks']} "
         f"restarts={lifecycle['restarts']}")
    params = {"num_ranks": r, "n_per_rank": n, "chunks": chunks}
    rep = telemetry.report.make_report(
        "runner", {f"r{r}_n{n}": telemetry.report.case(params, metrics)},
        smoke=smoke,
        mesh={"num_ranks": r, "backend": jax.default_backend()},
        counters=telemetry.report.counters_block(sim.metrics()),
        spans=telemetry.export(),
        lifecycle=lifecycle)
    out = "BENCH_runner_smoke.json" if smoke else "BENCH_runner.json"
    telemetry.report.write(os.path.join(ROOT, out), rep)


if __name__ == "__main__":
    main()
