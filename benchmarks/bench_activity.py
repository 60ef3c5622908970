"""Activity-phase cost: reference jnp scan vs fused Pallas megakernel.

Times one rate window (Delta electrical steps, no connectivity update) of
the engine's activity phase on a single rank — compile and steady state
reported separately (``_util.measure``) — and counts the HBM bytes one
*step* touches:

  reference  ``roofline.materialized_bytes`` of the optimized HLO of the
             activity window / Delta — every per-step ``(n, s_max)``
             temporary the scan materializes is counted trip-aware;
  fused      analytic streaming traffic of the single ``pallas_call``
             (``activity_fused.window_hbm_bytes``) / Delta. On CPU the
             kernel runs in interpret mode, whose HLO inlines the
             *interpreter*, so the TPU custom call's traffic (operands in
             once, state out once, zero per-step temporaries) is computed
             in closed form instead.

Emits CSV and writes a ``repro.telemetry/v1`` report: ``--smoke`` (n=64)
to ``BENCH_activity_smoke.json``, otherwise ``BENCH_activity.json`` —
the committed baseline ``benchmarks/check_regression.py`` gates against
(reproducing the CI smoke step locally cannot clobber the baseline).
"""
import dataclasses
import os
import sys

import jax

from benchmarks._util import ROOT, emit, measure
from repro import telemetry
from repro.configs.msp_brain import BrainConfig
from repro.core import engine
from repro.kernels.activity_fused import window_hbm_bytes
from repro.launch import roofline
from repro.sim import Simulator
from repro.sim import phases as sim_phases


def make_activity_fn(cfg, mesh):
    """Standalone activity-phase step (no connectivity update) through the
    facade's PhaseContext + registry dispatch."""
    num_ranks = mesh.shape["ranks"]
    shapes = jax.eval_shape(lambda: engine.init_state(cfg, 0, num_ranks))
    specs = engine.state_specs(shapes)

    def body(st):
        ctx = sim_phases.make_context(cfg, jax.lax.axis_index("ranks"),
                                      "ranks", num_ranks)
        return sim_phases.activity_phase(st, ctx)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(specs,),
                                 out_specs=specs, check_vma=False))


def main():
    smoke = "--smoke" in sys.argv
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    n = int(args[0]) if args else (64 if smoke else 256)
    base = BrainConfig(neurons_per_rank=n, local_levels=3, frontier_cap=32)
    mesh = engine.make_brain_mesh()
    num_ranks = mesh.shape["ranks"]
    delta = base.rate_period

    # one plasticity round first so the edge tables/rates are representative
    st = Simulator.from_config(base, mesh=mesh).step()
    jax.block_until_ready(st.positions)

    metrics = {}
    timings = {}
    for impl in ("reference", "fused"):
        cfg = dataclasses.replace(base, activity_impl=impl)
        act = make_activity_fn(cfg, mesh)
        with telemetry.span(f"bench.activity.{impl}", n=n):
            timing, _ = measure(act, st, iters=3)
        timings[impl] = timing
        metrics[f"{impl}_compile_ms"] = timing.compile_ms
        metrics[f"{impl}_steady_us_per_step"] = timing.steady_us / delta
        if impl == "reference":
            hlo = act.lower(st).compile().as_text()
            metrics["reference_hbm_bytes_per_step"] = \
                roofline.materialized_bytes(hlo) / delta
    metrics["fused_hbm_bytes_per_step"] = \
        window_hbm_bytes(n, base.max_synapses, num_ranks,
                         num_steps=delta) / delta
    ratio = metrics["reference_hbm_bytes_per_step"] / \
        max(metrics["fused_hbm_bytes_per_step"], 1.0)
    metrics["hbm_bytes_ratio"] = ratio
    assert ratio >= 3.0, f"fused HBM traffic must drop >=3x, got {ratio:.2f}"

    params = {"n_per_rank": n, "s_max": base.max_synapses,
              "num_ranks": num_ranks, "delta": delta}
    rep = telemetry.report.make_report(
        "activity", {f"n{n}": telemetry.report.case(params, metrics)},
        smoke=smoke, mesh={"num_ranks": num_ranks,
                           "backend": jax.default_backend()},
        spans=telemetry.export())
    out = "BENCH_activity_smoke.json" if smoke else "BENCH_activity.json"
    telemetry.report.write(os.path.join(ROOT, out), rep)
    emit(f"activity_reference_n{n}", metrics["reference_steady_us_per_step"],
         f"hbm_B/step={metrics['reference_hbm_bytes_per_step']:.0f} "
         f"compile_ms={metrics['reference_compile_ms']:.0f}")
    emit(f"activity_fused_n{n}", metrics["fused_steady_us_per_step"],
         f"hbm_B/step={metrics['fused_hbm_bytes_per_step']:.0f} "
         f"({ratio:.0f}x less) compile_ms={metrics['fused_compile_ms']:.0f}")


if __name__ == "__main__":
    main()
