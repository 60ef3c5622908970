"""Connectivity-update cost: reference jnp lowering vs the fused Pallas
kernels (connectivity_impl + tree_impl + apply_impl), with per-stage
attribution.

Times one full connectivity update (deletion routing + octree build +
phase A + phase B + accept) on a single rank for both lowerings — compile
and steady state reported separately (``_util.measure``) — and counts
materialized HBM bytes:

  reference  ``roofline.materialized_bytes`` of the optimized HLO of the
             whole update — every (Q, F) frontier temporary the restart
             loop materializes is counted trip-aware. NB on CPU XLA
             additionally *serializes* the frontier scatters into
             per-update-element while loops, so the reference count is a
             lowering-specific upper proxy (the metric's documented
             contract: relative comparisons of lowerings, not absolute
             HBM truth);
  fused      the reference total minus the roofline bytes of each
             standalone reference stage the kernels replace (phase B,
             the Morton sort, the synapse-apply/routing composite), plus
             each kernel's analytic streaming traffic. On CPU the kernels
             run in interpret mode, whose HLO inlines the *interpreter*,
             so the TPU custom calls' traffic is computed in closed form
             instead (the same accounting bench_activity uses).

Per-stage sub-metrics make a steady-time or byte anomaly attributable
without re-deriving the decomposition (the n64 interpret-overhead case):

  ``{impl}_sort_*``   the (rel, slot) Morton sort+rank pair feeding the
                      tree build — argsort+searchsorted vs radix kernel;
  ``{impl}_tree_*``   the whole local-tree build (sort + the shared
                      scatter-add/aggregation back half);
  ``{impl}_apply_*``  the synapse-table composite: 2x deletion routing
                      (pre-collective half), 2x drain+compact, 1x accept;
  ``exchange_*``      what still crosses ranks per update (branch-node
                      all-gather, 2x deletion all-to-all, 42B formation
                      requests, dense rate gather) — impl-independent,
                      bytes analytic, time measured over the collectives
                      alone.

Emits CSV and writes a ``repro.telemetry/v1`` report: ``--smoke`` (n=64)
to ``BENCH_connectivity_smoke.json``, otherwise ``BENCH_connectivity.json``
(n per rank in {256, 1024}) — the committed baseline
``benchmarks/check_regression.py`` gates against (the smoke file is
separate so reproducing the CI step locally cannot clobber the baseline).

The committed baseline additionally carries the smoke-scale ``n64`` case
captured under CI's gate environment (4 host devices — the byte model
depends on device count via ``q = num_ranks * cap_requests``, and the
ratio is not scale-free below n=256), so the smoke gate pairs it by exact
name at matched params. Regenerate that case with
``XLA_FLAGS=--xla_force_host_platform_device_count=4 ... --smoke`` and
copy it into the baseline; the n256/n1024 cases come from the plain
single-device run.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp

from benchmarks._util import ROOT, emit, measure
from repro import telemetry
from repro.configs.msp_brain import BrainConfig
from repro.connectome import routing, traverse
from repro.connectome import tree as ctree
from repro.core import engine, morton, spikes
from repro.kernels import ops as kops
from repro.kernels.bh_traverse import traverse_hbm_bytes
from repro.kernels.radix_sort import morton_sort_hbm_bytes
from repro.kernels.synapse_apply import apply_hbm_bytes, route_build_hbm_bytes
from repro.launch import roofline
from repro.sim import Simulator, registry
from repro.sim import phases as sim_phases

FUSED_FIELDS = dict(connectivity_impl="fused", tree_impl="fused",
                    apply_impl="fused")


def make_conn_fn(cfg, mesh):
    """Standalone connectivity update through the facade's PhaseContext +
    registry dispatch."""
    num_ranks = mesh.shape["ranks"]
    shapes = jax.eval_shape(lambda: engine.init_state(cfg, 0, num_ranks))
    specs = engine.state_specs(shapes)

    def body(st):
        ctx = sim_phases.make_context(cfg, jax.lax.axis_index("ranks"),
                                      "ranks", num_ranks)
        return sim_phases.connectivity_phase(st, ctx)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(specs,),
                                 out_specs=specs, check_vma=False))


def phase_b_reference_bytes(cfg, st, num_ranks):
    """Roofline bytes of the standalone jnp phase-B at the update's shapes
    (the part the traversal kernel replaces)."""
    n = cfg.neurons_per_rank
    q = num_ranks * routing.cap_requests(cfg, num_ranks)
    vac = jnp.maximum(st.neurons.de_elements[:n], 0.0)
    tree = ctree.build_local_tree(st.positions[:n], vac, 0, cfg, num_ranks)
    stacked = traverse.stack_levels(tree.counts, tree.centroids, 0)
    kw = dict(seed=cfg.seed, sizes=stacked.sizes, theta=cfg.theta,
              sigma=cfg.sigma, frontier=cfg.frontier_cap,
              n_levels=cfg.local_levels + 1)

    def f(counts, cents, members, npos, vac, x, start, gids, valid):
        return traverse.phase_b_core(counts, cents, members, npos, vac, x,
                                     start, gids, valid, jnp.int32(0),
                                     jnp.int32(0), **kw)

    args = (stacked.counts, stacked.centroids, tree.leaf_members,
            st.positions[:n], vac, jnp.zeros((q, 3), jnp.float32),
            jnp.zeros((q,), jnp.int32), jnp.zeros((q,), jnp.int32),
            jnp.ones((q,), bool))
    hlo = jax.jit(f).lower(*args).compile().as_text()
    return roofline.materialized_bytes(hlo), q, tree, stacked


# ------------------------------------------------------------ stage benches
def make_sort_fns(cfg, num_ranks):
    """The (rel, slot) Morton sort+rank pair at rank 0's geometry —
    'reference' (argsort + searchsorted ``positions_within``) vs the radix
    kernel. Exactly the part ``tree_impl`` swaps."""
    leaf_level, n_leaf, base_cell = ctree._tree_geometry(0, cfg, num_ranks)
    base = base_cell * 8 ** cfg.local_levels

    def reference(pos):
        rel = jnp.clip(morton.morton_encode(pos, leaf_level) - base,
                       0, n_leaf - 1)
        return rel, ctree.positions_within(rel, n_leaf)

    def fused(pos):
        return kops.morton_sort(pos, jnp.int32(base), leaf_level=leaf_level,
                                n_leaf=n_leaf)

    return {"reference": jax.jit(reference), "fused": jax.jit(fused)}


def make_tree_fns(cfg, num_ranks):
    """The whole local-tree build per ``tree_impl`` (sort + shared
    scatter-add/aggregation back half)."""
    return {impl: jax.jit(
        lambda pos, vac, build=registry.resolve("tree", impl):
        build(pos, vac, 0, cfg, num_ranks))
        for impl in ("reference", "fused")}


def make_apply_fns(cfg, num_ranks):
    """The synapse-table composite one update runs per ``apply_impl``:
    deletion routing for both tables (pre-collective half — the exchange
    itself is the ``exchange_*`` sub-metric), both drain+compact passes,
    and the accept pass."""
    n = cfg.neurons_per_rank
    cap = routing.cap_deletions(cfg, False)
    fns = {}
    for impl in ("reference", "fused"):
        ai = registry.resolve("apply", impl)

        def f(out_edges, in_edges, kill_out, kill_in, vac_d, rlid, rsrc,
              rvalid, key, ai=ai, impl=impl):
            gcol = jnp.arange(n, dtype=jnp.int32)[:, None]

            def route(kill, edges):
                fo = jnp.where(kill, edges, -1).reshape(-1)
                fm = jnp.broadcast_to(gcol, kill.shape).reshape(-1)
                if impl == "reference":
                    return routing.route_build_core(
                        fo, fm, n, num_ranks, cap, ctree.positions_within)[0]
                return kops.route_build(fo, fm, n=n, num_ranks=num_ranks,
                                        cap=cap)[0]

            mo = route(kill_out, out_edges).reshape(num_ranks * cap, 2)
            mi = route(kill_in, in_edges).reshape(num_ranks * cap, 2)
            ie = ai.deletion(in_edges, jnp.clip(mo[:, 0], 0, n - 1),
                             mo[:, 1], (mo[:, 0] >= 0) & (mo[:, 0] < n))
            oe = ai.deletion(out_edges, jnp.clip(mi[:, 0], 0, n - 1),
                             mi[:, 1], (mi[:, 0] >= 0) & (mi[:, 0] < n))
            acc, ie = ai.accept(rlid, rsrc, rvalid, vac_d, ie, key)
            return oe, ie, acc

        fns[impl] = jax.jit(f)
    return fns


def apply_stage_inputs(cfg, st, q, seed=7):
    """Representative apply-stage inputs from the live state: rank 0's
    tables, ~10% retraction kill masks, a full formation request batch."""
    n = cfg.neurons_per_rank
    k1, k2, k3, k4 = jax.random.split(jax.random.key(seed), 4)
    oe, ie = st.out_edges[:n], st.in_edges[:n]
    kill_out = (oe >= 0) & (jax.random.uniform(k1, oe.shape) < 0.1)
    kill_in = (ie >= 0) & (jax.random.uniform(k2, ie.shape) < 0.1)
    vac_d = jnp.maximum(st.neurons.de_elements[:n], 0.0)
    rlid = jax.random.randint(k3, (q,), 0, n, jnp.int32)
    rsrc = jax.random.randint(k4, (q,), 0, n, jnp.int32)
    rvalid = jnp.arange(q) % 3 != 0
    return oe, ie, kill_out, kill_in, vac_d, rlid, rsrc, rvalid, \
        jax.random.key(seed)


def make_exchange_fn(cfg, mesh):
    """The update's collectives in isolation, fed from cheap slices and
    broadcasts of the live state (no sorts or scatters, so the measured
    steady time is the exchange itself): the branch-node all-gather, the
    two deletion all-to-alls, and the dense rate-table gather."""
    num_ranks = mesh.shape["ranks"]
    c_per = morton.cells_per_rank(num_ranks)
    cap = routing.cap_deletions(cfg, False)
    shapes = jax.eval_shape(lambda: engine.init_state(cfg, 0, num_ranks))
    specs = engine.state_specs(shapes)
    P = jax.sharding.PartitionSpec

    def body(st):
        bc = jnp.broadcast_to(st.neurons.rate[:1], (c_per,))
        bz = jnp.broadcast_to(st.positions[:1], (c_per, 3))
        top_c = jax.lax.all_gather(bc, "ranks", axis=0, tiled=True)
        top_z = jax.lax.all_gather(bz, "ranks", axis=0, tiled=True)
        buf = jnp.full((num_ranks, cap, 2), -1, jnp.int32) + \
            st.in_edges[0, 0] * 0
        if num_ranks > 1:
            b1 = jax.lax.all_to_all(buf, "ranks", 0, 0, tiled=True)
            b2 = jax.lax.all_to_all(buf, "ranks", 0, 0, tiled=True)
        else:
            b1, b2 = buf, buf
        rates = spikes.exchange_rates(st.neurons.rate, "ranks", num_ranks)
        s = top_c.sum() + top_z.sum() + rates.sum() + \
            (b1.sum() + b2.sum()).astype(jnp.float32)
        return jnp.reshape(s, (1,))

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(specs,),
                                 out_specs=P("ranks"), check_vma=False))


def exchange_hbm_bytes(cfg, num_ranks, q):
    """Analytic bytes one rank sends+receives per update — the residency
    boundary the fused kernels do NOT remove (DESIGN.md §11): branch
    nodes (count f32 + centroid 3xf32 per cell), two (R, cap, 2) i32
    deletion buffers, the 42B formation-and-calculation requests, and the
    dense (R, n) rate-table gather."""
    c_per = morton.cells_per_rank(num_ranks)
    cap = routing.cap_deletions(cfg, False)
    return (num_ranks * c_per * 16 + 2 * num_ranks * cap * 8 + q * 42 +
            num_ranks * cfg.neurons_per_rank * 4)


def roofline_of(fn, *args):
    return roofline.materialized_bytes(
        fn.lower(*args).compile().as_text())


def bench_one(n, mesh):
    base = BrainConfig(neurons_per_rank=n, local_levels=3, frontier_cap=32)
    num_ranks = mesh.shape["ranks"]
    s_max = base.max_synapses
    cap = routing.cap_deletions(base, False)
    q = num_ranks * routing.cap_requests(base, num_ranks)

    # one plasticity round first so the edge tables/rates are representative
    st = Simulator.from_config(base, mesh=mesh).step()
    jax.block_until_ready(st.positions)

    metrics = {}
    for impl in ("reference", "fused"):
        over = FUSED_FIELDS if impl == "fused" else {}
        cfg = dataclasses.replace(base, **over)
        fn = make_conn_fn(cfg, mesh)
        with telemetry.span(f"bench.connectivity.{impl}", n=n):
            timing, _ = measure(fn, st, iters=3)
        metrics[f"{impl}_compile_ms"] = timing.compile_ms
        metrics[f"{impl}_steady_us_per_update"] = timing.steady_us
        if impl == "reference":
            hlo = fn.lower(st).compile().as_text()
            metrics["reference_hbm_bytes_per_update"] = \
                roofline.materialized_bytes(hlo)

    # ---- per-stage attribution (bytes: roofline vs analytic kernel) ------
    pos = st.positions[:n]
    vac = jnp.maximum(st.neurons.de_elements[:n], 0.0)
    sort_fns = make_sort_fns(base, num_ranks)
    tree_fns = make_tree_fns(base, num_ranks)
    apply_fns = make_apply_fns(base, num_ranks)
    apply_args = apply_stage_inputs(base, st, q)
    for impl in ("reference", "fused"):
        t, _ = measure(sort_fns[impl], pos, iters=3)
        metrics[f"{impl}_sort_us_per_update"] = t.steady_us
        t, _ = measure(tree_fns[impl], pos, vac, iters=3)
        metrics[f"{impl}_tree_us_per_update"] = t.steady_us
        t, _ = measure(apply_fns[impl], *apply_args, iters=3)
        metrics[f"{impl}_apply_us_per_update"] = t.steady_us
    exch = make_exchange_fn(base, mesh)
    t, _ = measure(exch, st, iters=3)
    metrics["exchange_us_per_update"] = t.steady_us

    metrics["reference_sort_hbm_bytes"] = \
        roofline_of(sort_fns["reference"], pos)
    metrics["reference_tree_hbm_bytes"] = \
        roofline_of(tree_fns["reference"], pos, vac)
    metrics["reference_apply_hbm_bytes"] = \
        roofline_of(apply_fns["reference"], *apply_args)
    metrics["fused_sort_hbm_bytes"] = morton_sort_hbm_bytes(n)
    # the scatter-add/aggregation back half is shared: fused tree = the
    # reference build with the sort term swapped for the kernel's traffic
    metrics["fused_tree_hbm_bytes"] = \
        metrics["reference_tree_hbm_bytes"] - \
        metrics["reference_sort_hbm_bytes"] + metrics["fused_sort_hbm_bytes"]
    qm = num_ranks * cap
    metrics["fused_apply_hbm_bytes"] = (
        2 * route_build_hbm_bytes(n, s_max, num_ranks, cap) +
        2 * apply_hbm_bytes(n, s_max, qm, 8) +      # deletion drains
        apply_hbm_bytes(n, s_max, 8, q))            # accept pass
    metrics["exchange_hbm_bytes"] = exchange_hbm_bytes(base, num_ranks, q)

    pb_bytes, q, tree, stacked = phase_b_reference_bytes(base, st, num_ranks)
    metrics["reference_phase_b_hbm_bytes"] = pb_bytes
    n_levels, c_max = stacked.counts.shape
    kernel_bytes = traverse_hbm_bytes(
        n_levels, c_max, tree.leaf_members.shape[0],
        tree.leaf_members.shape[1], n, q)
    metrics["fused_phase_b_hbm_bytes"] = kernel_bytes
    # fused total: swap each replaced reference stage for its kernel's
    # analytic traffic (the tree build swaps only its sort half — the
    # aggregation back half is shared and stays in the total)
    metrics["fused_hbm_bytes_per_update"] = max(
        metrics["reference_hbm_bytes_per_update"] - pb_bytes -
        metrics["reference_sort_hbm_bytes"] -
        metrics["reference_apply_hbm_bytes"] + kernel_bytes +
        metrics["fused_sort_hbm_bytes"] + metrics["fused_apply_hbm_bytes"],
        float(kernel_bytes))
    metrics["hbm_bytes_ratio"] = metrics["reference_hbm_bytes_per_update"] / \
        max(metrics["fused_hbm_bytes_per_update"], 1.0)
    assert metrics["hbm_bytes_ratio"] >= 1.0, \
        f"fused must not touch MORE HBM, got {metrics['hbm_bytes_ratio']:.2f}x"
    params = {"n_per_rank": n, "s_max": s_max,
              "num_ranks": num_ranks, "phase_b_queries": q}
    return params, metrics


def main():
    smoke = "--smoke" in sys.argv
    sizes = [64] if smoke else [256, 1024]
    mesh = engine.make_brain_mesh()
    cases = {}
    for n in sizes:
        params, metrics = bench_one(n, mesh)
        cases[f"n{n}"] = telemetry.report.case(params, metrics)
        emit(f"connectivity_reference_n{n}",
             metrics["reference_steady_us_per_update"],
             f"hbm_B/update={metrics['reference_hbm_bytes_per_update']:.0f} "
             f"compile_ms={metrics['reference_compile_ms']:.0f}")
        emit(f"connectivity_fused_n{n}",
             metrics["fused_steady_us_per_update"],
             f"hbm_B/update={metrics['fused_hbm_bytes_per_update']:.0f} "
             f"({metrics['hbm_bytes_ratio']:.1f}x less) "
             f"compile_ms={metrics['fused_compile_ms']:.0f}")
        for stage in ("sort", "tree", "apply"):
            emit(f"connectivity_{stage}_n{n}",
                 metrics[f"fused_{stage}_us_per_update"],
                 f"ref_us={metrics[f'reference_{stage}_us_per_update']:.0f} "
                 f"ref_B={metrics[f'reference_{stage}_hbm_bytes']:.0f} "
                 f"fused_B={metrics[f'fused_{stage}_hbm_bytes']:.0f}")
        emit(f"connectivity_exchange_n{n}",
             metrics["exchange_us_per_update"],
             f"B/update={metrics['exchange_hbm_bytes']:.0f}")
    rep = telemetry.report.make_report(
        "connectivity", cases, smoke=smoke,
        mesh={"num_ranks": mesh.shape["ranks"],
              "backend": jax.default_backend()},
        spans=telemetry.export())
    out = "BENCH_connectivity_smoke.json" if smoke \
        else "BENCH_connectivity.json"
    telemetry.report.write(os.path.join(ROOT, out), rep)


if __name__ == "__main__":
    main()
