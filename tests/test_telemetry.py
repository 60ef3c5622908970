"""repro.telemetry (DESIGN.md §9): Metrics pytree mechanics, the
bit-identity contract across variant lowerings (single-rank here, 4-rank
mesh via subprocess), span nesting, report schema round-trip /
normalization of the pre-schema layouts, and the regression gate's rule
taxonomy on synthetic baselines."""
import dataclasses
import glob
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs.msp_brain import BrainConfig
from repro.sim import Simulator
from repro.telemetry import metrics as tm

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

SMALL = BrainConfig(neurons_per_rank=32, local_levels=3, frontier_cap=32,
                    max_synapses=8, rate_period=10, requests_cap_factor=100,
                    subs_cap_factor=100)

# counters whose value depends on the exchange *layout* (dense ships the
# whole table, sparse ships subscriptions + requests) — everything else
# is physics and must be bit-identical across every variant axis
EXCHANGE_LAYOUT_KEYS = ("rates_sent", "subscription_requests",
                        "subscription_overflow", "request_overflow")
PHYSICS_KEYS = tuple(k for k in tm.COUNTER_KEYS
                     if k not in EXCHANGE_LAYOUT_KEYS)
# phase B's work and waste: query rows run, rows holding a request,
# restart iterations under the cap over all rows, live rows whose frontier
# overflowed, row-restarts the lowering computed
PHASE_B_KEYS = ("bh_query_slots", "bh_queries_live", "bh_rounds_run",
                "bh_frontier_overflow", "bh_rows_run")
# the one counter that tells the traversal lowerings apart: the fused
# kernel runs every row through the restart cap, the reference only the
# rows still searching, in whole row blocks
LOWERING_KEYS = ("bh_rows_run",)


# ---------------------------------------------------------------- metrics
def test_init_metrics_shapes_and_specs():
    m = tm.init_metrics(history=16)
    assert set(m.counters) == set(tm.COUNTER_KEYS)
    for k in tm.COUNTER_KEYS:
        assert m.counters[k].shape == (1,)
        assert m.per_chunk[k].shape == (1, 16)
    for k, b in tm.HIST_BUCKETS.items():
        assert m.hists[k].shape == (1, b)
    specs = tm.metrics_specs(m)
    # the spec tree matches the metrics tree leaf-for-leaf
    jax.tree.map(lambda s, l: None, specs, m,
                 is_leaf=lambda x: x is None)


def test_metrics_dict_compat_reads():
    m = tm.init_metrics().count("synapses_formed", 3.0)
    assert "synapses_formed" in m
    assert float(m["synapses_formed"].sum()) == 3.0
    assert set(m.keys()) == set(tm.COUNTER_KEYS)
    assert dict(m.items())["synapses_formed"] is m.counters["synapses_formed"]


def test_count_is_immutable_and_accumulates():
    m0 = tm.init_metrics()
    m1 = m0.count("bh_requests", jnp.float32(2.0)).count("bh_requests", 5)
    assert float(m0["bh_requests"][0]) == 0.0
    assert float(m1["bh_requests"][0]) == 7.0


def test_observe_scatter_adds_with_weights():
    m = tm.init_metrics()
    m = m.observe("frontier_depth", jnp.array([0, 0, 3, 7]))
    m = m.observe("frontier_depth", jnp.array([3]), jnp.array([0.0]))
    h = np.asarray(m.hists["frontier_depth"])[0]
    np.testing.assert_array_equal(h, [2, 0, 0, 1, 0, 0, 0, 1])


def test_record_chunk_ring_slots_and_deltas():
    m = tm.init_metrics(history=4)
    start = m.counters
    m = m.count("synapses_formed", 5.0)
    m = m.record_chunk(start, jnp.int32(0))
    start2 = m.counters
    m = m.count("synapses_formed", 2.0)
    m = m.record_chunk(start2, jnp.int32(5))    # slot 5 % 4 == 1
    ring = np.asarray(m.per_chunk["synapses_formed"])[0]
    np.testing.assert_array_equal(ring, [5.0, 2.0, 0.0, 0.0])


def test_metrics_pytree_roundtrip_with_stable_keys():
    m = tm.init_metrics(history=8).count("rates_sent", 1.0)
    leaves, treedef = jax.tree.flatten(m)
    m2 = jax.tree.unflatten(treedef, leaves)
    assert isinstance(m2, tm.Metrics)
    assert float(m2["rates_sent"][0]) == 1.0
    # key-path flatten exposes DictKey(.key) paths — the checkpoint
    # manager's stable leaf-naming contract
    kl, _ = jax.tree_util.tree_flatten_with_path(m)
    names = {"/".join(str(k.key) for k in path) for path, _ in kl}
    assert "counters/rates_sent" in names
    assert "hists/frontier_depth" in names
    for key in PHASE_B_KEYS:
        assert f"counters/{key}" in names
        assert f"per_chunk/{key}" in names


# ---------------------------------------------------------------- identity
def _counters(sim):
    return {k: np.asarray(v) for k, v in sim.metrics().counters.items()}


def _run(cfg):
    sim = Simulator(cfg)
    sim.run(2)
    return sim


def test_counters_bit_identical_reference_vs_fused_activity():
    a = _run(dataclasses.replace(SMALL, activity_impl="reference"))
    b = _run(dataclasses.replace(SMALL, activity_impl="fused"))
    ca, cb = _counters(a), _counters(b)
    for k in tm.COUNTER_KEYS:
        np.testing.assert_array_equal(ca[k], cb[k], err_msg=k)
    # the whole tree — per-chunk rings and histograms included
    for tree in ("per_chunk", "hists"):
        ta = getattr(a.metrics(), tree)
        tb = getattr(b.metrics(), tree)
        for k in ta:
            np.testing.assert_array_equal(np.asarray(ta[k]),
                                          np.asarray(tb[k]),
                                          err_msg=f"{tree}/{k}")
    assert ca["activity_spikes"].sum() > 0
    # each rank counts its own steps: 2 chunks x rate_period per rank,
    # regardless of how many host devices the suite runs under
    np.testing.assert_array_equal(
        ca["activity_steps"],
        np.full_like(ca["activity_steps"], 2 * SMALL.rate_period))


def test_counters_bit_identical_reference_vs_fused_connectivity():
    a = _run(dataclasses.replace(SMALL, connectivity_impl="reference"))
    b = _run(dataclasses.replace(SMALL, connectivity_impl="fused"))
    ca, cb = _counters(a), _counters(b)
    for k in tm.COUNTER_KEYS:
        if k not in LOWERING_KEYS:
            np.testing.assert_array_equal(ca[k], cb[k], err_msg=k)
    ha = np.asarray(a.metrics().hists["frontier_depth"])
    hb = np.asarray(b.metrics().hists["frontier_depth"])
    np.testing.assert_array_equal(ha, hb)
    assert ca["bh_restarts"].sum() > 0, "traversal depth never recorded"
    # phase B's work and waste, per chunk too
    for k in PHASE_B_KEYS:
        if k not in LOWERING_KEYS:
            np.testing.assert_array_equal(
                np.asarray(a.metrics().per_chunk[k]),
                np.asarray(b.metrics().per_chunk[k]), err_msg=k)
    assert ca["bh_queries_live"].sum() > 0
    assert ca["bh_frontier_overflow"].sum() <= ca["bh_queries_live"].sum()
    assert ca["bh_restarts"].sum() <= ca["bh_rounds_run"].sum()
    # rows computed: every row through the cap in the kernel; the searching
    # rows, in whole blocks, in the reference
    np.testing.assert_array_equal(cb["bh_rows_run"], cb["bh_rounds_run"])
    assert ca["bh_restarts"].sum() <= ca["bh_rows_run"].sum() \
        <= ca["bh_rounds_run"].sum()


def _overflow_tree(frontier):
    """One neuron at the centre of each of the 64 deepest cells of a
    two-level subtree: every cell holds a neuron, and at theta 0.3 every
    child of the root fails the acceptance criterion, so each query wants
    all 64 deepest cells in its frontier."""
    from repro.connectome import tree as ctree
    cfg = BrainConfig(neurons_per_rank=64, local_levels=2,
                      frontier_cap=frontier, max_synapses=8, theta=0.3)
    g = (np.arange(4) + 0.5) / 4
    pos = jnp.asarray(np.stack(np.meshgrid(g, g, g, indexing="ij"),
                               -1).reshape(-1, 3), jnp.float32)
    vac = jnp.ones((64,), jnp.float32)
    return cfg, ctree.build_local_tree(pos, vac, 0, cfg, num_ranks=1), pos, \
        vac


@pytest.mark.parametrize("block", [None, 16, 64])
@pytest.mark.parametrize("frontier,overflows", [(8, True), (64, False)])
def test_frontier_overflow_counter_on_hand_built_tree(frontier, overflows,
                                                      block):
    """A frontier of 8 cannot hold the root's expanded children: every live
    query overflows. A frontier of 64 holds the whole subtree: none does.
    Rows without a request are never counted. ``block`` None runs the
    plain ``phase_b_core`` (the fused kernel's body) over every row; a
    block size runs the reference's packed driver, which computes the
    searching rows in whole blocks only."""
    from repro.connectome import traverse
    cfg, tree, pos, vac = _overflow_tree(frontier)
    stacked = traverse.stack_levels(tree.counts, tree.centroids, 0)
    live = jnp.arange(64) % 4 != 0
    restarts = traverse.phase_b_levels(cfg)
    kw = dict(seed=cfg.seed, sizes=stacked.sizes, theta=cfg.theta,
              sigma=cfg.sigma, frontier=cfg.frontier_cap, n_levels=restarts)
    args = (pos, vac, pos, jnp.zeros((64,), jnp.int32),
            jnp.arange(64, dtype=jnp.int32), live, jnp.int32(1),
            jnp.int32(0))
    if block is None:
        _, _, depth, overflow = traverse.phase_b_core(
            stacked.counts, stacked.centroids, tree.leaf_members, *args,
            **kw)
        rows_run = 64 * restarts
    else:
        _, _, depth, overflow, rows_run = traverse.phase_b_reference(
            stacked, tree, *args, kw, block=block)
    m = tm.Recorder(n=64).traversal(tm.init_metrics(), depth, live, overflow,
                                    rows_run, restarts)
    counted = {k: float(m.counters[k][0]) for k in PHASE_B_KEYS}
    assert counted["bh_queries_live"] == 48
    assert counted["bh_query_slots"] == 64
    assert counted["bh_rounds_run"] == 64 * 3
    assert counted["bh_frontier_overflow"] == (48 if overflows else 0)
    if block is not None:
        assert counted["bh_rows_run"] % block == 0
        assert float(m.counters["bh_restarts"][0]) \
            <= counted["bh_rows_run"] < counted["bh_rounds_run"]


def test_phase_b_slots_and_live_rows_per_chunk():
    """Per chunk, phase B runs R * cap query rows (the new algorithm's
    receive buffer) and counts as live exactly the requests it received
    (all of them: nothing overflows at this cap)."""
    from repro.connectome import routing, traverse
    sim = _run(SMALL)
    r = sim.num_ranks
    ring = {k: np.asarray(v).sum(axis=0)[:2]
            for k, v in sim.metrics().per_chunk.items()}
    cap = routing.cap_requests(SMALL, r)
    np.testing.assert_array_equal(ring["bh_query_slots"], [r * r * cap] * 2)
    np.testing.assert_array_equal(ring["bh_rounds_run"],
                                  [r * r * cap
                                   * traverse.phase_b_levels(SMALL)] * 2)
    # the reference computes whole blocks (one block of all R * cap rows
    # at this size) and never a row past the cap
    assert not (ring["bh_rows_run"] % (r * cap)).any()
    assert (ring["bh_restarts"] <= ring["bh_rows_run"]).all()
    assert (ring["bh_rows_run"] <= ring["bh_rounds_run"]).all()
    assert not ring["request_overflow"].any()
    np.testing.assert_array_equal(ring["bh_queries_live"],
                                  ring["formation_requests"])
    assert ring["bh_queries_live"].sum() > 0


def test_last_chunk_counters_reads_the_ring_of_the_latest_state(
        monkeypatch):
    monkeypatch.setattr(tm, "_latest", None)
    assert telemetry.last_chunk_counters(1) is None
    sim = Simulator(SMALL)
    sim.run(2)
    before = {k: np.asarray(v).sum() for k, v in sim.stats(
        reduce=False).items()}
    sim.run(1)
    sim.step()
    after = sim.metrics()
    got = telemetry.last_chunk_counters(2)
    hist = after.per_chunk["bh_queries_live"].shape[-1]
    for k in tm.COUNTER_KEYS:
        ring = np.asarray(after.per_chunk[k]).sum(axis=0)
        np.testing.assert_array_equal(got[k], ring[[2 % hist, 3 % hist]],
                                      err_msg=k)
        np.testing.assert_allclose(
            got[k].sum(), np.asarray(after.counters[k]).sum() - before[k],
            err_msg=k)
    with pytest.raises(ValueError):
        telemetry.last_chunk_counters(5)


def test_physics_counters_identical_dense_vs_sparse():
    a = _run(dataclasses.replace(SMALL, rate_exchange="dense"))
    b = _run(dataclasses.replace(SMALL, rate_exchange="sparse"))
    ca, cb = _counters(a), _counters(b)
    for k in PHYSICS_KEYS:
        np.testing.assert_array_equal(ca[k], cb[k], err_msg=k)
    # layout-dependent histogram: only the sparse run populates occupancy
    assert float(np.asarray(a.metrics().hists["subs_occupancy"]).sum()) == 0


def test_per_chunk_rings_sum_to_counters():
    sim = _run(SMALL)
    m = sim.metrics()
    for k in tm.COUNTER_KEYS:
        total = float(np.asarray(m.counters[k]).sum())
        ring = float(np.asarray(m.per_chunk[k]).sum())
        np.testing.assert_allclose(ring, total, err_msg=k)


def test_counters_bit_identical_on_four_rank_mesh():
    """The full contract on a real mesh: physics counters identical
    across activity lowerings AND exchange layouts, per-rank resolution
    preserved (4 distinct per-rank entries, no premature sum)."""
    code = """
        import dataclasses
        import numpy as np
        from repro.configs.msp_brain import BrainConfig
        from repro.sim import Simulator
        from repro.telemetry import metrics as tm
        EXCH = ("rates_sent", "subscription_requests",
                "subscription_overflow", "request_overflow")
        base = BrainConfig(neurons_per_rank=32, local_levels=3,
                           frontier_cap=32, max_synapses=8, rate_period=10,
                           requests_cap_factor=1000, subs_cap_factor=1000)
        runs = {}
        for impl in ("reference", "fused"):
            for rex in ("dense", "sparse"):
                cfg = dataclasses.replace(base, activity_impl=impl,
                                          rate_exchange=rex)
                sim = Simulator(cfg)
                sim.run(2)
                runs[(impl, rex)] = sim
        ref = runs[("reference", "dense")]
        per_rank = ref.stats(reduce=False)
        assert per_rank["synapses_formed"].shape == (4,), \\
            per_rank["synapses_formed"].shape
        assert float(per_rank["synapses_formed"].sum()) > 0
        base_c = {k: np.asarray(v) for k, v in ref.metrics().counters.items()}
        for key, sim in runs.items():
            c = {k: np.asarray(v) for k, v in sim.metrics().counters.items()}
            for name in tm.COUNTER_KEYS:
                if name in EXCH and key[1] != "dense":
                    continue
                assert np.array_equal(base_c[name], c[name]), (key, name)
        # sparse ships strictly fewer rate records than the dense table
        dense_sent = float(base_c["rates_sent"].sum())
        sparse_sent = float(np.asarray(
            runs[("fused", "sparse")].metrics()["rates_sent"]).sum())
        assert 0 < sparse_sent < dense_sent, (dense_sent, sparse_sent)
        print("MESH-IDENTICAL", dense_sent / sparse_sent)
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=560,
                          env=env)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "MESH-IDENTICAL" in proc.stdout


# ---------------------------------------------------------------- spans
def test_span_nesting_depth_and_parent():
    telemetry.clear()
    with telemetry.span("outer", tag=1):
        with telemetry.span("inner"):
            pass
    inner, outer = telemetry.spans("inner")[-1], telemetry.spans("outer")[-1]
    assert inner.depth == 1 and inner.parent == "outer"
    assert outer.depth == 0 and outer.parent is None
    assert outer.attrs == {"tag": 1}
    assert outer.duration_ms >= inner.duration_ms >= 0.0
    # export() is JSON-shaped
    rec = [s for s in telemetry.export() if s["name"] == "inner"][-1]
    assert rec["parent"] == "outer" and rec["depth"] == 1


def test_simulator_records_spans():
    telemetry.clear()
    sim = Simulator(SMALL)
    sim.run(1)
    names = [s.name for s in telemetry.spans()]
    for expected in ("sim.construct", "sim.init", "sim.run"):
        assert expected in names, names
    run_span = telemetry.spans("sim.run")[-1]
    assert run_span.attrs.get("chunks") == 1


def test_span_times_on_the_profilers_clock():
    before = time.time_ns()
    with telemetry.span("clock") as rec:
        inside = time.time_ns()
    after = time.time_ns()
    assert before <= rec.start_ns <= inside <= rec.end_ns <= after
    assert telemetry.export()[-1]["start_ns"] == rec.start_ns


def test_run_opens_step_markers_numbered_by_the_host_chunk_count(
        tmp_path):
    """Each ``run`` is a StepTraceAnnotation in a captured trace, numbered
    by the chunks run before it, and its host event starts within 1 ms of
    the span's in-memory start."""
    from jax.profiler import ProfileData
    telemetry.clear()
    sim = Simulator(SMALL)
    sim.run(2)                       # compiled outside the capture
    sim.run(1)
    jax.profiler.start_trace(str(tmp_path))
    sim.run(1)
    sim.run(1)
    jax.profiler.stop_trace()
    spans = telemetry.spans("sim.run")
    assert [s.step_num for s in spans] == [0, 2, 3, 4]
    assert sim.host_chunk == 5 == int(sim.state.chunk)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path[-1])
    t0 = [int(dict(p.stats)["profile_start_time"]) for p in pd.planes
          if "profile_start_time" in dict(p.stats)][0]
    marks = {int(dict(ev.stats)["step_num"]): t0 + ev.start_ns
             for p in pd.planes for line in p.lines for ev in line.events
             if ev.name == "sim.run" and "step_num" in dict(ev.stats)}
    assert sorted(marks) == [3, 4]
    for s in spans[-2:]:
        assert abs(marks[s.step_num] - s.start_ns) < 1e6


def test_profile_none_is_noop():
    with telemetry.profile(None):
        pass                                    # must not touch the profiler
    telemetry.clear()


# ---------------------------------------------------------------- report
def test_report_roundtrip_v1(tmp_path):
    m = tm.init_metrics().count("rates_sent", 4.0)
    rep = telemetry.report.make_report(
        "activity", {"n32": telemetry.report.case(
            {"n_per_rank": 32, "num_ranks": 1},
            {"fused_compile_ms": 10.0, "hbm_bytes_ratio": 25.0})},
        smoke=True, mesh={"num_ranks": 1, "backend": "cpu"},
        counters=telemetry.report.counters_block(m),
        histograms=telemetry.report.histograms_block(m),
        spans=telemetry.export())
    path = str(tmp_path / "r.json")
    telemetry.report.write(path, rep)
    back = telemetry.report.load(path)
    assert back == rep
    norm = telemetry.report.normalize(back)
    assert norm["bench"] == "activity" and norm["smoke"] is True
    assert norm["cases"]["n32"]["metrics"]["hbm_bytes_ratio"] == 25.0
    assert back["counters"]["total"]["rates_sent"] == 4.0
    assert back["counters"]["per_rank"]["rates_sent"] == [4.0]


def test_roofline_block_from_compiled_hlo():
    """The analytic third source: roofline_block parses a real compiled
    module into the schema's JSON shape."""
    hlo = jax.jit(lambda x: jnp.dot(x, x)).lower(
        jnp.ones((8, 8), jnp.float32)).compile().as_text()
    blk = telemetry.report.roofline_block(hlo, 1)
    assert blk["dot_flops"] >= 2 * 8 * 8 * 8
    assert blk["materialized_hbm_bytes"] > 0
    assert blk["terms"]["dominant"] in ("compute", "memory", "collective")
    import json
    json.dumps(blk)                             # JSON-serializable


def test_normalize_old_flat_single_case():
    old = {"n_per_rank": 256, "num_ranks": 1, "smoke": False,
           "fused_us_per_step": 100.0, "hbm_bytes_ratio": 25.4}
    norm = telemetry.report.normalize(old, bench="activity")
    assert list(norm["cases"]) == ["n256"]
    c = norm["cases"]["n256"]
    assert c["params"]["n_per_rank"] == 256
    assert c["metrics"]["hbm_bytes_ratio"] == 25.4
    assert "n_per_rank" not in c["metrics"]


def test_normalize_old_multi_case_layout():
    old = {"smoke": False,
           "n256": {"n_per_rank": 256, "hbm_bytes_ratio": 49.6},
           "n1024": {"n_per_rank": 1024, "hbm_bytes_ratio": 49.9}}
    norm = telemetry.report.normalize(old, bench="connectivity")
    assert set(norm["cases"]) == {"n256", "n1024"}
    assert norm["cases"]["n1024"]["metrics"]["hbm_bytes_ratio"] == 49.9


def test_committed_baselines_normalize():
    """Every committed BENCH_*.json stays readable by the gate."""
    root = os.path.join(os.path.dirname(__file__), "..")
    found = 0
    for fam, fname in (("activity", "BENCH_activity.json"),
                       ("connectivity", "BENCH_connectivity.json"),
                       ("spikes", "BENCH_spikes.json"),
                       ("fig11", "BENCH_fig11.json")):
        path = os.path.join(root, fname)
        if not os.path.exists(path):
            continue
        found += 1
        norm = telemetry.report.normalize(telemetry.report.load(path),
                                          bench=fam)
        assert norm["cases"], fname
        for case in norm["cases"].values():
            assert "params" in case and "metrics" in case
            assert case["metrics"], fname
    assert found >= 2, "no committed baselines found at the repo root"


# ---------------------------------------------------------------- gate
def _gate():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    try:
        from benchmarks import check_regression
    finally:
        sys.path.pop(0)
    return check_regression


def _bench_util():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    try:
        from benchmarks import _util
    finally:
        sys.path.pop(0)
    return _util


def test_bench_num_ranks_must_match_requested_devices(monkeypatch):
    """A bench asked for R ranks through the host-device flag runs on
    exactly R devices or refuses (the flag makes CPU devices only)."""
    util = _bench_util()
    r = len(jax.devices())
    monkeypatch.setenv("XLA_FLAGS",
                       f"--xla_force_host_platform_device_count={r}")
    assert util.num_ranks() == r
    monkeypatch.setenv("XLA_FLAGS",
                       f"--xla_force_host_platform_device_count={r + 3}")
    with pytest.raises(RuntimeError, match=f"asked for {r + 3} ranks"):
        util.num_ranks()


def test_bench_run_sub_fails_when_child_fails():
    """No ERROR row in a table that exits 0: the parent fails too."""
    util = _bench_util()
    with pytest.raises(SystemExit, match="no_such_bench failed"):
        util.run_sub("benchmarks.no_such_bench", 1, timeout=120)


def _report(cases):
    return {"bench": "x", "smoke": False, "cases": cases}


def test_gate_identical_reports_pass():
    cr = _gate()
    cases = {"n64": {"params": {"n_per_rank": 64, "num_ranks": 1},
                     "metrics": {"hbm_bytes_ratio": 20.0,
                                 "fused_compile_ms": 100.0}}}
    findings = cr.compare("x", _report(cases), _report(cases))
    assert findings and all(f.ok for f in findings)


def test_gate_fails_on_perturbed_ratio():
    """The demonstrable-failure acceptance check: halving a paper-claim
    ratio beyond the 50% slack is flagged as a regression."""
    cr = _gate()
    base = {"n256": {"params": {"n_per_rank": 256, "num_ranks": 1},
                     "metrics": {"hbm_bytes_ratio": 25.0}}}
    cand = {"n64": {"params": {"n_per_rank": 64, "num_ranks": 1},
                    "metrics": {"hbm_bytes_ratio": 10.0}}}
    findings = cr.compare("activity", _report(base), _report(cand))
    bad = [f for f in findings if not f.ok]
    assert len(bad) == 1 and bad[0].metric == "hbm_bytes_ratio"
    # within slack: 25.0 -> 13.0 is fine (tol 0.5)
    cand["n64"]["metrics"]["hbm_bytes_ratio"] = 13.0
    findings = cr.compare("activity", _report(base), _report(cand))
    assert all(f.ok for f in findings)


def test_gate_time_rules_need_matching_params():
    cr = _gate()
    base = {"n256": {"params": {"n_per_rank": 256, "num_ranks": 1},
                     "metrics": {"fused_compile_ms": 100.0,
                                 "fused_steady_us_per_step": 50.0}}}
    # smoke at a smaller size: time metrics must NOT be compared
    small = {"n64": {"params": {"n_per_rank": 64, "num_ranks": 1},
                     "metrics": {"fused_compile_ms": 900.0,
                                 "fused_steady_us_per_step": 900.0}}}
    assert cr.compare("activity", _report(base), _report(small)) == []
    # same shape params: a 4x compile blowup exceeds the 2.0 slack
    matched = {"n256": {"params": {"n_per_rank": 256, "num_ranks": 1},
                        "metrics": {"fused_compile_ms": 400.0,
                                    "fused_steady_us_per_step": 60.0}}}
    findings = cr.compare("activity", _report(base), _report(matched))
    verdict = {f.metric: f.ok for f in findings}
    assert verdict == {"fused_compile_ms": False,
                       "fused_steady_us_per_step": True}


def test_gate_byte_counters_are_tight():
    cr = _gate()
    base = {"r4": {"params": {"n_per_rank": 64, "num_ranks": 4},
                   "metrics": {"sparse_rate_bytes_per_delta": 1000.0}}}
    cand = {"r4": {"params": {"n_per_rank": 64, "num_ranks": 4},
                   "metrics": {"sparse_rate_bytes_per_delta": 1500.0}}}
    findings = cr.compare("spikes", _report(base), _report(cand))
    assert [f.ok for f in findings] == [False]


def test_gate_pairs_with_smallest_n_baseline():
    cr = _gate()
    base = {"n1024": {"params": {"n_per_rank": 1024}, "metrics": {}},
            "n256": {"params": {"n_per_rank": 256},
                     "metrics": {"hbm_bytes_ratio": 49.6}}}
    cand = {"n64": {"params": {"n_per_rank": 64},
                    "metrics": {"hbm_bytes_ratio": 48.0}}}
    findings = cr.compare("connectivity", _report(base), _report(cand))
    assert len(findings) == 1
    assert findings[0].case == "n256->n64" and findings[0].ok


def test_gate_unknown_metrics_are_informational():
    cr = _gate()
    cases_b = {"n64": {"params": {"n_per_rank": 64},
                       "metrics": {"subs_per_rank_mean": 10.0}}}
    cases_c = {"n64": {"params": {"n_per_rank": 64},
                       "metrics": {"subs_per_rank_mean": 99.0}}}
    assert cr.compare("spikes", _report(cases_b), _report(cases_c)) == []
