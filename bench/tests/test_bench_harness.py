"""CPU tests of the benchmark harness: data found by name, the trace
reduction, the reference's primitives, and the refusal without a chip.

    PYTHONPATH=src python -m pytest -q bench/tests
"""
from __future__ import annotations

import gzip
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import cells, generate, reference  # noqa: E402
from bench import trace as btrace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def copy_benchmark(dst):
    """A checkout holding only BENCHMARK.json and bench/."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return str(dst)


# ------------------------------------------------------------ found by name
@pytest.mark.parametrize("workload", [w["name"] for w in
                                      cells.benchmark()["workloads"]])
def test_every_cell_loads_with_its_files(workload):
    cell = cells.load_cell(workload)
    brain = cell.brain_config()
    assert brain["neurons_per_rank"] > 0 and "seed" in brain
    assert set(cell.limits) >= {"elements_gap", "unpaired_edges",
                                "over_capacity", "request_overflow"}
    assert {m["name"] for m in cell.end_to_end} == {"chunk_ms", "setup_s"}
    assert len(cell.per_layer) == 6


@pytest.mark.parametrize("metric", [m["name"] for m in
                                    cells.benchmark()["per_layer"]])
def test_every_metric_has_a_reader_that_reads_nothing_without_a_trace(
        metric):
    read = cells.metric_reader(metric)
    assert read(types.SimpleNamespace(trace=None, chunks=1,
                                      compile_s=0.0)) is None


def test_benchmark_names_its_configurations_files():
    spec = cells.benchmark()
    for c in spec["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"]
        assert c["file"].startswith("bench/configs/")


def test_new_configuration_and_metric_are_found_without_edits(tmp_path):
    root = copy_benchmark(tmp_path)
    cfg = json.load(open(os.path.join(ROOT, "bench/configs/"
                                      "msp_weak_65k.json")))
    cfg["brain_config"]["neurons_per_rank"] = 4096
    json.dump(cfg, open(os.path.join(root, "bench/configs/dummy_4k.json"),
                        "w"))
    with open(os.path.join(root, "bench/metrics/dummy.per_chunk.py"),
              "w") as f:
        f.write("def read(run):\n    return 2.0 * run.chunks\n")
    shutil.copy(os.path.join(root, "bench/limits/msp65k.grow.json"),
                os.path.join(root, "bench/limits/dummy4k.grow.json"))
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({"name": "dummy_4k", "source": "test",
                            "file": "bench/configs/dummy_4k.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "dummy4k.grow", "config": "dummy_4k",
                              "traffic": "grow", "chips": 1, "why": "test"})
    spec["per_layer"].append({
        "name": "dummy.per_chunk", "unit": "1", "better": "lower",
        "source": "host_clock", "layer": "device", "moves": "chunk_ms"})
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))

    cell = cells.load_cell("dummy4k.grow", root)
    assert cell.brain_config()["neurons_per_rank"] == 4096
    assert "dummy.per_chunk" in [m["name"] for m in cell.per_layer]
    read = cells.metric_reader("dummy.per_chunk", root)
    assert read(types.SimpleNamespace(chunks=3)) == 6.0


def test_peaks_table_refuses_an_unknown_device():
    assert cells.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cells.peaks("cpu")


# ------------------------------------------------------------ refusals
def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _run(["--workload", "msp65k.grow", "--seed", "2147483659",
              "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    root = copy_benchmark(tmp_path)
    p = _run(["--workload", "msp65k.grow", "--seed", "1", "--seconds", "1",
              "--trace", "0"], root)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# ------------------------------------------------------------ generator
def test_same_seed_same_inputs_other_seed_other_inputs():
    cell = cells.load_cell("msp65k.grow")
    brain = dict(cell.brain_config(), neurons_per_rank=2048)
    a = generate.initial(cell.config, cell.traffic, brain, 1, 3_000_000_000)
    b = generate.initial(cell.config, cell.traffic, brain, 1, 3_000_000_000)
    c = generate.initial(cell.config, cell.traffic, brain, 1, 3_000_000_001)
    np.testing.assert_array_equal(a["positions"], b["positions"])
    assert not np.array_equal(a["positions"], c["positions"])
    assert a["edges"].shape == (0, 2)


# ------------------------------------------------------------ reference
def test_reference_threefry_matches_jax():
    from jax._src import prng
    k = np.array([7, 0x6E6F6973], np.uint32)
    c0 = np.arange(64, dtype=np.uint32)
    c1 = np.arange(64, dtype=np.uint32) * 3 + 1
    x0, x1 = prng.threefry_2x32(k, np.stack([c0, c1]))
    y0, y1 = reference.threefry2x32(7, 0x6E6F6973, c0, c1)
    np.testing.assert_array_equal(np.asarray(x0), y0)
    np.testing.assert_array_equal(np.asarray(x1), y1)


def test_unpaired_edges_counts_a_half_recorded_synapse():
    out_e = np.array([[1, -1], [-1, -1], [0, -1]], np.int32)
    in_e = np.array([[2, -1], [0, -1], [-1, -1]], np.int32)
    assert reference.unpaired_edges(out_e, in_e) == 0
    out_e[1, 0] = 2          # 1 -> 2 with no in-edge on row 2
    assert reference.unpaired_edges(out_e, in_e) == 1


def test_update_numbers_on_a_hand_made_update():
    pre = {"out_edges": np.array([[1, 2], [-1, -1], [-1, -1]], np.int32),
           "in_edges": np.array([[-1, -1], [0, -1], [0, -1]], np.int32)}
    # neuron 0 keeps 1 whole axonal element: one synapse retracted (0->2);
    # neurons 1 and 2 search, 1 forms 1 -> 2
    post = {"out_edges": np.array([[1, -1], [2, -1], [-1, -1]], np.int32),
            "in_edges": np.array([[-1, -1], [0, -1], [1, -1]], np.int32),
            "ax_elements": np.array([1.5, 1.2, 1.3], np.float32),
            "de_elements": np.array([0.0, 1.1, 1.9], np.float32)}
    counted = {"synapses_formed": 1.0, "synapses_deleted": 1.0,
               "formation_requests": 2.0, "request_overflow": 0.0}
    got = reference.update_numbers(pre, post, counted)
    assert got == {"unpaired_edges": 0, "over_capacity": 0, "deleted_gap": 0,
                   "multi_formed": 0, "formed_gap": 0, "searchers_gap": 0,
                   "formed_pct": 50.0, "request_overflow": 0}
    assert reference.update_numbers(
        pre, post, dict(counted, synapses_formed=2.0))["formed_gap"] == 1
    assert reference.update_numbers(
        pre, post, dict(counted, synapses_formed=0.0))["formed_gap"] == 1
    # a searcher left uncounted, and one counted that did not search
    assert reference.update_numbers(
        pre, post, dict(counted, formation_requests=1.0))[
            "searchers_gap"] == 1
    assert reference.update_numbers(
        pre, post, dict(counted, formation_requests=3.0))[
            "searchers_gap"] == 1


def test_update_numbers_allow_a_synapse_removed_and_formed_again():
    e = lambda rows: np.array(rows, np.int32)  # noqa: E731
    none = [-1, -1, -1]
    # neuron 0 retracts 0->3 (2 whole axonal elements for 3 synapses),
    # neuron 1 retracts its in-edge from 0; 0 searches with the slot freed
    # and forms 0->3 again: its row reads as before but for 0->1
    pre = {"out_edges": e([[1, 2, 3], none, none, none]),
           "in_edges": e([none, [0, -1, -1], [0, -1, -1], [0, -1, -1]])}
    post = {"out_edges": e([[2, 3, -1], none, none, none]),
            "in_edges": e([none, none, [0, -1, -1], [0, -1, -1]]),
            "ax_elements": np.array([2.5, 0.1, 0.1, 0.1], np.float32),
            "de_elements": np.array([0.0, 0.5, 1.2, 1.2], np.float32)}
    counted = {"synapses_formed": 1.0, "synapses_deleted": 2.0,
               "formation_requests": 1.0, "request_overflow": 0.0}
    got = reference.update_numbers(pre, post, counted)
    assert got["searchers_gap"] == 0 and got["formed_gap"] == 0
    assert got["unpaired_edges"] == 0 and got["deleted_gap"] == 0
    assert reference.update_numbers(pre, post, dict(
        counted, formation_requests=2.0))["searchers_gap"] == 1
    assert reference.update_numbers(pre, post, dict(
        counted, synapses_formed=2.0))["formed_gap"] == 1


def test_reach_against_the_kernel_and_without_it():
    rng = np.random.default_rng(5)
    n = 400
    x = rng.random((n, 3))
    empty = np.full((n, 4), -1, np.int32)
    post = {"de_elements": np.full(n, 1.5, np.float32),
            "in_edges": empty.copy()}
    sigma = 0.25

    def formed(targets):
        out = empty.copy()
        out[:, 0] = targets
        return dict(post, out_edges=out)

    d2 = ((x[:, None] - x[None]) ** 2).sum(-1)
    np.fill_diagonal(d2, np.inf)
    near = np.argmin(d2, axis=1)
    far = np.argmax(np.where(np.isinf(d2), -1, d2), axis=1)
    pre = {"out_edges": empty, "in_edges": empty}
    obs, kernel, blind, k = reference.reach(pre, formed(near), x, sigma)
    assert k == n and obs < kernel < blind
    obs, kernel, blind, _ = reference.reach(pre, formed(far), x, sigma)
    assert kernel < blind < obs
    # kernel draws read near the kernel's mean length
    p = 1.5 * np.exp(-np.where(np.isinf(d2), np.inf, d2) / sigma ** 2)
    drawn = np.array([rng.choice(n, p=row / row.sum()) for row in p])
    obs, kernel, blind, _ = reference.reach(pre, formed(drawn), x, sigma)
    assert abs(obs / kernel - 1) < 0.05 and obs < 0.6 * blind


# ------------------------------------------------------------ trace
def _ev(dev, start, dur, name, op_name):
    return btrace.OpEvent(dev, start, dur, name, op_name)


def test_reduce_own_time_union_and_gaps():
    act = "jit(run)/while/body/repro.activity/add"
    form = "jit(run)/repro.connectivity/repro.conn.formation/while/scatter"
    tree = "jit(run)/repro.connectivity/repro.conn.tree_build/sort"
    ops = [_ev("d0", 100, 50, "a", act),
           _ev("d0", 200, 300, "w", "jit(run)/repro.connectivity/"
               "repro.conn.formation/while"),
           _ev("d0", 250, 100, "s", form),     # nested in w
           _ev("d0", 600, 100, "t", tree),
           _ev("d0", 5, 10, "x", act)]         # before the window
    host = [("bench.window", 50, 1000), ("bench.chunk", 50, 700),
            ("bench.fence", 700, 1000)]
    s = btrace.reduce(ops, host)
    assert s.window_ns == 950
    assert s.scope_ns["repro.activity"] == 50
    assert s.scope_ns["repro.connectivity/repro.conn.formation"] == 300
    assert s.scope_ns["repro.connectivity/repro.conn.tree_build"] == 100
    assert s.busy_ns == 50 + 300 + 100
    assert sorted(s.idle_gaps, key=lambda g: -g[1])[0] == ("bench.fence",
                                                           3e-7)
    assert s.top_ops[0] == ("repro.conn.formation/while", 2e-7)
    # the check's copy between two chunks is cut out of the window
    cut = btrace.reduce(ops + [_ev("d0", 720, 40, "c", "")],
                        host + [("bench.check_copy", 710, 800)])
    assert cut.window_ns == 950 - 90
    assert cut.busy_ns == s.busy_ns and cut.scope_ns == s.scope_ns
    assert sum(g for _, g in cut.idle_gaps) == pytest.approx(
        sum(g for _, g in s.idle_gaps) - 90e-9)


def test_op_names_from_hlo_text():
    text = ("HloModule jit_runner, entry_computation_layout={}\n"
            "  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, "
            'metadata={op_name="jit(runner)/repro.activity/add" '
            'source_file="x.py"}\n'
            '  ROOT %sort.1 = s32[8]{0} sort(%a), metadata={op_name='
            '"jit(runner)/repro.connectivity/repro.conn.retraction/sort"}\n')
    names = btrace.op_names(text)
    assert btrace.module_name(text) == "jit_runner"
    assert names == {"fusion.3": "jit(runner)/repro.activity/add",
                     "sort.1": "jit(runner)/repro.connectivity/"
                               "repro.conn.retraction/sort"}
    assert btrace.repro_scope(names["sort.1"]) == \
        "repro.connectivity/repro.conn.retraction"


RECORDED = os.path.join(DATA, "tpu_trace.xplane.pb.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace")
def test_reducer_on_the_recorded_chip_trace(tmp_path):
    expect = json.load(open(os.path.join(DATA, "tpu_trace.expect.json")))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(gzip.decompress(open(RECORDED, "rb").read()))
    ops, host = btrace.read_xplane(str(path), expect["op_names"],
                                   expect["module"])
    s = btrace.reduce(ops, host)
    assert s.devices == 1
    for scope, ns in expect["scope_ns"].items():
        assert s.scope_ns[scope] == pytest.approx(ns, rel=1e-9)
    assert s.busy_ns == pytest.approx(expect["busy_ns"], rel=1e-9)
    assert s.window_ns == pytest.approx(expect["window_ns"], rel=1e-9)
    assert 0 < s.busy_ns <= s.window_ns
    total = sum(s.scope_ns.values())
    assert total <= s.busy_ns * (1 + 1e-9)
    # the compiler's own copies and layout changes are attributed to the
    # scope they run in: almost nothing is left without a repro scope
    assert s.scope_ns.get("", 0.0) < 0.01 * total
    assert s.scope_ns["repro.activity"] > 0
