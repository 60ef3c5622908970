"""The phase-B and activity-input instrumentation and the benchmark's
readers of it: the named scopes land in the compiled chunk's HLO where
``bench/trace.py`` finds them, and each reader under ``bench/metrics/``
turns a trace summary and the program's counters and spans into a finite,
in-range number, and into None without a trace or where the program has
no such scope."""
import math
import os
import sys
import types

import pytest

from repro import telemetry
from repro.configs.msp_brain import BrainConfig
from repro.sim import Simulator
from repro.telemetry import metrics as tm

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

from bench import cells  # noqa: E402
from bench import trace as btrace  # noqa: E402

SMALL = BrainConfig(neurons_per_rank=32, local_levels=3, frontier_cap=32,
                    max_synapses=8, rate_period=10, requests_cap_factor=100)

FORMATION = "repro.connectivity/repro.conn.formation"
SEARCH = FORMATION + "/repro.bh.search"
# reader -> (trace path it reads, or None for a counter or span reader)
TIME_READERS = {
    "conn.bh_expand.device_ms": SEARCH + "/repro.bh.expand",
    "conn.bh_sample.device_ms": SEARCH + "/repro.bh.sample",
    "conn.bh_loop.device_ms": SEARCH,
    "conn.bh_member.device_ms": FORMATION + "/repro.bh.member",
    "conn.accept.device_ms": FORMATION + "/repro.conn.accept",
    "activity.input.device_ms": "repro.activity/repro.act.input",
}
SHARE_READERS = ("conn.formation.live_pct", "conn.formation.round_use_pct",
                 "conn.formation.overflow_pct", "conn.formation.rows_run_pct")
NEW_READERS = tuple(TIME_READERS) + SHARE_READERS + ("host.dispatch_ms",)


def _summary(scope_ns):
    return btrace.Summary(scope_ns=scope_ns, busy_ns=1.0, window_ns=1.0,
                          devices=1, top_ops=[], idle_gaps=[])


@pytest.fixture(scope="module")
def two_chunks():
    """A 2-chunk window of a tiny CPU Simulator (one ``sim.run`` each),
    the spans and latest metrics the readers look at."""
    sim = Simulator(SMALL)
    sim.run(1)
    sim.run(1)
    return sim


def test_new_scopes_are_in_the_compiled_chunk():
    """Every new scope names ops of one compiled chunk; phase B's sit
    under ``repro.conn.formation``, the input's under ``repro.activity``."""
    hlo = Simulator(SMALL).lower(1).compile().as_text()
    paths = {btrace.repro_scope(v) for v in btrace.op_names(hlo).values()}
    for path in TIME_READERS.values():
        assert path in paths, (path, sorted(paths))


@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_metric_is_declared_for_the_cell(metric):
    spec = {m["name"]: m for m in cells.benchmark(ROOT)["per_layer"]}
    assert spec[metric]["moves"] == "chunk_ms"
    assert spec[metric]["workloads"] == ["msp65k.grow"]


@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_reader_reads_nothing_without_a_trace(metric, two_chunks):
    read = cells.metric_reader(metric, ROOT)
    assert read(types.SimpleNamespace(trace=None, chunks=2,
                                      compile_s=0.0)) is None


@pytest.mark.parametrize("metric", sorted(TIME_READERS))
def test_time_reader_sums_its_scope_per_chunk(metric):
    path = TIME_READERS[metric]
    scope_ns = {p: 1e6 * (i + 1) for i, p in enumerate(
        sorted(set(TIME_READERS.values())))}
    # the same scope under phase A, and the enclosing scopes, do not count
    scope_ns["repro.connectivity/repro.conn.phase_a/repro.bh.search"] = 7e9
    scope_ns[FORMATION] = 5e9
    scope_ns["repro.activity"] = 5e9
    read = cells.metric_reader(metric, ROOT)
    run = types.SimpleNamespace(trace=_summary(scope_ns), chunks=2)
    assert read(run) == pytest.approx(scope_ns[path] / 1e6 / 2)
    # a program without the scope (the parent of this instrumentation)
    del scope_ns[path]
    assert read(run) is None


@pytest.mark.parametrize("metric", SHARE_READERS + ("host.dispatch_ms",))
def test_counter_and_span_readers_on_a_two_chunk_window(metric, two_chunks):
    two_chunks.run(1)
    two_chunks.run(1)
    run = types.SimpleNamespace(trace=_summary({}), chunks=2)
    value = cells.metric_reader(metric, ROOT)(run)
    assert value is not None and math.isfinite(value)
    if metric == "host.dispatch_ms":
        spans = telemetry.spans("sim.run")[-2:]
        assert value == pytest.approx(sum(s.duration_ms for s in spans) / 2)
        assert value > 0
    else:
        assert 0 <= value <= 100
    if metric != "conn.formation.overflow_pct":
        assert value > 0


def test_live_share_matches_the_formation_requests(two_chunks):
    """On one rank with cap = n the live share is the harness's "% live"
    line: formation requests over the n query slots per chunk."""
    c = telemetry.last_chunk_counters(2)
    run = types.SimpleNamespace(trace=_summary({}), chunks=2)
    live = cells.metric_reader("conn.formation.live_pct", ROOT)(run)
    n = SMALL.neurons_per_rank * two_chunks.num_ranks
    assert live == pytest.approx(100 * c["formation_requests"].sum() / (2 * n))


def test_counter_readers_read_nothing_without_the_counters(monkeypatch):
    """Laid over a program that publishes no metrics, the counter readers
    give None rather than raising."""
    monkeypatch.setattr(tm, "_latest", None)
    run = types.SimpleNamespace(trace=_summary({}), chunks=1)
    for metric in SHARE_READERS:
        assert cells.metric_reader(metric, ROOT)(run) is None
    monkeypatch.delattr(telemetry, "last_chunk_counters")
    for metric in SHARE_READERS:
        assert cells.metric_reader(metric, ROOT)(run) is None
