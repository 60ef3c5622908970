"""``correct`` on the CPU at a size a test run holds: the program passes its
limits, the control (the reference in bfloat16 in the program's place)
fails them through the harness's own comparison, and so does a run whose
timed path is broken underneath.

``msp65k.grow`` keeps its traffic, its configuration but for the neuron
count (4,096, one neuron per leaf cell) and its limits, but for the one
limit that follows the neuron count: ``formed_pct``, the share of
searchers that form a synapse in the window's first chunk, which is 6.4%
here and under 1% at 65,536 neurons (PERF.md, limits).

    PYTHONPATH=src python -m pytest -q bench/tests
"""
from __future__ import annotations

import contextlib
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import cells, faults, harness  # noqa: E402

SEED = 2_147_483_659
NEURONS = 4096
# sound 6.3-6.6 on three seeds; nine requests in ten dropped 1.7-2.0
FORMED_PCT_MIN = 4.0


def small(neurons=NEURONS, **brain):
    cell = cells.load_cell("msp65k.grow")
    config = dict(cell.config, brain_config=dict(
        cell.config["brain_config"], neurons_per_rank=neurons, **brain))
    limits = dict(cell.limits, formed_pct={"min": FORMED_PCT_MIN})
    return cells.Cell(**{**cell.__dict__, "config": config,
                         "limits": limits})


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("bench_root"))


def run(cell, root, fault=None, with_control=False):
    import jax
    devices = jax.devices()[:1]
    res = harness.measure(cell, SEED, 1.0, False, devices,
                          time.perf_counter(), root, fault=fault,
                          with_control=with_control)
    return res, harness.result_line(cell, res, False, devices, root)


def test_program_passes_and_control_fails(root):
    cell = small()
    res, line = run(cell, root, with_control=True)
    assert line["correct"], line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "compared"
    # the control in the program's place, through the same comparison
    import jax
    ctrl = dict(res, numbers={**res["numbers"], **res["control"]})
    cline = harness.result_line(cell, ctrl, False, jax.devices()[:1], root)
    assert not cline["correct"], cline["compared"]


def _unchanged(sim, step):
    import jax
    import jax.numpy as jnp

    def s():
        keep = jax.tree.map(jnp.copy, sim.state)
        step()
        sim._state = keep
        return keep
    return s


def _half_left_out(sim, step):
    """The activity of the second half of the neurons left out."""
    import jax
    import jax.numpy as jnp

    def s():
        before = jax.tree.map(jnp.copy, sim.state.neurons)
        step()
        st = sim.state
        half = st.neurons.v.shape[0] // 2
        keep = jnp.arange(st.neurons.v.shape[0]) >= half
        fields = ("v", "u", "calcium", "ax_elements", "de_elements")
        sim._state = st._replace(neurons=st.neurons._replace(**{
            f: jnp.where(keep, getattr(before, f), getattr(st.neurons, f))
            for f in fields}))
        return sim._state
    return s


def _altered(what):
    def fault(sim, step):
        def s():
            step()
            st = sim.state
            nu = st.neurons
            if what == "rate":
                sim._state = st._replace(neurons=nu._replace(
                    rate=nu.rate.at[7].add(0.2)))
            elif what == "elements":
                sim._state = st._replace(neurons=nu._replace(
                    ax_elements=nu.ax_elements.at[7].add(0.01)))
            else:
                e = np.asarray(st.out_edges)
                row = int(np.argmax((e >= 0).sum(1)))
                col = int(np.argmax(e[row] >= 0))
                new = (int(e[row, col]) + 1) % e.shape[0]
                sim._state = st._replace(
                    out_edges=st.out_edges.at[row, col].set(new))
            return sim._state
        return s
    return fault


WRAPPED = {"state_unchanged": _unchanged,
           "half_the_neurons_left_out": _half_left_out,
           "elements_altered": _altered("elements"),
           "edge_target_altered": _altered("edge"),
           "rate_altered": _altered("rate")}


@pytest.mark.parametrize("name", [*WRAPPED, *faults.NAMES])
def test_broken_timed_path_is_not_correct(root, name):
    cell = small()
    with (faults.planted(name) if name in faults.NAMES
          else contextlib.nullcontext()):
        _, line = run(cell, root, fault=WRAPPED.get(name))
    assert not line["correct"], (name, line["compared"])
    assert line["failed"] >= 1
    print(name, json.dumps(line["compared"]))


def _four_ranks():
    """``msp65k.grow`` on four virtual CPU devices, 512 neurons per rank
    and three octree levels, with the sparse rate exchange at its ceiling
    (no request or subscription can overflow) and an ``exchange_gap``
    limit of 0: the sound run, one with the exchange left out (each rank's
    pushed remote rates zeroed after every chunk) and one per fault of
    ``WRAPPED``. ``formed_pct`` is not compared at this size."""
    import jax
    import jax.numpy as jnp
    cell = small(512, local_levels=3, frontier_cap=32,
                 rate_exchange="sparse", requests_cap_factor=4,
                 subs_cap_factor=12, subs_cap_base=128)
    limits = dict(cell.limits, exchange_gap=0)
    del limits["formed_pct"]
    cell = cells.Cell(**{**cell.__dict__, "chips": 4, "limits": limits})
    devices = jax.devices()[:4]

    def no_exchange(sim, step):
        def s():
            step()
            st = sim.state
            sim._state = st._replace(
                remote_rates=jnp.zeros_like(st.remote_rates))
            return sim._state
        return s

    out = {}
    for name, fault in (("sound", None), ("exchange_left_out", no_exchange),
                        *WRAPPED.items()):
        res = harness.measure(cell, SEED, 1.0, False, devices,
                              time.perf_counter(), ROOT, fault=fault)
        line = harness.result_line(cell, res, False, devices, ROOT)
        out[name] = {"correct": line["correct"],
                     "compared": line["compared"]}
    return out


def test_four_rank_faults_are_not_correct():
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, __file__], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"], out["sound"]
    assert out["exchange_left_out"]["compared"]["exchange_gap"]["value"] > 0
    for name in ("exchange_left_out", *WRAPPED):
        assert not out[name]["correct"], (name, out[name])


if __name__ == "__main__":
    print(json.dumps(_four_ranks()))
