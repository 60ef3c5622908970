"""Shared benchmark helpers: timing with an explicit compile/steady split,
subprocess fan-out over device counts, CSV emission (format:
name,us_per_call,derived)."""
from __future__ import annotations

import os
import re
import subprocess
import sys
import time
from typing import NamedTuple

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


class Timing(NamedTuple):
    """One measurement: first-call latency (trace + compile + first run,
    ms) and fenced steady-state per-iteration time (us). The two are
    reported separately in every ``BENCH_*.json`` (telemetry.report
    schema) — a compile-time regression must never hide in the
    steady-state number or vice versa."""
    compile_ms: float
    steady_us: float


def measure(fn, *args, warmup=1, iters=3):
    """Time ``fn(*args)`` with the compile/steady split: the first call
    (traced + compiled + executed, fenced) is ``compile_ms``; after
    ``warmup`` more fenced calls, ``iters`` fenced calls average into
    ``steady_us``. Returns (Timing, last_output)."""
    import jax
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    compile_ms = (time.perf_counter() - t0) * 1e3
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        jax.block_until_ready(out)
    steady_us = (time.perf_counter() - t0) / iters * 1e6
    return Timing(compile_ms, steady_us), out


def time_fn(fn, *args, warmup=1, iters=3):
    """Back-compat shim over ``measure``: (steady seconds/iter, output).
    The first warmup call doubles as the compile fence."""
    timing, out = measure(fn, *args, warmup=max(warmup - 1, 0), iters=iters)
    return timing.steady_us / 1e6, out


def emit(name: str, us_per_call: float, derived=""):
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


_HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count"


def run_sub(module: str, devices: int, *args, timeout=560):
    """Run a benchmark module in a subprocess with N host (CPU) devices;
    returns its stdout (the module prints CSV lines). A child that fails
    ends this process with its exit code: no partial table is printed as
    if it were complete."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"{_HOST_DEVICES_FLAG}={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + ROOT
    cmd = [sys.executable, "-m", module] + [str(a) for a in args]
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, env=env, cwd=ROOT)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-2000:])
        sys.exit(f"{module} failed with exit code {proc.returncode}")
    return proc.stdout


def num_ranks() -> int:
    """The rank count a brain bench runs on: ``len(jax.devices())``. Where
    ``XLA_FLAGS`` asks for N host devices, exactly N must exist — the flag
    only makes CPU devices, and on an accelerator host it is ignored, so a
    "4-rank" request would otherwise run on however many chips there are."""
    import jax
    r = len(jax.devices())
    m = re.search(rf"{_HOST_DEVICES_FLAG}=(\d+)",
                  os.environ.get("XLA_FLAGS", ""))
    if m and int(m.group(1)) != r:
        raise RuntimeError(
            f"asked for {m.group(1)} ranks through {_HOST_DEVICES_FLAG}, but "
            f"jax sees {r} {jax.default_backend()} device(s)")
    return r


# paper record sizes (bytes) for Table I/II accounting
PAPER_BYTES = {
    "old_request": 17, "new_request": 42, "new_response": 9,
    "spike_id": 8, "rate": 4, "tree_node": 32,
}


def brain_sim_timed(cfg_overrides, chunks=2):
    """Build + run the brain sim on whatever devices exist, through the
    ``repro.sim.Simulator`` facade, with the compile/steady split: the
    warmup chunk (compile + first plasticity round, fenced) is
    ``compile_ms``; ``chunks`` more fenced chunks average into
    ``steady_us``. Returns (Timing, simulator) — callers read the final
    state from ``sim.state`` and full telemetry from ``sim.metrics()``."""
    import jax
    from repro.configs.msp_brain import BrainConfig
    from repro.sim import Simulator
    cfg = BrainConfig(**cfg_overrides)
    sim = Simulator.from_config(cfg)
    t0 = time.perf_counter()
    st = sim.step()  # warmup/compile + first plasticity round
    jax.block_until_ready(st.positions)
    compile_ms = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    for _ in range(chunks):
        st = sim.step()
    jax.block_until_ready(st.positions)
    steady_us = (time.perf_counter() - t0) / chunks * 1e6
    return Timing(compile_ms, steady_us), sim


def brain_sim(cfg_overrides, chunks=2, stats_only=False):
    """Back-compat shim over ``brain_sim_timed``:
    (steady time_per_chunk_s, final_state)."""
    timing, sim = brain_sim_timed(cfg_overrides, chunks=chunks)
    return timing.steady_us / 1e6, sim.state


def paper_bytes_from_stats(stats, alg_conn: str, alg_spike: str,
                           num_ranks: int):
    """Tables I/II accounting with the paper's record sizes."""
    s = {k: float(v.sum()) for k, v in stats.items()}
    b = 0.0
    if alg_conn == "new":
        b += s["bh_requests"] * PAPER_BYTES["new_request"]
        b += s["bh_requests"] * PAPER_BYTES["new_response"]
    else:
        b += s["formation_requests"] * (PAPER_BYTES["old_request"] + 1)
        b += s["tree_nodes_downloaded"] * PAPER_BYTES["tree_node"]
    if alg_spike == "new":
        # rates_sent already counts rate records actually shipped (dense:
        # n*(R-1) broadcast per rank per Delta; sparse: the subscribed
        # pushes) — no fan-out factor here. The sparse exchange also ships
        # one 4B subscription-request id per pushed rate (zero under dense).
        b += s["rates_sent"] * PAPER_BYTES["rate"]
        b += s.get("subscription_requests", 0.0) * PAPER_BYTES["rate"]
    else:
        b += s["spikes_sent"] * PAPER_BYTES["spike_id"] * max(num_ranks - 1, 0)
    return b, s
