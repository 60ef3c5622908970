"""One run of one cell: set-up, the measured window, the check of what the
window produced against the plain reference, and the result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up: device check, the cell's initial connectome from ``--seed``,
``Simulator.from_connectome``, the compile of the one-chunk program (or its
load from JAX's persistent cache), one warm chunk. The window then runs
whole chunks, each fenced with ``block_until_ready``, and starts no chunk
that would end past ``--seconds`` by the last chunk's time; it always runs
one. Between two chunks the clock stops while the check copies the first
one's output. With ``--trace 1`` the window runs under the profiler and the run
reports the per-layer metrics instead of the end-to-end ones.

The program's own random streams keep the configuration's seed, so every
``--seed`` runs the same compiled program on other data.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import types

import ml_dtypes
import numpy as np

from bench import cells, generate, reference
from bench import trace as btrace

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CHUNK_KEYS = ("formation_requests", "synapses_formed", "synapses_deleted",
              "request_overflow")


class NoChip(RuntimeError):
    pass


def use_compile_cache(root: str) -> str:
    """JAX's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` where
    that is set (jax reads it itself), else ``<checkout>/.jax_cache``, one
    fixed path so that the next run in this checkout finds what this one
    compiled. Call before the first compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def tpu_devices(count: int):
    """The first ``count`` TPU devices; raises NoChip on any other platform
    or too few chips."""
    import jax
    devs = jax.devices()
    print(f"jax {jax.__version__}: platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)}", file=sys.stderr)
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax found platform {devs[0].platform!r}")
    if len(devs) < count:
        raise NoChip(f"the cell needs {count} TPU chips, found {len(devs)}")
    return devs[:count]


def fenced(fn):
    """(seconds, result) of ``fn()``, fenced with block_until_ready."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return time.perf_counter() - t0, out


class CompileLog:
    """Seconds of every backend compile (or persistent-cache load)."""

    def __init__(self):
        import jax
        self.secs = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.secs.append(secs)


def dataset(cell, arrays):
    from repro.workloads.datasets import ConnectomeDataset
    edges = arrays["edges"]
    return ConnectomeDataset(
        name=cell.config_name, positions=arrays["positions"], edges=edges,
        edge_types=(~arrays["is_excitatory"][edges[:, 0]]).astype(np.int32),
        region_ids=arrays["region_ids"],
        region_names=arrays["region_names"],
        region_boxes=arrays["region_boxes"],
        is_excitatory=arrays["is_excitatory"])


def _snapshot_fn():
    """A jitted copy of what the check needs of a state: neurons, edge
    tables, chunk counter, counters and the rates each rank holds after
    the exchange (the next chunk donates the state)."""
    import jax
    import jax.numpy as jnp

    def pick(st):
        return {"neurons": st.neurons._asdict(), "out_edges": st.out_edges,
                "in_edges": st.in_edges, "chunk": st.chunk,
                "counters": {k: st.stats.counters[k] for k in CHUNK_KEYS},
                "exchange": {"rates_table": st.rates_table,
                             "rate_slots": st.rate_slots,
                             "remote_rates": st.remote_rates}}

    return jax.jit(lambda st: jax.tree.map(jnp.copy, pick(st)))


def _host(snap) -> dict:
    import jax
    h = jax.device_get(snap)
    out = {k: np.asarray(v) for k, v in h["neurons"].items()}
    out.update(out_edges=np.asarray(h["out_edges"]),
               in_edges=np.asarray(h["in_edges"]),
               chunk=int(h["chunk"]),
               counters={k: float(np.sum(v))
                         for k, v in h["counters"].items()},
               **{k: None if v is None else np.asarray(v)
                  for k, v in h["exchange"].items()})
    return out


def within(value, limit) -> bool:
    """A limit is a largest value, or ``{"min": x}``, a smallest one."""
    return value >= limit["min"] if isinstance(limit, dict) \
        else value <= limit


def check(cell, brain: dict, arrays: dict, snaps):
    """The window's chunks against the reference: (per-chunk numbers, the
    window's numbers, chunks that broke a limit). A number is compared
    where the cell's limits file gives it a limit. The window's number is
    the worst chunk's, except ``formed_pct``, read on the window's first
    chunk (the first after the warm chunk in every run; later chunks find
    fewer vacancies), and the reach of new synapses, pooled over the
    window: ``reach_pct``, their length against targets drawn without the
    kernel, and ``reach_gap_pct``, against the kernel's."""
    n = int(brain["neurons_per_rank"])
    delta = int(brain["rate_period"])
    is_exc = arrays["is_excitatory"]
    per_chunk, numbers, failed = [], {}, 0
    reach = np.zeros(3)
    for i, (pre, post) in enumerate(zip(snaps[:-1], snaps[1:])):
        ref = reference.activity_window(brain, pre["chunk"], pre,
                                        pre["in_edges"], is_exc, n)
        nums = reference.activity_numbers(post, ref, delta)
        counted = {k: post["counters"][k] - pre["counters"][k]
                   for k in CHUNK_KEYS}
        nums.update(reference.update_numbers(pre, post, counted))
        nums["exchange_gap"] = reference.exchange_gap(post, n)
        reach += reference.reach(pre, post, arrays["positions"],
                                 float(brain["sigma"]))[:3]
        per_chunk.append(nums)
        judged = {k: v for k, v in nums.items()
                  if not (i and k == "formed_pct")}
        failed += not all(within(v, cell.limits[k])
                          for k, v in judged.items() if k in cell.limits)
        for k, v in judged.items():
            numbers[k] = max(numbers.get(k, v), v)
    observed, kernel, blind = (float(v) for v in reach)
    numbers["reach_pct"] = 100.0 * observed / blind if blind else 0.0
    numbers["reach_gap_pct"] = 100.0 * abs(observed - kernel) / kernel \
        if kernel else 0.0
    if not all(within(numbers[k], cell.limits[k])
               for k in ("reach_pct", "reach_gap_pct") if k in cell.limits):
        failed = max(failed, 1)
    return per_chunk, numbers, failed


def control(brain: dict, is_exc, snaps) -> dict:
    """The control: the reference in bfloat16 put in the program's place,
    from the same chunk inputs; its activity numbers against float32."""
    n = int(brain["neurons_per_rank"])
    delta = int(brain["rate_period"])
    worst = {}
    for pre in snaps[:-1]:
        args = (brain, pre["chunk"], pre, pre["in_edges"], is_exc, n)
        low = reference.activity_window(*args, dtype=ml_dtypes.bfloat16)
        low["rate"] = low["spikes"] / np.float32(delta)
        nums = reference.activity_numbers(low, reference.activity_window(
            *args), delta)
        for k, v in nums.items():
            worst[k] = max(worst.get(k, v), v)
    return worst


def measure(cell, seed: int, seconds: float, trace: bool, devices,
            t_start: float, root: str = cells.ROOT, fault=None,
            with_control: bool = False) -> dict:
    """Set-up, window and check of one run; returns the result line's
    fields. ``fault(sim, step)`` may wrap the chunk step (tests)."""
    import jax
    from repro.configs.msp_brain import BrainConfig
    from repro.core import engine
    from repro.sim import Simulator

    compiles = CompileLog()
    brain = cell.brain_config()
    cfg = BrainConfig(**brain)
    arrays = generate.initial(cell.config, cell.traffic, brain, cell.chips,
                              seed)
    mesh = engine.make_brain_mesh(devices)
    sim = Simulator.from_connectome(cfg, dataset(cell, arrays), mesh=mesh)
    executable = sim.lower(1).compile()
    snap_fn = _snapshot_fn()
    step = (lambda: sim.run(1)) if fault is None else fault(sim,
                                                            lambda: sim.run(1))
    t_warm, _ = fenced(step)
    setup_s = time.perf_counter() - t_start
    compile_s = float(sum(compiles.secs))
    print(f"set-up {setup_s:.6f} s: compile or cache load {compile_s:.6f} s,"
          f" warm chunk {t_warm:.6f} s", file=sys.stderr)
    snaps = [jax.block_until_ready(snap_fn(sim.state))]

    trace_dir = os.path.join(root, ".bench_trace", cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    n_compiles = len(compiles.secs)
    chunk_s = []
    with jax.profiler.TraceAnnotation(btrace.WINDOW):
        while True:
            tc = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.chunk"):
                step()
            with jax.profiler.TraceAnnotation("bench.fence"):
                jax.block_until_ready(sim.state)
            chunk_s.append(time.perf_counter() - tc)
            if sum(chunk_s) + chunk_s[-1] > seconds:
                break
            # the check's copy of this chunk's output, off the clock and
            # left out of the traced window
            with jax.profiler.TraceAnnotation(btrace.CHECK_COPY):
                snaps.append(jax.block_until_ready(snap_fn(sim.state)))
    if trace:
        jax.profiler.stop_trace()
    snaps.append(snap_fn(sim.state))
    window_s = sum(chunk_s)
    if len(compiles.secs) != n_compiles:
        raise RuntimeError(f"{len(compiles.secs) - n_compiles} compile(s) "
                           "inside the measured window")
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    snaps = [_host(s) for s in snaps]
    hlo = executable.as_text() if trace else ""
    del sim, executable, step
    k = len(chunk_s)
    print(f"window: {k} chunk(s) in {window_s:.6f} s; per chunk "
          f"{[round(c, 6) for c in chunk_s]}", file=sys.stderr)

    counted = {key: snaps[-1]["counters"][key] - snaps[0]["counters"][key]
               for key in CHUNK_KEYS}
    queries = cell.chips * int(brain["neurons_per_rank"]) * k
    print(f"formation_requests {counted['formation_requests']:.0f} of "
          f"{queries} phase-B query slots "
          f"({100 * counted['formation_requests'] / queries:.3f}% live); "
          f"synapses_formed {counted['synapses_formed']:.0f}; "
          f"synapses_deleted {counted['synapses_deleted']:.0f}; "
          f"request_overflow {counted['request_overflow']:.0f}; "
          f"peak_bytes_in_use {peak}", file=sys.stderr)

    per_chunk, numbers, failed = check(cell, brain, arrays, snaps)
    out = {"chunks": k, "window_s": window_s, "chunk_s": chunk_s,
           "setup_s": setup_s, "compile_s": compile_s, "numbers": numbers,
           "per_chunk": per_chunk,
           "failed": failed, "memory_peak_bytes": peak, "counted": counted}
    if with_control:
        out["control"] = control(brain, arrays["is_excitatory"], snaps)
    if trace:
        out["trace"] = btrace.summarize(trace_dir, hlo)
    return out


def result_line(cell, res: dict, trace: bool, devices, root: str) -> dict:
    """The contract's last line: correct, attempted, failed, metrics,
    device, optional breakdown, and the compared numbers last."""
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    metrics = {}
    if trace:
        summ = res["trace"]
        run = types.SimpleNamespace(trace=summ, chunks=res["chunks"],
                                    compile_s=res["compile_s"])
        for m in cell.per_layer:
            value = cells.metric_reader(m["name"], root)(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=summ.busy_ns / 1e9,
                      window_s=summ.window_ns / 1e9)
    else:
        values = {"chunk_ms": 1e3 * res["window_s"] / res["chunks"],
                  "setup_s": res["setup_s"]}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    compared = {k: {"value": res["numbers"][k],
                    **(limit if isinstance(limit, dict) else {"max": limit})}
                for k, limit in cell.limits.items()}
    line = {"correct": all(within(res["numbers"][k], limit)
                           for k, limit in cell.limits.items()),
            "attempted": res["chunks"], "failed": res["failed"],
            "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = {"device_ops": [list(x) for x in summ.top_ops],
                             "idle_gaps": [list(x) for x in summ.idle_gaps]}
    line["compared"] = compared
    return line


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_start: float, root: str = cells.ROOT) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        print(f"no program under {root}/src/repro", file=sys.stderr)
        return 2
    cell = cells.load_cell(args.workload, root)
    use_compile_cache(root)
    try:
        devices = tpu_devices(cell.chips)
    except NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    hbm = cells.peaks(devices[0].device_kind, root)["hbm_bytes"]
    res = measure(cell, args.seed, args.seconds, bool(args.trace), devices,
                  t_start, root)
    line = result_line(cell, res, bool(args.trace), devices, root)
    print(f"memory_peak_bytes {res['memory_peak_bytes']} of {hbm:.0f} "
          f"({100 * res['memory_peak_bytes'] / hbm:.3f}%)", file=sys.stderr)
    for k, v in res["numbers"].items():
        if k not in line["compared"]:
            print(f"not compared {k}: {v!r}", file=sys.stderr)
    for k, v in line["compared"].items():
        bound = "min" if "min" in v else "max"
        print(f"compared {k}: {v['value']!r} {bound} {v[bound]!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
