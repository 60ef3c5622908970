"""Bring-up smoke run of the MSP brain simulator on TPU chips.

  python chip_smoke.py             one chip: ``Simulator`` at ``CONFIG``
                                   (65,536 neurons), checked on the chip
  python chip_smoke.py --chips 4   four chips, and only this: the
                                   multi-rank invariants (sparse == dense
                                   and old == new, bit for bit, at zero
                                   overflow) at CONFIG per chip
  --neurons-per-rank N             run either phase at N neurons per chip
                                   instead of CONFIG's 65,536 (printed as
                                   a cut)

Everything runs in this one process, which holds the chips. JAX's
persistent compile cache lives in ``$JAX_COMPILATION_CACHE_DIR`` where that
is set, else in ``<repo>/.jax_cache``. A run that finds no TPU, or fails
any check, exits non-zero and prints no result line. A passing run ends
with one JSON line naming the device. Its times are those of one smoke run,
not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))

CHUNKS = 3   # chunks per run() call; one chunk = Delta steps + one update

FOUR_CHIP_CHUNKS = 2   # the sparse exchange first feeds activity in chunk 2

# Locality sends ~83% of a rank's requests to itself, more than the
# 3n/4 slots of factor 3 (which overflows); factor 4 = R gives every
# (source, destination) pair n slots, so each rank searches 4n phase-B
# queries (static buffers, routing.cap_requests). The subscription
# registry keeps its default factor: it holds n/2 remote sources, and
# after two chunks at 16,384 per rank the largest rank subscribes to
# about 3,000.
FOUR_RANK_CAPS = dict(requests_cap_factor=4)

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

# connectivity_alg / rate_exchange of the three 4-rank variants
VARIANTS = {"new/sparse": ("new", "sparse"),
            "new/dense": ("new", "dense"),
            "old/dense": ("old", "dense")}

NEURON_FIELDS = ("v", "u", "calcium", "rate", "spike_count",
                 "ax_elements", "de_elements")


class CheckFailed(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` where that is set (jax reads it itself,
    so nothing is set here), else ``<repo>/.jax_cache``: one fixed path,
    never a temporary name, so the next run in this checkout finds what
    this one compiled. Call before the first compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def tpu_devices(count: int):
    """The first ``count`` TPU devices; raises on any other platform (the
    CPU included) or too few chips. Prints what jax found first."""
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"jax {jax.__version__} devices: {devs}", flush=True)
    print(f"platform={d.platform} device_kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        raise CheckFailed(f"no TPU: jax found platform {d.platform!r}")
    check(len(devs) >= count, f"need {count} TPU chips, found {len(devs)}")
    return devs[:count]


def fenced(fn):
    """(seconds, result) of ``fn()``, fenced with block_until_ready."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return time.perf_counter() - t0, out


def edges_paired(out_edges, in_edges) -> bool:
    """Every out-edge (i -> j) has exactly one matching in-edge entry on
    row j, and vice versa (global gid == global row)."""
    import numpy as np
    rows = np.arange(out_edges.shape[0], dtype=np.int64)[:, None]
    big = np.int64(out_edges.shape[0])
    out_keys = np.broadcast_to(rows, out_edges.shape) * big + out_edges
    in_keys = in_edges.astype(np.int64) * big + rows
    return np.array_equal(np.sort(out_keys[out_edges >= 0]),
                          np.sort(in_keys[in_edges >= 0]))


def check_sim(sim, label: str) -> dict:
    """The on-chip checks of one simulator's current state. Returns the
    state on the host."""
    import jax
    import numpy as np
    flags = sim.probe_health()
    stats = sim.stats()
    host = jax.device_get(sim.state)
    bad = [jax.tree_util.keystr(path) for path, x
           in jax.tree_util.tree_flatten_with_path(host)[0]
           if np.issubdtype(np.asarray(x).dtype, np.floating)
           and not np.isfinite(x).all()]
    print(f"{label}: health_flags={flags} "
          f"synapses_formed={stats['synapses_formed']:.0f} "
          f"synapses_deleted={stats['synapses_deleted']:.0f} "
          f"request_overflow={stats['request_overflow']:.0f} "
          f"subscription_overflow={stats['subscription_overflow']:.0f}",
          flush=True)
    check(flags == 0, f"{label}: health_flags = {flags}")
    check(stats["synapses_formed"] > 0, f"{label}: no synapse formed")
    check(not bad, f"{label}: non-finite leaves {bad}")
    n_rows = sim.num_ranks * sim.cfg.neurons_per_rank
    check(host.out_edges.shape == (n_rows, sim.cfg.max_synapses),
          f"{label}: out_edges shape {host.out_edges.shape}")
    if stats["request_overflow"] == 0:
        check(edges_paired(np.asarray(host.out_edges),
                           np.asarray(host.in_edges)),
              f"{label}: out/in edge tables do not pair up")
        print(f"{label}: every out-edge pairs with one in-edge", flush=True)
    else:
        print(f"{label}: edge pairing not checked (dropped requests leave "
              "stale partner entries)", flush=True)
    return {"state": host, "stats": stats}


def peak_bytes(devices) -> str:
    out = []
    for d in devices:
        ms = d.memory_stats() or {}
        out.append(str(ms.get("peak_bytes_in_use", "not reported")))
    return ", ".join(out)


def one_chip(cfg, devices) -> None:
    """``Simulator.run`` at ``cfg`` on one device, timed and checked."""
    from repro.core import engine
    from repro.sim import Simulator
    print(f"one chip: neurons_per_rank={cfg.neurons_per_rank} "
          f"connectivity_alg={cfg.connectivity_alg} "
          f"spike_alg={cfg.spike_alg} rate_exchange={cfg.rate_exchange} "
          f"Delta={cfg.rate_period}", flush=True)
    sim = Simulator.from_config(cfg, mesh=engine.make_brain_mesh(devices))
    t_init, _ = fenced(sim.init)
    t_first, _ = fenced(lambda: sim.run(CHUNKS))
    t_steady, _ = fenced(lambda: sim.run(CHUNKS))
    print(f"set-up: init {t_init:.3f} s; first run({CHUNKS}) incl. compile "
          f"{t_first:.3f} s (~{t_first - t_steady:.3f} s of it compile)",
          flush=True)
    print(f"steady: {t_steady / CHUNKS * 1e3:.3f} ms per chunk over "
          f"{CHUNKS} chunks (one smoke run, not a benchmark)", flush=True)
    check_sim(sim, "one chip")
    print(f"peak_bytes_in_use: {peak_bytes(devices)}", flush=True)


def four_chips(cfg, devices) -> None:
    """The paper's multi-rank invariants at ``cfg`` per chip: three variants
    on one ``ranks`` mesh, compared bit for bit."""
    import jax
    import numpy as np
    from repro.core import engine
    from repro.sim import Simulator
    cfg = dataclasses.replace(cfg, **FOUR_RANK_CAPS)
    mesh = engine.make_brain_mesh(devices)
    print(f"{len(devices)} chips: neurons_per_rank={cfg.neurons_per_rank} "
          f"requests_cap_factor={cfg.requests_cap_factor} "
          f"subs_cap_factor={cfg.subs_cap_factor}", flush=True)
    sims = {name: Simulator.from_config(
                dataclasses.replace(cfg, connectivity_alg=conn,
                                    spike_alg="new", rate_exchange=rex),
                mesh=mesh)
            for name, (conn, rex) in VARIANTS.items()}
    # One chunk program took 114 s to compile on a v5e host, and each
    # second here costs four chips. XLA compiles release the GIL: compile
    # the three run programs at once; jit keeps each executable in memory,
    # and the run() calls below reuse them (their compile count is printed).
    lowered = [sim.lower(FOUR_CHIP_CHUNKS) for sim in sims.values()]
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(lowered)) as pool:
        list(pool.map(lambda low: low.compile(), lowered))
    t_compile = time.perf_counter() - t0
    print(f"set-up: {len(lowered)} run programs compiled concurrently in "
          f"{t_compile:.3f} s", flush=True)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(secs)
        if event == BACKEND_COMPILE else None)
    res = {}
    for name, sim in sims.items():
        sim.init()
        n_compiles = len(compiles)
        t_run, _ = fenced(lambda: sim.run(FOUR_CHIP_CHUNKS))
        print(f"{name}: run({FOUR_CHIP_CHUNKS}) {t_run:.3f} s, "
              f"{len(compiles) - n_compiles} compile(s)", flush=True)
        res[name] = check_sim(sim, name)
        check(res[name]["stats"]["request_overflow"] == 0,
              f"{name}: request_overflow")
        check(res[name]["stats"]["subscription_overflow"] == 0,
              f"{name}: subscription_overflow")
        per_rank = sim.stats(reduce=False)
        for key in ("activity_spikes", "bh_requests", "bh_responses",
                    "synapses_formed", "rates_sent", "tree_nodes_downloaded"):
            print(f"{name}: per-rank {key} = "
                  f"{np.asarray(per_rank[key]).tolist()}", flush=True)
        sims[name] = None   # frees this variant's device state
        del sim

    a, b = res["new/dense"]["state"], res["new/sparse"]["state"]
    for f in NEURON_FIELDS:
        check(np.array_equal(getattr(a.neurons, f), getattr(b.neurons, f)),
              f"sparse != dense: neurons.{f}")
    for f in ("out_edges", "in_edges", "positions"):
        check(np.array_equal(getattr(a, f), getattr(b, f)),
              f"sparse != dense: {f}")
    print("sparse == dense: neuron state and edge tables bit-identical",
          flush=True)
    a, b = res["new/dense"]["state"], res["old/dense"]["state"]
    for f in ("out_edges", "in_edges"):
        check(np.array_equal(np.sort(getattr(a, f), 1),
                             np.sort(getattr(b, f), 1)), f"old != new: {f}")
    check(res["new/dense"]["stats"]["synapses_formed"]
          == res["old/dense"]["stats"]["synapses_formed"],
          "old != new: synapses_formed")
    check(res["old/dense"]["stats"]["tree_nodes_downloaded"] > 0
          and res["new/dense"]["stats"]["tree_nodes_downloaded"] == 0,
          "old downloads tree nodes and new does not")
    print("old == new: edge tables bit-identical", flush=True)
    print(f"peak_bytes_in_use: {peak_bytes(devices)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--neurons-per-rank", type=int, default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    print(f"compile cache: {use_compile_cache()}", flush=True)
    devices = tpu_devices(args.chips)
    from repro.configs.msp_brain import CONFIG
    cfg = CONFIG
    if args.neurons_per_rank not in (None, CONFIG.neurons_per_rank):
        print(f"cut: neurons_per_rank {CONFIG.neurons_per_rank} -> "
              f"{args.neurons_per_rank} per chip (--neurons-per-rank)",
              flush=True)
        cfg = dataclasses.replace(CONFIG,
                                  neurons_per_rank=args.neurons_per_rank)
    (one_chip if args.chips == 1 else four_chips)(cfg, devices)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
