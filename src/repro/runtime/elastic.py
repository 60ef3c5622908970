"""Elastic re-meshing: resume a run on a different device count.

Checkpoints store full logical arrays (checkpoint/manager.py), so elasticity
is purely a sharding concern: build the new mesh from surviving devices,
recompute the sharding rules (they depend only on mesh axis sizes), and
device_put each restored array with its new sharding. Batch sizes stay global
(the data pipeline reshards rows by (seed, step, row) identity, so the token
stream is unchanged).
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh

from repro.checkpoint.manager import latest_step, restore
from repro.parallel import sharding as shd


def best_mesh_shape(n_devices: int, model_parallel: int = 0):
    """Factor n_devices into (data, model); model defaults to the largest
    power of two <= sqrt(n)."""
    if model_parallel <= 0:
        model_parallel = 1
        while model_parallel * 2 <= int(math.sqrt(n_devices)) and \
                n_devices % (model_parallel * 2) == 0:
            model_parallel *= 2
    assert n_devices % model_parallel == 0
    return (n_devices // model_parallel, model_parallel)


def make_elastic_mesh(devices=None, model_parallel: int = 0) -> Mesh:
    devs = jax.devices() if devices is None else devices
    da, mo = best_mesh_shape(len(devs), model_parallel)
    return Mesh(np.array(devs).reshape(da, mo), ("data", "model"))


def remesh_restore(ckpt_dir: str, target_tree, new_mesh: Mesh):
    """Load latest checkpoint and reshard every leaf onto ``new_mesh``."""
    step = latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no checkpoint in {ckpt_dir}")
    shardings = {
        "params": shd.make_param_shardings(
            jax.eval_shape(lambda t: t, target_tree["params"]), new_mesh),
        "opt": {
            "m": shd.make_param_shardings(
                jax.eval_shape(lambda t: t, target_tree["opt"]["m"]),
                new_mesh, opt_state=True),
            "v": shd.make_param_shardings(
                jax.eval_shape(lambda t: t, target_tree["opt"]["v"]),
                new_mesh, opt_state=True),
            "step": shd.replicated(new_mesh),
        },
    }
    tree, manifest = restore(ckpt_dir, step, target_tree, shardings)
    return step, tree, shardings


# ===================================================================== brain
def _latest_valid(ckpt_dir: str):
    """Newest step whose arrays pass verification, with its contents."""
    from repro.checkpoint import manager
    for step in reversed(manager.steps_available(ckpt_dir)):
        try:
            arrays, manifest = manager.load_arrays(ckpt_dir, step)
        except manager.CorruptCheckpointError:
            continue
        return step, arrays, manifest
    raise FileNotFoundError(f"no valid brain checkpoint in {ckpt_dir}")


def _collapse_ranks(key: str, arr: np.ndarray, r_old: int,
                    r_new: int) -> np.ndarray:
    """Fold a per-rank (R_old, ...) metrics leaf down to (R_new, ...):
    counters/rings/hists sum within each merged rank group (global sums —
    including the conservation-check inputs — are preserved); the psum'd
    ``health_flags`` gauge is a replicated bitmask and folds with max."""
    grouped = arr.reshape(r_new, r_old // r_new, *arr.shape[1:])
    if key.endswith("health_flags"):
        return np.asarray(grouped.max(axis=1))
    return np.asarray(grouped.sum(axis=1))


def remesh_restore_brain(ckpt_dir: str, cfg, mesh=None, step=None,
                         scenario=None, profile_dir=None):
    """Restore a brain checkpoint onto a Simulator built for ``cfg`` —
    possibly with a different rank count or exchange layout than the
    writer's. Returns ``(sim, step)``.

    Why this works (DESIGN.md §10): checkpoints store full logical arrays
    in gid order, and ``gid == global row index`` is invariant under
    re-meshing (gid = rank*n + lid with ranks owning consecutive rows), so
    the per-neuron state, positions, and the gid-valued edge tables pass
    through unchanged. The Morton domain decomposition of the new rank
    count covers the same contiguous cell span per merged rank group
    whenever ``R_new`` divides ``R_old`` (8^b' / R' cells starting at
    r'*8^b'/R' == the union of the old ranks' spans), so every neuron
    stays inside its owner's subdomain — the invariant the octree build
    needs. Growing the rank count would SPLIT ranks, and neuron order
    within a rank is not Morton-sorted, so growth is rejected.

    The rank-local exchange state is not resharded but re-derived: the
    dense (R, n) table is the gathered rate vector (reshape), and the
    sparse subscription registry / slot remap / rate buffer are rebuilt
    device-side by ``Simulator.rebuild_exchange`` — the same computation
    the chunk's exchange phase runs, hence bit-identical at a chunk
    boundary. Metrics leaves fold per merged rank group (sum; flags max).
    """
    from repro.checkpoint import manager
    from repro.core import spikes as core_spikes
    from repro.sim.api import Simulator

    if step is None:
        step, arrays, manifest = _latest_valid(ckpt_dir)
    else:
        arrays, manifest = manager.load_arrays(ckpt_dir, step)
    meta = manifest.get("metadata", {})

    sim = Simulator(cfg, scenario=scenario, mesh=mesh,
                    profile_dir=profile_dir)
    r_new, n_new = sim.num_ranks, cfg.neurons_per_rank
    n_total = arrays[".positions"].shape[0]
    r_old = int(meta.get("num_ranks",
                         n_total // int(meta.get("neurons_per_rank", n_new))))
    if r_new * n_new != n_total:
        raise ValueError(
            f"checkpoint holds {n_total} neurons; cfg gives "
            f"{r_new} ranks x {n_new} = {r_new * n_new}")
    if r_old % r_new != 0:
        raise ValueError(
            f"elastic brain resume requires the new rank count to divide "
            f"the old ({r_old} -> {r_new}): growing splits ranks whose "
            f"neurons are not Morton-sorted")

    target_leaves, treedef = manager._flatten(jax.eval_shape(sim.init_fn))
    shard_leaves, _ = manager._flatten(sim.shardings())
    out = []
    for i, (key, leaf) in enumerate(target_leaves):
        if key == ".rates_table":
            if ".rates_table" in arrays:           # dense -> dense
                arr = arrays[key].reshape(leaf.shape)
            else:                                   # sparse -> dense
                arr = arrays[".neurons/.rate"].reshape(leaf.shape)
        elif key == ".subs":
            arr = np.full(leaf.shape, int(core_spikes.NO_SUB), np.int32)
        elif key == ".rate_slots":
            arr = np.full(leaf.shape, -1, np.int32)
        elif key == ".remote_rates":
            arr = np.zeros(leaf.shape, np.float32)
        elif key.startswith(".stats/"):
            arr = _collapse_ranks(key, arrays[key], r_old, r_new)
        else:
            arr = arrays.get(key)
            if arr is None:
                raise KeyError(f"checkpoint missing leaf {key!r}")
            if list(arr.shape) != list(leaf.shape):
                raise ValueError(
                    f"{key}: shape {arr.shape} != {leaf.shape}")
        out.append(jax.device_put(np.asarray(arr), shard_leaves[i][1]))
    sim._state = jax.tree_util.tree_unflatten(treedef, out)
    sim.host_chunk = int(step)
    # re-derive the sparse registry for THIS rank count (no-op for dense)
    sim.rebuild_exchange()
    sim.lifecycle.update({k: int(v) for k, v in
                          meta.get("lifecycle", {}).items()})
    sim.lifecycle["checkpoint_restores"] += 1
    return sim, step
