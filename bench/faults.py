"""Faults planted in the program's timed path, for the check of
``correct``: each breaks one thing the connectivity update guarantees, and
a run with it in place has to come out not correct.

    formation_skipped   phase B forms nothing
    requests_dropped    nine searchers in ten (gid not a multiple of ten)
                        lose their request, and no counter says so
    kernel_ignored      the search picks targets without the distance
                        kernel (every distance weighs alike)

A fault is in place from ``planted(name)`` until its block ends, so the
cell's program has to be compiled inside the block.
"""
from __future__ import annotations

import contextlib

NAMES = ("formation_skipped", "requests_dropped", "kernel_ignored")


@contextlib.contextmanager
def planted(name: str):
    import jax.numpy as jnp
    from repro.connectome import traverse
    from repro.sim import registry

    registry.ensure_loaded()
    key = ("connectivity", "new")
    formation = registry._IMPLS[key]
    gauss = traverse._gauss

    def skipped(ctx, state, local_tree, vac_d_pos, out_edges, in_edges,
                *rest):
        return out_edges, in_edges, rest[-1]

    def dropped(*args):
        args = list(args)
        gids, valid_a = args[6], args[10]
        args[10] = valid_a & (gids % 10 == 0)
        return formation(*args)

    if name == "formation_skipped":
        registry._IMPLS[key] = skipped
    elif name == "requests_dropped":
        registry._IMPLS[key] = dropped
    elif name == "kernel_ignored":
        traverse._gauss = lambda d2, sigma: jnp.ones_like(d2)
    else:
        raise KeyError(f"no fault {name!r}; known: {NAMES}")
    try:
        yield
    finally:
        registry._IMPLS[key] = formation
        traverse._gauss = gauss
