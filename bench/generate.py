"""The one traffic generator: the initial connectome of a cell, made from
``--seed`` by the parameters of its configuration and traffic mix.

``initial: "empty"`` places each rank's neurons uniformly in its own
Morton cells, with no synapse (the paper's growth protocol). Rows come out
in Morton order, so each rank's block of rows is spatially coherent, and
excitation is laid out per block of ``neurons_per_rank`` rows as the
program's population table expects. The same seed gives the same arrays
on any machine.
"""
from __future__ import annotations

import numpy as np

_MORTON_LEVEL = 9


def _part1by2(x):
    x = x.astype(np.uint32) & np.uint32(0x3FF)
    x = (x | (x << 16)) & np.uint32(0x030000FF)
    x = (x | (x << 8)) & np.uint32(0x0300F00F)
    x = (x | (x << 4)) & np.uint32(0x030C30C3)
    x = (x | (x << 2)) & np.uint32(0x09249249)
    return x


def _compact1by2(x):
    x = x.astype(np.uint32) & np.uint32(0x09249249)
    x = (x ^ (x >> 2)) & np.uint32(0x030C30C3)
    x = (x ^ (x >> 4)) & np.uint32(0x0300F00F)
    x = (x ^ (x >> 8)) & np.uint32(0x030000FF)
    x = (x ^ (x >> 16)) & np.uint32(0x000003FF)
    return x


def morton_codes(pos, level: int):
    g = 1 << level
    ijk = np.clip((pos * g).astype(np.int64), 0, g - 1).astype(np.uint32)
    return (_part1by2(ijk[:, 0]) | (_part1by2(ijk[:, 1]) << 1)
            | (_part1by2(ijk[:, 2]) << 2)).astype(np.int64)


def _cell_boxes(level: int):
    cells = np.arange(8 ** level, dtype=np.uint32)
    ijk = np.stack([_compact1by2(cells), _compact1by2(cells >> 1),
                    _compact1by2(cells >> 2)], axis=-1)
    size = 1.0 / (1 << level)
    lo = ijk.astype(np.float32) * size
    return np.stack([lo, lo + np.float32(size)], axis=1)


def excitation(num_neurons: int, block: int, fraction: float):
    """The first ``int(block * fraction)`` rows of each block excitatory."""
    return (np.arange(num_neurons) % block) < int(block * fraction)


def branch_level(ranks: int) -> int:
    """Octree level whose cells the ranks divide among them: the smallest
    b with 8^b >= ranks (0 for one rank)."""
    b = 0
    while 8 ** b < ranks:
        b += 1
    return b


def empty(num_neurons: int, block: int, fraction_excitatory: float,
          seed: int) -> dict:
    """No synapse; each block of ``block`` rows (one rank) placed uniformly
    in that rank's Morton cells at the branch level, Morton-ordered."""
    rng = np.random.default_rng(seed)
    ranks = num_neurons // block
    level = branch_level(ranks)
    per_rank = 8 ** level // ranks
    offset = rng.random((num_neurons, 3))
    cell = (np.arange(num_neurons) // block) * per_rank
    if per_rank > 1:
        cell = cell + rng.integers(0, per_rank, num_neurons)
    lo = _cell_boxes(level)[cell, 0]
    pos = np.clip(lo + offset / (1 << level), 0.0,
                  1.0 - 1e-6).astype(np.float32)
    order = np.lexsort((morton_codes(pos, _MORTON_LEVEL),
                        np.arange(num_neurons) // block))
    pos = pos[order]
    return {"positions": pos, "edges": np.zeros((0, 2), np.int32),
            "region_ids": np.zeros(num_neurons, np.int32),
            "region_names": ("all",),
            "region_boxes": np.array([[[0, 0, 0], [1, 1, 1]]], np.float32),
            "is_excitatory": excitation(num_neurons, block,
                                        fraction_excitatory)}


def initial(config: dict, traffic: dict, brain: dict, chips: int,
            seed: int) -> dict:
    """The cell's initial connectome as arrays (see module docstring)."""
    block = int(brain["neurons_per_rank"])
    n_total = block * chips
    frac = float(brain["fraction_excitatory"])
    kind = traffic["initial"]
    if kind == "empty":
        return empty(n_total, block, frac, seed)
    raise ValueError(f"unknown initial connectome {kind!r}")
