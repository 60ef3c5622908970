"""Brain-sim core: Morton/octree invariants (hypothesis property tests), BH
search sanity, single-rank MSP dynamics."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.msp_brain import BrainConfig
from repro.core import barnes_hut as bh
from repro.core import connectivity as conn
from repro.core import engine, morton, octree


# ---------------------------------------------------------------- morton
@settings(max_examples=50, deadline=None)
@given(st.lists(st.floats(0.0, 0.999), min_size=3, max_size=3),
       st.integers(1, 8))
def test_morton_roundtrip_center(pos, level):
    p = jnp.asarray([pos])
    code = morton.morton_encode(p, level)
    center = morton.morton_cell_center(code, level)
    # the center must lie in the same cell
    assert int(morton.morton_encode(center, level)[0]) == int(code[0])
    # and within half a cell of the point per axis
    assert np.all(np.abs(np.asarray(center - p)) <= morton.cell_size(level))


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 512))
def test_branch_level_consistency(r):
    b = morton.branch_level(r)
    assert 8 ** b >= r
    if r > 1:
        assert 8 ** (b - 1) < r or b == 1
    if r & (r - 1) == 0:  # powers of two: paper's 1/2/4 consecutive cells
        assert morton.cells_per_rank(r) in (1, 2, 4, 8)


# ---------------------------------------------------------------- octree
@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(8, 64))
def test_octree_aggregation_conserves_mass(seed, n):
    cfg = BrainConfig(neurons_per_rank=n, local_levels=3)
    key = jax.random.key(seed)
    pos = jax.random.uniform(key, (n, 3), minval=0.0, maxval=0.999)
    w = jax.random.uniform(jax.random.fold_in(key, 1), (n,)) * 2
    tree = octree.build_local_tree(pos, w, 0, cfg, num_ranks=1)
    total = float(jnp.sum(w))
    for lvl, c in enumerate(tree.counts):
        np.testing.assert_allclose(float(jnp.sum(c)), total, rtol=1e-5,
                                   err_msg=f"level {lvl}")
    # centroid sums also conserved
    zsum = np.asarray(jnp.sum(pos * w[:, None], axis=0))
    for z in tree.centroids:
        np.testing.assert_allclose(np.asarray(jnp.sum(z, 0)), zsum, rtol=1e-4)


def test_octree_parent_equals_child_sum():
    cfg = BrainConfig(neurons_per_rank=128, local_levels=3)
    pos = jax.random.uniform(jax.random.key(0), (128, 3), maxval=0.999)
    w = jnp.ones((128,))
    tree = octree.build_local_tree(pos, w, 0, cfg, num_ranks=1)
    for k in range(len(tree.counts) - 1):
        parent = np.asarray(tree.counts[k])
        child = np.asarray(tree.counts[k + 1]).reshape(-1, 8).sum(1)
        np.testing.assert_allclose(parent, child, rtol=1e-6)


def test_leaf_members_point_to_correct_cells():
    cfg = BrainConfig(neurons_per_rank=64, local_levels=2)
    pos = jax.random.uniform(jax.random.key(1), (64, 3), maxval=0.999)
    tree = octree.build_local_tree(pos, jnp.ones(64), 0, cfg, num_ranks=1)
    members = np.asarray(tree.leaf_members)
    codes = np.asarray(morton.morton_encode(pos, cfg.local_levels))
    for cell in range(members.shape[0]):
        for m in members[cell]:
            if m >= 0:
                assert codes[m] == cell


# ---------------------------------------------------------------- BH search
def test_bh_search_prefers_nearby_mass():
    """With a heavy nearby cluster and a light far one, most samples land
    near the searcher."""
    cfg = BrainConfig(neurons_per_rank=64, local_levels=3, frontier_cap=64)
    near = jax.random.uniform(jax.random.key(2), (56, 3)) * 0.2 + 0.05
    far = jax.random.uniform(jax.random.key(3), (8, 3)) * 0.2 + 0.75
    pos = jnp.concatenate([near, far])
    tree = octree.build_local_tree(pos, jnp.ones(64), 0, cfg, num_ranks=1)
    stacked = bh.stack_levels(tree.counts, tree.centroids, 0)
    q = 64
    x = jnp.tile(jnp.array([[0.1, 0.1, 0.1]]), (q, 1))
    cell, valid, overflow, depth = bh.bh_search(
        stacked, x, jnp.arange(q, dtype=jnp.int32),
        jnp.zeros((q,), jnp.int32), seed=4, chunk=jnp.int32(0),
        theta=cfg.theta, sigma=cfg.sigma, frontier=cfg.frontier_cap,
        n_levels=cfg.local_levels + 1)
    assert bool(jnp.all(valid))
    # every settled query ran at least one expand/sample round
    assert bool(jnp.all(depth >= 1))
    centers = morton.morton_cell_center(cell, cfg.local_levels)
    d = jnp.linalg.norm(centers - x, axis=-1)
    assert float((d < 0.4).mean()) > 0.8, float((d < 0.4).mean())


def test_bh_theta_zero_like_behavior_is_exact_leafs():
    """Small theta forces descent to leaf cells (few approximations)."""
    cfg = BrainConfig(neurons_per_rank=32, local_levels=2, frontier_cap=64)
    pos = jax.random.uniform(jax.random.key(5), (32, 3), maxval=0.999)
    tree = octree.build_local_tree(pos, jnp.ones(32), 0, cfg, num_ranks=1)
    stacked = bh.stack_levels(tree.counts, tree.centroids, 0)
    cell, valid, _, _ = bh.bh_search(
        stacked, pos, jnp.arange(32, dtype=jnp.int32),
        jnp.zeros((32,), jnp.int32), seed=6, chunk=jnp.int32(0), theta=0.05,
        sigma=cfg.sigma, frontier=64, n_levels=cfg.local_levels + 1)
    # all returned nodes are leaf-level cells with actual neurons
    counts_leaf = np.asarray(tree.counts[-1])
    for c, v in zip(np.asarray(cell), np.asarray(valid)):
        if v:
            assert counts_leaf[c] > 0


# ---------------------------------------------------------------- dynamics
def test_single_rank_simulation_grows_towards_target():
    cfg = BrainConfig(neurons_per_rank=48, local_levels=3, frontier_cap=32,
                      max_synapses=24, fraction_excitatory=1.0)
    mesh = engine.make_brain_mesh()
    init_fn, chunk = engine.build_sim(cfg, mesh)
    st = init_fn()
    ca0 = float(st.neurons.calcium.mean())
    for _ in range(10):
        st = chunk(st)
    ca1 = float(st.neurons.calcium.mean())
    formed = float(st.stats["synapses_formed"].sum())
    assert ca1 > ca0 + 0.01, (ca0, ca1)
    assert formed > 0
    # in/out bookkeeping is globally consistent on one rank
    assert int((st.out_edges >= 0).sum()) == int((st.in_edges >= 0).sum())
    # no NaNs anywhere
    for leaf in jax.tree.leaves(st.neurons._asdict()):
        if leaf.dtype.kind == "f":
            assert bool(jnp.all(jnp.isfinite(leaf)))


def test_rate_window_refresh():
    from repro.core.neuron import init_neurons, refresh_rate
    cfg = BrainConfig()
    st = init_neurons(jax.random.key(0), cfg, 8)
    st = st._replace(spike_count=jnp.full((8,), 25.0))
    st = refresh_rate(st, cfg)
    np.testing.assert_allclose(np.asarray(st.rate), 0.25)
    assert float(st.spike_count.sum()) == 0.0


# ---------------------------------------------------------------- synapses
def test_accept_requests_respects_capacity():
    n, s_max = 4, 8
    in_edges = jnp.full((n, s_max), -1, jnp.int32)
    # 6 requests all to target 0, which has 2 vacant elements
    tgt = jnp.zeros((6,), jnp.int32)
    src = jnp.arange(100, 106, dtype=jnp.int32)
    valid = jnp.ones((6,), bool)
    vac = jnp.array([2.0, 0.0, 0.0, 0.0])
    acc, new_in = conn.accept_requests(tgt, src, valid, vac, in_edges,
                                       jax.random.key(0))
    assert int(acc.sum()) == 2
    assert int((new_in[0] >= 0).sum()) == 2
    assert int((new_in[1:] >= 0).sum()) == 0


def test_retract_and_remove_messages():
    edges = jnp.array([[5, 7, -1, -1], [3, -1, -1, -1]], jnp.int32)
    gids = jnp.array([0, 1], jnp.int32)
    new, kill = conn.retract_synapses(jax.random.key(1), edges,
                                      jnp.array([1, 0]), gids)
    assert int(kill.sum()) == 1
    assert int((new[0] >= 0).sum()) == 1
    # removal messages
    e2 = conn.remove_edges_by_messages(
        edges, jnp.array([0]), jnp.array([7]), jnp.array([True]))
    assert 7 not in np.asarray(e2[0])
    assert 5 in np.asarray(e2[0])


def test_compact():
    e = jnp.array([[-1, 3, -1, 9]], jnp.int32)
    c = conn.compact(e)
    np.testing.assert_array_equal(np.asarray(c[0]), [3, 9, -1, -1])
