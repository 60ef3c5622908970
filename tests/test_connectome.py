"""Connectome subsystem: octree membership-cap overflow, stable bucket-rank
property, vectorized synapse-table ops vs the sequential semantics, and the
Pallas Barnes-Hut traversal kernel — kernel-vs-reference bit-identity plus
the engine-level old==new invariant under ``connectivity_impl='fused'``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.msp_brain import BrainConfig
from repro.connectome import routing, synapses, traverse
from repro.connectome import tree as ctree
from repro.core import engine
from repro.kernels import ops as kops
from repro.scenarios import Lesion, Recover, Scenario, Stimulate, library


# ---------------------------------------------------------------- tree
@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 7), min_size=1, max_size=40))
def test_positions_within_stable_bucket_ranks(ids):
    """positions_within(ids)[i] counts the EARLIER occurrences of ids[i] —
    the stable-rank property every router (deletion messages, formation
    request slots, leaf membership) relies on."""
    a = jnp.asarray(ids, jnp.int32)
    got = np.asarray(ctree.positions_within(a, 8))
    want = [sum(1 for j in range(i) if ids[j] == ids[i])
            for i in range(len(ids))]
    np.testing.assert_array_equal(got, np.asarray(want))


@pytest.mark.parametrize("members_cap", [1, 2, 4])
def test_build_local_tree_members_cap_overflow(members_cap):
    """A leaf holding more neurons than members_cap keeps exactly the cap
    many, lowest-indexed first (stable), never corrupting other cells; the
    count/centroid aggregates still see every neuron."""
    cfg = BrainConfig(neurons_per_rank=12, local_levels=2)
    # 8 neurons stacked into one leaf cell, 4 spread elsewhere
    dense = jnp.tile(jnp.array([[0.03, 0.03, 0.03]]), (8, 1))
    sparse = jnp.array([[0.9, 0.9, 0.9], [0.6, 0.2, 0.2],
                        [0.2, 0.6, 0.2], [0.2, 0.2, 0.6]])
    pos = jnp.concatenate([dense, sparse])
    w = jnp.ones((12,))
    tree = ctree.build_local_tree(pos, w, 0, cfg, num_ranks=1,
                                  members_cap=members_cap)
    assert tree.leaf_members.shape[1] == members_cap
    from repro.core import morton
    cell = int(morton.morton_encode(dense[:1], cfg.local_levels)[0])
    row = np.asarray(tree.leaf_members[cell])
    # cap many members, stable: the lowest original indices win
    np.testing.assert_array_equal(row, np.arange(members_cap))
    # every other row holds no phantom members from the overflow
    members = np.asarray(tree.leaf_members)
    listed = members[members >= 0]
    assert len(listed) == len(set(listed.tolist()))
    overflow_victims = set(range(members_cap, 8))
    assert not (set(listed.tolist()) & overflow_victims)
    # aggregation is unaffected by the cap
    np.testing.assert_allclose(float(tree.counts[0].sum()), 12.0, rtol=1e-6)


# ---------------------------------------------------------------- synapses
def _seq_remove(edges, msg_lid, msg_gid, msg_valid):
    """The seed's sequential drain: each message removes the then-first
    matching slot of its row."""
    e = np.asarray(edges).copy()
    for lid, gid, ok in zip(msg_lid, msg_gid, msg_valid):
        if not ok:
            continue
        hits = np.where(e[int(lid)] == int(gid))[0]
        if len(hits):
            e[int(lid), hits[0]] = -1
    return e


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_remove_edges_by_messages_matches_sequential(seed):
    """The vectorized segment/cumsum removal == the sequential message drain,
    including duplicate messages, repeated edge values, and no-op messages."""
    rng = np.random.default_rng(seed)
    n, s_max, q = 5, 6, 16
    edges = rng.integers(-1, 7, size=(n, s_max)).astype(np.int32)
    lid = rng.integers(0, n, size=q).astype(np.int32)
    gid = rng.integers(-1, 7, size=q).astype(np.int32)
    valid = rng.random(q) < 0.75
    got = np.asarray(synapses.remove_edges_by_messages(
        jnp.asarray(edges), jnp.asarray(lid), jnp.asarray(gid),
        jnp.asarray(valid)))
    np.testing.assert_array_equal(got, _seq_remove(edges, lid, gid, valid))


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_compact_is_stable_front_packing(seed):
    rng = np.random.default_rng(seed)
    edges = rng.integers(-1, 9, size=(4, 7)).astype(np.int32)
    got = np.asarray(synapses.compact(jnp.asarray(edges)))
    for i in range(edges.shape[0]):
        occ = edges[i][edges[i] >= 0]
        want = np.concatenate([occ, -np.ones(7 - len(occ), np.int32)])
        np.testing.assert_array_equal(got[i], want)


# ---------------------------------------------------------------- kernel
def _phase_b_inputs(n=96, q=75, local_levels=3, key=0):
    """A local tree + batch of queries with a non-block-multiple Q (so the
    kernel's query padding is exercised)."""
    cfg = BrainConfig(neurons_per_rank=n, local_levels=local_levels,
                      frontier_cap=32, max_synapses=8)
    k = jax.random.key(key)
    pos = jax.random.uniform(jax.random.fold_in(k, 1), (n, 3), maxval=0.999)
    vac = jax.random.uniform(jax.random.fold_in(k, 2), (n,)) * 2
    tree = ctree.build_local_tree(pos, vac, 0, cfg, num_ranks=1)
    x = jax.random.uniform(jax.random.fold_in(k, 3), (q, 3), maxval=0.999)
    gids = jnp.arange(q, dtype=jnp.int32)
    start = jnp.zeros((q,), jnp.int32)
    valid = jnp.arange(q) % 5 != 0         # a few masked queries
    return cfg, tree, pos, vac, x, gids, start, valid


@pytest.mark.parametrize("block_q", [32, 128])
def test_bh_traverse_kernel_bit_identical_to_reference(block_q):
    """The Pallas traversal kernel (interpret) == the jnp phase_b_core, bit
    for bit, across query blockings — the connectivity_impl contract."""
    cfg, tree, pos, vac, x, gids, start, valid = _phase_b_inputs()
    stacked = traverse.stack_levels(tree.counts, tree.centroids, 0)
    kw = dict(seed=cfg.seed, sizes=stacked.sizes, theta=cfg.theta,
              sigma=cfg.sigma, frontier=cfg.frontier_cap,
              n_levels=cfg.local_levels + 1)
    chunk, gid_base = jnp.int32(3), jnp.int32(0)
    want = jax.jit(lambda: traverse.phase_b_core(
        stacked.counts, stacked.centroids, tree.leaf_members, pos, vac, x,
        start, gids, valid, chunk, gid_base, **kw))()
    from repro.kernels.bh_traverse import bh_traverse
    got = jax.jit(lambda: bh_traverse(
        stacked.counts, stacked.centroids, tree.leaf_members, pos, vac, x,
        start, gids, valid, chunk, gid_base, block_q=block_q,
        interpret=True, **kw))()
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert int(jnp.sum(got[1])) > 0, "no query found a partner at all"
    # masked queries stay masked
    assert not np.any(np.asarray(got[1])[::5])


def test_bh_traverse_prng_is_location_independent():
    """The Gumbel stream depends only on (seed, chunk, gid, round, draw):
    permuting the query batch permutes the results exactly — the property
    that lets the owning rank re-derive a remote searcher's stream."""
    cfg, tree, pos, vac, x, gids, start, valid = _phase_b_inputs()
    stacked = traverse.stack_levels(tree.counts, tree.centroids, 0)
    kw = dict(seed=cfg.seed, sizes=stacked.sizes, theta=cfg.theta,
              sigma=cfg.sigma, frontier=cfg.frontier_cap,
              n_levels=cfg.local_levels + 1)
    chunk, gid_base = jnp.int32(1), jnp.int32(0)
    perm = jnp.asarray(np.random.default_rng(7).permutation(x.shape[0]))
    a = traverse.phase_b_core(stacked.counts, stacked.centroids,
                              tree.leaf_members, pos, vac, x, start, gids,
                              valid, chunk, gid_base, **kw)
    b = traverse.phase_b_core(stacked.counts, stacked.centroids,
                              tree.leaf_members, pos, vac, x[perm],
                              start[perm], gids[perm], valid[perm], chunk,
                              gid_base, **kw)
    np.testing.assert_array_equal(np.asarray(a[0])[perm], np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1])[perm], np.asarray(b[1]))


@pytest.mark.parametrize("live,q,block", [
    ("all", 80, 16),
    ("every_fifth_masked", 80, 16),
    ("none", 80, 16),
    ("every_fifth_masked", 75, 16),     # Q not a multiple of the block
    ("every_fifth_masked", 75, 128),    # Q below the block
])
def test_packed_phase_b_reference_equals_phase_b_core(live, q, block):
    """The reference lowering runs each restart only over the rows still
    searching, packed into row blocks; it gives ``phase_b_core``'s targets
    on every row, and its depths and overflow flags on the live rows, bit
    for bit, and counts whole blocks."""
    cfg, tree, pos, vac, x, gids, start, valid = _phase_b_inputs(q=q)
    valid = {"all": jnp.ones((q,), bool), "none": jnp.zeros((q,), bool),
             "every_fifth_masked": valid}[live]
    stacked = traverse.stack_levels(tree.counts, tree.centroids, 0)
    kw = dict(seed=cfg.seed, sizes=stacked.sizes, theta=cfg.theta,
              sigma=cfg.sigma, frontier=cfg.frontier_cap,
              n_levels=traverse.phase_b_levels(cfg))
    chunk, gid_base = jnp.int32(2), jnp.int32(0)
    want = jax.jit(lambda: traverse.phase_b_core(
        stacked.counts, stacked.centroids, tree.leaf_members, pos, vac, x,
        start, gids, valid, chunk, gid_base, **kw))()
    got = jax.jit(lambda: traverse.phase_b_reference(
        stacked, tree, pos, vac, x, start, gids, valid, chunk, gid_base, kw,
        block=block))()
    live_rows = np.asarray(valid)
    for k in (0, 1):                                   # target, ok
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))
    for k in (2, 3):                                   # depth, overflow
        np.testing.assert_array_equal(np.asarray(got[k])[live_rows],
                                      np.asarray(want[k])[live_rows])
    rows_run, b = int(got[4]), min(q, block)
    assert rows_run % b == 0
    assert rows_run >= int(np.asarray(want[2])[live_rows].sum())
    if live == "none":
        assert rows_run == 0
    else:
        assert int(jnp.sum(got[1])) > 0, "no query found a partner at all"
        assert rows_run < q * kw["n_levels"]
    if live == "all":
        assert rows_run > 2 * b, "the search ran fewer than three blocks"


def test_connectivity_impl_validation():
    # unknown variant names fail eagerly at config construction
    base = BrainConfig(neurons_per_rank=16, local_levels=2, frontier_cap=32,
                       max_synapses=4)
    with pytest.raises(ValueError, match="connectivity_impl"):
        dataclasses.replace(base, connectivity_impl="bogus")
    with pytest.raises(ValueError, match="tree_impl"):
        dataclasses.replace(base, tree_impl="bogus")
    with pytest.raises(ValueError, match="apply_impl"):
        dataclasses.replace(base, apply_impl="bogus")


# ---------------------------------------------------------------- retract
def _retract_argsort_oracle(key, edges, n_delete, row_gids):
    """The pre-PR full per-row stable argsort over priorities — the oracle
    the masked top-k rank-by-counting must match bit-for-bit."""
    n, s_max = edges.shape
    occupied = edges >= 0
    flat_prio = synapses.edge_priority(
        key, jnp.broadcast_to(row_gids[:, None], edges.shape).reshape(-1),
        jnp.where(occupied, edges, 0).reshape(-1))
    prio = jnp.where(occupied, flat_prio.reshape(edges.shape), 2.0)
    order = jnp.argsort(prio, axis=1, stable=True)
    ranks = jnp.zeros_like(edges).at[
        jnp.arange(n)[:, None], order].set(jnp.arange(s_max)[None, :])
    kill = occupied & (ranks < n_delete[:, None])
    return jnp.where(kill, -1, edges), kill


def _check_retract_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    n, s_max = 24, 8
    edges = jnp.asarray(rng.integers(-1, 30, (n, s_max)), jnp.int32)
    # n_delete beyond occupancy and zero both occur
    nd = jnp.asarray(rng.integers(0, s_max + 2, n), jnp.int32)
    gids = jnp.asarray(rng.integers(0, 200, n), jnp.int32)
    key = jax.random.key(seed % 2**31)
    got_e, got_k = synapses.retract_synapses(key, edges, nd, gids)
    want_e, want_k = _retract_argsort_oracle(key, edges, nd, gids)
    np.testing.assert_array_equal(np.asarray(got_e), np.asarray(want_e))
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_retract_topk_matches_argsort_oracle(seed):
    """The masked top-k-by-priority retraction == the full per-row argsort
    it replaced, bit-for-bit (same Threefry priority stream)."""
    _check_retract_matches_oracle(seed)


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_retract_topk_matches_argsort_oracle_random(seed):
    _check_retract_matches_oracle(seed)


# ---------------------------------------------------------------- engine
SMALL = dataclasses.replace(library.SMOKE_SCENARIO_CONFIG,
                            neurons_per_rank=48, max_synapses=8,
                            rate_period=25)


def _scaled(scn: Scenario, div=20) -> Scenario:
    evs = []
    for e in scn.events:
        if isinstance(e, Stimulate):
            evs.append(dataclasses.replace(
                e, t0=e.t0 // div, t1=max(e.t1 // div, e.t0 // div + 10)))
        elif isinstance(e, (Lesion, Recover)):
            evs.append(dataclasses.replace(e, t=e.t // div))
    return dataclasses.replace(scn, events=tuple(evs))


def test_engine_fused_connectivity_equals_reference():
    """connectivity_impl='fused' commits bit-identical edge tables AND
    neuron state through the full jitted sim."""
    mesh = engine.make_brain_mesh()
    res = {}
    for impl in ("reference", "fused"):
        cfg = dataclasses.replace(SMALL, connectivity_impl=impl)
        init_fn, chunk = engine.build_sim(cfg, mesh)
        st = init_fn()
        for _ in range(3):
            st = chunk(st)
        res[impl] = st
    a, b = res["reference"], res["fused"]
    np.testing.assert_array_equal(np.asarray(a.out_edges),
                                  np.asarray(b.out_edges))
    np.testing.assert_array_equal(np.asarray(a.in_edges),
                                  np.asarray(b.in_edges))
    for f in ("v", "calcium", "ax_elements", "de_elements", "rate"):
        np.testing.assert_array_equal(np.asarray(getattr(a.neurons, f)),
                                      np.asarray(getattr(b.neurons, f)),
                                      err_msg=f)
    assert float(a.stats["synapses_formed"].sum()) > 0
    assert float(a.stats["formation_requests"].sum()) > 0  # tracked on 'new'


def test_engine_fused_tree_apply_equals_reference():
    """tree_impl='fused' + apply_impl='fused' (the radix-sort tree build and
    the VMEM-resident synapse-apply kernels) commit bit-identical edge
    tables AND neuron state through the full jitted sim at a single rank —
    the acceptance contract of the whole-chunk-residency PR. The lesion
    scenario drives BOTH stages of the kernel live (formation and
    deletion)."""
    scn = _scaled(library.get_scenario("lesion_rewiring"))
    mesh = engine.make_brain_mesh()
    res = {}
    for impl in ("reference", "fused"):
        cfg = dataclasses.replace(SMALL, tree_impl=impl, apply_impl=impl)
        init_fn, chunk = engine.build_sim(cfg, mesh, scenario=scn)
        st = init_fn()
        for _ in range(3):
            st = chunk(st)
        res[impl] = st
    a, b = res["reference"], res["fused"]
    np.testing.assert_array_equal(np.asarray(a.out_edges),
                                  np.asarray(b.out_edges))
    np.testing.assert_array_equal(np.asarray(a.in_edges),
                                  np.asarray(b.in_edges))
    for f in ("v", "calcium", "ax_elements", "de_elements", "rate"):
        np.testing.assert_array_equal(np.asarray(getattr(a.neurons, f)),
                                      np.asarray(getattr(b.neurons, f)),
                                      err_msg=f)
    assert float(a.stats["synapses_formed"].sum()) > 0
    assert float(a.stats["synapses_deleted"].sum()) > 0


@pytest.mark.parametrize("name", sorted(library.SCENARIOS))
def test_fused_tree_apply_old_new_identical(name):
    """THE paper invariant under the new kernels: with fused tree build and
    fused apply, both connectivity algorithms still commit bit-identical
    edge tables, for every library scenario (lesion protocols exercise the
    big-cap deletion routing path through the route_build kernel)."""
    scn = _scaled(library.get_scenario(name))
    mesh = engine.make_brain_mesh()
    res = {}
    for alg in ("old", "new"):
        cfg = dataclasses.replace(SMALL, tree_impl="fused",
                                  apply_impl="fused", connectivity_alg=alg)
        init_fn, chunk = engine.build_sim(cfg, mesh, scenario=scn)
        st = init_fn()
        for _ in range(3):
            st = chunk(st)
        res[alg] = (np.sort(np.asarray(st.out_edges), 1),
                    np.sort(np.asarray(st.in_edges), 1),
                    float(st.stats["synapses_formed"].sum()))
    assert res["old"][2] == res["new"][2] > 0
    np.testing.assert_array_equal(res["old"][0], res["new"][0])
    np.testing.assert_array_equal(res["old"][1], res["new"][1])


@pytest.mark.parametrize("name", sorted(library.SCENARIOS))
def test_fused_connectivity_old_new_identical(name):
    """THE paper invariant under the traversal kernel: with
    connectivity_impl='fused' both connectivity algorithms still commit
    bit-identical edge tables, for every library scenario."""
    scn = _scaled(library.get_scenario(name))
    mesh = engine.make_brain_mesh()
    res = {}
    for alg in ("old", "new"):
        cfg = dataclasses.replace(SMALL, connectivity_impl="fused",
                                  connectivity_alg=alg)
        init_fn, chunk = engine.build_sim(cfg, mesh, scenario=scn)
        st = init_fn()
        for _ in range(3):
            st = chunk(st)
        res[alg] = (np.sort(np.asarray(st.out_edges), 1),
                    np.sort(np.asarray(st.in_edges), 1),
                    float(st.stats["synapses_formed"].sum()))
    assert res["old"][2] == res["new"][2] > 0
    np.testing.assert_array_equal(res["old"][0], res["new"][0])
    np.testing.assert_array_equal(res["old"][1], res["new"][1])


# ---------------------------------------------------------------- routing
def test_formation_requests_counted_on_new_path():
    """42B formation-and-calculation requests show up in stats on the new
    algorithm path (they used to be tracked only for 'old')."""
    cfg = dataclasses.replace(SMALL, connectivity_alg="new")
    mesh = engine.make_brain_mesh()
    init_fn, chunk = engine.build_sim(cfg, mesh)
    st = init_fn()
    for _ in range(3):
        st = chunk(st)
    fr = float(st.stats["formation_requests"].sum())
    bh = float(st.stats["bh_requests"].sum())
    assert fr == bh > 0
