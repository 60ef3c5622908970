"""The on-device sort/apply kernels (PR: whole-chunk device residency):
``radix_argsort`` vs ``jnp.argsort(stable=True)`` on adversarial inputs, a
Morton-code known-answer test, and the fused ``morton_sort`` /
``synapse_apply`` / ``route_build`` kernels vs the exact jnp reference
expressions they replace — all in interpret mode (CPU CI)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.msp_brain import BrainConfig
from repro.connectome import routing
from repro.connectome import synapses as syn
from repro.connectome import tree as ctree
from repro.core import morton
from repro.kernels import ops as kops
from repro.kernels.radix_sort import bucket_ranks, stable_ranks


def _assert_matches_argsort(keys):
    k = jnp.asarray(keys, jnp.int32)
    s, order = kops.radix_argsort(k, interpret=True)
    ref = jnp.argsort(k, stable=True)
    np.testing.assert_array_equal(np.asarray(order), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(k[ref]))


# ------------------------------------------------------------- radix sort
@pytest.mark.parametrize("name,keys", [
    ("all_equal", np.full(257, 123)),
    ("pre_sorted", np.arange(300)),
    ("reversed", np.arange(300)[::-1].copy()),
    ("single", np.array([7])),
    ("two_buckets", np.array([1, 0] * 100)),
    ("max_range", np.array([2**30 - 1, 0, 2**30 - 1, 5])),
])
def test_radix_argsort_adversarial(name, keys):
    """Stable-argsort bit-identity on the classic adversarial layouts."""
    _assert_matches_argsort(keys)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 2**30 - 1), min_size=1, max_size=600))
def test_radix_argsort_matches_argsort_random(keys):
    _assert_matches_argsort(np.array(keys))


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 9), min_size=1, max_size=400),
       st.integers(10, 300))
def test_stable_ranks_match_argsort_and_positions_within(ids, nb):
    """The kernel-side rank primitives == their host-shaped counterparts:
    ``stable_ranks`` is the inverse of the stable argsort permutation,
    ``bucket_ranks`` is ``positions_within``."""
    k = jnp.asarray(ids, jnp.int32)
    order = jnp.argsort(k, stable=True)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(k.shape[0]))
    np.testing.assert_array_equal(np.asarray(stable_ranks(k, nb)),
                                  np.asarray(inv))
    np.testing.assert_array_equal(np.asarray(bucket_ranks(k, nb)),
                                  np.asarray(ctree.positions_within(k, nb)))


# ------------------------------------------------------------ morton KAT
def test_morton_code_known_answers():
    """Known-answer interleave: cell (i, j, k) at level L encodes to
    sum_t i_t<<3t | j_t<<(3t+1) | k_t<<(3t+2)."""
    # level 1: (i, j, k) = (1, 0, 1) -> 1 + 0 + 4 = 5
    pos = jnp.array([[0.6, 0.4, 0.7]])
    np.testing.assert_array_equal(np.asarray(morton.morton_encode(pos, 1)),
                                  [5])
    # level 3: (i, j, k) = (3, 5, 6); bits i=011, j=101, k=110 ->
    # t0: 1+2+0=3; t1: 8+0+32=40; t2: 0+128+256=384; total 427
    pos = jnp.array([[(3 + 0.5) / 8, (5 + 0.5) / 8, (6 + 0.5) / 8]])
    np.testing.assert_array_equal(np.asarray(morton.morton_encode(pos, 3)),
                                  [427])
    # corners of the unit cube at any level
    np.testing.assert_array_equal(
        np.asarray(morton.morton_encode(jnp.zeros((1, 3)), 4)), [0])
    np.testing.assert_array_equal(
        np.asarray(morton.morton_encode(jnp.ones((1, 3)) * 0.999, 4)),
        [8**4 - 1])


def test_morton_sort_kernel_matches_reference_path():
    """(rel, slot) from the kernel == the reference morton_encode +
    positions_within pair, including out-of-block clipping."""
    rng = np.random.default_rng(3)
    pos = jnp.asarray(rng.random((257, 3)), jnp.float32)
    for num_ranks, rank in [(1, 0), (4, 2)]:
        b = morton.branch_level(num_ranks)
        c_per = morton.cells_per_rank(num_ranks)
        lloc = 3
        leaf_level, n_leaf = b + lloc, c_per * 8**lloc
        base = rank * c_per * 8**lloc
        rel_ref = jnp.clip(morton.morton_encode(pos, leaf_level) - base,
                           0, n_leaf - 1)
        slot_ref = ctree.positions_within(rel_ref, n_leaf)
        rel, slot = kops.morton_sort(pos, base, leaf_level=leaf_level,
                                     n_leaf=n_leaf, interpret=True)
        np.testing.assert_array_equal(np.asarray(rel), np.asarray(rel_ref))
        np.testing.assert_array_equal(np.asarray(slot), np.asarray(slot_ref))


def test_tree_impl_fused_builds_identical_tree():
    """build_local_tree_fused == build_local_tree leaf-for-leaf (counts,
    centroids, membership table, base cell)."""
    rng = np.random.default_rng(11)
    cfg = BrainConfig(neurons_per_rank=96, local_levels=3, frontier_cap=32,
                      max_synapses=8)
    pos = jnp.asarray(rng.random((96, 3)), jnp.float32)
    w = jnp.asarray(rng.random(96) * 2, jnp.float32)
    ref = ctree.build_local_tree(pos, w, 0, cfg, 1)
    fus = ctree.build_local_tree_fused(pos, w, 0, cfg, 1, interpret=True)
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(fus)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------- synapse apply
def _random_tables(rng, n=48, s_max=8, qm=16, qr=24):
    edges = syn.compact(jnp.asarray(
        rng.integers(-1, n * 2, (n, s_max)), jnp.int32))
    msg_lid = jnp.asarray(rng.integers(0, n, qm), jnp.int32)
    msg_gid = jnp.asarray(rng.integers(0, n * 2, qm), jnp.int32)
    msg_valid = jnp.asarray(rng.random(qm) < 0.7)
    req_lid = jnp.asarray(rng.integers(0, n, qr), jnp.int32)
    req_src = jnp.asarray(rng.integers(0, n * 2, qr), jnp.int32)
    req_valid = jnp.asarray(rng.random(qr) < 0.8)
    vac = jnp.asarray(rng.random(n) * 3, jnp.float32)
    return edges, msg_lid, msg_gid, msg_valid, req_lid, req_src, req_valid, \
        vac


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_synapse_apply_kernel_matches_reference_sequence(seed):
    """One kernel pass == remove_edges_by_messages -> compact ->
    accept_core, bit-for-bit, with both stages live at once."""
    rng = np.random.default_rng(seed)
    (edges, mlid, mgid, mval, rlid, rsrc, rval, vac) = _random_tables(rng)
    key = jax.random.key(seed % 1000)
    prio = syn.request_priority(key, rlid, rsrc, rval)

    ref = syn.remove_edges_by_messages(edges, mlid, mgid, mval)
    ref = syn.compact(ref)
    acc_ref, ref = syn.accept_core(rlid, rsrc, rval, vac, ref, prio)

    out, acc = kops.synapse_apply(edges, mlid, mgid, mval, rlid, rsrc, rval,
                                  prio, vac, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(acc_ref))


def test_apply_impl_fused_stage_identities():
    """The fused deletion/accept entry points (each disabling the other
    stage) == the reference ApplyImpl callables."""
    from repro.sim import registry
    rng = np.random.default_rng(5)
    (edges, mlid, mgid, mval, rlid, rsrc, rval, vac) = _random_tables(rng)
    key = jax.random.key(9)
    ref = registry.resolve("apply", "reference")
    fus = registry.resolve("apply", "fused")
    np.testing.assert_array_equal(
        np.asarray(ref.deletion(edges, mlid, mgid, mval)),
        np.asarray(fus.deletion(edges, mlid, mgid, mval, interpret=True)))
    a0, n0 = ref.accept(rlid, rsrc, rval, vac, edges, key)
    a1, n1 = fus.accept(rlid, rsrc, rval, vac, edges, key, interpret=True)
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(a1))
    np.testing.assert_array_equal(np.asarray(n0), np.asarray(n1))


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_route_build_kernel_matches_route_deletions(seed):
    """The fused routing-buffer build == the pre-collective half of
    route_deletions (buffer and dropped count)."""
    rng = np.random.default_rng(seed)
    n, s_max, num_ranks = 40, 8, 4
    cfg = dataclasses.replace(
        BrainConfig(neurons_per_rank=n, local_levels=2, frontier_cap=32,
                    max_synapses=s_max))
    edges = jnp.asarray(rng.integers(-1, n * num_ranks, (n, s_max)),
                        jnp.int32)
    kill = (edges >= 0) & jnp.asarray(rng.random((n, s_max)) < 0.5)
    gcol = jnp.arange(n, dtype=jnp.int32)[:, None]
    flat_other = jnp.where(kill, edges, -1).reshape(-1)
    flat_mine = jnp.broadcast_to(gcol, kill.shape).reshape(-1)
    cap = routing.cap_deletions(cfg, False)
    buf_ref, drop_ref = routing.route_build_core(
        flat_other, flat_mine, n, num_ranks, cap, ctree.positions_within)
    buf, drop = kops.route_build(flat_other, flat_mine, n=n,
                                 num_ranks=num_ranks, cap=cap,
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(buf), np.asarray(buf_ref))
    assert float(drop[0]) == float(drop_ref)
