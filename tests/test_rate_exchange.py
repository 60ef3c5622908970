"""Sparse subscription-based rate exchange (DESIGN.md §7): registry/remap
construction against numpy oracles, dense-vs-sparse reconstruction parity,
engine-level plumbing, overflow accounting, and the lookup_spikes binary
search property-tested against a dense membership oracle."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.msp_brain import BrainConfig
from repro.connectome import routing
from repro.core import engine, spikes
from repro.kernels.activity_fused import reconstruct_remote_spikes

INT_MAX = np.iinfo(np.int32).max


def _rand_edges(rng, n, s_max, num_ranks, p_empty=0.3):
    e = rng.integers(0, num_ranks * n, size=(n, s_max), dtype=np.int32)
    e[rng.random((n, s_max)) < p_empty] = -1
    return e


# ---------------------------------------------------------------- registry
def test_build_subscriptions_matches_numpy_oracle():
    rng = np.random.default_rng(0)
    n, s_max, num_ranks, rank = 64, 8, 4, 1
    edges = _rand_edges(rng, n, s_max, num_ranks)
    want = np.unique(edges[(edges >= 0) & (edges // n != rank)])
    cap = routing.cap_subs(
        BrainConfig(neurons_per_rank=n, max_synapses=s_max,
                    subs_cap_factor=1000), num_ranks)
    assert cap >= want.size
    subs, slots, ovf = jax.jit(
        spikes.build_subscriptions, static_argnums=(1, 2, 3))(
        jnp.asarray(edges), rank, n, cap)
    subs, slots = np.asarray(subs), np.asarray(slots)
    assert float(ovf) == 0.0
    # sorted unique remote gids, NO_SUB-padded
    np.testing.assert_array_equal(subs[:want.size], want)
    assert (subs[want.size:] == INT_MAX).all()
    # remap: every remote edge points at its gid's slot, others at -1
    for i in range(n):
        for j in range(s_max):
            src = edges[i, j]
            if src >= 0 and src // n != rank:
                assert subs[slots[i, j]] == src
            else:
                assert slots[i, j] == -1


def test_build_subscriptions_all_local_or_empty():
    n = 16
    edges = jnp.asarray([[0, 5, -1, 15]] * n, jnp.int32)   # rank 0's own gids
    subs, slots, ovf = spikes.build_subscriptions(edges, 0, n, 8)
    assert (np.asarray(subs) == INT_MAX).all()
    assert (np.asarray(slots) == -1).all()
    assert float(ovf) == 0.0


def test_build_subscriptions_overflow_counted():
    """More unique remote sources than subs_cap: the smallest gids keep
    their slots, the rest are dropped (slot -1) and counted."""
    n, cap = 8, 4
    edges = jnp.asarray([np.arange(n, 2 * n, dtype=np.int32)], jnp.int32)
    edges = jnp.broadcast_to(edges, (n, n))                # 8 unique remotes
    subs, slots, ovf = spikes.build_subscriptions(edges, 0, n, cap)
    assert float(ovf) == float(n - cap)
    np.testing.assert_array_equal(np.asarray(subs),
                                  np.arange(n, n + cap, dtype=np.int32))
    slots = np.asarray(slots)
    assert (slots[:, :cap] == np.arange(cap)).all()
    assert (slots[:, cap:] == -1).all()


def test_cap_subs_ceiling():
    cfg = BrainConfig(neurons_per_rank=64, max_synapses=8,
                      subs_cap_factor=10 ** 6)
    # head-room factor saturates at min(n*s_max, (R-1)*n)
    assert routing.cap_subs(cfg, 4) == min(64 * 8, 3 * 64)
    assert routing.cap_subs(cfg, 2) == min(64 * 8, 64)
    small = dataclasses.replace(cfg, subs_cap_factor=1)
    assert 32 <= routing.cap_subs(small, 4) <= 3 * 64


# ---------------------------------------------------------------- parity
def test_reconstruct_sparse_equals_dense():
    """Given a registry consistent with the dense table, the compact-buffer
    reconstruction draws bit-identical remote spikes (same edge-keyed
    Bernoulli stream, same rates)."""
    rng = np.random.default_rng(3)
    n, s_max, num_ranks, rank = 48, 8, 4, 2
    edges = jnp.asarray(_rand_edges(rng, n, s_max, num_ranks))
    table = jnp.asarray(rng.random((num_ranks, n), dtype=np.float32) * 0.3)
    subs, slots, ovf = spikes.build_subscriptions(edges, rank, n, 256)
    assert float(ovf) == 0.0
    safe = jnp.where(subs == spikes.NO_SUB, 0, subs)
    remote_rates = jnp.where(subs == spikes.NO_SUB, 0.0,
                             table[safe // n, safe % n])
    for gstep in (0, 7, 123):
        dense = reconstruct_remote_spikes(0, jnp.int32(gstep), table, edges,
                                          rank, n)
        sparse = reconstruct_remote_spikes(0, jnp.int32(gstep), remote_rates,
                                           edges, rank, n, rate_slots=slots)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(sparse))
    assert np.asarray(dense).sum() > 0, "no remote spikes drawn at all"


# ---------------------------------------------------------------- engine
def test_engine_sparse_equals_dense_single_rank():
    """Plumbing check on one rank (the cross-rank bit-identity sweep —
    3 library scenarios x both lowerings x 4 ranks — runs in
    tests/test_multidevice.py)."""
    base = BrainConfig(neurons_per_rank=48, local_levels=3, frontier_cap=32,
                       max_synapses=8, rate_period=25)
    mesh = engine.make_brain_mesh()
    res = {}
    for rex in ("dense", "sparse"):
        cfg = dataclasses.replace(base, rate_exchange=rex)
        init_fn, chunk = engine.build_sim(cfg, mesh)
        stt = init_fn()
        for _ in range(3):
            stt = chunk(stt)
        res[rex] = stt
    a, b = res["dense"], res["sparse"]
    for f in ("v", "u", "calcium", "rate", "spike_count"):
        np.testing.assert_array_equal(np.asarray(getattr(a.neurons, f)),
                                      np.asarray(getattr(b.neurons, f)),
                                      err_msg=f)
    np.testing.assert_array_equal(np.asarray(a.in_edges),
                                  np.asarray(b.in_edges))
    # layout-dependent state: dense holds the table, sparse the registry
    assert a.subs is None and a.rates_table is not None
    assert b.rates_table is None and b.subs is not None
    # single rank has no remote sources: nothing subscribed, nothing pushed
    assert float(b.stats["rates_sent"].sum()) == 0.0
    assert (np.asarray(b.subs) == INT_MAX).all()


def test_unknown_rate_exchange_raises():
    # unknown variant names fail eagerly at config construction
    with pytest.raises(ValueError, match="rate_exchange"):
        BrainConfig(rate_exchange="banana")


def test_window_hbm_bytes_sparse_model():
    """The megakernel's analytic traffic model: sparse swaps the (R, n)
    rates operand for the (subs_cap,) buffer + (n, S) slot remap — a win
    once R*n outgrows subs_cap + n*s_max."""
    from repro.kernels.activity_fused import window_hbm_bytes
    n, s_max, r, cap = 1024, 32, 64, 512
    dense = window_hbm_bytes(n, s_max, r)
    sparse = window_hbm_bytes(n, s_max, r, subs_cap=cap)
    assert dense - sparse == r * n * 4 - (cap * 4 + n * s_max * 4)
    assert sparse < dense
    # small meshes go the other way: the slot table outweighs a tiny table
    assert window_hbm_bytes(n, s_max, 2, subs_cap=cap) > \
        window_hbm_bytes(n, s_max, 2)


# ---------------------------------------------------------------- lookup
def _lookup_case(rng, num_ranks, n, s_max):
    """Build (all_ids, in_edges, spiked) exactly like the old algorithm's
    send side: per-rank sorted spiked gids, INT_MAX pad."""
    spiked = rng.random((num_ranks, n)) < rng.random((num_ranks, 1))
    gids = np.arange(num_ranks * n, dtype=np.int32).reshape(num_ranks, n)
    all_ids = np.where(spiked, gids, INT_MAX).astype(np.int32)
    all_ids.sort(axis=1)
    edges = _rand_edges(rng, n, s_max, num_ranks)
    return all_ids, edges, spiked


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 5),
       st.integers(2, 40), st.integers(1, 9))
def test_lookup_spikes_matches_membership_oracle(seed, num_ranks, n, s_max):
    """The vectorized binary search == dense membership: an in-edge hits iff
    its source gid is in the sender rank's spiked set. Covers all-padded
    rows (ranks that spiked nowhere) by construction."""
    rng = np.random.default_rng(seed)
    all_ids, edges, spiked = _lookup_case(rng, num_ranks, n, s_max)
    got = np.asarray(spikes.lookup_spikes(jnp.asarray(all_ids),
                                          jnp.asarray(edges), n))
    flat = spiked.reshape(-1)
    want = (edges >= 0) & flat[np.clip(edges, 0, num_ranks * n - 1)]
    np.testing.assert_array_equal(got, want)


def test_lookup_spikes_all_padded_rows():
    """No rank spiked: every row is pure INT_MAX pad, nothing may hit."""
    n, s_max, num_ranks = 16, 4, 3
    all_ids = np.full((num_ranks, n), INT_MAX, np.int32)
    edges = _rand_edges(np.random.default_rng(1), n, s_max, num_ranks,
                        p_empty=0.2)
    got = np.asarray(spikes.lookup_spikes(jnp.asarray(all_ids),
                                          jnp.asarray(edges), n))
    assert not got.any()


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 5), st.integers(2, 40))
def test_exchange_spiked_ids_sorted_duplicate_free(seed, num_ranks, n):
    """Send-side invariant the binary search relies on: each row is sorted
    ascending and duplicate-free apart from the INT_MAX pad tail."""
    rng = np.random.default_rng(seed)
    spiked = jnp.asarray(rng.random(n) < 0.4)
    ids, count = spikes.exchange_spiked_ids(spiked, 0, n, None, 1)
    row = np.asarray(ids[0])
    assert (np.diff(row) >= 0).all()
    live = row[row != INT_MAX]
    assert live.size == int(count[0]) == int(np.asarray(spiked).sum())
    assert np.unique(live).size == live.size
