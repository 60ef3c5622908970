"""Formation/deletion request routing over the ranks mesh — the paper's
byte-counted record exchanges (§IV-A):

OLD ("move data"): the searching rank downloads the remote subtrees (modeled
as the all-gather of every rank's local tree + leaf neuron data — the
cache-everything endpoint of the paper's RMA+cache scheme) and finishes the
search locally. Then a plain formation request (source id, target id, type:
17 B in the paper) is all-to-all exchanged for accept/decline.

NEW ("move compute", location-aware): the searching rank ships a
formation-AND-calculation request — source id, source position, target node,
node kind, cell type: 42 B — to the rank owning the branch cell; that rank
finishes the search against its own subtree (zero additional communication)
and answers with (found id, success): 9 B.

Both run the identical phase-B search code against the same tree content,
keyed to the searcher's gid (connectome.traverse), so they form bit-identical
synapses — tested in tests/test_multidevice.py and tests/test_connectome.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.connectome import synapses as syn
from repro.connectome import traverse
from repro.connectome import tree as ctree
from repro.sim import registry


def cap_requests(cfg, num_ranks: int):
    """Per-(source, dest)-rank request buffer capacity. Locality skews demand
    toward the home rank, so tests/benchmarks needing zero overflow set
    requests_cap_factor >= num_ranks (=> cap = n)."""
    n = cfg.neurons_per_rank
    per_dest = max(n // max(num_ranks, 1), 1) * cfg.requests_cap_factor
    return min(n, max(32, -(-per_dest // 8) * 8))


def subs_base(cfg, num_ranks: int) -> int:
    """The per-rank unique-remote-source estimate the subscription registry
    is sized from: the measured count baked into ``cfg.subs_cap_base`` by
    ``Simulator.from_connectome`` (heavy-tailed real connectomes), else the
    near-uniform synthetic default ``n // num_ranks``. ``cap_subs`` and the
    runner's degradation ladder (which inverts cap -> factor) must use the
    same base, so it lives in one place."""
    if getattr(cfg, "subs_cap_base", None) is not None:
        return max(int(cfg.subs_cap_base), 32)
    return max(cfg.neurons_per_rank // max(num_ranks, 1), 32)


def cap_subs(cfg, num_ranks: int):
    """Subscription-registry capacity for the sparse rate exchange. The hard
    ceiling is min(n * s_max, (R-1) * n) — a rank can never subscribe to more
    unique remote sources than it has in-edge slots or than exist remotely.
    ``subs_cap_factor`` scales the head-room over ``subs_base`` below that
    (tests and benchmarks that require sparse == dense bit-identity raise it
    until ``stats['request_overflow']`` stays zero, like
    requests_cap_factor; ``from_connectome`` instead measures the base)."""
    n = cfg.neurons_per_rank
    full = min(n * cfg.max_synapses, max(num_ranks - 1, 1) * n)
    per = subs_base(cfg, num_ranks) * cfg.subs_cap_factor
    return int(min(full, max(32, -(-per // 8) * 8)))


def push_subscribed_rates(subs, rate, axis_name, num_ranks: int, n: int):
    """Sparse exchange, per-Delta push: ship each rank's subscription
    requests to the owner ranks (tiled all_to_all, once per connectivity
    update — the registry only changes with the connectome) and have owners
    answer with exactly the subscribed rates.

    ``subs``: (subs_cap,) sorted unique remote gids (``spikes.NO_SUB`` pad);
    ``rate``: (n,) this rank's advertised rates. Returns ``(remote_rates,
    pushed)`` — the (subs_cap,) compact rate buffer aligned with ``subs``
    (0.0 on pads) and the number of rate records actually pushed to this
    rank (the real exchange volume, O(|subs|) instead of O(R·n))."""
    from repro.core.spikes import NO_SUB
    subs_cap = subs.shape[0]
    valid = subs != NO_SUB
    pushed = jnp.sum(valid).astype(jnp.float32)
    if num_ranks == 1:
        return jnp.zeros((subs_cap,), jnp.float32), pushed
    owner = jnp.where(valid, subs // n, num_ranks)
    # subs is sorted, so owners are contiguous; slot < subs_cap always holds
    # (at most subs_cap valid entries total) — per-owner cap never overflows
    slot = ctree.positions_within(owner, num_ranks + 1)
    req = jnp.full((num_ranks, subs_cap), -1, jnp.int32)
    req = req.at[jnp.where(valid, owner, num_ranks), slot].set(
        jnp.where(valid, subs % n, -1), mode="drop")
    req = jax.lax.all_to_all(req, axis_name, 0, 0, tiled=True)
    # req[p, j] is now the local id rank p subscribed to — answer with rates
    payload = jnp.where(req >= 0, rate[jnp.clip(req, 0, n - 1)], 0.0)
    payload = jax.lax.all_to_all(payload, axis_name, 0, 0, tiled=True)
    # payload[o, j] = rate of this rank's j-th request to owner o — realign
    remote_rates = jnp.where(
        valid, payload[jnp.where(valid, owner, 0), slot], 0.0)
    return remote_rates, pushed


def cap_deletions(cfg, lesions: bool = False):
    """Deletion-message buffer capacity. Lesion protocols retract EVERY edge
    of a dead neuron in one update, so the cap then scales with
    requests_cap_factor like the formation buffers (n * s_max is the most a
    rank can ever send to one destination); without lesions the seed's
    homeostatic trickle keeps the original small buffer (and its collective
    bytes) unchanged."""
    n = cfg.neurons_per_rank
    if not lesions:
        return max(16, n // 4)
    return min(n * cfg.max_synapses,
               max(16, (n // 4) * cfg.requests_cap_factor))


def route_build_core(flat_other, flat_mine, n: int, num_ranks: int, cap: int,
                     ranker):
    """Build the per-destination (num_ranks, cap, 2) notification buffers
    from the flattened (partner gid, my gid) pairs — the pre-collective half
    of ``route_deletions``, shared verbatim by the reference path and the
    fused kernel body (kernels/synapse_apply.py). ``ranker(ids, buckets)``
    supplies the stable within-destination slot ranks (``positions_within``
    or the kernel's per-bucket cumsum ``bucket_ranks`` — integer-identical).
    Returns (buf, dropped count)."""
    valid = flat_other >= 0
    dest = jnp.where(valid, flat_other // n, num_ranks)
    slot = ranker(dest, num_ranks + 1)
    ok = valid & (slot < cap)
    buf = jnp.full((num_ranks, cap, 2), -1, jnp.int32)
    buf = buf.at[jnp.where(ok, dest, num_ranks),
                 jnp.where(ok, slot, 0)].set(
        jnp.stack([jnp.where(ok, flat_other, -1),
                   jnp.where(ok, flat_mine, -1)], -1), mode="drop")
    return buf, jnp.sum(valid & ~ok).astype(jnp.float32)


def route_deletions(kill, edges, my_gid_col, cfg, axis_name, num_ranks: int,
                    lesions: bool):
    """All-to-all the (partner gid, my gid) retraction notifications (paper:
    'the affected partner gains a vacant element'). Returns the received
    (num_ranks * cap, 2) messages and the dropped-notification count."""
    n = cfg.neurons_per_rank
    flat_other = jnp.where(kill, edges, -1).reshape(-1)
    flat_mine = jnp.broadcast_to(my_gid_col, kill.shape).reshape(-1)
    cap = cap_deletions(cfg, lesions)
    buf, dropped = route_build_core(flat_other, flat_mine, n, num_ranks, cap,
                                    ctree.positions_within)
    if num_ranks > 1:
        buf = jax.lax.all_to_all(buf, axis_name, 0, 0, tiled=True)
    return buf.reshape(num_ranks * cap, 2), dropped


def formation_new(cfg, positions, local_tree, vacant_d, in_edges, gids,
                  branch_cell, owner, start_rel, valid_a, rank, axis_name,
                  num_ranks: int, key, chunk):
    """Location-aware algorithm: 42B requests out, local phase B + accept,
    9B responses back. Returns (tgt_gid, accept dict, overflow count,
    (depth, processed, frontier_overflow, rows_run)) — the
    per-received-request phase-B restart depth, its validity mask, the
    frontier overflow flags and the row-restarts phase B computed, recorded
    into the telemetry by the caller."""
    n = cfg.neurons_per_rank
    cap = cap_requests(cfg, num_ranks)
    dest = jnp.where(valid_a, owner, num_ranks)
    slot = ctree.positions_within(dest, num_ranks + 1)
    ok = valid_a & (slot < cap)
    ovf = jnp.sum(valid_a & ~ok).astype(jnp.float32)

    ibuf = jnp.full((num_ranks, cap, 2), -1, jnp.int32)   # src_gid, start_cell
    fbuf = jnp.zeros((num_ranks, cap, 3), jnp.float32)    # position
    d_c = jnp.where(ok, dest, num_ranks)
    s_c = jnp.where(ok, slot, 0)
    ibuf = ibuf.at[d_c, s_c].set(
        jnp.stack([jnp.where(ok, gids, -1), start_rel], -1), mode="drop")
    fbuf = fbuf.at[d_c, s_c].set(positions, mode="drop")
    if num_ranks > 1:
        ibuf = jax.lax.all_to_all(ibuf, axis_name, 0, 0, tiled=True)
        fbuf = jax.lax.all_to_all(fbuf, axis_name, 0, 0, tiled=True)

    r_src = ibuf[..., 0].reshape(-1)
    r_cell = ibuf[..., 1].reshape(-1)
    r_pos = fbuf.reshape(-1, 3)
    r_valid = r_src >= 0
    # the receiver re-derives the SAME per-searcher Gumbel stream from the
    # shipped source gid (counter-hash keyed by (chunk, gid) — DESIGN.md §2)
    tgt, bvalid, depth, frontier_ovf, rows_run = traverse.phase_b(
        local_tree, positions, vacant_d, r_pos,
        jnp.where(r_valid, r_src, -2), jnp.clip(r_cell, 0, None), r_valid,
        cfg, num_ranks, rank * n, chunk=chunk)
    # accept/decline where the target lives (same rank — no extra comms);
    # the table mutation dispatches through the "apply" registry domain
    apply_impl = registry.resolve("apply", cfg.apply_impl)
    with jax.named_scope("repro.conn.accept"):
        acc, new_in = apply_impl.accept(
            jnp.clip(tgt - rank * n, 0, n - 1), r_src, bvalid & (tgt >= 0),
            vacant_d, in_edges, key)
    # 9B responses retrace the request route
    rbuf = jnp.stack([jnp.where(acc, tgt, -1),
                      acc.astype(jnp.int32)], -1).reshape(num_ranks, cap, 2)
    if num_ranks > 1:
        rbuf = jax.lax.all_to_all(rbuf, axis_name, 0, 0, tiled=True)
    resp_tgt = rbuf[d_c, s_c, 0]
    resp_ok = (rbuf[d_c, s_c, 1] > 0) & ok
    return resp_tgt, {"accepted": resp_ok, "in_edges": new_in}, ovf, \
        (depth, r_valid, frontier_ovf, rows_run)


def formation_old(cfg, positions, local_tree, vacant_d, in_edges, gids,
                  branch_cell, valid_a, rank, axis_name, num_ranks: int, key,
                  chunk):
    """Baseline: download every rank's subtree + leaf data (RMA+cache
    endpoint), search locally, then exchange 17B formation requests.
    Returns (tgt_gid, accepted, new_in_edges, downloaded node count,
    (depth, searched, frontier_overflow, rows_run)) — the per-local-searcher
    phase-B restart depth, its mask, the frontier overflow flags and the
    row-restarts phase B computed, for the telemetry."""
    n = cfg.neurons_per_rank
    # ---- the download: all levels, members, positions, weights ----
    if num_ranks > 1:
        g_counts = tuple(jax.lax.all_gather(c, axis_name, axis=0, tiled=True)
                         for c in local_tree.counts)
        g_cents = tuple(jax.lax.all_gather(z, axis_name, axis=0, tiled=True)
                        for z in local_tree.centroids)
        members_g = jnp.where(local_tree.leaf_members >= 0,
                              local_tree.leaf_members + rank * n, -1)
        g_members = jax.lax.all_gather(members_g, axis_name, axis=0,
                                       tiled=True)
        g_pos = jax.lax.all_gather(positions, axis_name, axis=0, tiled=True)
        g_vac = jax.lax.all_gather(vacant_d, axis_name, axis=0, tiled=True)
    else:
        g_counts, g_cents = local_tree.counts, local_tree.centroids
        g_members = local_tree.leaf_members
        g_pos, g_vac = positions, vacant_d
    downloaded = (sum(c.shape[0] for c in g_counts) + g_pos.shape[0]) \
        * (num_ranks - 1) / max(num_ranks, 1)
    g_tree = ctree.LocalTree(g_counts, g_cents, g_members,
                             jnp.zeros((), jnp.int32))
    # ---- phase B locally for my searchers (same PRNG stream as 'new') ----
    tgt, bvalid, depth, frontier_ovf, rows_run = traverse.phase_b(
        g_tree, g_pos, g_vac, positions, gids, branch_cell, valid_a, cfg,
        num_ranks, 0, chunk=chunk)
    # ---- classic 17B formation request to the target's rank ----
    cap = cap_requests(cfg, num_ranks)
    dest = jnp.where(bvalid & (tgt >= 0), tgt // n, num_ranks)
    slot = ctree.positions_within(dest, num_ranks + 1)
    ok = (dest < num_ranks) & (slot < cap)
    ibuf = jnp.full((num_ranks, cap, 2), -1, jnp.int32)
    d_c = jnp.where(ok, dest, num_ranks)
    s_c = jnp.where(ok, slot, 0)
    ibuf = ibuf.at[d_c, s_c].set(
        jnp.stack([jnp.where(ok, gids, -1), jnp.where(ok, tgt, -1)], -1),
        mode="drop")
    if num_ranks > 1:
        ibuf = jax.lax.all_to_all(ibuf, axis_name, 0, 0, tiled=True)
    r_src = ibuf[..., 0].reshape(-1)
    r_tgt = ibuf[..., 1].reshape(-1)
    r_valid = (r_src >= 0) & (r_tgt >= 0)
    apply_impl = registry.resolve("apply", cfg.apply_impl)
    with jax.named_scope("repro.conn.accept"):
        acc, new_in = apply_impl.accept(
            jnp.clip(r_tgt - rank * n, 0, n - 1), r_src, r_valid, vacant_d,
            in_edges, key)
    rbuf = acc.astype(jnp.int32).reshape(num_ranks, cap)
    if num_ranks > 1:
        rbuf = jax.lax.all_to_all(rbuf, axis_name, 0, 0, tiled=True)
    accepted = (rbuf[d_c, s_c] > 0) & ok
    return tgt, accepted, new_in, jnp.asarray(downloaded, jnp.float32), \
        (depth, valid_a, frontier_ovf, rows_run)
