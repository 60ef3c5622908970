"""The per-chunk connectivity update (paper phase 3), orchestrated:

  3a  deletion by retraction — element loss breaks bound synapses, partners
      are notified via routed messages and regain vacant elements;
  3b  formation — octree build, branch-node exchange, phase-A search over
      the replicated top tree, then the algorithm pair (phase registry
      domain "connectivity"): 'old' downloads every subtree and searches
      locally, 'new' ships 42B requests to the owning rank (routing.py);
  3c  rate refresh + Delta-periodic rate exchange (registry domain
      "rate_exchange") — 'dense' all-gathers the replicated (R, n) table;
      'sparse' rebuilds the subscription registry from the just-updated
      in-edge table (subscriptions only change when the connectome does)
      and owners push only the subscribed rates (DESIGN.md §7).

All scenario effects (lesion masks) apply before the algorithm branch, so
old == new stays bit-identical under every protocol. Randomness: retraction
and acceptance use chunk-keyed jax.random priorities (rank-independent);
every Barnes-Hut draw uses the counter hash keyed by (chunk, source gid)
(connectome.traverse) — both reconstructible wherever the computation runs
(DESIGN.md §2/§6).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.connectome import routing
from repro.connectome import synapses as syn
from repro.connectome import traverse
from repro.connectome import tree as ctree
from repro.core import morton, spikes
from repro.core.neuron import refresh_rate
from repro.scenarios import protocol as proto
from repro.sim import registry


# ---------------------------------------------------------------- formation
@registry.register_phase("connectivity", "new")
def formation_phase_new(ctx, state, local_tree, vac_d_pos, out_edges,
                        in_edges, gids, branch_cell, owner, start_rel,
                        valid_a, k_accept, stats):
    """Paper's NEW algorithm: ship 42B formation-and-calculation requests
    to the rank that owns the target subtree (move compute to the data)."""
    tgt_gid, accept, ovf, searched = routing.formation_new(
        ctx.cfg, state.positions, local_tree, vac_d_pos, in_edges, gids,
        branch_cell, owner, start_rel, valid_a, ctx.rank, ctx.axis_name,
        ctx.num_ranks, k_accept, state.chunk)
    in_edges = accept.pop("in_edges")
    stats = stats.count("request_overflow", ovf)
    stats = stats.count("bh_responses", jnp.sum(accept["accepted"]))
    # restart depths and frontier overflow of the phase-B searches THIS
    # rank executed (the received requests) — identical under both
    # traversal lowerings
    stats = ctx.metrics.traversal(stats, *searched,
                                  traverse.phase_b_levels(ctx.cfg))
    out_edges = syn.add_out_edges(out_edges, tgt_gid, accept["accepted"])
    stats = stats.count("synapses_formed", jnp.sum(accept["accepted"]))
    return out_edges, in_edges, stats


@registry.register_phase("connectivity", "old")
def formation_phase_old(ctx, state, local_tree, vac_d_pos, out_edges,
                        in_edges, gids, branch_cell, owner, start_rel,
                        valid_a, k_accept, stats):
    """Paper's OLD baseline: download every remote subtree + leaf neuron
    data ("RMA download with caching") and finish the search locally."""
    tgt_gid, accepted, new_in, downloaded, searched = \
        routing.formation_old(
            ctx.cfg, state.positions, local_tree, vac_d_pos, in_edges, gids,
            branch_cell, valid_a, ctx.rank, ctx.axis_name, ctx.num_ranks,
            k_accept, state.chunk)
    out_edges = syn.add_out_edges(out_edges, tgt_gid, accepted)
    stats = stats.count("tree_nodes_downloaded", downloaded)
    # restart depths and frontier overflow of MY searchers against the
    # downloaded global tree
    stats = ctx.metrics.traversal(stats, *searched,
                                  traverse.phase_b_levels(ctx.cfg))
    stats = stats.count("synapses_formed", jnp.sum(accepted))
    return out_edges, new_in, stats


# ---------------------------------------------------------------- exchange
@registry.register_phase("rate_exchange", "dense")
def exchange_dense(ctx, state, neurons, in_edges, stats):
    """All-gather every rank's full (n,) rate vector into the replicated
    (R, n) table — O(R*n) bytes per rank per Delta (reference layout)."""
    n = ctx.cfg.neurons_per_rank
    rates_table = spikes.exchange_rates(neurons.rate, ctx.axis_name,
                                        ctx.num_ranks)
    # every rank broadcasts its full n rates to the other R-1 ranks —
    # rates_sent counts rate records actually shipped over the wire
    stats = stats.count("rates_sent", float(n * max(ctx.num_ranks - 1, 0)))
    return rates_table, state.subs, state.rate_slots, state.remote_rates, \
        stats


@registry.register_phase("rate_exchange", "sparse")
def exchange_sparse(ctx, state, neurons, in_edges, stats):
    """Demand-driven push: rebuild the subscription registry from the
    just-updated in-edge table (subscriptions only change when the
    connectome does — computation moves to the data), then owners push
    exactly the subscribed rates — O(unique remote sources) instead of
    O(R*n)."""
    cfg, n = ctx.cfg, ctx.cfg.neurons_per_rank
    subs, rate_slots, ovf = spikes.build_subscriptions(
        in_edges, ctx.rank, n, routing.cap_subs(cfg, ctx.num_ranks))
    # counted both in the aggregate drop counter and in a dedicated key
    # (benchmarks must not infer it from the shared aggregate)
    stats = stats.count("request_overflow", ovf)
    stats = stats.count("subscription_overflow", ovf)
    # one registry-occupancy histogram entry per chunk (sparse only —
    # the dense layout has no registry and leaves the histogram zero)
    stats = ctx.metrics.subs_occupancy(stats, subs, spikes.NO_SUB)
    remote_rates, pushed = routing.push_subscribed_rates(
        subs, neurons.rate, ctx.axis_name, ctx.num_ranks, n)
    # the exchange ships one 4B request id out AND one 4B rate back per
    # subscription — both streams are counted (Tables I/II honesty)
    stats = stats.count("subscription_requests", pushed)
    stats = stats.count("rates_sent", pushed)
    return state.rates_table, subs, rate_slots, remote_rates, stats


# ---------------------------------------------------------------- update
def connectivity_update(state, ctx):
    """One structural-plasticity update. ``state`` is the engine's
    BrainState (any NamedTuple with neurons/out_edges/in_edges/positions,
    the rate-exchange fields rates_table (dense) or subs/rate_slots/
    remote_rates (sparse), chunk, and stats); ``ctx`` a
    ``repro.sim.phases.PhaseContext``. Returns the state updated with chunk
    advanced."""
    cfg, rank = ctx.cfg, ctx.rank
    axis_name, num_ranks = ctx.axis_name, ctx.num_ranks
    n = cfg.neurons_per_rank
    # chunk_key is rank-independent: every rank derives the same stream, so
    # per-(gid) sub-streams are reproducible wherever the computation runs —
    # the property that makes old == new bit-identical (DESIGN.md §2)
    chunk_key = jax.random.fold_in(jax.random.key(cfg.seed + 2), state.chunk)
    gid0 = rank * n
    gids = gid0 + jnp.arange(n, dtype=jnp.int32)
    stats = state.stats          # telemetry.metrics.Metrics (immutable)

    # lesion mask at the update instant (the step right after this chunk's
    # activity scan). Applied BEFORE the algorithm branch so 'old' and 'new'
    # see identical inputs — the bit-identity invariant holds per protocol.
    alive = proto.alive_mask(ctx.events, ctx.regions, state.positions,
                             (state.chunk + 1) * cfg.rate_period) \
        if ctx.events else None
    if alive is not None:
        # dead neurons lose all synaptic elements -> full retraction below,
        # partners are notified and regain vacant elements
        state = state._replace(neurons=state.neurons._replace(
            ax_elements=jnp.where(alive, state.neurons.ax_elements, 0.0),
            de_elements=jnp.where(alive, state.neurons.de_elements, 0.0)))

    # ---- deletion by retraction (phase 3a) -------------------------------
    with jax.named_scope("repro.conn.retraction"):
        out_edges, in_edges = state.out_edges, state.in_edges
        out_cnt, in_cnt = syn.counts(out_edges), syn.counts(in_edges)
        del_out = jnp.maximum(
            out_cnt - jnp.floor(state.neurons.ax_elements).astype(jnp.int32),
            0)
        del_in = jnp.maximum(
            in_cnt - jnp.floor(state.neurons.de_elements).astype(jnp.int32),
            0)
        k_out, k_in, k_accept = jax.random.split(chunk_key, 3)
        out_edges, kill_out = syn.retract_synapses(k_out, out_edges, del_out,
                                                   gids)
        in_edges, kill_in = syn.retract_synapses(k_in, in_edges, del_in, gids)
        stats = stats.count("synapses_deleted",
                            jnp.sum(kill_out) + jnp.sum(kill_in))

        # notify partners; kill masks index the PRE-retraction tables.
        # Routing + table mutation dispatch through the "apply" registry
        # domain ('fused' = the VMEM-resident kernels, bit-identical)
        apply_impl = registry.resolve("apply", cfg.apply_impl)
        lesions = proto.has_lesions(ctx.scenario)
        msgs_out, ovf_out = apply_impl.route(
            kill_out, state.out_edges, gids[:, None], cfg, axis_name,
            num_ranks, lesions)
        msgs_in, ovf_in = apply_impl.route(
            kill_in, state.in_edges, gids[:, None], cfg, axis_name, num_ranks,
            lesions)
        # dropped notifications leave stale partner edges — surface them
        stats = stats.count("request_overflow", ovf_out + ovf_in)
        # apply: partner of my out-edge removes its in-edge, and vice versa
        # (each table drains its messages and re-compacts in one stage)
        in_edges = apply_impl.deletion(
            in_edges, jnp.clip(msgs_out[:, 0] - gid0, 0, n - 1),
            msgs_out[:, 1],
            (msgs_out[:, 0] >= gid0) & (msgs_out[:, 0] < gid0 + n))
        out_edges = apply_impl.deletion(
            out_edges, jnp.clip(msgs_in[:, 0] - gid0, 0, n - 1),
            msgs_in[:, 1],
            (msgs_in[:, 0] >= gid0) & (msgs_in[:, 0] < gid0 + n))

    # ---- formation (phase 3b) --------------------------------------------
    out_cnt, in_cnt = syn.counts(out_edges), syn.counts(in_edges)
    vac_a = jnp.floor(state.neurons.ax_elements).astype(jnp.int32) - out_cnt
    vac_d = state.neurons.de_elements - in_cnt.astype(jnp.float32)
    vac_d_pos = jnp.maximum(vac_d, 0.0)

    with jax.named_scope("repro.conn.tree_build"):
        # registry domain "tree": 'reference' (jnp Morton sort) | 'fused'
        # (Pallas radix-sort kernel), bit-identical builds
        local_tree = ctree.build_tree(cfg, state.positions, vac_d_pos, rank,
                                      num_ranks)
        top = ctree.exchange_branch_nodes(local_tree, axis_name, num_ranks)
        stats = ctx.metrics.tree_built(stats, local_tree)

    searching = vac_a >= 1
    if alive is not None:
        # dead neurons neither search for partners nor offer vacancies
        searching = searching & alive
        vac_d_pos = jnp.where(alive, vac_d_pos, 0.0)
    with jax.named_scope("repro.conn.phase_a"):
        branch_cell, valid_a = traverse.phase_a(top, state.positions, gids,
                                                cfg, num_ranks,
                                                chunk=state.chunk)
    valid_a = valid_a & searching
    c_per = morton.cells_per_rank(num_ranks)
    owner = jnp.clip(branch_cell // c_per, 0, num_ranks - 1)
    start_rel = branch_cell - owner * c_per
    stats = stats.count("bh_requests", jnp.sum(valid_a))
    # either algorithm sends one formation request per valid searcher (17 B
    # plain / 42 B formation-and-calculation — Tables I/II accounting)
    stats = stats.count("formation_requests", jnp.sum(valid_a))

    formation = registry.resolve("connectivity", cfg.connectivity_alg)
    with jax.named_scope("repro.conn.formation"):
        out_edges, in_edges, stats = formation(
            ctx, state, local_tree, vac_d_pos, out_edges, in_edges, gids,
            branch_cell, owner, start_rel, valid_a, k_accept, stats)

    # ---- rate refresh + Delta-periodic exchange (phase 3c) ---------------
    neurons = refresh_rate(state.neurons, cfg, alive)
    rates_table = state.rates_table
    subs, rate_slots = state.subs, state.rate_slots
    remote_rates = state.remote_rates
    if cfg.spike_alg != "old":
        # (on the old spike path the rate state is dead — skip the
        # per-chunk exchange and its accounting entirely)
        exchange = registry.resolve("rate_exchange", cfg.rate_exchange)
        with jax.named_scope("repro.conn.exchange"):
            rates_table, subs, rate_slots, remote_rates, stats = exchange(
                ctx, state, neurons, in_edges, stats)
    return state._replace(neurons=neurons, out_edges=out_edges,
                          in_edges=in_edges, rates_table=rates_table,
                          subs=subs, rate_slots=rate_slots,
                          remote_rates=remote_rates,
                          chunk=state.chunk + 1, stats=stats)
