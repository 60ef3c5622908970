"""Vectorized Barnes-Hut partner search (paper §III-B0c / §IV-A).

The paper's recursive search — collect nodes meeting the acceptance criterion
(cell_size / distance < theta), sample one by connection probability, restart
inside it if it is an inner node — is reformulated level-synchronously for the
TPU: a static-size frontier per searching neuron is expanded in lockstep
(rejected nodes are replaced by their 8 children), then one Gumbel-max sample
selects the target; sampling an inner node restarts the expansion from it.

Static-shape deviations (documented in DESIGN.md §2/§6): the frontier is
capped at F entries — parents whose children would overflow are kept as
sampling candidates at coarser granularity; phase B returns each
query's overflow flag, and the telemetry counts the live ones
(``bh_frontier_overflow``).

Two restart drivers run the same ``expand_and_sample``: ``bh_search`` runs
every restart over every row (the Pallas kernel body and phase A);
``bh_search_packed``, phase B's reference lowering, runs each restart only
over the rows still searching, in row blocks of ``PACK_ROWS``.

Named scopes mark the stages for the profiler: ``repro.bh.search`` (the
restart loop, with the packed driver's row partition and block loop),
``repro.bh.expand`` (frontier set-up and expansion rounds),
``repro.bh.sample`` (the Gumbel pick) and ``repro.bh.member`` (member
selection). They change only HLO metadata.

PRNG contract: every Gumbel draw comes from the counter-based Threefry hash
(kernels/hash.py) keyed by ``(seed, BH_DOMAIN, bh_ctr(chunk, round, draw),
source_gid)`` — pure integers, no key arrays. Because the *same* stream is
derived from the source gid wherever the search executes — locally after
downloading remote subtrees (old algorithm), on the owning rank (new
location-aware algorithm), in the jnp reference path, or inside the Pallas
traversal kernel (kernels/bh_traverse.py) — all four make bit-identical
choices. Round slots: phase A expands from round 0, phase B from
``PHASE_B_ROUND_BASE``, member selection uses the last round.

Distances use the ``bh_gauss`` MXU identity |x|^2+|y|^2-2<x,y> with the
coordinate axis zero-padded to 8 lanes (``pairwise_d2``) so the kernel's
systolic-array mapping and the reference see identical floats.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import morton
from repro.kernels import hash as chash
from repro.sim import registry

NEG = -1e30
PAD = 8   # coordinate lanes (3 -> 8), the bh_gauss MXU alignment

PHASE_A_ROUND_BASE = 0
PHASE_B_ROUND_BASE = 16
MEMBER_ROUND = chash.BH_ROUNDS - 1
# query rows per block of the reference lowering's packed restart driver
# (``bh_search_packed``); Q below it runs as one block. Of 4,096, 8,192 and
# 16,384, 4,096 ran phase B fastest at 65,536 queries on a TPU v5e: the
# last block of each restart pads less
PACK_ROWS = 4096


class StackedTree(NamedTuple):
    """Uniform view of consecutive octree levels for traced indexing.
    counts: (L, C_max); centroids: (L, C_max, 3) cell centroids (mean
    position, zero for empty cells); sizes: STATIC tuple of L
    cell edge lengths (compile-time floats, so the Pallas kernel body closes
    over them instead of capturing a constant array).
    Level k covers absolute octree level (start_level + k); cell indices are
    relative to ``cell_base * 8^k`` (the owning subtree block)."""
    counts: jnp.ndarray
    centroids: jnp.ndarray
    sizes: tuple
    start_level: int


def stack_levels(counts_tuple, cents_tuple, start_level: int) -> StackedTree:
    """Stack the tree's levels; ``cents_tuple`` holds per-cell position
    SUMS, divided by the counts here, once per cell. Dividing after the
    per-query gather instead would materialise a (Q, F, 3) quotient, whose
    3-wide minor axis the TPU pads to 128 lanes (8 GB at 262,144 queries)."""
    lmax = max(c.shape[0] for c in counts_tuple)
    cs, zs = [], []
    for c, z in zip(counts_tuple, cents_tuple):
        pad = lmax - c.shape[0]
        cs.append(jnp.pad(c, (0, pad)))
        zs.append(jnp.pad(z / jnp.maximum(c, 1e-9)[:, None],
                          ((0, pad), (0, 0))))
    sizes = level_sizes(len(counts_tuple), start_level)
    return StackedTree(jnp.stack(cs), jnp.stack(zs), sizes, start_level)


def level_sizes(n_levels: int, start_level: int):
    """Static per-level cell edge lengths (the kernel takes these as a
    compile-time tuple)."""
    return tuple(morton.cell_size(start_level + k) for k in range(n_levels))


def _gauss(d2, sigma: float):
    return jnp.exp(-d2 / (sigma * sigma))


def _pad_lanes(x):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, PAD - x.shape[-1])])


def pairwise_d2(x, y):
    """||x - y||^2 for x: (Q, 3) against y: (Q, K, 3), via the MXU identity
    |x|^2 + |y|^2 - 2<x,y> with the coordinate axis zero-padded to 8 lanes —
    the same systolic-array mapping as kernels/bh_gauss.py, shared by the
    Pallas traversal kernel and the jnp reference so both see identical
    floats (precision caveat for tiny sigma documented in bh_gauss)."""
    xp = _pad_lanes(x.astype(jnp.float32))
    yp = _pad_lanes(y.astype(jnp.float32))
    xx = jnp.sum(xp * xp, axis=-1)[:, None]
    yy = jnp.sum(yp * yp, axis=-1)
    xy = jax.lax.dot_general(xp, yp, (((1,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    return jnp.maximum(xx + yy - 2.0 * xy, 0.0)


def _level_size_at(sizes, lvl_rel):
    """Per-entry cell edge length from the STATIC per-level tuple — a chain
    of scalar selects instead of a constant-array gather (Pallas kernel
    bodies may not capture constant arrays)."""
    out = jnp.full(lvl_rel.shape, jnp.float32(sizes[0]))
    for k in range(1, len(sizes)):
        out = jnp.where(lvl_rel == k, jnp.float32(sizes[k]), out)
    return out


def _node_stats(tree: StackedTree, lvl_rel, cell, x, sigma):
    """Vectorized gather of (count, prob-weight, size/dist) for entries.
    lvl_rel, cell: (Q, F) int; x: (Q, 3)."""
    cnt = tree.counts[lvl_rel, cell]
    d2 = pairwise_d2(x, tree.centroids[lvl_rel, cell])
    size = _level_size_at(tree.sizes, lvl_rel)
    crit = size / jnp.sqrt(jnp.maximum(d2, 1e-12))
    prob = cnt * _gauss(d2, sigma)
    return cnt, prob, crit


def _check_caps(frontier: int, round_base: int, restarts: int):
    if frontier > chash.BH_DRAWS:
        raise ValueError(f"frontier_cap {frontier} exceeds the PRNG draw "
                         f"window ({chash.BH_DRAWS})")
    if round_base + restarts > MEMBER_ROUND:
        raise ValueError(f"{restarts} restarts from round base {round_base} "
                         f"would collide with the member-selection round")


def expand_and_sample(tree: StackedTree, x, root_cell, root_rel, src_gid, rnd,
                      *, seed: int, chunk, theta: float, sigma: float,
                      frontier: int, n_levels: int):
    """One paper 'round': expand from the root node until every frontier entry
    meets the acceptance criterion (or is a deepest-level cell), then sample.

    x: (Q, 3); root_cell/root_rel: (Q,) current node (relative level index);
    src_gid: (Q,) searcher gids keying the Gumbel stream; rnd: scalar round
    index. Returns (cell, rel_level, valid, overflowed): all (Q,).
    """
    q = x.shape[0]
    f = frontier
    with jax.named_scope("repro.bh.expand"):
        cells, lvls, valid, overflow = _expand(
            tree, x, root_cell, root_rel, sigma=sigma, theta=theta, f=f,
            n_levels=n_levels)

    with jax.named_scope("repro.bh.sample"):
        cnt, prob, _ = _node_stats(tree, lvls, cells, x, sigma)
        logits = jnp.where(valid & (cnt > 1e-9),
                           jnp.log(jnp.maximum(prob, 1e-30)), NEG)
        g = chash.gumbel(seed, chash.BH_DOMAIN,
                         chash.bh_ctr(chunk, rnd, jnp.arange(f))[None, :],
                         src_gid[:, None])
        pick = jnp.argmax(logits + g, axis=1)
        qi = jnp.arange(q)
        any_valid = jnp.any(logits > NEG / 2, axis=1)
        return (cells[qi, pick], lvls[qi, pick], any_valid, overflow)


def _expand(tree: StackedTree, x, root_cell, root_rel, *, sigma, theta,
            f: int, n_levels: int):
    """The frontier set-up and the ``n_levels`` expansion rounds of
    ``expand_and_sample``. Returns (cells, lvls, valid, overflow): the
    final (Q, F) frontier and the (Q,) overflow flags."""
    q = x.shape[0]
    last = n_levels - 1
    # init: children of root (or root itself if already deepest)
    at_leaf = root_rel >= last
    child_rel = jnp.where(at_leaf, root_rel, root_rel + 1)
    base8 = jnp.where(at_leaf, root_cell, root_cell * 8)
    cells0 = jnp.full((q, f), 0, jnp.int32)
    lvls0 = jnp.full((q, f), 0, jnp.int32)
    valid0 = jnp.zeros((q, f), bool)
    js = jnp.arange(8)
    cells0 = cells0.at[:, :8].set(base8[:, None] + jnp.where(
        at_leaf[:, None], 0, js[None, :]))
    lvls0 = lvls0.at[:, :8].set(child_rel[:, None])
    valid0 = valid0.at[:, :8].set(jnp.where(at_leaf[:, None], js[None] == 0,
                                            True))
    overflow0 = jnp.zeros((q,), bool)

    def round_fn(state, _):
        cells, lvls, valid, overflow = state
        cnt, prob, crit = _node_stats(tree, lvls, cells, x, sigma)
        nonempty = cnt > 1e-9
        accepted = (crit < theta) | (lvls >= last)
        expand = valid & nonempty & ~accepted
        keepers = valid & ~expand & nonempty
        need = jnp.where(expand, 8, jnp.where(keepers, 1, 0))
        off = jnp.cumsum(need, axis=1) - need
        fits = (off + need) <= f
        # pass 2: overflowing expanders retained as coarse candidates
        need2 = jnp.where(expand & fits, 8, jnp.where(
            (keepers | (expand & ~fits)), 1, 0))
        off2 = jnp.cumsum(need2, axis=1) - need2
        fits2 = (off2 + need2) <= f
        ncells = jnp.zeros((q, f), jnp.int32)
        nlvls = jnp.zeros((q, f), jnp.int32)
        nvalid = jnp.zeros((q, f), bool)
        qi = jnp.arange(q)[:, None]
        # singles
        single = (need2 == 1) & fits2
        tgt = jnp.where(single, off2, f)
        ncells = ncells.at[qi, tgt].set(cells, mode="drop")
        nlvls = nlvls.at[qi, tgt].set(lvls, mode="drop")
        nvalid = nvalid.at[qi, tgt].set(single, mode="drop")
        # expansions
        exp8 = (need2 == 8) & fits2
        qij = jnp.arange(q)[:, None, None]
        tgt8 = jnp.where(exp8[..., None], off2[..., None] + js, f)
        ncells = ncells.at[qij, tgt8].set(cells[..., None] * 8 + js,
                                          mode="drop")
        nlvls = nlvls.at[qij, tgt8].set((lvls + 1)[..., None]
                                        * jnp.ones_like(js), mode="drop")
        nvalid = nvalid.at[qij, tgt8].set(exp8[..., None] & jnp.ones_like(
            js, bool), mode="drop")
        overflow = overflow | jnp.any(expand & ~fits2, axis=1)
        return (ncells, nlvls, nvalid, overflow), None

    state = (cells0, lvls0, valid0, overflow0)
    state, _ = jax.lax.scan(round_fn, state, None, length=n_levels)
    return state


def _settle(st, new, last: int):
    """One restart's carry update: rows already ``done`` keep their result,
    the others take ``new`` (``expand_and_sample``'s outputs) and count the
    restart; a row is done once it holds a deepest-level cell or found no
    candidate."""
    cell, rel, valid, done, overflow, depth = st
    ncell, nrel, nvalid, noverf = new
    cell = jnp.where(done, cell, ncell)
    rel = jnp.where(done, rel, nrel)
    valid = jnp.where(done, valid, nvalid)
    overflow = overflow | jnp.where(done, False, noverf)
    depth = depth + jnp.where(done, 0, 1).astype(jnp.int32)
    done = done | (rel >= last) | ~valid
    return (cell, rel, valid, done, overflow, depth)


def bh_search(tree: StackedTree, x, src_gid, start_cell, *, seed: int, chunk,
              theta, sigma, frontier, n_levels, round_base=0,
              max_restarts=None):
    """Full search: expand/sample, restarting inside sampled inner nodes until
    a deepest-level cell is returned (paper's 'process restarts' loop).

    A restart that does not finish moves its row at least one level down:
    the frontier starts at the restart node's children, so the sampled node
    lies below it. No row therefore needs more than ``n_levels - 1``
    restarts, and the cap's last iteration finds every row done. This
    driver runs every restart over every row, as a kernel body must (the
    Pallas traversal kernel, phase A); ``bh_search_packed`` runs only the
    rows still searching.

    x: (Q,3); src_gid: (Q,) searcher gids (PRNG entities); start_cell: (Q,)
    cell at tree level 0. Returns (leaf_cell (Q,), valid (Q,), overflow (Q,),
    depth (Q,) i32 — expand/sample rounds executed before the query settled,
    the paper's 'process restarts' count; fed to the telemetry frontier-depth
    histogram).
    """
    q = x.shape[0]
    last = n_levels - 1
    restarts = max_restarts or n_levels
    _check_caps(frontier, round_base, restarts)

    def body(i, st):
        new = expand_and_sample(
            tree, x, st[0], st[1], src_gid, round_base + i, seed=seed,
            chunk=chunk, theta=theta, sigma=sigma, frontier=frontier,
            n_levels=n_levels)
        return _settle(st, new, last)

    with jax.named_scope("repro.bh.search"):
        st = (start_cell.astype(jnp.int32), jnp.zeros((q,), jnp.int32),
              jnp.ones((q,), bool), jnp.zeros((q,), bool),
              jnp.zeros((q,), bool), jnp.zeros((q,), jnp.int32))
        cell, rel, valid, done, overflow, depth = jax.lax.fori_loop(
            0, restarts, body, st)
        valid = valid & (rel >= last)
    return cell, valid, overflow, depth


def bh_search_packed(tree: StackedTree, x, src_gid, start_cell, live, *,
                     seed: int, chunk, theta, sigma, frontier, n_levels,
                     round_base=0, block=None):
    """``bh_search`` that runs each restart only over the rows still
    searching. Rows with ``live`` false start done. At each restart a stable
    partition puts the searching rows first; ``expand_and_sample`` runs over
    them in row blocks of ``block`` (default ``PACK_ROWS``, at most Q, Q
    padded up to a multiple of it with done rows), and the block results go
    back to their own rows. Every op of ``expand_and_sample`` is
    row-independent and its Gumbel stream is keyed by the source gid, not
    the row, so each live row gets ``bh_search``'s result bit for bit. A
    restart with no searching row runs no block.

    Returns ``bh_search``'s four outputs (depth 0 and overflow false on rows
    not ``live``) and the row-restarts computed, block padding included
    (i32 scalar)."""
    q = x.shape[0]
    b = min(q, block or PACK_ROWS)
    qp = -(-q // b) * b
    last = n_levels - 1
    _check_caps(frontier, round_base, n_levels)

    def pad(a):
        return jnp.pad(a, [(0, qp - q)] + [(0, 0)] * (a.ndim - 1))

    x, src_gid, start_cell, live = map(pad, (x, src_gid, start_cell, live))

    def restart(i, carry):
        st, rows_run = carry
        cell, rel, done = st[0], st[1], st[3]
        order = jnp.argsort(done, stable=True)        # searching rows first
        searching = ~done
        n_active = jnp.sum(searching.astype(jnp.int32))
        # packed position of each row under ``order``
        packed_at = jnp.where(
            searching, jnp.cumsum(searching) - 1,
            n_active + jnp.cumsum(done) - 1)
        n_blocks = (n_active + b - 1) // b

        def block_body(c):
            k, out = c
            rows = jax.lax.dynamic_slice(order, (k * b,), (b,))
            new = expand_and_sample(
                tree, x[rows], cell[rows], rel[rows], src_gid[rows],
                round_base + i, seed=seed, chunk=chunk, theta=theta,
                sigma=sigma, frontier=frontier, n_levels=n_levels)
            out = tuple(jax.lax.dynamic_update_slice(o, v, (k * b,))
                        for o, v in zip(out, new))
            return k + 1, out

        out = (jnp.zeros((qp,), jnp.int32), jnp.zeros((qp,), jnp.int32),
               jnp.zeros((qp,), bool), jnp.zeros((qp,), bool))
        _, out = jax.lax.while_loop(lambda c: c[0] < n_blocks, block_body,
                                    (jnp.int32(0), out))
        new = tuple(o[packed_at] for o in out)
        return _settle(st, new, last), rows_run + n_blocks * b

    with jax.named_scope("repro.bh.search"):
        st = (start_cell.astype(jnp.int32), jnp.zeros((qp,), jnp.int32),
              jnp.ones((qp,), bool), ~live, jnp.zeros((qp,), bool),
              jnp.zeros((qp,), jnp.int32))
        st, rows_run = jax.lax.fori_loop(0, n_levels, restart,
                                         (st, jnp.int32(0)))
        cell, rel, valid, _, overflow, depth = (a[:q] for a in st)
        valid = valid & (rel >= last)
    return cell, valid, overflow, depth, rows_run


def select_member(x, member_pos, member_weight, member_valid, src_gid, *,
                  seed: int, chunk, sigma):
    """Pick an actual neuron within the chosen leaf cell, kernel-weighted
    (paper: 'the new partner must be a genuine neuron').
    member_*: (Q, M, ...). Returns (idx (Q,), valid (Q,))."""
    m = member_pos.shape[1]
    if m > chash.BH_DRAWS:
        raise ValueError(f"members_cap {m} exceeds the PRNG draw window "
                         f"({chash.BH_DRAWS})")
    d2 = pairwise_d2(x, member_pos)
    w = member_weight * _gauss(d2, sigma)
    logits = jnp.where(member_valid & (w > 1e-12),
                       jnp.log(jnp.maximum(w, 1e-30)), NEG)
    g = chash.gumbel(seed, chash.BH_DOMAIN,
                     chash.bh_ctr(chunk, MEMBER_ROUND, jnp.arange(m))[None, :],
                     src_gid[:, None])
    pick = jnp.argmax(logits + g, axis=1)
    valid = jnp.any(logits > NEG / 2, axis=1)
    return pick, valid


# ---------------------------------------------------------------- phase A
def phase_a(top, pos, src_gid, cfg, num_ranks: int, *, chunk):
    """Search the replicated tree down to the branch level. pos: (Q,3);
    src_gid: (Q,). Returns (branch_cell (Q,), valid (Q,))."""
    b = morton.branch_level(num_ranks)
    if b == 0:
        q = pos.shape[0]
        return jnp.zeros((q,), jnp.int32), jnp.ones((q,), bool)
    tree = stack_levels(top.counts, top.centroids, 0)
    cell, valid, _, _ = bh_search(
        tree, pos, src_gid, jnp.zeros((pos.shape[0],), jnp.int32),
        seed=cfg.seed, chunk=chunk, theta=cfg.theta, sigma=cfg.sigma,
        frontier=cfg.frontier_cap, n_levels=b + 1,
        round_base=PHASE_A_ROUND_BASE)
    return cell, valid


# ---------------------------------------------------------------- phase B
def phase_b_core(counts, cents, leaf_members, neuron_pos, vacant_d, x,
                 start_cell_rel, src_gid, valid_in, chunk, gid_base, *,
                 seed: int, sizes, theta: float, sigma: float, frontier: int,
                 n_levels: int):
    """Finish the search inside one rank's subtree, raw stacked arrays: the
    body of the Pallas traversal kernel (kernels/bh_traverse.py), which
    runs ``bh_search`` over every row of its query block. Every operation
    is row-independent over Q, so the kernel's query blocking cannot change
    results, and the reference lowering (``phase_b_reference``: the same
    ``expand_and_sample`` through ``bh_search_packed``, then the same
    ``phase_b_members``) gives the same answers.

    counts: (L, C); cents: (L, C, 3) centroids (``stack_levels``); sizes:
    static tuple of per-level cell edge lengths; leaf_members: (n_leaf,
    M); neuron_pos/vacant_d: the subtree's neuron data;
    x/start_cell_rel/src_gid/valid_in: (Q, ...) queries; chunk/gid_base:
    traced i32 scalars.
    Returns (target_gid (Q,), valid (Q,), depth (Q,) i32 restart rounds,
    overflow (Q,) bool: the query's frontier overflowed in some round)."""
    tree = StackedTree(counts, cents, tuple(sizes), 0)
    leaf_cell, valid, overflow, depth = bh_search(
        tree, x, src_gid, start_cell_rel, seed=seed, chunk=chunk, theta=theta,
        sigma=sigma, frontier=frontier, n_levels=n_levels,
        round_base=PHASE_B_ROUND_BASE)
    tgt, ok = phase_b_members(leaf_members, neuron_pos, vacant_d, x, src_gid,
                              leaf_cell, valid & valid_in, chunk, gid_base,
                              seed=seed, sigma=sigma)
    return tgt, ok, depth, overflow


def phase_b_members(leaf_members, neuron_pos, vacant_d, x, src_gid,
                    leaf_cell, valid, chunk, gid_base, *, seed: int, sigma):
    """Phase B's member selection in the leaf cell each query settled on.
    Returns (target_gid (Q,), -1 where not ok; ok (Q,))."""
    with jax.named_scope("repro.bh.member"):
        members = leaf_members[leaf_cell]              # (Q, M) local ids
        mvalid = members >= 0
        msafe = jnp.where(mvalid, members, 0)
        mgid = gid_base + msafe
        # exclude self-connection (a neuron never proposes to itself)
        mvalid = mvalid & (mgid != src_gid[:, None])
        mpos = neuron_pos[msafe]
        mw = jnp.where(mvalid, vacant_d[msafe], 0.0)
        pick, pvalid = select_member(x, mpos, mw, mvalid, src_gid, seed=seed,
                                     chunk=chunk, sigma=sigma)
        tgt_local = jnp.take_along_axis(msafe, pick[:, None], axis=1)[:, 0]
        tgt_gid = gid_base + tgt_local
        ok = valid & pvalid
        return jnp.where(ok, tgt_gid, -1), ok


@registry.register_phase("traversal", "reference")
def phase_b_reference(stacked, local, neuron_pos, vacant_d, pos,
                      start_cell_rel, src_gid, valid_in, chunk, gid_base,
                      kw, interpret=None, block=None):
    """jnp phase B: ``bh_search_packed`` over the query rows that hold a
    request, then ``phase_b_members``. Returns ``phase_b_core``'s outputs
    and the row-restarts computed. ``block`` overrides ``PACK_ROWS``."""
    leaf_cell, valid, overflow, depth, rows_run = bh_search_packed(
        stacked, pos, src_gid, start_cell_rel, valid_in, seed=kw["seed"],
        chunk=chunk, theta=kw["theta"], sigma=kw["sigma"],
        frontier=kw["frontier"], n_levels=kw["n_levels"],
        round_base=PHASE_B_ROUND_BASE, block=block)
    tgt, ok = phase_b_members(local.leaf_members, neuron_pos, vacant_d, pos,
                              src_gid, leaf_cell, valid & valid_in, chunk,
                              gid_base, seed=kw["seed"], sigma=kw["sigma"])
    return tgt, ok, depth, overflow, rows_run


@registry.register_phase("traversal", "fused")
def phase_b_fused(stacked, local, neuron_pos, vacant_d, pos,
                  start_cell_rel, src_gid, valid_in, chunk, gid_base, kw,
                  interpret=None):
    """The Pallas traversal kernel (kernels/bh_traverse.py), query-blocked,
    same core math — bit-identical to the reference. Every row runs every
    restart: the row-restarts computed are Q x ``n_levels``."""
    from repro.kernels import ops as kops   # lazy: kernels import us
    out = kops.bh_traverse(
        stacked.counts, stacked.centroids, local.leaf_members,
        neuron_pos, vacant_d, pos, start_cell_rel, src_gid, valid_in,
        chunk, gid_base, interpret=interpret, **kw)
    return (*out, jnp.int32(pos.shape[0] * kw["n_levels"]))


def phase_b_levels(cfg) -> int:
    """Levels of the subtree phase B searches, its branch node included,
    and its restart cap: the fused kernel runs that many restart iterations
    over every query row; the reference runs each over the rows still
    searching only (``bh_search_packed``), and its last over none."""
    return cfg.local_levels + 1


def phase_b(local, neuron_pos, vacant_d, pos, src_gid, start_cell_rel,
            valid_in, cfg, num_ranks: int, gid_base, *, chunk,
            interpret=None):
    """Phase-B dispatch per ``cfg.connectivity_impl`` (phase-registry
    domain "traversal"): 'reference' vs 'fused' — bit-identical lowerings
    of the same core math. Returns (target_gid, ok, depth, overflow): (Q,)
    each, and the row-restarts computed (i32 scalar).

    local: a tree.LocalTree (or the gathered global tree in the old
    algorithm, with gid_base = 0 and global leaf members)."""
    b = morton.branch_level(num_ranks)
    stacked = stack_levels(local.counts, local.centroids, b)
    kw = dict(seed=cfg.seed, sizes=stacked.sizes, theta=cfg.theta,
              sigma=cfg.sigma, frontier=cfg.frontier_cap,
              n_levels=phase_b_levels(cfg))
    impl = registry.resolve("traversal", cfg.connectivity_impl)
    return impl(stacked, local, neuron_pos, vacant_d, pos, start_cell_rel,
                src_gid, valid_in, chunk, gid_base, kw, interpret=interpret)
