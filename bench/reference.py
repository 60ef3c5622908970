"""Plain reference of one MSP chunk, in numpy, written from the model's
description and independent of the program under test.

A chunk is Delta electrical steps of every neuron followed by one
structural-plasticity update. The reference takes a chunk's input state (the
state the program handed to the chunk) and:

* recomputes the activity window in full: Izhikevich integration (two
  half-ms Euler steps), calcium trace, synaptic-element growth, background
  drive N(mean, std) and remote spikes drawn from Threefry-2x32 keyed by
  (seed, domain, global step, entity), true spikes for same-rank edges;
* checks the connectivity update by its guarantees: every out-edge pairs
  with one in-edge, no neuron holds more synapses than its whole elements,
  deletions are exactly the excess of bound synapses over whole elements,
  each searcher forms at most one synapse, the program counts the searchers
  and the synapses it formed as the tables show them, no request was
  dropped, and new synapses reach as far as the model's Gaussian kernel
  makes them.

``dtype`` selects the precision of the activity arithmetic: float32 is the
reference, bfloat16 the control (the nearest precision below the
configuration's float32).
"""
from __future__ import annotations

import numpy as np

NOISE_DOMAIN = 0x6E6F6973
SPIKE_DOMAIN = 0x73706B73
_PARITY = 0x1BD11BDA
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_TWO_PI = np.float32(2.0 * 3.14159265358979)


def _u32(x):
    return (np.asarray(x, np.int64) & 0xFFFFFFFF).astype(np.uint32)


def threefry2x32(k0, k1, c0, c1):
    """Threefry-2x32 with 20 rounds (Salmon et al., SC 2011): key (k0, k1),
    counter (c0, c1), broadcast together. Returns two uint32 arrays."""
    k0, k1 = _u32(k0), _u32(k1)
    x0, x1 = np.broadcast_arrays(_u32(c0), _u32(c1))
    ks = (k0, k1, k0 ^ k1 ^ np.uint32(_PARITY))
    with np.errstate(over="ignore"):
        x0 = x0 + ks[0]
        x1 = x1 + ks[1]
        for g in range(5):
            for r in _ROTATIONS[g % 2]:
                x0 = x0 + x1
                x1 = ((x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))) ^ x0
            x0 = x0 + ks[(g + 1) % 3]
            x1 = x1 + ks[(g + 2) % 3] + np.uint32(g + 1)
    return x0, x1


def _unit(word):
    """Top 24 bits of a uint32 as a float32 in [0, 1)."""
    return (word >> np.uint32(8)).astype(np.float32) * np.float32(2.0 ** -24)


def uniform(seed, domain, ctr, entity):
    return _unit(threefry2x32(seed, domain, ctr, entity)[0])


def normal(seed, domain, ctr, entity):
    """Standard normal by Box-Muller on the two words of one draw."""
    x0, x1 = threefry2x32(seed, domain, ctr, entity)
    r = np.sqrt(np.float32(-2.0) * np.log1p(-_unit(x0)))
    return r * np.cos(_TWO_PI * _unit(x1))


def activity_window(cfg: dict, chunk: int, neurons: dict, in_edges,
                    is_excitatory, n: int, dtype=np.float32) -> dict:
    """Delta electrical steps of all N neurons from a chunk's input state.

    cfg: the configuration's ``brain_config`` merged with the traffic's;
    neurons: v, u, calcium, ax_elements, de_elements, spiked, rate as (N,)
    host arrays; in_edges: (N, S) source gids, -1 empty; n: neurons per
    rank (row r lives on rank r // n). Returns the window's outputs, with
    ``spikes`` the per-neuron spike count."""
    dt = np.dtype(dtype)

    def c(x):
        return np.asarray(x, np.float32).astype(dt)

    n_total, s_max = in_edges.shape
    delta = int(cfg["rate_period"])
    seed = int(cfg["seed"])
    gid = np.arange(n_total, dtype=np.int64)
    valid = in_edges >= 0
    src = np.where(valid, in_edges, 0).astype(np.int64)
    local = valid & ((src // n) == (gid // n)[:, None])
    remote = valid & ~local
    w = np.where(is_excitatory, np.float32(cfg["synapse_weight"]),
                 np.float32(-cfg["synapse_weight"]))[src]
    w = np.where(valid, w, np.float32(0.0))
    rows, slots = np.nonzero(remote)
    remote_rate = np.asarray(neurons["rate"], np.float32)[src[rows, slots]]
    edge_id = gid[rows] * s_max + slots

    v, u = c(neurons["v"]), c(neurons["u"])
    ca = c(neurons["calcium"])
    ax, de = c(neurons["ax_elements"]), c(neurons["de_elements"])
    spiked = np.asarray(neurons["spiked"], bool)
    count = np.zeros(n_total, np.int32)
    a, b, cc, d = (c(cfg[k]) for k in ("izh_a", "izh_b", "izh_c", "izh_d"))
    decay, beta = c(cfg["calcium_decay"]), c(cfg["calcium_beta"])
    nu, eps = c(cfg["element_growth_rate"]), c(cfg["target_calcium"])
    mean, std = np.float32(cfg["background_mean"]), \
        np.float32(cfg["background_std"])
    k004, k5, k140, half = c(0.04), c(5.0), c(140.0), c(0.5)
    one, zero, thirty = c(1.0), c(0.0), c(30.0)
    for t in range(delta):
        step = chunk * delta + t
        hits = local & spiked[src]
        if rows.size:
            hits[rows, slots] = uniform(seed, SPIKE_DOMAIN, step,
                                        edge_id) < remote_rate
        syn = np.sum(hits * w, axis=1, dtype=np.float32)
        noise = mean + std * normal(seed, NOISE_DOMAIN, step, gid)
        i_t = c(syn + noise)
        for _ in range(2):
            v = v + half * (k004 * v * v + k5 * v + k140 - u + i_t)
        u = u + a * (b * v - u)
        fired = v >= thirty
        v = np.where(fired, cc, v)
        u = np.where(fired, u + d, u)
        ca = ca + (-ca * decay + beta * fired.astype(dt))
        count += fired
        drive = nu * (one - ca / eps)
        ax = np.maximum(ax + drive, zero)
        de = np.maximum(de + drive, zero)
        spiked = fired
    f32 = np.float32
    return {"v": v.astype(f32), "u": u.astype(f32), "calcium": ca.astype(f32),
            "ax_elements": ax.astype(f32), "de_elements": de.astype(f32),
            "spiked": spiked, "spikes": count}


def spike_counts(rate, delta: int):
    """The window's spike counts from the advertised rate (count / Delta)."""
    return np.rint(np.asarray(rate, np.float64) * delta).astype(np.int32)


def activity_numbers(program: dict, ref: dict, delta: int) -> dict:
    """How far the program's window outputs lie from the reference's."""
    spikes = spike_counts(program["rate"], delta)
    gap = lambda k: float(np.max(np.abs(  # noqa: E731
        np.asarray(program[k], np.float64) - ref[k])))
    return {"spike_mismatch_pct":
            100.0 * float(np.mean(spikes != ref["spikes"])),
            "spike_count_gap": int(np.max(np.abs(spikes - ref["spikes"]))),
            "rate_gap_pct": 100.0 * abs(int(spikes.sum())
                                        - int(ref["spikes"].sum()))
            / max(int(ref["spikes"].sum()), 1),
            "calcium_gap": gap("calcium"),
            "elements_gap": max(gap("ax_elements"), gap("de_elements"))}


def _row_counter(edges):
    """(keys, counts) of the multiset of (row, entry) pairs of a table."""
    rows = np.broadcast_to(np.arange(edges.shape[0])[:, None], edges.shape)
    keys = rows[edges >= 0].astype(np.int64) * edges.shape[0] \
        + edges[edges >= 0]
    return np.unique(keys, return_counts=True)


def unpaired_edges(out_edges, in_edges) -> int:
    """Out-edges (i -> j) with no matching in-edge on row j, and in-edges
    with no matching out-edge, counted with multiplicity."""
    n_total = out_edges.shape[0]
    rows = np.broadcast_to(np.arange(n_total)[:, None], out_edges.shape)
    out_keys = rows[out_edges >= 0].astype(np.int64) * n_total \
        + out_edges[out_edges >= 0]
    in_keys = in_edges[in_edges >= 0].astype(np.int64) * n_total \
        + rows[in_edges >= 0]
    keys, inv = np.unique(np.concatenate([out_keys, in_keys]),
                          return_inverse=True)
    sign = np.concatenate([np.ones(out_keys.size), -np.ones(in_keys.size)])
    return int(np.abs(np.bincount(inv, weights=sign,
                                  minlength=keys.size)).sum())


def _row_changes(pre, post):
    """What a chunk changed in one edge table, row by row, as multisets:
    (removed, added) per row, and the (row, entry) pairs that ``post``
    holds more often than ``pre``."""
    n = pre.shape[0]
    kp, cp = _row_counter(pre)
    kq, cq = _row_counter(post)
    keys = np.union1d(kp, kq)
    had = np.zeros(keys.size, np.int64)
    has = np.zeros(keys.size, np.int64)
    had[np.searchsorted(keys, kp)] = cp
    has[np.searchsorted(keys, kq)] = cq
    row = keys // n
    removed = np.bincount(row, weights=np.maximum(had - has, 0),
                          minlength=n).astype(np.int64)
    added = np.bincount(row, weights=np.maximum(has - had, 0),
                        minlength=n).astype(np.int64)
    new = keys[has > had]
    return removed, added, (new // n, new % n)


def update_numbers(pre: dict, post: dict, counted: dict) -> dict:
    """The connectivity update's guarantees, from the chunk's input tables
    (``pre``), its output tables and whole elements (``post``), and the
    program's counter increments over the chunk (``counted``).

    An edge removed and formed again within one chunk leaves its row as it
    was; at most ``hidden`` (the deletions due less those seen) can be so,
    and each reading allows for them. ``formed_pct`` is the share of
    searchers that formed a synapse."""
    out_pre, in_pre = pre["out_edges"], pre["in_edges"]
    out_post, in_post = post["out_edges"], post["in_edges"]
    whole_ax = np.floor(post["ax_elements"]).astype(np.int64)
    whole_de = np.floor(post["de_elements"]).astype(np.int64)
    cnt = lambda e: (e >= 0).sum(1).astype(np.int64)  # noqa: E731
    over = (cnt(out_post) > np.maximum(whole_ax, 0)) \
        | (cnt(in_post) > np.maximum(whole_de, 0))
    expected_deleted = int(np.maximum(cnt(out_pre) - whole_ax, 0).sum()
                           + np.maximum(cnt(in_pre) - whole_de, 0).sum())
    removed_out, added_out, _ = _row_changes(out_pre, out_post)
    _, added_in, _ = _row_changes(in_pre, in_post)
    hidden = max(expected_deleted - int(removed_out.sum()), 0)

    # a neuron searches where its whole axonal elements exceed its
    # out-edges left after the deletions
    vacant = whole_ax - (cnt(out_pre) - removed_out)
    searchers = int((vacant >= 1).sum())
    maybe = int(((vacant == 0) & (added_out == 0)
                 & (cnt(out_pre) >= 1)).sum())
    requests = int(round(counted["formation_requests"]))
    most = searchers + min(hidden, maybe)

    formed = int(round(counted["synapses_formed"]))
    formed_gap = max(max(int(a.sum()) - formed, formed - int(a.sum())
                         - hidden, 0) for a in (added_out, added_in))
    return {"unpaired_edges": unpaired_edges(out_post, in_post),
            "over_capacity": int(over.sum()),
            "deleted_gap": abs(expected_deleted
                               - int(round(counted["synapses_deleted"]))),
            "multi_formed": int((added_out > 1).sum()),
            "formed_gap": formed_gap,
            "searchers_gap": max(searchers - requests, requests - most, 0),
            "formed_pct": 100.0 * int(added_out.sum()) / max(searchers, 1),
            "request_overflow": int(round(counted["request_overflow"]))}


def reach(pre: dict, post: dict, positions, sigma: float, block: int = 64):
    """How far the chunk's new synapses reach: (observed, kernel, blind,
    synapses), the first three sums of lengths over the new synapses. A
    searcher i picks target j with probability in proportion to j's vacant
    dendritic elements times the model's kernel
    exp(-|x_i - x_j|^2 / sigma^2), and a formed synapse ends at a target
    that could accept it (a whole vacant element and a free slot):
    ``kernel`` sums the mean length that gives for each new synapse's
    source, ``blind`` the mean length without the kernel (targets in
    proportion to vacant elements alone). A kernel that falls with
    distance makes every source's mean length at most its blind one."""
    s_max = pre["in_edges"].shape[1]
    _, added, (rows, tgts) = _row_changes(pre["out_edges"],
                                          post["out_edges"])
    one = added[rows] == 1
    rows, tgts = rows[one], tgts[one]
    if rows.size == 0:
        return 0.0, 0.0, 0.0, 0
    removed_in, _, _ = _row_changes(pre["in_edges"], post["in_edges"])
    in_cnt = (pre["in_edges"] >= 0).sum(1) - removed_in
    vacant = np.asarray(post["de_elements"], np.float64) - in_cnt
    can = np.minimum(np.floor(vacant), s_max - in_cnt) >= 1
    cand = np.nonzero(can & (vacant > 0))[0]
    x = np.asarray(positions, np.float64)
    w = vacant[cand]
    observed = float(np.linalg.norm(x[rows] - x[tgts], axis=1).sum())
    kernel = blind = 0.0
    for b in range(0, rows.size, block):
        r = rows[b:b + block]
        d2 = ((x[r][:, None, :] - x[cand][None, :, :]) ** 2).sum(-1)
        d = np.sqrt(d2)
        wb = np.where(r[:, None] == cand[None, :], 0.0, w)
        p = wb * np.exp(-d2 / (sigma * sigma))
        kernel += float(((p * d).sum(1) / np.maximum(p.sum(1), 1e-300))
                        .sum())
        blind += float(((wb * d).sum(1) / np.maximum(wb.sum(1), 1e-300))
                       .sum())
    return observed, kernel, blind, int(rows.size)


def exchange_gap(post: dict, n: int) -> int:
    """Remote in-edges whose source rate, as the receiving rank holds it
    after the chunk's exchange, is not the source's advertised rate. The
    dense layout holds the (R, n) table; the sparse layout a compact
    buffer per rank, ``subs_cap`` long, reached through the (N, S) edge ->
    slot map (slot -1: not subscribed)."""
    in_edges, rate = post["in_edges"], post["rate"]
    rows = np.arange(in_edges.shape[0])[:, None] // n
    remote = (in_edges >= 0) & (in_edges // n != rows)
    i, s = np.nonzero(remote)
    src = in_edges[i, s]
    if post.get("rates_table") is not None:
        held = post["rates_table"][src // n, src % n]
        ok = np.ones(src.size, bool)
    else:
        cap = post["remote_rates"].shape[0] // (in_edges.shape[0] // n)
        slot = post["rate_slots"][i, s]
        ok = slot >= 0
        held = post["remote_rates"][(i // n) * cap + np.maximum(slot, 0)]
    return int((~ok | (held != rate[src])).sum())
