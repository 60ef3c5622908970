"""Fused activity-phase megakernel: one Pallas pass per rate window.

The engine's reference activity phase runs ~6 separate jnp passes per
electrical step x Delta=100 steps per chunk, materializing several
``(n, s_max)`` temporaries in HBM each step (local-spike hits, remote
Bernoulli draws, per-edge weights, the synaptic-input reduction, the noise
vector, and the two element updates). This module fuses the whole window
into a single ``pallas_call`` with ``grid=(num_steps,)``:

  * per step it (a) accumulates synaptic input from the ``(n, s_max)``
    in-edge table — true local spikes, counter-hash-reconstructed remote
    Bernoulli(rate) spikes, per-source signed weights — (b) adds per-region
    background noise plus protocol stimulation, and (c) runs Izhikevich
    integration + calcium + element growth under the lesion mask;
  * neuron state lives in VMEM for the whole window: every state operand is
    a full block with a constant index map and is aliased to its output
    (``input_output_aliases``), so nothing round-trips HBM between steps and
    zero ``(n, s_max)`` temporaries are ever materialized.

All randomness is the counter-based hash of ``kernels/hash.py`` keyed by
``(seed, domain, global step, neuron/edge id)``. ``step_core`` — the exact
per-step math — is plain jnp shared by this kernel, the jnp oracle
(``kernels/ref.activity_window_ref``) and the engine's reference scan,
which is what makes ``activity_impl='fused'`` bit-identical to
``'reference'`` (DESIGN.md §5).

TPU sizing: the window keeps the in-edge table and ~16 ``(n,)`` vectors
VMEM-resident, i.e. roughly ``(s_max + 16) * 4 * n`` bytes — n = 64k at
s_max = 32 is ~12.5 MB, the practical per-core ceiling. Beyond that, fall
back to ``activity_impl='reference'``. The dense rate exchange adds an
``(R, n)`` rates operand on top — O(R·n) VMEM that cannot survive large
meshes; the sparse exchange (``rate_slots`` given) replaces it with the
compact ``(subs_cap,)`` subscribed-rate buffer plus an ``(n, s_max)`` slot
remap (DESIGN.md §7). Like the other kernels in this package, CPU
containers run it with ``interpret=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import hash as chash

_N_STATE = 7   # v, u, calcium, ax_elements, de_elements, spiked, spike_count


def local_spike_hits(spiked_last, in_edges, rank, n: int):
    """True spikes for same-rank edges ('virtually free' in the paper).
    The math lives here (not core/spikes) so the kernel package never
    imports the engine package; ``core.spikes.local_spikes`` delegates."""
    src = in_edges
    valid = src >= 0
    src_rank = jnp.where(valid, src // n, 0)
    src_lid = jnp.where(valid, src % n, 0)
    local = valid & (src_rank == rank)
    return local & spiked_last[src_lid]


def reconstruct_remote_spikes(seed: int, gstep, all_rates, in_edges, rank,
                              n: int, rate_slots=None):
    """NEW spike algorithm, receive side: Bernoulli(rate) per REMOTE edge
    from the counter hash keyed by ``(seed, SPIKE_DOMAIN, gstep,
    dst_gid*S + slot)``. The edge id derives from the receiver's table
    coordinates, so any rank holding the same edge table draws the same
    stream. Returns (n, S) bool (False on local/empty edges).

    ``rate_slots=None`` (dense exchange): ``all_rates`` is the replicated
    (R, n) table, looked up by the edge's (src rank, src lid) — a 2-D
    gather over the full table. Otherwise (sparse exchange): ``all_rates``
    is the compact (subs_cap,) subscribed-rate buffer and ``rate_slots``
    the (n, S) edge→slot remap — a 1-D gather; slot -1 (local, empty, or
    overflowed subscription) reads rate 0. The Bernoulli stream is keyed by
    the edge id either way, so both layouts draw identical spikes wherever
    the subscription held the true rate (DESIGN.md §7)."""
    src = in_edges
    s_max = src.shape[1]
    valid = src >= 0
    src_rank = jnp.where(valid, src // n, 0)
    src_lid = jnp.where(valid, src % n, 0)
    remote = valid & (src_rank != rank)
    if rate_slots is None:
        rates = all_rates[src_rank, src_lid]
    else:
        cap = all_rates.shape[0]
        rates = jnp.where(rate_slots >= 0,
                          all_rates[jnp.clip(rate_slots, 0, cap - 1)], 0.0)
    dst_gid = rank * n + jnp.arange(n, dtype=jnp.int32)
    edge_id = dst_gid[:, None] * s_max + jnp.arange(s_max, dtype=jnp.int32)
    u = chash.uniform(seed, chash.SPIKE_DOMAIN, gstep, edge_id)
    return remote & (u < rates)


def step_core(state, in_edges, w_table, rates, bg_mean, bg_std, izh,
              ca_consts, seed: int, gstep, rank, n: int,
              stim=None, lesions=None, remote_override=None,
              rate_slots=None):
    """One electrical step, pure jnp — the single source of truth executed
    by the Pallas kernel body, the jnp oracle, and the engine's reference
    scan (bit-identity by construction).

    state: (v, u, ca, ax, de, spiked, spike_count); izh: (a, b, c, d, nu,
    eps) scalars or (n,); ca_consts: (calcium_decay, calcium_beta) floats;
    stim: ((E, n) f32 masks, ((amplitude, t0, t1), ...)) or None; lesions:
    ((W, n) bool masks, ((t0, t1), ...)) or None; remote_override: (n, S)
    bool remote-spike hits (old spike algorithm) or None to reconstruct
    them from the counter hash; rate_slots: None when ``rates`` is the
    dense (R, n) table, else the (n, S) edge→slot remap into the compact
    (subs_cap,) subscribed-rate buffer (sparse exchange)."""
    v, u, ca, ax, de, spiked, spike_count = state
    a, b, c, d, nu, eps = izh
    ca_decay, ca_beta = ca_consts

    # ---- (a) synaptic input from the in-edge table -----------------------
    with jax.named_scope("repro.act.input"):
        local_in = local_spike_hits(spiked, in_edges, rank, n)
        if remote_override is None:
            remote_in = reconstruct_remote_spikes(
                seed, gstep, rates, in_edges, rank, n, rate_slots=rate_slots)
        else:
            remote_in = remote_override
        valid = in_edges >= 0
        src_lid = jnp.where(valid, in_edges, 0) % n
        weights = jnp.where(valid, w_table[src_lid], 0.0)
        syn_in = jnp.sum((local_in | remote_in) * weights, axis=-1)

    # ---- (b) background noise + stimulation ------------------------------
    gid = rank * n + jnp.arange(n, dtype=jnp.int32)
    noise = bg_mean + bg_std * chash.normal(seed, chash.NOISE_DOMAIN,
                                            gstep, gid)
    if stim is not None:
        masks, meta = stim
        for i, (amp, t0, t1) in enumerate(meta):
            active = ((gstep >= t0) & (gstep < t1)).astype(jnp.float32)
            noise = noise + amp * active * masks[i]
    alive = None
    if lesions is not None:
        masks, meta = lesions
        alive = jnp.ones((n,), bool)
        for i, (t0, t1) in enumerate(meta):
            alive = alive & ~(masks[i] & (gstep >= t0) & (gstep < t1))

    # ---- (c) Izhikevich + calcium + element growth -----------------------
    u_prev = u
    i_t = syn_in + noise
    for _ in range(2):  # two half-ms Euler steps (reference Izhikevich impl)
        v = v + 0.5 * (0.04 * v * v + 5.0 * v + 140.0 - u + i_t)
    u = u + a * (b * v - u)
    fired = v >= 30.0
    v = jnp.where(fired, c, v)
    u = jnp.where(fired, u + d, u)
    if alive is not None:
        fired = fired & alive
        v = jnp.where(alive, v,
                      jnp.broadcast_to(jnp.asarray(c, jnp.float32), v.shape))
        u = jnp.where(alive, u, u_prev)
    ca = ca + (-ca * ca_decay + ca_beta * fired)
    spike_count = spike_count + fired
    drive = nu * (1.0 - ca / eps)
    ax = jnp.maximum(ax + drive, 0.0)
    de = jnp.maximum(de + drive, 0.0)
    if alive is not None:
        ax = jnp.where(alive, ax, 0.0)
        de = jnp.where(alive, de, 0.0)
    return v, u, ca, ax, de, fired, spike_count


def _window_kernel(*refs, n_in, num_steps, seed, ca_consts, n, stim_meta,
                   lesion_meta, has_slots):
    t = pl.program_id(0)
    outs = refs[n_in:n_in + _N_STATE]
    spk_ref = refs[n_in + _N_STATE]   # (1,) block of the (T,) per-step counts

    @pl.when(t == 0)
    def _init():   # noqa: ANN202 — Delta-resident state: load once per window
        for o, i in zip(outs, refs[:_N_STATE]):
            o[...] = i[...]

    state = tuple(o[...] for o in outs)
    nxt = _N_STATE
    in_edges = refs[nxt][...]
    w_table = refs[nxt + 1][...]
    rates = refs[nxt + 2][...]
    nxt += 3
    rate_slots = None
    if has_slots:
        rate_slots = refs[nxt][...]
        nxt += 1
    bg_mean = refs[nxt][...]
    bg_std = refs[nxt + 1][...]
    izh = tuple(r[...] for r in refs[nxt + 2:nxt + 8])
    scal = refs[nxt + 8][...]
    chunk, rank = scal[0], scal[1]
    nxt += 9
    stim = None
    if stim_meta is not None:
        stim = (refs[nxt][...], stim_meta)
        nxt += 1
    lesions = None
    if lesion_meta is not None:
        lesions = (refs[nxt][...], lesion_meta)
        nxt += 1
    gstep = chunk * num_steps + t
    new = step_core(state, in_edges, w_table, rates, bg_mean, bg_std, izh,
                    ca_consts, seed, gstep, rank, n,
                    stim=stim, lesions=lesions, rate_slots=rate_slots)
    for o, val in zip(outs, new):
        o[...] = val
    # this step's fired count — the same reduction the reference scan emits
    # as its ys (telemetry spikes-per-step; bit-identity by construction)
    spk_ref[...] = jnp.sum(new[5].astype(jnp.float32))[None]


def activity_window(state, in_edges, w_table, rates, bg_mean, bg_std,
                    chunk, rank, *, seed: int, num_steps: int, izh,
                    ca_consts, stim=None, lesions=None, rate_slots=None,
                    interpret=False):
    """Run ``num_steps`` electrical steps in one ``pallas_call``.

    state: 7-tuple (v, u, ca, ax, de, spiked (bool), spike_count), all (n,);
    in_edges: (n, s_max) i32; w_table: (n,) signed per-source weights;
    rates: the dense (R, n) replicated table, or — with ``rate_slots``
    (n, s_max) given — the compact (subs_cap,) subscribed-rate buffer of the
    sparse exchange (the kernel then holds O(subs_cap) rate state in VMEM
    instead of O(R·n)); bg_mean/bg_std: scalar or (n,); chunk/rank: traced
    i32 scalars; izh: 6-tuple, scalar or (n,); stim/lesions: protocol
    tables (see ``scenarios.protocol.stim_tables``/``lesion_tables``).
    Returns ``(state7, spikes_per_step)`` — the updated 7-tuple (inputs
    donated via input_output_aliases) plus the (num_steps,) f32 per-step
    fired counts (each grid step writes one slot of an unaliased output;
    the telemetry spikes-per-step signal, identical to the reference scan's
    per-step reduction)."""
    n = state[0].shape[0]
    s_max = in_edges.shape[1]
    f32 = jnp.float32
    vec = lambda x: jnp.broadcast_to(jnp.asarray(x, f32), (n,))  # noqa: E731
    bg_mean, bg_std = vec(bg_mean), vec(bg_std)
    izh = tuple(vec(x) for x in izh)
    scal = jnp.stack([jnp.asarray(chunk, jnp.int32),
                      jnp.asarray(rank, jnp.int32)])

    row = pl.BlockSpec((n,), lambda t: (0,))
    operands = list(state) + [in_edges, w_table, rates]
    in_specs = [row] * _N_STATE + [
        pl.BlockSpec((n, s_max), lambda t: (0, 0)),       # in_edges
        row,                                              # w_table
        # rates: dense (R, n) table or sparse (subs_cap,) compact buffer
        pl.BlockSpec(rates.shape, lambda t: (0,) * rates.ndim),
    ]
    if rate_slots is not None:
        operands.append(rate_slots)
        in_specs.append(pl.BlockSpec((n, s_max), lambda t: (0, 0)))
    operands += [bg_mean, bg_std, *izh, scal]
    in_specs += [
        row, row,                                         # bg_mean, bg_std
        *([row] * 6),                                     # izh
        pl.BlockSpec((2,), lambda t: (0,)),               # chunk, rank
    ]
    stim_meta = lesion_meta = None
    if stim is not None:
        masks, stim_meta = stim
        operands.append(masks)
        in_specs.append(pl.BlockSpec(masks.shape, lambda t: (0, 0)))
    if lesions is not None:
        masks, lesion_meta = lesions
        operands.append(masks)
        in_specs.append(pl.BlockSpec(masks.shape, lambda t: (0, 0)))

    out_shape = [jax.ShapeDtypeStruct((n,), f32)] * 5 + \
        [jax.ShapeDtypeStruct((n,), jnp.bool_),
         jax.ShapeDtypeStruct((n,), f32),
         jax.ShapeDtypeStruct((num_steps,), f32)]   # per-step fired counts
    kernel = functools.partial(
        _window_kernel, n_in=len(operands), num_steps=num_steps, seed=seed,
        ca_consts=(float(ca_consts[0]), float(ca_consts[1])), n=n,
        stim_meta=stim_meta, lesion_meta=lesion_meta,
        has_slots=rate_slots is not None)
    res = pl.pallas_call(
        kernel, grid=(num_steps,), in_specs=in_specs,
        out_specs=[row] * _N_STATE + [pl.BlockSpec((1,), lambda t: (t,))],
        out_shape=out_shape,
        input_output_aliases={i: i for i in range(_N_STATE)},
        interpret=interpret,
    )(*operands)
    return tuple(res[:_N_STATE]), res[_N_STATE]


def window_hbm_bytes(n: int, s_max: int, num_ranks: int,
                     num_stim: int = 0, num_lesions: int = 0, *,
                     subs_cap=None, num_steps: int = 100) -> int:
    """Analytic HBM traffic of one fused window on TPU: each operand is
    streamed HBM->VMEM once and the 7 state outputs written back once —
    there are no per-step HBM temporaries (that is the point). Used by
    ``benchmarks/bench_activity.py`` against the roofline byte count of the
    reference lowering.

    ``subs_cap=None`` models the dense exchange (the replicated (R, n)
    rates table streams in); an integer models the sparse exchange (the
    compact (subs_cap,) rate buffer plus the (n, s_max) slot remap);
    ``num_steps`` sizes the (T,) per-step spike-count telemetry output."""
    state_in = 6 * 4 * n + n                 # 6 f32 vectors + bool spiked
    if subs_cap is None:
        rate_bytes = num_ranks * n * 4       # dense (R, n) table
    else:
        rate_bytes = subs_cap * 4 + s_max * 4 * n   # compact buffer + slots
    tables = (s_max * 4 * n                  # in_edges
              + 4 * n                        # w_table
              + rate_bytes
              + 2 * 4 * n                    # bg mean/std
              + 6 * 4 * n                    # izh params
              + 8                            # chunk, rank
              + num_stim * 4 * n + num_lesions * n)
    state_out = state_in
    spk_out = 4 * num_steps                  # (T,) per-step fired counts
    return state_in + tables + state_out + spk_out
