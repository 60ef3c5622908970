"""Device-side metrics: the structured ``Metrics`` pytree carried through
the jitted scan (DESIGN.md §9).

``Metrics`` replaces the engine's old flat ``stats`` dict of summed scalars
with three groups of small per-rank device buffers:

  counters   {name: (1,) f32}      monotone per-rank totals — the paper's
                                   byte-accounting counters plus per-phase
                                   work counters (see ``PHASE_OF``);
  per_chunk  {name: (1, H) f32}    a ring buffer of per-chunk (per-Delta)
                                   counter increments, indexed by
                                   ``chunk % H`` — per-Delta resolution is
                                   preserved on device instead of being
                                   lost to a running sum;
  hists      {name: (1, B) f32}    fixed-size histograms (spikes-per-step
                                   fraction, subscription occupancy,
                                   traversal restart depth);
  gauges     {name: (1,) f32}      last-written values (SET, not summed) —
                                   the device-side health verdict computed
                                   at the end of every ``sim_chunk`` inside
                                   the jitted scan (``GAUGE_KEYS``): a
                                   NaN/Inf census of the physical state,
                                   live synapse-table entry counts, and the
                                   psum'd ``health_flags`` bitmask the
                                   fault-tolerant runner polls each
                                   checkpoint interval (DESIGN.md §10).

Every leaf keeps its leading per-rank axis of size 1 so the whole tree
shards over the 'ranks' mesh axis like the old counters did
(``metrics_specs``); nothing is ``.sum()``-ed before the host asks for a
reduction (``Simulator.stats`` / ``Simulator.metrics``).

Bit-identity contract: all recording happens in plain jnp *outside* the
variant lowerings, on values both lowerings produce identically (the
per-step fired counts, the shared tree, the shared traversal depths), so
``activity_impl``/``connectivity_impl``/``rate_exchange`` variants commit
bit-identical physics counters (tests/test_telemetry.py). Bucket weights
are 0/1 and counts are small integers, so the f32 scatter-adds are exact
and order-independent.

This module is import-light (jax and numpy only) — the engine, kernels, and
connectome all import it without cycles.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

# the 11 legacy byte-accounting counters (paper Tables I/II) ...
LEGACY_KEYS = ("spikes_sent", "rates_sent", "subscription_requests",
               "subscription_overflow", "bh_requests", "bh_responses",
               "formation_requests", "synapses_formed", "synapses_deleted",
               "tree_nodes_downloaded", "request_overflow")
# ... plus the per-phase work counters added with the telemetry layer
EXTRA_KEYS = ("activity_steps", "activity_spikes", "tree_nodes_built",
              "bh_restarts", "bh_query_slots", "bh_queries_live",
              "bh_rounds_run", "bh_frontier_overflow", "bh_rows_run")
COUNTER_KEYS = LEGACY_KEYS + EXTRA_KEYS

# counter -> phase of the three-phase loop it instruments; the report
# groups counters by these (telemetry/report.py)
PHASE_OF = {
    "activity_steps": "activity", "activity_spikes": "activity",
    "spikes_sent": "activity",
    "tree_nodes_built": "tree_build", "tree_nodes_downloaded": "tree_build",
    "bh_requests": "phase_b", "bh_responses": "phase_b",
    "bh_restarts": "phase_b", "bh_query_slots": "phase_b",
    "bh_queries_live": "phase_b", "bh_rounds_run": "phase_b",
    "bh_frontier_overflow": "phase_b", "bh_rows_run": "phase_b",
    "formation_requests": "phase_b",
    "request_overflow": "phase_b",
    "synapses_formed": "synapse_update", "synapses_deleted": "synapse_update",
    "rates_sent": "exchange", "subscription_requests": "exchange",
    "subscription_overflow": "exchange",
}

# histogram -> bucket count. All fixed at trace time.
HIST_BUCKETS = {
    "spikes_per_step": 16,   # fraction of neurons firing per step, [0, 1)
    "subs_occupancy": 16,    # filled fraction of the subscription registry
    "frontier_depth": 8,     # Barnes-Hut restarts per phase-B query
}

# gauges: last-written (not summed) per-rank health values, refreshed at
# the end of every sim_chunk inside the jitted scan (sim/phases.py).
GAUGE_KEYS = (
    "health_flags",      # psum'd bitmask of HEALTH_* below (same on
                         # every rank; read with max(), never sum())
    "nonfinite_state",   # rank-local NaN/Inf count over v/u/calcium/
                         # rate/positions
    "out_edges_live",    # rank-local live out_edges entries (>= 0)
    "in_edges_live",     # rank-local live in_edges entries (>= 0)
)

# health_flags bits (DESIGN.md §10)
HEALTH_NONFINITE = 1     # NaN/Inf anywhere in the physical state
HEALTH_ASYMMETRY = 2     # sum(out_live) != sum(in_live) w/o overflow
HEALTH_CONSERVATION = 4  # live entries outside the [2F-2D, 2F-D] bound

# host-side runner lifecycle counters, merged into Simulator.stats() and
# the repro.telemetry/v1 report (runtime/sim_runner.py maintains them)
LIFECYCLE_KEYS = ("checkpoint_saves", "checkpoint_restores", "rollbacks",
                  "restarts", "degrade_events", "heartbeat_stale")

DEFAULT_HISTORY = 64         # per-chunk ring length (BrainConfig.metrics_history)


@dataclasses.dataclass(frozen=True)
class Metrics:
    """The device-side metrics tree (see module docstring). Immutable:
    every recording method returns a new ``Metrics``. ``m["key"]`` and
    ``m.items()`` delegate to ``counters`` so the old ``stats['key']``
    read idiom keeps working."""
    counters: Dict[str, Any]
    per_chunk: Dict[str, Any]
    hists: Dict[str, Any]
    gauges: Dict[str, Any]

    # -------------------------------------------------- dict-compat reads
    def __getitem__(self, key):
        return self.counters[key]

    def __contains__(self, key):
        return key in self.counters

    def keys(self):
        return self.counters.keys()

    def items(self):
        return self.counters.items()

    # -------------------------------------------------- recording
    def count(self, name: str, delta) -> "Metrics":
        """Add ``delta`` (scalar, any numeric dtype) to counter ``name``."""
        c = dict(self.counters)
        c[name] = c[name] + jnp.asarray(delta, jnp.float32)
        return dataclasses.replace(self, counters=c)

    def observe(self, name: str, bucket, weight=None) -> "Metrics":
        """Scatter-add ``weight`` (default 1.0 each) into histogram
        ``name`` at ``bucket`` (any-shape i32, pre-clipped by the
        caller)."""
        h = dict(self.hists)
        b = jnp.ravel(bucket)
        w = jnp.ones(b.shape, jnp.float32) if weight is None \
            else jnp.ravel(weight).astype(jnp.float32)
        h[name] = h[name].at[0, b].add(w)
        return dataclasses.replace(self, hists=h)

    def record_chunk(self, start_counters: Dict[str, Any],
                     chunk) -> "Metrics":
        """Write this chunk's counter increments (current - ``start``)
        into ring slot ``chunk % H``. Called once per ``sim_chunk`` with
        the counters snapshotted at chunk entry."""
        pc = dict(self.per_chunk)
        for k, ring in pc.items():
            slot = jnp.asarray(chunk, jnp.int32) % ring.shape[1]
            delta = self.counters[k][0] - start_counters[k][0]
            pc[k] = ring.at[0, slot].set(delta)
        return dataclasses.replace(self, per_chunk=pc)

    def set_gauges(self, updates: Dict[str, Any]) -> "Metrics":
        """Overwrite the named gauges with fresh scalar values (broadcast
        to the (1,) per-rank leaf). Gauges are levels, not totals."""
        g = dict(self.gauges)
        for k, v in updates.items():
            g[k] = jnp.reshape(jnp.asarray(v, jnp.float32), (1,))
        return dataclasses.replace(self, gauges=g)


def _flatten_with_keys(m: Metrics):
    K = jax.tree_util.DictKey
    return (((K("counters"), m.counters), (K("per_chunk"), m.per_chunk),
             (K("hists"), m.hists), (K("gauges"), m.gauges)), None)


jax.tree_util.register_pytree_with_keys(
    Metrics, _flatten_with_keys, lambda aux, ch: Metrics(*ch))


def init_metrics(history: int = DEFAULT_HISTORY) -> Metrics:
    """Fresh zeroed per-rank metrics ((1, ...) leaves, sharded P('ranks')
    in the engine's state specs)."""
    return Metrics(
        counters={k: jnp.zeros((1,), jnp.float32) for k in COUNTER_KEYS},
        per_chunk={k: jnp.zeros((1, history), jnp.float32)
                   for k in COUNTER_KEYS},
        hists={k: jnp.zeros((1, b), jnp.float32)
               for k, b in HIST_BUCKETS.items()},
        gauges={k: jnp.zeros((1,), jnp.float32) for k in GAUGE_KEYS})


def metrics_specs(m: Metrics) -> Metrics:
    """PartitionSpecs matching ``init_metrics`` leaf-for-leaf: everything
    is per-rank on its leading axis."""
    return Metrics(
        counters={k: P("ranks") for k in m.counters},
        per_chunk={k: P("ranks", None) for k in m.per_chunk},
        hists={k: P("ranks", None) for k in m.hists},
        gauges={k: P("ranks") for k in m.gauges})


# ==================================================================
# Recorder: the PhaseContext ``metrics`` handle. One object shared by
# every @register_phase implementation; it centralizes the recording
# *math* so each quantity is computed by exactly one jnp expression no
# matter which variant lowering produced its inputs (the bit-identity
# surface of DESIGN.md §9).
# ==================================================================
@dataclasses.dataclass(frozen=True)
class Recorder:
    """Static recording config for one rank's trace. ``n`` is
    neurons-per-rank (the spikes-per-step normalizer)."""
    n: int

    def activity_window(self, m: Metrics, spikes_per_step) -> Metrics:
        """Record one rate window from its (T,) per-step fired counts —
        produced identically by the reference scan (stacked ys) and the
        fused megakernel (the per-step output block)."""
        t = spikes_per_step.shape[0]
        m = m.count("activity_steps", jnp.float32(t))
        m = m.count("activity_spikes", jnp.sum(spikes_per_step))
        nb = HIST_BUCKETS["spikes_per_step"]
        frac = spikes_per_step / jnp.float32(self.n)
        bucket = jnp.clip((frac * nb).astype(jnp.int32), 0, nb - 1)
        return m.observe("spikes_per_step", bucket)

    def tree_built(self, m: Metrics, local_tree) -> Metrics:
        """Count the non-empty octree nodes of this chunk's local tree
        (all levels) — the 'new' algorithm's answer to the old
        algorithm's ``tree_nodes_downloaded``."""
        built = sum(jnp.sum((c > 0).astype(jnp.float32))
                    for c in local_tree.counts)
        return m.count("tree_nodes_built", built)

    def traversal(self, m: Metrics, depth, mask, overflow, rows_run,
                  restarts: int) -> Metrics:
        """Record one phase B over its (Q,) query rows, ``mask`` the rows
        holding a request: ``bh_query_slots`` (Q), ``bh_queries_live``,
        ``bh_rounds_run`` (Q x the ``restarts`` cap), ``bh_restarts`` (the
        iterations live rows needed), ``bh_rows_run`` (``rows_run``, the
        row-restarts the traversal lowering computed: Q x ``restarts`` in
        the fused kernel, the searching rows in whole row blocks in the
        reference), ``bh_frontier_overflow`` (live rows whose frontier
        overflowed) and the frontier-depth histogram. On the live rows,
        depths and overflow flags are identical under both lowerings."""
        w = mask.astype(jnp.float32)
        slots = mask.shape[0]
        m = m.count("bh_query_slots", jnp.float32(slots))
        m = m.count("bh_queries_live", jnp.sum(w))
        m = m.count("bh_rounds_run", jnp.float32(slots * restarts))
        m = m.count("bh_rows_run", jnp.asarray(rows_run, jnp.float32))
        m = m.count("bh_restarts", jnp.sum(depth.astype(jnp.float32) * w))
        m = m.count("bh_frontier_overflow",
                    jnp.sum(overflow.astype(jnp.float32) * w))
        nb = HIST_BUCKETS["frontier_depth"]
        bucket = jnp.clip(depth, 0, nb - 1)
        return m.observe("frontier_depth", bucket, w)

    def subs_occupancy(self, m: Metrics, subs, no_sub) -> Metrics:
        """One histogram entry per chunk: the filled fraction of the
        sparse exchange's subscription registry (zeros stay zero under
        the dense layout)."""
        cap = subs.shape[0]
        frac = jnp.sum((subs != no_sub).astype(jnp.float32)) / cap
        nb = HIST_BUCKETS["subs_occupancy"]
        bucket = jnp.clip((frac * nb).astype(jnp.int32), 0, nb - 1)
        return m.observe("subs_occupancy", bucket[None])


# ==================================================================
# The process's latest metrics. ``Simulator.run``/``step``/``step_with``
# hand their output state's metrics and chunk counter here as device
# references — no transfer on the chunk path; ``last_chunk_counters``
# fetches the per-chunk ring only when it is called.
# ==================================================================
_latest = None


def publish_latest(stats: Metrics, chunk) -> None:
    """Keep ``stats`` and the ``chunk`` counter (chunks run) of the newest
    state as the process's latest metrics, unfetched."""
    global _latest
    _latest = (stats, chunk)


def last_chunk_counters(k: int):
    """The counter increments of each of the last ``k`` chunks of the
    latest published state, from its per-chunk ring, summed over ranks:
    ``{key: (k,) float64 array}``, oldest chunk first. One transfer, made
    now. None when no state was published, or its buffers were donated
    since. ``k`` may exceed neither the chunks run nor the ring's
    length."""
    if _latest is None:
        return None
    if any(getattr(x, "is_deleted", lambda: False)()
           for x in jax.tree.leaves(_latest)):
        return None
    rings, chunk = jax.device_get((_latest[0].per_chunk, _latest[1]))
    chunk = int(chunk)
    history = next(iter(rings.values())).shape[-1]
    if not 1 <= k <= min(chunk, history):
        raise ValueError(f"last {k} chunks asked of a state after {chunk} "
                         f"chunk(s) with a ring of {history}")
    slots = [(chunk - k + i) % history for i in range(k)]
    return {key: np.asarray(r, np.float64).reshape(-1, history)
            .sum(axis=0)[slots] for key, r in rings.items()}
