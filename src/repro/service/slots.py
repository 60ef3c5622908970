"""SlotBatch: the device layer of the multi-tenant service — B independent
scenario instances stacked on a leading *slot* axis, one compiled trace
(DESIGN.md §12).

Layout: every ``BrainState`` leaf gains a leading axis of size
``num_slots`` (PartitionSpec ``P(None, *solo_spec)`` — the slot axis is
never sharded; each lane stays sharded over 'ranks' exactly like a solo
run). One service chunk is ``shard_map(vmap(sim_chunk))``: the vmap lifts
every per-instance op to a batched op that is elementwise in the slot
axis, and the collectives batch per-lane over 'ranks' only — **no op in
the trace mixes lanes**, which is the fault-isolation argument: a NaN,
an overflow, or any other poisoned value in lane *b* is algebraically
confined to lane *b*.

Per-slot identity rides in the lane itself: the seed is a traced (B,)
argument (``dataclasses.replace(cfg, seed=lane_seed)`` inside the vmapped
body — integer Threefry hashing is exact, so a traced seed produces the
same bits as a solo run's static seed), and the chunk counter is already
a per-state field. Together with the counter-keyed randomness contract
(DESIGN.md §2) this makes slot placement invisible: a lane's trajectory
is bit-identical to a solo ``Simulator`` run with the same config + seed,
asserted on a 4-rank mesh for dense and sparse exchange in
tests/test_service.py.

The fused Pallas lowerings bake ``seed`` as a static kernel parameter, so
a SlotBatch requires the jnp reference lowerings (typed
``ServiceConfigError`` otherwise) — the batch axis and the kernels are
orthogonal wins; fusing the vmapped trace is ROADMAP follow-up work.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import engine
from repro.service.types import ServiceConfigError
from repro.sim import phases as sim_phases
from repro.sim import registry

# cfg fields that must stay on the jnp reference lowering: the Pallas
# kernels take seed as a *static* kernel parameter, incompatible with the
# per-slot traced seed
_REFERENCE_ONLY = ("activity_impl", "connectivity_impl", "tree_impl",
                   "apply_impl")


def stacked_specs(specs):
    """Prepend the (unsharded) slot axis to every solo PartitionSpec."""
    return jax.tree.map(lambda sp: P(None, *sp), specs,
                        is_leaf=lambda x: isinstance(x, P))


class SlotBatch:
    """Device-side state + compiled callables for ``num_slots`` co-batched
    instances of one ``BrainConfig``/scenario template. Host-side slot
    bookkeeping lives in ``repro.service.service.SimulationService``."""

    def __init__(self, cfg, num_slots: int, mesh=None, scenario=None):
        for field in _REFERENCE_ONLY:
            if getattr(cfg, field) != "reference":
                raise ServiceConfigError(
                    f"service template needs {field}='reference' (the "
                    f"fused kernels bake the seed as a static parameter; "
                    f"the service's per-slot seed is traced), got "
                    f"{getattr(cfg, field)!r}")
        if num_slots < 1:
            raise ServiceConfigError(f"num_slots must be >= 1, "
                                     f"got {num_slots}")
        registry.ensure_loaded()
        self.cfg = cfg
        self.scenario = scenario
        self.num_slots = int(num_slots)
        self.mesh = mesh if mesh is not None else engine.make_brain_mesh()
        self.num_ranks = self.mesh.shape["ranks"]
        shapes = jax.eval_shape(
            lambda: engine.init_state(cfg, 0, self.num_ranks, scenario))
        self.specs = engine.state_specs(shapes)
        self.sspecs = stacked_specs(self.specs)
        self._build()

    # ------------------------------------------------------------ build
    def _ctx(self, cfg_slot, rank):
        return sim_phases.make_context(cfg_slot, rank, "ranks",
                                       self.num_ranks, self.scenario)

    def _build(self):
        cfg, R, B = self.cfg, self.num_ranks, self.num_slots
        mesh, specs, sspecs = self.mesh, self.specs, self.sspecs
        scenario = self.scenario

        def init_all_body(seeds):
            rank = jax.lax.axis_index("ranks")

            def one(sd):
                c = dataclasses.replace(cfg, seed=sd)
                return engine.init_state(c, rank, R, scenario)

            return jax.vmap(one)(seeds)

        self.init_all = jax.jit(jax.shard_map(
            init_all_body, mesh=mesh, in_specs=(P(None),),
            out_specs=sspecs, check_vma=False))

        def init_one_body(seed):
            rank = jax.lax.axis_index("ranks")
            return engine.init_state(dataclasses.replace(cfg, seed=seed),
                                     rank, R, scenario)

        self.init_lane = jax.jit(jax.shard_map(
            init_one_body, mesh=mesh, in_specs=(P(),), out_specs=specs,
            check_vma=False))

        def chunk_body(st, seeds):
            rank = jax.lax.axis_index("ranks")

            def one(s, sd):
                return sim_phases.sim_chunk(
                    s, self._ctx(dataclasses.replace(cfg, seed=sd), rank))

            return jax.vmap(one)(st, seeds)

        # the service chunk: ONE compiled trace, shared by every slot and
        # every tick (seeds are a traced argument — no retrace on tenant
        # turnover); donated carry like Simulator.run
        self.step = jax.jit(jax.shard_map(
            chunk_body, mesh=mesh, in_specs=(sspecs, P(None)),
            out_specs=sspecs, check_vma=False), donate_argnums=(0,))

        def probe_body(st, seeds):
            rank = jax.lax.axis_index("ranks")

            def one(s, sd):
                ctx = self._ctx(dataclasses.replace(cfg, seed=sd), rank)
                return sim_phases.health_verdict(s, ctx).gauges[
                    "health_flags"]

            return jax.vmap(one)(st, seeds)      # (B, 1) per rank

        # health re-probe of the CURRENT stacked state (per-slot verdict
        # on exactly what a snapshot would capture — DESIGN.md §10 rule
        # "every rollback target is verified-good", now per slot)
        self._probe = jax.jit(jax.shard_map(
            probe_body, mesh=mesh, in_specs=(sspecs, P(None)),
            out_specs=P(None, "ranks"), check_vma=False))

        # lane surgery: dynamic-update-slice on the slot axis only —
        # every other lane's bits pass through untouched
        self._place = jax.jit(
            lambda st, lane, b: jax.tree.map(
                lambda f, o: f.at[b].set(o), st, lane),
            donate_argnums=(0,))
        self._extract = jax.jit(
            lambda st, b: jax.tree.map(lambda f: f[b], st))

        def observe_body(st):
            live = jnp.sum((st.out_edges >= 0).astype(jnp.float32),
                           axis=(1, 2))
            return jnp.stack([st.chunk.astype(jnp.float32),
                              jnp.mean(st.neurons.rate, axis=1),
                              jnp.mean(st.neurons.calcium, axis=1),
                              live], axis=1)

        # per-slot observable row (chunk, mean rate, mean calcium, live
        # out-edges): one tiny transfer per tick feeds the result streams
        self._observe = jax.jit(observe_body)

    # ------------------------------------------------------------ lanes
    def place(self, state, lane, b: int):
        """Write ``lane`` (a solo-shaped BrainState) into slot ``b``."""
        return self._place(state, lane, jnp.asarray(b, jnp.int32))

    def extract(self, state, b: int):
        """Copy slot ``b`` out as a solo-shaped BrainState."""
        return self._extract(state, jnp.asarray(b, jnp.int32))

    # ---------------------------------------------------------- readouts
    def probe(self, state, seeds) -> np.ndarray:
        """Per-slot health bitmask of the CURRENT state: (B,) ints. The
        in-scan gauges only reflect the last completed chunk; this
        re-evaluates ``health_verdict`` on the state as it is now."""
        flags = jax.device_get(self._probe(state, seeds))   # (B, R)
        return np.asarray(flags).max(axis=1).astype(np.int64)

    def health_flags(self, state) -> np.ndarray:
        """Per-slot psum'd health bitmask written by the last completed
        chunk (the in-scan verdict): (B,) ints, max-reduced over ranks."""
        g = jax.device_get(state.stats.gauges["health_flags"])  # (B, R)
        return np.asarray(g).max(axis=1).astype(np.int64)

    def chunks(self, state) -> np.ndarray:
        """Per-slot chunk counters: (B,) ints."""
        return np.asarray(jax.device_get(state.chunk)).astype(np.int64)

    def counters(self, state, b: Optional[int] = None):
        """Device counters summed over ranks: dict of (B,) arrays, or of
        floats for one slot when ``b`` is given."""
        c = jax.device_get(state.stats.counters)
        out = {k: np.asarray(v).sum(axis=tuple(range(1, np.ndim(v))))
               for k, v in c.items()}
        if b is None:
            return out
        return {k: float(v[b]) for k, v in out.items()}

    def observe(self, state) -> np.ndarray:
        """(B, 4) observable rows (chunk, mean rate, mean calcium, live
        out-edges) for the streaming path."""
        return np.asarray(jax.device_get(self._observe(state)))

    # ------------------------------------------------------------- misc
    def lane_sharding(self, leaf_path_example: Any = None):
        """NamedShardings of the stacked tree (for chaos injectors that
        re-place a host-edited leaf)."""
        return jax.tree.map(
            lambda sp: NamedSharding(self.mesh, sp), self.sspecs,
            is_leaf=lambda x: isinstance(x, P))
