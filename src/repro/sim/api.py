"""The user-facing simulation facade.

``Simulator`` owns everything the examples and benchmarks used to
hand-roll: mesh construction, sharded init under shard_map, the jitted
per-chunk step, a fused multi-chunk ``run`` (ONE jitted ``lax.scan`` over
chunks with donated carry — no Python dispatch between chunks), summed
stats, scenario-aware lowering for the dry-run/roofline path, and
checkpointing built on ``repro.checkpoint.manager``.

Bit-identity contract: ``engine.build_sim`` (the deprecated shim) returns
this class's own jitted callables, so both entry points share one trace;
and ``run(k)`` is bit-identical to ``k`` sequential ``step()`` calls
because every source of randomness is keyed by counters carried in the
state (``state.chunk``, the per-step counter hash), never by Python-side
loop indices (DESIGN.md §2/§8; tests/test_sim_api.py,
tests/test_multidevice.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import telemetry
from repro.checkpoint import manager
from repro.connectome import routing
from repro.core import engine
from repro.core import spikes
from repro.scenarios import observables
from repro.scenarios import protocol as proto
from repro.sim import phases as sim_phases
from repro.sim import registry
from repro.telemetry import metrics as telemetry_metrics


class Simulator:
    """Drive the MSP brain simulation.

    >>> sim = Simulator.from_config(cfg, scenario=scn)   # mesh + init
    >>> sim.run(20)                                      # one fused scan
    >>> sim.stats()["synapses_formed"]

    Observability: every public entry point runs under a
    ``telemetry.span`` (wall-clock records + jax.profiler trace
    annotations; read back via ``telemetry.spans()``), and
    ``profile_dir=...`` wraps every ``run`` in a profiler capture
    (one trace directory per run, viewable in Perfetto/XProf). ``run``,
    ``step`` and ``step_with`` open their spans as profiler step markers
    numbered by ``host_chunk``, and publish the new state's metrics,
    unfetched, for ``telemetry.last_chunk_counters``.
    """

    def __init__(self, cfg, scenario=None, mesh=None, profile_dir=None):
        # cfg was validated eagerly in BrainConfig.__post_init__ (registry
        # .check_config); here we only make sure every @register_phase
        # decorator has run before the first resolve() inside a trace
        registry.ensure_loaded()
        self.cfg = cfg
        self.scenario = scenario
        self.profile_dir = profile_dir
        self.mesh = mesh if mesh is not None else engine.make_brain_mesh()
        self.num_ranks = self.mesh.shape["ranks"]
        with telemetry.span("sim.construct", ranks=self.num_ranks,
                            n=cfg.neurons_per_rank):
            shapes = jax.eval_shape(
                lambda: engine.init_state(cfg, 0, self.num_ranks, scenario))
            self.specs = engine.state_specs(shapes)

            def init_body():
                rank = jax.lax.axis_index("ranks")
                return engine.init_state(cfg, rank, self.num_ranks, scenario)

            self.init_fn = jax.jit(jax.shard_map(
                init_body, mesh=self.mesh, in_specs=(), out_specs=self.specs,
                check_vma=False))

            def chunk_body(st):
                rank = jax.lax.axis_index("ranks")
                ctx = sim_phases.make_context(cfg, rank, "ranks",
                                              self.num_ranks, scenario)
                return sim_phases.sim_chunk(st, ctx)

            # the un-jitted shard_map'd chunk: `step` jits it directly,
            # `run` scans it — both drive the SAME traced computation
            self._chunk_shard = jax.shard_map(
                chunk_body, mesh=self.mesh, in_specs=(self.specs,),
                out_specs=self.specs, check_vma=False)
            self.chunk_fn = jax.jit(self._chunk_shard, donate_argnums=(0,))
            self._run_cache = {}
            self._state = None
            # the chunk counter of the current state, kept on the host:
            # numbers the step markers without a read from the device
            self.host_chunk = 0
            self._probe_fn = None
            self._rebuild_fn = None
            self._dyn_fn = None
            # host-side runner lifecycle counters (telemetry.metrics
            # .LIFECYCLE_KEYS), merged into stats() and owned jointly
            # with runtime.sim_runner.SimulationRunner
            self.lifecycle = {k: 0 for k in telemetry_metrics.LIFECYCLE_KEYS}

    @classmethod
    def from_config(cls, cfg, scenario=None, mesh=None,
                    profile_dir=None) -> "Simulator":
        return cls(cfg, scenario=scenario, mesh=mesh,
                   profile_dir=profile_dir)

    @classmethod
    def from_connectome(cls, cfg, dataset, scenario=None, mesh=None,
                        profile_dir=None) -> "Simulator":
        """A Simulator whose initial state is wired from a
        ``workloads.datasets.ConnectomeDataset`` instead of empty tables
        (DESIGN.md §13).

        The dataset's row count must equal ``num_ranks *
        cfg.neurons_per_rank`` (gid == global row), its excitation layout
        must match the (cfg, scenario) population table per rank block
        (checked eagerly), and no degree may exceed ``cfg.max_synapses``.
        Under the sparse exchange the subscription registry is sized from
        the MEASURED per-rank unique-remote-source count (baked into
        ``cfg.subs_cap_base``; ``subs_cap_factor`` stays head-room on top)
        so heavy-tailed degree distributions don't start life overflowing,
        and the registry itself is derived through ``rebuild_exchange`` —
        the exact per-chunk computation, so sparse == dense bit-identity
        holds from the very first chunk."""
        from repro.workloads import datasets as wds
        wds.validate(dataset)
        mesh = mesh if mesh is not None else engine.make_brain_mesh()
        num_ranks = mesh.shape["ranks"]
        n = cfg.neurons_per_rank
        if dataset.num_neurons != num_ranks * n:
            raise ValueError(
                f"dataset {dataset.name!r} has {dataset.num_neurons} "
                f"neurons; need num_ranks*neurons_per_rank = "
                f"{num_ranks}*{n} = {num_ranks * n} (gid == global row)")
        wds.check_population_layout(dataset, cfg, scenario, num_ranks)
        if cfg.rate_exchange == "sparse" and cfg.subs_cap_base is None:
            cfg = dataclasses.replace(
                cfg, subs_cap_base=wds.max_unique_remote_sources(dataset, n))
        sim = cls(cfg, scenario=scenario, mesh=mesh,
                  profile_dir=profile_dir)
        sim._install_connectome(dataset)
        return sim

    def _install_connectome(self, dataset) -> None:
        """Overwrite the freshly initialized state's connectivity with the
        dataset: positions, front-packed out/in edge tables, per-neuron
        excitation, and synaptic-element counts covering the wired degrees
        (each neuron keeps its seeded vacant draw ON TOP of the wired
        elements, so the loaded connectome is homeostatically stable — the
        first update grows from it rather than retracting it)."""
        from repro.workloads import datasets as wds
        with telemetry.span("sim.from_connectome",
                            neurons=dataset.num_neurons,
                            edges=dataset.num_edges):
            out_e, in_e = wds.edge_tables(dataset, self.cfg.max_synapses)
            st = self.init()
            sh = self.shardings()
            out_deg = (out_e >= 0).sum(1).astype(np.float32)
            in_deg = (in_e >= 0).sum(1).astype(np.float32)
            vac_a = np.asarray(jax.device_get(st.neurons.ax_elements))
            vac_d = np.asarray(jax.device_get(st.neurons.de_elements))
            neurons = st.neurons._replace(
                ax_elements=jax.device_put(vac_a + out_deg,
                                           sh.neurons.ax_elements),
                de_elements=jax.device_put(vac_d + in_deg,
                                           sh.neurons.de_elements),
                is_excitatory=jax.device_put(dataset.is_excitatory,
                                             sh.neurons.is_excitatory))
            self._state = st._replace(
                neurons=neurons,
                positions=jax.device_put(dataset.positions, sh.positions),
                out_edges=jax.device_put(out_e, sh.out_edges),
                in_edges=jax.device_put(in_e, sh.in_edges))
            # derive subs/rate_slots/remote_rates from the installed
            # in-edge table (rates are all zero, so the pushed buffer
            # matches the dense table's zeros bit-for-bit)
            self.rebuild_exchange()

    # ------------------------------------------------------------ state
    @property
    def state(self):
        """The current BrainState (global sharded arrays); initializes on
        first access."""
        if self._state is None:
            self.init()
        return self._state

    def init(self):
        """(Re)initialize from cfg.seed and return the fresh state."""
        with telemetry.span("sim.init"):
            self._state = self.init_fn()
        self.host_chunk = 0
        return self._state

    def _advanced(self, num_chunks: int) -> None:
        """Count chunks just dispatched, and hand the new state's metrics
        and chunk counter to the telemetry as device references (no
        transfer). The dispatch that donates this state replaces them
        right after."""
        self.host_chunk += num_chunks
        telemetry.publish_latest(self._state.stats, self._state.chunk)

    # ------------------------------------------------------------ driving
    def step(self):
        """Advance one chunk (Delta activity steps + connectivity update)."""
        state = self.state
        with telemetry.span("sim.step", step_num=self.host_chunk):
            self._state = self.chunk_fn(state)
        self._advanced(1)
        return self._state

    def step_with(self, dyn):
        """Advance one chunk with a ``phases.DynamicParams`` pytree fed as
        a TRACED ARGUMENT (replicated leaves) — the host may change the
        values between every chunk without a single retrace, which
        ``dyn_compile_count`` asserts. This is the drive surface of the
        assimilation loop (``workloads.assimilate``; ROADMAP item 5's
        static/dynamic split, first slice). With ``dyn=None`` semantics
        are ``step()``'s exactly (use that instead — the argument-free
        trace is the bit-identity baseline)."""
        if self._dyn_fn is None:
            cfg, num_ranks, scn = self.cfg, self.num_ranks, self.scenario

            def body(st, dyn):
                rank = jax.lax.axis_index("ranks")
                ctx = sim_phases.make_context(cfg, rank, "ranks", num_ranks,
                                              scn, dyn=dyn)
                return sim_phases.sim_chunk(st, ctx)

            dyn_specs = jax.tree.map(lambda _: P(), dyn)
            self._dyn_fn = jax.jit(jax.shard_map(
                body, mesh=self.mesh, in_specs=(self.specs, dyn_specs),
                out_specs=self.specs, check_vma=False), donate_argnums=(0,))
        state = self.state
        with telemetry.span("sim.step_with", step_num=self.host_chunk):
            self._state = self._dyn_fn(state, dyn)
        self._advanced(1)
        return self._state

    def dyn_compile_count(self) -> int:
        """Number of compiled traces behind ``step_with`` — the
        assimilation loop asserts this stays at 1 across a whole run
        (retrace-free dynamic params)."""
        return 0 if self._dyn_fn is None else self._dyn_fn._cache_size()

    def run(self, num_chunks: int, recorder: Optional[object] = None):
        """Advance ``num_chunks`` chunks as ONE jitted ``lax.scan`` with
        donated carry — a single dispatch, no per-chunk Python overhead.

        With ``recorder`` (an ``observables.Recorder``), one row of
        per-region observables is recorded after every chunk (on the
        global arrays, inside the same scan) and the advanced recorder is
        returned: ``state, rec = sim.run(k, recorder=rec)``. Without it,
        returns the final state.

        Runs under a ``telemetry.span``, a step marker numbered by the
        first chunk it advances; with ``profile_dir`` set, the whole call
        (fenced by ``block_until_ready``) is captured as one profiler
        trace under ``<profile_dir>/``."""
        state = self.state   # init outside the run span/capture
        fn = self._run_fn(int(num_chunks), recorder is not None)
        with telemetry.profile(self.profile_dir), \
                telemetry.span("sim.run", step_num=self.host_chunk,
                               chunks=int(num_chunks)):
            if recorder is None:
                self._state = fn(state)
                out = self._state
            else:
                self._state, recorder = fn(state, recorder)
                out = (self._state, recorder)
            if self.profile_dir:
                # fence so the capture contains the device work, not just
                # the async dispatch
                jax.block_until_ready(self._state)
        self._advanced(int(num_chunks))
        return out

    def _run_fn(self, k: int, with_recorder: bool):
        key = (k, with_recorder)
        if key in self._run_cache:
            return self._run_cache[key]
        chunk, cfg = self._chunk_shard, self.cfg
        scn = self.scenario
        regions = scn.regions if scn is not None else ()
        events = scn.events if scn is not None else ()

        if with_recorder:
            def body(carry, _):
                st, rec = carry
                st = chunk(st)
                # st.chunk already advanced: the global step at this
                # chunk's end, correct even when resuming from a restore
                alive = proto.alive_mask(events, regions, st.positions,
                                         st.chunk * cfg.rate_period) \
                    if events else None
                rec = observables.record(rec, st.positions,
                                         st.neurons.calcium,
                                         st.neurons.rate, st.out_edges,
                                         regions, alive)
                return (st, rec), None

            def runner(st, rec):
                (st, rec), _ = jax.lax.scan(body, (st, rec), None, length=k)
                return st, rec

            # only the state is donated: donating the caller's recorder
            # would silently invalidate their reference, and its buffers
            # are a few (cap, nb) rows — nothing worth reusing
            fn = jax.jit(runner, donate_argnums=(0,))
        else:
            def runner(st):
                st, _ = jax.lax.scan(lambda s, _: (chunk(s), None), st,
                                     None, length=k)
                return st

            fn = jax.jit(runner, donate_argnums=(0,))
        self._run_cache[key] = fn
        return fn

    # ------------------------------------------------------------ readout
    def stats(self, reduce: bool = True) -> dict:
        """The device counters (paper byte accounting + per-phase work),
        fetched in ONE ``jax.device_get`` of the whole counter subtree
        (not one transfer per key), plus the host-side runner lifecycle
        counters (``checkpoint_saves``/``restores``/``rollbacks``/
        ``restarts``/``degrade_events``). ``reduce=True`` (default) sums
        over ranks to plain floats; ``reduce=False`` keeps the (R,)
        per-rank resolution as host arrays (device counters only)."""
        counters = jax.device_get(self.state.stats.counters)
        if reduce:
            out = {k: float(v.sum()) for k, v in counters.items()}
            out.update({k: float(v) for k, v in self.lifecycle.items()})
            return out
        return dict(counters)

    def health(self) -> dict:
        """The health gauges written by the LAST completed chunk (one
        cheap transfer of four scalars per rank — the per-interval poll
        of DESIGN.md §10). ``health_flags`` is the psum'd global bitmask
        (reduced with max, identical on every rank); the census gauges
        sum over ranks. Zero flags = healthy. Stale until a chunk has
        run — use ``probe_health`` to evaluate the current state."""
        g = jax.device_get(self.state.stats.gauges)
        return {k: float(v.max() if k == "health_flags" else v.sum())
                for k, v in g.items()}

    def probe_health(self) -> int:
        """Recompute the health verdict on the CURRENT state (same device
        math as the in-scan gauge refresh — ``phases.health_verdict``) and
        return the global ``health_flags`` bitmask. The runner calls this
        on the exact state it is about to checkpoint, so every checkpoint
        on disk is verified-good."""
        if self._probe_fn is None:
            cfg, num_ranks, scn = self.cfg, self.num_ranks, self.scenario

            def body(st):
                rank = jax.lax.axis_index("ranks")
                ctx = sim_phases.make_context(cfg, rank, "ranks", num_ranks,
                                              scn)
                stats = sim_phases.health_verdict(st, ctx)
                return stats.gauges["health_flags"]

            self._probe_fn = jax.jit(jax.shard_map(
                body, mesh=self.mesh, in_specs=(self.specs,),
                out_specs=P("ranks"), check_vma=False))
        flags = jax.device_get(self._probe_fn(self.state))
        return int(flags.max())

    def rebuild_exchange(self):
        """Re-derive the sparse rate-exchange fields (subscription
        registry, edge->slot remap, subscribed-rate buffer) from the
        in-edge table and advertised rates — the exact computation
        ``exchange_sparse`` runs at every chunk's end, so on a state
        restored at a chunk boundary the rebuilt fields are bit-identical
        to the checkpointed ones. The elastic resume path uses this to
        rebuild the registry for a new rank count; no-op under the dense
        layout (whose table restores/reshapes directly)."""
        if self.cfg.rate_exchange != "sparse":
            return self.state
        if self._rebuild_fn is None:
            cfg, num_ranks = self.cfg, self.num_ranks
            n = cfg.neurons_per_rank

            def body(st):
                rank = jax.lax.axis_index("ranks")
                subs, rate_slots, _ = spikes.build_subscriptions(
                    st.in_edges, rank, n, routing.cap_subs(cfg, num_ranks))
                remote_rates, _ = routing.push_subscribed_rates(
                    subs, st.neurons.rate, "ranks", num_ranks, n)
                return st._replace(subs=subs, rate_slots=rate_slots,
                                   remote_rates=remote_rates)

            self._rebuild_fn = jax.jit(jax.shard_map(
                body, mesh=self.mesh, in_specs=(self.specs,),
                out_specs=self.specs, check_vma=False))
        with telemetry.span("sim.rebuild_exchange"):
            self._state = self._rebuild_fn(self.state)
        return self._state

    def shardings(self):
        """The state's NamedShardings on THIS simulator's mesh (same
        structure as ``self.specs``)."""
        return jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec), self.specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))

    def metrics(self) -> "telemetry.Metrics":
        """The full device metrics tree — counters, per-chunk rings, and
        histograms — fetched in one transfer; leaves are host arrays with
        the per-rank leading axis intact."""
        with telemetry.span("sim.metrics"):
            return jax.device_get(self.state.stats)

    def lower(self, num_chunks: Optional[int] = None):
        """Lower one sim chunk at the global sharded shapes — scenario
        included, so the dry-run/roofline path sees the trace that will
        actually run (stimulus tables, population params, lesion masks).
        With ``num_chunks``, lower the program ``run(num_chunks)`` calls
        instead; compiling it ahead leaves the executable in jit's
        in-memory cache, so that ``run`` compiles nothing."""
        fn = self.chunk_fn if num_chunks is None else \
            self._run_fn(int(num_chunks), False)
        shapes = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            jax.eval_shape(self.init_fn), self.shardings())
        with telemetry.span("sim.lower"):
            return fn.lower(shapes)

    # ------------------------------------------------------------ persist
    def ckpt_metadata(self) -> dict:
        """Checkpoint metadata: enough for a fresh process (possibly on a
        different rank count or after a degrade) to decide how to restore
        — see runtime.sim_runner.try_resume / runtime.elastic."""
        return {"cfg": self.cfg.name,
                "rate_exchange": self.cfg.rate_exchange,
                "num_ranks": self.num_ranks,
                "neurons_per_rank": self.cfg.neurons_per_rank,
                "subs_cap_factor": self.cfg.subs_cap_factor,
                "subs_cap_base": self.cfg.subs_cap_base,
                "requests_cap_factor": self.cfg.requests_cap_factor,
                "lifecycle": dict(self.lifecycle)}

    def save(self, path: str) -> int:
        """Atomic full-state checkpoint at ``<path>/step_<chunk>/`` via
        ``checkpoint.manager``. Returns the saved chunk number."""
        st = self.state
        step = int(jax.device_get(st.chunk))
        with telemetry.span("sim.save", step=step):
            manager.save(path, step, st, metadata=self.ckpt_metadata())
        self.lifecycle["checkpoint_saves"] += 1
        return step

    def restore(self, path: str, step: Optional[int] = None) -> int:
        """Load a checkpoint (latest step by default) and reshard it onto
        THIS simulator's mesh. ``run``/``step`` continue bit-identically
        to an uninterrupted run: all randomness is keyed by the restored
        ``chunk`` counter and the per-step hash, and the stats
        accumulators travel with the state."""
        if step is None:
            step = manager.latest_step(path)
            if step is None:
                raise FileNotFoundError(f"no checkpoint under {path!r}")
        with telemetry.span("sim.restore", step=step):
            target = jax.eval_shape(self.init_fn)
            tree, _ = manager.restore(path, step, target, self.shardings())
            self._state = tree
        self.host_chunk = int(step)
        self.lifecycle["checkpoint_restores"] += 1
        return step
