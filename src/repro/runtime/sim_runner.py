"""Fault-tolerant simulation runner: ``SimulationRunner`` wraps
``Simulator.run`` with the full run lifecycle (DESIGN.md §10).

Mechanisms:
  * periodic async atomic keep-k checkpoints (``AsyncCheckpointer``) at
    chunk boundaries + resume-from-latest — the counter-keyed randomness
    contract (seed, chunk, per-step hash) makes kill-and-resume at any
    chunk boundary bit-identical to an uninterrupted run;
  * device-side health verdict: the jitted scan refreshes the health
    gauges every chunk (``sim.phases.health_verdict``); the runner polls
    the psum'd ``health_flags`` bitmask each checkpoint interval (a
    four-scalar transfer), and additionally *probes* the exact state it
    is about to save — every checkpoint on disk is verified-good, so a
    rollback target is never itself poisoned;
  * bounded rollback: on a bad verdict, restore the newest checkpoint
    that passes checksum + structure verification (walking past corrupt
    steps) and re-run; more than ``max_rollbacks`` raises;
  * graceful degradation: persistent ``subscription_overflow`` /
    ``request_overflow`` across ``overflow_patience`` intervals
    re-materializes the Simulator through the elastic restore path with a
    grown ``subs_cap_factor`` (then falls back to ``rate_exchange=
    'dense'``), or a grown ``requests_cap_factor`` — each escalation is a
    ``runner.degrade`` span and a ``degrade_events`` counter;
  * SIGTERM-style preemption draining: ``preempt()`` (signal-handler
    safe) makes the loop write a final checkpoint and return
    ``"preempted"`` at the next chunk boundary;
  * atomic heartbeat JSON per interval (``fault_tolerance
    .write_heartbeat``) for an external watchdog, with an optional
    self-check of the previous beat's age (``fault_tolerance
    .read_heartbeat``) surfacing a ``heartbeat_stale`` lifecycle count;
  * elastic resume: a fresh runner whose cfg disagrees with the
    checkpoint metadata (rank count after shrinking the job, exchange
    layout or caps after a degrade) routes through
    ``elastic.remesh_restore_brain`` instead of a direct reshard.

Lifecycle counters (``checkpoint_saves``/``checkpoint_restores``/
``rollbacks``/``restarts``/``degrade_events``) live on the Simulator and
surface through ``Simulator.stats()`` and the ``repro.telemetry/v1``
report. Fault injection for all of the above lives in ``runtime.chaos``;
deterministic recovery tests in tests/test_runtime.py.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional

import jax

from repro import telemetry
from repro.checkpoint import manager
from repro.checkpoint.manager import AsyncCheckpointer
from repro.runtime import elastic
from repro.runtime.fault_tolerance import read_heartbeat, write_heartbeat


@dataclasses.dataclass
class SimRunnerConfig:
    """Runner knobs. ``ckpt_every`` is in chunks (one chunk = Delta
    activity steps + one connectivity update); a smaller value narrows
    the re-run window after a fault at the cost of checkpoint I/O."""
    ckpt_dir: str
    ckpt_every: int = 10
    keep: int = 3
    max_rollbacks: int = 3
    heartbeat_path: Optional[str] = None
    # previous-beat age (s) beyond which the runner records a
    # heartbeat_stale lifecycle event before publishing a fresh beat —
    # the in-band echo of the external watchdog's verdict
    heartbeat_max_age_s: Optional[float] = None
    # degradation ladder
    max_degrades: int = 2
    overflow_patience: int = 2     # consecutive overflowing intervals
    # achieved-cap multiplier per escalation; 0 disables growth so the
    # first subscription-overflow escalation falls straight back to dense
    subs_growth_factor: int = 4
    requests_growth_factor: int = 4


# metadata keys that must agree for a direct (non-elastic) resume
_SHAPE_KEYS = ("num_ranks", "neurons_per_rank", "rate_exchange",
               "subs_cap_factor", "requests_cap_factor")


class SimulationRunner:
    """Drive a Simulator to a target chunk count, surviving preemption,
    state corruption, checkpoint corruption, and exchange-capacity
    exhaustion.

    >>> runner = SimulationRunner(SimRunnerConfig(ckpt_dir), cfg)
    >>> runner.run(100)      # resumes from ckpt_dir if checkpoints exist
    'done'
    """

    def __init__(self, run_cfg: SimRunnerConfig, cfg=None, sim=None,
                 scenario=None, mesh=None, resume: bool = True):
        from repro.sim.api import Simulator
        if (cfg is None) == (sim is None):
            raise ValueError("pass exactly one of cfg= or sim=")
        self.cfg = run_cfg
        self.scenario = scenario if sim is None else sim.scenario
        self.mesh_arg = mesh
        self.sim = sim if sim is not None else Simulator(
            cfg, scenario=scenario, mesh=mesh)
        self.ckpt = AsyncCheckpointer(run_cfg.ckpt_dir, keep=run_cfg.keep)
        self.preempted = False
        self.degrades = 0
        self._overflow_strikes = 0
        self._last_saved_chunk: Optional[int] = None
        # chaos hooks: callables(runner) invoked after every segment,
        # BEFORE the health poll/checkpoint — see runtime.chaos
        self.chaos_hooks: List[Callable] = []
        if resume:
            self.try_resume()

    # ---------------------------------------------------------- resume
    def _latest_valid_manifest(self):
        self.ckpt.wait()
        for step in reversed(manager.steps_available(self.cfg.ckpt_dir)):
            try:
                arrays, manifest = manager.load_arrays(self.cfg.ckpt_dir,
                                                       step)
            except manager.CorruptCheckpointError:
                continue
            return step, manifest
        return None, None

    def try_resume(self) -> bool:
        """Adopt the newest valid checkpoint, if any. Shape-compatible
        checkpoints reshard directly onto the runner's mesh; anything
        else (different rank count, exchange layout, or caps) goes
        through the elastic restore, which re-derives rank-local
        sharding and rebuilds the subscription registry."""
        step, manifest = self._latest_valid_manifest()
        if step is None:
            return False
        meta = manifest.get("metadata", {})
        mine = self.sim.ckpt_metadata()
        direct = all(meta.get(k) == mine[k] for k in _SHAPE_KEYS)
        with telemetry.span("runner.restore", step=step,
                            elastic=not direct):
            if direct:
                self.sim.restore(self.cfg.ckpt_dir, step)
                self.sim.lifecycle.update(
                    {k: int(v)
                     for k, v in meta.get("lifecycle", {}).items()})
                self.sim.lifecycle["checkpoint_restores"] += 1
            else:
                self.sim, step = elastic.remesh_restore_brain(
                    self.cfg.ckpt_dir, self.sim.cfg, mesh=self.mesh_arg,
                    step=step, scenario=self.scenario)
        self.sim.lifecycle["restarts"] += 1
        self._last_saved_chunk = step
        return True

    # ------------------------------------------------------- checkpoint
    def _checkpoint(self) -> bool:
        """Probe the current state and, if healthy, save it (async,
        atomic, keep-k). Returns False — save REFUSED — when the probe
        flags corruption, so a poisoned state can never become a
        rollback target."""
        if self.sim.probe_health() != 0:
            return False
        step = int(jax.device_get(self.sim.state.chunk))
        with telemetry.span("runner.checkpoint_save", step=step):
            self.ckpt.save(step, self.sim.state,
                           metadata=dict(self.sim.ckpt_metadata(),
                                         chunk=step))
        self.sim.lifecycle["checkpoint_saves"] += 1
        self._last_saved_chunk = step
        return True

    def _rollback(self):
        """Restore the newest checkpoint that verifies AND matches the
        current state structure (post-degrade runners skip pre-degrade
        shapes), bounded by ``max_rollbacks``."""
        self.sim.lifecycle["rollbacks"] += 1
        if self.sim.lifecycle["rollbacks"] > self.cfg.max_rollbacks:
            raise RuntimeError(
                f"giving up after {self.cfg.max_rollbacks} rollbacks")
        self.ckpt.wait()
        target = jax.eval_shape(self.sim.init_fn)
        shardings = self.sim.shardings()
        with telemetry.span("runner.rollback"):
            for step in reversed(
                    manager.steps_available(self.cfg.ckpt_dir)):
                try:
                    tree, _ = manager.restore(self.cfg.ckpt_dir, step,
                                              target, shardings)
                except (manager.CorruptCheckpointError, KeyError,
                        ValueError):
                    continue
                self.sim._state = tree
                self.sim.host_chunk = step
                self.sim.lifecycle["checkpoint_restores"] += 1
                if self.sim.probe_health() == 0:
                    return step
        raise RuntimeError("no healthy checkpoint to roll back to")

    # ---------------------------------------------------------- degrade
    def _maybe_degrade(self, stats_before: dict, stats_after: dict):
        """Escalate when the exchange keeps overflowing: every dropped
        subscription/request this interval counts a strike; after
        ``overflow_patience`` consecutive strikes, re-materialize the
        Simulator one rung down the ladder (grown sparse caps -> dense
        fallback / grown request caps) via the elastic restore at the
        same rank count."""
        keys = ("subscription_overflow", "request_overflow")
        delta = {k: stats_after[k] - stats_before[k] for k in keys}
        if not any(v > 0 for v in delta.values()):
            self._overflow_strikes = 0
            return
        self._overflow_strikes += 1
        if self._overflow_strikes < self.cfg.overflow_patience:
            return
        self._overflow_strikes = 0
        if self.degrades >= self.cfg.max_degrades:
            return
        from repro.connectome import routing
        cfg = self.sim.cfg
        if cfg.rate_exchange == "sparse" and \
                delta["subscription_overflow"] > 0:
            # grow the ACHIEVED cap (cap_subs floors/ceils the factor),
            # not the raw factor: pick the smallest integer factor whose
            # cap is >= growth x the current cap
            cap_old = routing.cap_subs(cfg, self.sim.num_ranks)
            denom = routing.subs_base(cfg, self.sim.num_ranks)
            new_factor = -(-cap_old * self.cfg.subs_growth_factor
                           // denom)
            new_cfg = dataclasses.replace(cfg,
                                          subs_cap_factor=int(new_factor))
            if routing.cap_subs(new_cfg, self.sim.num_ranks) <= cap_old:
                # cap already at its hard ceiling (or growth disabled):
                # last rung — the dense reference layout never overflows
                new_cfg = dataclasses.replace(cfg, rate_exchange="dense")
                action = "dense_fallback"
            else:
                action = "grow_subs_cap"
        else:
            new_cfg = dataclasses.replace(
                cfg, requests_cap_factor=(cfg.requests_cap_factor
                                          * self.cfg.requests_growth_factor))
            action = "grow_requests_cap"
        # checkpoint the (healthy) current state so the elastic path has
        # a boundary to restore from, then swap in the re-materialized
        # Simulator and checkpoint again under the NEW shapes so later
        # rollbacks stay structure-compatible
        if not self._checkpoint():
            return    # poisoned right now: let the health path roll back
        self.ckpt.wait()
        with telemetry.span("runner.degrade", action=action,
                            chunk=self._last_saved_chunk):
            self.sim, _ = elastic.remesh_restore_brain(
                self.cfg.ckpt_dir, new_cfg, mesh=self.mesh_arg,
                step=self._last_saved_chunk, scenario=self.scenario)
        self.degrades += 1
        self.sim.lifecycle["degrade_events"] += 1
        self._checkpoint()

    # ------------------------------------------------------------- misc
    def preempt(self):
        """External preemption signal (a SIGTERM handler calls this);
        the loop drains at the next chunk boundary."""
        self.preempted = True

    def _heartbeat(self, chunk: int):
        if self.cfg.heartbeat_path:
            # staleness self-check: if the previous beat aged past the
            # watchdog threshold, the interval overran — record it as a
            # lifecycle event (the in-band echo of read_heartbeat's
            # 'stale' verdict) before publishing the fresh beat
            if self.cfg.heartbeat_max_age_s is not None:
                _, _, verdict = read_heartbeat(
                    self.cfg.heartbeat_path,
                    max_age_s=self.cfg.heartbeat_max_age_s)
                if verdict == "stale":
                    self.sim.lifecycle["heartbeat_stale"] += 1
            write_heartbeat(self.cfg.heartbeat_path,
                            {"chunk": chunk,
                             "lifecycle": dict(self.sim.lifecycle)})

    # -------------------------------------------------------- main loop
    def run(self, num_chunks: int) -> str:
        """Advance ``num_chunks`` chunks past the CURRENT chunk (resumed
        runs count from where the checkpoint left off... i.e. a fresh
        runner resumed at chunk j with run(k-j) lands exactly on chunk
        k). Returns "done" or "preempted"."""
        end = int(jax.device_get(self.sim.state.chunk)) + int(num_chunks)
        if self._last_saved_chunk is None:
            # an initial verified checkpoint: rollback always has a target
            if not self._checkpoint():
                raise RuntimeError("initial state is unhealthy")
        while True:
            cur = int(jax.device_get(self.sim.state.chunk))
            if self.preempted:
                self._checkpoint()
                self.ckpt.wait()
                return "preempted"
            if cur >= end:
                break
            stats_before = self.sim.stats()
            self.sim.run(min(self.cfg.ckpt_every, end - cur))
            for hook in list(self.chaos_hooks):
                hook(self)
            cur = int(jax.device_get(self.sim.state.chunk))
            self._heartbeat(cur)
            # cheap per-interval poll of the in-scan verdict
            if self.sim.health()["health_flags"] != 0:
                self._rollback()
                continue
            self._maybe_degrade(stats_before, self.sim.stats())
            if not self._checkpoint():
                # state was poisoned between the scan and the save
                self._rollback()
        if self._last_saved_chunk != int(
                jax.device_get(self.sim.state.chunk)):
            self._checkpoint()
        self.ckpt.wait()
        return "done"
