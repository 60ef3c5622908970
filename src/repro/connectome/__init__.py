"""Connectome subsystem: the MSP connectivity update as a first-class data
structure + algorithm package (paper §III-B/§IV-A; DESIGN.md §6).

The paper's headline result is the 6x faster connectivity update, and its
Fig. 11 attributes ~55% of the optimized runtime to Barnes-Hut computation —
so the whole phase lives here, out of the engine:

  tree.py      level-array octree (rank-local subtree + replicated top tree)
  traverse.py  vectorized Barnes-Hut search — phase A over the top tree,
               phase B over one subtree; ``expand_and_sample`` and the
               member selection are the shared jnp math of the reference
               path (restarts over the searching rows only) and the Pallas
               traversal kernel (kernels/bh_traverse.py), bit-identical
  synapses.py  synapse-table ops (counts/compact/accept/retract/remove),
               all vectorized segment/cumsum — no sequential loops
  routing.py   formation/deletion request routing over the ranks mesh
               (the paper's 17B/42B/9B record exchanges)
  update.py    the per-chunk connectivity update orchestration

Selection: ``BrainConfig.connectivity_impl ∈ {"reference", "fused"}``
(mirroring ``activity_impl``) picks the jnp phase-B or the Pallas kernel.
"""
from repro.connectome.synapses import SynapseTable, init_synapses
from repro.connectome.update import connectivity_update

__all__ = ["SynapseTable", "init_synapses", "connectivity_update"]
