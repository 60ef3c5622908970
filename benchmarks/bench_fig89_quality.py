"""Paper Figs. 8/9: quality of the rate approximation — 32 neurons, target
calcium 0.7, growth 1e-3, N(5,1) background (paper §V-D setup), comparing old
(exact spikes) vs new (rate) transmission. Reports calcium median/IQR at
checkpoints. Default 60k steps (600 chunks); --full for the paper's 200k."""
import sys

import numpy as np

from benchmarks._util import emit, num_ranks


def main():
    full = "--full" in sys.argv
    chunks = 2000 if full else 600
    import dataclasses
    from repro.configs.msp_brain import BrainConfig
    from repro.sim import Simulator

    ndev = num_ranks()
    # paper: 32 neurons SPREAD ACROSS RANKS (one per rank at 32 ranks) so the
    # rate approximation is fully exercised; here 32 total over ndev ranks
    base = BrainConfig(neurons_per_rank=max(32 // ndev, 1), local_levels=3,
                       frontier_cap=32, max_synapses=32,
                       fraction_excitatory=1.0, requests_cap_factor=64)
    marks = [chunks // 4, chunks // 2, 3 * chunks // 4, chunks]
    for alg in ("old", "new"):
        cfg = dataclasses.replace(base, spike_alg=alg)
        sim = Simulator.from_config(cfg)
        for i in range(1, chunks + 1):
            st = sim.step()
            if i in marks:
                ca = np.asarray(st.neurons.calcium)
                q1, med, q3 = np.percentile(ca, [25, 50, 75])
                syn = float((st.in_edges >= 0).sum()) / 32
                emit(f"fig89_calcium_{alg}_step{i * 100}", med * 1e6,
                     f"iqr={q3 - q1:.3f};syn_per_neuron={syn:.1f}")

    # function next to the calcium-approximation quality: the engram
    # pattern-completion workload (workloads.engram, DESIGN.md §13) —
    # recall overlap on the rate-based transmission the figure evaluates
    from repro.workloads import engram as weng
    m, _ = weng.run_engram()
    emit("fig89_engram_recall", m["recall_overlap"] * 1e6,
         f"selectivity={m['engram_selectivity']:.3f};"
         f"background={m['background_activation']:.3f}")


if __name__ == "__main__":
    main()
