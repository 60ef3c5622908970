"""Paper Fig. 3: connectivity-update time, old vs location-aware Barnes-Hut.
Weak scaling over rank counts (reduced CPU scale). Run by benchmarks.run in
subprocesses with varying host-device counts; directly runnable too:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src:. python -m benchmarks.bench_fig3_connectivity 256
"""
import sys

from benchmarks._util import brain_sim, emit, num_ranks


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    r = num_ranks()
    times = {}
    for alg in ("old", "new"):
        # rate_period=10 so the chunk is dominated by the connectivity update;
        # cap_factor=1 keeps new's padded request slots == old's searcher count
        dt, st = brain_sim(dict(
            neurons_per_rank=n, local_levels=3, frontier_cap=32,
            max_synapses=16, connectivity_alg=alg, spike_alg="new",
            rate_period=10, requests_cap_factor=1), chunks=2)
        times[alg] = dt
    speedup = times["old"] / times["new"]
    emit(f"fig3_connectivity_old_r{r}_n{n}", times["old"] * 1e6)
    emit(f"fig3_connectivity_new_r{r}_n{n}", times["new"] * 1e6,
         f"speedup={speedup:.2f}x")


if __name__ == "__main__":
    main()
