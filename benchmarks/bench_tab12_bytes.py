"""Paper Tables I/II: bytes transferred, old vs new algorithm pairs, using the
paper's record sizes (17/42/9 B requests, 8 B spike IDs, 4 B rates, tree-node
downloads) counted from simulation event counters."""
import sys

from benchmarks._util import (brain_sim, emit, num_ranks,
                              paper_bytes_from_stats)


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    r = num_ranks()
    out = {}
    for conn, spike in (("old", "old"), ("new", "new")):
        dt, st = brain_sim(dict(
            neurons_per_rank=n, local_levels=3, frontier_cap=32,
            max_synapses=16, connectivity_alg=conn, spike_alg=spike,
            requests_cap_factor=max(r, 4)), chunks=3)
        b, s = paper_bytes_from_stats(st.stats, conn, spike, r)
        out[conn] = b
        emit(f"tab{'1' if conn == 'old' else '2'}_bytes_{conn}_r{r}_n{n}",
             b, f"formed={s['synapses_formed']:.0f}")
    ratio = out["old"] / max(out["new"], 1.0)
    emit(f"tab12_bytes_ratio_r{r}_n{n}", ratio, "old/new")


if __name__ == "__main__":
    main()
