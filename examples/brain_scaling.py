"""Weak-scaling comparison of the paper's old vs new algorithms over multiple
(emulated) ranks — reproduces the shape of paper Figs. 3/4 and Tables I/II at
CPU scale. Spawns subprocesses with 1..8 host devices.

  PYTHONPATH=src python examples/brain_scaling.py
"""
import os
import subprocess
import sys

sys.path.insert(0, "src")
sys.path.insert(0, ".")

CODE = r"""
import dataclasses, time, sys
import jax
from repro.configs.msp_brain import BrainConfig
from repro.sim import Simulator
from benchmarks._util import num_ranks, paper_bytes_from_stats

r = num_ranks()
for conn, spike in (("old", "old"), ("new", "new")):
    cfg = BrainConfig(neurons_per_rank=256, local_levels=3, frontier_cap=32,
                      max_synapses=16, connectivity_alg=conn, spike_alg=spike,
                      requests_cap_factor=1)
    sim = Simulator.from_config(cfg)
    st = sim.step()   # compile + first plasticity round
    jax.block_until_ready(st.positions)
    t0 = time.time()
    for _ in range(2):
        st = sim.step()
    jax.block_until_ready(st.positions)
    dt = (time.time() - t0) / 2
    b, s = paper_bytes_from_stats(st.stats, conn, spike, r)
    print(f"ranks={r} {conn}/{spike}: {dt*1e3:8.1f} ms/chunk  "
          f"paper-bytes={b/1e6:8.2f} MB  formed={s['synapses_formed']:.0f}",
          flush=True)
"""


def main():
    for devices in (1, 2, 4, 8):
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
        env["PYTHONPATH"] = "src" + os.pathsep + "."
        out = subprocess.run([sys.executable, "-c", CODE], env=env,
                             capture_output=True, text=True, timeout=560)
        sys.stdout.write(out.stdout)
        if out.returncode != 0:
            sys.stderr.write(out.stderr[-800:])
            sys.exit(f"{devices}-rank run failed with exit code "
                     f"{out.returncode}")


if __name__ == "__main__":
    main()
