"""Readings from which a cell's limits are set: for each seed, the numbers
``correct`` compares for the program and for the control (the reference in
bfloat16 in the program's place), from one run of the cell's window each,
all in one process that holds the cell's chips. With ``--fault`` the
program runs with that fault of ``bench/faults.py`` planted, and no
control.

    python bench/calibrate.py --workload <name> --seconds <s> --seeds 1 2 3
    python bench/calibrate.py --workload <name> --seconds <s> --seeds 1 2 3 \
        --fault requests_dropped

Prints one JSON line per seed. The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import cells, faults, harness
    cell = cells.load_cell(args.workload, ROOT)
    harness.use_compile_cache(ROOT)
    devices = harness.tpu_devices(cell.chips)
    with (faults.planted(args.fault) if args.fault
          else contextlib.nullcontext()):
        for seed in args.seeds:
            res = harness.measure(cell, seed, args.seconds, False, devices,
                                  time.perf_counter(), ROOT,
                                  with_control=not args.fault)
            report(cell, seed, args.fault, res)
    return 0


def report(cell, seed, fault, res):
    print(json.dumps({"workload": cell.name, "seed": seed, "fault": fault,
                      "chunks": res["chunks"],
                      "chunk_ms": 1e3 * res["window_s"] / res["chunks"],
                      "counted": res["counted"],
                      "program": res["numbers"],
                      "per_chunk": res["per_chunk"],
                      "control": res.get("control")}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
