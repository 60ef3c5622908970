"""The benchmark's data, found by name: cells and metrics in
``BENCHMARK.json``; each configuration, traffic mix, limit set and per-layer
metric reader in a file of its own under ``bench/``.

    bench/configs/<config>.json    the configuration as it is run
    bench/traffic/<traffic>.json   the traffic mix: initial connectome,
                                   vacant elements, background drive
    bench/limits/<workload>.json   the limit of each number ``correct``
                                   compares: its largest value, or
                                   ``{"min": x}``, its smallest
    bench/metrics/<metric>.py      a reader ``read(run) -> float | None``

A cell, configuration, traffic mix or metric is added by adding files and
entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    limits: dict          # bench/limits/<workload>.json
    end_to_end: tuple     # BENCHMARK.json end_to_end entries
    per_layer: tuple      # BENCHMARK.json per_layer entries

    def brain_config(self) -> dict:
        """The simulator's configuration: the configuration's fields with
        the traffic's protocol fields; the two may not overlap."""
        both = set(self.config["brain_config"]) & set(
            self.traffic["brain_config"])
        if both:
            raise ValueError(f"{self.config_name} and {self.traffic_name} "
                             f"both set {sorted(both)}")
        return {**self.config["brain_config"],
                **self.traffic["brain_config"]}


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload`` with every file it names."""
    spec = benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    bench = os.path.join(root, "bench")
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"],
        config=_json(os.path.join(root, configs[w["config"]]["file"])),
        traffic=_json(os.path.join(bench, "traffic", w["traffic"] + ".json")),
        limits=_json(os.path.join(bench, "limits", workload + ".json")),
        end_to_end=tuple(spec["end_to_end"]),
        per_layer=tuple(spec["per_layer"]))


def metric_reader(name: str, root: str = ROOT) -> Callable[[object],
                                                           Optional[float]]:
    """``read`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, root: str = ROOT) -> dict:
    """Published peaks of one device kind; an unknown kind is an error."""
    table = _json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(table['devices'])}")
    return table["devices"][device_kind]
