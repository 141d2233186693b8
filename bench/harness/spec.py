"""Find a cell and its data files by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``: a configuration (``bench/configs/``),
a traffic mix (``bench/traffic/<traffic>.json``), the chips it needs, and
the limits its comparisons are held to (``bench/limits/<cell>.json``). A
later cell, configuration or per-layer metric is added as files and entries
alone: nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration file, as run
    traffic_name: str
    traffic: dict
    limits: dict            # compared number -> limit
    end_to_end: list        # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell called ``name``; KeyError when BENCHMARK.json has none."""
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=_load_json(os.path.join(root,
                                       configs[w["config"]]["file"])),
        traffic_name=w["traffic"],
        traffic=_load_json(os.path.join(BENCH, "traffic",
                                        w["traffic"] + ".json")),
        limits=_load_json(os.path.join(BENCH, "limits", name + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)])


def load_module(path: str, name: str):
    """Import a file of the benchmark by path (readers, references)."""
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reference_module(config: dict):
    """The plain reference named by a configuration file."""
    return load_module(os.path.join(BENCH, "configs",
                                    config["reference"] + ".py"),
                       "bench_ref_" + config["reference"])


def layer_reader(metric_name: str):
    """``bench/layer_metrics/<name>.py``'s ``read``."""
    return load_module(os.path.join(BENCH, "layer_metrics",
                                    metric_name + ".py"),
                       "bench_metric_" + metric_name.replace(".", "_")).read
