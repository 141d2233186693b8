"""Find a cell and its data files by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``: a configuration (``bench/configs/``),
a traffic mix (``bench/traffic/<traffic>.json``), the chips it needs, and
the limits its comparisons are held to (``bench/limits/<cell>.json``). A
later cell, configuration or per-layer metric is added as files and entries
alone: nothing here names one.

A configuration file names two modules beside it in ``bench/configs/``:
its plain reference (``"reference"``) and its program module
(``"program"``), which holds what only that architecture knows:
``model_config(cfg)``, the program's model config built from the file;
``work(cfg)``, a dict of the counts of one training step at
``cfg["train"]`` sizes (``step_flops``, the model FLOPs of the step, and
``attention_fwd_flops`` and ``attention_bwd_flops``, those of one
attention kernel call: one layer, the whole batch); and ``smoke(cfg)``, a
copy of the configuration cut to a size the CPU runs in seconds.
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


def _bench_dir(root: str) -> str:
    return os.path.join(root, os.path.relpath(BENCH, ROOT))


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell called ``name``; KeyError when BENCHMARK.json has none, or
    when its configuration file names no program module."""
    bench = _bench_dir(root)
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config_file = configs[w["config"]]["file"]
    config = _load_json(os.path.join(root, config_file))
    if "program" not in config:
        raise KeyError(f"{config_file} names no program module (its "
                       "\"program\" key, a module of bench/configs/)")
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"],
        traffic=_load_json(os.path.join(bench, "traffic",
                                        w["traffic"] + ".json")),
        limits=_load_json(os.path.join(bench, "limits", name + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)])


def load_module(path: str, name: str):
    """Import a file of the benchmark by path (readers, references)."""
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _config_module(config: dict, key: str, root: str):
    return load_module(os.path.join(_bench_dir(root), "configs",
                                    config[key] + ".py"),
                       f"bench_{key}_{config[key]}")


def reference_module(config: dict, root: str = ROOT):
    """The plain reference named by a configuration file."""
    return _config_module(config, "reference", root)


def program_module(config: dict, root: str = ROOT):
    """The program module named by a configuration file: its
    ``model_config``, ``work`` and ``smoke``."""
    return _config_module(config, "program", root)


def layer_reader(metric_name: str):
    """``bench/layer_metrics/<name>.py``'s ``read``."""
    return load_module(os.path.join(BENCH, "layer_metrics",
                                    metric_name + ".py"),
                       "bench_metric_" + metric_name.replace(".", "_")).read
