"""BENCHMARK.json against the rules the harness relies on: every cell,
configuration, traffic mix and per-layer metric is found by its name as a
file under bench/, and nothing else names one."""

import json
import os
import re

import pytest

from harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_and_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}


def test_every_cell_resolves_to_files(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips in (1, 4)
        assert cell.traffic["driver"]
        assert os.path.exists(os.path.join(spec.BENCH, "harness",
                                           cell.traffic["driver"] + ".py"))
        assert cell.limits
        assert spec.reference_module(cell.config).train_readings
        assert cell.end_to_end and cell.per_layer


def test_every_per_layer_metric_has_a_reader(bench):
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert callable(spec.layer_reader(m["name"]))
        assert m["moves"] in end_to_end
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in bench["workloads"]}


def test_config_files_hold_the_published_widths(bench):
    for c in bench["configs"]:
        cfg = spec.load_cell(next(w["name"] for w in bench["workloads"]
                                  if w["config"] == c["name"])).config
        for key, published in cfg.get("published", {}).items():
            assert key in c["reduced"]
            assert cfg[key] != published
        assert cfg["hidden_size"] == 3072 and cfg["intermediate_size"] == 8192
        assert cfg["num_attention_heads"] == 32
        assert cfg["vocab_size"] == 32064


def test_unknown_workload():
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell")
