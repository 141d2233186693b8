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
    """Each key a configuration changed from its source is in ``reduced``,
    and its program module has what the harness calls. The widths of each
    configuration are checked in its own file (test_config_<name>.py)."""
    for c in bench["configs"]:
        cfg = spec.load_cell(next(w["name"] for w in bench["workloads"]
                                  if w["config"] == c["name"])).config
        for key, published in cfg.get("published", {}).items():
            assert key in c["reduced"]
            assert cfg[key] != published
        program = spec.program_module(cfg)
        for fn in ("model_config", "work", "smoke"):
            assert callable(getattr(program, fn)), (c["name"], fn)


def test_unknown_workload():
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell")


TOY = {"hidden_size": 32, "num_hidden_layers": 1, "vocab_size": 64,
       "train": {"batch": 2, "seq_len": 16}}

TOY_PROGRAM = '''
from harness import flops


def model_config(cfg):
    return ("toy", cfg["hidden_size"], cfg["num_hidden_layers"])


def work(cfg):
    t = cfg["train"]
    fwd, bwd = flops.attention_call_flops(t["batch"], 1, t["seq_len"], None,
                                          cfg["hidden_size"], 8)
    return {"step_flops": 6.0 * cfg["hidden_size"] ** 2 * t["seq_len"],
            "attention_fwd_flops": fwd, "attention_bwd_flops": bwd}


def smoke(cfg):
    return dict(cfg, hidden_size=8)
'''


def _toy_tree(root, *, program: bool):
    """A checkout that holds one cell of a toy architecture: its
    BENCHMARK.json, configuration, program module, reference stub, traffic
    and limits, and no file of the harness's own."""
    bench = root / os.path.relpath(spec.BENCH, spec.ROOT)
    for d in ("configs", "traffic", "limits"):
        (bench / d).mkdir(parents=True)
    cfg = dict(TOY, reference="toy_reference")
    if program:
        cfg["program"] = "toy_program"
    (bench / "configs" / "toy.json").write_text(json.dumps(cfg))
    (bench / "configs" / "toy_program.py").write_text(TOY_PROGRAM)
    (bench / "configs" / "toy_reference.py").write_text(
        "def train_readings(cfg, params, batches, **kw):\n    return {}\n")
    (bench / "traffic" / "steady.json").write_text('{"driver": "periodic"}')
    (bench / "limits" / "toy.steady.json").write_text('{"loss_gap": 0.1}')
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": str(
            (bench / "configs" / "toy.json").relative_to(root))}],
        "workloads": [{"name": "toy.steady", "config": "toy",
                       "traffic": "steady", "chips": 1}],
        "end_to_end": [{"name": "setup_s"}],
        "per_layer": [{"name": "step_mfu", "workloads": ["toy.steady"]}]}))


def test_a_configuration_is_added_as_files_alone(tmp_path):
    _toy_tree(tmp_path, program=True)
    cell = spec.load_cell("toy.steady", str(tmp_path))
    assert cell.config["hidden_size"] == 32
    assert cell.traffic == {"driver": "periodic"}
    assert cell.limits == {"loss_gap": 0.1}
    assert [m["name"] for m in cell.per_layer] == ["step_mfu"]
    program = spec.program_module(cell.config, str(tmp_path))
    assert program.model_config(cell.config) == ("toy", 32, 1)
    work = program.work(cell.config)
    assert work["step_flops"] == 6.0 * 32 ** 2 * 16
    # 136 causal pairs of 16 positions, 2 rows, one head, d_qk 32, d_v 8
    assert work["attention_fwd_flops"] == 2.0 * 2 * 136 * 40
    assert work["attention_bwd_flops"] == 2 * work["attention_fwd_flops"]
    assert program.smoke(cell.config)["hidden_size"] == 8
    ref = spec.reference_module(cell.config, str(tmp_path))
    assert ref.train_readings(cell.config, None, []) == {}


def test_a_configuration_without_a_program_module_is_refused(tmp_path):
    _toy_tree(tmp_path, program=False)
    with pytest.raises(KeyError, match="toy.json"):
        spec.load_cell("toy.steady", str(tmp_path))
