#!/usr/bin/env python3
"""Readings that set a training cell's limits, on the chip at the cell's
own size, many seeds in one process:

    python3 bench/readings.py --workload <name> --seeds 1,2,3 [--control 1]

For each seed: the program's first training steps against the plain
reference (the lower readings), and with ``--control 1`` also the reference
computed in float8 (the control) and the reference over half of each batch
(a planted fault) against the same float32 reference. Each side also gets
its per-leaf gradient and change gaps, each against the leaf's own norm
(``compare.leaf_gaps``). Each seed prints one JSON line; the benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


def seed_readings(cell, seed: int, *, control: bool, root: str,
                  cache_dir) -> dict:
    from harness import compare, training

    driver = importlib.import_module("harness." + cell.traffic["driver"])
    template, prog = driver.program_readings(
        cell, seed, root=root, cache_dir=cache_dir)
    gc.collect()
    ref = training.reference(cell, seed, template)

    def readings(side):
        return {**compare.gaps(side, ref),
                "grad_leaves": compare.leaf_gaps(side["grad"], ref["grad"]),
                "change_leaves": compare.leaf_gaps(side["change"],
                                                   ref["change"])}

    out = {"seed": seed, "program": readings(prog),
           "loss_ref": ref["loss"], "loss_program": prog["loss"]}
    if control:
        batch = cell.config["train"]["batch"]
        for name, kw in (("control_fp8", {"quant": "fp8"}),
                         ("fault_half_batch",
                          {"rows": slice(0, max(batch // 2, 1))})):
            out[name] = readings(training.reference(cell, seed, template,
                                                    **kw))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    from harness import spec

    cell = spec.load_cell(args.workload, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("readings are taken on the cell's chips only",
              file=sys.stderr)
        return 1
    from repro.launch.train import setup_compilation_cache

    cache_dir = setup_compilation_cache(os.path.join(ROOT, ".jax_cache"))
    for seed in (int(x) for x in args.seeds.split(",")):
        print(json.dumps(seed_readings(cell, seed, control=bool(args.control),
                                       root=ROOT, cache_dir=cache_dir)),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
