#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the checkout's root;
its configuration, traffic and limits are files under ``bench/``. The run
makes its weights and inputs from ``--seed``, warms every program the cell
uses (set-up, timed as ``setup_s``), measures for about ``--seconds``, then
checks what the timed path produced against the plain reference. With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window is traced and the result carries its per-layer
metrics. The last line of standard output is the result as one JSON object;
the numbers compared are the last lines of standard error.

Without a TPU, or with fewer chips than the cell needs, it prints no result
and exits non-zero. The compile cache is ``JAX_COMPILATION_CACHE_DIR`` where
set, otherwise ``.jax_cache/`` in the checkout; checkpoints go to
``.spoton_ckpts/<cell>/`` and traces to ``.bench_trace/<cell>/`` in the
checkout, and both are removed by the run.
"""

from __future__ import annotations

import argparse
import importlib
import os
import shutil
import sys
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    from harness import common, spec

    cell = spec.load_cell(args.workload, ROOT)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"the program under test is not at {src}", file=sys.stderr)
        return 1
    sys.path.insert(0, src)

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("no TPU found: the benchmark runs on the chip only",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.launch.train import setup_compilation_cache

    compile_log = common.CompileLog()
    cache_dir = setup_compilation_cache(os.path.join(ROOT, ".jax_cache"))
    print("compile cache: " + (cache_dir or "JAX_COMPILATION_CACHE_DIR="
                               + os.environ["JAX_COMPILATION_CACHE_DIR"]))
    os.makedirs(os.path.join(ROOT, ".spoton_ckpts"), exist_ok=True)
    print("checkpoint volume free bytes: "
          f"{shutil.disk_usage(os.path.join(ROOT, '.spoton_ckpts')).free}",
          flush=True)
    driver = importlib.import_module("harness." + cell.traffic["driver"])
    result, checks = driver.run(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        root=ROOT, cache_dir=cache_dir, devices=devices, t_start=T_START,
        compile_log=compile_log)
    print(f"compile totals: {compile_log.snapshot()}", flush=True)
    common.emit(result, checks)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
