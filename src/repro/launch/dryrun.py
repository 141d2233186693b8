import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input-shape × mesh)
cell against the production mesh with ShapeDtypeStruct stand-ins (no
allocation), then extract memory_analysis / cost_analysis / collective bytes
for the roofline table.

The two lines above MUST precede every other import: jax locks the device
count at first init, and the dry-run needs 512 placeholder host devices for
jax.make_mesh. Run as
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
Results land in results/dryrun/<tag>/<mesh>/<arch>__<shape>.json.
`--all` executes each cell in a subprocess (compiler memory isolation on the
1-core container) and skips cells whose JSON already exists.
"""

import argparse
import json
import subprocess
import sys
import time
import traceback

import jax

from ..configs import SHAPES, ARCH_IDS, cell_is_runnable, get_config, resolve
from ..distributed.sharding import (ShardingRules, cache_shardings,
                                    tree_shardings, use_sharding_rules)
from ..models.config import ModelConfig
from ..models.model import cache_specs, input_specs, param_specs
from ..optim import AdamWConfig
from ..serve.serve_step import make_decode_step, make_prefill
from ..train.train_step import init_train_state, make_train_step
from .hlo_analysis import analyze
from .mesh import make_production_mesh, mesh_info
from .roofline import model_flops, roofline_terms

from jax.sharding import NamedSharding, PartitionSpec as P

# the production mesh is v5e pods; the host placeholder devices stand in
# for them, so the roofline uses the target chip's peaks
TARGET_DEVICE_KIND = "TPU v5 lite"

# Baseline per-arch lowering knobs (§Perf changes these and re-measures).
# fsdp applies to train cells; fsdp_inference to prefill/decode cells (serving
# wants TP-only weights unless the model cannot fit one chip row: >=100B).
ARCH_DEFAULTS = {
    "command_r_plus_104b": dict(fsdp=True, fsdp_inference=True, remat="full", microbatches=8, seq_parallel=True),
    "grok1_314b": dict(fsdp=True, fsdp_inference=True, remat="full", microbatches=8, seq_parallel=True),
    "llava_next_34b": dict(fsdp=True, fsdp_inference=False, remat="full", microbatches=4, seq_parallel=True),
    "minitron_8b": dict(fsdp=True, remat="dots", microbatches=4),
    "deepseek_moe_16b": dict(fsdp=True, remat="dots", microbatches=2),
    "falcon_mamba_7b": dict(fsdp=True, remat="full", microbatches=4),
    "musicgen_medium": dict(fsdp=False, remat="dots", microbatches=4),
    "gemma3_1b": dict(fsdp=False, remat="dots", microbatches=4),
    "phi3_mini_3p8b": dict(fsdp=True, remat="dots", microbatches=4),
    "recurrentgemma_2b": dict(fsdp=False, remat="dots", microbatches=4),
}


def _knobs(arch: str, args, kind: str = "train") -> dict:
    k = dict(ARCH_DEFAULTS.get(arch, dict(fsdp=False, remat="dots", microbatches=1)))
    k.setdefault("seq_parallel", False)
    k.setdefault("fused_ce", True)
    k.setdefault("fsdp_inference", False)
    if kind != "train":
        k["fsdp"] = k.pop("fsdp_inference")
    else:
        k.pop("fsdp_inference")
    if args.remat is not None:
        k["remat"] = args.remat
    if args.microbatches is not None:
        k["microbatches"] = args.microbatches
    if args.fsdp is not None:
        k["fsdp"] = bool(args.fsdp)
    if args.seq_parallel is not None:
        k["seq_parallel"] = bool(args.seq_parallel)
    if args.fused_ce is not None:
        k["fused_ce"] = bool(args.fused_ce)
    if args.pure_fsdp is not None:
        k["pure_fsdp"] = bool(args.pure_fsdp)
    k.setdefault("pure_fsdp", False)
    if args.factored_opt is not None:
        k["factored_opt"] = bool(args.factored_opt)
    k.setdefault("factored_opt", False)
    return k


def _rules(mesh, knobs) -> ShardingRules:
    if knobs.get("pure_fsdp"):
        # full-mesh data parallelism: every axis carries batch; weights are
        # ZeRO-3-sharded over the same combined axis set
        data_axes = tuple(mesh.axis_names)
    else:
        data_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    return ShardingRules(mesh, data_axes=data_axes, fsdp=knobs["fsdp"],
                         seq_parallel=knobs["seq_parallel"],
                         pure_fsdp=knobs.get("pure_fsdp", False))


def _batch_shardings(rules, specs):
    def shard(s):
        dp = rules.dp_axes_for(s.shape[0]) if s.ndim >= 1 else None
        return NamedSharding(rules.mesh, P(dp, *([None] * (s.ndim - 1)))) \
            if s.ndim >= 1 else NamedSharding(rules.mesh, P())
    return {k: shard(v) for k, v in specs.items()}


def lower_cell(cfg: ModelConfig, shape_name: str, mesh, knobs: dict):
    """Build + lower + compile the step function for one cell.
    Returns (lowered, compiled, extras)."""
    sh = SHAPES[shape_name]
    kind, S, B = sh["kind"], sh["seq_len"], sh["global_batch"]
    rules = _rules(mesh, knobs)

    with use_sharding_rules(rules), mesh:
        if kind == "train":
            opt_cfg = AdamWConfig(total_steps=10_000,
                                  factored_second_moment=knobs.get("factored_opt", False))
            step = make_train_step(cfg, opt_cfg, remat=knobs["remat"],
                                   microbatches=knobs["microbatches"],
                                   fused_ce=knobs.get("fused_ce", True))
            state_shapes = jax.eval_shape(lambda: init_train_state(cfg, opt_cfg, 0))
            state_sh = tree_shardings(state_shapes, rules)
            in_specs = input_specs(cfg, kind="train", seq_len=S, batch=B)
            batch_sh = _batch_shardings(rules, in_specs)
            jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None), donate_argnums=(0,))
            lowered = jitted.lower(state_shapes, in_specs)
        elif kind == "prefill":
            fn = make_prefill(cfg)
            p_shapes = param_specs(cfg)
            p_sh = tree_shardings(p_shapes, rules)
            in_specs = input_specs(cfg, kind="prefill", seq_len=S, batch=B)
            batch_sh = _batch_shardings(rules, in_specs)
            c_shapes = jax.eval_shape(lambda: cache_specs(cfg, B, S))
            c_sh = cache_shardings(c_shapes, rules)
            jitted = jax.jit(fn, in_shardings=(p_sh, batch_sh["inputs"]),
                             out_shardings=(None, c_sh, None))
            lowered = jitted.lower(p_shapes, in_specs["inputs"])
        elif kind == "decode":
            fn = make_decode_step(cfg)
            p_shapes = param_specs(cfg)
            p_sh = tree_shardings(p_shapes, rules)
            in_specs = input_specs(cfg, kind="decode", seq_len=S, batch=B)
            c_shapes = cache_specs(cfg, B, S)
            c_sh = cache_shardings(c_shapes, rules)
            tok_sh = _batch_shardings(rules, {"x": in_specs["inputs"]})["x"]
            pos_sh = NamedSharding(rules.mesh, P())
            jitted = jax.jit(fn, in_shardings=(p_sh, tok_sh, c_sh, pos_sh),
                             out_shardings=(None, None, c_sh),
                             donate_argnums=(2,))
            lowered = jitted.lower(p_shapes, in_specs["inputs"], c_shapes,
                                   in_specs["pos"])
        else:
            raise ValueError(kind)
        compiled = lowered.compile()
    return lowered, compiled


def _mem_dict(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, f, None)
        if v is not None:
            out[f] = int(v)
    out["live_bytes"] = (out.get("argument_size_in_bytes", 0)
                         + out.get("output_size_in_bytes", 0)
                         + out.get("temp_size_in_bytes", 0)
                         - out.get("alias_size_in_bytes", 0))
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str, args) -> dict:
    arch = resolve(arch)
    cfg = get_config(arch)
    runnable, reason = cell_is_runnable(cfg, shape_name)
    if not runnable:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": reason}
    knobs = _knobs(arch, args, SHAPES[shape_name]["kind"])
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    t0 = time.time()
    lowered, compiled = lower_cell(cfg, shape_name, mesh, knobs)
    compile_s = time.time() - t0
    # trip-count-aware analysis (cost_analysis counts loop bodies once; see
    # launch/hlo_analysis.py) — all quantities are PER-DEVICE (SPMD module).
    hlo = analyze(compiled.as_text())
    flops = float(hlo["flops"])
    bytes_accessed = float(hlo["bytes"])
    xla_raw = compiled.cost_analysis() or {}
    sh = SHAPES[shape_name]
    mf = model_flops(cfg, kind=sh["kind"], batch=sh["global_batch"],
                     seq_len=sh["seq_len"])
    terms = roofline_terms(per_device_flops=flops,
                           per_device_bytes=bytes_accessed,
                           per_device_coll_bytes=hlo["collective_bytes"],
                           device_kind=TARGET_DEVICE_KIND)
    hlo_flops_global = flops * n_chips
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mesh_info": mesh_info(mesh), "status": "ok",
        "knobs": knobs, "compile_s": compile_s,
        "n_chips": n_chips,
        "per_device": {
            "hlo_flops": flops,
            "hlo_bytes": bytes_accessed,
            "collective_bytes": hlo["collective_bytes"],
            "collectives": hlo["collectives"],
            "collective_counts": hlo["collective_counts"],
            "xla_raw_flops": float(xla_raw.get("flops", 0.0)),
            "xla_raw_bytes": float(xla_raw.get("bytes accessed", 0.0)),
        },
        "memory": _mem_dict(compiled),
        "model_flops": mf,
        "hlo_flops_global": hlo_flops_global,
        "useful_flops_frac": (mf / hlo_flops_global) if hlo_flops_global else None,
        "roofline": terms,
    }
    return result


def _out_path(args, mesh_kind, arch, shape_name):
    d = os.path.join(args.out, args.tag, mesh_kind)
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{resolve(arch)}__{shape_name}.json")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--fsdp", type=int, default=None)
    ap.add_argument("--seq-parallel", type=int, default=None)
    ap.add_argument("--fused-ce", type=int, default=None)
    ap.add_argument("--pure-fsdp", type=int, default=None)
    ap.add_argument("--factored-opt", type=int, default=None)
    ap.add_argument("--timeout", type=float, default=1800.0)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.all:
        cells = [(a, s, m) for m in meshes for a in ARCH_IDS for s in SHAPES]
        failures = []
        for arch, shape_name, mesh_kind in cells:
            path = _out_path(args, mesh_kind, arch, shape_name)
            if os.path.exists(path) and not args.force:
                print(f"[skip-cached] {mesh_kind}/{arch}/{shape_name}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name, "--mesh", mesh_kind,
                   "--out", args.out, "--tag", args.tag]
            for flag, val in (("--remat", args.remat),
                              ("--microbatches", args.microbatches),
                              ("--fsdp", args.fsdp),
                              ("--seq-parallel", args.seq_parallel),
                              ("--fused-ce", args.fused_ce)):
                if val is not None:
                    cmd += [flag, str(val)]
            print(f"[run] {mesh_kind}/{arch}/{shape_name}", flush=True)
            try:
                rc = subprocess.run(cmd, timeout=args.timeout).returncode
            except subprocess.TimeoutExpired:
                rc = -9
            if rc != 0:
                failures.append((mesh_kind, arch, shape_name, rc))
                with open(path, "w") as f:
                    json.dump({"arch": arch, "shape": shape_name,
                               "mesh": mesh_kind, "status": "failed",
                               "returncode": rc}, f)
        print(f"done; {len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    assert args.arch and args.shape, "--arch and --shape (or --all) required"
    for mesh_kind in meshes:
        path = _out_path(args, mesh_kind, args.arch, args.shape)
        try:
            result = run_cell(args.arch, args.shape, mesh_kind, args)
        except Exception:
            traceback.print_exc()
            result = {"arch": resolve(args.arch), "shape": args.shape,
                      "mesh": mesh_kind, "status": "error",
                      "error": traceback.format_exc()[-2000:]}
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
        status = result["status"]
        if status == "ok":
            r = result["roofline"]
            print(f"{mesh_kind}/{result['arch']}/{args.shape}: OK "
                  f"compile={result['compile_s']:.0f}s "
                  f"compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s "
                  f"coll={r['collective_s']:.3e}s dominant={r['dominant']} "
                  f"useful={result['useful_flops_frac'] and round(result['useful_flops_frac'],3)} "
                  f"live={result['memory']['live_bytes']/2**30:.2f}GiB/dev")
        else:
            print(f"{mesh_kind}/{result['arch']}/{args.shape}: {status}")
            if status == "error":
                sys.exit(1)


if __name__ == "__main__":
    main()
