"""Roofline bookkeeping: analytic model FLOPs, HLO collective-byte parsing,
and the three roofline terms (EXPERIMENTS.md §Roofline).

All compiled artifacts on the 512-device host platform are SPMD per-device
modules, so cost_analysis()['flops'], 'bytes accessed' and parsed collective
operand bytes are PER-DEVICE quantities; with the prompt's formulas
  compute = HLO_FLOPs/(chips·peak), memory = bytes/(chips·HBM),
  collective = coll_bytes/(chips·link)
the chips factor cancels: term = per-device quantity / per-chip rate.
"""

from __future__ import annotations

import re

from ..models.config import ModelConfig, SSMConfig, RGLRUConfig
from .mesh import peaks_for

# ---------------------------------------------------------------------------
# analytic model FLOPs (the "useful work" numerator)
# ---------------------------------------------------------------------------


def active_matmul_params(cfg: ModelConfig) -> int:
    """Per-token matmul parameters: routed experts counted at top_k (+shared),
    embedding lookup excluded, lm_head included, norms ignored."""
    D, F = cfg.d_model, cfg.d_ff
    n_mats = 3 if cfg.mlp_gated else 2
    total = 0
    kinds = cfg.layer_kinds()
    prelude = cfg.moe.dense_prelude_layers if cfg.moe else 0
    for li, kind in enumerate(kinds):
        if kind in ("global", "local"):
            total += D * cfg.q_dim + 2 * D * cfg.kv_dim + cfg.q_dim * D
            if cfg.moe is not None and li >= prelude:
                m = cfg.moe
                total += D * m.n_experts
                total += (m.top_k + m.n_shared) * n_mats * D * m.d_expert
            else:
                f = cfg.moe.d_ff_prelude if (cfg.moe and li < prelude) else F
                total += n_mats * D * f
        elif kind == "mamba":
            s = cfg.ssm or SSMConfig()
            di = s.expand * D
            dt = s.resolved_dt_rank(D)
            total += D * 2 * di + di * s.d_conv + di * (dt + 2 * s.d_state)
            total += dt * di + di * D
        elif kind == "rglru":
            r = cfg.rglru or RGLRUConfig()
            W = r.lru_width or D
            nb = r.n_blocks or cfg.n_heads
            total += 2 * D * W + W * r.d_conv + 2 * nb * (W // nb) ** 2 + W * D
            total += n_mats * D * F
    total += cfg.d_model * cfg.vocab_size  # lm head
    return total


def _attn_context_sum(cfg: ModelConfig, S: int) -> float:
    """Σ over layers of Σ_i ctx(i) for a causal prefill of length S."""
    total = 0.0
    for kind in cfg.layer_kinds():
        if kind == "global":
            total += S * (S + 1) / 2
        elif kind == "local":
            W = cfg.window or S
            if S <= W:
                total += S * (S + 1) / 2
            else:
                total += W * (W + 1) / 2 + (S - W) * W
    return total


def _scan_flops_per_token(cfg: ModelConfig) -> float:
    """Elementwise recurrence flops per token (mamba/rglru layers)."""
    total = 0.0
    for kind in cfg.layer_kinds():
        if kind == "mamba":
            s = cfg.ssm or SSMConfig()
            total += 10.0 * (s.expand * cfg.d_model) * s.d_state
        elif kind == "rglru":
            r = cfg.rglru or RGLRUConfig()
            total += 12.0 * (r.lru_width or cfg.d_model)
    return total


def model_flops(cfg: ModelConfig, *, kind: str, batch: int, seq_len: int) -> float:
    """Analytic MODEL_FLOPS for one step of the given cell (global, not
    per-device). train = 3× forward (the standard 6ND convention)."""
    N = active_matmul_params(cfg)
    if kind == "train":
        tokens = batch * seq_len
        mm = 2.0 * N * tokens
        attn = 4.0 * cfg.n_heads * cfg.head_dim * batch * _attn_context_sum(cfg, seq_len)
        scan = _scan_flops_per_token(cfg) * tokens
        return 3.0 * (mm + attn + scan)
    if kind == "prefill":
        tokens = batch * seq_len
        mm = 2.0 * N * tokens
        attn = 4.0 * cfg.n_heads * cfg.head_dim * batch * _attn_context_sum(cfg, seq_len)
        return mm + attn + _scan_flops_per_token(cfg) * tokens
    if kind == "decode":
        mm = 2.0 * N * batch
        ctx = 0.0
        for k in cfg.layer_kinds():
            if k == "global":
                ctx += seq_len
            elif k == "local":
                ctx += min(cfg.window or seq_len, seq_len)
        attn = 4.0 * cfg.n_heads * cfg.head_dim * batch * ctx
        return mm + attn + _scan_flops_per_token(cfg) * batch
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "ragged-all-to-all")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-device operand bytes of every collective op, by kind + count."""
    out = {k: 0.0 for k in _COLL_KINDS}
    counts = {k: 0 for k in _COLL_KINDS}
    for line in hlo_text.splitlines():
        for kind in _COLL_KINDS:
            tok = f" {kind}("
            tok_start = f" {kind}-start("
            if tok in line:
                opname = tok
            elif tok_start in line:
                opname = tok_start
            else:
                continue
            operands = line.split(opname, 1)[1].split(")", 1)[0]
            for dt, dims in _SHAPE_RE.findall(operands):
                out[kind] += _shape_bytes(dt, dims)
            counts[kind] += 1
            break
    total = sum(out.values())
    return {"by_kind": out, "counts": counts, "total": total}


# ---------------------------------------------------------------------------
# roofline terms
# ---------------------------------------------------------------------------

def roofline_terms(*, per_device_flops: float, per_device_bytes: float,
                   per_device_coll_bytes: float, device_kind: str) -> dict:
    peaks = peaks_for(device_kind)
    compute_s = per_device_flops / peaks["flops_bf16"]
    memory_s = per_device_bytes / peaks["hbm_bw"]
    coll_s = per_device_coll_bytes / peaks["ici_link_bw"]
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    dominant = max(terms, key=terms.get)
    bound_s = terms[dominant]
    return {**terms, "dominant": dominant.replace("_s", ""),
            "bound_s": bound_s}
