"""Decoder assembly for every assigned architecture.

Layers are grouped into **period segments**: the config's `block_pattern` is
one period (e.g. gemma3's 5×local+1×global, recurrentgemma's rglru,rglru,attn);
parameters are stacked over period repeats and the repeats are driven by
`jax.lax.scan`, so HLO size is ~independent of depth (critical for compiling
64-layer/314B configs with a 512-device SPMD partitioner on one CPU).
Heterogeneous layers live at different *positions inside* the period body,
where their kind — and hence window size, RoPE theta, cache structure — is
static. A leading dense-MLP prelude (DeepSeek-MoE) is its own segment.

Three entry points per model: `forward` (teacher-forced logits; training and
prefill), `prefill` (forward + cache construction), `decode_step` (one token).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from . import layers as L
from . import moe as MOE
from . import rglru as RG
from . import ssm as SSM
from .config import ModelConfig, RGLRUConfig, SSMConfig
from ..distributed.sharding import current_rules, shard_act
from ..kernels.flash_attention import flash_attention
from ..kernels.flash_attention import supports as flash_supports


# ---------------------------------------------------------------------------
# segmentation plan
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Segment:
    pattern: tuple[str, ...]   # kinds at each position of one period
    n_repeats: int
    prelude: bool = False      # dense-MLP prelude layers (MoE models)


def plan_segments(cfg: ModelConfig) -> tuple[Segment, ...]:
    kinds = list(cfg.layer_kinds())
    segs: list[Segment] = []
    start = 0
    if cfg.moe is not None and cfg.moe.dense_prelude_layers:
        n = cfg.moe.dense_prelude_layers
        segs.append(Segment(tuple(kinds[:n]), 1, prelude=True))
        start = n
    rest = kinds[start:]
    p = len(cfg.block_pattern)
    n_full = len(rest) // p
    if n_full:
        segs.append(Segment(tuple(cfg.block_pattern), n_full))
    r = len(rest) % p
    if r:
        segs.append(Segment(tuple(rest[-r:]), 1))
    assert sum(len(s.pattern) * s.n_repeats for s in segs) == cfg.n_layers
    return tuple(segs)


def _kind_window(cfg: ModelConfig, kind: str) -> int:
    return cfg.window if kind == "local" else 0


def _kind_theta(cfg: ModelConfig, kind: str) -> float:
    if kind == "global" and cfg.rope_theta_global:
        return cfg.rope_theta_global
    return cfg.rope_theta


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def _dense(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[-2] if len(shape) >= 2 else shape[-1]
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)


def _mlp_params(key, d_in, d_ff, dtype, gated):
    ks = jax.random.split(key, 3)
    w = {"up": _dense(ks[0], (d_in, d_ff), dtype),
         "down": _dense(ks[1], (d_ff, d_in), dtype)}
    if gated:
        w["gate"] = _dense(ks[2], (d_in, d_ff), dtype)
    return w


def _attn_params(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense(ks[0], (cfg.d_model, cfg.q_dim), dtype),
        "wk": _dense(ks[1], (cfg.d_model, cfg.kv_dim), dtype),
        "wv": _dense(ks[2], (cfg.d_model, cfg.kv_dim), dtype),
        "wo": _dense(ks[3], (cfg.q_dim, cfg.d_model), dtype),
    }


def _moe_params(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    ks = jax.random.split(key, 5)
    E, D, F = m.n_experts, cfg.d_model, m.d_expert
    w = {
        "router": _dense(ks[0], (D, E), jnp.float32),
        "experts": {
            "up": _dense(ks[1], (E, D, F), dtype, fan_in=D),
            "down": _dense(ks[2], (E, F, D), dtype, fan_in=F),
            "gate": _dense(ks[3], (E, D, F), dtype, fan_in=D),
        },
    }
    if not _gated(cfg):
        del w["experts"]["gate"]
    if m.n_shared:
        w["shared"] = _mlp_params(ks[4], D, m.n_shared * F, dtype, _gated(cfg))
    return w


def _mamba_params(key, cfg: ModelConfig, dtype):
    s = cfg.ssm or SSMConfig()
    D = cfg.d_model
    DI = s.expand * D
    dt = s.resolved_dt_rank(D)
    ks = jax.random.split(key, 6)
    A = jnp.tile(jnp.arange(1, s.d_state + 1, dtype=jnp.float32), (DI, 1))
    return {
        "in_proj": _dense(ks[0], (D, 2 * DI), dtype),
        "conv": _dense(ks[1], (DI, s.d_conv), dtype, fan_in=s.d_conv),
        "x_proj": _dense(ks[2], (DI, dt + 2 * s.d_state), dtype),
        "dt_proj": _dense(ks[3], (dt, DI), dtype),
        "dt_bias": jnp.full((DI,), -4.6, dtype),  # softplus^-1(0.01)
        "A_log": jnp.log(A),
        "D": jnp.ones((DI,), jnp.float32),
        "out_proj": _dense(ks[4], (DI, D), dtype),
    }


def _rglru_params(key, cfg: ModelConfig, dtype):
    r = cfg.rglru or RGLRUConfig()
    D = cfg.d_model
    W = r.lru_width or D
    nb = r.n_blocks or cfg.n_heads
    bs = W // nb
    ks = jax.random.split(key, 7)
    # Λ init so a ∈ (0.9, 0.999) at r=0.5 (Griffin appendix)
    lam = jax.random.uniform(ks[5], (W,), jnp.float32, 0.3, 1.5)
    return {
        "in_x": _dense(ks[0], (D, W), dtype),
        "in_gate": _dense(ks[1], (D, W), dtype),
        "conv": _dense(ks[2], (W, r.d_conv), dtype, fan_in=r.d_conv),
        "lru": {
            "w_r": _dense(ks[3], (nb, bs, bs), jnp.float32, fan_in=bs),
            "w_i": _dense(ks[4], (nb, bs, bs), jnp.float32, fan_in=bs),
            "b_r": jnp.zeros((W,), jnp.float32),
            "b_i": jnp.zeros((W,), jnp.float32),
            "lam": lam,
        },
        "out": _dense(ks[6], (W, D), dtype),
    }


def _gated(cfg: ModelConfig) -> bool:
    return cfg.mlp_gated


def _layer_params(key, cfg: ModelConfig, kind: str, prelude: bool, dtype):
    ks = jax.random.split(key, 3)
    D = cfg.d_model
    w: dict[str, Any] = {"norm1": jnp.zeros((D,), jnp.float32)}
    if kind in ("global", "local"):
        w["attn"] = _attn_params(ks[0], cfg, dtype)
        w["norm2"] = jnp.zeros((D,), jnp.float32)
        if cfg.moe is not None and not prelude:
            w["moe"] = _moe_params(ks[1], cfg, dtype)
        else:
            d_ff = cfg.moe.d_ff_prelude if (cfg.moe and prelude) else cfg.d_ff
            w["mlp"] = _mlp_params(ks[1], D, d_ff, dtype, _gated(cfg))
    elif kind == "mamba":
        w["mamba"] = _mamba_params(ks[0], cfg, dtype)
    elif kind == "rglru":
        w["rec"] = _rglru_params(ks[0], cfg, dtype)
        w["norm2"] = jnp.zeros((D,), jnp.float32)
        w["mlp"] = _mlp_params(ks[1], D, cfg.d_ff, dtype, _gated(cfg))
    else:
        raise ValueError(kind)
    return w


def init_params(cfg: ModelConfig, key) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    segs = plan_segments(cfg)
    params: dict[str, Any] = {"segments": []}
    for si, seg in enumerate(segs):
        seg_params = {}
        for pi, kind in enumerate(seg.pattern):
            def one(r, _pi=pi, _kind=kind, _seg=seg, _si=si):
                k = jax.random.fold_in(key, _si * 10007 + _pi * 101 + r)
                return _layer_params(k, cfg, _kind, _seg.prelude, dtype)
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                                   *[one(r) for r in range(seg.n_repeats)])
            seg_params[f"pos{pi}"] = stacked
        params["segments"].append(seg_params)
    if cfg.embed_inputs:
        params["embed"] = _dense(jax.random.fold_in(key, 999_983),
                                 (cfg.vocab_size, cfg.d_model), dtype,
                                 fan_in=cfg.d_model)
    if not (cfg.tie_embeddings and cfg.embed_inputs):
        params["lm_head"] = _dense(jax.random.fold_in(key, 999_979),
                                   (cfg.d_model, cfg.vocab_size), dtype)
    params["final_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
    return params


# ---------------------------------------------------------------------------
# block application — sequence path
# ---------------------------------------------------------------------------

def _channel_mix(x, w, cfg: ModelConfig, *, decode: bool = False):
    """MLP or MoE residual branch. Returns (delta, aux)."""
    if "moe" in w:
        if decode:  # exact dropless path (see moe.moe_block_dense)
            return MOE.moe_block_dense(x, w["moe"], cfg.moe, act=cfg.act,
                                       gated=_gated(cfg)), jnp.zeros((), jnp.float32)
        y, aux = MOE.moe_block(x, w["moe"], cfg.moe, act=cfg.act, gated=_gated(cfg))
        return y, aux
    y = L.mlp(x, w["mlp"], act=cfg.act, gated=_gated(cfg))
    return y, jnp.zeros((), jnp.float32)


def _tpu_backend() -> bool:
    return jax.default_backend() == "tpu"


def _flash_engages(q, k) -> bool:
    """The Pallas kernel takes sequence attention on a TPU, on one device
    (no sharding rules, or a mesh of one), at shapes it was compiled and
    tested for; every other case keeps XLA's chunked path."""
    rules = current_rules()
    return (_tpu_backend() and (rules is None or rules.mesh.size == 1)
            and flash_supports(q.shape, k.shape))


def _sequence_attention(q, k, v, window: int):
    """Causal self-attention over the whole sequence (q_offset 0, no cache).
    The kernel's custom VJP keeps only O(S) residuals (q, k, v, o and the
    log-sum-exp). XLA's path runs under its own checkpoint, which saves only
    q, k, v and recomputes the score and softmax intermediates in backward
    (otherwise any remat-dots policy pins O(S·ctx) score matrices per
    layer)."""
    if _flash_engages(q, k):
        return flash_attention(q, k, v, window=window)
    return jax.checkpoint(
        lambda q_, k_, v_: L.attention(q_, k_, v_, window=window))(q, k, v)


def _attn_mix(x, w, cfg: ModelConfig, kind: str, positions):
    """Attention residual branch (sequence). Returns (delta, (k, v))."""
    B, S, D = x.shape
    q = (x @ w["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = (x @ w["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ w["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    theta = _kind_theta(cfg, kind)
    q = L.rope(q, positions, theta=theta)
    k = L.rope(k, positions, theta=theta)
    q = shard_act(q, "heads")
    k = shard_act(k, "kv_heads")
    v = shard_act(v, "kv_heads")
    out = _sequence_attention(q, k, v, _kind_window(cfg, kind))
    return out.reshape(B, S, cfg.q_dim) @ w["wo"], (k, v)


def _apply_block_seq(kind, w, x, cfg: ModelConfig, positions, *,
                     collect_cache: bool, cache_len: int | None = None):
    """One layer, full sequence. Returns (x, aux, cache_or_None)."""
    cache = None
    if kind in ("global", "local"):
        h = L.rms_norm(x, w["norm1"], eps=cfg.norm_eps)
        delta, (k, v) = _attn_mix(h, w["attn"], cfg, kind, positions)
        x = shard_act(x + delta, "residual")
        h2 = L.rms_norm(x, w["norm2"], eps=cfg.norm_eps)
        delta2, aux = _channel_mix(h2, w, cfg)
        x = shard_act(x + delta2, "residual")
        if collect_cache:
            if kind == "local" and cfg.window:
                cache = L.ring_fill_from_prefill(k, v, cfg.window)
            else:
                pad = (cache_len or k.shape[1]) - k.shape[1]
                if pad > 0:
                    k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                cache = {"k": k, "v": v}
    elif kind == "mamba":
        h = L.rms_norm(x, w["norm1"], eps=cfg.norm_eps)
        if collect_cache:
            delta, cache = _mamba_seq_with_cache(h, w["mamba"], cfg.ssm)
        else:
            delta = SSM.mamba_block(h, w["mamba"], cfg.ssm)
        x = shard_act(x + delta, "residual")
        aux = jnp.zeros((), jnp.float32)
    elif kind == "rglru":
        h = L.rms_norm(x, w["norm1"], eps=cfg.norm_eps)
        if collect_cache:
            delta, cache = _rglru_seq_with_cache(h, w["rec"], cfg.rglru or RGLRUConfig())
        else:
            delta = RG.recurrent_block(h, w["rec"], cfg.rglru or RGLRUConfig())
        x = shard_act(x + delta, "residual")
        h2 = L.rms_norm(x, w["norm2"], eps=cfg.norm_eps)
        x = shard_act(x + L.mlp(h2, w["mlp"], act=cfg.act, gated=_gated(cfg)), "residual")
        aux = jnp.zeros((), jnp.float32)
    else:
        raise ValueError(kind)
    return x, aux, cache


def _mamba_seq_with_cache(x, w, scfg: SSMConfig):
    A = -jnp.exp(w["A_log"].astype(jnp.float32))
    ug = x @ w["in_proj"]
    u_raw, gate = jnp.split(ug, 2, axis=-1)
    K = scfg.d_conv
    conv_state = u_raw[:, -(K - 1):] if x.shape[1] >= K - 1 else \
        jnp.pad(u_raw, ((0, 0), (K - 1 - x.shape[1], 0), (0, 0)))
    u = jax.nn.silu(L.causal_conv1d(u_raw, w["conv"]))
    dt_rank = scfg.resolved_dt_rank(x.shape[-1])
    xdbc = u @ w["x_proj"]
    dt, Bm, Cm = jnp.split(xdbc, [dt_rank, dt_rank + scfg.d_state], axis=-1)
    delta = jax.nn.softplus(dt @ w["dt_proj"] + w["dt_bias"])
    y, h_last = SSM.selective_scan(u, delta, A, Bm, Cm, w["D"])
    y = y * jax.nn.silu(gate)
    return y @ w["out_proj"], {"conv": conv_state, "ssm": h_last}


def _rglru_seq_with_cache(x, w, rcfg: RGLRUConfig):
    branch_raw = x @ w["in_x"]
    K = rcfg.d_conv
    conv_state = branch_raw[:, -(K - 1):] if x.shape[1] >= K - 1 else \
        jnp.pad(branch_raw, ((0, 0), (K - 1 - x.shape[1], 0), (0, 0)))
    branch = L.causal_conv1d(branch_raw, w["conv"])
    y, h_last = RG.rg_lru(branch, w["lru"])
    gate = jax.nn.gelu(x @ w["in_gate"])
    return (y * gate) @ w["out"], {"conv": conv_state, "h": h_last}


# ---------------------------------------------------------------------------
# full-model sequence forward
# ---------------------------------------------------------------------------

def _embed(params, cfg: ModelConfig, inputs):
    if cfg.embed_inputs:
        x = jnp.take(params["embed"], inputs, axis=0)
    else:
        x = inputs.astype(jnp.dtype(cfg.dtype))
    return shard_act(x, "residual")


def _unembed(params, cfg: ModelConfig, x):
    x = L.rms_norm(x, params["final_norm"], eps=cfg.norm_eps)
    head = params["embed"].T if (cfg.tie_embeddings and cfg.embed_inputs) \
        else params["lm_head"]
    return shard_act(x @ head, "logits")


def unembed_weights(params, cfg: ModelConfig):
    return params["embed"].T if (cfg.tie_embeddings and cfg.embed_inputs) \
        else params["lm_head"]


def forward(params, cfg: ModelConfig, inputs, *, remat: str = "none",
            collect_cache: bool = False, cache_len: int | None = None,
            return_hidden: bool = False):
    """Teacher-forced forward. inputs: tokens (B,S) int or embeds (B,S,D).
    Returns (logits (B,S,V), aux_loss, caches|None). `cache_len` sizes the
    full-attention KV caches (>= S) so decode can continue past prefill.
    With `return_hidden` the final norm output is returned instead of logits
    (the fused unembed+CE path owns the head matmul)."""
    x = _embed(params, cfg, inputs)
    S = x.shape[1]
    positions = jnp.arange(S)
    segs = plan_segments(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    caches: list[Any] = []

    for seg, seg_params in zip(segs, params["segments"]):
        def period_body(x, layer_params, _seg=seg):
            aux_p = jnp.zeros((), jnp.float32)
            cache_p = {}
            for i, kind in enumerate(_seg.pattern):
                x, aux_i, cache_i = _apply_block_seq(
                    kind, layer_params[f"pos{i}"], x, cfg, positions,
                    collect_cache=collect_cache, cache_len=cache_len)
                aux_p = aux_p + aux_i
                if collect_cache:
                    cache_p[f"pos{i}"] = cache_i
            return x, (aux_p, cache_p)

        if remat != "none":
            policy = None if remat == "full" else \
                jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
            period_body = jax.checkpoint(period_body, policy=policy,
                                         static_argnums=())

        def scan_body(carry, layer_params):
            x = carry
            x, (aux_p, cache_p) = period_body(x, layer_params)
            return x, (aux_p, cache_p)

        x, (aux_seg, cache_seg) = jax.lax.scan(scan_body, x, seg_params)
        aux_total = aux_total + jnp.sum(aux_seg)
        caches.append(cache_seg)

    if return_hidden:
        x = L.rms_norm(x, params["final_norm"], eps=cfg.norm_eps)
        return x, aux_total, (caches if collect_cache else None)
    logits = _unembed(params, cfg, x)
    return logits, aux_total, (caches if collect_cache else None)


def prefill(params, cfg: ModelConfig, inputs, *, cache_len: int | None = None):
    """Returns (logits_last (B,V), caches, next_pos). Caches are stacked per
    segment/position exactly as decode_step consumes them; pass `cache_len`
    > S to leave room for decoded tokens."""
    logits, _, caches = forward(params, cfg, inputs, collect_cache=True,
                                cache_len=cache_len)
    S = inputs.shape[1]
    return logits[:, -1], caches, S


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, *, dtype=None):
    """Empty caches shaped like prefill output (stacked per segment/pos)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    segs = plan_segments(cfg)
    caches = []
    for seg in segs:
        seg_cache = {}
        for pi, kind in enumerate(seg.pattern):
            if kind == "global":
                c = L.init_kv_cache(batch, max_len, cfg.n_kv_heads, cfg.head_dim, dtype)
            elif kind == "local":
                c = L.init_kv_cache(batch, min(cfg.window or max_len, max_len),
                                    cfg.n_kv_heads, cfg.head_dim, dtype)
            elif kind == "mamba":
                c = SSM.mamba_init_state(batch, cfg.d_model, cfg.ssm, dtype)
            elif kind == "rglru":
                r = cfg.rglru or RGLRUConfig()
                c = RG.recurrent_init_state(batch, r.lru_width or cfg.d_model, r, dtype)
            else:
                raise ValueError(kind)
            seg_cache[f"pos{pi}"] = jax.tree.map(
                lambda a, n=seg.n_repeats: jnp.broadcast_to(a, (n, *a.shape)), c)
        caches.append(seg_cache)
    return caches


def _apply_block_decode(kind, w, x, cache, pos, cfg: ModelConfig):
    """One layer, one token. x: (B,1,D). Returns (x, new_cache)."""
    if kind in ("global", "local"):
        h = L.rms_norm(x, w["norm1"], eps=cfg.norm_eps)
        B = x.shape[0]
        q = (h @ w["attn"]["wq"]).reshape(B, 1, cfg.n_heads, cfg.head_dim)
        k = (h @ w["attn"]["wk"]).reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ w["attn"]["wv"]).reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
        theta = _kind_theta(cfg, kind)
        ppos = pos[None] if jnp.ndim(pos) == 0 else pos
        q = L.rope(q, ppos, theta=theta)
        k = L.rope(k, ppos, theta=theta)
        if kind == "local" and cfg.window:
            cache = L.cache_update_ring(cache, k, v, pos)
            out = L.decode_attention_ring(q, cache, pos, window=cfg.window)
        else:
            cache = L.cache_update_full(cache, k, v, pos)
            out = L.attention(q, cache["k"], cache["v"], q_offset=pos,
                              kv_valid_len=pos + 1)
        x = x + out.reshape(B, 1, cfg.q_dim) @ w["attn"]["wo"]
        h2 = L.rms_norm(x, w["norm2"], eps=cfg.norm_eps)
        delta2, _ = _channel_mix(h2, w, cfg, decode=True)
        x = x + delta2
    elif kind == "mamba":
        h = L.rms_norm(x, w["norm1"], eps=cfg.norm_eps)
        delta, cache = SSM.mamba_step(h, cache, w["mamba"], cfg.ssm)
        x = x + delta
    elif kind == "rglru":
        h = L.rms_norm(x, w["norm1"], eps=cfg.norm_eps)
        delta, cache = RG.recurrent_step(h, cache, w["rec"], cfg.rglru or RGLRUConfig())
        x = x + delta
        h2 = L.rms_norm(x, w["norm2"], eps=cfg.norm_eps)
        x = x + L.mlp(h2, w["mlp"], act=cfg.act, gated=_gated(cfg))
    else:
        raise ValueError(kind)
    return x, cache


def decode_step(params, cfg: ModelConfig, inputs, caches, pos):
    """One decode step. inputs: token ids (B,1) or embeds (B,1,D); `pos` is the
    global position being written. Returns (logits (B,V), new_caches)."""
    x = _embed(params, cfg, inputs)
    segs = plan_segments(cfg)
    new_caches = []
    for seg, seg_params, seg_cache in zip(segs, params["segments"], caches):
        def scan_body(x, xs, _seg=seg):
            layer_params, layer_cache = xs
            new_cache = {}
            for i, kind in enumerate(_seg.pattern):
                x, new_cache[f"pos{i}"] = _apply_block_decode(
                    kind, layer_params[f"pos{i}"], x, layer_cache[f"pos{i}"],
                    pos, cfg)
            return x, new_cache

        x, seg_new = jax.lax.scan(scan_body, x, (seg_params, seg_cache))
        new_caches.append(seg_new)
    logits = _unembed(params, cfg, x)
    return logits[:, 0], new_caches
