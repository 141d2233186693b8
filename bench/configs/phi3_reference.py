"""Plain reference of a Phi-3 decoder (arXiv:2404.14219; the Hugging Face
``Phi3ForCausalLM`` semantics) and of the AdamW steps that train it, in
float32 with ``highest`` matmul precision. It imports nothing of the program
under test and is given the benchmark's weights and batches.

The forward pass: token embedding; per layer RMSNorm, multi-head attention
with rotary position embedding (half-split rotation, base ``rope_theta``)
under a causal mask kept to ``sliding_window`` keys, a residual add, RMSNorm,
a SiLU-gated MLP and a residual add; a final RMSNorm, the output head and the
mean next-token cross-entropy. Departures from the published description,
none of which changes the function: the RMSNorm scale is stored as ``w`` and
applied as ``1 + w`` (the published ``ones`` init is ``w = 0``), the fused
``qkv_proj`` and ``gate_up_proj`` are stored as separate matrices, and the
layers are stacked along a leading axis, as the weights are laid out in the
checkpoint (``params.segments[i].pos<j>.<name>``).

The training step is AdamW as the configuration's ``train.optimizer``
states: linear warm-up to ``peak_lr`` then a cosine to ``min_lr_frac``,
clipping by the global gradient norm, bias-corrected moments kept in
float32, decoupled weight decay on every parameter, and each updated
parameter stored back in the dtype it was given in (``train.param_dtype``
for the matrices, float32 for the norm scales).

``quant="fp8"`` is the control: every matmul's operands are rounded to
float8 (e4m3, one absmax scale per tensor) in the forward pass, the
precision one step below the configuration's bfloat16.
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
E4M3_MAX = 448.0


def _fq8(x):
    """Round to float8 e4m3 with one absmax scale; identity gradient."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, quant):
    if quant == "fp8":
        a, b = _fq8(a), _fq8(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, theta):
    """x: (B, S, H, hd), rotated by position with the half-split rotation."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) * 2.0 / hd))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window, quant, q_block=256):
    """Causal softmax attention, one block of queries at a time."""
    b, s, h, hd = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    q_block = min(q_block, s)
    n = s // q_block
    kpos = jnp.arange(s)

    @jax.checkpoint
    def block(args):
        qc, start = args
        if quant == "fp8":
            qc8, k8 = _fq8(qc), _fq8(k)
        else:
            qc8, k8 = qc, k
        sc = jnp.einsum("bqhd,bkhd->bhqk", qc8, k8,
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
        qpos = start + jnp.arange(q_block)
        keep = kpos[None, :] <= qpos[:, None]
        if window:
            keep &= kpos[None, :] > qpos[:, None] - window
        sc = jnp.where(keep[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        v8 = _fq8(v) if quant == "fp8" else v
        p8 = _fq8(p) if quant == "fp8" else p
        return jnp.einsum("bhqk,bkhd->bqhd", p8, v8,
                          precision=jax.lax.Precision.HIGHEST)

    qs = q.reshape(b, n, q_block, h, hd).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(block, (qs, jnp.arange(n) * q_block))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)


def _layer(x, w, cfg, quant):
    b, s, d = x.shape
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    eps = cfg["rms_norm_eps"]
    a = _rms(x, w["norm1"], eps)
    q = _mm(a, w["attn"]["wq"], quant).reshape(b, s, h, hd)
    k = _mm(a, w["attn"]["wk"], quant).reshape(b, s, kvh, hd)
    v = _mm(a, w["attn"]["wv"], quant).reshape(b, s, kvh, hd)
    q = _rope(q, cfg["rope_theta"])
    k = _rope(k, cfg["rope_theta"])
    o = _attention(q, k, v, cfg.get("sliding_window"), quant)
    x = x + _mm(o.reshape(b, s, h * hd), w["attn"]["wo"], quant)
    m = _rms(x, w["norm2"], eps)
    g = _mm(m, w["mlp"]["gate"], quant)
    u = _mm(m, w["mlp"]["up"], quant)
    return x + _mm(jax.nn.silu(g) * u, w["mlp"]["down"], quant)


def loss_fn(params, inputs, labels, cfg, quant=None):
    """Mean next-token cross-entropy of float32 ``params``."""
    x = params["embed"][inputs]
    for seg in params["segments"]:
        names = sorted(seg, key=lambda n: int(n[3:]))
        n_rep = jax.tree.leaves(seg[names[0]])[0].shape[0]
        for r in range(n_rep):
            for name in names:
                w = jax.tree.map(lambda a: a[r], seg[name])
                x = jax.checkpoint(partial(_layer, cfg=cfg, quant=quant))(x, w)
    x = _rms(x, params["final_norm"], cfg["rms_norm_eps"])
    logits = _mm(x, params["lm_head"], quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def _lr(opt, count):
    """Linear warm-up to ``peak_lr``, then a cosine down to
    ``min_lr_frac`` of it at ``total_steps``."""
    c = count.astype(F32)
    warm = opt["peak_lr"] * c / max(opt["warmup_steps"], 1)
    t = jnp.clip((c - opt["warmup_steps"])
                 / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0, 1.0)
    cos = opt["peak_lr"] * (opt["min_lr_frac"] + (1 - opt["min_lr_frac"])
                            * 0.5 * (1 + jnp.cos(math.pi * t)))
    return jnp.where(c < opt["warmup_steps"], warm, cos)


def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(F32))))
            for a in jax.tree.leaves(tree)]


@partial(jax.jit, static_argnames=("spec", "quant"), donate_argnums=(0, 1, 2))
def _step(params, mu, nu, count, inputs, labels, spec, quant):
    spec = json.loads(spec)
    cfg, opt = spec["model"], spec["opt"]
    p32 = jax.tree.map(lambda a: a.astype(F32), params)
    loss, g = jax.value_and_grad(loss_fn)(p32, inputs, labels, cfg, quant)
    count = count + 1
    lr = _lr(opt, count)
    raw = leaf_norms(g)
    gnorm = jnp.sqrt(sum(n * n for n in raw))
    scale = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9))
    g = jax.tree.map(lambda a: a * scale, g)
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    b1c = 1 - b1 ** count.astype(F32)
    b2c = 1 - b2 ** count.astype(F32)

    def upd(p, m, v, stored):
        step = (m / b1c) / (jnp.sqrt(v / b2c) + opt["eps"])
        return (p - lr * (step + opt["weight_decay"] * p)).astype(stored.dtype)

    new = jax.tree.map(upd, p32, mu, nu, params)
    return new, mu, nu, count, loss, leaf_norms(g), raw


@jax.jit
def _diff_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32) - y.astype(F32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def train_readings(cfg: dict, params, batches, *, total_steps: int,
                   quant=None, rows: slice | None = None) -> dict:
    """Train ``params`` (the benchmark's initial weights) on ``batches`` and
    return what the comparison reads: each step's loss, the per-leaf norms
    of the first gradient as the optimizer takes it (clipped) and raw, and
    the per-leaf norms of the parameters' change after the last step.
    ``total_steps`` is the schedule's horizon. ``rows`` keeps only those
    rows of each batch (a planted fault)."""
    train = cfg["train"]
    spec = json.dumps({
        "model": {k: v for k, v in cfg.items() if k != "train"},
        "opt": dict(train["optimizer"], total_steps=total_steps)},
        sort_keys=True)
    names = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
    p = jax.tree.map(jnp.copy, params)
    # moments laid out across chips as the weights are given
    mu = jax.tree.map(lambda a: jnp.zeros_like(a, F32), params)
    nu = jax.tree.map(lambda a: jnp.zeros_like(a, F32), params)
    count = jnp.zeros((), jnp.int32)
    losses, first, first_raw = [], None, None
    with jax.default_matmul_precision("highest"):
        for b in batches:
            inputs, labels = b["inputs"], b["labels"]
            if rows is not None:
                inputs, labels = inputs[rows], labels[rows]
            p, mu, nu, count, loss, gn, raw = _step(
                p, mu, nu, count, jnp.asarray(inputs), jnp.asarray(labels),
                spec=spec, quant=quant)
            losses.append(float(loss))
            if first is None:
                first = [float(x) for x in gn]
                first_raw = [float(x) for x in raw]
        del mu, nu
        change = [float(x) for x in _diff_norms(p, params)]
    return {"loss": losses, "grad": dict(zip(names, first)),
            "grad_raw": dict(zip(names, first_raw)),
            "change": dict(zip(names, change))}
