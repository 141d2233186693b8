"""The program's side of a Phi-3 configuration file (a dense decoder): the
model config it is built with, the counts of one training step at the
file's sizes, and the file cut to a size the CPU runs in seconds.

Model FLOPs of one training step count the forward and backward passes
(backward = twice forward) and leave recomputation out: a step with full
rematerialization does more, and that surplus is not useful work.
Attention is counted causal: only the query-key pairs the mask keeps, and
within a sliding window only the pairs inside it. The embedding lookup is
not a matmul and is not counted; the output head is.
"""

from __future__ import annotations

from harness import flops


def model_config(cfg: dict):
    """``repro.models.config.ModelConfig`` of a Phi-3 configuration file."""
    from repro.models.config import ModelConfig

    if cfg["hidden_act"] != "silu":
        raise ValueError(f"unsupported activation {cfg['hidden_act']!r}")
    heads = cfg["num_attention_heads"]
    window = cfg.get("sliding_window") or 0
    return ModelConfig(
        name=f"{cfg['model_type']}-{cfg['num_hidden_layers']}l",
        family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=heads,
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // heads,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        block_pattern=("local",) if window else ("global",), window=window,
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        act="silu", mlp_gated=True,
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["train"]["param_dtype"])


def matmul_params(cfg: dict) -> int:
    """Matmul weights one token passes through (dense decoder)."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    f = cfg["intermediate_size"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f     # gated MLP
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def train_step_flops(cfg: dict, batch: int, seq_len: int) -> float:
    """Model FLOPs of one forward + backward pass over ``batch`` rows."""
    tokens = batch * seq_len
    dense = 6.0 * matmul_params(cfg) * tokens
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    # QK^T and PV: 2 FLOPs per multiply-add each, per kept pair and head
    attn_fwd = (4.0 * cfg["num_attention_heads"] * hd
                * flops.attention_pairs(seq_len, cfg.get("sliding_window"))
                * batch * cfg["num_hidden_layers"])
    return dense + 3.0 * attn_fwd


def work(cfg: dict) -> dict:
    """Counts of one training step at ``cfg["train"]`` sizes."""
    batch, seq_len = cfg["train"]["batch"], cfg["train"]["seq_len"]
    heads = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // heads
    fwd, bwd = flops.attention_call_flops(
        batch, heads, seq_len, cfg.get("sliding_window"), hd, hd)
    return {"step_flops": train_step_flops(cfg, batch, seq_len),
            "attention_fwd_flops": fwd, "attention_bwd_flops": bwd}


def smoke(cfg: dict) -> dict:
    """The configuration at every width cut down, for runs on the CPU."""
    out = dict(cfg)
    out.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=4, num_hidden_layers=2, vocab_size=512,
               sliding_window=31)
    out["train"] = dict(cfg["train"], seq_len=64)
    return out
