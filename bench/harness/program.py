"""The program's side of a configuration: its model config built from the
configuration file, so the file holds the sizes as they are run."""

from __future__ import annotations


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
