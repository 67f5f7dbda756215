"""The Qwen2 architecture as the system under test takes it: its model
configuration and its serving configuration, built from a configuration
file of this directory.  Nothing here computes; the harness calls these
to build the program from the file's sizes."""
from __future__ import annotations


def model_config(cfg: dict):
    from repro.configs.base import AttnConfig, ModelConfig, dense_stages

    if cfg["hidden_act"] != "silu" or float(cfg["rms_norm_eps"]) != 1e-6:
        # the program's RMSNorm epsilon and SwiGLU activation are fixed
        raise ValueError(f"{cfg['name']}: the program runs silu and "
                         f"rms_norm_eps 1e-6 only")
    if cfg.get("use_sliding_window"):
        raise ValueError(f"{cfg['name']}: sliding windows are not served")
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    layers = int(cfg["num_hidden_layers"])
    return ModelConfig(
        name=cfg["name"], family="dense", d_model=d, n_layers=layers,
        vocab=int(cfg["vocab_size"]), d_ff=int(cfg["intermediate_size"]),
        stages=dense_stages(layers),
        attn=AttnConfig(n_heads=heads,
                        n_kv_heads=int(cfg["num_key_value_heads"]),
                        head_dim=d // heads, qkv_bias=True,
                        rope_theta=float(cfg["rope_theta"])),
        act="silu", glu=True,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
    )


def serve_config(cfg: dict):
    from repro.serve.config import ServeConfig

    return ServeConfig(**cfg["serve"])
