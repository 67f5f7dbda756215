"""Model operations counted from a configuration file's sizes and the
request shapes, never from what a kernel's grid or padding happens to
do: a program that skips work it need not do reads a higher share on
the same yardstick."""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {"d": d, "layers": int(cfg["num_hidden_layers"]), "h": h,
            "kv": int(cfg["num_key_value_heads"]), "hd": d // h,
            "f": int(cfg["intermediate_size"]), "v": int(cfg["vocab_size"])}


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    m = dims(cfg)
    attn = m["d"] * m["hd"] * (2 * m["h"] + 2 * m["kv"])
    return attn + 3 * m["d"] * m["f"]


def attention_flops(cfg: dict, start: int, n: int) -> int:
    """Score and value products, all layers, for ``n`` causal queries at
    positions ``start .. start+n-1`` (query i sees start + i + 1 keys)."""
    m = dims(cfg)
    keys = n * start + n * (n + 1) // 2
    return m["layers"] * 4 * m["h"] * m["hd"] * keys


def decode_flops(cfg: dict, length: int) -> int:
    """One decoded token whose context, itself included, is ``length``
    tokens: every layer's products, attention, and one row of logits."""
    m = dims(cfg)
    return (2 * m["layers"] * layer_matmul_params(cfg)
            + attention_flops(cfg, length - 1, 1) + 2 * m["d"] * m["v"])


def prefill_flops(cfg: dict, start: int, n: int) -> int:
    """Prefilling ``n`` prompt tokens after ``start`` cached ones, and the
    one row of logits that picks the first token."""
    m = dims(cfg)
    return (2 * m["layers"] * layer_matmul_params(cfg) * n
            + attention_flops(cfg, start, n) + 2 * m["d"] * m["v"])
