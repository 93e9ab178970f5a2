"""Operations and bytes a dense decoder step needs, from the shapes.

Counted from the configuration's sizes, not from what the program
happens to do: attention at the live positions of the active slots, not
at ``max_seq``; one read of the parameters per step, in the dtype and
shard the program holds them.  So no implementation can need less, and a
share of the roofline built on these counts cannot pass 100%.
"""
from __future__ import annotations


def sizes(config: dict) -> dict:
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    return {"layers": config["num_hidden_layers"], "d": d, "heads": h,
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config.get("head_dim") or d // h,
            "ff": config["intermediate_size"], "vocab": config["vocab_size"]}


def matmul_params(config: dict) -> int:
    """Weights one token multiplies by: every layer's projections and the
    output head (the embedding lookup is a gather, not a product)."""
    s = sizes(config)
    d, hd = s["d"], s["head_dim"]
    attn = d * hd * (2 * s["heads"] + 2 * s["kv_heads"])
    mlp = 3 * d * s["ff"]
    return s["layers"] * (attn + mlp) + d * s["vocab"]


def kv_bytes_per_position(config: dict, cache_itemsize: int = 2) -> int:
    s = sizes(config)
    return s["layers"] * 2 * s["kv_heads"] * s["head_dim"] * cache_itemsize


def token_flops(config: dict, live: int) -> float:
    """Model FLOPs of one token attending ``live`` positions."""
    s = sizes(config)
    attn = 2 * 2 * s["layers"] * s["heads"] * s["head_dim"] * live
    return 2.0 * matmul_params(config) + attn


def decode_flops(config: dict, live: tuple) -> float:
    """One decode step: one token per active slot."""
    return sum(token_flops(config, n) for n in live)


def decode_bytes(config: dict, live: tuple, param_bytes: float,
                 chips: int = 1, cache_itemsize: int = 2) -> float:
    """Bytes one chip must move in one decode step: its parameters once,
    its share of the live K/V of every active slot, and the new K/V."""
    per_pos = kv_bytes_per_position(config, cache_itemsize)
    kv = sum(n * per_pos for n in live) + len(live) * per_pos
    return param_bytes + kv / chips


def least_time(flops: float, nbytes: float, peaks: dict,
               chips: int = 1) -> tuple[float, str]:
    """(seconds, bound): the larger of compute and memory time on one chip."""
    t_c = flops / chips / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
