"""A tiny cell of the benchmark for the CPU: the real harness, the
program's real engine, at widths a test run holds."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench.spec import Cell  # noqa: E402

#: qwen2-1.5b's registry entry shrunk to two layers of width 64, in the
#: program's own numerics (bfloat16 activations, float32 weights).
OVERRIDES = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
             "head_dim": 16, "d_ff": 128, "vocab": 256, "max_seq": 128}


def config(**over) -> dict:
    c = {"name": "tiny", "program_arch": "qwen2-1.5b",
         "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
         "vocab_size": 256, "qkv_bias": True, "rope_theta": 1000000.0,
         "rms_norm_eps": 1e-05, "tie_word_embeddings": True,
         "numerics": {"param_dtype": "float32", "dtype": "bfloat16"}}
    c.update(over)
    return c


def cell(arrivals: str = "poisson", *, gap_limit: float = 0.03,
         min_tokens: int = 20, **over) -> Cell:
    mix = {"arrivals": arrivals, "prompt": "uniform:4:40",
           "output": "uniform:2:24", "shape_seed": 1}
    c = {"slots": 4, "max_seq": 128, "rate_per_s": 4.0, "backlog": 8,
         "program_overrides": dict(OVERRIDES),
         "check": {"tokens": 200, "rows": 8,
                   "limits": {"logit_gap": {"max": gap_limit},
                              "tokens_compared": {"min": min_tokens},
                              "finished_short": {"max": 0},
                              "out_of_vocab": {"max": 0}}}}
    c.update(over)
    e2e = ({"name": "ttft_p90_s", "unit": "s"},
           {"name": "itl_p99_ms", "unit": "ms"},
           {"name": "tokens_per_s", "unit": "tokens/s"},
           {"name": "setup_s", "unit": "s"})
    return Cell(f"tiny.{arrivals}", 1, config(), mix, c, e2e, ())
