"""A dense decoder-only LM, run by the program's dense family
(`repro.models`, one pattern group per layer): pre-RMSNorm blocks of GQA
attention with RMSNorm on q and k, a SwiGLU MLP, an untied head.  The
configuration file is the model's published config.json, cut to size."""
from __future__ import annotations

import math

from bench.reference.dense_lm import node_grad  # noqa: F401  (the family's reference)
from bench.work import F32

def plan(cfg: dict) -> dict:
    """(shape, scale) per leaf of a dense decoder (one pattern group per
    layer): matrices N(0, 1/fan_in), the embedding N(0, 0.02^2), RMSNorm
    weights stored as (weight - 1) and so zero."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    lead = (cfg["num_hidden_layers"], 1)
    fan = lambda n: 1.0 / math.sqrt(n)
    return {
        "blocks": {
            "attn": {
                "k_norm": {"w": (lead + (hd,), 0.0)},
                "q_norm": {"w": (lead + (hd,), 0.0)},
                "wk": (lead + (d, kv * hd), fan(d)),
                "wo": (lead + (h * hd, d), fan(h * hd)),
                "wq": (lead + (d, h * hd), fan(d)),
                "wv": (lead + (d, kv * hd), fan(d)),
            },
            "ln1": {"w": (lead + (d,), 0.0)},
            "ln2": {"w": (lead + (d,), 0.0)},
            "mlp": {
                "wd": (lead + (f, d), fan(f)),
                "wg": (lead + (d, f), fan(d)),
                "wu": (lead + (d, f), fan(d)),
            },
        },
        "embed": ((v, d), 0.02),
        "head": ((d, v), fan(d)),
        "ln_f": {"w": ((d,), 0.0)},
    }


def matmul_params(cfg: dict) -> int:
    """Per-node parameters that enter matrix products, per token."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    per_layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * v


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward FLOPs of QK^T and AV per token, causal."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return cfg["num_hidden_layers"] * 2 * 2 * h * hd * (seq + 1) / 2


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs per token, no recompute."""
    return 3 * (2 * matmul_params(cfg) + attention_flops_per_token(cfg, seq))


def node_batch(cfg: dict, traffic: dict) -> tuple[int, int]:
    """(tokens, bytes) of one node's batch: ``batch`` rows of ``seq + 1``
    int32 token ids (inputs and next-token targets)."""
    return traffic["batch"] * traffic["seq"], traffic["batch"] * (traffic["seq"] + 1) * F32


def program_config(cfg: dict, dtype: str):
    """The program's ModelConfig for a configuration file of the dense family."""
    from repro.models.config import ModelConfig

    if cfg["model_type"] != "qwen3":
        raise ValueError(f"no dense-family mapping for model_type {cfg['model_type']!r}")
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], head_dim=cfg["head_dim"], qk_norm=True,
        rope_theta=float(cfg["rope_theta"]), dtype=dtype)
