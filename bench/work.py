"""Operations and bytes that a tick requires, counted from shapes.

"Required" means what the mathematics needs, read once and written once,
not what the program happens to do: a roofline share built on these counts
cannot pass 100% unless the time leaves out part of the work.

* Model step: 2 FLOPs per multiply-add of every matrix product in the
  forward pass, times 3 for forward and backward (no recompute); times 2
  for the linear classifier, whose input needs no gradient.  The token
  embedding is a gather and counts no FLOPs; causal attention counts the
  (S + 1) / 2 keys each query sees on average.
* Screen (BRIDGE-T): each node's broadcast value is read once and each
  node's screened value written once, in float32: 2 x M x d x 4 bytes; the
  comparisons and additions are far below the chip's FLOP roofline and are
  not counted.
* Whole tick: the screen's bytes, plus reading every node's batch and
  iterate and writing its new iterate.
"""
from __future__ import annotations

from bench import weights

F32 = 4


def matmul_params(cfg: dict) -> int:
    """Per-node parameters that enter matrix products, per token."""
    if cfg["model"] == "linear":
        return cfg["input_dim"] * cfg["classes"]
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    per_layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * v


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward FLOPs of QK^T and AV per token, causal."""
    if cfg["model"] == "linear":
        return 0.0
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return cfg["num_hidden_layers"] * 2 * 2 * h * hd * (seq + 1) / 2


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs per token (or per sample, for the linear
    task), no recompute."""
    passes = 2 if cfg["model"] == "linear" else 3
    return passes * (2 * matmul_params(cfg) + attention_flops_per_token(cfg, seq))


def screen_bytes(cfg: dict, nodes: int) -> float:
    return 2 * nodes * weights.count(cfg) * F32


def tick_counts(cfg: dict, traffic: dict) -> dict:
    """FLOPs and bytes one tick of the whole deployment requires."""
    m, d = traffic["nodes"], weights.count(cfg)
    if cfg["model"] == "linear":
        per_node = traffic["batch"]
        batch_bytes = m * traffic["batch"] * (cfg["input_dim"] + 1) * F32
    else:
        per_node = traffic["batch"] * traffic["seq"]
        batch_bytes = m * traffic["batch"] * (traffic["seq"] + 1) * F32
    flops = m * per_node * train_flops_per_token(cfg, traffic.get("seq", 0))
    model_bytes = 2 * m * d * F32  # read every iterate, write every new one
    return {"flops": flops, "bytes": screen_bytes(cfg, m) + model_bytes + batch_bytes,
            "screen_bytes": screen_bytes(cfg, m), "tokens": m * per_node}
