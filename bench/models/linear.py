"""The paper's convex task (Sec. V-A of arXiv:1908.08098): a one-vs-all
linear classifier, ``input_dim`` x ``classes`` weights and a bias per class,
one sample an input row."""
from __future__ import annotations

import jax

from bench.reference.linear import node_grad  # noqa: F401  (the family's reference)
from bench.work import F32

def plan(cfg: dict) -> dict:
    """(shape, scale) per leaf of the one-vs-all linear classifier."""
    d, c = cfg["input_dim"], cfg["classes"]
    return {"b": ((c,), 0.0), "w": ((d, c), cfg["init_scale"])}


def matmul_params(cfg: dict) -> int:
    """Per-node parameters that enter matrix products, per sample."""
    return cfg["input_dim"] * cfg["classes"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs per sample: twice the forward pass, as the
    input needs no gradient."""
    return 2 * 2.0 * matmul_params(cfg)


def node_batch(cfg: dict, traffic: dict) -> tuple[int, int]:
    """(samples, bytes) of one node's batch: float32 pixels and a label each."""
    return traffic["batch"], traffic["batch"] * (cfg["input_dim"] + 1) * F32


def program_init(cfg: dict):
    from repro.models import small

    return lambda k: small.init_linear(k, cfg["input_dim"], cfg["classes"])


def program_grad_fn(cfg: dict):
    from repro.models import small

    l2 = cfg["l2"]
    return jax.value_and_grad(lambda p, b: small.linear_loss(p, b, l2=l2))
