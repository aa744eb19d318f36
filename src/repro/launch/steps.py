"""Jittable step functions for the production mesh.

* ``make_train_step`` — one BRIDGE iteration (Algorithm 1) over the mesh:
  per-node local grads (vmap over the sharded node axis), gossip + screening
  over the node axis (the paper's technique), plain GD update with rho(t).
* ``make_prefill_step`` — inference prefill: forward, last-position logits
  (whisper: encoder + cross-KV build).
* ``make_serve_step`` — single-token decode against a KV cache/SSM state.
"""
from __future__ import annotations

from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.gossip import gossip_screen_params
from repro.models import api as model_api
from repro.models import dense, encdec, hybrid, moe, ssm, vlm
from repro.models.config import ModelConfig


def make_train_step(
    cfg: ModelConfig,
    mesh,
    node_axes: tuple,
    param_specs: Any,
    adjacency: jnp.ndarray,
    *,
    rule: str = "trimmed_mean",
    num_byzantine: int = 0,
    gossip_schedule: str = "all_gather",
    lam: float = 1.0,
    t0: float = 200.0,
    gossip_first: bool = True,
    gossip_quantize: bool = False,
) -> Callable:
    """Returns train_step(params, batch, t) -> (new_params, metrics).

    ``gossip_first`` controls collective/compute overlap (§Perf): the screen
    of w(t) only depends on w(t), so issuing the gossip before the backward
    pass lets XLA's latency-hiding scheduler overlap ICI with the MXU.
    """
    api = model_api.build(cfg)

    def local_grads(params, batch):
        def one(p, bt):
            return jax.value_and_grad(lambda pp: api.train_loss(pp, bt, cfg))(p)

        with jax.named_scope("mesh.grad"):
            return jax.vmap(one)(params, batch)

    def gossip(params, t):
        return gossip_screen_params(
            params, param_specs, mesh=mesh, node_axes=node_axes, rule=rule,
            b=num_byzantine, adjacency=adjacency, schedule=gossip_schedule, t=t,
            quantize=gossip_quantize,
        )

    def train_step(params, batch, t):
        if gossip_first:
            y = gossip(params, t)
            losses, grads = local_grads(params, batch)
        else:
            losses, grads = local_grads(params, batch)
            y = gossip(params, t)
        rho = (1.0 / (lam * (t0 + t))).astype(jnp.float32)

        def upd(yy, gg):
            return (yy.astype(jnp.float32) - rho * gg.astype(jnp.float32)).astype(yy.dtype)

        new_params = jax.tree_util.tree_map(upd, y, grads)
        return new_params, {"loss": jnp.mean(losses)}

    return train_step


def make_prefill_step(cfg: ModelConfig) -> Callable:
    """prefill_step(params, batch) -> last-token logits [B, 1, V]
    (whisper: encoder output + cross-KV; see DESIGN.md)."""
    if cfg.family == "dense":
        def step(params, batch):
            return dense.forward(params, batch["tokens"], cfg, last_only=True)
    elif cfg.family == "vlm":
        def step(params, batch):
            tokens = batch["tokens"]
            x = vlm.merge_embeds(params, tokens, batch["image_embeds"], cfg)
            mpos = vlm.make_mrope_positions(tokens.shape[0], tokens.shape[1],
                                            batch["image_embeds"].shape[1])
            return dense.forward(params, tokens, cfg, input_embeds=x,
                                 mrope_positions=mpos, last_only=True)
    elif cfg.family == "moe":
        def step(params, batch):
            logits, _ = moe.forward(params, batch["tokens"], cfg, last_only=True)
            return logits
    elif cfg.family == "rwkv":
        def step(params, batch):
            return ssm.forward(params, batch["tokens"], cfg, last_only=True)
    elif cfg.family == "hybrid":
        def step(params, batch):
            return hybrid.forward(params, batch["tokens"], cfg, last_only=True)
    elif cfg.family == "encdec":
        def step(params, batch):
            enc_out = encdec.encode(params, batch["audio_embeds"], cfg)
            logits = encdec.decode_train(params, enc_out, batch["tokens"], cfg)
            return logits[:, -1:]
    else:
        raise ValueError(cfg.family)
    return step


def make_serve_step(cfg: ModelConfig) -> Callable:
    api = model_api.build(cfg)

    def serve_step(params, cache, batch):
        return api.decode_step(params, cache, batch["tokens"], cfg)

    return serve_step
