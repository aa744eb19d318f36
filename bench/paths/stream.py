"""The streaming trainer (`repro.stream.StreamBridgeTrainer`) on a dense LM:
attack, screen and apply run block by block over each parameter leaf, the
model's forward and backward pass vmapped over the nodes on one chip."""
from __future__ import annotations

import functools

import jax

from bench import generators
from bench.paths.chunked import ChunkedCell
from bench.reference import dense_lm as ref_lm


def model_config(cfg: dict, dtype: str):
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


def lm_batches(cfg: dict, traffic: dict, nodes: int, ticks: int, seed: int) -> list:
    return generators.token_batches(
        vocab=cfg["vocab_size"], nodes=nodes, batch=traffic["batch"], seq=traffic["seq"],
        ticks=ticks, successors=traffic["successors"], restart=traffic["restart"], seed=seed)


class Cell(ChunkedCell):
    def make_batches(self, ticks: int) -> list:
        return lm_batches(self.cfg, self.traffic, self.m, ticks, self.seed)

    def trainer(self):
        from repro.models import api
        from repro.stream import StreamBridgeTrainer

        grad_fn = api.build(model_config(self.cfg, self.dtype)).grad_fn()
        return lambda config: StreamBridgeTrainer(config, grad_fn)

    def program_layout(self):
        from repro.core import replicate
        from repro.models import api

        mc = model_config(self.cfg, self.dtype)
        return jax.eval_shape(lambda k: replicate(api.build(mc).init_params(k, mc), self.m),
                              jax.random.PRNGKey(0))

    def reference_grad(self):
        return functools.partial(ref_lm.node_grad, self.cfg)
