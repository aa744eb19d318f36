"""The streaming trainer (`repro.stream.StreamBridgeTrainer`) on a language
model (`bench.models`): attack, screen and apply run block by block over
each parameter leaf, the model's forward and backward pass vmapped over the
nodes on one chip."""
from __future__ import annotations

import jax

from bench import generators, models
from bench.paths.chunked import ChunkedCell


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

        mc = models.of(self.cfg).program_config(self.cfg, self.dtype)
        grad_fn = api.build(mc).grad_fn()
        return lambda config: StreamBridgeTrainer(config, grad_fn)

    def program_layout(self):
        from repro.core import replicate
        from repro.models import api

        mc = models.of(self.cfg).program_config(self.cfg, self.dtype)
        return jax.eval_shape(lambda k: replicate(api.build(mc).init_params(k, mc), self.m),
                              jax.random.PRNGKey(0))
