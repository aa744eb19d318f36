"""The flat trainer (`repro.core.BridgeTrainer`) on the paper's task:
every node's iterate one row of an [M, d] matrix, neighbour views gathered
through the sparse [M, K] table; MNIST-like batches made from the seed."""
from __future__ import annotations

import jax

from bench import generators, models
from bench.paths.chunked import ChunkedCell


class Cell(ChunkedCell):
    sparse = True

    def make_batches(self, ticks: int) -> list:
        cfg, t = self.cfg, self.traffic
        x, y = generators.mnist_like(t["samples_per_node"] * self.m, n_classes=cfg["classes"],
                                     noise=cfg["noise"], seed=self.seed)
        shards = generators.partition_iid(x, y, self.m, seed=self.seed)
        return generators.node_batches(shards, t["batch"], ticks, seed=self.seed + 1)

    def trainer(self):
        from repro.core import BridgeTrainer

        grad_fn = models.of(self.cfg).program_grad_fn(self.cfg)
        return lambda config: BridgeTrainer(config, grad_fn)

    def program_layout(self):
        from repro.core import replicate

        init = models.of(self.cfg).program_init(self.cfg)
        return jax.eval_shape(lambda k: replicate(init(k), self.m), jax.random.PRNGKey(0))
