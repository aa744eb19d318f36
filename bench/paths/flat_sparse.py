"""The flat trainer (`repro.core.BridgeTrainer`) on the paper's linear task:
every node's iterate one row of an [M, d] matrix, neighbour views gathered
through the sparse [M, K] table."""
from __future__ import annotations

import functools

import jax

from bench import generators
from bench.paths.chunked import ChunkedCell
from bench.reference import linear as ref_linear


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
        from repro.models import small

        l2 = self.cfg["l2"]
        grad_fn = jax.value_and_grad(lambda p, b: small.linear_loss(p, b, l2=l2))
        return lambda config: BridgeTrainer(config, grad_fn)

    def program_layout(self):
        from repro.core import replicate
        from repro.models import small

        return jax.eval_shape(lambda k: replicate(small.init_linear(
            k, self.cfg["input_dim"], self.cfg["classes"]), self.m), jax.random.PRNGKey(0))

    def reference_grad(self):
        return functools.partial(ref_linear.node_grad, self.cfg)
