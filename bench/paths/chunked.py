"""Cells whose window calls a BRIDGE trainer's ``run_chunks``: one donated
scan of ``ticks_per_call`` ticks per call, batches stacked by the trainer
from the host arrays the benchmark made in set-up."""
from __future__ import annotations

import functools

import jax
import numpy as np

from bench import generators, models, weights


class ChunkedCell:
    """The shared driver; a path module supplies the trainer, the batches
    and the program's weight layout, and the model family (`bench.models`)
    the reference's gradient."""

    check_calls = 1  # the first call's ticks are the ones the reference follows
    sparse = False  # the sparse [M, K] neighbour table (`BridgeConfig.sparse`)

    def __init__(self, cfg: dict, traffic: dict, seed: int, *, dtype: str | None = None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.dtype = dtype or cfg["dtype"]
        self.m, self.b = traffic["nodes"], traffic["b"]
        self.adj = generators.graph(traffic["graph"], self.m, self.b)
        self.byz = generators.byzantine_mask(self.m, traffic["byzantine"],
                                             traffic["byzantine_seed"])
        self.ticks_per_call = traffic["ticks_per_call"]
        self.pool = self.ticks_per_call * traffic["pool_calls"]
        self.init = weights.make_init(cfg, self.m, traffic["init_perturb"], self.dtype)

    # -- what a path module provides ------------------------------------
    def make_batches(self, ticks: int) -> list:
        raise NotImplementedError

    def trainer(self):
        raise NotImplementedError

    def program_layout(self):
        raise NotImplementedError

    # -- the run ----------------------------------------------------------
    def setup(self) -> None:
        from repro.core.bridge import BridgeConfig
        from repro.core.graph import Topology

        self.batches = self.make_batches(self.pool)
        t = self.traffic
        config = BridgeConfig(
            topology=Topology(adjacency=self.adj, num_byzantine=self.b), rule=t["rule"],
            num_byzantine=self.b, attack=t["attack"]["name"], byzantine_seed=t["byzantine_seed"],
            lam=t["lam"], t0=t["t0"], sparse=self.sparse)
        self.tr = self.trainer()(config)
        if not np.array_equal(np.asarray(self.tr.byz_mask), self.byz):
            raise RuntimeError("the program's Byzantine set differs from the traffic file's")
        params = self.init(weights.key_from_seed(self.seed))
        weights.check_layout(params, self.program_layout())
        self.state = self.tr.init(params, seed=int(self.seed) % (1 << 31))
        del params  # the state owns them; run_chunks donates it

    def call(self, k: int):
        c, lo = self.ticks_per_call, (k * self.ticks_per_call) % self.pool
        self.state, ms = self.tr.run_chunks(
            self.state, lambda i: self.batches[(lo + i) % self.pool], c, chunk=c)
        return ms["loss"]

    def sync(self) -> None:
        jax.block_until_ready(self.state.params)

    def snapshot(self):
        """The parameters after the check ticks, on the host."""
        return jax.device_get(self.state.params)

    def compiled_window(self) -> tuple:
        c = self.ticks_per_call
        xs = jax.tree_util.tree_map(lambda *a: np.stack(a), *self.batches[:c])
        return window_program(self.tr._chunk_scan().lower(self.tr._cell, self.state, xs).compile())

    def release(self) -> None:
        del self.state, self.tr

    def reference_spec(self) -> dict:
        honest = ~self.byz
        return {"batches": self.batches[:self.ticks_per_call * self.check_calls],
                "node_grad": functools.partial(models.of(self.cfg).node_grad, self.cfg),
                "adj": self.adj, "byz": self.byz, "b": self.b, "attack": self.traffic["attack"],
                "lam": self.traffic["lam"], "t0": self.traffic["t0"], "honest": honest,
                "nodes_per_call": self.traffic["reference_nodes_per_call"],
                "params0": self.reference_params}

    def reference_params(self):
        """The initial weights again, in float32, from the same seed."""
        return weights.make_init(self.cfg, self.m, self.traffic["init_perturb"], "float32")(
            weights.key_from_seed(self.seed))


def window_program(compiled) -> tuple:
    """([HLO text], [memory analysis]) of the window's compiled program."""
    ma = compiled.memory_analysis()
    return [compiled.as_text()], [{
        "argument": ma.argument_size_in_bytes, "output": ma.output_size_in_bytes,
        "temp": ma.temp_size_in_bytes, "alias": ma.alias_size_in_bytes}]
