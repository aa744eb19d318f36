"""The mesh path: `repro.launch.steps.make_train_step` jitted over a
(nodes, 1) ("data", "model") mesh, one node per chip.  Each tick all-gathers
every node's parameters over the node axis (`repro.core.gossip`), screens
its own row, and applies its own gradient.  One call is one tick: the host
puts the tick's batch on the mesh and dispatches the step."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import generators, models, weights
from bench.paths.chunked import window_program
from bench.paths.stream import lm_batches


class Cell:
    ticks_per_call = 1

    def __init__(self, cfg: dict, traffic: dict, seed: int, *, dtype: str | None = None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.dtype = dtype or cfg["dtype"]
        self.m, self.b = traffic["nodes"], traffic["b"]
        self.adj = generators.graph(traffic["graph"], self.m, self.b)
        self.byz = generators.byzantine_mask(self.m, traffic["byzantine"],
                                             traffic["byzantine_seed"])
        if traffic["attack"]["name"] != "none" or self.byz.any():
            raise ValueError("the mesh path has no attack hook")
        self.check_calls = traffic["check_ticks"]
        self.pool = traffic["pool_ticks"]

    def setup(self) -> None:
        from jax.sharding import AxisType

        from repro.core import replicate
        from repro.launch import sharding
        from repro.launch.steps import make_train_step
        from repro.models import api

        devs = jax.devices()[:self.m]
        if len(devs) < self.m:
            raise RuntimeError(f"the mesh cell needs {self.m} devices, JAX sees {len(devs)}")
        mesh = jax.make_mesh((self.m, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2,
                             devices=devs)
        nax = ("data",)
        mc = models.of(self.cfg).program_config(self.cfg, self.dtype)
        layout = jax.eval_shape(lambda k: replicate(api.build(mc).init_params(k, mc), self.m),
                                jax.random.PRNGKey(0))
        pspecs = sharding.param_specs(mc, layout, node_axes=nax)
        self.shardings = sharding.named(mesh, pspecs)
        init = weights.make_init(self.cfg, self.m, self.traffic["init_perturb"], self.dtype,
                                 out_shardings=self.shardings)
        self.params = init(weights.key_from_seed(self.seed))
        weights.check_layout(self.params, layout)
        self.batches = lm_batches(self.cfg, self.traffic, self.m, self.pool, self.seed)
        self.batch_sharding = sharding.named(
            mesh, sharding.train_batch_specs(self.batches[0], nax))
        t = self.traffic
        self.step = jax.jit(
            make_train_step(mc, mesh, nax, pspecs, jnp.asarray(self.adj), rule=t["rule"],
                            num_byzantine=self.b, gossip_schedule=t["gossip"],
                            lam=t["lam"], t0=t["t0"]),
            in_shardings=(self.shardings, None, None), out_shardings=(self.shardings, None),
            donate_argnums=(0,))

    def call(self, k: int):
        batch = jax.device_put(self.batches[k % self.pool], self.batch_sharding)
        self.params, mets = self.step(self.params, batch, jnp.float32(k))
        return mets["loss"]

    def sync(self) -> None:
        jax.block_until_ready(self.params)

    def snapshot(self):
        return jax.device_get(self.params)

    def compiled_window(self) -> tuple:
        batch = jax.device_put(self.batches[0], self.batch_sharding)
        return window_program(self.step.lower(self.params, batch, jnp.float32(0)).compile())

    def release(self) -> None:
        del self.params, self.step

    def reference_spec(self) -> dict:
        return {"batches": self.batches[:self.check_calls],
                "node_grad": functools.partial(models.of(self.cfg).node_grad, self.cfg),
                "adj": self.adj, "byz": self.byz, "b": self.b, "attack": self.traffic["attack"],
                "lam": self.traffic["lam"], "t0": self.traffic["t0"],
                "honest": np.ones(self.m, bool),
                "nodes_per_call": self.traffic["reference_nodes_per_call"],
                "params0": self.reference_params}

    def reference_params(self):
        return weights.make_init(self.cfg, self.m, self.traffic["init_perturb"], "float32")(
            weights.key_from_seed(self.seed))

