"""Initial weights, made on the device from the run's seed.

The same function makes the program's weights and, after the window, the
reference's: the weights are the benchmark's input data, not something the
program made.  `init` builds every node's replica of every leaf in one jitted
call, in the storage dtype the run asks for; each leaf draws from its own
folded key, so the values do not depend on how many leaves there are.

The pytree has the program's own layout, as the model family's ``plan``
gives it (`bench.models`); `check_layout` compares it with
the program's ``init_params`` by shapes alone, so a layout change in the
program fails loudly at set-up instead of training different weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import models


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any whole number (run seeds may exceed 32 bits)."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def plan(cfg: dict) -> dict:
    """(shape, scale) per leaf: the plan of the configuration's model
    family (`bench.models`)."""
    return models.of(cfg).plan(cfg)


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make_init(cfg: dict, nodes: int, perturb: float, dtype, **jit_kw):
    """``init(key) -> [nodes, ...]`` pytree: one base draw per leaf, plus an
    independent ``perturb``-scaled draw per node (nodes start inside a common
    ball, as in the paper; ``perturb = 0`` starts them equal)."""
    specs, treedef = jax.tree_util.tree_flatten(plan(cfg), is_leaf=_is_spec)

    def init(key):
        leaves = []
        for i, (shape, scale) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            base = scale * jax.random.normal(jax.random.fold_in(k, 0), shape, jnp.float32)
            w = jnp.broadcast_to(base, (nodes,) + shape)
            if perturb:
                w = w + perturb * jax.random.normal(jax.random.fold_in(k, 1),
                                                    (nodes,) + shape, jnp.float32)
            leaves.append(w.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(init, **jit_kw)


def check_layout(ours, program_shapes) -> None:
    """Raise unless two pytrees have the same structure and leaf shapes."""
    a = jax.tree_util.tree_map(lambda x: tuple(x.shape), ours)
    b = jax.tree_util.tree_map(lambda x: tuple(x.shape), program_shapes)
    if jax.tree_util.tree_structure(a) != jax.tree_util.tree_structure(b) or a != b:
        raise RuntimeError(f"weight layout differs from the program's:\n{a}\nvs\n{b}")


def count(cfg: dict) -> int:
    """Parameters per node, from the plan's shapes."""
    specs = jax.tree_util.tree_leaves(plan(cfg), is_leaf=_is_spec)
    return sum(int(np.prod(s)) for s, _ in specs)
