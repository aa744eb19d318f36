"""Initial weights, made on the device from the run's seed.

The same function makes the program's weights and, after the window, the
reference's: the weights are the benchmark's input data, not something the
program made.  `init` builds every node's replica of every leaf in one jitted
call, in the storage dtype the run asks for; each leaf draws from its own
folded key, so the values do not depend on how many leaves there are.

The pytree has the program's own layout (nested dicts, the dense family's
layers stacked as ``[groups, pattern, ...]``); `check_layout` compares it with
the program's ``init_params`` by shapes alone, so a layout change in the
program fails loudly at set-up instead of training different weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any whole number (run seeds may exceed 32 bits)."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def linear_plan(cfg: dict) -> dict:
    """(shape, scale) per leaf of the one-vs-all linear classifier."""
    d, c = cfg["input_dim"], cfg["classes"]
    return {"b": ((c,), 0.0), "w": ((d, c), cfg["init_scale"])}


def dense_lm_plan(cfg: dict) -> dict:
    """(shape, scale) per leaf of a dense decoder (one pattern group per
    layer): matrices N(0, 1/fan_in), the embedding N(0, 0.02^2), RMSNorm
    weights stored as (weight - 1) and so zero."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    lead = (cfg["num_hidden_layers"], 1)
    fan = lambda n: 1.0 / math.sqrt(n)
    return {
        "blocks": {
            "attn": {
                "k_norm": {"w": (lead + (hd,), 0.0)},
                "q_norm": {"w": (lead + (hd,), 0.0)},
                "wk": (lead + (d, kv * hd), fan(d)),
                "wo": (lead + (h * hd, d), fan(h * hd)),
                "wq": (lead + (d, h * hd), fan(d)),
                "wv": (lead + (d, kv * hd), fan(d)),
            },
            "ln1": {"w": (lead + (d,), 0.0)},
            "ln2": {"w": (lead + (d,), 0.0)},
            "mlp": {
                "wd": (lead + (f, d), fan(f)),
                "wg": (lead + (d, f), fan(d)),
                "wu": (lead + (d, f), fan(d)),
            },
        },
        "embed": ((v, d), 0.02),
        "head": ((d, v), fan(d)),
        "ln_f": {"w": ((d,), 0.0)},
    }


PLANS = {"linear": linear_plan, "dense_lm": dense_lm_plan}


def plan(cfg: dict) -> dict:
    return PLANS[cfg["model"]](cfg)


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
