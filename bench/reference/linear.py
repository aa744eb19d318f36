"""Plain reference of the paper's convex task (Sec. V-A): a one-vs-all
linear classifier on 784 pixels and 10 classes with the squared hinge loss
and an L2 penalty on every weight."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def loss(p, batch, cfg, dtype=jnp.float32):
    """Mean over samples of sum over classes of max(0, 1 - t * s)^2, plus
    l2 * |p|^2, with t = +1 for the sample's class and -1 otherwise."""
    x, y = batch
    x = jnp.asarray(x, dtype)
    w, b = p["w"].astype(dtype), p["b"].astype(dtype)
    scores = jnp.dot(x, w, precision="highest") + b
    t = jnp.where(jnp.arange(cfg["classes"])[None, :] == jnp.asarray(y)[:, None], 1.0, -1.0)
    hinge = jnp.maximum(0.0, 1.0 - t.astype(dtype) * scores)
    return jnp.mean(jnp.sum(hinge * hinge, axis=1)) + cfg["l2"] * (jnp.sum(w * w) + jnp.sum(b * b))


def node_grad(cfg, dtype=jnp.float32):
    """``(params_node, batch_node) -> (loss, grads)``."""
    return jax.value_and_grad(lambda p, bt: loss(p, bt, cfg, dtype))
