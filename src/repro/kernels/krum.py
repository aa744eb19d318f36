"""Pallas TPU kernel for Krum pairwise-distance scoring (BRIDGE-K/B).

The O(n^2 d) hot loop of the vector screening rules is the pairwise
squared-distance (Gram) accumulation.  We tile the coordinate dimension into
VMEM blocks and accumulate  G += X_blk @ X_blk^T  on the MXU across grid
steps (output revisiting), then form  d2 = diag + diag^T - 2G  in the final
grid step.  The [n, n] score matrix is tiny (n <= ~64) — the kernel is
entirely bound by streaming X through VMEM once, which is optimal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, gram_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)  # [n, blk]

    @pl.when(i == 0)
    def _init():
        gram_ref[...] = jnp.zeros_like(gram_ref)

    gram_ref[...] += jnp.dot(x, x.T, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def pairwise_sq_dists_pallas(
    stacked: jax.Array,
    *,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """[n, n] squared euclidean distances between rows of ``stacked [n, d]``."""
    n, d = stacked.shape
    pad_d = (-d) % block_d
    xp = jnp.pad(stacked, ((0, 0), (0, pad_d)))
    dp = d + pad_d
    gram = pl.pallas_call(
        _kernel,
        grid=(dp // block_d,),
        in_specs=[pl.BlockSpec((n, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
    )(xp)
    sq = jnp.diagonal(gram)
    return jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gram, 0.0)
