"""Pallas TPU kernel for coordinate-wise trimmed-mean screening (BRIDGE-T).

TPU adaptation of the paper's screening hot loop (Eqs. 7-10).  A GPU
implementation would sort each coordinate's n neighbor values; on TPU a full
sort wastes the VPU — instead we exploit b << n and *iteratively extract* the
b maxima and b minima with masked max/min reductions over the (8-sublane
aligned) neighbor axis, which is a pure element-wise/reduce pattern the VPU
pipelines well.  The coordinate dimension is tiled into 128-lane-aligned VMEM
blocks; each grid step screens one block of coordinates for one node.

Shapes: values ``[n, d]`` (n = padded neighborhood), mask ``[n]`` marks real
neighbors, self_value ``[d]``; out ``[d]``.  A leading *experiment* axis is
also accepted — ``values [E, n, d]``, ``mask [E, n]``, ``self_value [E, d]``
-> ``out [E, d]`` — mapping E onto the first Pallas grid dimension so batched
rule x attack x seed sweeps (`repro.sim`) screen every experiment in one
kernel launch.  b is static and shared across the batch.

Masked lanes use ±inf sentinels (matching `repro.core.screening`): a finite
sentinel mis-ranks legitimately huge payloads (>1e30 fp32 values, bf16
overflow products).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INF = float("inf")


def _trimmed_mean_block(values, valid, self_value, b: int):
    """Screen one ``[n, blk]`` block against ``self_value [1, blk]``; ``valid``
    is the ``[n, 1]`` float 0/1 per-row neighbor mask.  Returns ``[1, blk]``.

    Each extraction removes the *first* row holding the running extremum
    (ties broken by row index) via a min over a row iota — no bool stacking
    or cumsum, which Mosaic cannot lower.  The trim width is clamped to
    ``min(b, (count - 1) // 2)`` exactly like
    `repro.core.screening.effective_trim`: identical at or above Table II's
    ``2b + 1`` minimum, and degrades instead of dividing through zero on a
    starved neighborhood (dynamic schedules)."""
    n = values.shape[0]
    count = jnp.sum(valid)  # |N_j| (mask is per-row)
    b_eff = jnp.minimum(jnp.float32(b), jnp.floor(jnp.maximum(count - 1.0, 0.0) / 2.0))
    rows = jax.lax.broadcasted_iota(jnp.int32, values.shape, 0)
    v = values
    m = jnp.broadcast_to(valid, values.shape) > 0.5
    for i in range(2 * b):  # drop up to b maxima, then up to b minima
        top = i < b
        fill = -_INF if top else _INF
        red = jnp.max if top else jnp.min
        cur = red(jnp.where(m, v, fill), axis=0, keepdims=True)
        first = jnp.min(jnp.where((v == cur) & m, rows, n), axis=0, keepdims=True)
        m = m & ~((rows == first) & (i % b < b_eff))
    total = jnp.sum(jnp.where(m, v, 0.0), axis=0, keepdims=True) + self_value
    return total / (count - 2 * b_eff + 1)


def _kernel(values_ref, mask_ref, self_ref, out_ref, *, b: int):
    values = values_ref[0].astype(jnp.float32)  # [n, blk]
    # NaN payloads -> +inf so they are trimmed as maximal outliers instead of
    # poisoning the max/min extraction (matches repro.core.screening)
    values = jnp.where(jnp.isnan(values), _INF, values)
    out_ref[0] = _trimmed_mean_block(
        values, mask_ref[0], self_ref[0].astype(jnp.float32), b
    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("b", "block_d", "interpret"))
def trimmed_mean_pallas(
    values: jax.Array,
    mask: jax.Array,
    self_value: jax.Array,
    b: int,
    *,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Trimmed-mean screening of ``values [n, d]`` (or ``[E, n, d]``) against
    ``self_value [d]`` (or ``[E, d]``)."""
    squeeze = values.ndim == 2
    if squeeze:
        values, mask, self_value = values[None], mask[None], self_value[None]
    e, n, d = values.shape
    pad_d = (-d) % block_d
    vp = jnp.pad(values, ((0, 0), (0, 0), (0, pad_d)))
    sp = jnp.pad(self_value, ((0, 0), (0, pad_d)))[:, None, :]  # [E, 1, dpad]
    mp = mask.astype(jnp.float32)[:, :, None]  # [E, n, 1]
    dp = d + pad_d
    grid = (e, dp // block_d)
    out = pl.pallas_call(
        functools.partial(_kernel, b=b),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n, block_d), lambda ei, i: (ei, 0, i)),
            pl.BlockSpec((1, n, 1), lambda ei, i: (ei, 0, 0)),
            pl.BlockSpec((1, 1, block_d), lambda ei, i: (ei, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_d), lambda ei, i: (ei, 0, i)),
        out_shape=jax.ShapeDtypeStruct((e, 1, dp), values.dtype),
        interpret=interpret,
    )(vp, mp, sp)
    out = out[:, 0, :d]
    return out[0] if squeeze else out
