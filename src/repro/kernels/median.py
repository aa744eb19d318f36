"""Pallas TPU kernel for coordinate-wise median screening (BRIDGE-M).

Rank-by-counting instead of sorting: for each row i we count, per coordinate,
how many valid entries precede it in the (value, index) lexicographic order.
The two middle order statistics are then selected by rank equality and
averaged (even/odd cardinalities handled uniformly).  O(n^2 * blk) VPU
compares with an unrolled outer loop — n (neighbors+self) is <= a few dozen,
so this beats a bitonic sort's log^2 passes at these sizes and needs no
cross-lane shuffles.

Input rows INCLUDE the node's own value (mask row set accordingly) — the
median in Eq. (11) ranges over N_j ∪ {j}.

A leading *experiment* axis is accepted — ``values [E, n, d]``, ``mask
[E, n]`` -> ``out [E, d]`` — mapped onto the first Pallas grid dimension so
batched sweeps (`repro.sim`) screen every experiment in one launch.

Masked lanes use a ``+inf`` sentinel (matching `repro.core.screening`): a
finite sentinel mis-ranks legitimately huge payloads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INF = float("inf")


def _median_block(values, valid):
    """Median over axis 0 of one ``[n, blk]`` block under the ``[n, 1]`` float
    0/1 per-row mask.  Returns ``[1, blk]``.

    Row i's rank is accumulated for all rows at once, one neighbor j per
    unrolled step (j precedes i when ``v_j < v_i`` or, tied, ``j < i``), so
    every operand stays a full ``[n, blk]`` tile."""
    n = values.shape[0]
    count = jnp.sum(valid).astype(jnp.int32)  # cardinality (per-row mask)
    lo = (count - 1) // 2
    hi = count // 2
    rows = jax.lax.broadcasted_iota(jnp.int32, values.shape, 0)
    validb = jnp.broadcast_to(valid, values.shape)
    ok = validb > 0.5
    v = jnp.where(ok, values, _INF)
    less = jnp.zeros(values.shape, jnp.int32)
    for j in range(n):
        vj = v[j:j + 1]
        prec = (vj < v) | ((vj == v) & (rows > j))
        less = less + jnp.where(prec & (validb[j:j + 1] > 0.5), 1, 0)
    acc_lo = jnp.sum(jnp.where(ok & (less == lo), v, 0.0), axis=0, keepdims=True)
    acc_hi = jnp.sum(jnp.where(ok & (less == hi), v, 0.0), axis=0, keepdims=True)
    return 0.5 * (acc_lo + acc_hi)


def _with_self(v, valid, self_row):
    """Eq. (11) medians over N_j ∪ {j}: append the node's own ``[1, blk]``
    row (NaN-guarded, always valid) to a ``[K, blk]`` neighborhood and its
    ``[K, 1]`` mask."""
    rows = jnp.concatenate([v, jnp.where(jnp.isnan(self_row), _INF, self_row)], axis=0)
    return rows, jnp.concatenate([valid, jnp.ones((1, 1), jnp.float32)], axis=0)


def _kernel(values_ref, mask_ref, out_ref):
    values = values_ref[0].astype(jnp.float32)
    # NaN payloads -> +inf so rank-counting stays total-ordered (matches
    # repro.core.screening's guard)
    values = jnp.where(jnp.isnan(values), _INF, values)
    out_ref[0] = _median_block(values, mask_ref[0]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def median_pallas(
    values: jax.Array,
    mask: jax.Array,
    *,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Masked coordinate-wise median of ``values [n, d]`` (or ``[E, n, d]``)
    over the neighbor axis."""
    squeeze = values.ndim == 2
    if squeeze:
        values, mask = values[None], mask[None]
    e, n, d = values.shape
    pad_d = (-d) % block_d
    vp = jnp.pad(values, ((0, 0), (0, 0), (0, pad_d)))
    mp = mask.astype(jnp.float32)[:, :, None]  # [E, n, 1]
    dp = d + pad_d
    out = pl.pallas_call(
        _kernel,
        grid=(e, dp // block_d),
        in_specs=[
            pl.BlockSpec((1, n, block_d), lambda ei, i: (ei, 0, i)),
            pl.BlockSpec((1, n, 1), lambda ei, i: (ei, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_d), lambda ei, i: (ei, 0, i)),
        out_shape=jax.ShapeDtypeStruct((e, 1, dp), values.dtype),
        interpret=interpret,
    )(vp, mp)
    out = out[:, 0, :d]
    return out[0] if squeeze else out
