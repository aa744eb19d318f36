"""Fused Pallas gather -> (dequantize ->) screen kernels — the sparse hot path.

On the neighbor-indexed layout (`repro.core.neighbors`) screening node j
means: gather its K in-neighbor rows from the ``[M, d]`` broadcast matrix (or
its ``[M, P]`` int8 codeword bank), decode them, and reduce coordinate-wise.
The staged jnp pipeline materializes the gathered ``[M, K, d]`` float tensor
in HBM just to immediately reduce it; these kernels instead gather the K rows
*inside the VMEM block* with dynamic row slices, dequantize in-register, and
screen in the same pass — one kernel launch per coordinate block, and neither
``[M, M, d]`` nor ``[M, K, d]`` ever exists.

Layout per grid step ``(j, i)``: the whole value bank's rows for coordinate
block ``i`` sit in VMEM (``[M, block_d]`` — f32 at block_d=512 and M=512 is
1 MB, comfortably inside VMEM), the flattened ``[M * K]`` neighbor table
sits in SMEM (scalar prefetch), and K unrolled ``pl.ds`` row loads at node
j's indices build the ``[K, block_d]`` neighborhood.  K is static and small
(the whole point of the sparse layout), so the unrolled gather is a handful
of sublane moves.  Packed int8 tiles admit no single-row dynamic load, so
the codeword kernels first widen the bank block to f32 in a VMEM scratch.

The correctness anchors are the staged paths: ``gather -> screening rule``
(pure jnp, `repro.core.screening`) for the f32 kernels and ``gather ->
`repro.kernels.dequant_screen` `` for the codeword kernels; the tests assert
exact agreement and ``benchmarks/scale_bench.py`` times fused vs staged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.comm.codec import SCALE_BLOCK
from repro.kernels.dequant_screen import _dequant_rows, scale_blocks
from repro.kernels.median import _median_block, _with_self
from repro.kernels.trimmed_mean import _trimmed_mean_block

_INF = float("inf")


def _gather_rows(w_ref, idx_ref, k: int):
    """K unrolled dynamic row loads: ``[K, blk]`` neighborhood of this node
    (``idx_ref`` is the flattened ``[M * K]`` table, prefetched into SMEM)."""
    base = pl.program_id(0) * k
    rows = [w_ref[pl.ds(idx_ref[base + kk], 1), :] for kk in range(k)]
    return jnp.concatenate(rows, axis=0)


def _gather_scales(scale_ref, idx_ref, k: int):
    """The gathered rows' ``[2, K, sb]`` scale/zero columns."""
    base = pl.program_id(0) * k
    return jnp.concatenate(
        [scale_ref[0, :, pl.ds(idx_ref[base + kk], 1), :] for kk in range(k)], axis=1)


def _gtm_kernel(idx_ref, valid_ref, w_ref, self_ref, out_ref, *, b: int, k: int):
    v = _gather_rows(w_ref, idx_ref, k)  # [K, blk]
    v = jnp.where(jnp.isnan(v), _INF, v)
    out_ref[0] = _trimmed_mean_block(v, valid_ref[0], self_ref[0], b)


def _gmed_kernel(idx_ref, valid_ref, w_ref, self_ref, out_ref, *, k: int):
    v = _gather_rows(w_ref, idx_ref, k)
    v = jnp.where(jnp.isnan(v), _INF, v)
    out_ref[0] = _median_block(*_with_self(v, valid_ref[0], self_ref[0]))


def _gather_codes(q_ref, qf_ref, idx_ref, k: int):
    """Gather int8 code rows as exact f32: the packed int8 tile admits no
    dynamic single-row load, so the bank block is widened into an f32 VMEM
    scratch first."""
    qf_ref[...] = q_ref[...].astype(jnp.float32)
    return _gather_rows(qf_ref, idx_ref, k)  # [K, blk]


def _gdq_tm_kernel(idx_ref, valid_ref, q_ref, scale_ref, self_ref, out_ref, qf_ref, *,
                   b: int, k: int):
    q = _gather_codes(q_ref, qf_ref, idx_ref, k)
    v = _dequant_rows(q, _gather_scales(scale_ref, idx_ref, k))  # guarded f32 [K, blk]
    out_ref[0] = _trimmed_mean_block(v, valid_ref[0], self_ref[0], b)


def _gdq_med_kernel(idx_ref, valid_ref, q_ref, scale_ref, self_ref, out_ref, qf_ref, *,
                    k: int):
    q = _gather_codes(q_ref, qf_ref, idx_ref, k)
    v = _dequant_rows(q, _gather_scales(scale_ref, idx_ref, k))
    out_ref[0] = _median_block(*_with_self(v, valid_ref[0], self_ref[0]))


def _prep(idx, valid, m: int, d: int, block_d: int):
    if idx.ndim != 2 or idx.shape != valid.shape or idx.shape[0] != m:
        raise ValueError(f"idx/valid must be [M={m}, K], got {idx.shape} / {valid.shape}")
    k = idx.shape[1]
    # padded slots (sentinel index M) are clamped to a real row and killed by
    # the valid mask — same contract as NeighborTable.safe_idx
    idx = jnp.minimum(idx.astype(jnp.int32), m - 1).reshape(-1)
    pad_d = (-d) % block_d
    return k, idx, valid.astype(jnp.float32)[:, :, None], pad_d


def _kernel_for(rule: str, tm_kernel, med_kernel, b: int, k: int):
    if rule == "trimmed_mean":
        return functools.partial(tm_kernel, b=b, k=k)
    if rule == "median":
        return functools.partial(med_kernel, k=k)
    raise ValueError(f"rule must be trimmed_mean|median, got {rule!r}")


def _call(kernel, m: int, k: int, dp: int, block_d: int, bank_specs, interpret: bool,
          scratch_shapes=()):
    """One grid step per (node j, coordinate block i).  The flattened
    neighbor table rides in SMEM (scalar prefetch) so the row loads can take
    dynamic offsets; per-node rows use a ``[M, 1, d]`` layout so their blocks
    obey the TPU block-shape rule."""
    row = pl.BlockSpec((1, 1, block_d), lambda j, i, idx: (j, 0, i))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m, dp // block_d),
            in_specs=[pl.BlockSpec((1, k, 1), lambda j, i, idx: (j, 0, 0)),
                      *bank_specs, row],
            out_specs=row,
            scratch_shapes=scratch_shapes,
        ),
        out_shape=jax.ShapeDtypeStruct((m, 1, dp), jnp.float32),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("b", "rule", "block_d", "interpret"))
def gather_screen_pallas(
    w: jax.Array,
    idx: jax.Array,
    valid: jax.Array,
    self_vals: jax.Array,
    b: int,
    *,
    rule: str = "trimmed_mean",
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Fused gather->screen over float values: ``w [M, d]`` stacked broadcast
    rows, ``idx/valid [M, K]`` the neighbor table, ``self_vals [M, d]`` the
    (never-gathered) own iterates -> ``[M, d]`` screened outputs.  ``rule``
    is ``trimmed_mean`` (BRIDGE-T) or ``median`` (BRIDGE-M)."""
    m, d = w.shape
    k, idx, validf, pad_d = _prep(idx, valid, m, d, block_d)
    wp = jnp.pad(w.astype(jnp.float32), ((0, 0), (0, pad_d)))
    sp = jnp.pad(self_vals.astype(jnp.float32), ((0, 0), (0, pad_d)))[:, None, :]
    dp = d + pad_d
    kernel = _kernel_for(rule, _gtm_kernel, _gmed_kernel, b, k)
    bank = [pl.BlockSpec((m, block_d), lambda j, i, idx: (0, i))]
    out = _call(kernel, m, k, dp, block_d, bank, interpret)(idx, validf, wp, sp)
    return out[:, 0, :d]


@functools.partial(jax.jit, static_argnames=("b", "rule", "block_d", "interpret"))
def gather_dequant_screen_pallas(
    q: jax.Array,
    scale: jax.Array,
    idx: jax.Array,
    valid: jax.Array,
    self_vals: jax.Array,
    b: int,
    *,
    rule: str = "trimmed_mean",
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Fused gather->dequantize->screen over int8 codewords: ``q [M, d]``
    int8 codes + ``scale [M, S, 2]`` per-`SCALE_BLOCK` affine pairs (the
    `repro.comm` wire layout), gathered per node through ``idx/valid [M, K]``
    and screened against the uncompressed ``self_vals [M, d]`` -> ``[M, d]``.
    Neither the decoded float bank nor the gathered neighborhood tensor ever
    reaches HBM."""
    if block_d % SCALE_BLOCK:
        raise ValueError(f"block_d must be a multiple of {SCALE_BLOCK}, got {block_d}")
    m, d = q.shape
    k, idx, validf, pad_d = _prep(idx, valid, m, d, block_d)
    qp = jnp.pad(q, ((0, 0), (0, pad_d)))
    scp = scale_blocks(scale, (d + pad_d) // SCALE_BLOCK, block_d)  # [nb, 2, M, sb]
    sp = jnp.pad(self_vals.astype(jnp.float32), ((0, 0), (0, pad_d)))[:, None, :]
    dp = d + pad_d
    sb = block_d // SCALE_BLOCK
    kernel = _kernel_for(rule, _gdq_tm_kernel, _gdq_med_kernel, b, k)
    bank = [pl.BlockSpec((m, block_d), lambda j, i, idx: (0, i)),
            pl.BlockSpec((1, 2, m, sb), lambda j, i, idx: (i, 0, 0, 0))]
    out = _call(kernel, m, k, dp, block_d, bank, interpret,
                [pltpu.VMEM((m, block_d), jnp.float32)])(idx, validf, qp, scp, sp)
    return out[:, 0, :d]
