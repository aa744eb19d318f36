"""Fused Pallas dequantize -> screen kernels (compressed-exchange hot path).

With an int8 wire codec (`repro.comm`), each node holds its neighbors'
*codewords*: an ``int8 [n, d]`` payload buffer plus a per-sender ``[n, 2]``
(scale, zero) dequantization pair.  The naive pipeline materializes
``float32 [n, d]`` (4x the codeword bytes) in HBM just to immediately reduce
it coordinate-wise; these kernels instead dequantize *inside the VMEM block*
and run the screening reduction in the same pass — one kernel launch, no
float32 neighbor tensor, 4x less HBM traffic on the dominant operand.  The
decode-then-screen pipeline (`repro.kernels.ops.dequant` followed by the
screening kernels, or the pure-jnp `ref` path) is the correctness anchor:
``benchmarks/comm_bench.py`` times fused vs staged and the tests assert
exact agreement.

Dequantization is the codec's affine map ``q * scale + zero`` — including
whatever a wire attack left in the scale field, so screening is exercised
against what decoders actually emit (scale abuse can produce ``inf``, and
``inf * 0`` NaNs are guarded to ``+inf`` exactly like `repro.core.screening`).

Shapes mirror the other kernels: ``q [n, d]`` int8 / ``scale [n, S, 2]``
(one affine pair per `repro.comm.codec.SCALE_BLOCK` coordinates — the codec's
wire layout) / ``mask [n]`` / ``self_value [d]`` -> ``[d]``, with an optional
leading experiment axis (``[E, n, d]`` etc.) mapped onto the first Pallas
grid dimension.  ``b`` is static; ``block_d`` must be a multiple of
`SCALE_BLOCK` so each grid step's scale slice aligns with its coordinates.
Inside the kernels the scales travel as one ``[2, n, block_d / SCALE_BLOCK]``
tile per coordinate block (`scale_blocks`), a layout whose block shape the
TPU compiler accepts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.comm.codec import SCALE_BLOCK
from repro.kernels.median import _median_block, _with_self
from repro.kernels.trimmed_mean import _trimmed_mean_block

_INF = float("inf")


def _dequant_rows(q, sz):
    """``[n, blk]`` int8 codes + ``sz [2, n, sb]`` per-`SCALE_BLOCK` scale and
    zero columns -> guarded f32 rows (sb = blk / SCALE_BLOCK).  Each
    128-lane slice takes its own column, broadcast along lanes."""
    qf = q.astype(jnp.float32)
    parts = [qf[:, t * SCALE_BLOCK:(t + 1) * SCALE_BLOCK] * sz[0][:, t:t + 1] + sz[1][:, t:t + 1]
             for t in range(sz.shape[-1])]
    v = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    # abused scales decode to inf; inf * 0 codes to NaN — guard to +inf so
    # rank-based screening trims them as maximal outliers (core.screening)
    return jnp.where(jnp.isnan(v), _INF, v)


def _dequant_kernel(q_ref, scale_ref, out_ref):
    out_ref[0] = _dequant_rows(q_ref[0], scale_ref[0, 0]).astype(out_ref.dtype)


def _fused_tm_kernel(q_ref, scale_ref, mask_ref, self_ref, out_ref, *, b: int):
    v = _dequant_rows(q_ref[0], scale_ref[0, 0])  # [n, blk]
    self_value = self_ref[0].astype(jnp.float32)  # [1, blk]
    out_ref[0] = _trimmed_mean_block(v, mask_ref[0], self_value, b).astype(out_ref.dtype)


def _fused_med_kernel(q_ref, scale_ref, mask_ref, self_ref, out_ref):
    v = _dequant_rows(q_ref[0], scale_ref[0, 0])  # [n, blk]
    # the node's own (never-compressed) iterate joins the dequantized
    # neighbor rows inside the block
    rows, valid = _with_self(v, mask_ref[0], self_ref[0].astype(jnp.float32))
    out_ref[0] = _median_block(rows, valid).astype(out_ref.dtype)


def scale_blocks(scale: jax.Array, s_need: int, block_d: int) -> jax.Array:
    """Wire-layout scales ``[..., R, S, 2]`` -> kernel layout
    ``[..., S_pad / sb, 2, R, sb]`` (sb = block_d / SCALE_BLOCK): one
    ``[2, R, sb]`` tile per coordinate block, whose trailing two dims equal
    the array's — the TPU block-shape rule.  Scale blocks are zero-padded to
    ``s_need`` so the zero-padded coordinate tail decodes to exact zeros."""
    sb = block_d // SCALE_BLOCK
    lead, (r, s) = scale.shape[:-3], scale.shape[-3:-1]
    pad = [(0, 0)] * len(lead) + [(0, 0), (0, s_need - s), (0, 0)]
    sc = jnp.pad(scale, pad).reshape(lead + (r, s_need // sb, sb, 2))
    k = len(lead)
    return jnp.transpose(sc, tuple(range(k)) + (k + 1, k + 3, k, k + 2))


def _prep(q, scale, mask, self_value, block_d):
    """Shared batching/padding: returns (squeeze, e, n, d, padded d, and the
    padded codes, scale tiles, mask and self operands)."""
    if block_d % SCALE_BLOCK:
        raise ValueError(f"block_d must be a multiple of {SCALE_BLOCK}, got {block_d}")
    squeeze = q.ndim == 2
    if squeeze:
        q, scale, mask = q[None], scale[None], mask[None]
        if self_value is not None:
            self_value = self_value[None]
    e, n, d = q.shape
    pad_d = (-d) % block_d
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_d)))
    scp = scale_blocks(scale, (d + pad_d) // SCALE_BLOCK, block_d)  # [E, nb, 2, n, sb]
    sp = None
    if self_value is not None:
        sp = jnp.pad(self_value, ((0, 0), (0, pad_d)))[:, None, :]  # [E, 1, dpad]
    mp = None if mask is None else mask.astype(jnp.float32)[:, :, None]  # [E, n, 1]
    return squeeze, e, n, d, d + pad_d, qp, scp, mp, sp


def _scale_spec(n: int, block_d: int):
    return pl.BlockSpec((1, 1, 2, n, block_d // SCALE_BLOCK), lambda ei, i: (ei, i, 0, 0, 0))


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def dequant_pallas(
    q: jax.Array,
    scale: jax.Array,
    *,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Standalone decode: ``q [n, d]`` (or ``[E, n, d]``) int8 codes +
    ``scale [n, 2]`` affine pairs -> guarded ``float32`` values.  This is the
    first stage of the *unfused* decode-then-screen pipeline the fused
    kernels are benchmarked against (it materializes the float32 tensor the
    fused path never writes)."""
    squeeze, e, n, d, dp, qp, sc, _, _ = _prep(
        q, scale, jnp.ones(q.shape[:-1], bool), None, block_d)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(e, dp // block_d),
        in_specs=[
            pl.BlockSpec((1, n, block_d), lambda ei, i: (ei, 0, i)),
            _scale_spec(n, block_d),
        ],
        out_specs=pl.BlockSpec((1, n, block_d), lambda ei, i: (ei, 0, i)),
        out_shape=jax.ShapeDtypeStruct((e, n, dp), jnp.float32),
        interpret=interpret,
    )(qp, sc)
    out = out[:, :, :d]
    return out[0] if squeeze else out


@functools.partial(jax.jit, static_argnames=("b", "block_d", "interpret"))
def dequant_trimmed_mean_pallas(
    q: jax.Array,
    scale: jax.Array,
    mask: jax.Array,
    self_value: jax.Array,
    b: int,
    *,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Fused int8-codeword trimmed-mean screening (BRIDGE-T): dequantize each
    VMEM block and screen it in one pass — ``float32 [n, d]`` never exists."""
    squeeze, e, n, d, dp, qp, sc, mp, sp = _prep(
        q, scale, mask, self_value, block_d)
    out = pl.pallas_call(
        functools.partial(_fused_tm_kernel, b=b),
        grid=(e, dp // block_d),
        in_specs=[
            pl.BlockSpec((1, n, block_d), lambda ei, i: (ei, 0, i)),
            _scale_spec(n, block_d),
            pl.BlockSpec((1, n, 1), lambda ei, i: (ei, 0, 0)),
            pl.BlockSpec((1, 1, block_d), lambda ei, i: (ei, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_d), lambda ei, i: (ei, 0, i)),
        out_shape=jax.ShapeDtypeStruct((e, 1, dp), jnp.float32),
        interpret=interpret,
    )(qp, sc, mp, sp)
    out = out[:, 0, :d]
    return out[0] if squeeze else out


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def dequant_median_pallas(
    q: jax.Array,
    scale: jax.Array,
    mask: jax.Array,
    self_value: jax.Array,
    *,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Fused int8-codeword coordinate-median screening (BRIDGE-M) over
    N_j ∪ {j}; the self row joins uncompressed inside the kernel."""
    squeeze, e, n, d, dp, qp, sc, mp, sp = _prep(
        q, scale, mask, self_value, block_d)
    out = pl.pallas_call(
        _fused_med_kernel,
        grid=(e, dp // block_d),
        in_specs=[
            pl.BlockSpec((1, n, block_d), lambda ei, i: (ei, 0, i)),
            _scale_spec(n, block_d),
            pl.BlockSpec((1, n, 1), lambda ei, i: (ei, 0, 0)),
            pl.BlockSpec((1, 1, block_d), lambda ei, i: (ei, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_d), lambda ei, i: (ei, 0, i)),
        out_shape=jax.ShapeDtypeStruct((e, 1, dp), jnp.float32),
        interpret=interpret,
    )(qp, sc, mp, sp)
    out = out[:, 0, :d]
    return out[0] if squeeze else out
