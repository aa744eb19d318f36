"""Public jit'd wrappers for the screening kernels.

``use_pallas`` selects the Pallas TPU path vs the pure-jnp reference; both
produce identical results.  The Pallas path compiles for the TPU unless the
caller passes ``interpret=True`` (the CPU tests and benchmarks do): nothing
here picks interpret mode from the backend, so a run without a chip fails
instead of silently timing the Pallas interpreter.

Every entry point runs under a ``jax.named_scope`` (``kernels.<name>``) so
``jax.profiler`` captures (``--profile`` on the launch CLIs) attribute
device time to the kernel, not to an anonymous fusion.  Named scopes are
op-metadata only — they never change the computed values.
"""
from __future__ import annotations


import jax

from repro.kernels import ref
from repro.kernels.dequant_screen import (
    dequant_median_pallas,
    dequant_pallas,
    dequant_trimmed_mean_pallas,
)
from repro.kernels.krum import pairwise_sq_dists_pallas
from repro.kernels.median import median_pallas
from repro.kernels.trimmed_mean import trimmed_mean_pallas


def trimmed_mean(values, mask, self_value, b: int, *, use_pallas: bool = True, **kw):
    with jax.named_scope("kernels.trimmed_mean"):
        if use_pallas:
            return trimmed_mean_pallas(values, mask, self_value, b, **kw)
        return ref.trimmed_mean_ref(values, mask, self_value, b)


def median(values, mask, *, use_pallas: bool = True, **kw):
    with jax.named_scope("kernels.median"):
        if use_pallas:
            return median_pallas(values, mask, **kw)
        return ref.median_ref(values, mask)


def pairwise_sq_dists(stacked, *, use_pallas: bool = True, **kw):
    with jax.named_scope("kernels.pairwise_sq_dists"):
        if use_pallas:
            return pairwise_sq_dists_pallas(stacked, **kw)
        return ref.pairwise_sq_dists_ref(stacked)


def dequant(q, scale, *, use_pallas: bool = True, **kw):
    """Decode int8 codewords to float32 (stage 1 of the unfused pipeline)."""
    with jax.named_scope("kernels.dequant"):
        if use_pallas:
            return dequant_pallas(q, scale, **kw)
        return ref.dequant_ref(q, scale)


def dequant_trimmed_mean(q, scale, mask, self_value, b: int, *, use_pallas: bool = True, **kw):
    """Fused dequantize->trimmed-mean over int8 neighbor codewords."""
    with jax.named_scope("kernels.dequant_trimmed_mean"):
        if use_pallas:
            return dequant_trimmed_mean_pallas(q, scale, mask, self_value, b, **kw)
        return ref.dequant_trimmed_mean_ref(q, scale, mask, self_value, b)


def dequant_median(q, scale, mask, self_value, *, use_pallas: bool = True, **kw):
    """Fused dequantize->median over int8 neighbor codewords (self joins
    uncompressed)."""
    with jax.named_scope("kernels.dequant_median"):
        if use_pallas:
            return dequant_median_pallas(q, scale, mask, self_value, **kw)
        return ref.dequant_median_ref(q, scale, mask, self_value)


# ---------------------------------------------------------------------------
# static-analysis contracts (checked by `python -m repro.analysis`)
# ---------------------------------------------------------------------------

from repro.analysis.contracts import Contract  # noqa: E402  (dependency-light)

CONTRACTS: tuple[Contract, ...] = (
    Contract(
        "kernels.dispatch.ref_twin", "lint",
        "every public kernel dispatcher routes to BOTH a `_pallas` "
        "implementation and a `ref.` twin — the parity surface that lets "
        "interpret-mode CPU CI stand in for the TPU path",
        params=(("check", "kernel_ref_twins"), ("module", "repro.kernels.ops")),
    ),
)
