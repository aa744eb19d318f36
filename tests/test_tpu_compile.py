"""Ahead-of-time v5e compiles of every Pallas kernel (``interpret=False``).

Nothing runs: each kernel is lowered and compiled for one chip of a
described ``v5e:2x2`` topology, which the installed TPU compiler accepts
without a chip attached.  The interpret-mode tests cannot see what Mosaic
refuses (block shapes off the (8, 128) tiling, dynamic loads from packed
int8 tiles, unsupported vector casts); these can, at the shapes the chip
runs: n = 17 rows over d = 65,536 coordinates, and the gather kernels at
M = 128 nodes, K = 16 neighbors, d = 7850 (the paper's linear model).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and it keeps it until
it exits, so the tests of this file stay together in this one file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.comm.codec import SCALE_BLOCK
from repro.kernels.dequant_screen import (
    dequant_median_pallas,
    dequant_pallas,
    dequant_trimmed_mean_pallas,
)
from repro.kernels.gather_screen import gather_dequant_screen_pallas, gather_screen_pallas
from repro.kernels.krum import pairwise_sq_dists_pallas
from repro.kernels.median import median_pallas
from repro.kernels.trimmed_mean import trimmed_mean_pallas

N, D, B = 17, 65536, 2
M, K, GD = 128, 16, 7850


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _cases():
    f32, i32, i8, b = jnp.float32, jnp.int32, jnp.int8, jnp.bool_
    s, gs = D // SCALE_BLOCK, -(-GD // SCALE_BLOCK)
    return {
        "trimmed_mean": (lambda v, m, sv: trimmed_mean_pallas(v, m, sv, B),
                         [((N, D), f32), ((N,), b), ((D,), f32)]),
        "median": (lambda v, m: median_pallas(v, m), [((N, D), f32), ((N,), b)]),
        "pairwise_sq_dists": (lambda v: pairwise_sq_dists_pallas(v), [((N, D), f32)]),
        "dequant": (lambda q, sc: dequant_pallas(q, sc), [((N, D), i8), ((N, s, 2), f32)]),
        "dequant_trimmed_mean": (
            lambda q, sc, m, sv: dequant_trimmed_mean_pallas(q, sc, m, sv, B),
            [((N, D), i8), ((N, s, 2), f32), ((N,), b), ((D,), f32)]),
        "dequant_median": (
            lambda q, sc, m, sv: dequant_median_pallas(q, sc, m, sv),
            [((N, D), i8), ((N, s, 2), f32), ((N,), b), ((D,), f32)]),
        "gather_screen_trimmed_mean": (
            lambda w, i, v, sv: gather_screen_pallas(w, i, v, sv, B),
            [((M, GD), f32), ((M, K), i32), ((M, K), b), ((M, GD), f32)]),
        "gather_screen_median": (
            lambda w, i, v, sv: gather_screen_pallas(w, i, v, sv, B, rule="median"),
            [((M, GD), f32), ((M, K), i32), ((M, K), b), ((M, GD), f32)]),
        "gather_dequant_screen_trimmed_mean": (
            lambda q, sc, i, v, sv: gather_dequant_screen_pallas(q, sc, i, v, sv, B),
            [((M, GD), i8), ((M, gs, 2), f32), ((M, K), i32), ((M, K), b), ((M, GD), f32)]),
        "gather_dequant_screen_median": (
            lambda q, sc, i, v, sv: gather_dequant_screen_pallas(q, sc, i, v, sv, B,
                                                                 rule="median"),
            [((M, GD), i8), ((M, gs, 2), f32), ((M, K), i32), ((M, K), b), ((M, GD), f32)]),
    }


@pytest.mark.parametrize("name", list(_cases()))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = _cases()[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
