"""Screening-kernel throughput: Pallas (interpret on CPU; compiled on TPU)
vs the pure-jnp oracle, swept over model dimension d.

Emits ``BENCH_kernels.json`` for the CI regression gate (the jnp-oracle
timings are the gated hot path — they are what `repro.core.screening`
actually runs on CPU; the interpret-mode Pallas rows are recorded for
context but deliberately keyed so the gate ignores them, since interpreter
speed is not a property of the kernel).

    PYTHONPATH=src python -m benchmarks.kernels_bench
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(_ROOT, "BENCH_kernels.json")


def _time(fn, *args, reps=3):
    """(steady us/call, compile seconds): the warm-up call's excess over a
    cached call is the trace+compile cost."""
    t0 = time.perf_counter()
    fn(*args).block_until_ready()
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        fn(*args).block_until_ready()
    us = (time.perf_counter() - t0) / reps * 1e6
    return us, max(first_s - us / 1e6, 0.0)


def kernel_throughput(n=25, b=2, dims=(4096, 65536, 1048576)):
    rows = []
    record = {}
    rng = np.random.default_rng(0)
    compile_total = steady_total = 0.0
    for d in dims:
        vals = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        mask = jnp.ones((n,), bool)
        sv = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
        us_ref, c_ref = _time(jax.jit(lambda v, m, s: ref.trimmed_mean_ref(v, m, s, b)), vals, mask, sv)
        mbs = n * d * 4 / (us_ref / 1e6) / 1e6
        rows.append((f"kernel/trimmed_mean_ref/d{d}", us_ref, f"MB_s={mbs:.0f}"))
        record[f"trimmed_mean_ref_d{d}"] = {"us_per_call": us_ref, "mb_per_s": mbs}
        us_med, c_med = _time(jax.jit(lambda v, m: ref.median_ref(v, m)), vals, mask)
        rows.append((f"kernel/median_ref/d{d}", us_med, ""))
        record[f"median_ref_d{d}"] = {"us_per_call": us_med}
        compile_total += c_ref + c_med
        steady_total += (us_ref + us_med) / 1e6
        if d <= 65536:  # interpret mode is python-speed; keep it bounded
            us_pl, _ = _time(
                lambda v=vals, m=mask, s=sv: ops.trimmed_mean(v, m, s, b, block_d=512, interpret=True),
                reps=1,
            )
            rows.append((f"kernel/trimmed_mean_pallas_interp/d{d}", us_pl,
                         "interpret=True (TPU target)"))
            # interpreter speed is environment, not kernel, quality: keyed
            # so the regression gate's metric discovery skips it
            record[f"trimmed_mean_pallas_interp_d{d}"] = {"interp_us": us_pl}
    with open(BENCH_JSON, "w") as f:
        json.dump({"kernels": record,
                   "config": {"n": n, "b": b, "dims": list(dims),
                              "backend": jax.default_backend()},
                   # total across the gated jnp-oracle calls (interpret-mode
                   # rows excluded); compile_s is never gated
                   "compile_s": compile_total,
                   "steady_state_s": steady_total},
                  f, indent=2, sort_keys=True)
    return rows


def main(argv=None):
    del argv
    print("name,us_per_call,derived")
    for name, us, derived in kernel_throughput():
        print(f"{name},{us:.1f},{derived}", flush=True)
    print(f"wrote {BENCH_JSON}")


if __name__ == "__main__":
    main()
