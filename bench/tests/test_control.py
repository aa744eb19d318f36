"""The comparison fails what it must, at sizes a test run holds.

* the control: each cell's precision lowered one step reads above the
  cell's limits (the readings on the chip at the cell's own size are in
  PERF.md; `bench/calibrate.py` makes them);
* the faults: a run whose timed path is broken underneath comes out
  ``correct`` false: a step that returns its state unchanged, half of each
  node's batch left out, and on the mesh the exchange between chips left
  out.  The harness's look for a chip is skipped (`bench.tests.tiny`); the
  rest of the run is the chip run's.
"""
import io
import json
import os
import subprocess
import sys

import pytest

from bench import calibrate
from bench.tests import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def unchanged(cls):
    """The path's driver with a step that returns the state it was given
    (the tick counter still advances, the losses are the real ones)."""

    class Unchanged(cls):
        def setup(self):
            super().setup()
            if hasattr(self, "tr"):
                real = self.tr._raw_step
                self.tr._raw_step = lambda c, st, b: (st._replace(t=st.t + 1), real(c, st, b)[1])
                self.tr._chunk_scan_fn = None
            else:  # the mesh step donates its input: keep a copy to return
                import jax
                import jax.numpy as jnp

                real = self.step
                self.step = lambda p, b, t: (lambda keep: (keep, real(p, b, t)[1]))(
                    jax.tree_util.tree_map(jnp.copy, p))
    return Unchanged


def half_batch(cls):
    """The path's driver with every node's gradient and loss taken over the
    first half of its batch."""
    import jax

    def halve(batch):
        return jax.tree_util.tree_map(lambda a: a[: a.shape[0] // 2], batch)

    class Half(cls):
        def trainer(self):  # the chunked paths: wrap the trainer's grad_fn
            make = super().trainer()

            def build(config):
                tr = make(config)
                grad = tr.grad_fn
                tr.__init__(config, lambda p, b: grad(p, halve(b)))
                return tr
            return build

        def setup(self):
            if hasattr(cls, "trainer"):
                return super().setup()
            import repro.launch.steps as steps  # the mesh path: wrap its model's loss

            orig = steps.model_api.build

            def build(cfg):
                a = orig(cfg)
                return a.__class__(a.cfg, a.init_params,
                                   lambda p, b, c: a.train_loss(p, halve(b), c),
                                   a.init_cache, a.decode_step, a.extra)
            steps.model_api.build = build
            try:
                super().setup()
            finally:
                steps.model_api.build = orig
    return Half


def no_exchange(cls):
    """The mesh driver with the gossip left out: every node keeps its own
    parameters where it should screen its neighbours'.  The step is traced
    at its first call, so the patch stays for the process (the test runs it
    in a child process of its own)."""

    class NoExchange(cls):
        def setup(self):
            import repro.launch.steps as steps

            steps.gossip_screen_params = lambda params, *a, **k: params
            super().setup()
    return NoExchange


FAULTS = {"unchanged": unchanged, "half_batch": half_batch, "no_exchange": no_exchange}


def _cls(path):
    import importlib

    return importlib.import_module(f"bench.paths.{tiny.files(path)[2]['path']}").Cell


@pytest.mark.parametrize("path,fault", [("paper", "unchanged"), ("paper", "half_batch"),
                                        ("stream", "unchanged"), ("stream", "half_batch")])
def test_fault_is_not_correct(path, fault):
    out = tiny.run_tiny(path, cell_cls=FAULTS[fault](_cls(path)))
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_exchange"])
def test_mesh_fault_is_not_correct(fault):
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    code = (f"import json, sys; sys.path[:0] = [{ROOT!r}]; "
            "from bench.tests import tiny, test_control as tc; "
            f"out = tiny.run_tiny('mesh', cell_cls=tc.FAULTS[{fault!r}](tc._cls('mesh'))); "
            "print(json.dumps([out['correct'], out['compared']]))")
    res = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600, cwd=ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    correct, compared = json.loads(res.stdout.strip().splitlines()[-1])
    assert not correct, compared


@pytest.mark.parametrize("path", ["paper", "stream"])
def test_control_reads_above_the_limits(path):
    """One step lower in precision fails one of the cell's numbers."""
    work, cfg, tr, limits = tiny.files(path)
    summary = calibrate.calibrate(work, cfg, tr, [5], {5}, out=io.StringIO())
    assert any(summary["control"][n] > limits[n] for n in limits), (summary, limits)
    assert all(summary["program"][n] <= limits[n] for n in limits), (summary, limits)
