"""The model families (`bench.models`): what the cells read stays pinned, and
a new family comes in as a new module file alone."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from bench import common, models, weights, work
from bench.tests import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _cfg(name):
    return common.load_json(os.path.join(common.BENCH, "configs", f"{name}.json"))


def _traffic(name):
    return common.load_json(os.path.join(common.BENCH, "traffic", f"{name}.json"))


F = 0.01976423537605237  # 1 / sqrt(2560)
PLANS = {
    "mnist-linear": [((10,), 0.0), ((784, 10), 0.01)],
    "qwen3-4b-l1v8": [
        ((1, 1, 128), 0.0), ((1, 1, 128), 0.0), ((1, 1, 2560, 1024), F),
        ((1, 1, 4096, 2560), 0.015625), ((1, 1, 2560, 4096), F), ((1, 1, 2560, 1024), F),
        ((1, 1, 2560), 0.0), ((1, 1, 2560), 0.0), ((1, 1, 9728, 2560), 0.010138838820672659),
        ((1, 1, 2560, 9728), F), ((1, 1, 2560, 9728), F), ((18992, 2560), 0.02),
        ((2560, 18992), F), ((2560,), 0.0)],
}


@pytest.mark.parametrize("conf,n", [("mnist-linear", 7_850), ("qwen3-4b-l1v8", 198_172_416)])
def test_weight_count_is_pinned(conf, n):
    assert weights.count(_cfg(conf)) == n


@pytest.mark.parametrize("conf", sorted(PLANS))
def test_plan_is_pinned(conf):
    """Leaf order and (shape, scale): `weights.make_init` folds the i-th
    leaf's key from its place in this list."""
    got = jax.tree_util.tree_leaves(weights.plan(_cfg(conf)), is_leaf=weights._is_spec)
    assert got == PLANS[conf]


@pytest.mark.parametrize("conf,traffic,counts", [
    ("mnist-linear", "m128k16",
     {"flops": 128450560.0, "bytes": 28938240, "screen_bytes": 8038400, "tokens": 4096}),
    ("qwen3-4b-l1v8", "stream-t2048",
     {"flops": 7556793630720.0, "bytes": 12683067424, "screen_bytes": 6341517312,
      "tokens": 8192}),
    ("qwen3-4b-l1v8", "mesh4-t2048",
     {"flops": 7556793630720.0, "bytes": 12683067424, "screen_bytes": 6341517312,
      "tokens": 8192}),
])
def test_tick_counts_are_pinned(conf, traffic, counts):
    assert work.tick_counts(_cfg(conf), _traffic(traffic)) == counts


def test_model_is_found_by_name():
    assert models.of(_cfg("mnist-linear")).__name__ == "bench.models.linear"
    assert models.of(_cfg("qwen3-4b-l1v8")).__name__ == "bench.models.dense_lm"


NEW = "dense_lm_renamed"


@pytest.fixture
def new_family(tmp_path, monkeypatch):
    """A copy of the dense family under another name, in a directory of its
    own put on `bench.models`' search path: a family that comes in as a new
    file, with no file of the benchmark changed."""
    shutil.copy(os.path.join(common.BENCH, "models", "dense_lm.py"), tmp_path / f"{NEW}.py")
    monkeypatch.setattr(models, "__path__", list(models.__path__) + [str(tmp_path)])
    monkeypatch.setitem(tiny.TINY_LM, "model", NEW)
    yield str(tmp_path)
    sys.modules.pop(f"bench.models.{NEW}", None)


def test_new_family_runs_the_stream_path(new_family):
    out = tiny.run_tiny("stream")
    assert sys.modules[f"bench.models.{NEW}"].__file__.startswith(new_family)
    assert out["correct"], out["compared"]
    for c in out["compared"].values():
        assert c["value"] < 1e-4


def test_new_family_runs_the_mesh_path(new_family):
    """Four virtual CPU devices, in a child process of their own."""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    code = (f"import json, sys; sys.path[:0] = [{ROOT!r}]; "
            "from bench import models; from bench.tests import tiny; "
            f"models.__path__.append({new_family!r}); tiny.TINY_LM['model'] = {NEW!r}; "
            "out = tiny.run_tiny('mesh'); "
            f"print(json.dumps([sys.modules['bench.models.{NEW}'].__file__, out['correct'], "
            "out['compared']]))")
    res = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600, cwd=ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    path, correct, compared = json.loads(res.stdout.strip().splitlines()[-1])
    assert path.startswith(new_family)
    assert correct, compared
    for c in compared.values():
        assert c["value"] < 1e-4
