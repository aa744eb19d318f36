"""Each cell's plain reference against the program, at small sizes on the
CPU: sound runs read far below the cells' limits."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests import tiny

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("path", ["paper", "stream", "stream-b1"])
def test_program_matches_reference(path):
    out = tiny.run_tiny(path)
    assert out["correct"], out["compared"]
    for c in out["compared"].values():
        assert c["value"] < 1e-4


def test_mesh_program_matches_reference():
    """The mesh path needs four devices: a child process with four virtual
    CPU devices (this process keeps one)."""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    code = ("import json, sys; sys.path[:0] = [%r]; from bench.tests import tiny; "
            "print(json.dumps(tiny.run_tiny('mesh')['compared']))" % os.path.dirname(
                os.path.dirname(HERE)))
    res = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600, cwd=os.path.dirname(os.path.dirname(HERE)))
    assert res.returncode == 0, res.stderr[-3000:]
    compared = json.loads(res.stdout.strip().splitlines()[-1])
    for c in compared.values():
        assert c["value"] < 1e-4
