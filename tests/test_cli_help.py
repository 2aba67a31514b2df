"""Every CLI's --help must render (a stray % in an argparse help string
raises at format time — caught here once, kept caught)."""

import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("target", [
    ["-m", "distributed_pytorch_from_scratch_tpu.train"],
    ["-m", "distributed_pytorch_from_scratch_tpu.evaluate"],
    ["bench.py"],
])
def test_help_renders(target):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, *target, "--help"],
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO_ROOT, env=env)
    assert p.returncode == 0, p.stderr[-1500:]
    assert "usage:" in p.stdout
