"""Tests of the benchmark's own arithmetic and plumbing. They run on the
CPU (``python -m pytest perfbench/tests -q``) and are not part of the
program's tier-1 suite."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

DRIVER = """
import sys, time
sys.path.insert(0, {root!r})
{patch}
from perfbench import harness
sys.exit(harness.main(time.monotonic(), {argv!r}))
"""


def run_cell(argv, patch="", devices=1, cwd=ROOT):
    """One run of the harness in a process of its own (it claims fd 1).
    Returns ``(exit code, stdout lines, stderr)``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, "-c",
         DRIVER.format(root=ROOT, patch=patch, argv=list(argv))],
        capture_output=True, text=True, env=env, cwd=cwd, timeout=900)
    return proc.returncode, proc.stdout.strip().splitlines(), proc.stderr


@pytest.fixture(scope="session")
def run():
    return run_cell


def last_json(lines):
    return json.loads(lines[-1])
