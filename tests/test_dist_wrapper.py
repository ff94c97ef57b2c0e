"""Launches the distributed suite (tests/dist) in a fresh interpreter with 8
placeholder CPU devices. The device-count flag must be set before JAX starts
and would change every other test, so the main pytest process keeps one
device and the multi-device tests get a process of their own."""
from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_distributed_suite():
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        REPRO_DIST_TESTS="1",
        PYTHONPATH=os.pathsep.join(
            [os.path.join(REPO, "src"), os.environ.get("PYTHONPATH", "")]),
    )
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.join(REPO, "tests", "dist"),
         "-q", "--no-header", "-p", "no:cacheprovider"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=3000)
    tail = proc.stdout[-4000:] + "\n" + proc.stderr[-2000:]
    assert proc.returncode == 0, f"distributed suite failed:\n{tail}"
    print(proc.stdout.strip().splitlines()[-1])
