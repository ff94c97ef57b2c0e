"""The checks that decide ``correct``, at toy sizes on the CPU (kernels
interpreted): each passes on the program's output and fails on a corrupted
or lower-precision one, and the control (the plain reference one precision
below the configuration's, in the program's place) fails."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench.harness import ROOT, prng_key, resolve
from repro.launch.mesh import make_mesh

SEED = 2 ** 32 + 11


def _passes(checks):
    return all(np.isfinite(v) and v <= lim for v, lim in checks.values())


def _setup(name):
    cell = resolve(name, tiny=True)
    mesh = make_mesh((1, 1), ("rows", "cols"), devices=jax.devices()[:1])
    st = cell.driver.setup(cell, mesh, prng_key(SEED), SEED)
    return cell.driver, st, np.asarray(cell.driver.call(st))


@pytest.fixture(scope="module")
def hpl():
    return _setup("hpl.n32768.1chip")


@pytest.fixture(scope="module")
def ptrans():
    return _setup("ptrans.n28672.1chip")


def test_hpl_check_passes_on_the_programs_lu(hpl):
    drv, st, lu = hpl
    assert _passes(drv.check(st, lu))


def test_hpl_check_fails_on_a_corrupted_lu(hpl):
    drv, st, lu = hpl
    bad = lu.copy()
    bad[0, 100, 37] += 1.0
    assert not _passes(drv.check(st, bad))


def test_hpl_check_fails_on_a_bf16_rounded_lu(hpl):
    drv, st, lu = hpl
    rounded = np.asarray(jax.numpy.asarray(lu).astype(jax.numpy.bfloat16)
                         .astype(jax.numpy.float32))
    checks = drv.check(st, rounded)
    assert not _passes(checks)


def test_hpl_control_fails_and_the_reference_passes(hpl):
    drv, st, _ = hpl
    control = drv.control_check(st, drv.control(st))
    assert checks_fail_on(control, "berr")
    assert _passes(drv.control_check(st, drv.reference(st)))


def checks_fail_on(checks, name):
    v, lim = checks[name]
    return not v <= lim


def test_ptrans_check_passes_and_fails_on_a_corrupted_c(ptrans):
    drv, st, c = ptrans
    assert drv.check(st, c)["max_abs_err"][0] == 0.0
    bad = c.copy()
    bad[0, 3, 5] = np.nextafter(bad[0, 3, 5], np.float32(np.inf))
    assert not _passes(drv.check(st, bad))


def test_ptrans_control_fails(ptrans):
    drv, st, _ = ptrans
    assert not _passes(drv.control_check(st, drv.control(st)))


@pytest.mark.parametrize("cell", ["hpl.n32768.1chip", "ptrans.n28672.1chip"])
def test_tiny_rehearsal_runs_its_check(cell):
    """The whole command at toy sizes: a correct run, no device metric."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", cell,
         "--seed", str(SEED), "--seconds", "0.3", "--tiny"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert "metrics" not in result and result["checks"]
    # the compared numbers are the last lines of standard error
    last = proc.stderr.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") for line in last)


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "ptrans.n28672.1chip", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
