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

from bench import layout
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


# ---------------------------------------------------------------------------
# the checks as first written, kept as the plain reference: the global
# matrices by one transpose of the stacks, HPL's residual one block row at
# a time with whole-slab float64 casts, PTRANS's B + A^T on the global
# matrices. The checks the runs make now give PTRANS's number exactly and
# HPL's to rounding: BLAS's float64 products can differ in the last bit
# with the alignment of the host buffers they read
# ---------------------------------------------------------------------------

HPL_REL = 1e-12


def plain_undistribute(shards, pg, b):
    _, m, _ = shards.shape
    lb, n = m // b, m * pg
    x = shards.reshape(pg, pg, lb, b, lb, b)
    return x.transpose(2, 0, 3, 4, 1, 5).reshape(n, n)


def plain_solve(lu, rhs, lower, step=2048):
    from scipy.linalg import solve_triangular
    n = lu.shape[0]
    y = rhs.copy()
    starts = range(0, n, step) if lower else range((n - 1) // step * step,
                                                   -1, -step)
    for i in starts:
        j = min(i + step, n)
        if lower and i:
            y[i:j] -= lu[i:j, :i].astype(np.float64) @ y[:i]
        elif not lower and j < n:
            y[i:j] -= lu[i:j, j:].astype(np.float64) @ y[j:]
        y[i:j] = solve_triangular(lu[i:j, i:j].astype(np.float64), y[i:j],
                                  lower=lower, unit_diagonal=lower,
                                  check_finite=False)
    return y


def plain_residual(a, lu, step=2048):
    n = a.shape[0]
    rhs = np.empty(n)
    a_norm = 0.0
    for i in range(0, n, step):
        blk = a[i:i + step]
        rhs[i:i + step] = blk.sum(1, dtype=np.float64)
        a_norm = max(a_norm, float(np.abs(blk).sum(1, dtype=np.float64).max()))
    x = plain_solve(lu, plain_solve(lu, rhs, True, step), False, step)
    delta = (x - 1.0).astype(np.float32)
    r = np.concatenate([a[i:i + step] @ delta for i in range(0, n, step)])
    eps = float(np.finfo(np.float32).eps)
    return float(np.abs(r).max()
                 / (eps * (a_norm * np.abs(x).max() + np.abs(rhs).max()) * n))


def plain_backward_error(a, lu, rows, cols):
    t = np.arange(a.shape[0])
    lr = np.where(t[None, :] < rows[:, None],
                  lu[rows, :].astype(np.float64), 0.0)
    lr[np.arange(len(rows)), rows] = 1.0
    uc = np.where(t[:, None] <= cols[None, :],
                  lu[:, cols].astype(np.float64), 0.0)
    err = np.abs(a[np.ix_(rows, cols)].astype(np.float64) - lr @ uc)
    return float(np.max(err / (np.abs(lr) @ np.abs(uc))))


def plain_hpl_checks(drv, st, a_stack, lu_stack):
    a = plain_undistribute(a_stack, st.pg, st.b)
    lu = plain_undistribute(lu_stack, st.pg, st.b)
    rng = np.random.default_rng([st.seed, 0x4850])
    rows = drv._rows_per_class(st.n, st.b, st.pg, rng, drv.BERR_SAMPLES)
    cols = drv._rows_per_class(st.n, st.b, st.pg, rng, drv.BERR_SAMPLES)
    return {"resid": plain_residual(a, lu),
            "berr": plain_backward_error(a, lu, rows, cols)}


def plain_max_abs_err(a_stack, b_stack, c_stack, pg, b):
    a, bm, c = (plain_undistribute(x, pg, b)
                for x in (a_stack, b_stack, c_stack))
    with np.errstate(invalid="ignore"):
        err = float(np.max(np.abs(c - (bm + a.T))))
    return err if np.isfinite(err) else float("inf")


def _values(checks):
    return {k: v for k, (v, _) in checks.items()}


def _close(got, want):
    return got.keys() == want.keys() and all(
        got[k] == pytest.approx(want[k], rel=HPL_REL, abs=0) for k in want)


def test_hpl_check_equals_the_plain_formulas_on_one_device():
    drv, st, lu = _setup("hpl.n32768.1chip")
    want = plain_hpl_checks(drv, st, np.asarray(st.a), lu)
    assert _close(_values(drv.check(st, lu)), want)


@pytest.mark.parametrize("pg", [1, 2, 3])
def test_hpl_check_equals_the_plain_formulas_over_slabs(pg):
    """An HPL-AI matrix and its float32 LU (no pivot is taken), two slabs
    of the residual's block rows, on P x P stacks."""
    from scipy.linalg import lu_factor
    drv = resolve("hpl.n32768.1chip").driver
    b, n = 128, 2304 if pg == 3 else 2560
    rng = np.random.default_rng(pg)
    a = rng.uniform(-0.5, 0.5, (n, n)).astype(np.float32)
    a[np.arange(n), np.arange(n)] += n
    lu, piv = lu_factor(a, check_finite=False)
    assert (piv == np.arange(n)).all()
    stacks = [layout.distribute(x, pg, b) for x in (a, lu.astype(np.float32))]
    st = drv.State(n=n, b=b, pg=pg, seed=SEED + pg, a=stacks[0],
                   limits={"resid": 16.0, "berr": 5e-6})
    got = _values(drv.check(st, stacks[1]))
    assert _close(got, plain_hpl_checks(drv, st, *stacks))
    assert got["resid"] > 0 and got["berr"] > 0


@pytest.mark.parametrize("pg,n,b", [(1, 768, 128), (2, 1536, 128),
                                    (3, 2304, 128), (2, 1280, 128)])
def test_ptrans_check_equals_the_plain_formula(pg, n, b):
    """On P x P stacks, tiles of the check's size and ragged ones: exact,
    one element a unit in the last place off, one not a number."""
    drv = resolve("ptrans.n28672.1chip").driver
    rng = np.random.default_rng(n)
    m = n // pg
    a, bm = (rng.standard_normal((pg * pg, m, m), dtype=np.float32)
             for _ in range(2))
    c = layout.distribute(plain_undistribute(bm, pg, b)
                          + plain_undistribute(a, pg, b).T, pg, b)
    st = drv.State(n=n, b=b, pg=pg, seed=1, a=a, bm=bm,
                   limits={"max_abs_err": 0.0})
    for where, value in [(None, None), ((pg * pg - 1, m - 1, 3), "ulp"),
                         ((0, m // 2, m - 1), np.nan)]:
        bad = c.copy()
        if where:
            bad[where] = (np.nextafter(bad[where], np.float32(np.inf))
                          if value == "ulp" else value)
        st.host = None
        got = drv.check(st, bad)["max_abs_err"][0]
        assert got == plain_max_abs_err(a, bm, bad, pg, b)
        assert (got == 0.0) == (where is None)
        assert drv.control_check(st, plain_undistribute(bad, pg, b))[
            "max_abs_err"][0] == got


CHECKS_ON_FOUR = """
import sys
sys.path[:0] = [{root!r}, {src!r}, {here!r}]
import jax, numpy as np
from bench.harness import prng_key, resolve
from bench_cases import spec_with_2x2
from test_bench_checks import _close, plain_hpl_checks, plain_max_abs_err
for name in ("hpl.n32768.2x2", "ptrans.n28672.1chip"):
    c = resolve(name, tiny=True, spec=spec_with_2x2())
    if c.chips == 1:     # PTRANS's 1x1 mix, on a 2x2 grid of the same n
        from dataclasses import replace
        c.size = replace(c.size, grid=2)
        c.chips = 4
    drv = c.driver
    st = drv.setup(c, drv.mesh(c.size, jax.devices()[:4]), prng_key(9), 9)
    out = drv.call(st)
    stacks = [np.asarray(x) for x in (st.a, getattr(st, "bm", None), out)
              if x is not None]
    got = {{k: v for k, (v, _) in drv.check(st, out).items()}}
    if name.startswith("hpl"):
        want = plain_hpl_checks(drv, st, stacks[0], stacks[1])
    else:
        want = {{"max_abs_err": plain_max_abs_err(*stacks, 2, c.size.b)}}
    print("EQUAL", name, _close(got, want), got)
"""


def test_checks_equal_the_plain_formulas_on_four_devices():
    """The program's outputs on a 2x2 torus of (virtual) devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHECKS_ON_FOUR.format(root=str(ROOT), src=str(ROOT / "src"),
                                 here=str(ROOT / "tests" / "bench"))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("EQUAL")]
    assert len(lines) == 2 and all(ln.split()[2] == "True" for ln in lines), \
        lines
