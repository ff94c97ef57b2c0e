"""A run with the timed path broken underneath comes out not correct: the
harness runs as on the chip (at toy sizes on the CPU, past its look for a
chip), and the fault is planted in the program. These cells have no batch,
so the fault of half a batch left out does not arise."""
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench.harness import ROOT, resolve, run


def _run(name):
    return run(resolve(name, tiny=True), seed=5, seconds=0.2, trace=False,
               tiny=True, t_start=time.perf_counter())


def test_hpl_runs_correct_without_a_fault():
    assert _run("hpl.n32768.1chip")["correct"] is True


def test_hpl_state_returned_unchanged(monkeypatch):
    import repro.core.hpl as H
    monkeypatch.setattr(H, "make_factorize",
                        lambda mesh, **kw: (lambda a: a))
    assert _run("hpl.n32768.1chip")["correct"] is False


def test_hpl_answer_altered_where_produced(monkeypatch):
    import repro.core.hpl as H
    from repro.kernels import ops
    monkeypatch.setattr(H, "lu_factor_block",
                        lambda a: ops.lu_factor_block(a).at[1, 0].add(1.0))
    assert _run("hpl.n32768.1chip")["correct"] is False


def test_ptrans_state_returned_unchanged(monkeypatch):
    import repro.core.ptrans as T
    monkeypatch.setattr(T, "make_step",
                        lambda mesh, pg, engine, nchunks=1: (lambda a, b: b))
    assert _run("ptrans.n28672.1chip")["correct"] is False


def test_ptrans_answer_altered_where_produced(monkeypatch):
    import repro.core.ptrans as T
    from repro.kernels import ops
    monkeypatch.setattr(T, "transpose_add",
                        lambda a, b: ops.transpose_add(a, b).at[3, 5].add(1.0))
    assert _run("ptrans.n28672.1chip")["correct"] is False


EXCHANGE_LEFT_OUT = """
import sys, time
sys.path[:0] = [{root!r}, {src!r}, {here!r}]
from bench.harness import resolve, run
from bench_cases import spec_with_2x2
from repro.comm.engine import CollectiveEngine
if {broken}:
    CollectiveEngine.bcast = lambda self, x, axis, root, **kw: x
r = run(resolve("hpl.n32768.2x2", tiny=True, spec=spec_with_2x2()),
        seed=5, seconds=0.2,
        trace=False, tiny=True, t_start=time.perf_counter())
print("CORRECT", r["correct"])
"""


@pytest.mark.parametrize("broken", [False, True])
def test_hpl_2x2_exchange_left_out(broken):
    """On four (virtual) devices the broadcasts between chips are what the
    factorization needs; without them the run is not correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = EXCHANGE_LEFT_OUT.format(root=str(ROOT), src=str(ROOT / "src"),
                                    here=str(Path(__file__).parent),
                                    broken=broken)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip().splitlines()[-1] == f"CORRECT {not broken}"
