"""A run reports the time of each of its phases on standard error, and
``bench/wall.py`` adds the wall time to the result line and to the exit,
which the run cannot see itself."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import scopes
from bench import trace as tr
from bench.harness import ROOT, resolve, run
from bench.peaks import Peaks
from test_bench_scopes import OPS, PATHS, SPANS, _hlo

SETUP = ["setup", "imports", "devices", "data", "warmup"]
CHECKS = {"hpl.n32768.1chip": ["check.copy", "check.resid",
                               "check.berr"],
          "ptrans.n28672.1chip": ["check.copy", "check.max_abs_err"]}


@pytest.mark.parametrize("cell", sorted(CHECKS))
def test_wall_reports_every_phase_of_a_run(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "wall.py"), "--workload", cell,
         "--seed", "2147483659", "--seconds", "0.3", "--tiny"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    *_, result, wall = proc.stdout.strip().splitlines()
    assert json.loads(result)["correct"] is True
    assert wall.startswith("wall ")
    w = json.loads(wall[len("wall "):])
    assert w["rc"] == 0
    assert 0 < w["result_s"] <= w["exit_s"]
    assert w["tail_s"] == pytest.approx(w["exit_s"] - w["result_s"])
    assert set(SETUP + ["window", "check"] + CHECKS[cell]) <= set(w["phases"])
    # the run's own clock ends where the result line is made
    to_result = [float(ln.split("=")[1]) for ln in proc.stderr.splitlines()
                 if ln.startswith("bench to_result_s=")]
    assert len(to_result) == 1 and to_result[0] <= w["result_s"]


def test_a_traced_run_reports_the_trace_phases(monkeypatch, capfd):
    """The trace's phases, on a hand-made trace in place of the device's:
    the profiler's start and stop, the load, busy time, the scope map, each
    per-layer metric and the breakdown."""
    import bench.peaks
    monkeypatch.setattr(tr, "load", lambda d: tr.Trace(
        ops=OPS, spans=SPANS, window=(0, 100)))
    monkeypatch.setattr(scopes, "program_hlo", lambda c: _hlo(PATHS))
    monkeypatch.setattr(bench.peaks, "peaks_for",
                        lambda kind: Peaks(flops=1e18, hbm_bw=1e12,
                                           hbm_bytes=1))
    cell = resolve("hpl.n32768.1chip", tiny=True)
    result = run(cell, seed=3, seconds=0.2, trace=True, tiny=True,
                 t_start=time.perf_counter())
    assert result["correct"] is True
    assert set(result["metrics"]) == {m["name"] for m in cell.per_layer}
    err = capfd.readouterr().err
    names = {ln.split()[2].split("_s=")[0] for ln in err.splitlines()
             if ln.startswith("bench phase ")}
    want = {"start_trace", "window", "stop_trace", "trace.load",
            "trace.busy", "trace.breakdown", "check"}
    want |= {f"metric.{m['name']}" for m in cell.per_layer}
    assert want <= names
    assert "bench scopes_s=" in err
