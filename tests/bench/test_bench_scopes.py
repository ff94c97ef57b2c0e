"""Device ops mapped to the program's named scopes, the reducers and
metrics that read them, and idle gaps labelled by the host's threads: on
small hand-made traces (times in ns) and on the programs compiled on the
CPU."""
import fnmatch
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import host_events, scopes
from bench import trace as tr
from bench.harness import (ROOT, MetricContext, load_benchmark, load_module,
                           resolve)
from bench.peaks import Peaks

SCOPE_METRICS = ["hpl_update_roofline", "hpl_panel_s", "hpl_bcast_exposed_s",
                 "ptrans_exchange_phase_s", "ptrans_transpose_roofline"]
BODY = "jit(_hpl_body)/shard_map/while/body/closed_call"

# HLO instruction -> op_name path, as the compiled programs give them
PATHS = {
    "gemm_update.1": f"{BODY}/hpl.update/jit(gemm_update)/pallas_call",
    "lu_factor_block.3": f"{BODY}/hpl.factor/jit(lu_factor_block)/pallas_call",
    "trsm_lower_left.2": f"{BODY}/hpl.trsm/jit(trsm_lower_left)/pallas_call",
    "collective-permute-done.2": f"{BODY}/comm.bcast:hpl.panel/ppermute",
    "all-gather.1": f"{BODY}/comm.bcast:hpl.block/all_gather",
    "fusion.3": f"{BODY}/hpl.writeback/dynamic_update_slice",
    "copy.4": "jit(_hpl_body)/shard_map/while/body/add",
}
# window [0, 100); device 0: two gemm tiles, a panel kernel, a broadcast
# that half overlaps the second gemm, a copy no scope holds; device 1: one
# gemm, a lone broadcast, a write-back
OPS = {
    0: [("gemm_update.1", 0, 30), ("gemm_update.1", 40, 70),
        ("lu_factor_block.3", 70, 75), ("collective-permute-done.2", 60, 80),
        ("copy.4", 85, 90), ("fusion.3", 90, 120)],
    1: [("gemm_update.1", 10, 50), ("all-gather.1", 50, 60),
        ("fusion.3", 60, 64), ("trsm_lower_left.2", 64, 70)],
}
SPANS = [("bench.window", 0, 100), ("bench.call", 0, 50),
         ("bench.block", 5, 50), ("bench.call", 50, 100)]


def _trace(ops=OPS):
    return tr.Trace(ops=ops, spans=SPANS, window=(0, 100))


def _scoped(ops=OPS, paths=PATHS):
    return scopes.Scoped(trace=_trace(ops), paths=paths,
                         names=scopes.registry())


def _hlo(paths):
    return "\n".join(f'  %{n} = f32[8]{{0}} custom-call(), '
                     f'metadata={{op_name="{p}" stack_frame_id=3}}'
                     for n, p in paths.items())


def test_op_paths_read_each_instructions_own_op_name():
    text = ("ENTRY %main {\n"
            '  %gemm_update.12 = f32[8,8]{1,0:T(8,128)} custom-call(%a), '
            'custom_call_target="tpu_custom_call", metadata={op_name='
            '"jit(f)/hpl.update/jit(gemm_update)/pallas_call" '
            'stack_frame_id=14}, backend_config={"a":"b"}\n'
            '  ROOT %select_dynamic-update-slice_fusion.5 = f32[8] fusion('
            '%x), kind=kLoop, calls=%fused_computation.1.clone, metadata='
            '{op_name="jit(f)/hpl.writeback/dynamic_update_slice"}\n'
            "  %copy.12 = f32[8] copy(%y)\n}")
    assert scopes.op_paths(text) == {
        "gemm_update.12": "jit(f)/hpl.update/jit(gemm_update)/pallas_call",
        "select_dynamic-update-slice_fusion.5":
            "jit(f)/hpl.writeback/dynamic_update_slice"}


@pytest.mark.parametrize("path,phase", [
    (f"{BODY}/hpl.trsm/mul", "hpl.trsm"),
    # innermost wins: the engine scope inside a phase is the engine's
    (f"{BODY}/hpl.trsm/comm.bcast:hpl.panel/ppermute",
     "comm.bcast:hpl.panel"),
    # compute nested inside an engine op (pipelined's consume) is compute
    ("jit(f)/comm.pipelined:ptrans.exchange/ptrans.transpose_add/"
     "jit(transpose_add)/pallas_call", "ptrans.transpose_add"),
    ("jit(f)/comm.pipelined:ptrans.exchange/"
     "comm.grid_transpose:ptrans.exchange/ppermute",
     "comm.grid_transpose:ptrans.exchange"),
    ("jit(f)/comm.allreduce:untagged/psum", "comm.allreduce:untagged"),
    # a name that is not registered is no scope
    ("jit(f)/hpl.other/comm.bcast@hpl.block/add", scopes.UNATTRIBUTED),
    ("jit(f)/while/body/add", scopes.UNATTRIBUTED),
])
def test_phase_of_takes_the_innermost_registered_scope(path, phase):
    assert scopes.phase_of(path, scopes.registry()) == phase


def test_phase_time_and_exposed_engine_time():
    sc = _scoped()
    assert sc.phase("copy.4") == scopes.UNATTRIBUTED
    assert sc.phase("no-such-op.1") == scopes.UNATTRIBUTED
    assert scopes.phase_ns(sc, ["hpl.update"]) == {0: 60, 1: 40}
    # the write-back fusion is clipped to the window on device 0
    assert scopes.phase_ns(sc, ["hpl.factor", "hpl.trsm"]) == {0: 5, 1: 6}
    assert scopes.phase_ns(sc, ["hpl.writeback"]) == {0: 10, 1: 4}
    bcast = ["comm.*:hpl.block", "comm.*:hpl.panel"]
    assert scopes.phase_ns(sc, bcast) == {0: 20, 1: 10}
    # device 0: [60,80) beside gemm [60,70) and lu [70,75) -> 5 exposed;
    # device 1: [50,60) alone -> 10
    assert scopes.phase_exposed_ns(sc, bcast) == {0: 5, 1: 10}
    shares = scopes.by_phase(sc)
    assert shares["unattributed"] == pytest.approx(5 / 2 / 1e9)
    # every op's own time, overlaps counted for each op
    assert sum(shares.values()) == pytest.approx((100 + 60) / 2 / 1e9)
    assert scopes.unattributed_ops(sc) == [["copy.4", 5 / 2 / 1e9]]
    # busy unions: device 0 [0,30)+[40,80)+[85,100) = 85, device 1 60
    a = scopes.attribution(sc, calls=2)
    assert a["phases"]["hpl.update"] == pytest.approx(100 / 2 / 2 / 1e9)
    assert a["attributed_pct"] == pytest.approx(100 * (1 - 2.5 / 72.5))
    assert a["unattributed_ops"] == [["copy.4", pytest.approx(5 / 4 / 1e9)]]


def _ctx(cell, monkeypatch, paths=PATHS, ops=OPS, calls=2,
         peaks=Peaks(flops=1e18, hbm_bw=1e12, hbm_bytes=1)):
    monkeypatch.setattr(scopes, "program_hlo", lambda c: _hlo(paths))
    return MetricContext(cell=cell, trace=_trace(ops), peaks=peaks,
                         calls=calls)


def _spec():
    # with the 2x2 cell, whose mix holds a 2x2 grid
    from bench_cases import spec_with_2x2
    return spec_with_2x2()


def test_scope_metrics_on_a_known_trace(monkeypatch):
    hpl = resolve("hpl.n32768.2x2", spec=_spec())
    ctx = _ctx(hpl, monkeypatch)

    def m(name):
        return load_module("metrics", name).compute(ctx)

    need = hpl.driver.required_update_flops_total(hpl.size.n, hpl.size.b)
    # the update phase holds exactly the gemm ops: the twin of the
    # name-matched roofline reads the same
    assert m("hpl_update_roofline") == pytest.approx(
        100 * (2 * need / 1e18) / (100 / 1e9))
    assert m("hpl_update_roofline") == pytest.approx(m("hpl_gemm_roofline"))
    assert m("hpl_panel_s") == pytest.approx((5 + 6) / 2 / 2 / 1e9)
    assert m("hpl_panel_s") >= m("hpl_panel_kernel_s")
    assert m("hpl_bcast_exposed_s") == pytest.approx(15 / 2 / 2 / 1e9)


def test_ptrans_scope_metrics_twin_the_name_matched_ones(monkeypatch):
    pt = resolve("ptrans.n28672.1chip")
    paths = {"collective-permute-start": "jit(_ptrans_body)/shard_map/"
             "comm.grid_transpose:ptrans.exchange/ppermute",
             "transpose_add.1": "jit(_ptrans_body)/shard_map/"
             "ptrans.transpose_add/jit(transpose_add)/pallas_call"}
    ops = {0: [("collective-permute-start", 0, 40),
               ("transpose_add.1", 40, 90)]}
    ctx = _ctx(pt, monkeypatch, paths=paths, ops=ops, calls=5,
               peaks=Peaks(flops=1, hbm_bw=1e15, hbm_bytes=1))

    def m(name):
        return load_module("metrics", name).compute(ctx)

    assert m("ptrans_exchange_phase_s") == pytest.approx(40 / 5 / 1e9)
    assert m("ptrans_exchange_phase_s") == pytest.approx(
        m("ptrans_exchange_s"))
    assert m("ptrans_transpose_roofline") == pytest.approx(
        100 * (5 * 3 * 28672 ** 2 * 4 / 1e15) / (50 / 1e9))
    assert m("ptrans_transpose_roofline") == pytest.approx(
        m("ptrans_kernel_roofline"))


def test_an_empty_exchange_scope_reads_zero(monkeypatch):
    """Once the calls ran in an instrumented program, an exchange that
    holds no device op reads 0.0, not nothing."""
    pt = resolve("ptrans.n28672.1chip")
    paths = {"transpose_add.1": "jit(f)/ptrans.transpose_add/pallas_call"}
    ops = {0: [("transpose_add.1", 0, 50)]}
    ctx = _ctx(pt, monkeypatch, paths=paths, ops=ops, calls=3)
    assert load_module("metrics", "ptrans_exchange_phase_s").compute(
        ctx) == 0.0
    ctx.calls = 0
    assert load_module("metrics", "ptrans_exchange_phase_s").compute(
        ctx) is None


@pytest.mark.parametrize("metric", SCOPE_METRICS)
def test_a_program_without_scopes_gives_nothing(metric, monkeypatch):
    """A program with no registry, as before it had one, reads nothing
    and is not compiled; one whose ops carry no registered scope reads
    nothing either."""
    cell = resolve("hpl.n32768.2x2" if metric.startswith("hpl")
                   else "ptrans.n28672.1chip", spec=_spec())
    mod = load_module("metrics", metric)

    def no_compile(c):
        raise AssertionError("compiled a program that has no registry")

    monkeypatch.setattr(scopes, "registry", lambda: None)
    monkeypatch.setattr(scopes, "program_hlo", no_compile)
    ctx = MetricContext(cell=cell, trace=_trace(), peaks=Peaks(1, 1, 1),
                        calls=2)
    assert mod.compute(ctx) is None
    monkeypatch.undo()
    bare = {n: "jit(f)/while/body/" + n.split(".")[0] for n in PATHS}
    assert mod.compute(_ctx(cell, monkeypatch, paths=bare)) is None


def test_a_failed_compile_reads_nothing(monkeypatch):
    def broken(c):
        raise RuntimeError("no executable")

    monkeypatch.setattr(scopes, "program_hlo", broken)
    ctx = MetricContext(cell=resolve("hpl.n32768.1chip"), trace=_trace(),
                        peaks=Peaks(1, 1, 1), calls=2)
    assert load_module("metrics", "hpl_panel_s").compute(ctx) is None


def test_one_compile_serves_every_metric_of_a_run(monkeypatch):
    calls = []

    def hlo(c):
        calls.append(c.name)
        return _hlo(PATHS)

    monkeypatch.setattr(scopes, "program_hlo", hlo)
    ctx = MetricContext(cell=resolve("hpl.n32768.2x2", spec=_spec()),
                        trace=_trace(), peaks=Peaks(1, 1, 1), calls=2)
    for name in ("hpl_update_roofline", "hpl_panel_s",
                 "hpl_bcast_exposed_s"):
        assert load_module("metrics", name).compute(ctx) is not None
    assert calls == ["hpl.n32768.2x2"]


def test_every_scope_a_metric_reads_is_registered():
    from repro.scopes import registered
    names = registered()
    for metric in SCOPE_METRICS:
        for pattern in load_module("metrics", metric).PHASES:
            assert any(fnmatch.fnmatchcase(n, pattern) for n in names), (
                metric, pattern)


def test_every_scope_metric_is_in_the_benchmark():
    """All but the broadcasts' exposed time, which waits for a cell on
    four chips."""
    spec = {m["name"]: m for m in load_benchmark()["per_layer"]}
    for metric in SCOPE_METRICS:
        if metric == "hpl_bcast_exposed_s":
            assert metric not in spec
            continue
        assert spec[metric]["source"] == "device_trace"
        assert spec[metric]["workloads"]


# ---------------------------------------------------------------------------
# idle gaps labelled by the host's threads
# ---------------------------------------------------------------------------

LINES = {
    "python": [("PjitFunction(f)", 0, 45), ("CommonPjRtBuffer::Await", 5, 50)],
    "tpu_runtime/1": [("ExecuteProgram", 25, 38), ("AllocateBuffer", 30, 36),
                      ("Defragment", 55, 100)],
    "tpu_runtime/2": [("HostCallback", 0, 100)],
    "empty": [],
}


def test_gap_labels_keep_the_span_and_add_the_host_threads():
    t = _trace()
    old = tr.idle_gaps(t)
    new = host_events.idle_gaps(t, LINES)
    assert [g[1] for g in new] == [g[1] for g in old]
    assert [g[0].split("|")[0] for g in new] == [g[0] for g in old]
    # device 0's [30,40) gap, middle 35: runtime threads first, shortest
    # first, two names at most; the Python thread's Await is left out
    assert host_events.label(t, LINES, 35) == \
        "bench.block|AllocateBuffer|HostCallback"
    assert host_events.label(t, LINES, 47) == \
        "bench.block|HostCallback|CommonPjRtBuffer::Await"
    assert host_events.label(t, {}, 47) == "bench.block"
    assert host_events.runtime_events(LINES, 200) == []
    # the Python tracer's line is named after the executable
    py3 = {"python3": LINES["python"], "main/561": [("ReadSyncFlag", 46, 48)]}
    assert host_events.runtime_events(py3, 47) == [
        "ReadSyncFlag", "CommonPjRtBuffer::Await"]
    assert host_events.overlapping(LINES, 30e0, 40e0, min_ns=10) == [
        ["tpu_runtime/2", "HostCallback", -30e-9, 100e-9],
        ["python", "PjitFunction(f)", -30e-9, 45e-9],
        ["python", "CommonPjRtBuffer::Await", -25e-9, 45e-9],
        ["tpu_runtime/1", "ExecuteProgram", -5e-9, 13e-9]]


def test_gaps_of_at_least_a_length_are_all_kept():
    t = _trace()
    assert len(host_events.idle_gaps(t, LINES, k=1)) == 1
    # gaps of 30, 10, 10 and 5 ns
    assert len(host_events.idle_gaps(t, LINES, k=1, min_s=9e-9)) == 3
    assert len(host_events.idle_gaps(t, LINES, k=1, min_s=20e-9)) == 1


def test_host_events_load_from_a_profile(tmp_path):
    """The loader reads every host thread's events from a real profile of
    a CPU run, without the harness's spans."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones((64, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    lines = host_events.load(str(tmp_path))
    assert lines
    names = {n for evs in lines.values() for n, _, _ in evs}
    assert "bench.window" not in names
    assert all(s <= e for evs in lines.values() for _, s, e in evs)


# ---------------------------------------------------------------------------
# the programs, compiled on the CPU
# ---------------------------------------------------------------------------

HPL_PHASES = {"hpl.factor", "hpl.trsm", "hpl.update", "hpl.writeback"}


def _phases_in(hlo_text):
    names = scopes.registry()
    return {scopes.phase_of(p, names)
            for p in scopes.op_paths(hlo_text).values()}


def test_hpl_and_ptrans_phases_are_in_the_compiled_programs():
    hpl = resolve("hpl.n32768.1chip", tiny=True)
    assert HPL_PHASES <= _phases_in(scopes.program_hlo(hpl))
    pt = resolve("ptrans.n28672.1chip", tiny=True)
    assert {"ptrans.transpose_add", "comm.grid_transpose:ptrans.exchange"} \
        <= _phases_in(scopes.program_hlo(pt))


def test_the_lookahead_strip_phase_is_in_its_program():
    import jax
    from repro.core.hpl import make_factorize
    hpl = resolve("hpl.n32768.1chip", tiny=True)
    s = hpl.size
    mesh = hpl.driver.mesh(s, jax.devices()[:1])
    fn = make_factorize(mesh, pg=1, nb=s.n // s.b, b=s.b, lookahead=2)
    text = fn.lower(scopes._local(mesh, s)).compile().as_text()
    assert HPL_PHASES | {"hpl.strip"} <= _phases_in(text)


@pytest.mark.parametrize("cell", ["hpl.n32768.1chip", "ptrans.n28672.1chip"])
def test_the_mapped_program_is_the_drivers(cell):
    """The program the map is read from lowers exactly as the one the
    driver's setup times."""
    import jax
    from bench.harness import prng_key
    c = resolve(cell, tiny=True)
    mesh = c.driver.mesh(c.size, jax.devices()[:1])
    st = c.driver.setup(c, mesh, prng_key(3), 3)
    fn, args = scopes.PROGRAMS[c.config["entry"]](c, mesh)
    timed, timed_args = ((st.fact, (st.a,)) if hasattr(st, "fact")
                         else (st.step, (st.a, st.bm)))
    assert fn.lower(*args).as_text() == timed.lower(*timed_args).as_text()


TWO_BY_TWO = """
import sys
sys.path[:0] = [{root!r}, {src!r}, {here!r}]
import jax
from bench import scopes
from bench.harness import prng_key, resolve
from bench_cases import spec_with_2x2
c = resolve("hpl.n32768.2x2", tiny=True, spec=spec_with_2x2())
mesh = c.driver.mesh(c.size, jax.devices()[:4])
st = c.driver.setup(c, mesh, prng_key(3), 3)
fn, args = scopes.PROGRAMS["hpl"](c, mesh)
assert fn.lower(*args).as_text() == st.fact.lower(st.a).as_text()
names = scopes.registry()
found = {{scopes.phase_of(p, names)
         for p in scopes.op_paths(scopes.program_hlo(c)).values()}}
print("PHASES", " ".join(sorted(found)))
"""


def test_hpl_2x2_program_has_its_phases_and_broadcast_scopes():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = TWO_BY_TWO.format(root=str(ROOT), src=str(ROOT / "src"),
                             here=str(Path(__file__).parent))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    found = set(proc.stdout.strip().splitlines()[-1].split()[1:])
    assert HPL_PHASES | {"comm.bcast:hpl.block", "comm.bcast:hpl.panel"} \
        <= found
