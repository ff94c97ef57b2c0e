"""The trace reducers and the per-layer metrics, on small traces with known
intervals (times in ns)."""
import pytest

from bench import trace as tr
from bench.harness import MetricContext, load_module, resolve
from bench.peaks import Peaks
from bench_cases import spec_with_2x2

# window [0, 100); device 0: two gemm tiles, a panel kernel and a collective
# that half overlaps the second gemm; device 1: one gemm, a lone collective
OPS = {
    0: [("gemm_update.1", 0, 30), ("gemm_update.1", 40, 70),
        ("lu_factor_block.3", 70, 75), ("collective-permute-done.2", 60, 80),
        ("fusion.3", 90, 120)],
    1: [("gemm_update.1", 10, 50), ("all-gather.1", 50, 60)],
}
SPANS = [("bench.window", 0, 100), ("bench.call", 0, 50),
         ("bench.block", 5, 50), ("bench.call", 50, 100)]


def _trace():
    return tr.Trace(ops=OPS, spans=SPANS, window=(0, 100))


def test_union_and_overlap():
    ivs = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40)]
    assert tr.union(ivs) == [(0, 20), (30, 40)]
    assert tr.covered_ns(ivs) == 30
    assert tr.overlap_ns(ivs, [("x", 15, 35)]) == 10


def test_busy_and_idle_share():
    t = _trace()
    # dev 0: [0,30) + [40,80) + [90,100) clipped = 80; dev 1: [10,60) = 50
    assert tr.busy_ns(t) == {0: 80, 1: 50}
    assert tr.idle_share(t) == pytest.approx(1 - 65 / 100)


def test_op_time_clips_to_the_window():
    t = _trace()
    assert tr.op_ns(t, "gemm_update") == {0: 60, 1: 40}
    assert tr.op_ns(t, "fusion") == {0: 10, 1: 0}


def test_exposed_collective_time():
    t = _trace()
    coll = "collective-permute|all-gather"
    # dev 0: [60,80) overlaps gemm [60,70) and lu [70,75) -> 5 exposed
    # dev 1: [50,60) runs alone -> 10
    assert tr.exposed_ns(t, coll) == {0: 5, 1: 10}


def test_top_ops_and_idle_gaps():
    t = _trace()
    top = dict(tr.top_ops(t))
    assert top["gemm_update.1"] == pytest.approx(100 / 2 / 1e9)
    gaps = tr.idle_gaps(t)
    # dev 0 idles [30,40) (host waits in bench.block) and [80,90) (second
    # bench.call); dev 1 idles [0,10) (middle 5 is in bench.block) and
    # [60,100) (second bench.call)
    assert gaps[0] == ["bench.call", 40 / 1e9]
    assert sorted(gaps[1:]) == sorted([["bench.block", 10 / 1e9],
                                       ["bench.call", 10 / 1e9],
                                       ["bench.block", 10 / 1e9]])


def _ctx(cell, calls=2):
    return MetricContext(cell=cell, trace=_trace(),
                         peaks=Peaks(flops=1e18, hbm_bw=1e12, hbm_bytes=1),
                         calls=calls)


def test_metrics_on_a_known_trace():
    hpl = resolve("hpl.n32768.2x2", spec=spec_with_2x2())
    ctx = _ctx(hpl)

    def m(name):
        return load_module("metrics", name).compute(ctx)

    assert m("idle_pct.hpl") == pytest.approx(35.0)
    assert m("hpl_panel_kernel_s") == pytest.approx(5 / 2 / 2 / 1e9)
    assert m("hpl_collective_exposed_s") == pytest.approx(15 / 2 / 2 / 1e9)
    need = hpl.driver.required_update_flops_total(hpl.size.n, hpl.size.b)
    assert m("hpl_gemm_roofline") == pytest.approx(
        100 * (2 * need / 1e18) / (100 / 1e9))
    # busy 80 ns on device 0 and 50 on device 1: 65 ns of device time
    assert m("hpl_mfu") == pytest.approx(
        100 * 2 * hpl.driver.nominal_flops(hpl.size.n) / 65e-9 / (4 * 1e18))
    assert m("ptrans_exchange_s") == pytest.approx((20 + 10) / 2 / 2 / 1e9)


def test_metrics_read_nothing_where_nothing_runs():
    one = resolve("hpl.n32768.1chip")
    assert load_module("metrics", "hpl_collective_exposed_s").compute(
        _ctx(one)) is None
    pt = resolve("ptrans.n28672.1chip")
    # no transpose_add op in the trace: no roofline, never a 0
    assert load_module("metrics", "ptrans_kernel_roofline").compute(
        _ctx(pt)) is None


def test_ptrans_roofline_on_a_known_trace():
    pt = resolve("ptrans.n28672.1chip")
    t = tr.Trace(ops={0: [("transpose_add.1", 0, 50)]},
                 spans=SPANS, window=(0, 100))
    ctx = MetricContext(cell=pt, trace=t,
                        peaks=Peaks(flops=1, hbm_bw=1e15, hbm_bytes=1),
                        calls=5)
    want = 100 * (5 * 3 * 28672 ** 2 * 4 / 1e15) / (50 / 1e9)
    assert load_module("metrics", "ptrans_kernel_roofline").compute(
        ctx) == pytest.approx(want)


def test_names_and_leaves():
    name = ("%gemm_update.12 = f32[16384,16384]{1,0:T(8,128)} custom-call("
            "f32[16384,16384]{1,0:T(8,128)} %get-tuple-element.178)")
    assert tr.short_name(name) == "gemm_update.12"
    # a while loop around two ops, then a copy: only the leaves stay
    ivs = [("copy.1", 0, 5), ("while.11", 5, 50), ("gemm_update.1", 6, 20),
           ("trsm_lower_left.1", 20, 50)]
    assert tr.leaves(ivs) == [("copy.1", 0, 5), ("gemm_update.1", 6, 20),
                              ("trsm_lower_left.1", 20, 50)]
