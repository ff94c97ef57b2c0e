"""Reads a JAX profiler trace and reduces it to device busy time, per-op
device time, and idle gaps labelled by the benchmark's host spans.

Everything here works on plain ``(name, start_ns, end_ns)`` tuples, so the
reducers are checked on small hand-made traces (tests/bench) and give every
per-layer metric the same arithmetic.

- ops: the leaf events of each device's "XLA Ops" line, named by their HLO
  instruction (``gemm_update.12``, ``collective-permute-start``);
- busy: the union of the intervals in which an op runs on a device, clipped
  to the traced window (the host span ``bench.window``);
- idle share: 1 - busy / window, averaged over the devices used;
- op time: the summed device durations of the ops whose name matches;
- exposed time of a set of ops (collectives): the part of their union during
  which no other op runs on that device;
- idle gaps: the holes in a device's busy union inside the window, each
  labelled by the innermost ``bench.*`` host span that covers its middle.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

Interval = tuple  # (name, start_ns, end_ns)

# host spans the harness opens (jax.profiler.TraceAnnotation)
WINDOW_SPAN = "bench.window"
HOST_SPANS = (WINDOW_SPAN, "bench.call", "bench.block", "bench.validate")

# HLO instructions of collectives (the "-start"/"-done" halves included)
COLLECTIVE_OPS = (r"^(all-reduce|all-gather|reduce-scatter|collective-permute|"
                  r"all-to-all|send|recv)")

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


@dataclass
class Trace:
    """Device ops per device id, host spans, and the traced window (ns).
    Each device's ops clipped to the window, and the union of their
    intervals, are kept once computed: every metric of a run reads them."""
    ops: dict[int, list[Interval]]
    spans: list[Interval] = field(default_factory=list)
    window: tuple[int, int] = (0, 0)
    _clipped: dict = field(default_factory=dict, repr=False, compare=False)
    _union: dict = field(default_factory=dict, repr=False, compare=False)

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]


def short_name(name: str) -> str:
    """An XLA op event is named by its whole HLO instruction; keep the
    instruction's name: ``%gemm_update.12 = f32[...] custom-call(...)`` ->
    ``gemm_update.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def leaves(ivs) -> list[Interval]:
    """Drop the events that contain another one (a ``while`` loop around
    the ops of its body), so that each instant is counted for the op that
    runs in it."""
    ivs = sorted(ivs, key=lambda x: (x[1], -x[2]))
    return [iv for i, iv in enumerate(ivs)
            if not (i + 1 < len(ivs) and ivs[i + 1][1] < iv[2]
                    and ivs[i + 1][2] <= iv[2])]


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops, spans = {}, []
    for plane in data.planes:
        dev = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name == OPS_LINE:
                ops[int(dev.group(1))] = leaves(
                    (short_name(e.name), int(e.start_ns), int(e.end_ns))
                    for e in line.events)
            elif not dev:
                spans.extend((e.name, int(e.start_ns), int(e.end_ns))
                             for e in line.events if e.name in HOST_SPANS)
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN} host span")
    if not ops:
        raise ValueError("trace has no device op line")
    _, lo, hi = windows[-1]
    return Trace(ops=ops, spans=spans, window=(lo, hi))


def clip(ivs, lo: int, hi: int) -> list[Interval]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in ivs
            if min(e, hi) > max(s, lo)]


def union(ivs) -> list[tuple[int, int]]:
    """Merged (start, end) pairs of the intervals, sorted."""
    out = []
    for _, s, e in sorted(ivs, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered_ns(ivs) -> int:
    return sum(e - s for s, e in union(ivs))


def window_ops(trace: Trace, dev: int) -> list[Interval]:
    if dev not in trace._clipped:
        trace._clipped[dev] = clip(trace.ops.get(dev, []), *trace.window)
    return trace._clipped[dev]


def busy_union(trace: Trace, dev: int) -> list[tuple[int, int]]:
    if dev not in trace._union:
        trace._union[dev] = union(window_ops(trace, dev))
    return trace._union[dev]


def busy_ns(trace: Trace) -> dict[int, int]:
    return {d: sum(e - s for s, e in busy_union(trace, d))
            for d in trace.ops}


def idle_share(trace: Trace) -> float:
    """1 - busy / window, averaged over devices."""
    busy = busy_ns(trace)
    return 1.0 - sum(busy.values()) / len(busy) / trace.window_ns


def matching(ivs, pattern: str) -> list[Interval]:
    rx = re.compile(pattern)
    return [iv for iv in ivs if rx.search(iv[0])]


def op_ns(trace: Trace, pattern: str) -> dict[int, int]:
    """Per device: summed duration of the window's ops matching
    ``pattern`` (a regular expression searched in the op name)."""
    return {d: sum(e - s for _, s, e in matching(window_ops(trace, d),
                                                 pattern))
            for d in trace.ops}


def exposed_ns(trace: Trace, pattern: str) -> dict[int, int]:
    """Per device: the part of the matching ops' union during which no
    other (non-matching) op runs: the busy union less the others' union."""
    rx = re.compile(pattern)
    busy = busy_ns(trace)
    return {d: busy[d] - covered_ns([iv for iv in window_ops(trace, d)
                                     if not rx.search(iv[0])])
            for d in trace.ops}


def top_ops(trace: Trace, k: int = 10) -> list[list]:
    """The k ops with the most device time, seconds averaged over devices."""
    tot: dict[str, int] = {}
    for d in trace.ops:
        for n, s, e in window_ops(trace, d):
            tot[n] = tot.get(n, 0) + (e - s)
    ndev = len(trace.ops)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / ndev / 1e9] for n, v in best]


def _label(spans, t: float) -> str:
    """The innermost (shortest) host span covering time ``t``."""
    cover = [(e - s, n) for n, s, e in spans if s <= t < e]
    return min(cover)[1] if cover else "outside"


def gaps(trace: Trace) -> list[tuple[int, float]]:
    """Every hole in a device's busy union inside the window, as
    (nanoseconds, middle), longest first."""
    lo, hi = trace.window
    out = []
    for d in trace.ops:
        prev = lo
        for s, e in busy_union(trace, d) + [(hi, hi)]:
            if s > prev:
                out.append((s - prev, (s + prev) / 2))
            prev = max(prev, e)
    out.sort(key=lambda g: -g[0])
    return out


def idle_gaps(trace: Trace, k: int = 10) -> list[list]:
    """The k longest holes in any device's busy union inside the window,
    as [host span label, seconds]."""
    return [[_label(trace.spans, mid), ns / 1e9]
            for ns, mid in gaps(trace)[:k]]
