"""What the host was doing while a device idled: the events of the host's
threads (the TPU runtime's and Python's own) from the trace's non-device
planes, on the clock of the device ops.

``bench/trace.py`` keeps only the four ``bench.*`` spans of the host and
labels an idle gap with the innermost one covering the gap's middle. This
module keeps every host event, one list per host thread (a line of a
non-device plane), and labels a gap ``<bench span>|<event>[|<event>]``: the
same span first, then the innermost event of each host thread covering the
middle, runtime threads before the Python thread, shortest first, at most
two names. The label's first part is the one ``bench/trace.py`` gives, and
the gaps and their lengths are the same.
"""
from __future__ import annotations

import glob
import os
import re

from bench import trace as tr

# host lines of the Python interpreter itself (the profiler's Python
# tracer names its line after the executable, ``python3`` for instance);
# their innermost event in a gap is the harness waiting, so the runtime's
# threads come first in a label
PYTHON_LINE = re.compile(r"^python")
_DEVICE_PLANE = re.compile(r"^/device:")


def load(trace_dir: str) -> dict[str, list[tr.Interval]]:
    """Host thread name -> its events, from the newest ``.xplane.pb``
    under ``trace_dir``. The ``bench.*`` spans are left out: they are the
    first part of every label already."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    lines: dict[str, list] = {}
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.end_ns))
                   for e in line.events if e.name not in tr.HOST_SPANS]
            if evs:
                lines.setdefault(line.name, []).extend(evs)
    return lines


def runtime_events(lines: dict, t: float, k: int = 2) -> list[str]:
    """The innermost event of each host thread that covers time ``t``,
    runtime threads first, shortest first: at most ``k`` distinct names."""
    cover = []
    for name, evs in lines.items():
        inner = [(e - s, n) for n, s, e in evs if s <= t < e]
        if inner:
            dur, ev = min(inner)
            cover.append((bool(PYTHON_LINE.match(name)), dur, ev))
    out = []
    for _, _, ev in sorted(cover):
        if ev not in out:
            out.append(ev)
    return out[:k]


def overlapping(lines: dict, lo: float, hi: float,
                min_ns: int = 1_000_000) -> list[list]:
    """The host events of at least ``min_ns`` that overlap [lo, hi), as
    [thread, event, start - lo (s), duration (s)], longest first."""
    out = [[name, n, (s - lo) / 1e9, (e - s) / 1e9]
           for name, evs in lines.items() for n, s, e in evs
           if e - s >= min_ns and s < hi and e > lo]
    return sorted(out, key=lambda x: -x[3])


def label(trace: tr.Trace, lines: dict, t: float) -> str:
    """``bench/trace.py``'s span label of time ``t``, then the host
    threads' innermost events there."""
    return "|".join([tr._label(trace.spans, t),
                     *runtime_events(lines, t)])


def idle_gaps(trace: tr.Trace, lines: dict, k: int = 10,
              min_s: float = 0.0) -> list[list]:
    """The k longest idle gaps, and every gap of at least ``min_s``
    seconds, as [label, seconds] with the host threads' events in the
    label."""
    out = []
    for i, (ns, mid) in enumerate(tr.gaps(trace)):
        if i >= k and not (min_s > 0 and ns >= min_s * 1e9):
            break
        out.append([label(trace, lines, mid), ns / 1e9])
    return out
