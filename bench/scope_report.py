#!/usr/bin/env python3
"""Runs one cell's window under the profiler, as ``bench/run.py --trace 1``
does, and reports where the device time went by the program's named scopes
and what the host was doing in the device's idle gaps.

    python3 bench/scope_report.py --workload ptrans.n28672.1chip --seed 7 \
        --seconds 30 [--out ptrans_scopes.json]

The last line of standard output is one JSON object:

- ``calls``, ``window_s``, ``busy_s`` (device busy time averaged over the
  cell's chips);
- ``phases``: device seconds per call of each registered scope (and of
  ``unattributed``), averaged over chips, and ``attributed_pct``, the share
  of busy time in a registered scope;
- ``unattributed_ops``: the largest unattributed ops by HLO name, seconds
  per call;
- ``idle_gaps``: the ten longest gaps and every gap of at least 50 ms, each
  labelled by the harness's host span and the host threads' innermost
  events (``bench/host_events.py``);
- ``long_gap_events``: for each gap of at least 50 ms, its seconds and the
  host events of at least 1 ms that overlap it (thread, event, start from
  the gap's start, duration);
- ``op_event_stats``: the stat names a device op event of the trace
  carries (whether the trace itself holds each op's ``op_name``);
- ``host_lines``: the host threads of the trace with their event counts.

It checks nothing and prints no benchmark metric. It needs the chips the
cell asks for.
"""
from __future__ import annotations

import gc
import glob
import json
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import harness, host_events, scopes  # noqa: E402
from bench import trace as tr  # noqa: E402

GAP_MIN_S = 0.05


def op_event_stats(trace_dir: str) -> list[str]:
    """Stat names of the first op event of the first device's op line."""
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == tr.OPS_LINE:
                for e in line.events:
                    return [str(k) for k, _ in e.stats]
    return []


def report(cell, seed: int, seconds: float, trace_dir: str) -> dict:
    import jax
    devs = harness.devices_for(cell, tiny=False)
    mesh = cell.driver.mesh(cell.size, devs)
    counter = harness.CompileCounter()
    st = cell.driver.setup(cell, mesh, harness.prng_key(seed), seed)
    jax.block_until_ready(cell.driver.call(st))
    gc.collect()
    gc.freeze()
    jax.profiler.start_trace(trace_dir)
    try:
        win = harness.run_window(cell, st, seconds, counter)
    finally:
        jax.profiler.stop_trace()
    win.out = None
    del st
    t = tr.load(trace_dir)
    lines = host_events.load(trace_dir)
    busy = tr.busy_ns(t)
    busy_s = sum(busy.values()) / len(busy) / 1e9
    out = {"workload": cell.name, "seed": seed, "calls": win.calls,
           "window_s": t.window_ns / 1e9, "busy_s": busy_s}
    sc = scopes.Scoped(trace=t, paths=scopes.op_paths(
        scopes.program_hlo(cell)), names=scopes.registry() or frozenset())
    out.update(scopes.attribution(sc, win.calls))
    out["idle_gaps"] = host_events.idle_gaps(t, lines, min_s=GAP_MIN_S)
    out["long_gap_events"] = [
        [ns / 1e9, host_events.overlapping(lines, mid - ns / 2,
                                           mid + ns / 2)[:12]]
        for ns, mid in tr.gaps(t) if ns >= GAP_MIN_S * 1e9]
    out["op_event_stats"] = op_event_stats(trace_dir)
    out["host_lines"] = {n: len(evs) for n, evs in lines.items()}
    return out


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", help="also write the report to this file")
    args = p.parse_args(argv)
    cell = harness.resolve(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="bench-scopes-") as d:
        try:
            out = report(cell, args.seed, args.seconds, d)
        except harness.NoDevice as e:
            print(f"bench: {e}", file=sys.stderr)
            return 2
    out["run_s"] = time.perf_counter() - t0
    text = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
