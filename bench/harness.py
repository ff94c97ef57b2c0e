"""The benchmark's harness: finds a cell's configuration, mix, driver and
metrics by name, runs the cell, and prints its result line.

Everything that belongs to one configuration, one mix or one per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

- ``bench/configs/<config>.json``: the entry (a driver), precision, block
  size, matrix rule, checks with their limits, source, reduced, assumed;
- ``bench/mixes/<traffic>.json``: the sizes the driver reads (for the HPCC
  entries N, the grid, and a toy size for ``--tiny``);
- ``bench/drivers/<entry>.py``: sizes and mesh, data from the seed, the
  call into the program, its end-to-end numbers, the work and bytes the
  algorithm requires, and the check;
- ``bench/metrics/<metric>.py``: ``compute(ctx)`` from the trace and the
  cell to one number, or None where the trace holds nothing to read.

A driver is a module with

- ``size(config, mix, chips, tiny)``: the cell's sizes (``Cell.size``),
  raising ValueError for a mix the configuration cannot run;
- ``mesh(size, devices)``: the mesh the entry runs over;
- ``setup(cell, mesh, key, seed)``: the state, with an ``info`` dict;
- ``call(state)``: one whole repetition, as the window times it;
- ``end_to_end(cell, calls, seconds)``: its end-to-end metrics by name, for
  ``calls`` completed in ``seconds``; the harness adds ``setup_s``;
- ``check(state, out)``: each compared number as ``(value, limit)``.

A run: compile cache, device check, mesh, data from the seed on the
devices, the program's entry, one warm-up call (set-up ends here), whole
calls for ``--seconds`` each ending in ``block_until_ready``, then the check
of the last call's output and the result line.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import re
import statistics
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

from bench.clock import phase

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class UnknownName(LookupError):
    pass


def _checked(name: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise UnknownName(f"not a valid name: {name!r}")
    return name


def load_json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{_checked(name)}.json"
    if not path.is_file():
        raise UnknownName(f"no {kind} entry {name!r} ({path})")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    path = BENCH / kind / f"{_checked(name)}.py"
    if not path.is_file():
        raise UnknownName(f"no {kind} entry {name!r} ({path})")
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    driver: object
    size: object             # what the driver's size() makes of config, mix
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)


def resolve(name: str, *, tiny: bool = False, spec: dict = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files loaded."""
    spec = spec or load_benchmark()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise UnknownName(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise UnknownName(f"workload {name!r} names no known config")
    cfg = load_json("configs", w["config"])
    mix = load_json("mixes", w["traffic"])
    driver = load_module("drivers", cfg["entry"])
    try:
        size = driver.size(cfg, mix, w["chips"], tiny)
    except ValueError as e:
        raise ValueError(f"{name}: {e}") from None
    cell = Cell(name=name, config=cfg, mix=mix, chips=w["chips"],
                driver=driver, size=size,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if _applies(m, name)])
    given = set(driver.end_to_end(cell, 1, 1.0)) | {"setup_s"}
    for m in cell.end_to_end:
        if m["name"] not in given:
            raise UnknownName(f"{name}: driver {cfg['entry']!r} gives no "
                              f"end-to-end metric {m['name']!r}")
    return cell


def prng_key(seed: int):
    """A key from any non-negative seed, wider than 32 bits included."""
    import jax
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    hi = seed >> 32
    while hi:
        key = jax.random.fold_in(key, hi & 0xFFFFFFFF)
        hi >>= 32
    return key


class NoDevice(RuntimeError):
    pass


def devices_for(cell: Cell, tiny: bool):
    """The chips the cell runs on. Anything but a TPU listed in the peaks
    table is refused (``--tiny`` takes the CPU devices instead)."""
    import jax
    from bench.peaks import peaks_for

    devs = jax.devices()
    if tiny:
        if devs[0].platform != "cpu":
            raise NoDevice("--tiny runs on the CPU only")
    else:
        if devs[0].platform != "tpu":
            raise NoDevice(f"no TPU: JAX runs on {devs[0].platform}")
        peaks_for(devs[0].device_kind)
    if len(devs) < cell.chips:
        raise NoDevice(f"{cell.name} needs {cell.chips} chips, "
                       f"JAX finds {len(devs)}")
    return devs[:cell.chips]


class CompileCounter:
    """Counts traces, compiles and persistent-cache loads while on."""

    def __init__(self):
        import jax.monitoring as mon
        self.on = False
        self.count = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if self.on and event.startswith("/jax/core/compile/"):
            self.count += 1

    def _event(self, event, **kw):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.count += 1


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


@dataclass
class Window:
    calls: int
    seconds: float           # first call's start to last call's end
    per_call: list
    compiles: int
    out: object


def run_window(cell: Cell, st, seconds: float, counter) -> Window:
    """Whole calls until ``seconds`` have passed, each waited for."""
    import jax
    driver = cell.driver
    per_call = []
    out = None
    counter.on = True
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        t = t0
        while t - t0 < seconds:
            out = None                     # free the last output first
            with jax.profiler.TraceAnnotation("bench.call"):
                start = time.perf_counter()
                out = driver.call(st)
                with jax.profiler.TraceAnnotation("bench.block"):
                    jax.block_until_ready(out)
                t = time.perf_counter()
            per_call.append(t - start)
    counter.on = False
    return Window(calls=len(per_call), seconds=t - t0, per_call=per_call,
                  compiles=counter.count, out=out)


@dataclass
class MetricContext:
    """What a per-layer metric reads: the reduced trace and the cell."""
    cell: Cell
    trace: object            # bench.trace.Trace
    peaks: object            # bench.peaks.Peaks
    calls: int               # calls completed in the traced window


def per_layer_metrics(cell: Cell, ctx: MetricContext) -> dict:
    out = {}
    for m in cell.per_layer:
        with phase(f"metric.{m['name']}"):
            val = load_module("metrics", m["name"]).compute(ctx)
        if val is not None:
            out[m["name"]] = {"value": val, "unit": m["unit"]}
    return out


def _log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def run(cell: Cell, *, seed: int, seconds: float, trace: bool, tiny: bool,
        t_start: float) -> dict:
    """One run of the cell; returns the result line's object."""
    import jax
    from bench import trace as tr
    from bench.peaks import peaks_for

    t_import = time.perf_counter()
    devs = devices_for(cell, tiny)
    mesh = cell.driver.mesh(cell.size, devs)
    counter = CompileCounter()
    driver = cell.driver

    t_devices = time.perf_counter()
    st = driver.setup(cell, mesh, prng_key(seed), seed)
    jax.block_until_ready(vars(st))             # the data, made on device
    t_data = time.perf_counter()
    jax.block_until_ready(driver.call(st))      # warm-up: the one shape
    # set-up's objects leave the collector's scans, so that no full
    # collection over them stalls a call in the window
    gc.collect()
    gc.freeze()
    t_end = time.perf_counter()
    setup_s = t_end - t_start
    _log(f"bench {cell.name}: {cell.size} seed={seed} "
         + " ".join(f"{k}={v}" for k, v in st.info.items()))
    _log(f"bench setup_s={setup_s!r} imports_s={t_import - t_start!r} "
         f"devices_s={t_devices - t_import!r} data_s={t_data - t_devices!r} "
         f"warmup_s={t_end - t_data!r}")

    tdir = None
    if trace:
        import tempfile
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        with phase("start_trace"):
            jax.profiler.start_trace(tdir)
    try:
        with phase("window"):
            win = run_window(cell, st, seconds, counter)
    finally:
        if trace:
            with phase("stop_trace"):
                jax.profiler.stop_trace()
    mem = peak_bytes(devs)
    best = min(win.per_call)
    _log(f"bench calls={win.calls} window_s={win.seconds!r} "
         f"compiles_in_window={win.compiles} peak_bytes_in_use={mem}")
    q = statistics.quantiles(win.per_call, n=4) if win.calls > 1 else [best] * 3
    slowest = max(range(win.calls), key=win.per_call.__getitem__)
    _log(f"bench per_call_s first={win.per_call[:3]!r} min={best!r} "
         f"quartiles={q!r} max={win.per_call[slowest]!r} "
         f"max_at_call={slowest}")
    _log(f"bench best_of_s={best!r} best_of "
         + " ".join(f"{k}={v!r}" for k, v in
                    driver.end_to_end(cell, 1, best).items())
         + " (HPCC's own number)")

    result = {"metrics": {}, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs), "memory_peak_bytes": mem}}
    if trace:
        with phase("trace.load"):
            t = tr.load(tdir)
        import shutil
        shutil.rmtree(tdir, ignore_errors=True)
        with phase("trace.busy"):
            busy = tr.busy_ns(t)
        result["device"]["busy_s"] = sum(busy.values()) / len(busy) / 1e9
        result["device"]["window_s"] = t.window_ns / 1e9
        ctx = MetricContext(cell=cell, trace=t,
                            peaks=peaks_for(devs[0].device_kind),
                            calls=win.calls)
        result["metrics"] = per_layer_metrics(cell, ctx)
        with phase("trace.breakdown"):
            result["breakdown"] = {"device_ops": tr.top_ops(t),
                                   "idle_gaps": tr.idle_gaps(t)}
    elif not tiny:
        values = {**driver.end_to_end(cell, win.calls, win.seconds),
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}

    with phase("check"), jax.profiler.TraceAnnotation("bench.validate"):
        checks = driver.check(st, win.out)
    del st, win
    correct = all(math.isfinite(v) and v <= lim for v, lim in checks.values())
    result = {"correct": correct, "attempted": 1, "failed": 0 if correct else 1,
              **result,
              "checks": {k: {"value": v, "limit": lim}
                         for k, (v, lim) in checks.items()}}
    _log(f"bench to_result_s={time.perf_counter() - t_start!r}")
    for k, (v, lim) in checks.items():
        _log(f"check {k} = {v!r} limit {lim!r}")
    return result


def main(argv=None, t_start: float = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    import argparse
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--tiny", action="store_true",
                   help="toy sizes on the CPU, kernels interpreted; prints "
                        "no device metric")
    args = p.parse_args(argv)
    cell = resolve(args.workload, tiny=args.tiny)
    if args.tiny and cell.chips > 1:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count="
                                   f"{cell.chips}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    # every program, small ones too, comes from the cache after a first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        # --tiny reads no trace: the CPU has no device plane to read
        result = run(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace) and not args.tiny, tiny=args.tiny,
                     t_start=t_start)
    except NoDevice as e:
        _log(f"bench: {e}")
        return 2
    if args.tiny:
        result = {"tiny": True, **{k: result[k] for k in
                                   ("correct", "attempted", "failed",
                                    "checks")}}
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] or not args.tiny else 1
