"""Benchmark driver:
``PYTHONPATH=src python -m benchmarks.run [--quick] [--schedule NAME]
[--sweep-schedules] [--autotune] [modules...]``.

``--schedule`` selects a registered collective-engine schedule (``chain``,
``native``, ``staged``, ``ring2d``, ``rs_ag``, ``int8_ef``; see
repro.comm.engine) for every benchmark that communicates; the engine's
resolved schedule name is recorded in each result file. Without the flag
(or with ``--schedule auto``) every engine resolves per callsite through
the cost model (repro.comm.autotune) and the driver prints the choices.

``--sweep-schedules`` instead runs each selected benchmark once per schedule
registered for its primary collective op — plus an ``auto`` row showing what
the cost model picks — and emits one comparison table per benchmark (the
paper's Figs. 10-16 with schedules as columns), saved to
``results/bench/schedule_sweep.json``. Modules with a software-pipeline
dimension (PTRANS chunk count, HPL lookahead depth) are additionally swept
over S in {1, 2, 4, auto} under ``schedule="auto"`` and get a second
pipeline-depth comparison table.

``--autotune`` microbenchmarks every registered schedule per op on the live
devices, persists the per-size winners to ``results/tuning.json`` (loaded by
every subsequent ``schedule="auto"`` engine), and fails if any ``auto``
resolution names an unregistered schedule. Combine with modules to run
benchmarks against the freshly measured table in the same invocation.

Module arguments accept short aliases: ``hpl`` -> hpl_scaling, ``ptrans`` ->
ptrans_scaling, ``beff`` -> beff_bandwidth, ``overlap`` -> overlap_bench,
``gups`` / ``fftd`` -> gups_fft_bench.

One module per paper table/figure (DESIGN.md §6):
  beff_bandwidth   Fig. 10/11 + Eqs. 1/2/4
  ptrans_scaling   Fig. 12 + Eqs. 5/6
  hpl_matrix_sweep Fig. 13
  hpl_scaling      Figs. 14/15
  legacy_suite     Fig. 16
  gups_fft_bench   beyond-paper distributed GUPS + pencil FFT: the legacy
                   suite's two kernels engine-routed (ra.updates /
                   fft.transpose callsites) next to their zero-comm
                   references (records the resolved schedules and exits 1
                   if any is unregistered — the --autotune gate)
  resource_table   Table 7 analogue (production-mesh compiled footprints)
  lm_step_bench    beyond-paper LM roofline table + explicit-vs-GSPMD MoE
                   (engine-routed expert exchanges; records the resolved
                   moe.dispatch / moe.combine / dp.grads schedules and
                   exits 1 if any is unregistered — the --autotune gate)
  overlap_bench    Figs. 5/7 analogue (lookahead HPL + bucketed reduction)
  serve_bench      beyond-paper continuous-batching serving loop: paged-KV
                   explicit-vs-GSPMD decode parity + tokens/sec and p50/p99
                   per-token latency vs batch size (records the resolved
                   decode.qkv / decode.out / decode.moe schedules and exits
                   1 if any is unregistered — the --autotune gate)
  resilience_bench beyond-paper degraded-link resilience: scripted fault ->
                   drift detection -> narrow retune -> mid-run schedule flip
                   (bit-exact, deterministic gate), plus straggler-flagged
                   train degradation and zero-lost-token serve preemption
  failover_bench   beyond-paper hard-failure survival: link-down ->
                   health-masked reroute (provably off the cut, bit-exact),
                   rank loss -> elastic resume from a resharded checkpoint
                   (bitwise vs control), and zero-lost-token serve drain
"""
from __future__ import annotations

import sys
import time
import traceback

from benchmarks.common import ensure_devices, save_result, table

ensure_devices()  # 8 placeholder CPU devices for every measured benchmark

MODULES = [
    "beff_bandwidth",
    "ptrans_scaling",
    "hpl_matrix_sweep",
    "hpl_scaling",
    "legacy_suite",
    "gups_fft_bench",
    "resource_table",
    "lm_step_bench",
    "overlap_bench",
    "serve_bench",
    "resilience_bench",
    "failover_bench",
]

ALIASES = {
    "hpl": "hpl_scaling",
    "ptrans": "ptrans_scaling",
    "beff": "beff_bandwidth",
    "overlap": "overlap_bench",
    "gups": "gups_fft_bench",
    "fftd": "gups_fft_bench",
    "lm": "lm_step_bench",
    "serve": "serve_bench",
    "resilience": "resilience_bench",
    "failover": "failover_bench",
}

# primary collective op per module: --sweep-schedules runs the module once
# per schedule registered for that op (None = no communication to sweep)
SWEEP_OPS = {
    "beff_bandwidth": "ring_exchange",
    "ptrans_scaling": "grid_transpose",
    "hpl_matrix_sweep": "bcast",
    "hpl_scaling": "bcast",
    "legacy_suite": None,      # embarrassingly parallel — ignores schedule
    # routed GUPS + pencil FFT both exchange over all_to_all_tiles (the
    # ra.updates / fft.transpose callsites): the sweep reruns both per
    # registered schedule next to their zero-comm references
    "gups_fft_bench": "all_to_all_tiles",
    "resource_table": None,
    # the GSPMD steps ignore schedule (XLA picks the collectives), but the
    # explicit-MoE section routes its dispatch/combine exchanges through the
    # engine — the sweep exercises every registered all_to_all_tiles schedule
    "lm_step_bench": "all_to_all_tiles",
    "overlap_bench": "allreduce",
    # the decode.qkv/decode.out/decode.moe exchanges are all_to_all_tiles:
    # the sweep reruns the serving loop once per registered schedule
    "serve_bench": "all_to_all_tiles",
    # the whole point is the *adaptive* auto path: a fixed-schedule sweep
    # would defeat the retune under test
    "resilience_bench": None,
    # likewise: the health-masked re-resolution IS the subject under test
    "failover_bench": None,
}

# modules with a software-pipeline dimension: --sweep-schedules also runs
# them once per pipeline depth S (chunk count for PTRANS, lookahead depth
# for HPL; "auto" = the cost-model resolution) under schedule="auto"
PIPELINE_SWEEP = ("ptrans_scaling", "hpl_scaling")
PIPELINE_DEPTHS = (1, 2, 4, "auto")


def _parse_schedule(argv):
    """--schedule NAME or --schedule=NAME; validated against the registry."""
    schedule = None
    rest = []
    it = iter(argv)
    for a in it:
        if a == "--schedule":
            schedule = next(it, None)
            if schedule is None or schedule.startswith("-"):
                raise SystemExit("--schedule requires a value, e.g. "
                                 "--schedule ring2d")
        elif a.startswith("--schedule="):
            schedule = a.split("=", 1)[1]
        else:
            rest.append(a)
    if schedule is not None:
        # engine construction is the single source of schedule validation
        from repro.comm.engine import CollectiveEngine
        CollectiveEngine(schedule=schedule)
    return schedule, rest


def _print_resolved(name, record):
    """Surface the cost-model choices: every resolved schedule recorded in
    the module's result dict (the literal "auto" never appears here)."""
    picks = sorted({str(v["schedule"]) for v in (record or {}).values()
                    if isinstance(v, dict) and "schedule" in v})
    if picks:
        print(f"[{name}: cost-model resolved schedule(s): "
              f"{', '.join(picks)}]")


def _run_module(name, quick, schedule, pipeline=None):
    print("\n" + "=" * 78)
    print(f"### benchmarks.{name}"
          + (f" (schedule={schedule})" if schedule else "")
          + (f" (pipeline={pipeline})" if pipeline is not None else ""))
    print("=" * 78)
    t0 = time.time()
    mod = __import__(f"benchmarks.{name}", fromlist=["main"])
    kw = {"quick": quick, "schedule": schedule}
    if pipeline is not None:
        kw["pipeline"] = pipeline
    record = mod.main(**kw)
    if schedule in (None, "auto"):
        _print_resolved(name, record)
    print(f"[{name} done in {time.time() - t0:.1f}s]")
    return record


def _autotune(quick):
    """Measure registered schedules on the live mesh, persist the tuning
    table, refresh the default cost model, and verify every auto resolution
    is a registered name (CI gate)."""
    import jax

    from repro.comm.autotune import (autotune_mesh, default_cost_model,
                                     default_table_path)
    from repro.comm.engine import OPS, schedules_for
    from repro.comm.topology import AxisTopology

    print("\n" + "=" * 78)
    print("### autotune: measuring registered schedules on the live mesh")
    print("=" * 78)
    table, record = autotune_mesh(quick=quick)
    path = table.save(default_table_path())
    save_result("autotune_raw", record)
    print(f"[tuning table -> {path}]")
    for op, sigs in table.entries.items():
        for sig, rows in sigs.items():
            bands = ", ".join(
                f"<= {b}B: {n}" if b is not None else f"rest: {n}"
                for b, n in rows)
            print(f"  {op:16s} {sig:28s} {bands}")

    model = default_cost_model(refresh=True)
    # gate: auto must resolve to a registered schedule for every op across
    # the measured topologies and a size ladder spanning the table bands
    bad = []
    probe_axes = {
        "ring": (AxisTopology("x", len(jax.devices()), "ring"),),
    }
    for op in OPS:
        for sig, axes in probe_axes.items():
            for lg in range(0, 27, 2):
                choice = model.choose(op, 1 << lg, axes)
                if choice is None or choice not in schedules_for(op):
                    bad.append((op, sig, 1 << lg, choice))
    for op, sigs in table.entries.items():
        base_op = op.split("@", 1)[0]  # callsite-tagged keys (bcast@hpl.panel)
        for sig, rows in sigs.items():
            for _, nm in rows:
                if nm not in schedules_for(base_op):
                    bad.append((op, sig, "table", nm))
    if bad:
        print("UNREGISTERED auto resolutions:", bad)
        raise SystemExit(1)
    print("[autotune ok: every auto resolution is a registered schedule]")


def _metric_rows(record):
    """(key, gflops-like scalar) pairs from a benchmark record, for the
    cross-schedule comparison table."""
    rows = []
    for key, val in (record or {}).items():
        if isinstance(val, dict):
            for field in ("gflops", "gbps", "gups", "bandwidth_gbs", "time"):
                if field in val:
                    rows.append((key, field, float(val[field])))
                    break
    return rows


def _sweep(modules, quick):
    from repro.comm.engine import schedules_for
    sweep_record = {}
    failures = []
    for name in modules:
        op = SWEEP_OPS.get(name)
        # "auto" rides along as its own column: the cost-model pick should
        # sit within noise of the best fixed schedule
        schedules = list(schedules_for(op)) + ["auto"] if op else [None]
        per_schedule = {}
        for s in schedules:
            try:
                per_schedule[s or "default"] = _run_module(name, quick, s)
            except Exception:  # noqa: BLE001
                failures.append(f"{name}[{s}]")
                print(f"[{name} schedule={s} FAILED]\n"
                      f"{traceback.format_exc()[-3000:]}")
        sweep_record[name] = per_schedule

        # one comparison table per module: record keys x schedules
        cols = list(per_schedule)
        cells = {}
        metric_field = {}
        for s, rec in per_schedule.items():
            for key, field, v in _metric_rows(rec):
                cells.setdefault(key, {})[s] = v
                metric_field[key] = field
        if cells:
            print(f"\n-- {name}: schedule comparison "
                  f"({op or 'no collective op'}) --")
            rows = [[key, metric_field[key]]
                    + [f"{cells[key].get(s, float('nan')):.4g}" for s in cols]
                    for key in cells]
            print(table(rows, ["config", "metric"] + cols))

        # pipeline-depth columns: the same module swept over the software-
        # pipeline dimension (chunk count / lookahead depth) under auto
        if name in PIPELINE_SWEEP:
            per_pipe = {}
            for s in PIPELINE_DEPTHS:
                try:
                    per_pipe[f"S={s}"] = _run_module(name, quick, "auto",
                                                     pipeline=s)
                except Exception:  # noqa: BLE001
                    failures.append(f"{name}[pipeline={s}]")
                    print(f"[{name} pipeline={s} FAILED]\n"
                          f"{traceback.format_exc()[-3000:]}")
            sweep_record[f"{name}/pipeline"] = per_pipe
            pcols = list(per_pipe)
            pcells, pfield = {}, {}
            for s, rec in per_pipe.items():
                for key, field, v in _metric_rows(rec):
                    pcells.setdefault(key, {})[s] = v
                    pfield[key] = field
            if pcells:
                print(f"\n-- {name}: pipeline-depth comparison "
                      f"(schedule=auto) --")
                rows = [[key, pfield[key]]
                        + [f"{pcells[key].get(s, float('nan')):.4g}"
                           for s in pcols]
                        for key in pcells]
                print(table(rows, ["config", "metric"] + pcols))
    save_result("schedule_sweep", sweep_record)
    return failures


def main():
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    schedule, argv = _parse_schedule(sys.argv[1:])
    quick = "--quick" in argv
    sweep = "--sweep-schedules" in argv
    autotune = "--autotune" in argv
    only = [ALIASES.get(a, a) for a in argv if not a.startswith("-")]
    for name in only:
        if name not in MODULES:
            raise SystemExit(f"unknown benchmark {name!r}; modules are "
                             f"{MODULES} (aliases: {ALIASES})")
    modules = only or MODULES

    if autotune:
        _autotune(quick)  # SystemExit(1) on unregistered auto resolutions
        if not only and not sweep:
            return  # tune-only invocation (the CI smoke step)

    if sweep:
        if schedule is not None:
            raise SystemExit("--sweep-schedules and --schedule are "
                             "mutually exclusive")
        failures = _sweep(modules, quick)
    else:
        failures = []
        for name in modules:
            try:
                _run_module(name, quick, schedule)
            except Exception:  # noqa: BLE001
                failures.append(name)
                print(f"[{name} FAILED]\n{traceback.format_exc()[-3000:]}")
    print("\n" + "=" * 78)
    if failures:
        print("FAILED:", failures)
        raise SystemExit(1)
    print("all benchmarks passed")


if __name__ == "__main__":
    main()
