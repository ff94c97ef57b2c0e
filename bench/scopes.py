"""Maps each device op of a trace to the program's named scope it ran in,
and reduces device time by scope.

The program runs its phases and its engine calls under registered
``jax.named_scope`` names (``repro.scopes``): ``hpl.update``,
``ptrans.transpose_add``, ``comm.bcast:hpl.panel``. A trace names a device
op by its HLO instruction (``bench/trace.py``); the scopes it ran in are
that instruction's ``metadata={op_name="jit(f)/.../hpl.update/..."}`` in
the compiled program of the timed call. This module reads them from the
compiled HLO text: it builds the cell's program as the driver's ``setup``
does (``PROGRAMS``, by the configuration's entry), lowers and compiles it
for the cell's chips after the window (the warm-up put the executable in
the persistent compile cache, so this is a cache load), and keeps each
instruction's ``op_name``.

- ``phase_of(path)``: the innermost registered scope in the path, or
  ``unattributed``; a compute phase nested inside an engine scope is the
  innermost, so it counts as compute;
- ``phase_ns(scoped, phases)``: per device, the summed duration of the
  window's ops whose phase matches one of ``phases`` (``fnmatch``
  patterns: ``comm.*:hpl.block``);
- ``phase_exposed_ns(scoped, phases)``: per device, the time in which only
  those phases' ops run.

A program without the registry (``repro.scopes``), or whose compiled ops
carry none of its names, gives no map: ``scoped`` returns None and the
metrics that read scopes read nothing.
"""
from __future__ import annotations

import fnmatch
import re
import sys
import time
import traceback
from dataclasses import dataclass

from bench import trace as tr

UNATTRIBUTED = "unattributed"

# one HLO instruction per line: its name, then its own metadata's op_name
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{op_name="([^"]*)"')


def op_paths(hlo_text: str) -> dict[str, str]:
    """HLO instruction name -> its ``op_name`` path, for every instruction
    of the compiled module text that carries one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def registry():
    """The program's registered scope names, or None for a program that
    has no registry."""
    try:
        from repro.scopes import registered
    except ImportError:
        return None
    return frozenset(registered())


def phase_of(path: str, names) -> str:
    """The innermost registered scope of an ``op_name`` path."""
    for part in reversed(path.split("/")):
        if part in names:
            return part
    return UNATTRIBUTED


@dataclass
class Scoped:
    """A reduced trace with each op's phase: ``paths`` maps HLO
    instruction names to ``op_name`` paths, ``names`` is the registry."""
    trace: tr.Trace
    paths: dict
    names: frozenset

    def __post_init__(self):
        self._phase = {}
        self._members = {}

    def phase(self, op: str) -> str:
        if op not in self._phase:
            path = self.paths.get(op)
            self._phase[op] = (UNATTRIBUTED if path is None
                               else phase_of(path, self.names))
        return self._phase[op]

    def members(self, phases) -> frozenset:
        """The names of the trace's ops whose phase matches one of
        ``phases``."""
        key = tuple(phases)
        if key not in self._members:
            ops = {n for ivs in self.trace.ops.values() for n, _, _ in ivs}
            self._members[key] = frozenset(
                n for n in ops
                if any(fnmatch.fnmatchcase(self.phase(n), p) for p in key))
        return self._members[key]


def phase_ns(sc: Scoped, phases) -> dict[int, int]:
    """Per device: summed duration of the window's ops in ``phases``."""
    names = sc.members(phases)
    return {d: sum(e - s for n, s, e in tr.window_ops(sc.trace, d)
                   if n in names)
            for d in sc.trace.ops}


def phase_exposed_ns(sc: Scoped, phases) -> dict[int, int]:
    """Per device: the part of the union of the ops in ``phases`` during
    which no op of another phase runs: the busy union less the others'."""
    names = sc.members(phases)
    busy = tr.busy_ns(sc.trace)
    return {d: busy[d] - tr.covered_ns([iv for iv in tr.window_ops(sc.trace, d)
                                        if iv[0] not in names])
            for d in sc.trace.ops}


def by_phase(sc: Scoped) -> dict[str, float]:
    """Seconds of device time per phase, averaged over devices."""
    tot: dict[str, int] = {}
    for d in sc.trace.ops:
        for n, s, e in tr.window_ops(sc.trace, d):
            ph = sc.phase(n)
            tot[ph] = tot.get(ph, 0) + (e - s)
    ndev = len(sc.trace.ops)
    return {ph: ns / ndev / 1e9 for ph, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])}


def unattributed_ops(sc: Scoped, k: int = 5) -> list[list]:
    """The k unattributed ops with the most device time, seconds averaged
    over devices."""
    tot: dict[str, int] = {}
    for d in sc.trace.ops:
        for n, s, e in tr.window_ops(sc.trace, d):
            if sc.phase(n) == UNATTRIBUTED:
                tot[n] = tot.get(n, 0) + (e - s)
    ndev = len(sc.trace.ops)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / ndev / 1e9] for n, v in best]


# ---------------------------------------------------------------------------
# the compiled program of the timed call, by the configuration's entry
# ---------------------------------------------------------------------------


def _local(mesh, size):
    """The (P*P, m, m) float32 block-cyclic stack both entries take."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    m = size.n // size.grid
    return jax.ShapeDtypeStruct(
        (size.grid * size.grid, m, m), jnp.float32,
        sharding=NamedSharding(mesh, P(("rows", "cols"), None, None)))


def _hpl(cell, mesh):
    """``make_factorize`` with the program's defaults, as the HPL driver's
    setup builds it."""
    from repro.core.hpl import make_factorize
    s = cell.size
    return (make_factorize(mesh, pg=s.grid, nb=s.n // s.b, b=s.b),
            (_local(mesh, s),))


def _ptrans(cell, mesh):
    """``make_step`` with the engine's chunk count for the local payload,
    as the PTRANS driver's setup builds it."""
    from repro.comm.callsites import PTRANS_EXCHANGE
    from repro.comm.engine import CollectiveEngine
    from repro.core.ptrans import make_step
    s = cell.size
    engine = CollectiveEngine.for_mesh(mesh)
    nchunks = max(int(engine.pipeline_chunks(
        "grid_transpose", nbytes=(s.n // s.grid) ** 2 * 4,
        axis=("rows", "cols"), callsite=PTRANS_EXCHANGE)), 1)
    arg = _local(mesh, s)
    return make_step(mesh, s.grid, engine, nchunks=nchunks), (arg, arg)


PROGRAMS = {"hpl": _hpl, "ptrans": _ptrans}


def program_hlo(cell) -> str:
    """The compiled HLO text of the cell's timed call on its chips."""
    import jax
    mesh = cell.driver.mesh(cell.size, jax.devices()[:cell.chips])
    fn, args = PROGRAMS[cell.config["entry"]](cell, mesh)
    return fn.lower(*args).compile().as_text()


def scoped(ctx) -> Scoped | None:
    """The metric context's trace with each op's phase, or None where the
    program has no registry, the entry no ``PROGRAMS`` item, or no op a
    registered scope. Built once per context, which the harness hands to
    every metric of a run, so they share one compile."""
    if "scoped" not in vars(ctx):
        vars(ctx)["scoped"] = _build(ctx)
    return vars(ctx)["scoped"]


def _build(ctx) -> Scoped | None:
    names = registry()
    if names is None or ctx.cell.config["entry"] not in PROGRAMS:
        return None
    t0 = time.perf_counter()
    try:
        paths = op_paths(program_hlo(ctx.cell))
    except Exception:  # noqa: BLE001 — a metric reads nothing, the run goes on
        print("bench scopes: no op_name map", file=sys.stderr, flush=True)
        traceback.print_exc()
        return None
    print(f"bench scopes_s={time.perf_counter() - t0!r} "
          f"instructions={len(paths)}", file=sys.stderr, flush=True)
    if not any(phase_of(p, names) != UNATTRIBUTED for p in paths.values()):
        return None
    sc = Scoped(trace=ctx.trace, paths=paths, names=names)
    _log_phases(sc, ctx.calls)
    return sc


def attribution(sc: Scoped, calls: int) -> dict:
    """Where the busy time went by scope: device seconds per call of each
    phase (averaged over devices), the share of busy time in a registered
    scope (op time outside one over the union of op intervals), and the
    largest unattributed ops, seconds per call."""
    phases = by_phase(sc)
    busy = tr.busy_ns(sc.trace)
    busy_s = sum(busy.values()) / len(busy) / 1e9
    per = max(calls, 1)
    return {"phases": {ph: s / per for ph, s in phases.items()},
            "attributed_pct": (100.0 * (1 - phases.get(UNATTRIBUTED, 0.0)
                                        / busy_s) if busy_s > 0 else 0.0),
            "unattributed_ops": [[n, s / per]
                                 for n, s in unattributed_ops(sc)]}


def _log_phases(sc: Scoped, calls: int):
    a = attribution(sc, calls)
    print("bench phases_s_per_call "
          + " ".join(f"{ph}={s!r}" for ph, s in a["phases"].items()),
          file=sys.stderr, flush=True)
    print(f"bench attributed_pct={a['attributed_pct']!r} unattributed_top="
          + ",".join(f"{n}:{s!r}" for n, s in a["unattributed_ops"]),
          file=sys.stderr, flush=True)
