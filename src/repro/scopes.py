"""Central registry of named scopes.

The program runs each phase of its benchmarks under a stable
``jax.named_scope`` name. The names land in every HLO instruction's
``metadata={op_name=".../<scope>/..."}``, so a reducer can map a device op
of a profile to the phase it belongs to (the innermost registered scope in
its path), whatever the kernel or fusion that implements the phase is
called. Scopes are metadata only: they do not change the compiled program.

Two kinds of name:

- **phases**, one constant each below, wrapped around the code of their
  owning module (:data:`PHASES` gives each name's owner);
- **engine scopes**, one rule: every public op of
  :class:`~repro.comm.engine.CollectiveEngine` runs its lowering under
  ``comm.<op>:<callsite>`` (:func:`engine_scope`), ``untagged`` when the
  call gives no callsite. The names are derived from the callsite registry
  (:func:`registered`), so every registered tag has one per engine op. The
  separator is not ``@`` (the ``op@callsite`` of tuning-table keys): XLA
  cuts a location name at ``@`` when it writes ``op_name``, which would
  drop the tag and the op's own name from every instruction in the scope.

A compute phase nested inside an engine scope (PTRANS's transpose-add as
the ``consume`` of ``pipelined``) is the innermost scope of its ops, so it
counts as compute.

This module is import-free on purpose, like :mod:`repro.comm.callsites`:
the engine and the benchmarks import its constants without cycles. The
README's "Scope registry" table mirrors :data:`PHASES` and
``tests/test_docs.py`` cross-checks the two.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

# -- phase constants (wrap the code in ``jax.named_scope(<constant>)``) -----

HPL_FACTOR = "hpl.factor"        # diagonal-block LU and its slice
HPL_TRSM = "hpl.trsm"            # both TRSMs, their masks, the panel slices
HPL_UPDATE = "hpl.update"        # the trailing GEMM update
HPL_STRIP = "hpl.strip"          # lookahead strip GEMMs (_strip_panels)
HPL_WRITEBACK = "hpl.writeback"  # the three panel write-backs
PTRANS_TRANSPOSE_ADD = "ptrans.transpose_add"  # C = B + A^T on the device

# -- the engine rule --------------------------------------------------------

ENGINE_MODULE = "repro.comm.engine"
UNTAGGED = "untagged"
# the public ops of CollectiveEngine, each run under engine_scope(op, tag)
ENGINE_OPS = ("bcast", "all_to_all_tiles", "allreduce", "allreduce_tree",
              "ring_exchange", "grid_transpose", "pipelined")


@dataclass(frozen=True)
class Scope:
    """Owner of one phase name: ``module`` wraps its code in the scope and
    imports ``const`` from this module."""
    module: str
    const: str


PHASES: Dict[str, Scope] = {
    HPL_FACTOR: Scope("repro.core.hpl", "HPL_FACTOR"),
    HPL_TRSM: Scope("repro.core.hpl", "HPL_TRSM"),
    HPL_UPDATE: Scope("repro.core.hpl", "HPL_UPDATE"),
    HPL_STRIP: Scope("repro.core.hpl", "HPL_STRIP"),
    HPL_WRITEBACK: Scope("repro.core.hpl", "HPL_WRITEBACK"),
    PTRANS_TRANSPOSE_ADD: Scope("repro.core.ptrans", "PTRANS_TRANSPOSE_ADD"),
}


def engine_scope(op: str, callsite: Optional[str] = None) -> str:
    """The scope an engine op runs under: ``comm.<op>:<callsite>``."""
    return f"comm.{op}:{callsite or UNTAGGED}"


def registered() -> Dict[str, str]:
    """Every registered scope name -> its owning module: the phases, and
    the engine scope of every engine op under every callsite tag."""
    from repro.comm.callsites import CALLSITES
    out = {name: s.module for name, s in PHASES.items()}
    for tag in (*CALLSITES, None):
        for op in ENGINE_OPS:
            out[engine_scope(op, tag)] = ENGINE_MODULE
    return out
