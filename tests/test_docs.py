"""Doc-layer drift gates.

Two guarantees, both cheap and fully offline:

* the README's "Callsite tag registry" table is a faithful rendering of
  :data:`repro.comm.callsites.CALLSITES` — same tags, same ops, same
  owning modules, pairing claims that exist in :mod:`repro.comm.autotune`
  — and every constant really is imported and used by its owning module;
* every relative markdown link and anchor in README.md / ROADMAP.md /
  docs/*.md resolves (tools/check_md_links.py).
"""
from __future__ import annotations

import importlib
import importlib.util
import inspect
import os
import re

from repro.comm import callsites as CS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
README = os.path.join(REPO, "README.md")


# ---------------------------------------------------------------------------
# README table <-> CALLSITES
# ---------------------------------------------------------------------------


def _registry_table_rows():
    """Parse the '### Callsite tag registry' table into
    {tag: (op, module, pairing_cell)}."""
    with open(README, encoding="utf-8") as f:
        text = f.read()
    m = re.search(r"#+\s*Callsite tag registry(.*?)(?:\n#|\Z)", text,
                  re.DOTALL)
    assert m, "README is missing the 'Callsite tag registry' section"
    rows = {}
    for line in m.group(1).splitlines():
        if not line.strip().startswith("|"):
            continue
        cells = [c.strip().strip("`") for c in line.strip().strip("|")
                 .split("|")]
        if len(cells) < 4 or cells[0] in ("tag", "") or set(cells[0]) <= {"-"}:
            continue
        rows[cells[0]] = (cells[1], cells[2], cells[3])
    return rows


def test_readme_table_matches_registry():
    rows = _registry_table_rows()
    assert set(rows) == set(CS.CALLSITES), (
        f"README table rows {sorted(rows)} != registry tags "
        f"{sorted(CS.CALLSITES)}")
    for tag, (op, module, pairing) in rows.items():
        cs = CS.CALLSITES[tag]
        assert op == cs.op, (tag, op, cs.op)
        assert module == cs.module, (tag, module, cs.module)
        if cs.tuned is None:
            assert "fallback" in pairing or "untagged" in pairing, (
                f"{tag}: registry says untagged fallback, table says "
                f"{pairing!r}")
        else:
            assert cs.tuned in pairing, (
                f"{tag}: table pairing {pairing!r} does not name the "
                f"measured pattern {cs.tuned!r}")


def test_constants_used_by_owning_modules():
    """Each tag's constant is imported from repro.comm.callsites by its
    owning module and actually used there — a renamed or orphaned tag
    fails here, not silently at tuning time."""
    for tag, cs in CS.CALLSITES.items():
        assert getattr(CS, cs.const) == tag, (cs.const, tag)
        mod = importlib.import_module(cs.module)
        src = inspect.getsource(mod)
        assert re.search(r"from repro\.comm\.callsites import", src), (
            f"{cs.module} does not import from repro.comm.callsites")
        assert re.search(rf"\b{cs.const}\b", src), (
            f"constant {cs.const} ({tag!r}) unused in {cs.module}")
        assert f'"{tag}"' not in src.replace(f'"{cs.op}@{tag}"', ""), (
            f"{cs.module} inlines the literal {tag!r} instead of "
            f"using {cs.const}")


def test_tuned_patterns_exist_in_autotune():
    """Every `tuned` claim maps to a real autotune pattern: the key is in
    autotune_mesh's default op list, and when a tag inherits a paired
    measurement, PAIRED_ALIASES really aliases it."""
    from repro.comm.autotune import PAIRED_ALIASES, autotune_mesh

    default_ops = inspect.signature(autotune_mesh).parameters["ops"].default
    for tag, cs in CS.CALLSITES.items():
        if cs.tuned is None:
            continue
        assert cs.tuned in default_ops, (
            f"{tag}: measured pattern {cs.tuned!r} is not in "
            f"autotune_mesh's default ops {default_ops}")
        own_key = f"{cs.op}@{tag}"
        if cs.tuned != own_key:
            assert own_key in PAIRED_ALIASES.get(cs.tuned, ()), (
                f"{tag}: inherits {cs.tuned!r} but PAIRED_ALIASES does "
                f"not alias {own_key!r} to it")


# ---------------------------------------------------------------------------
# README scope table <-> repro.scopes
# ---------------------------------------------------------------------------


def _scope_table_rows():
    """Parse the '### Scope registry' table into {scope: owning module}."""
    with open(README, encoding="utf-8") as f:
        text = f.read()
    m = re.search(r"#+\s*Scope registry(.*?)(?:\n#|\Z)", text, re.DOTALL)
    assert m, "README is missing the 'Scope registry' section"
    rows = {}
    for line in m.group(1).splitlines():
        if not line.strip().startswith("|"):
            continue
        cells = [c.strip().strip("`") for c in line.strip().strip("|")
                 .split("|")]
        if len(cells) < 3 or cells[0] in ("scope", "") \
                or set(cells[0]) <= {"-"}:
            continue
        rows[cells[0]] = cells[-1]
    return rows


def test_readme_scope_table_matches_registry():
    from repro import scopes as SC
    rows = _scope_table_rows()
    engine_row = SC.engine_scope("<op>", "<callsite>")
    assert set(rows) == set(SC.PHASES) | {engine_row}, (
        f"README scope rows {sorted(rows)} != registry phases "
        f"{sorted(SC.PHASES)} + {engine_row!r}")
    for name, module in rows.items():
        want = (SC.ENGINE_MODULE if name == engine_row
                else SC.PHASES[name].module)
        assert module == want, (name, module, want)


def test_scope_constants_wrap_code_in_owning_modules():
    """Each phase constant is imported from repro.scopes by its owning
    module and opens a named scope there; the engine applies its rule in
    one helper that every public op goes through."""
    from repro import scopes as SC
    for name, scope in SC.PHASES.items():
        assert getattr(SC, scope.const) == name, (scope.const, name)
        src = inspect.getsource(importlib.import_module(scope.module))
        assert re.search(r"from repro\.scopes import", src), scope.module
        assert re.search(rf"jax\.named_scope\({scope.const}\)", src), (
            f"{scope.module} opens no named scope {scope.const}")
        assert f'"{name}"' not in src, (
            f"{scope.module} inlines the literal {name!r}")
    from repro.comm.engine import CollectiveEngine
    for op in SC.ENGINE_OPS:
        assert getattr(CollectiveEngine, op).__wrapped__.__name__ == op


def test_every_callsite_tag_has_engine_scopes():
    from repro import scopes as SC
    names = SC.registered()
    for tag in (*CS.CALLSITES, None):
        for op in SC.ENGINE_OPS:
            assert names[SC.engine_scope(op, tag)] == SC.ENGINE_MODULE
    assert SC.engine_scope("bcast", CS.HPL_PANEL) == "comm.bcast:hpl.panel"
    assert SC.engine_scope("allreduce") == "comm.allreduce:untagged"
    assert all("@" not in n and "/" not in n for n in names)


# ---------------------------------------------------------------------------
# resilience docs <-> code
# ---------------------------------------------------------------------------


def _section(path, heading_re):
    with open(path, encoding="utf-8") as f:
        text = f.read()
    m = re.search(heading_re + r"(.*?)(?:\n## |\Z)", text, re.DOTALL)
    assert m, f"{os.path.relpath(path, REPO)} is missing {heading_re!r}"
    return m.group(1)


def test_architecture_resilience_section_names_real_api():
    """ARCHITECTURE.md §8 must keep naming the symbols it documents, and
    every one of them must still exist where the section says it lives."""
    sec = _section(os.path.join(REPO, "docs", "ARCHITECTURE.md"),
                   r"## 8\. Resilience")
    symbols = {
        "repro.comm.faults": ["FaultInjector", "LinkFault", "FaultSchedule",
                              "degrade_window", "hardware_view", "injected",
                              "extra_time", "sleep"],
        "repro.comm.retune": ["RetuneController", "RetuneEvent",
                              "on_straggler", "hw_probe"],
        "repro.train.straggler": ["StragglerMonitor", "POLICIES"],
    }
    for module, names in symbols.items():
        mod = importlib.import_module(module)
        src = inspect.getsource(mod)
        for name in names:
            assert name in sec, f"ARCHITECTURE §8 no longer mentions {name}"
            assert re.search(rf"\b{name}\b", src), (
                f"§8 documents {name} but {module} no longer defines/uses it")
    # the engine hook the whole section pivots on
    from repro.comm.engine import CollectiveEngine
    assert "invalidate_resolutions" in sec
    assert callable(CollectiveEngine.invalidate_resolutions)
    # the documented straggler policies are the real ones
    from repro.train.straggler import POLICIES
    for policy in POLICIES:
        assert f"`{policy}`" in sec, f"§8 does not document policy {policy!r}"
    # the documented serve finish reasons exist in the scheduler contract
    import repro.serve.scheduler as sched
    for reason in ("timeout", "rejected"):
        assert f'"{reason}"' in sec
        assert f'"{reason}"' in inspect.getsource(sched)


def test_readme_resilience_quickstart_executes():
    """The README's fault-injection quickstart is executable as written —
    including its asserts, so the documented chain -> staged -> chain flip
    is re-proven against the live cost model on every run."""
    sec = _section(README, r"## Resilience")
    m = re.search(r"```python\n(.*?)```", sec, re.DOTALL)
    assert m, "README Resilience section lost its python quickstart"
    exec(compile(m.group(1), "README.md#resilience", "exec"), {})


def test_readme_failover_quickstart_executes():
    """The README's dead-link quickstart is executable as written —
    including its asserts, so the documented chain -> chain_rooted ->
    chain reroute and the route-exclusion proof are re-proven against the
    live cost model on every run."""
    sec = _section(README, r"## Failover")
    m = re.search(r"```python\n(.*?)```", sec, re.DOTALL)
    assert m, "README Failover section lost its python quickstart"
    exec(compile(m.group(1), "README.md#failover", "exec"), {})


# ---------------------------------------------------------------------------
# markdown links
# ---------------------------------------------------------------------------


def _load_checker():
    path = os.path.join(REPO, "tools", "check_md_links.py")
    spec = importlib.util.spec_from_file_location("check_md_links", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_markdown_links_resolve():
    checker = _load_checker()
    problems = []
    for f in checker.default_files():
        problems += [(os.path.relpath(f, REPO), link, why)
                     for link, why in checker.check_file(f)]
    assert not problems, f"broken markdown links: {problems}"
