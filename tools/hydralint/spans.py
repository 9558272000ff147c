"""HL008 — span discipline.

The request-tracing layer (``repro.core.tracing``) only yields a usable
conservation invariant if call sites follow three rules:

  * ``ctx.span(...)`` is a context manager: the span's end timestamp is
    taken in ``__exit__``, so a bare call (``ctx.span("x")``) times
    nothing and silently records a zero-length phase.  Cross-thread
    waits that cannot be a ``with`` block use ``add_span(name, t0, t1)``
    with two explicit timestamps instead.
  * Span names come from the closed ``PHASES`` registry in
    ``core/tracing.py`` — an ad-hoc name would aggregate into nothing
    (``summary()`` emits the fixed vocabulary) and break the
    ``BENCH_trace.json`` key-shape gate.  Host phases opened with
    ``phase(...)`` (profiler spans ``hydra.<name>``) follow the same two
    rules against the ``HOST_PHASES`` registry: a ``with`` block, and a
    registered name, which is what the benchmark's readers look for.
  * Sim code (the HL003 scope) never traces: the simulator models
    phases, it does not measure them, and a tracing import there would
    couple the deterministic event loop to wall-clock span timestamps.

Both registries are read from the AST of ``src/repro/core/tracing.py``
itself (from the project when linted, else from disk under the project
root) so this checker can never drift from the vocabulary it enforces.
``core/tracing.py`` is exempt — it defines the machinery.
"""
from __future__ import annotations

import ast
from pathlib import Path

from tools.hydralint import Finding, Project, str_const
from tools.hydralint.determinism import _is_sim_file

CODE = "HL008"

TRACING_PATH = "src/repro/core/tracing.py"
TRACING_MODULE = "repro.core.tracing"
# methods of RequestTrace/_NullTrace that take a phase name first
NAMED_METHODS = ("span", "add_span")
# the host-phase helper, called bare or as ``tracing.phase``
HOST_HELPER = "phase"


def _load_phases(project: Project, registry: str = "PHASES"):
    """The ``registry`` tuple (``PHASES`` or ``HOST_PHASES``) from
    core/tracing.py — from the parsed project when tracing.py is among
    the lint roots, else parsed off disk. None when unavailable
    (registry checks are skipped rather than guessed)."""
    sf = project.by_path.get(TRACING_PATH)
    tree = sf.tree if sf is not None else None
    if tree is None:
        p = Path(project.root) / TRACING_PATH
        try:
            tree = ast.parse(p.read_text(), filename=TRACING_PATH)
        except (OSError, SyntaxError):
            return None
    for node in tree.body:
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == registry
                   for t in node.targets):
            continue
        if isinstance(node.value, (ast.Tuple, ast.List)):
            names = [str_const(e) for e in node.value.elts]
            if all(n is not None for n in names):
                return frozenset(names)
    return None


def _is_host_phase_call(node) -> bool:
    f = node.func
    return ((isinstance(f, ast.Name) and f.id == HOST_HELPER)
            or (isinstance(f, ast.Attribute) and f.attr == HOST_HELPER))


def check(project: Project) -> list:
    phases = _load_phases(project)
    host_phases = _load_phases(project, "HOST_PHASES")
    findings = []
    for sf in project.files:
        if sf.path.endswith("core/tracing.py"):
            continue
        sim = _is_sim_file(sf)
        if sim:
            findings.extend(_check_sim_imports(sf))
        # calls that ARE a with-item context expression are compliant
        # context-manager uses; collect their identities first
        with_calls = set()
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    if isinstance(item.context_expr, ast.Call):
                        with_calls.add(id(item.context_expr))
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call) and _is_host_phase_call(node):
                findings.extend(_check_host_phase(
                    sf, node, host_phases, sim, id(node) in with_calls))
                continue
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in NAMED_METHODS):
                continue
            meth = node.func.attr
            if sim:
                findings.append(Finding(
                    CODE, sf.path, node.lineno, node.col_offset,
                    f".{meth}() tracing call in sim code — the simulator "
                    f"models phases, it must not measure them (HL003 "
                    f"scope)",
                    f"sim-tracing:{meth}:L{node.lineno}"))
                continue
            name = str_const(node.args[0]) if node.args else None
            if name is not None and phases is not None \
                    and name not in phases:
                findings.append(Finding(
                    CODE, sf.path, node.lineno, node.col_offset,
                    f"span name {name!r} is not in the PHASES registry "
                    f"(core/tracing.py) — ad-hoc names break the "
                    f"fixed-vocabulary aggregation",
                    f"unknown-phase:{name}"))
            if meth == "span" and id(node) not in with_calls:
                findings.append(Finding(
                    CODE, sf.path, node.lineno, node.col_offset,
                    f".span({name!r}) must be used as a context manager "
                    f"(with ctx.span(...) as sp:) — a bare call never "
                    f"records the end timestamp; for cross-thread waits "
                    f"use add_span(name, t0, t1)",
                    f"bare-span:{name}:L{node.lineno}"))
    return findings


def _check_host_phase(sf, node, host_phases, sim: bool,
                      in_with: bool) -> list:
    if sim:
        return [Finding(
            CODE, sf.path, node.lineno, node.col_offset,
            "phase() tracing call in sim code — the simulator models "
            "phases, it must not measure them (HL003 scope)",
            f"sim-tracing:phase:L{node.lineno}")]
    out = []
    name = str_const(node.args[0]) if node.args else None
    if name is not None and host_phases is not None \
            and name not in host_phases:
        out.append(Finding(
            CODE, sf.path, node.lineno, node.col_offset,
            f"host phase {name!r} is not in the HOST_PHASES registry "
            f"(core/tracing.py) — the profiler span hydra.{name} would "
            f"be charged to nothing",
            f"unknown-host-phase:{name}"))
    if not in_with:
        out.append(Finding(
            CODE, sf.path, node.lineno, node.col_offset,
            f"phase({name!r}) must be used as a context manager "
            f"(with phase(...):) — a bare call opens no profiler span",
            f"bare-phase:{name}:L{node.lineno}"))
    return out


def _check_sim_imports(sf) -> list:
    findings = []
    for node in ast.walk(sf.tree):
        bad = None
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.startswith(TRACING_MODULE):
                    bad = alias.name
        elif isinstance(node, ast.ImportFrom):
            if node.module and node.module.startswith(TRACING_MODULE):
                bad = node.module
        if bad is not None:
            findings.append(Finding(
                CODE, sf.path, node.lineno, node.col_offset,
                f"import of {bad} in sim code — sim modules must stay "
                f"tracing-free (deterministic event time only)",
                f"sim-import:{bad}"))
    return findings
