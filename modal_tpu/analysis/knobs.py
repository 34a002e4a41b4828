"""Env-knob passes.

``knob-parity`` — every literal ``MODAL_TPU_*`` string in the package must
be declared in ``knob_catalog.py`` (type/default/doc pointer), and every
explicitly declared knob must still appear as a literal somewhere: dead
catalog entries fail too. Same discipline as SPAN_CATALOG (new code can't
ship observability names the tooling never heard of), applied to the
configuration surface.

``degradation-symmetry`` — every knob the catalog marks ``feature_gate``
must have a grep-able test line toggling it OFF, so "every rung
individually degradable" (docs/DISPATCH.md, docs/SERVING.md) stays true by
construction instead of by memory.
"""

from __future__ import annotations

import os
import re
from typing import Optional

from . import knob_catalog
from .core import (
    AnalysisContext,
    AnalysisPass,
    Finding,
    SourceModule,
    register,
)

KNOB_RE = re.compile(r"MODAL_TPU_[A-Z0-9_]+")
CATALOG_RELPATH = "analysis/knob_catalog.py"

# knob family owned by out-of-package tooling (bench.py orchestration): it
# never appears in modal_tpu/ and is not part of the product configuration
# surface this catalog governs
_EXTERNAL_PREFIXES = ("MODAL_TPU_BENCH_",)


def collect_knob_literals(modules: list[SourceModule]) -> dict[str, list[tuple[str, int]]]:
    """knob name -> [(relpath, line)] for every literal occurrence. Tokens
    ending in '_' are prefix fragments (``startswith`` checks), not knobs.
    The analysis package itself is excluded — the catalog naming every knob
    must not make the usage scan vacuously true."""
    out: dict[str, list[tuple[str, int]]] = {}
    for mod in modules:
        if mod.relpath.startswith("analysis/"):
            continue
        for node in mod.index.strings:
            for m in KNOB_RE.finditer(node.value):
                name = m.group(0)
                if name.endswith("_") or name == "MODAL_TPU":
                    continue
                if name.startswith(_EXTERNAL_PREFIXES):
                    continue
                out.setdefault(name, []).append((mod.relpath, node.lineno))
    return out


def _catalog_line(modules: list[SourceModule], name: str) -> tuple[str, int]:
    """(relpath, line) of a knob's declaration in the catalog module (falls
    back to line 1 so findings stay anchored even if the lookup misses)."""
    for mod in modules:
        if mod.relpath == CATALOG_RELPATH:
            for lineno, line in enumerate(mod.text.splitlines(), 1):
                if f'"{name}"' in line:
                    return mod.relpath, lineno
            return mod.relpath, 1
    return CATALOG_RELPATH, 1


def knob_parity_findings(
    modules: list[SourceModule],
    catalog: Optional[dict] = None,
    declared: Optional[dict] = None,
) -> list[Finding]:
    catalog = knob_catalog.KNOB_CATALOG if catalog is None else catalog
    declared = (knob_catalog.declared_knobs() if declared is None else declared)
    literals = collect_knob_literals(modules)
    findings: list[Finding] = []
    for name in sorted(set(literals) - set(declared)):
        path, line = literals[name][0]
        findings.append(
            Finding(
                rule="knob-parity",
                path=path,
                line=line,
                scope="<module>",
                token=name,
                message=(
                    f"env knob `{name}` is read here but not declared in "
                    f"analysis/knob_catalog.py ({len(literals[name])} occurrence(s))"
                ),
                hint="declare it with type/default/doc in knob_catalog.py (and docs/ANALYSIS.md regenerates)",
            )
        )
    for name in sorted(set(catalog) - set(literals)):
        path, line = _catalog_line(modules, name)
        findings.append(
            Finding(
                rule="knob-parity",
                path=path,
                line=line,
                scope="KNOB_CATALOG",
                token=name,
                message=f"catalog declares `{name}` but no literal in the package reads it (dead knob)",
                hint="retire the entry, or wire the knob back up",
            )
        )
    return findings


def _run_knob_parity(modules: list[SourceModule], ctx: AnalysisContext) -> list[Finding]:
    # foreign trees (lint --src-root over a fixture package) carry no knob
    # catalog — there is no contract to enforce, so the pass is a no-op
    if not any(m.relpath == CATALOG_RELPATH for m in modules):
        return []
    return knob_parity_findings(modules)


register(
    AnalysisPass(
        rule="knob-parity",
        description="every literal MODAL_TPU_* knob declared in knob_catalog.py; no dead entries",
        hint="keep knob_catalog.py in lockstep with the code",
        run=_run_knob_parity,
    )
)

# --------------------------------------------------------------------------
# degradation-symmetry
# --------------------------------------------------------------------------

# a line toggles a knob OFF when the knob name is followed (same line) by an
# off-ish value, or the line deletes it from the env
_OFF_VALUE_RE = re.compile(r"""["'](0|false|no|off)["']|=\s*(0|false|no|off)\b""")


def _line_toggles_off(line: str) -> bool:
    return bool(_OFF_VALUE_RE.search(line)) or "delenv" in line or ".pop(" in line


def iter_test_files(tests_root: str) -> list[str]:
    out = []
    for dirpath, dirs, files in os.walk(tests_root):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        out.extend(os.path.join(dirpath, f) for f in sorted(files) if f.endswith(".py"))
    return out


def degradation_findings(
    modules: list[SourceModule],
    tests_root: Optional[str],
    gates: Optional[dict] = None,
) -> list[Finding]:
    gates = knob_catalog.feature_gates() if gates is None else gates
    if not gates:
        return []
    toggled: set[str] = set()
    if tests_root and os.path.isdir(tests_root):
        for path in iter_test_files(tests_root):
            with open(path, encoding="utf-8") as f:
                for line in f:
                    if "MODAL_TPU_" not in line or not _line_toggles_off(line):
                        continue
                    for m in KNOB_RE.finditer(line):
                        toggled.add(m.group(0))
    findings: list[Finding] = []
    for name in sorted(set(gates) - toggled):
        path, line = _catalog_line(modules, name)
        findings.append(
            Finding(
                rule="degradation-symmetry",
                path=path,
                line=line,
                scope="KNOB_CATALOG",
                token=name,
                message=(
                    f"feature gate `{name}` has no test toggling it off under tests/ — "
                    f"'individually degradable' is unproven for this rung"
                ),
                hint="add a test that sets the knob to 0/off and asserts the degraded path",
            )
        )
    return findings


def _run_degradation(modules: list[SourceModule], ctx: AnalysisContext) -> list[Finding]:
    if not any(m.relpath == CATALOG_RELPATH for m in modules):
        return []  # foreign tree: no catalog, no gate contract (see above)
    return degradation_findings(modules, ctx.tests_root)


register(
    AnalysisPass(
        rule="degradation-symmetry",
        description="every cataloged feature-gate knob has a grep-able off-toggle test",
        hint="write the off-path test before shipping the gate",
        run=_run_degradation,
    )
)
