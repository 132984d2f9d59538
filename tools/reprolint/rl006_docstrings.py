"""RL006 — the docstring audit, promoted from tests/test_docs.py.

The docs pass (DESIGN.md §7's PR) established that the architecture notes
stay load-bearing: every public function/class/method in the AUDITED
modules carries a docstring whose chain (own -> class -> module) cites a
DESIGN.md section, and every ``DESIGN.md §N`` cited anywhere in src/ must
be a real DESIGN.md heading.  Enforcing it here puts the audit in the
same diff-time gate as the other contracts; tests/test_docs.py remains a
thin wrapper that asserts this checker is clean (single source of truth:
this module owns the AUDITED list).

Static equivalents of the runtime checks:

* public = module-level ``def``/``class`` (and public methods of public
  classes) whose name has no leading underscore;
* a docstring "cites DESIGN.md" when the literal string ``DESIGN.md``
  appears in it; the chain falls back to the class docstring, then the
  module docstring;
* § citations are validated against the ``## §N`` headings of the repo's
  DESIGN.md (skipped when linting a tree with no DESIGN.md, e.g. test
  fixtures).
"""
from __future__ import annotations

import ast
import re
from typing import Iterable, Optional

from .base import Checker, Finding, Module, Project

# The audited public surface (grown per PR; see tests/test_docs.py).
AUDITED = [
    "repro.serving.engine",
    "repro.core.kv_cache",
    "repro.models.backends",
    "repro.serving.warmup",
    "repro.serving.host_loop",
    "repro.serving.loadgen",
    "repro.serving.metrics",
    "repro.serving.faults",
    "repro.core.block_pool",
    "repro.serving.tracing",
]

CITE_RE = re.compile(r"DESIGN\.md §(\w+)")


def _doc(node) -> Optional[str]:
    try:
        return ast.get_docstring(node)
    except TypeError:  # pragma: no cover
        return None


class DocstringChecker(Checker):
    code = "RL006"
    name = "docstring-audit"

    def check(self, module: Module, project: Project) -> Iterable[Finding]:
        modpath = module.module_path()
        if modpath in AUDITED:
            yield from self._audit(module)
        # §-citation validation applies to every src/ file
        if project.design_sections is not None and modpath is not None:
            for i, line in enumerate(module.source.splitlines(), start=1):
                for sec in CITE_RE.findall(line):
                    if sec not in project.design_sections:
                        yield self.finding(
                            module, i,
                            f"cites DESIGN.md §{sec}, which is not a "
                            f"DESIGN.md heading (have: "
                            f"{', '.join(sorted(project.design_sections))})")

    def _audit(self, module: Module) -> Iterable[Finding]:
        mod_doc = _doc(module.tree) or ""
        for node in module.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if node.name.startswith("_"):
                    continue
                yield from self._need(module, node, node.name, mod_doc)
            elif isinstance(node, ast.ClassDef) \
                    and not node.name.startswith("_"):
                cls_doc = _doc(node) or ""
                yield from self._need(module, node, node.name, mod_doc)
                for m in node.body:
                    if isinstance(m, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)) \
                            and not m.name.startswith("_"):
                        yield from self._need(
                            module, m, f"{node.name}.{m.name}", cls_doc)

    def _need(self, module: Module, node, qual: str, owner_doc: str
              ) -> Iterable[Finding]:
        doc = _doc(node)
        if not doc:
            yield self.finding(
                module, node,
                f"public {qual} has no docstring (audited module — "
                f"DESIGN.md §12 docstring contract)")
        elif "DESIGN.md" not in doc and "DESIGN.md" not in owner_doc:
            yield self.finding(
                module, node,
                f"docstring of {qual} cites no DESIGN.md section "
                f"(directly or via its class/module docstring)")
