"""Documentation gate (wired into scripts/smoke.sh).

Two checks, both fast and dependency-free:

1. **Docstring audit** — every public module / class / function /
   method of the public API surface (the modules listed in
   ``API_MODULES``) carries a docstring.  "Public" = name does not start
   with an underscore and the object is *defined* in that module (re-
   exports are the defining module's responsibility).  This is the
   enforcement half of the PR-4 docstring audit: shapes, packed-domain
   conventions, and determinism guarantees live in docstrings, so a
   missing docstring is a missing contract.

2. **Doc snippet import-check** — every ```python fenced block in
   README.md, DESIGN.md, and docs/*.md must (a) parse and (b) have its
   top-level ``import`` / ``from`` statements actually execute, so code
   snippets cannot silently rot as modules move.  Snippet bodies are NOT
   executed (they may train models / write files); imports are the part
   that goes stale.

Run:  PYTHONPATH=src python scripts/check_docs.py
Exit status 0 on success; prints every violation otherwise.
"""

from __future__ import annotations

import ast
import inspect
import importlib
import re
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]

#: the public API surface held to the docstring bar
API_MODULES = [
    "repro.pipeline",
    "repro.spec",
    "repro.deploy",
    "repro.serve.picbnn",
    "repro.serve.scheduler",
    "repro.obs",
    "repro.core.physics",
    "repro.core.binarize",
    "repro.core.bnn",
    "repro.core.convnet",
    "repro.core.ensemble",
    "repro.core.mapping",
    "repro.kernels.fused_mlp",
    "repro.kernels.fused_conv",
    "repro.kernels.ref",
    "repro.configs.paper_mlp",
    "repro.configs.paper_cnn",
    "repro.data.synthetic",
]

#: documentation files whose ```python blocks are import-checked
DOC_FILES = ["README.md", "DESIGN.md"]


def _missing_docstrings(mod) -> list[str]:
    """Names in `mod` (module, public defs, public methods) lacking docs."""
    bad = []
    if not (mod.__doc__ or "").strip():
        bad.append(f"{mod.__name__} (module)")
    for name, obj in vars(mod).items():
        if name.startswith("_"):
            continue
        if not (inspect.isclass(obj) or inspect.isfunction(obj)):
            continue
        if getattr(obj, "__module__", None) != mod.__name__:
            continue  # re-export: audited at its defining module
        if not (inspect.getdoc(obj) or "").strip():
            bad.append(f"{mod.__name__}.{name}")
        if inspect.isclass(obj):
            for mname, meth in vars(obj).items():
                if mname.startswith("_"):
                    continue
                fn = meth
                if isinstance(meth, (staticmethod, classmethod)):
                    fn = meth.__func__
                elif isinstance(meth, property):
                    fn = meth.fget
                if not inspect.isfunction(fn):
                    continue
                if not (inspect.getdoc(fn) or "").strip():
                    bad.append(f"{mod.__name__}.{name}.{mname}")
    return bad


_FENCE = re.compile(r"```python\n(.*?)```", re.DOTALL)


def _snippet_errors(path: Path) -> list[str]:
    """Syntax + import errors in a doc file's ```python blocks."""
    errors = []
    text = path.read_text()
    for i, block in enumerate(_FENCE.findall(text), 1):
        where = f"{path.name} python block #{i}"
        try:
            tree = ast.parse(block)
        except SyntaxError as e:
            errors.append(f"{where}: syntax error: {e}")
            continue
        imports = [
            node for node in tree.body
            if isinstance(node, (ast.Import, ast.ImportFrom))
        ]
        for node in imports:
            src = ast.unparse(node)
            try:
                exec(compile(ast.Module([node], []), where, "exec"), {})
            except Exception as e:
                errors.append(f"{where}: `{src}` failed: {e}")
    return errors


def main() -> int:
    """Run both gates; print violations; return a process exit status."""
    failures = []
    for name in API_MODULES:
        try:
            mod = importlib.import_module(name)
        except Exception as e:
            failures.append(f"cannot import {name}: {e}")
            continue
        failures += [f"missing docstring: {n}"
                     for n in _missing_docstrings(mod)]

    doc_paths = [REPO_ROOT / f for f in DOC_FILES]
    doc_paths += sorted((REPO_ROOT / "docs").glob("*.md"))
    n_files = 0
    for path in doc_paths:
        if not path.exists():
            failures.append(f"missing documentation file: {path.name}")
            continue
        n_files += 1
        failures += _snippet_errors(path)

    if failures:
        print(f"check_docs: {len(failures)} violation(s)")
        for f in failures:
            print(f"  - {f}")
        return 1
    print(f"check_docs OK: {len(API_MODULES)} modules audited, "
          f"{n_files} doc files snippet-checked")
    return 0


if __name__ == "__main__":
    sys.exit(main())
