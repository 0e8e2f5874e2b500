"""Docs cross-reference check (scripts/check.sh).

Every ``SOMENAME.md`` mentioned anywhere under ``src/`` or ``scripts/``
(docstrings, comments) or cited by another doc under ``docs/`` must
exist — at the referenced path, at the repo root, or in ``docs/``.
Guards against dangling design-doc citations: the codebase cited
"DESIGN.md §2" for three PRs before the file existed, and doc-to-doc
links (docs/FAULTS.md ↔ docs/SERVING.md) rot just as easily.

Exit 0 and a summary line when clean; exit 1 listing every missing
reference and its citing files otherwise.
"""
from __future__ import annotations

import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
_MD_REF = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_/.-]*\.md\b")

# placeholder names, not citations: the docstring example above
_IGNORE = {"SOMENAME.md"}


def _scan_files():
    yield from sorted((ROOT / "src").rglob("*.py"))
    yield from sorted((ROOT / "scripts").rglob("*.py"))
    yield from sorted((ROOT / "docs").rglob("*.md"))


def check() -> int:
    missing: dict[str, set] = {}
    n_refs = 0
    for f in _scan_files():
        for ref in set(_MD_REF.findall(f.read_text(encoding="utf-8"))):
            if ref in _IGNORE:
                continue
            n_refs += 1
            candidates = (ROOT / ref,
                          ROOT / pathlib.Path(ref).name,
                          ROOT / "docs" / pathlib.Path(ref).name)
            if not any(c.is_file() for c in candidates):
                missing.setdefault(ref, set()).add(
                    str(f.relative_to(ROOT)))
    if missing:
        for ref, files in sorted(missing.items()):
            print(f"MISSING {ref}  (referenced by "
                  f"{', '.join(sorted(files))})")
        return 1
    print(f"docs-xref OK ({n_refs} doc references under src/, scripts/ "
          "and docs/ all resolve)")
    return 0


if __name__ == "__main__":
    sys.exit(check())
