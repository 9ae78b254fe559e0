#!/usr/bin/env python
"""Docs-consistency check (CI gate).

Fails if:
  * any `DESIGN.md §<sec>` / `EXPERIMENTS.md §<sec>` reference in `src/`
    or `tools/` cites a file or section heading that does not exist (continuations like "EXPERIMENTS.md §Tracing and §Decode"
    count, and the § may land on the next line of a wrapped docstring);
  * any file mentioning DESIGN.md / EXPERIMENTS.md exists while the cited
    doc is missing from the repo root;
  * README.md's workload table is stale (it is generated:
    `python -m repro.prim.registry`) or the Docs map links are missing.

Run from the repo root:  PYTHONPATH=src python tools/check_docs.py
"""
from __future__ import annotations

import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

REF = re.compile(r"(DESIGN|EXPERIMENTS)\.md\s*(§[\w-]+(?:\s+and\s+§[\w-]+)*)?")
TOKEN = re.compile(r"§([\w-]+)")

errors: list[str] = []


def headings(doc: str) -> list[str]:
    path = ROOT / doc
    if not path.exists():
        return []
    return [line.strip() for line in path.read_text().splitlines()
            if line.startswith("##")]


def check_ref(doc: str, sec: str, where: str) -> None:
    if not (ROOT / doc).exists():
        errors.append(f"{where}: cites {doc}, which does not exist")
        return
    heads = headings(doc)
    if doc == "DESIGN.md":
        ok = any(re.match(rf"##\s+§{re.escape(sec)}\b", h) for h in heads)
    else:   # EXPERIMENTS.md: named sections, e.g. §Perf -> "## Perf"
        ok = any(sec.lower() in h.lower() for h in heads)
    if not ok:
        errors.append(f"{where}: cites {doc} §{sec}, but no matching "
                      f"'## ...' heading exists in {doc}")


def scan_sources() -> None:
    for tree in ("src", "tools"):
        for py in sorted((ROOT / tree).rglob("*.py")):
            text = py.read_text()
            rel = py.relative_to(ROOT)
            for m in REF.finditer(text):
                doc = f"{m.group(1)}.md"
                if not (ROOT / doc).exists():
                    errors.append(f"{rel}: mentions {doc}, "
                                  "which does not exist")
                    continue
                for sec in TOKEN.findall(m.group(2) or ""):
                    check_ref(doc, sec, str(rel))


def check_readme() -> None:
    readme = (ROOT / "README.md").read_text()
    begin, end = "<!-- registry-table:begin -->", "<!-- registry-table:end -->"
    if begin not in readme or end not in readme:
        errors.append("README.md: missing registry-table markers")
    else:
        from repro.prim.registry import markdown_table
        embedded = readme.split(begin)[1].split(end)[0].strip()
        if embedded != markdown_table().strip():
            errors.append("README.md: workload table is stale — regenerate "
                          "with `PYTHONPATH=src python -m repro.prim."
                          "registry` and paste between the markers")
    for doc in ("DESIGN.md", "EXPERIMENTS.md", "CHANGES.md"):
        if f"({doc})" not in readme:
            errors.append(f"README.md: Docs map must link {doc}")


def main() -> int:
    scan_sources()
    check_readme()
    if errors:
        print("docs-consistency FAILED:")
        for e in errors:
            print(f"  - {e}")
        return 1
    print("docs-consistency OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
