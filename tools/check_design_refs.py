#!/usr/bin/env python
"""Docs contract check (stdlib-only — CI's no-deps docs lane runs it).

Three checks, all exiting non-zero with a listing on failure:

1. **Section references**: every ``DESIGN.md §n`` citation under ``src/``,
   ``tests/``, ``benchmarks/``, ``examples/``, and ``tools/`` must resolve
   to a ``§<n>`` heading in ``DESIGN.md``.
2. **Symbol coverage**: every section in ``SYMBOL_SECTIONS`` must mention
   the full public surface it owns — the module's ``__all__`` (parsed
   with ``ast``, so new exports automatically demand coverage) plus
   listed extras.  Currently §2 ↔ ``repro.core.dist_sort`` (sharded
   output, XLA local sort, shape buckets), §8 ↔ ``repro.serve.sortd`` (serving layer),
   §9 ↔ ``repro.perf`` (perf gate), §10 ↔ ``repro.serve.fleet``
   (multi-worker serving), §11 ↔ ``repro.net.faults`` (degraded
   serving), and §12 ↔ ``repro.core.workloads`` (engine workload ops).
3. **Intra-repo markdown links**: every relative ``[text](target)`` link
   in the top-level docs, ``docs/``, and ``benchmarks/README.md`` must
   point at an existing file (external ``http(s)``/``mailto`` links and
   pure ``#anchor`` links are skipped; ``#fragment`` suffixes are stripped
   before the existence check).

``tests/test_docs_refs.py`` runs the same script under pytest.
"""

from __future__ import annotations

import ast
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
SCAN_DIRS = ("src", "tests", "benchmarks", "examples", "tools")
REF_RE = re.compile(r"DESIGN\.md\s*§(\d+)")
HEADING_RE = re.compile(r"^#+\s*§(\d+)\b", re.MULTILINE)
LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")

# Markdown files whose intra-repo links the docs contract covers.
MD_FILES = (
    "README.md",
    "DESIGN.md",
    "PAPER.md",
    "ROADMAP.md",
    "benchmarks/README.md",
)
MD_GLOBS = ("docs/*.md",)

# Sections that own a public API surface: DESIGN.md §<n> must mention
# every name in the module's ``__all__`` (parsed with ``ast``, so a new
# export without documentation fails this check) plus the listed extras.
SYMBOL_SECTIONS = {
    2: (
        "src/repro/core/dist_sort.py",  # sharded output, local sort
        (
            "scatter_to_buckets",
            "bucketed_length",
            "sort_pairs",
            "argsort_keys",
        ),
    ),
    8: (
        "src/repro/serve/sortd.py",  # serving layer
        (
            "sort_segments",
            "sort_many",
            "plan_segments",
            "estimate_batch_stats",
            "choose_batch_plan",
            "SEGMENT_BITONIC_MAX",
            "pack_segments",
            "unpack_segments",
            "SegmentScenario",
        ),
    ),
    9: (
        "src/repro/perf/__init__.py",  # perf gate
        (
            "calibrate_host",
            "bound_time_s",
            "set_smoke",
            "TRAJECTORY_KEEP",
            "WARN_FRACTION",
        ),
    ),
    10: (
        "src/repro/serve/fleet/__init__.py",  # multi-worker serving
        (
            "request_mix",
            "drive_closed_loop",
            "drive_open_loop",
            "worker_down",
            "idle_flush_s",
        ),
    ),
    11: (
        "src/repro/net/faults.py",  # degraded serving
        (
            "set_fault_scenario",
            "apply_fault_scenario",
            "fault_slowdown",
            "is_degraded",
            "optical_link_down",
            "group_uplinks_down",
            "random_links",
            "worker_down",
            "degraded_flushes",
            "fault_grid",
        ),
    ),
    12: (
        "src/repro/core/workloads.py",  # engine workload ops
        (
            "top_k",
            "plan_top_k",
            "merge_sorted",
            "sort_pairs",
            "argsort_keys",
            "argsort",
            "submit_merge",
            "merge",
            "OpScenario",
            "op_smoke_grid",
            "op_tier1_grid",
            "run_op_grid",
            "run_op_scenario",
        ),
    ),
}


def defined_sections() -> set[int]:
    design = ROOT / "DESIGN.md"
    if not design.exists():
        return set()
    return {int(m) for m in HEADING_RE.findall(design.read_text())}


def find_references() -> list[tuple[str, int, int]]:
    """All (relative path, line number, section) citations in the tree."""
    refs = []
    for d in SCAN_DIRS:
        for path in sorted((ROOT / d).rglob("*.py")):
            for lineno, line in enumerate(
                path.read_text(errors="replace").splitlines(), 1
            ):
                for m in REF_RE.finditer(line):
                    refs.append(
                        (str(path.relative_to(ROOT)), lineno, int(m.group(1)))
                    )
    return refs


def module_all(py_path: pathlib.Path) -> list[str]:
    """``__all__`` of a module via ast — no import, no dependencies."""
    tree = ast.parse(py_path.read_text())
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == "__all__" for t in node.targets
        ):
            return list(ast.literal_eval(node.value))
    return []


def section_text(number: int) -> str:
    """Body of DESIGN.md section §<number> (heading to next § heading)."""
    text = (ROOT / "DESIGN.md").read_text()
    starts = [
        (int(m.group(1)), m.start())
        for m in re.finditer(r"^#+\s*§(\d+)\b", text, re.MULTILINE)
    ]
    for i, (num, start) in enumerate(starts):
        if num == number:
            end = starts[i + 1][1] if i + 1 < len(starts) else len(text)
            return text[start:end]
    return ""


def check_symbol_coverage() -> list[str]:
    problems = []
    for section, (module, extras) in sorted(SYMBOL_SECTIONS.items()):
        path = ROOT / module
        if not path.exists():
            problems.append(f"symbol coverage: {module} missing")
            continue
        exported = module_all(path)
        if not exported:
            problems.append(f"symbol coverage: {module} has no __all__")
        body = section_text(section)
        if not body:
            problems.append(
                f"symbol coverage: DESIGN.md has no §{section} section"
            )
            continue
        for sym in tuple(exported) + tuple(extras):
            if not re.search(rf"\b{re.escape(sym)}\b", body):
                problems.append(
                    f"UNDOCUMENTED: DESIGN.md §{section} does not mention "
                    f"`{sym}` (public symbol of {module})"
                )
    return problems


def md_files() -> list[pathlib.Path]:
    out = [ROOT / f for f in MD_FILES if (ROOT / f).exists()]
    for g in MD_GLOBS:
        out.extend(sorted(ROOT.glob(g)))
    return out


def check_markdown_links() -> list[str]:
    problems = []
    for md in md_files():
        for lineno, line in enumerate(md.read_text().splitlines(), 1):
            for m in LINK_RE.finditer(line):
                target = m.group(1)
                if target.startswith(("http://", "https://", "mailto:", "#")):
                    continue
                rel = target.split("#", 1)[0]
                if not rel:
                    continue
                if not (md.parent / rel).exists():
                    problems.append(
                        f"BROKEN LINK: {md.relative_to(ROOT)}:{lineno} → "
                        f"{target} (no such file)"
                    )
    return problems


def main() -> int:
    sections = defined_sections()
    refs = find_references()
    dangling = [(p, ln, s) for p, ln, s in refs if s not in sections]
    if not sections:
        print("check_design_refs: DESIGN.md missing or has no § headings")
        return 1
    problems = []
    if dangling:
        for p, ln, s in dangling:
            problems.append(f"DANGLING: {p}:{ln} cites DESIGN.md §{s} (not defined)")
    problems += check_symbol_coverage()
    problems += check_markdown_links()
    if problems:
        for p in problems:
            print(p)
        print(
            f"check_design_refs: {len(problems)} problems "
            f"({len(dangling)} dangling of {len(refs)} refs; "
            f"defined sections: {sorted(sections)})"
        )
        return 1
    covered = ", ".join(f"§{n}" for n in sorted(SYMBOL_SECTIONS))
    print(
        f"check_design_refs: OK — {len(refs)} § references resolve to sections "
        f"{sorted(sections)}, {covered} cover their public symbols, "
        f"{len(md_files())} markdown files link-checked"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
