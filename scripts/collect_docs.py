"""Doc/source collector — the ``collect_project.sh`` analogue (ref H14).

The reference concatenates a curated file list into one reviewable
``project.txt`` (reference collect_project.sh:1-60, collect_p_docs.sh) so a
grader or LLM can read the whole project in one pass. Same capability here,
selected by framework area instead of version directory:

    python scripts/collect_docs.py                    # everything
    python scripts/collect_docs.py ops parallel       # just those areas
    python scripts/collect_docs.py --docs-only        # markdown docs only
    python scripts/collect_docs.py --out review.txt

Each included file is fenced with a header line giving its path and line
count; a table of contents is emitted first. Missing areas are skipped with
a note (the reference script's "only include files that actually exist"
behavior).
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path
from typing import Dict, List

ROOT = Path(__file__).resolve().parent.parent
PKG = "cuda_mpi_gpu_cluster_programming_tpu"

# Area -> glob patterns relative to the repo root (curated like the
# reference's FILES_TO_COLLECT, but by subsystem).
AREAS: Dict[str, List[str]] = {
    "docs": ["README.md", "docs/*.md", "BASELINE.md", "SURVEY.md"],
    "models": [f"{PKG}/models/*.py"],
    "ops": [f"{PKG}/ops/*.py"],
    "parallel": [f"{PKG}/parallel/*.py"],
    "runtime": [f"{PKG}/*.py", f"{PKG}/utils/*.py"],
    "native": [f"{PKG}/native/__init__.py", f"{PKG}/native/csrc/*.cpp"],
    "examples": [f"{PKG}/examples/*.py"],
    "harness": ["__graft_entry__.py", "scripts/*.py"],
    "tests": ["tests/*.py"],
}


def collect(areas: List[str], docs_only: bool) -> List[Path]:
    wanted = ["docs"] if docs_only else (areas or list(AREAS))
    files: List[Path] = []
    for area in wanted:
        if area not in AREAS:
            print(f"note: unknown area {area!r} skipped "
                  f"(choose from {', '.join(AREAS)})", file=sys.stderr)
            continue
        for pat in AREAS[area]:
            hits = sorted(ROOT.glob(pat))
            if not hits:
                print(f"note: no files for {area}:{pat}", file=sys.stderr)
            files.extend(h for h in hits if h.is_file())
    seen, unique = set(), []
    for f in files:
        if f not in seen:
            seen.add(f)
            unique.append(f)
    return unique


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="scripts/collect_docs.py")
    ap.add_argument("areas", nargs="*", help=f"areas: {', '.join(AREAS)}")
    ap.add_argument("--out", default="project.txt")
    ap.add_argument("--docs-only", action="store_true")
    args = ap.parse_args(argv)

    files = collect(args.areas, args.docs_only)
    lines: List[str] = ["# Collected project sources", ""]
    lines.append("## Table of contents")
    total = 0
    bodies: List[str] = []
    for f in files:
        text = f.read_text(errors="replace")
        n = text.count("\n") + 1
        total += n
        rel = f.relative_to(ROOT)
        lines.append(f"- {rel} ({n} lines)")
        bodies.append(f"\n{'=' * 78}\n=== {rel} ({n} lines)\n{'=' * 78}\n{text}")
    lines.append(f"\nTotal: {len(files)} files, {total} lines.")
    out = Path(args.out)
    out.write_text("\n".join(lines) + "".join(bodies))
    print(f"wrote {out} ({len(files)} files, {total} lines)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
