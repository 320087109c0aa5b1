"""Rule registry + two-pass engine + baseline + CLI.

Pass 1 parses every file once and builds the :class:`RepoIndex` (imports,
axis-name bindings, function defs). Pass 2 runs every registered rule whose
``applies(path)`` predicate matches, with the index as cross-file context.
Findings then flow through three suppression layers:

1. ``# noqa`` / ``# noqa: <code>`` resolved against the flagged construct's
   full line span (``end_lineno``), not just the reported line;
2. a file-level ``# noqa-file: <code>`` pragma in the first 5 lines;
3. the committed suppression baseline (``staticcheck_baseline.json``):
   per-(file, code) finding COUNTS grandfathered at adoption time. New
   findings (count above baseline) fail the run; grandfathered ones are
   reported as a summary number so they get tracked down, not forgotten.
   Counts — not line numbers — so unrelated edits shifting lines don't
   churn the baseline.

Exit code: 0 = no new findings, 1 = new findings (the historical lint.py
contract). ``--format json`` emits one machine-readable object.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple, Type

from .findings import (
    Finding,
    is_suppressed,
    parse_noqa_file,
    parse_noqa_lines,
)
from .index import ModuleIndex, RepoIndex

ROOT = Path(__file__).resolve().parents[2]
DEFAULT_PATHS = [
    "cuda_mpi_gpu_cluster_programming_tpu",
    "tests",
    "scripts",
    "__graft_entry__.py",
]
BASELINE_NAME = "staticcheck_baseline.json"


@dataclasses.dataclass
class FileContext:
    path: Path
    src: str
    lines: List[str]
    tree: object  # ast.Module
    mod: ModuleIndex
    repo: RepoIndex
    root: Path


class Rule:
    """One check: a code, a scope predicate, and a checker.

    Subclass, set ``code`` (and optionally ``severity``), override
    ``applies`` for scoping and ``check`` for the logic, and decorate with
    :func:`register`. ``check`` runs only on files that parse; use
    ``ctx.mod``/``ctx.repo`` for indexed context instead of re-walking.
    """

    code: str = ""
    severity: str = "error"

    def applies(self, path: Path) -> bool:
        return True

    def check(self, ctx: FileContext) -> List[Finding]:
        raise NotImplementedError

    def finding(
        self,
        ctx: FileContext,
        line: int,
        message: str,
        span: Optional[Tuple[int, int]] = None,
        code: Optional[str] = None,
    ) -> Finding:
        return Finding(
            ctx.path, line, code or self.code, message, self.severity, span
        )


_REGISTRY: List[Rule] = []


def register(cls: Type[Rule]) -> Type[Rule]:
    _REGISTRY.append(cls())
    return cls


def all_rules() -> List[Rule]:
    if not any(r.code == "unused-import" for r in _REGISTRY):
        from . import rules_core, rules_jax  # noqa  (registration side effect)
    return list(_REGISTRY)


# ---------------------------------------------------------------------------
# baseline


def load_baseline(path: Path) -> Dict[str, Dict[str, int]]:
    try:
        data = json.loads(path.read_text())
    except (OSError, ValueError):
        return {}
    entries = data.get("entries", {}) if isinstance(data, dict) else {}
    out: Dict[str, Dict[str, int]] = {}
    for file_key, codes in entries.items():
        if isinstance(codes, dict):
            out[file_key] = {
                c: int(n) for c, n in codes.items() if isinstance(n, int) and n > 0
            }
    return out


def baseline_key(path: Path, root: Path) -> str:
    try:
        return str(path.resolve().relative_to(root))
    except ValueError:
        return str(path)


def split_by_baseline(
    findings: List[Finding], baseline: Dict[str, Dict[str, int]], root: Path
) -> Tuple[List[Finding], List[Finding]]:
    """(new, grandfathered): per (file, code), the first N findings in line
    order are grandfathered where N is the baseline count."""
    budget: Dict[Tuple[str, str], int] = {}
    for file_key, codes in baseline.items():
        for code, n in codes.items():
            budget[(file_key, code)] = n
    new: List[Finding] = []
    old: List[Finding] = []
    for f in sorted(findings, key=lambda f: (str(f.path), f.line, f.code)):
        k = (baseline_key(f.path, root), f.code)
        if budget.get(k, 0) > 0:
            budget[k] -= 1
            old.append(f)
        else:
            new.append(f)
    return new, old


def baseline_payload(findings: List[Finding], root: Path) -> dict:
    entries: Dict[str, Dict[str, int]] = {}
    for f in findings:
        codes = entries.setdefault(baseline_key(f.path, root), {})
        codes[f.code] = codes.get(f.code, 0) + 1
    return {
        "version": 1,
        "note": (
            "Grandfathered staticcheck findings: per-(file, code) counts. "
            "New findings above these counts fail the gate; shrink counts "
            "as grandfathered sites get fixed. Regenerate with "
            "python -m cuda_mpi_gpu_cluster_programming_tpu.staticcheck "
            "--update-baseline."
        ),
        "entries": {k: dict(sorted(v.items())) for k, v in sorted(entries.items())},
    }


# ---------------------------------------------------------------------------
# run


def collect_files(paths: Sequence[Path]) -> List[Path]:
    files: List[Path] = []
    for p in paths:
        p = Path(p)
        if p.is_dir():
            files.extend(
                f for f in sorted(p.rglob("*.py")) if "__pycache__" not in f.parts
            )
        else:
            files.append(p)
    return files


def check_files(files: Sequence[Path]) -> Tuple[List[Finding], RepoIndex]:
    """All findings after noqa filtering (baseline NOT applied here)."""
    sources = [(f, f.read_text(errors="replace")) for f in files]
    repo = RepoIndex.build(sources)
    rules = all_rules()
    findings: List[Finding] = []
    for path, src in sources:
        mod = repo.modules[path]
        if mod.syntax_error is not None:
            findings.append(
                Finding(
                    path,
                    mod.syntax_error.lineno or 0,
                    "syntax",
                    str(mod.syntax_error.msg),
                )
            )
            continue
        ctx = FileContext(
            path=path,
            src=src,
            lines=src.splitlines(),
            tree=mod.tree,
            mod=mod,
            repo=repo,
            root=ROOT,
        )
        noqa = parse_noqa_lines(src)
        file_codes = parse_noqa_file(src)
        seen = set()  # nested loops can surface one construct twice
        for rule in rules:
            if not rule.applies(path):
                continue
            for f in rule.check(ctx):
                key = (f.line, f.code, f.message)
                if key in seen:
                    continue
                seen.add(key)
                if not is_suppressed(f, noqa, file_codes):
                    findings.append(f)
    findings.sort(key=lambda f: (str(f.path), f.line, f.code))
    return findings, repo


def run(
    paths: Sequence[Path],
    baseline_path: Optional[Path] = None,
    fmt: str = "text",
    update_baseline: bool = False,
    out=None,
) -> int:
    out = out or sys.stdout
    files = collect_files(paths)
    findings, _repo = check_files(files)

    if update_baseline and baseline_path is not None:
        payload = baseline_payload(findings, ROOT)
        baseline_path.write_text(json.dumps(payload, indent=2) + "\n")
        print(
            f"staticcheck: baseline updated ({len(findings)} findings "
            f"grandfathered) -> {baseline_path}",
            file=out,
        )
        return 0

    baseline = load_baseline(baseline_path) if baseline_path else {}
    new, grandfathered = split_by_baseline(findings, baseline, ROOT)

    if fmt == "json":
        print(
            json.dumps(
                {
                    "files": len(files),
                    "new": [f.as_dict(ROOT) for f in new],
                    "grandfathered": [f.as_dict(ROOT) for f in grandfathered],
                }
            ),
            file=out,
        )
    else:
        for f in new:
            print(f"{f.location(ROOT)}: [{f.code}] {f.message}", file=out)
        tail = f", {len(grandfathered)} baselined" if grandfathered else ""
        print(
            f"lint: {len(files)} files, {len(new)} findings{tail}", file=out
        )
    return 1 if new else 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="staticcheck",
        description=(
            "Repo static-analysis gate (the clang-tidy analogue): hygiene + "
            "JAX/shard_map-aware rules. Exit 0 = clean, 1 = new findings. "
            "See docs/STATIC_ANALYSIS.md."
        ),
    )
    ap.add_argument("paths", nargs="*", help="files/dirs (default: repo set)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument(
        "--baseline",
        default=None,
        help=f"suppression baseline JSON (default: <repo>/{BASELINE_NAME} if present)",
    )
    ap.add_argument(
        "--no-baseline", action="store_true", help="ignore any baseline"
    )
    ap.add_argument(
        "--update-baseline",
        action="store_true",
        help="rewrite the baseline grandfathering every current finding",
    )
    ap.add_argument(
        "--list-rules", action="store_true", help="print registered rule codes"
    )
    args = ap.parse_args(argv if argv is not None else sys.argv[1:])

    if args.list_rules:
        for rule in sorted(all_rules(), key=lambda r: r.code):
            print(f"{rule.code} ({rule.severity})")
        return 0

    paths = (
        [Path(p) for p in args.paths]
        if args.paths
        else [ROOT / p for p in DEFAULT_PATHS]
    )
    if args.no_baseline:
        baseline_path = None
    elif args.baseline:
        baseline_path = Path(args.baseline)
    else:
        default = ROOT / BASELINE_NAME
        baseline_path = default if (default.exists() or args.update_baseline) else None
    return run(
        paths,
        baseline_path=baseline_path,
        fmt=args.format,
        update_baseline=args.update_baseline,
    )
