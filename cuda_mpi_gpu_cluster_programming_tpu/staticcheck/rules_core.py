"""The migrated lint.py rule set: hygiene + the repo-specific footgun rules.

Codes (unchanged from scripts/lint.py so existing ``# noqa: <code>``
annotations keep working):

  unused-import, bare-except, mutable-default, deprecated, raw-subprocess,
  atomic-write, tabs, trailing-ws, long-line

(`syntax` findings are emitted by the engine itself — a file that does not
parse runs no rules.)
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import List

from .engine import FileContext, Rule, register
from .findings import SEVERITY_STYLE, Finding

MAX_LINE = 120

# Deprecated/banned API census (substring, reason) — the tidy "checks"
# list; grown as CI surfaces new deprecations.
DEPRECATED = [
    ("lax.pvary", "deprecated in JAX 0.9: use lax.pcast(x, axis, to='varying')"),  # noqa
    (".tree_multimap", "removed from JAX: use jax.tree_util.tree_map"),  # noqa
    ("jax.tree_map", "deprecated alias: use jax.tree_util.tree_map"),  # noqa
    ("np.float_", "removed in NumPy 2.0"),  # noqa
]


def _node_span(node: ast.AST):
    end = getattr(node, "end_lineno", None) or node.lineno
    return (node.lineno, end)


@register
class UnusedImportRule(Rule):
    code = "unused-import"

    def applies(self, path: Path) -> bool:
        # __init__.py re-exports are legitimate "unused".
        return path.name != "__init__.py"

    def check(self, ctx: FileContext) -> List[Finding]:
        imported = dict(ctx.mod.imports)
        used = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
                used.add(node.id)
            elif isinstance(node, ast.Attribute):
                root = node
                while isinstance(root, ast.Attribute):
                    root = root.value
                if isinstance(root, ast.Name):
                    used.add(root.id)
        out = []
        for name, lineno in imported.items():
            if name in used or name == "annotations":
                continue
            # Referenced only inside a docstring/string (e.g. doctest) still
            # counts as unused; that is what # noqa is for.
            out.append(self.finding(ctx, lineno, f"'{name}' imported but unused"))
        return out


@register
class BareExceptRule(Rule):
    code = "bare-except"

    def check(self, ctx: FileContext) -> List[Finding]:
        return [
            self.finding(
                ctx, node.lineno,
                "bare 'except:' also catches KeyboardInterrupt/SystemExit",
            )
            for node in ast.walk(ctx.tree)
            if isinstance(node, ast.ExceptHandler) and node.type is None
        ]


@register
class MutableDefaultRule(Rule):
    code = "mutable-default"

    def check(self, ctx: FileContext) -> List[Finding]:
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            # noqa resolves over the whole signature span (decorators
            # through the last line before the body) — the reported line is
            # the default's own line, but on a multi-line def the annotation
            # often sits on the `def` or closing-paren line.
            sig_first = min(
                [node.lineno] + [d.lineno for d in node.decorator_list]
            )
            sig_last = max(node.lineno, node.body[0].lineno - 1)
            defaults = list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d
            ]
            for d in defaults:
                if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                    out.append(
                        self.finding(
                            ctx, d.lineno,
                            f"mutable default argument in {node.name}()",
                            span=(sig_first, max(sig_last, _node_span(d)[1])),
                        )
                    )
        return out


@register
class DeprecatedRule(Rule):
    code = "deprecated"

    def check(self, ctx: FileContext) -> List[Finding]:
        out = []
        for i, line in enumerate(ctx.lines, 1):
            if line.lstrip().startswith("#"):
                continue
            for pat, why in DEPRECATED:
                if pat in line:
                    out.append(self.finding(ctx, i, f"{pat}: {why}"))
        return out


# Directories where one-shot subprocess execution is a resilience
# regression (the deploy transports and the evidence-capture scripts); the
# members checked are the execution entry points, not the module itself.
_RAW_SUBPROCESS_DIRS = ("parallel", "scripts")
_SUBPROCESS_CALLS = {"run", "Popen", "call", "check_call", "check_output"}


@register
class RawSubprocessRule(Rule):
    code = "raw-subprocess"

    def applies(self, path: Path) -> bool:
        return any(part in _RAW_SUBPROCESS_DIRS for part in path.parts)

    def check(self, ctx: FileContext) -> List[Finding]:
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if (
                isinstance(f, ast.Attribute)
                and f.attr in _SUBPROCESS_CALLS
                and isinstance(f.value, ast.Name)
                and f.value.id == "subprocess"
            ):
                out.append(
                    self.finding(
                        ctx, node.lineno,
                        f"bare subprocess.{f.attr}() bypasses the retrying "
                        "transport (use parallel.deploy._transport_run or a "
                        "bounded wrapper; annotate deliberate call sites "
                        "with # noqa: raw-subprocess)",
                        span=_node_span(node),
                    )
                )
        return out


# Modules allowed to open run artifacts with a truncating 'w': the atomic
# writers themselves. Tests are exempt (they build fixtures).
_ATOMIC_WRITE_EXEMPT_FILES = {"journal.py", "checkpoint.py"}
_ARTIFACT_SUFFIXES = (".csv", ".json", ".jsonl")


def _static_str_tail(node: ast.expr) -> str:
    """Best-effort static tail of a path expression: the literal suffix of a
    Constant / f-string / ``dir / "name.json"`` BinOp / ``Path(...)`` call."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr) and node.values:
        last = node.values[-1]
        if isinstance(last, ast.Constant) and isinstance(last.value, str):
            return last.value
    if isinstance(node, ast.BinOp):  # pathlib's dir / "file.json"
        return _static_str_tail(node.right)
    if (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Name)
        and node.func.id == "Path"
        and node.args
    ):
        return _static_str_tail(node.args[-1])
    return ""


def _artifact_hint(node: ast.expr) -> bool:
    """True when a path expression statically looks like a run artifact."""
    tail = _static_str_tail(node)
    if tail:
        return tail.endswith(_ARTIFACT_SUFFIXES)
    ident = ""
    if isinstance(node, ast.Name):
        ident = node.id
    elif isinstance(node, ast.Attribute):
        ident = node.attr
    return any(h in ident.lower() for h in ("csv", "json"))


@register
class AtomicWriteRule(Rule):
    code = "atomic-write"

    def applies(self, path: Path) -> bool:
        return (
            path.name not in _ATOMIC_WRITE_EXEMPT_FILES
            and "tests" not in path.parts
        )

    def check(self, ctx: FileContext) -> List[Finding]:
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if (
                isinstance(f, ast.Name)
                and f.id == "open"
                and len(node.args) >= 2
                and isinstance(node.args[1], ast.Constant)
                and isinstance(node.args[1].value, str)
                and node.args[1].value.startswith("w")
                and _artifact_hint(node.args[0])
            ):
                out.append(self._finding(ctx, node, f"open(..., {node.args[1].value!r})"))
            if (
                isinstance(f, ast.Attribute)
                and f.attr == "write_text"
                and _artifact_hint(f.value)
            ):
                out.append(self._finding(ctx, node, ".write_text()"))
        return out

    def _finding(self, ctx, node, what: str) -> Finding:
        return self.finding(
            ctx, node.lineno,
            f"truncating {what} of a run artifact outside the "
            "journal/checkpoint helpers — a kill mid-write leaves a torn "
            "file as committed evidence (use resilience.journal."
            "atomic_write_text/atomic_writer; deliberate sites: "
            "# noqa: atomic-write)",
            span=_node_span(node),
        )


@register
class HygieneRule(Rule):
    """tabs / trailing-ws / long-line in one line sweep (style severity)."""

    code = "hygiene"  # umbrella; findings carry their specific code
    severity = SEVERITY_STYLE

    def check(self, ctx: FileContext) -> List[Finding]:
        out = []
        for i, line in enumerate(ctx.lines, 1):
            if "\t" in line:
                out.append(Finding(ctx.path, i, "tabs", "tab character", self.severity))
            if line != line.rstrip():
                out.append(
                    Finding(ctx.path, i, "trailing-ws", "trailing whitespace", self.severity)
                )
            if len(line) > MAX_LINE:
                out.append(
                    Finding(
                        ctx.path, i, "long-line",
                        f"{len(line)} > {MAX_LINE} chars", self.severity,
                    )
                )
        return out
