"""The documents a new owner reads first name things that exist.

For each document: in its fenced code blocks every ``python <path>.py`` is a
file of the repo, every ``python -m <module>`` a module that can be found,
and every ``--option`` handed to the four command-line entry points of the
package is one their parsers know. And no document names the retired second
benchmark's environment variables or its round files (PR 29): speed is
measured by ``benchmark/run.py`` alone; nor the environment names that chose
a Pallas lowering until PR 42: a lowering is the code's default or a tune
plan's winner.
"""

import argparse
import contextlib
import functools
import importlib
import importlib.util
import io
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
PKG = "cuda_mpi_gpu_cluster_programming_tpu"
DOCS = [
    "README.md",
    *sorted(str(p.relative_to(ROOT)) for p in (ROOT / "docs").glob("*.md")),
    "benchmark/README.md",
    ".claude/skills/verify/SKILL.md",
]

FENCE = re.compile(r"^```.*?$(.*?)^```\s*$", re.S | re.M)
SCRIPT = re.compile(r"\bpython3? +(?!-)(\S+\.py)\b")
MODULE = re.compile(r"\bpython3? +-m +([A-Za-z_][\w.]*)(.*)")
OPTION = re.compile(r"(?<![\w-])(--[a-z][\w-]*)")
RETIRED = re.compile(
    "BENCH" + r"_(?:[A-Z][A-Z0-9_]*|r[\d*][\w.*]*)" + "|TPU_FRAMEWORK" + r"_\w*"
)


def _commands(text: str):
    """The fenced blocks' lines, backslash continuations joined."""
    for block in FENCE.findall(text):
        yield from block.replace("\\\n", " ").splitlines()


def _parser_options(parser: argparse.ArgumentParser) -> set:
    known = set()
    for action in parser._actions:
        known.update(action.option_strings)
        if isinstance(action, argparse._SubParsersAction):
            for sub in action.choices.values():
                known |= _parser_options(sub)
    return known


@functools.cache
def _known_options(module: str) -> set:
    """Every ``--option`` the entry point ``module`` accepts."""
    if module == f"{PKG}.staticcheck":  # builds its parser inside main()
        from cuda_mpi_gpu_cluster_programming_tpu.staticcheck.engine import main

        out = io.StringIO()
        with contextlib.redirect_stdout(out), pytest.raises(SystemExit):
            main(["--help"])
        return set(OPTION.findall(out.getvalue()))
    name = f"{module}.__main__" if module == f"{PKG}.observability" else module
    return _parser_options(importlib.import_module(name).make_parser())


CHECKED_ENTRY_POINTS = tuple(
    f"{PKG}.{name}" for name in ("run", "train", "observability", "staticcheck")
)


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_what_exists(doc):
    text = (ROOT / doc).read_text()
    wrong = []
    for line in _commands(text):
        for path in SCRIPT.findall(line):
            if not (ROOT / path).is_file():
                wrong.append(f"no such file: {path!r} in `{line.strip()}`")
        m = MODULE.search(line)
        if not m:
            continue
        module, rest = m.group(1), re.split(r"[|;&>]", m.group(2))[0]
        if importlib.util.find_spec(module) is None:
            wrong.append(f"no such module: {module!r} in `{line.strip()}`")
        elif module in CHECKED_ENTRY_POINTS:
            for opt in OPTION.findall(rest):
                if opt not in _known_options(module):
                    wrong.append(f"{module.rsplit('.', 1)[1]} has no option {opt}")
    wrong += [f"retired name: {m.group(0)}" for m in RETIRED.finditer(text)]
    assert not wrong, f"{doc}:\n  " + "\n  ".join(wrong)
