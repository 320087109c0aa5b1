"""Which top-level unit of the package may import which: one table, pinned.

A unit is a module or sub-package directly under
``cuda_mpi_gpu_cluster_programming_tpu/``. Every ``import`` in a unit's
files, at module level or inside a function, is resolved to the sibling
unit it reaches and compared with ``ALLOWED``. An import outside a unit's
row fails with its file and line; a row entry that no import uses any
more fails too, so the table can only shrink.
"""

import ast
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
PKG = "cuda_mpi_gpu_cluster_programming_tpu"
PKG_DIR = ROOT / PKG

# Written from the tree as PR 29 left it; not a design. The graph has cycles
# (ROADMAP debt D9): ops <-> models, models <-> parallel,
# parallel <-> resilience, serving <-> observability,
# resilience -> configs -> parallel -> resilience, and utils reaches up into
# ops, parallel, models and resilience. Cutting one means deleting its entry
# here; a new arrow means arguing for a new entry.
ALLOWED = {
    "analysis": {"harness", "models", "parallel"},
    "configs": {
        "models", "observability", "ops", "parallel", "precision", "tuning",
        "utils",
    },
    "examples": {"models", "ops", "parallel", "utils"},
    "harness": {"configs", "resilience", "utils"},
    "models": {"observability", "ops", "parallel"},
    "native": {"models", "parallel"},
    "observability": {"models", "ops", "resilience", "serving", "utils"},
    "ops": {"models"},
    "parallel": {
        "models", "observability", "ops", "precision", "resilience", "tuning",
        "utils",
    },
    "precision": {"models", "observability", "ops", "parallel", "resilience"},
    "resilience": {"configs", "models", "observability", "ops", "parallel"},
    "run": {
        "configs", "models", "native", "observability", "parallel",
        "resilience", "serving", "tuning", "utils",
    },
    "scaffold": {"utils"},
    "serving": {
        "configs", "models", "observability", "precision", "resilience",
        "tuning",
    },
    "staticcheck": set(),
    "train": {
        "configs", "models", "native", "observability", "parallel",
        "resilience", "training", "utils",
    },
    "training": {"models", "parallel"},
    "tuning": {"models", "observability", "ops", "precision", "resilience", "utils"},
    "utils": {"models", "ops", "parallel", "resilience"},
}
UNITS = sorted(ALLOWED)


def _unit_files(unit: str):
    single = PKG_DIR / f"{unit}.py"
    return [single] if single.exists() else sorted((PKG_DIR / unit).rglob("*.py"))


def _absolute_imports(path: Path):
    """(dotted module, imported names, line) of every import in ``path``,
    relative ones resolved against the file's own package."""
    parts = path.relative_to(ROOT).with_suffix("").parts
    package = parts[:-1]  # of a/b.py and of a/__init__.py alike: a
    for node in ast.walk(ast.parse(path.read_text(), filename=str(path))):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, (), node.lineno
        elif isinstance(node, ast.ImportFrom):
            base = package[: len(package) - (node.level - 1)] if node.level else ()
            module = ".".join((*base, *(node.module.split(".") if node.module else ())))
            yield module, tuple(a.name for a in node.names), node.lineno


def _sibling_imports(unit: str):
    """{sibling unit: [file:line, ...]} over every file of ``unit``."""
    found = {}
    for path in _unit_files(unit):
        for module, names, line in _absolute_imports(path):
            parts = module.split(".")
            if parts[0] != PKG:
                continue
            # `from <package root> import a, b` names units; anything deeper
            # names the unit in its second component
            reached = names if len(parts) == 1 else (parts[1],)
            for other in reached:
                if other in ALLOWED and other != unit:
                    found.setdefault(other, []).append(
                        f"{path.relative_to(ROOT)}:{line}"
                    )
    return found


def test_the_table_lists_every_unit():
    on_disk = {
        p.stem if p.is_file() else p.name
        for p in PKG_DIR.iterdir()
        if (p.suffix == ".py" and p.stem not in ("__init__", "__main__"))
        or (p.is_dir() and (p / "__init__.py").exists())
    }
    assert on_disk == set(ALLOWED)


@pytest.mark.parametrize("unit", UNITS)
def test_imports_point_where_the_table_says(unit):
    found = _sibling_imports(unit)
    outside = {o: where for o, where in found.items() if o not in ALLOWED[unit]}
    assert not outside, (
        f"{unit} imports a sibling outside its row of ALLOWED: "
        + "; ".join(f"{o} at {', '.join(w)}" for o, w in sorted(outside.items()))
    )
    unused = ALLOWED[unit] - set(found)
    assert not unused, (
        f"no import of {unit} reaches {sorted(unused)} any more: "
        "delete the entries from its row of ALLOWED"
    )


def test_the_package_imports_no_benchmark_and_no_test():
    """The benchmark and the tests read the program; never the reverse."""
    offenders = []
    for unit in UNITS:
        for path in _unit_files(unit):
            for module, _names, line in _absolute_imports(path):
                if module.split(".")[0] in ("benchmark", "bench", "tests"):
                    offenders.append(f"{path.relative_to(ROOT)}:{line} imports {module}")
    assert not offenders, "\n".join(offenders)
