"""Static-analysis gate test — the suite enforces a clean lint run.

Reference analogue: clang-tidy wired into the V4 build (reference
README.md:172,307; final_project/v4_mpi_cuda/.clang-tidy). VERDICT r2
item 8.
"""

import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def test_repo_lints_clean():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "lint.py")],
        capture_output=True,
        text=True,
        cwd=ROOT,
        timeout=120,
    )
    assert proc.returncode == 0, "lint findings:\n" + proc.stdout


def test_lint_detects_violations(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import os\n"            # unused-import
        "try:\n    pass\n"
        "except:\n    pass\n"    # bare-except
        "def f(x=[]):\n    return x\n"  # mutable-default
        # Split so the lint gate doesn't flag THIS file for the banned API.
        "y = lax.pv" + "ary(z, 'i')\n"  # deprecated
    )
    proc = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "lint.py"), str(bad)],
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode == 1
    for code in ("unused-import", "bare-except", "mutable-default", "deprecated"):
        assert code in proc.stdout, proc.stdout


def test_lint_noqa_suppresses(tmp_path):
    ok = tmp_path / "ok.py"
    ok.write_text("import os  # noqa\n")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "lint.py"), str(ok)],
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stdout


def test_lint_raw_subprocess_scoped_to_transport_dirs(tmp_path):
    """Bare subprocess execution is flagged ONLY under parallel//scripts/
    (where it bypasses the retrying transport); elsewhere it is fine, and
    a deliberate bounded call site opts out with # noqa: raw-subprocess."""
    src = (
        "import subprocess\n"
        "subprocess.run(['true'])\n"
        "subprocess.Popen(['true'])  # noqa: raw-subprocess\n"
    )
    scoped = tmp_path / "scripts" / "bad.py"
    scoped.parent.mkdir()
    scoped.write_text(src)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "lint.py"), str(scoped)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 1
    assert proc.stdout.count("[raw-subprocess]") == 1  # the noqa line is exempt
    assert ":2:" in proc.stdout  # the bare run() call

    unscoped = tmp_path / "elsewhere" / "ok.py"
    unscoped.parent.mkdir()
    unscoped.write_text(src)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "lint.py"), str(unscoped)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stdout


def test_lint_variant_env_reads_scoped_to_tuning(tmp_path):
    """Direct reads of the Pallas variant knobs fork the env > TunePlan >
    default precedence (docs/TUNING.md): flagged everywhere except tuning/
    and ops/pallas_kernels.py; writes and noqa'd reads are fine."""
    src = (
        "import os\n"
        "a = os.environ.get('TPU_FRAMEWORK_CONV')\n"        # read: flagged
        "b = os.environ['TPU_FRAMEWORK_KBLOCK']\n"          # read: flagged
        "c = os.getenv('PALLAS_WHATEVER_KNOB')\n"           # read: flagged
        "os.environ['TPU_FRAMEWORK_CONV'] = 'taps'\n"       # write: fine
        "d = os.environ.get('OTHER_CONFIG')\n"              # other var: fine
        "e = os.environ.get('TPU_FRAMEWORK_FUSE')  # noqa: variant-env\n"
    )
    bad = tmp_path / "stray.py"
    bad.write_text(src)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "lint.py"), str(bad)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 1
    assert proc.stdout.count("[variant-env]") == 3, proc.stdout
    for lineno in (":2:", ":3:", ":4:"):
        assert lineno in proc.stdout

    # The sanctioned readers are exempt wholesale.
    for rel in ("tuning", ):
        scoped = tmp_path / rel / "reader.py"
        scoped.parent.mkdir(exist_ok=True)
        scoped.write_text(src.replace("  # noqa: variant-env", ""))
        proc = subprocess.run(
            [sys.executable, str(ROOT / "scripts" / "lint.py"), str(scoped)],
            capture_output=True, text=True, timeout=60,
        )
        assert "[variant-env]" not in proc.stdout, proc.stdout


def test_lint_atomic_write_rule(tmp_path):
    """Truncating writes of run artifacts are flagged everywhere except the
    sanctioned journal/checkpoint helpers; appends, non-artifacts and noqa'd
    sites pass."""
    bad = tmp_path / "writer.py"
    bad.write_text(
        "import json\n"
        "from pathlib import Path\n"
        "def f(rows, session):\n"
        "    with open('perf/results.json', 'w') as fh:\n"      # flagged
        "        json.dump(rows, fh)\n"
        "    (Path('logs') / 'summary.csv').write_text('x')\n"  # flagged
        "    with open(session.csv_path, 'w') as fh:\n"         # flagged (ident hint)
        "        fh.write('x')\n"
        "    with open('rows.jsonl', 'a') as fh:\n"             # append: fine
        "        fh.write('{}')\n"
        "    with open('notes.md', 'w') as fh:\n"               # not an artifact
        "        fh.write('x')\n"
        "    with open('perf/ok.json', 'w') as fh:  # noqa: atomic-write\n"
        "        json.dump(rows, fh)\n"
    )
    proc = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "lint.py"), str(bad)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 1
    flagged = [l for l in proc.stdout.splitlines() if "[atomic-write]" in l]
    assert len(flagged) == 3, proc.stdout
    assert any(":4:" in l for l in flagged)
    assert any(":6:" in l for l in flagged)
    assert any(":7:" in l for l in flagged)


def test_lint_atomic_write_exempts_sanctioned_helpers(tmp_path):
    """The atomic writers themselves (journal.py / checkpoint.py) and tests
    may open artifacts with 'w' — they ARE the crash-consistent path."""
    src = (
        "import json\n"
        "def f(rows):\n"
        "    with open('perf/results.json', 'w') as fh:\n"
        "        json.dump(rows, fh)\n"
    )
    for rel in ("journal.py", "checkpoint.py", "tests/test_x.py"):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
        proc = subprocess.run(
            [sys.executable, str(ROOT / "scripts" / "lint.py"), str(p)],
            capture_output=True, text=True, timeout=60,
        )
        assert "[atomic-write]" not in proc.stdout, rel
