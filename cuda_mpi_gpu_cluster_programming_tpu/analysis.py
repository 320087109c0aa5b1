"""Performance-analysis ETL: warehouse, stats, speedup, plots, exports.

Python analogue of the reference's L6 layer (``log_analysis.py``, 296 LoC,
Typer + DuckDB). DuckDB is not in this image, so the warehouse is stdlib
``sqlite3`` with registered aggregate functions giving the same SQL-view
surface; the command set is identical:

- ``ingest``  — walk a logs root, SHA1-dedup files (log_analysis.py:104,113-115),
  load harness summary CSVs, scrape run logs by regex, compute source stats
  (log_analysis.py:75-160 analogue).
- ``stats``   — run_stats view: n, mean, stddev, 95% CI per variant/np/batch
  (log_analysis.py:176-198).
- ``speedup`` — S(N)=T1/TN and E=S/N against the V1 serial baseline, in SQL
  (log_analysis.py:213-222).
- ``plot``    — matplotlib speedup/efficiency PNGs (log_analysis.py:226-266).
- ``export``  — dump any view to csv/parquet (log_analysis.py:269-292).

Variant names ingested from the harness CSVs use the reference's canonical
version-name mapping (analysis.md:60-80) extended with the V6 TPU family, so
historical reference data and new TPU data plot on the same axes.
"""

from __future__ import annotations

import argparse
import csv
import hashlib
import json
import math
import re
import sqlite3
import statistics
import sys
from pathlib import Path
from typing import List, Optional

DEFAULT_DB = ".warehouse/cluster_logs.sqlite"

# Canonical version-name normalisation (analysis.md:60-80 analogue): maps raw
# variant strings from either the reference's CSVs or ours onto one family.
CANONICAL_VARIANTS = {
    "v1": "V1 Serial",
    "v1 serial": "V1 Serial",
    "v1_serial": "V1 Serial",
    "v2.1": "V2.1 BroadcastAll",
    "v2 2.1-broadcast-all": "V2.1 BroadcastAll",
    "v2.1 broadcastall": "V2.1 BroadcastAll",
    "v2.2": "V2.2 ScatterHalo",
    "v2 2.2-scatter-halo": "V2.2 ScatterHalo",
    "v2.2 scatterhalo": "V2.2 ScatterHalo",
    "v3": "V3 CUDA",
    "v3 cuda": "V3 CUDA",
    "v3 cuda only": "V3 CUDA",
    "v4": "V4 MPI+CUDA",
    "v4 mpi+cuda": "V4 MPI+CUDA",
    "v5": "V5 MPI+CUDA-Aware",
    "v5 mpi+cuda-aware": "V5 MPI+CUDA-Aware",
}


def canonical_variant(name: str) -> str:
    return CANONICAL_VARIANTS.get(name.strip().lower(), name.strip())


class _Stdev:
    """Sample stddev aggregate (DuckDB's stddev_samp analogue for sqlite)."""

    def __init__(self) -> None:
        self.vals: List[float] = []

    def step(self, v) -> None:
        if v is not None:
            self.vals.append(float(v))

    def finalize(self) -> Optional[float]:
        # NULL for n<2, matching DuckDB's stddev_samp — a single-sample group
        # must not masquerade as a zero-variance measurement.
        return statistics.stdev(self.vals) if len(self.vals) > 1 else None


def _backfill_platform(conn: sqlite3.Connection) -> None:
    """Derive platform for rows that predate the column. The
    sha1-incremental ingest never revisits unchanged CSVs, so without this
    an upgraded warehouse would keep pooling its old CPU and TPU rows in
    one NULL-platform group — the exact conflation the column exists to
    fix. Runs on EVERY connect, not just the migration: it is idempotent
    (only NULL rows are touched, so the steady-state query is cheap) and a
    one-shot attempt could fail silently-forever when the log paths don't
    resolve from the current cwd (src_csv is stored as ingested, often
    relative) — retrying each connect picks those rows up the next time
    the warehouse is opened from the right directory."""
    # Reference-corpus rows intentionally stay NULL (platform is encoded in
    # the variant name), so exclude them in SQL — otherwise every connect
    # re-fetches and re-skips them forever (round-3 advisor finding); the
    # steady-state scan only sees genuinely unresolved rows.
    rows = conn.execute(
        "SELECT rowid, src_csv, log_file, corpus FROM summary_runs "
        "WHERE platform IS NULL "
        "  AND COALESCE(corpus, '') != 'reference' "
        "  AND src_csv IS NOT NULL AND src_csv != '' "
        "  AND NOT (corpus IS NULL AND (src_csv LIKE '%/reference/%' "
        "           OR src_csv LIKE '%reference_import%'))"
    ).fetchall()
    defaults: dict = {}
    n = 0
    for rowid, src_csv, log_file, corpus in rows:
        csv_path = Path(src_csv)
        if csv_path not in defaults:
            defaults[csv_path] = _session_platform(csv_path)
        p = _row_platform({"LogFile": log_file}, csv_path, defaults[csv_path])
        if p:
            conn.execute(
                "UPDATE summary_runs SET platform=? WHERE rowid=?", (p, rowid)
            )
            n += 1
    if n:
        # Persist explicitly: read-only subcommands (stats/speedup/plot/
        # export/report) never call conn.commit(), so without this the
        # UPDATEs roll back on close and the backfill re-runs forever.
        conn.commit()
        print(f"backfilled platform for {n} pre-migration rows", file=sys.stderr)


def connect(db_path: str | Path) -> sqlite3.Connection:
    path = Path(db_path)
    path.parent.mkdir(parents=True, exist_ok=True)
    conn = sqlite3.connect(path)
    conn.create_aggregate("stddev_samp", 1, _Stdev)
    # SQLite's built-in math functions (SQRT among them) are a compile-time
    # option (-DSQLITE_ENABLE_MATH_FUNCTIONS) this image's build lacks —
    # register a Python sqrt so the run_stats ci95 view works on any build.
    # NULL-in/NULL-out and negative-input NULL match the SQL convention.
    conn.create_function(
        "SQRT", 1,
        lambda v: math.sqrt(v) if v is not None and v >= 0 else None,
        deterministic=True,
    )
    conn.executescript(
        """
        CREATE TABLE IF NOT EXISTS file_index (
            path TEXT PRIMARY KEY, sha1 TEXT, kind TEXT, ingested_at TEXT
        );
        CREATE TABLE IF NOT EXISTS summary_runs (
            session_id TEXT, machine_id TEXT, git_commit TEXT, ts TEXT,
            variant TEXT, config_key TEXT, np INTEGER, batch INTEGER,
            build_status TEXT, run_status TEXT, parse_status TEXT, status TEXT,
            time_ms REAL, compile_ms REAL, shape TEXT, first5 TEXT,
            log_file TEXT, src_csv TEXT, corpus TEXT, platform TEXT
        );
        CREATE TABLE IF NOT EXISTS run_logs (
            path TEXT, session_id TEXT, time_ms REAL, shape TEXT
        );
        CREATE TABLE IF NOT EXISTS source_stats (
            path TEXT PRIMARY KEY, loc INTEGER, lang TEXT
        );
        """
    )
    # Migration for warehouses created before the corpus column existed:
    # add it (NULL rows fall through to the view's src_csv heuristic). Must
    # run before the views below, which reference the column.
    cols = {r[1] for r in conn.execute("PRAGMA table_info(summary_runs)")}
    if "corpus" not in cols:  # pragma: no cover — legacy DB only
        conn.execute("ALTER TABLE summary_runs ADD COLUMN corpus TEXT")
    if "platform" not in cols:
        conn.execute("ALTER TABLE summary_runs ADD COLUMN platform TEXT")
    conn.executescript(
        """
        DROP VIEW IF EXISTS perf_runs;
        DROP VIEW IF EXISTS best_runs;
        DROP VIEW IF EXISTS run_stats;
        CREATE VIEW perf_runs AS
            SELECT session_id, machine_id, git_commit, variant, config_key,
                   np, batch, time_ms, compile_ms, shape,
                   COALESCE(corpus,
                       CASE WHEN src_csv LIKE '%/reference/%'
                              OR src_csv LIKE '%reference_import%'
                            THEN 'reference' ELSE 'local' END) AS corpus,
                   platform
            FROM summary_runs
            WHERE status = 'OK' AND time_ms IS NOT NULL;
        -- Grouping includes platform: one repo's sessions span the CPU
        -- mesh and the TPU; pooling 11 ms CPU passes with
        -- 0.3 ms TPU passes would fabricate wild stddevs and meaningless
        -- baselines (NULL platform = pre-column or reference rows, which
        -- group among themselves per corpus).
        CREATE VIEW best_runs AS
            SELECT variant, np, batch, MIN(time_ms) AS best_ms, COUNT(*) AS n,
                   corpus, platform
            FROM perf_runs GROUP BY corpus, platform, variant, np, batch;
        CREATE VIEW run_stats AS
            SELECT variant, np, batch, COUNT(*) AS n,
                   AVG(time_ms) AS mean_ms,
                   stddev_samp(time_ms) AS stdev_ms,
                   1.96 * stddev_samp(time_ms) / SQRT(COUNT(*)) AS ci95_ms,
                   corpus, platform
            FROM perf_runs GROUP BY corpus, platform, variant, np, batch;
        """
    )
    _backfill_platform(conn)
    return conn


def _sha1(path: Path) -> str:
    h = hashlib.sha1()
    h.update(path.read_bytes())
    return h.hexdigest()


def _already_ingested(conn: sqlite3.Connection, path: Path, sha1: str) -> bool:
    row = conn.execute("SELECT sha1 FROM file_index WHERE path=?", (str(path),)).fetchone()
    return row is not None and row[0] == sha1


def _mark(conn: sqlite3.Connection, path: Path, sha1: str, kind: str) -> None:
    conn.execute(
        "INSERT OR REPLACE INTO file_index VALUES (?,?,?,datetime('now'))",
        (str(path), sha1, kind),
    )


# Reference-schema column mapping (log_analysis.py:45-72 normalises two
# schema generations; we accept both of them plus our own):
# gen-2 = the reference's session CSVs (summary_report_*.csv), gen-1 = its
# early ts/version/np/total_time_s exports (all_runs.csv style).
_REF_GEN2_MAP = {
    "ProjectVariant": "Variant",
    "NumProcesses": "NP",
    "EntryTimestamp": "Timestamp",
    "OutputFirst5Values": "First5Values",
    "RunLogFile": "LogFile",
    "OverallStatusSymbol": "Status",
}


def _normalize_row(r: dict) -> dict:
    """Normalise one CSV row to our column names and tag its corpus.

    The corpus ('reference' vs 'local') is decided by SCHEMA, not by file
    path: both reference schema generations are unmistakable from their
    headers, so reference CSVs copied anywhere (a tmp logs tree, a
    reference_import staging dir) still classify correctly. The per-corpus
    speedup baseline depends on this tag.
    """
    if "ProjectVariant" in r:  # reference gen-2 session schema
        out = dict(r)
        out["_corpus"] = "reference"
        for src, dst in _REF_GEN2_MAP.items():
            if src in out:
                out[dst] = out.pop(src)
        for src, dst in (
            ("BuildSucceeded", "BuildStatus"),
            ("RunCommandSucceeded", "RunStatus"),
            ("ParseSucceeded", "ParseStatus"),
        ):
            if src in out:
                out[dst] = "OK" if str(out.pop(src)).lower() == "true" else "FAIL"
        # Status symbols (✔/⚠/✘, common_test_utils.sh:119-178) -> our words,
        # so the perf_runs view's status='OK' filter sees both corpora.
        out["Status"] = {"✔": "OK", "⚠": "WARN", "✘": "FAIL", "✗": "FAIL"}.get(
            str(out.get("Status", "")).strip(), out.get("Status")
        )
        return out
    if "version" in r and "total_time_s" in r:  # reference gen-1 export schema
        out = {
            "_corpus": "reference",
            "Timestamp": r.get("ts"),
            "Variant": r.get("version"),
            "NP": r.get("np"),
            # gen-1 exports (all_runs.csv) contain only completed perf runs —
            # no status column exists, so mark OK or the perf_runs view's
            # status='OK' filter would silently drop the whole corpus.
            "Status": "OK",
        }
        if r.get("total_time_s"):
            out["ExecutionTime_ms"] = str(float(r["total_time_s"]) * 1e3)
        return out
    return r


_RE_DEVICES = re.compile(r"Devices: \d+ x .+ \((\w+)\)")


def _session_platform(csv_path: Path) -> Optional[str]:
    """Session-level platform fallback from the harness's env.json dump."""
    try:
        env = json.loads((csv_path.parent / "env.json").read_text()).get("env", {})
    except (OSError, ValueError):
        return None
    # JAX_PLATFORMS is a comma-separated priority list; the first entry is
    # the effective backend ('tpu,cpu' must not mint a separate group).
    jp = str(env.get("JAX_PLATFORMS", "")).lower().split(",")[0].strip()
    return jp or None


def _row_platform(r: dict, csv_path: Path, session_default: Optional[str]) -> Optional[str]:
    """Per-row platform: the run log's 'Devices: N x <kind> (<platform>)'
    line is authoritative (a session could mix backends); fall back to the
    session env. Reference-corpus rows get NULL — their platform axis
    (CPU vs CUDA) is already encoded in the variant name."""
    if r.get("_corpus") == "reference":
        return None
    log = r.get("LogFile")
    if log:
        try:
            m = _RE_DEVICES.search((csv_path.parent / log).read_text(errors="replace"))
            if m:
                return m.group(1).lower()
        except OSError:
            pass
    return session_default


def ingest_summary_csv(conn: sqlite3.Connection, path: Path) -> int:
    """Load one summary CSV — ours (harness.CSV_COLUMNS) or either of the
    reference's two schema generations, so historical reference data and new
    TPU data land in one warehouse and plot on the same axes (SURVEY §7.3)."""
    with open(path, newline="") as f:
        rows = [_normalize_row(r) for r in csv.DictReader(f)]
    conn.execute("DELETE FROM summary_runs WHERE src_csv=?", (str(path),))
    session_default = _session_platform(path)
    n = 0
    for r in rows:
        conn.execute(
            "INSERT INTO summary_runs VALUES (?,?,?,?,?,?,?,?,?,?,?,?,?,?,?,?,?,?,?,?)",
            (
                r.get("SessionID"),
                r.get("MachineID"),
                r.get("GitCommit"),
                r.get("Timestamp"),
                canonical_variant(r.get("Variant", "")),
                r.get("ConfigKey"),
                int(r["NP"]) if r.get("NP") else None,
                int(r["Batch"]) if r.get("Batch") else None,
                r.get("BuildStatus"),
                r.get("RunStatus"),
                r.get("ParseStatus"),
                r.get("Status"),
                float(r["ExecutionTime_ms"]) if r.get("ExecutionTime_ms") else None,
                float(r["Compile_ms"]) if r.get("Compile_ms") else None,
                r.get("OutputShape"),
                r.get("First5Values"),
                r.get("LogFile"),
                str(path),
                r.get("_corpus", "local"),
                _row_platform(r, path, session_default),
            ),
        )
        n += 1
    return n


def ingest_run_log(conn: sqlite3.Connection, path: Path) -> int:
    """Regex-scrape one run log (log_analysis.py run-log scrape analogue)."""
    from .harness import _RE_SHAPE, _RE_TIME

    text = path.read_text(errors="replace")
    t = _RE_TIME.search(text)
    s = _RE_SHAPE.search(text)
    conn.execute("DELETE FROM run_logs WHERE path=?", (str(path),))
    conn.execute(
        "INSERT INTO run_logs VALUES (?,?,?,?)",
        (
            str(path),
            path.parent.name,
            float(t.group(1)) if t else None,
            s.group(1) if s else None,
        ),
    )
    return 1


_LANG = {".py": "python", ".sh": "bash", ".cpp": "c++", ".cc": "c++", ".h": "c++", ".hpp": "c++"}
_SKIP_DIRS = {"node_modules", "__pycache__", "venv", "build", "dist"}


def ingest_source_stats(conn: sqlite3.Connection, repo_root: Path) -> int:
    n = 0
    for p in sorted(repo_root.rglob("*")):
        if p.suffix not in _LANG or not p.is_file():
            continue
        rel_parts = p.relative_to(repo_root).parts
        if any(part.startswith(".") or part in _SKIP_DIRS for part in rel_parts):
            continue
        loc = sum(1 for _ in open(p, errors="replace"))
        conn.execute(
            "INSERT OR REPLACE INTO source_stats VALUES (?,?,?)",
            (str(p.relative_to(repo_root)), loc, _LANG[p.suffix]),
        )
        n += 1
    return n


def _csv_kind(path: Path) -> Optional[str]:
    """Schema-sniff a CSV header: ours/gen-2 session schema or the gen-1
    export schema (e.g. the reference's root-level ``all_runs.csv``, whose
    name a bare "summary" filter would miss)."""
    try:
        with open(path, newline="", errors="replace") as f:
            header = f.readline()
    except OSError:
        return None
    if "ProjectVariant" in header or ("Variant" in header and "Status" in header):
        return "summary_csv"
    if "version" in header and "total_time_s" in header:
        return "summary_csv"
    return None


def cmd_ingest(conn: sqlite3.Connection, logs_root: Path, repo_root: Optional[Path]) -> None:
    n_csv = n_log = skipped = 0
    for path in sorted(logs_root.rglob("*")):
        if not path.is_file():
            continue
        if path.suffix == ".csv":
            kind = _csv_kind(path)
            if kind is None:
                continue
        elif path.suffix == ".log":
            kind = "run_log"
        else:
            continue
        sha1 = _sha1(path)
        if _already_ingested(conn, path, sha1):  # incremental re-ingest
            skipped += 1
            continue
        if kind == "summary_csv":
            n_csv += ingest_summary_csv(conn, path)
        else:
            n_log += ingest_run_log(conn, path)
        _mark(conn, path, sha1, kind)
    n_src = ingest_source_stats(conn, repo_root) if repo_root else 0
    conn.commit()
    print(f"ingested: {n_csv} csv rows, {n_log} run logs, {n_src} source files, {skipped} unchanged")


SPEEDUP_SQL = """
WITH base AS (
    SELECT corpus, COALESCE(platform, '') AS platform,
           COALESCE(batch, 1) AS batch, MIN(best_ms) AS t1_ms
    FROM best_runs
    WHERE variant = ? AND np = 1
    GROUP BY corpus, COALESCE(platform, ''), COALESCE(batch, 1)
)
SELECT b.variant, b.np, b.batch, b.best_ms,
       base.t1_ms / b.best_ms AS speedup,
       base.t1_ms / b.best_ms / b.np AS efficiency,
       b.corpus, b.platform
FROM best_runs b
JOIN base ON base.corpus = b.corpus
         AND base.platform = COALESCE(b.platform, '')
         AND base.batch = COALESCE(b.batch, 1)
ORDER BY b.corpus, b.platform, b.variant, b.batch, b.np
"""
# batch NULL (the reference corpus has no batch column; it is batch-1 by
# construction) is COALESCEd to 1 so historical reference rows and new
# batch-1 TPU rows share one per-image baseline. Rows at other batch sizes
# still require a same-batch np=1 baseline — no silent cross-batch ratios.
# The baseline T1 is additionally grouped PER CORPUS (reference-ingested
# CSVs vs this repo's own sessions, derived from src_csv origin) AND PER
# PLATFORM (local sessions span the CPU mesh and the TPU — a 0.3 ms TPU
# run must not be "sped up" against an 11 ms CPU baseline): each (corpus, platform) group is judged against its own
# serial baseline — mirroring log_analysis.py:213-222, which only ever
# saw one corpus on one backend. Cross-corpus/platform comparison stays
# available via the raw best_runs view (all share the variant axis).


def cmd_speedup(conn: sqlite3.Connection, baseline: str) -> List[tuple]:
    rows = conn.execute(SPEEDUP_SQL, (baseline,)).fetchall()
    if not rows:
        print(f"no data (is there a '{baseline}' np=1 run ingested?)", file=sys.stderr)
        return []
    print(
        f"{'variant':22s} {'np':>3s} {'batch':>5s} {'best_ms':>10s} {'S(N)':>7s} "
        f"{'E(N)':>6s} {'corpus':>9s} {'platform':>8s}"
    )
    for v, np_, b, ms, s, e, corpus, platform in rows:
        # batch is NULL for reference-corpus rows (the reference is batch-1
        # with no batch column).
        print(
            f"{v:22s} {np_:3d} {str(b) if b is not None else '-':>5s} "
            f"{ms:10.3f} {s:7.2f} {e:6.2f} {corpus:>9s} {platform or '-':>8s}"
        )
    return rows


def cmd_stats(conn: sqlite3.Connection) -> None:
    rows = conn.execute(
        "SELECT variant, np, batch, n, mean_ms, stdev_ms, ci95_ms, corpus, platform "
        "FROM run_stats ORDER BY corpus, platform, variant, batch, np"
    ).fetchall()
    print(
        f"{'variant':22s} {'np':>3s} {'batch':>5s} {'n':>4s} {'mean_ms':>10s} "
        f"{'stdev':>8s} {'ci95':>8s} {'corpus':>9s} {'platform':>8s}"
    )
    for v, np_, b, n, mean, sd, ci, corpus, platform in rows:
        # batch NULL = the (batch-1) reference corpus; '-' like the other
        # commands, never a fabricated 0.
        print(
            f"{v:22s} {np_:3d} {str(b) if b is not None else '-':>5s} {n:4d} "
            f"{mean:10.3f} {sd or 0:8.3f} {ci or 0:8.3f} {corpus:>9s} "
            f"{platform or '-':>8s}"
        )


def cmd_plot(conn: sqlite3.Connection, out_dir: Path, baseline: str) -> None:
    import matplotlib

    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    rows = conn.execute(SPEEDUP_SQL, (baseline,)).fetchall()
    if not rows:
        print("no data to plot", file=sys.stderr)
        return
    out_dir.mkdir(parents=True, exist_ok=True)
    groups = {(r[6], r[7]) for r in rows}
    by_variant: dict = {}
    for v, np_, b, ms, s, e, corpus, platform in rows:
        # batch NULL = the (batch-1) reference corpus; normalize so mixed
        # corpora sort and label consistently. The corpus/platform tag only
        # appears when the warehouse actually holds more than one group.
        label = f"{v} (b={b if b is not None else 1})"
        if len(groups) > 1:
            label += f" [{corpus}{'/' + platform if platform else ''}]"
        by_variant.setdefault(label, []).append((np_, s, e))
    for idx, (title, ylab, fname) in enumerate(
        [("Speedup vs serial baseline", "S(N) = T1/TN", "speedup.png"),
         ("Parallel efficiency", "E(N) = S(N)/N", "efficiency.png")]
    ):
        fig, ax = plt.subplots(figsize=(7, 4.5))
        for label, pts in sorted(by_variant.items()):
            pts.sort()
            xs = [p[0] for p in pts]
            ys = [p[1 + idx] for p in pts]
            ax.plot(xs, ys, marker="o", label=label)
        if idx == 0:
            lim = max(p[0] for pts in by_variant.values() for p in pts)
            ax.plot([1, lim], [1, lim], "k--", alpha=0.4, label="ideal")
        else:
            ax.axhline(1.0, color="k", ls="--", alpha=0.4)
        ax.set_xlabel("shard count (np)")
        ax.set_ylabel(ylab)
        ax.set_title(title)
        ax.legend(fontsize=7)
        fig.tight_layout()
        fig.savefig(out_dir / fname, dpi=120)
        plt.close(fig)
        print(f"wrote {out_dir / fname}")


def cmd_report(conn: sqlite3.Connection, out: Path, baseline: str) -> None:
    """Markdown analysis report — the reference's ``best_runs.md`` /
    ``analysis_exports/*_report.md`` analogue, generated from the warehouse.
    """
    import datetime

    lines: List[str] = []
    lines.append("# Performance analysis report")
    lines.append("")
    n_runs = conn.execute("SELECT COUNT(*) FROM summary_runs").fetchone()[0]
    n_perf = conn.execute("SELECT COUNT(*) FROM perf_runs").fetchone()[0]
    sessions = conn.execute(
        "SELECT COUNT(DISTINCT session_id) FROM summary_runs"
    ).fetchone()[0]
    machines = [
        r[0]
        for r in conn.execute(
            "SELECT DISTINCT machine_id FROM summary_runs WHERE machine_id IS NOT NULL"
        )
    ]
    lines.append(
        f"Generated {datetime.datetime.now(datetime.timezone.utc).strftime('%Y-%m-%d %H:%M UTC')} "
        f"from {n_runs} ingested rows ({n_perf} OK perf runs) across "
        f"{sessions} sessions; machines: {', '.join(machines) or 'n/a'}."
    )

    lines.append("")
    lines.append("## Best runs (min time per variant / np / batch)")
    lines.append("")
    lines.append("| variant | np | batch | best_ms | img/s | n | corpus | platform |")
    lines.append("|---|---:|---:|---:|---:|---:|---|---|")
    for v, np_, b, ms, n, corpus, platform in conn.execute(
        "SELECT variant, np, batch, best_ms, n, corpus, platform FROM best_runs "
        "ORDER BY corpus, platform, variant, batch, np"
    ):
        imgs = (b or 1) / (ms / 1e3) if ms else 0.0
        lines.append(
            f"| {v} | {np_} | {b if b is not None else '-'} | {ms:.3f} | {imgs:.1f} "
            f"| {n} | {corpus} | {platform or '-'} |"
        )

    lines.append("")
    lines.append(
        f"## Speedup & efficiency vs `{baseline}` (np=1, same batch, same corpus+platform)"
    )
    lines.append("")
    lines.append("| variant | np | batch | best_ms | S(N) | E(N) | corpus | platform |")
    lines.append("|---|---:|---:|---:|---:|---:|---|---|")
    for v, np_, b, ms, s, e, corpus, platform in conn.execute(SPEEDUP_SQL, (baseline,)):
        lines.append(
            f"| {v} | {np_} | {b if b is not None else '-'} | {ms:.3f} | {s:.2f} "
            f"| {e:.2f} | {corpus} | {platform or '-'} |"
        )

    lines.append("")
    lines.append("## Run statistics (mean / stddev / 95% CI)")
    lines.append("")
    lines.append(
        "| variant | np | batch | n | mean_ms | stdev_ms | ci95_ms | corpus | platform |"
    )
    lines.append("|---|---:|---:|---:|---:|---:|---:|---|---|")
    for v, np_, b, n, mean, sd, ci, corpus, platform in conn.execute(
        "SELECT variant, np, batch, n, mean_ms, stdev_ms, ci95_ms, corpus, platform "
        "FROM run_stats ORDER BY corpus, platform, variant, batch, np"
    ):
        lines.append(
            f"| {v} | {np_} | {b if b is not None else '-'} | {n} | {mean:.3f} "
            f"| {f'{sd:.3f}' if sd is not None else '-'} "
            f"| {f'{ci:.3f}' if ci is not None else '-'} | {corpus} | {platform or '-'} |"
        )

    lines.append("")
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text("\n".join(lines))
    print(f"wrote {out} ({n_perf} perf runs, {sessions} sessions)")


# Stage map for the narrative: canonical variant name (shared with the
# reference's corpus), this framework's config key, and what the stage IS.
# The names are the join key between the two corpora, so the narrative can
# put the reference's GPU/MPI measurements and the TPU re-design's
# measurements in one story (reference analysis.md's canonical-name
# discipline, canonical_version_name).
_STAGES = (
    ("V1 Serial", "v1_jit", "single-device XLA baseline (reference: serial C++)"),
    ("V2.1 BroadcastAll", "v2.1_replicated", "replicate-compute-everywhere (the negative-scaling pedagogy stage)"),
    ("V2.2 ScatterHalo", "v2.2_sharded", "row-sharded + multi-hop ppermute halos (reference: MPI scatter+halo)"),
    ("V3 CUDA", "v3_pallas", "hand-written kernels (Pallas MXU vs reference CUDA)"),
    ("V4 MPI+CUDA", "v4_hybrid", "sharded + all_gather-staged halos (reference: host-staged MPI+CUDA)"),
    ("V5 MPI+CUDA-Aware", "v5_collective", "device-device halos over ICI (reference: planned, never built)"),
    ("V6 AlexNet Full", "v6_full_jit", "full 8-layer AlexNet + FC head (beyond the reference's blocks 1-2)"),
    ("V7 TensorParallel", "v7_tp", "conv-K tensor parallelism (beyond the reference)"),
)


def cmd_narrative(conn: sqlite3.Connection, out: Path, baseline: str) -> None:
    """The H7 narrative artifact: a regenerable reference-vs-TPU story woven
    from the warehouse — per-stage comparison, scaling pedagogy, MFU, and
    the static comm plan — not a table dump (that's ``report``). The
    reference's equivalent is its ``analysis.md``/notebook walk-through."""
    import datetime
    import json as _json

    L: List[str] = []
    say = L.append
    say("# Analysis narrative: the staged study, reference GPU/MPI vs TPU re-design")
    say("")
    say(
        f"Generated {datetime.datetime.now(datetime.timezone.utc).strftime('%Y-%m-%d %H:%M UTC')} "
        "by `python -m cuda_mpi_gpu_cluster_programming_tpu.analysis narrative` "
        "from the measurement warehouse (re-run after any capture to refresh)."
    )
    say("")

    # --- 1. The study -----------------------------------------------------
    say("## 1. What is being compared")
    say("")
    say(
        "The reference project tells a staged story — serial C++, naive "
        "replication, scatter+halo MPI, CUDA kernels, hybrid MPI+CUDA — each "
        "stage measured on the same AlexNet blocks-1-2 workload. This "
        "framework re-designs every stage TPU-first (XLA/Pallas/shard_map "
        "over a device mesh) and ingests the reference's own measurement "
        "corpus next to its own, so both sit in one warehouse under "
        "canonical stage names:"
    )
    say("")
    say("| stage | TPU config | what it is |")
    say("|---|---|---|")
    for name, key, desc in _STAGES:
        say(f"| {name} | `{key}` | {desc} |")
    say("")
    # COALESCE like the views: legacy NULL-corpus rows count as local
    # (SQL NULL != 'reference' is NULL, which would drop them from BOTH).
    n_ref = conn.execute(
        "SELECT COUNT(*) FROM summary_runs WHERE COALESCE(corpus,'')='reference'"
    ).fetchone()[0]
    n_loc = conn.execute(
        "SELECT COUNT(*) FROM summary_runs WHERE COALESCE(corpus,'')!='reference'"
    ).fetchone()[0]
    say(
        f"Warehouse contents: {n_ref} reference-corpus rows (the reference's "
        f"committed CSVs/logs) and {n_loc} rows from this framework's own "
        "sessions, keyed by (corpus, platform) so nothing is ever judged "
        "against another machine's baseline."
    )
    say("")

    # --- 2. Headline ------------------------------------------------------
    say("## 2. Headline")
    say("")
    bench_path = Path("perf/bench_latest.json")
    if bench_path.exists():
        try:
            bl = _json.loads(bench_path.read_text())
        except ValueError:
            bl = {}
        if bl.get("value"):
            say(
                f"The committed headline (`perf/bench_latest.json`): "
                f"**{bl['value']:,.0f} img/s** {bl.get('compute', 'fp32')} at "
                f"batch {bl.get('batch', '?')} on the {bl.get('device_kind', 'TPU')} "
                f"— {bl.get('vs_baseline', 0):,.0f}x the reference's best GPU "
                "stage (V4 MPI+CUDA, RTX-3090-class, 0.183 s/image — "
                "reference best_runs.md)."
            )
            if bl.get("mfu") is not None:
                say("")
                say(
                    f"MFU {bl['mfu']:.3f} against the chip's bf16 MXU peak"
                    + (
                        f"; fp32 runs synthesize true-fp32 from ~6 bf16 MXU "
                        f"passes, so the same measurement is "
                        f"**{bl['fp32_ceiling_fraction']:.0%} of the "
                        f"achievable fp32 ceiling**."
                        if bl.get("fp32_ceiling_fraction")
                        else "."
                    )
                )
            if isinstance(bl.get("bf16"), dict) and bl["bf16"].get("value"):
                b16 = bl["bf16"]
                say("")
                say(
                    f"bf16 headline alongside: **{b16['value']:,.0f} img/s** "
                    f"(MFU {b16.get('mfu', 0):.3f}, n={b16.get('timing_n', '?')}, "
                    f"ci95 {b16.get('timing_ci95_ms', 0):.3f} ms)."
                )
    else:
        say("No committed headline yet (perf/bench_latest.json absent).")
    say("")

    # --- 3. Stage by stage ------------------------------------------------
    say("## 3. The staged comparison, on chip")
    say("")
    say(
        "Per-image best times (min over ingested runs; ours are ms/batch at "
        "the best batch, the reference's corpus is batch-1 by construction):"
    )
    say("")
    say("| stage | reference best (ms/img, np) | TPU best (ms/img, batch) | TPU vs ref |")
    say("|---|---|---|---|")
    pending = []
    for name, key, _ in _STAGES:
        ref = conn.execute(
            "SELECT MIN(best_ms), np FROM best_runs "
            "WHERE corpus='reference' AND variant=?",
            (name,),
        ).fetchone()
        # best_ms > 0.001 excludes rows at the timing clamp floor (1e-3 ms
        # = the documented RTT-shadow fabrication from pre-work-floor
        # sessions, utils/timing.py) — a 0.001 ms "measurement" is a bound
        # that was explicitly not trusted, not a best run.
        tpu = conn.execute(
            "SELECT MIN(best_ms / COALESCE(batch, 1)) FROM best_runs "
            "WHERE corpus!='reference' AND platform='tpu' AND variant=? "
            "AND best_ms > 0.001",
            (name,),
        ).fetchone()
        ref_s = f"{ref[0]:.1f} (np={ref[1]})" if ref and ref[0] else "—"
        if tpu and tpu[0]:
            tpu_s = f"{tpu[0]:.3f}"
            ratio = f"**{ref[0] / tpu[0]:,.0f}x**" if ref and ref[0] else "—"
        else:
            tpu_s, ratio = "*pending capture*", "—"
            pending.append(name)
        say(f"| {name} | {ref_s} | {tpu_s} | {ratio} |")
    say("")
    if pending:
        say(
            f"Stages still without an on-chip row: {', '.join(pending)}. "
            "Regenerate this narrative after a chip capture lands."
        )
    else:
        say("Every stage the reference measured has an on-chip row.")
    say("")

    # --- 4. Scaling pedagogy ----------------------------------------------
    say("## 4. The scaling pedagogy (reference corpus)")
    say("")
    rows = [
        r
        for r in conn.execute(SPEEDUP_SQL, (baseline,))
        if r[6] == "reference"
    ]
    v21 = sorted((r for r in rows if r[0] == "V2.1 BroadcastAll"), key=lambda r: r[1])
    v22 = sorted((r for r in rows if r[0] == "V2.2 ScatterHalo"), key=lambda r: r[1])
    if v21:
        curve = ", ".join(f"S({r[1]})={r[4]:.2f}" for r in v21)
        say(
            f"V2.1 BroadcastAll is the study's negative result and its best "
            f"lesson: every rank recomputes everything, so adding ranks only "
            f"adds broadcast cost — the reference's own corpus shows "
            f"{curve}. The TPU analogue (`v2.1_replicated`) keeps the stage "
            "as a measured config precisely to reproduce this curve."
        )
        say("")
    if v22:
        curve = ", ".join(f"S({r[1]})={r[4]:.2f}" for r in v22)
        say(
            f"V2.2 ScatterHalo actually divides work ({curve}); its TPU "
            "analogue moves the same halos device-to-device over ICI via "
            "multi-hop `ppermute` instead of MPI_Irecv/Isend, and the exact "
            "row-ownership planner (parallel/plan.py) fixes the trim bug "
            "that corrupted the reference's np=4 gathers."
        )
        say("")
    if not (v21 or v22):
        say("(reference corpus not ingested — run the capture/ingest first)")
        say("")

    # --- 4b. This framework's own measured scaling curves -----------------
    say("## 4b. This framework's own S(N)/E(N) study (virtual CPU mesh)")
    say("")
    cpu_rows = [
        r
        for r in conn.execute(SPEEDUP_SQL, (baseline,))
        if r[6] != "reference" and (r[7] or "") == "cpu"
    ]
    if cpu_rows:
        say(
            "The same shards sweep the reference ran with `mpirun -np N "
            "--oversubscribe`, measured with THIS framework's own configs on "
            "the 8-virtual-device CPU mesh — the first self-measured scaling "
            "rows for the sharded/distributed family. **Honest caveat, same "
            "as the reference's oversubscribe runs** (its "
            "common_test_utils.sh warned the ranks share cores): this host "
            "has ONE physical core, so the mesh time-slices and wall time "
            "tracks *total work plus partition/collective overhead*, not "
            "parallel speedup. Read the curves as a work-conservation and "
            "overhead study: a work-conserving sharded config should hold "
            "S(N) ≈ 1 (flat time as shards grow), replicate-everything "
            "should fall as S(N) ≈ 1/N (N× work), and any extra droop is "
            "the cost of halos/gathers/regrouping. ICI-speedup claims stay "
            "with the on-chip rows."
        )
        say("")
        say("| variant | np | best ms | S(N) vs V1 | E(N) |")
        say("|---|---:|---:|---:|---:|")
        for v, np_, b, ms, s, e, _corpus, _plat in sorted(
            cpu_rows, key=lambda r: (r[0], r[1])
        ):
            say(f"| {v} (b={b}) | {np_} | {ms:.1f} | {s:.2f} | {e:.2f} |")
        say("")
        # Per-(variant, batch) np->ms cells — same-batch rows only (a
        # variant measured at several batches but one np would otherwise
        # fake a huge "scaling" ratio out of the batch difference), and
        # only where the np axis actually spans a range.
        by_cell: dict = {}
        for v, np_, b, ms, _s, _e, _c, _p in cpu_rows:
            by_cell.setdefault((v, b), {})[np_] = ms
        v21_cells = sorted(
            (pts for (name, _b), pts in by_cell.items()
             if name == "V2.1 BroadcastAll" and len(pts) >= 2),
            key=len, reverse=True,
        )
        flat = {
            (f"{name} (b={b})", min(pts), max(pts)): pts[max(pts)] / pts[min(pts)]
            for (name, b), pts in by_cell.items()
            if len(pts) >= 2 and name != "V2.1 BroadcastAll"
        }
        if v21_cells:
            pts = v21_cells[0]
            lo, hi = min(pts), max(pts)
            say(
                f"Measured: V2.1 BroadcastAll grows "
                f"{pts[lo]:.0f} → {pts[hi]:.0f} ms from np={lo} "
                f"to np={hi} (every shard recomputes everything — the "
                "reference's negative-scaling lesson, reproduced with this "
                "framework's own data)."
            )
        if flat:
            (bname, blo, bhi), bratio = min(flat.items(), key=lambda kv: kv[1])
            (wname, wlo, whi), wratio = max(flat.items(), key=lambda kv: kv[1])
            say(
                f"The work-dividing configs hold time ~flat on the shared "
                f"core: {bratio:.2f}× T(np={bhi})/T(np={blo}) ({bname}) to "
                f"{wratio:.2f}× T(np={whi})/T(np={wlo}) ({wname}) — the "
                "spread IS the measured partition/collective overhead."
            )
        say("")
    else:
        say(
            "*(no CPU-mesh scaling rows ingested yet — run the shards sweep "
            "via the harness with --fake-devices and re-ingest)*"
        )
        say("")

    # --- 5. Where the bytes go --------------------------------------------
    say("## 5. Where the bytes go (static comm/compute plan, 4 shards)")
    say("")
    try:
        from .models.alexnet import BLOCKS12
        from .parallel.breakdown import comm_compute_breakdown

        halo = comm_compute_breakdown(BLOCKS12, 4)
        staged = comm_compute_breakdown(BLOCKS12, 4, staged=True)
        say("| layer | halo rows (t/b) | collectives | KiB/pass | MFLOP | flop/byte |")
        say("|---|---|---:|---:|---:|---:|")
        for r in halo:
            inten = f"{r.intensity:.1f}" if r.halo_bytes else "∞"
            say(
                f"| {r.name} | {r.h_top}/{r.h_bot} | {r.collectives} "
                f"| {r.halo_bytes / 1024:.1f} | {r.flops / 1e6:.1f} | {inten} |"
            )
        hb = sum(r.halo_bytes for r in halo)
        sb = sum(r.halo_bytes for r in staged)
        say("")
        say(
            f"The staged (V4-style all_gather) transport would move "
            f"{sb / 1024:.0f} KiB/pass against the halo-only ppermute "
            f"transport's {hb / 1024:.0f} KiB — **{sb / hb:.1f}x more bytes "
            "for identical math**, which is the V4-vs-V5 story stated "
            "statically; tests assert the compiled jaxpr contains exactly "
            "these collective counts (tests/test_breakdown.py)."
        )
    except Exception as e:  # narrative must never fail the pipeline
        say(f"(static plan unavailable: {e})")
    say("")

    # --- 6. Measurement discipline ----------------------------------------
    say("## 6. Measurement discipline")
    say("")
    cells = conn.execute(
        "SELECT COUNT(*), SUM(CASE WHEN n >= 3 THEN 1 ELSE 0 END), "
        "MAX(CASE WHEN n >= 2 THEN ci95_ms END) FROM run_stats "
        "WHERE corpus!='reference' AND platform='tpu'"
    ).fetchone()
    if cells and cells[0]:
        say(
            f"{cells[0]} on-chip (variant, np, batch) cells; "
            f"{cells[1] or 0} with n>=3 samples; worst 95% CI "
            f"{cells[2]:.3f} ms." if cells[2] is not None else
            f"{cells[0]} on-chip cells (single samples so far)."
        )
    else:
        say("No on-chip cells yet.")
    say("")
    say(
        "Timing protocol: every number uses the amortized "
        "two-queue-length fence with a 100 ms work floor and a MAD-based "
        "CI on the median (utils/timing.py) — sub-3 ms rows previously "
        "carried ~40% session-to-session spread; the work floor is the fix."
    )
    say("")
    spread_path = Path("perf/session_spread_latest.json")
    if spread_path.exists():
        # Quote the ACHIEVED two-session spread (scripts/session_spread.py
        # persists the newest comparison) — measured, pass or fail, never
        # just the protocol's claim.
        try:
            sp = json.loads(spread_path.read_text())
            bar = sp.get("bar", 0.10)
            fast = [c for c in sp.get("cells", []) if c.get("sub3ms")]
            b1 = [c for c in fast if c.get("batch") == 1]
            rest = [c for c in fast if c.get("batch") != 1]
            parts = [
                "Achieved two-session spread "
                f"({' vs '.join(sp.get('sessions', []))}):"
            ]
            if rest:
                worst = max(c["spread"] for c in rest)
                batches = sorted({c["batch"] for c in rest})
                verdict = "met" if worst <= bar else "MISSED"
                parts.append(
                    f"sub-3 ms cells at batch in {batches} within "
                    f"{worst:.1%} (bar {bar:.0%} {verdict});"
                )
            if b1:
                lo_ms = min(min(c["t_a_ms"], c["t_b_ms"]) for c in b1)
                hi_ms = max(max(c["t_a_ms"], c["t_b_ms"]) for c in b1)
                lo = min(c["spread"] for c in b1)
                hi = max(c["spread"] for c in b1)
                parts.append(
                    f"batch=1 cells ({lo_ms:.1f}-{hi_ms:.1f} ms/pass) spread "
                    f"{lo:.0%}-{hi:.0%}"
                    + (
                        " — a shift the timing chain cannot average out, so "
                        "b=1 latency is reported as a bound, not a claim."
                        if hi > bar
                        else f" (bar {bar:.0%} met)."
                    )
                )
            say(" ".join(parts))
            say("")
        except (OSError, ValueError):
            pass
    say("---")
    say(
        "Regenerate: `python -m cuda_mpi_gpu_cluster_programming_tpu.analysis "
        "narrative --out docs/ANALYSIS.md` (after `... analysis ingest`)."
    )
    say("")
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text("\n".join(L))
    print(f"wrote {out}")


VIEWS = ("perf_runs", "best_runs", "run_stats", "summary_runs", "run_logs", "source_stats")


def cmd_export(conn: sqlite3.Connection, view: str, out: Path, fmt: str) -> None:
    if view not in VIEWS:
        raise SystemExit(f"unknown view {view!r}; choose from {VIEWS}")
    cur = conn.execute(f"SELECT * FROM {view}")  # noqa: S608 — view name validated above
    cols = [d[0] for d in cur.description]
    rows = cur.fetchall()
    out.parent.mkdir(parents=True, exist_ok=True)
    if fmt == "csv":
        with open(out, "w", newline="") as f:
            wtr = csv.writer(f)
            wtr.writerow(cols)
            wtr.writerows(rows)
    elif fmt == "parquet":
        import pandas as pd

        pd.DataFrame(rows, columns=cols).to_parquet(out)
    else:
        raise SystemExit(f"unknown format {fmt!r}")
    print(f"exported {len(rows)} rows from {view} to {out}")


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="cuda_mpi_gpu_cluster_programming_tpu.analysis")
    p.add_argument("--db", default=DEFAULT_DB)
    sub = p.add_subparsers(dest="cmd", required=True)
    pi = sub.add_parser("ingest", help="walk logs root, dedup, load warehouse")
    pi.add_argument("--logs", default="logs")
    pi.add_argument("--repo-root", default=".", help="root for source stats ('' to skip)")
    sub.add_parser("stats", help="run_stats view (n/mean/stddev/95%% CI)")
    ps = sub.add_parser("speedup", help="S(N)=T1/TN and E=S/N vs baseline")
    ps.add_argument("--baseline", default="V1 Serial")
    pp = sub.add_parser("plot", help="speedup/efficiency PNGs")
    pp.add_argument("--out", default="plots")
    pp.add_argument("--baseline", default="V1 Serial")
    pe = sub.add_parser("export", help="dump a view to csv/parquet")
    pe.add_argument("--view", required=True)
    pe.add_argument("--out", required=True)
    pe.add_argument("--fmt", choices=["csv", "parquet"], default="csv")
    pr = sub.add_parser("report", help="markdown best-runs/stats report")
    pr.add_argument("--out", default="analysis_exports/best_runs_report.md")
    pr.add_argument("--baseline", default="V1 Serial")
    pn = sub.add_parser(
        "narrative", help="reference-vs-TPU analysis narrative (H7 artifact)"
    )
    pn.add_argument("--out", default="docs/ANALYSIS.md")
    pn.add_argument("--baseline", default="V1 Serial")
    return p


def main(argv=None) -> int:
    args = make_parser().parse_args(argv)
    conn = connect(args.db)
    try:
        if args.cmd == "ingest":
            cmd_ingest(
                conn,
                Path(args.logs),
                Path(args.repo_root) if args.repo_root else None,
            )
        elif args.cmd == "stats":
            cmd_stats(conn)
        elif args.cmd == "speedup":
            cmd_speedup(conn, args.baseline)
        elif args.cmd == "plot":
            cmd_plot(conn, Path(args.out), args.baseline)
        elif args.cmd == "export":
            cmd_export(conn, args.view, Path(args.out), args.fmt)
        elif args.cmd == "report":
            cmd_report(conn, Path(args.out), args.baseline)
        elif args.cmd == "narrative":
            cmd_narrative(conn, Path(args.out), args.baseline)
    finally:
        conn.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
