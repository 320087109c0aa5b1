"""Bench/test harness: sweep, triage, CSV, ASCII summary table.

Python replacement for the reference's bash harness layer (L5):

- ``scripts/common_test_utils.sh`` — run+classify (exit 0/3/4 =
  ok / mpi-warn / critical via log grep, :84-117), CSV row writer
  (:71-81), box-drawing ASCII summary table (:119-178), per-case pipeline
  (:187-346).
- ``scripts/0_run_final_project.sh`` / ``1_final_unique_machine.sh`` — the
  variant x np sweep matrix (:44-70) and the 20-column CSV schema (:41).
- ``final_project/v4_mpi_cuda/test_v4.sh`` — per-case log capture + colored
  PASS/FAIL/WARN summary.
- ``scripts/test_hw.sh`` — per-run timeout (:124) and sweep skip rules.

Each case runs ``python -m cuda_mpi_gpu_cluster_programming_tpu.run`` in a
subprocess (the ``mpirun -np N ./template`` analogue); ``--fake-devices``
maps to ``--oversubscribe`` (N virtual XLA host devices stand in for N TPU
cores). The stdout contract parsed here is the same one the reference greps
(``Final Output Shape:`` / first-10 / ``completed in X ms``).
"""

from __future__ import annotations

import argparse
import csv
import dataclasses
import datetime
import json
import os
import platform
import re
import subprocess
import sys
import time
from pathlib import Path
from typing import List, Optional, Sequence

from .resilience.journal import JOURNAL_NAME, Journal, atomic_write_text
from .resilience.policy import DEGRADED, Deadline, FaultLog, RetryPolicy
from .utils.env_info import cpu_subprocess_env

# 20-column CSV schema (analogue of 0_run_final_project.sh:41) + the two
# resilience attempt-metadata columns (appended, so historical column
# indexes are untouched).
CSV_COLUMNS = [
    "SessionID",
    "MachineID",
    "GitCommit",
    "Timestamp",
    "Variant",
    "ConfigKey",
    "NP",
    "Batch",
    "BuildStatus",
    "BuildMsg",
    "RunStatus",
    "RunMsg",
    "ParseStatus",
    "ParseMsg",
    "Status",
    "ExecutionTime_ms",
    "Compile_ms",
    "OutputShape",
    "First5Values",
    "LogFile",
    "Attempts",
    "ResilienceMsg",
    "PlanHash",
    "SupervisorMsg",
    "Dtype",
]

# Exit-code triage classes (common_test_utils.sh:96-116); DEGRADED comes
# from resilience.policy — a run that succeeded only on a fallback tier.
OK, MESH_WARN, CRITICAL, FAIL, TIMEOUT, PARSE_ERR = (
    "OK",
    "MESH_WARN",
    "CRITICAL",
    "FAIL",
    "TIMEOUT",
    "PARSE_ERR",
)
STATUS_SYMBOL = {
    OK: "✓",  # ✓
    MESH_WARN: "⚠",  # ⚠
    PARSE_ERR: "⚠",
    DEGRADED: "↓",  # succeeded on a fallback tier — warn, don't fail
    CRITICAL: "✗",  # ✗
    FAIL: "✗",
    TIMEOUT: "⏱",  # ⏱
}

_MESH_PATTERNS = [
    r"needs \d+ devices, have \d+",
]
_CRITICAL_PATTERNS = [
    r"Segmentation fault",
    r"core dumped",
    r"Illegal instruction",
    r"Fatal Python error",
    r"MemoryError",
]


def classify(returncode: int, log_text: str) -> str:
    """Classify a finished run (common_test_utils.sh:96-116 analogue).

    MESH_WARN doesn't fail the suite — this is how a host with fewer
    devices than a case's shard count still exercises the other cases. It
    is matched only against the tail of the log (the actual raised error).
    A run that was asked for the TPU and could not initialise it is a FAIL
    like any other: nothing here excuses a missing device.
    """
    if returncode == 0:
        return OK
    if returncode == 124:  # killed by a `timeout` wrapper (test_hw.sh:124)
        return TIMEOUT
    lines = [ln for ln in log_text.strip().splitlines() if ln.strip()]
    tail = "\n".join(lines[-8:])
    for pat in _CRITICAL_PATTERNS:
        if re.search(pat, log_text):
            return CRITICAL
    for pat in _MESH_PATTERNS:
        if re.search(pat, tail):
            return MESH_WARN
    return FAIL


# Stdout-contract regexes (common_test_utils.sh:296-317 analogue).
_RE_TIME = re.compile(r"completed in ([0-9.]+) ms")
_RE_COMPILE = re.compile(r"Compile time: ([0-9.]+) ms")
_RE_SHAPE = re.compile(r"Final Output Shape: ([0-9x]+)")
_RE_FIRST = re.compile(r"Final Output \(first 10 values\): (.+)")
# Structured fallback event printed by the run CLI's Degrader
# (resilience.policy.DegradedEvent.__str__).
_RE_DEGRADED = re.compile(r"^DEGRADED\(.+?\): .*$", re.MULTILINE)
# Tuning-plan identity printed by the run CLI (run.py "Tune plan:" line):
# rows measured under a tuned per-layer variant plan carry its hash, so a
# tuned number can never masquerade as a default-lowering one in the CSV.
_RE_PLAN = re.compile(r"^Tune plan: (?:cache|swept|loaded) hash=([0-9a-f]+)", re.MULTILINE)
# Elastic-supervisor incident line printed by the run CLI under --supervise
# (resilience.supervisor.Supervisor.summary): attempts/trips/degradations
# plus the ladder rung that finally served the batch.
_RE_SUPERVISOR = re.compile(r"^Supervisor: (.+)$", re.MULTILINE)
# Precision-policy line printed by the run CLI (docs/PRECISION.md): the
# dtype the run ACTUALLY measured under (an int8w/bf16 row must never be
# read as fp32, and a tuned-winner adoption is visible per row).
_RE_PRECISION = re.compile(r"^Precision: dtype=(\S+)", re.MULTILINE)


@dataclasses.dataclass
class CaseResult:
    variant: str
    config_key: str
    np: int
    batch: int
    build_status: str = "OK"
    build_msg: str = ""
    run_status: str = FAIL
    run_msg: str = ""
    parse_status: str = "OK"
    parse_msg: str = ""
    time_ms: Optional[float] = None
    compile_ms: Optional[float] = None
    shape: str = ""
    first5: str = ""
    log_file: str = ""
    attempts: int = 1
    resilience_msg: str = ""  # retry/suppression trail (FaultLog.summary)
    degraded_msg: str = ""  # the run CLI's DEGRADED(from -> to) event line
    plan_hash: str = ""  # TunePlan identity the run measured under ("" = untuned)
    supervisor_msg: str = ""  # the run CLI's 'Supervisor: ...' incident line
    dtype: str = ""  # precision policy the run measured under ("" = pre-policy log)

    @property
    def status(self) -> str:
        if self.run_status != OK:
            return self.run_status
        if self.degraded_msg:
            # Degradation outranks parse nits: the row's numbers belong to a
            # FALLBACK tier and must never be read as the requested one.
            return DEGRADED
        if self.parse_status != "OK":
            return PARSE_ERR
        return OK


def parse_run_log(text: str, result: CaseResult) -> None:
    """Extract time/shape/first-values; missing fields degrade to parse
    errors, not failures (common_test_utils.sh:319-324)."""
    missing = []
    m = _RE_TIME.search(text)
    if m:
        result.time_ms = float(m.group(1))
    else:
        missing.append("time")
    m = _RE_COMPILE.search(text)
    if m:
        result.compile_ms = float(m.group(1))
        result.build_msg = f"jit compile {result.compile_ms:.0f} ms"
    m = _RE_SHAPE.search(text)
    if m:
        result.shape = m.group(1)
    else:
        missing.append("shape")
    m = _RE_FIRST.search(text)
    if m:
        result.first5 = " ".join(m.group(1).split()[:5])
    else:
        missing.append("values")
    if missing:
        result.parse_status = PARSE_ERR
        result.parse_msg = "missing: " + ",".join(missing)


def git_commit() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True,
            text=True,
            cwd=Path(__file__).resolve().parent.parent,
            timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _csv_line(values: List) -> str:
    """One CSV-encoded line (with terminator) — csv handles the quoting."""
    import io

    buf = io.StringIO()
    csv.writer(buf).writerow(values)
    return buf.getvalue()


@dataclasses.dataclass
class Session:
    """A harness session: one log dir, one CSV (0_run_final_project.sh:15-23),
    one crash-consistent journal.

    Every committed case is journaled (kind ``case``, the full row keyed by
    its sweep coordinates) AFTER its CSV append, making the journal the
    source of truth: ``resume=True`` reopens an interrupted session, REBUILDS
    the CSV atomically from the journaled rows (dropping any torn row a kill
    mid-append left behind), and exposes ``completed`` so the sweep skips
    journaled-complete cases and re-runs interrupted ones.
    """

    log_root: Path
    session_id: str = ""
    machine_id: str = ""
    commit: str = ""
    resume: bool = False

    def __post_init__(self) -> None:
        ts = datetime.datetime.now().strftime("%Y%m%d_%H%M%S")
        self.machine_id = self.machine_id or platform.node() or "unknown"
        self.session_id = self.session_id or f"bench_{ts}_{self.machine_id}"
        self.commit = self.commit or git_commit()
        self.dir = self.log_root / self.session_id
        self.dir.mkdir(parents=True, exist_ok=True)
        self.csv_path = self.dir / "summary.csv"
        journal_path = self.dir / JOURNAL_NAME
        self.completed: dict = {}
        if self.resume:
            self.completed = Journal.completed(Journal.load(journal_path), "case")
            text = _csv_line(CSV_COLUMNS)
            for rec in self.completed.values():
                row = rec.get("row", {})
                text += _csv_line([row.get(c, "") for c in CSV_COLUMNS])
            atomic_write_text(self.csv_path, text)
        else:
            atomic_write_text(self.csv_path, _csv_line(CSV_COLUMNS))
        self.journal = Journal(journal_path)
        # Environment dump next to the CSV (the pc_v4_environment_info.txt
        # analogue) so analysis can attribute numbers to toolchains. No
        # device probe here — the harness process must not initialize a
        # backend the run subprocesses will claim.
        if not (self.resume and (self.dir / "env.json").exists()):
            from .utils.env_info import collect

            atomic_write_text(
                self.dir / "env.json",
                json.dumps(collect(probe_devices=False), indent=2) + "\n",
            )

    def log_row(self, r: CaseResult, journal_key: str = "") -> None:
        values = [
            self.session_id,
            self.machine_id,
            self.commit,
            datetime.datetime.now().isoformat(timespec="seconds"),
            r.variant,
            r.config_key,
            r.np,
            r.batch,
            r.build_status,
            r.build_msg,
            r.run_status,
            r.run_msg,
            r.parse_status,
            r.parse_msg,
            r.status,
            f"{r.time_ms:.3f}" if r.time_ms is not None else "",
            f"{r.compile_ms:.1f}" if r.compile_ms is not None else "",
            r.shape,
            r.first5,
            r.log_file,
            r.attempts,
            r.resilience_msg or r.degraded_msg,
            r.plan_hash,
            r.supervisor_msg,
            r.dtype,
        ]
        with open(self.csv_path, "a", newline="") as f:
            csv.writer(f).writerow(values)
        # Journal AFTER the CSV append: a kill between the two re-runs the
        # case on --resume and the rebuilt CSV drops the orphan row, so a
        # case is never double-counted.
        self.journal.append(
            "case",
            key=journal_key or f"{r.config_key}|np={r.np}|b={r.batch}",
            row=dict(zip(CSV_COLUMNS, values)),
        )


def case_result_from_row(row: dict) -> CaseResult:
    """Rebuild a CaseResult from a journaled CSV-row dict (the --resume
    replay path: journaled-complete cases re-enter the summary table and
    exit-code triage without re-running)."""
    r = CaseResult(
        variant=str(row.get("Variant", "")),
        config_key=str(row.get("ConfigKey", "")),
        np=int(row.get("NP", 0) or 0),
        batch=int(row.get("Batch", 0) or 0),
        build_status=str(row.get("BuildStatus", "OK")),
        build_msg=str(row.get("BuildMsg", "")),
        run_status=str(row.get("RunStatus", FAIL)),
        run_msg=str(row.get("RunMsg", "")),
        parse_status=str(row.get("ParseStatus", "OK")),
        parse_msg=str(row.get("ParseMsg", "")),
        shape=str(row.get("OutputShape", "")),
        first5=str(row.get("First5Values", "")),
        log_file=str(row.get("LogFile", "")),
        attempts=int(row.get("Attempts", 1) or 1),
        resilience_msg=str(row.get("ResilienceMsg", "")),
        plan_hash=str(row.get("PlanHash", "")),
        supervisor_msg=str(row.get("SupervisorMsg", "")),
        dtype=str(row.get("Dtype", "")),
    )
    if row.get("ExecutionTime_ms"):
        r.time_ms = float(row["ExecutionTime_ms"])
    if row.get("Compile_ms"):
        r.compile_ms = float(row["Compile_ms"])
    if row.get("Status") == DEGRADED:
        r.degraded_msg = r.resilience_msg or "DEGRADED (journaled)"
    return r


def _run_once(
    r: CaseResult,
    cmd: List[str],
    env: dict,
    log_path: Path,
    timeout_s: float,
) -> None:
    """One attempt of the build→run→classify pipeline, logged to ``log_path``.

    There is no ``make`` step on TPU; the "build" is XLA jit compilation,
    reported by the runner as ``Compile time:`` and recorded in BuildMsg.
    """
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            cmd,
            capture_output=True,
            text=True,
            timeout=timeout_s,
            env=env,
            cwd=Path(__file__).resolve().parent.parent,
        )
        text = proc.stdout + "\n--- stderr ---\n" + proc.stderr
        r.run_status = classify(proc.returncode, text)
        if r.run_status != OK:
            last = [ln for ln in proc.stderr.strip().splitlines() if ln.strip()]
            r.run_msg = (last[-1][:160] if last else f"exit {proc.returncode}")
    except subprocess.TimeoutExpired as e:
        def _s(x):
            return x.decode(errors="replace") if isinstance(x, bytes) else (x or "")

        text = _s(e.stdout) + "\n--- stderr ---\n" + _s(e.stderr)
        r.run_status = TIMEOUT
        r.run_msg = f"timeout after {timeout_s:.0f}s"
    wall = time.perf_counter() - t0
    log_path.write_text(f"$ {' '.join(cmd)}\n# wall {wall:.2f}s\n{text}")

    if r.run_status == OK:
        parse_run_log(text, r)
        m = _RE_DEGRADED.search(text)
        if m:
            r.degraded_msg = m.group(0)[:200]
        m = _RE_PLAN.search(text)
        if m:
            r.plan_hash = m.group(1)
        m = _RE_SUPERVISOR.search(text)
        if m:
            r.supervisor_msg = m.group(1)[:200]
        m = _RE_PRECISION.search(text)
        if m:
            r.dtype = m.group(1)


def run_case(
    session: Session,
    config_key: str,
    variant: str,
    np_: int,
    batch: int,
    timeout_s: float = 300.0,
    fake_devices: int = 0,
    extra_args: Sequence[str] = (),
    log_tag: str = "",
    retry_policy: Optional[RetryPolicy] = None,
    deadline: Optional[Deadline] = None,
    sleep=time.sleep,
    journal_key: str = "",
) -> CaseResult:
    """Run one case with bounded retry, then commit exactly ONE row
    (common_test_utils.sh:223-346, hardened).

    The one retryable outcome is TIMEOUT. Each retry backs off per
    ``retry_policy`` and respects ``deadline``.
    """
    policy = retry_policy or RetryPolicy(max_retries=0)
    deadline = deadline or Deadline.after(None)
    flog = FaultLog(site=f"case:{config_key}/np{np_}/b{batch}")
    # Journal the attempt BEFORE launching: a case with a start record but
    # no committed row is exactly the "interrupted" state --resume re-runs.
    session.journal.append(
        "case_start", key=journal_key or f"{config_key}|np={np_}|b={batch}"
    )
    safe_key = config_key.replace(".", "_")
    tag = f"_{log_tag}" if log_tag else ""

    cmd = [
        sys.executable,
        "-m",
        "cuda_mpi_gpu_cluster_programming_tpu.run",
        "--config",
        config_key,
        "--shards",
        str(np_),
        "--batch",
        str(batch),
        *extra_args,
    ]
    if fake_devices:
        # The --oversubscribe analogue: N virtual host devices on CPU.
        env = cpu_subprocess_env(fake_devices)
    else:
        env = dict(os.environ)

    for attempt in range(max(0, policy.max_retries) + 1):
        r = CaseResult(variant=variant, config_key=config_key, np=np_, batch=batch)
        r.attempts = attempt + 1
        # Retries keep every attempt's log on disk (the first attempt keeps
        # the historical un-suffixed name).
        try_tag = f"_try{attempt}" if attempt else ""
        log_path = session.dir / f"run_{safe_key}_np{np_}_b{batch}{tag}{try_tag}.log"
        r.log_file = log_path.name
        t0 = time.monotonic()
        _run_once(r, cmd, env, log_path, deadline.remaining(cap=timeout_s))
        if r.run_status != TIMEOUT:
            flog.record("ok", duration_s=time.monotonic() - t0)
            break
        cause = r.run_status
        if attempt >= policy.max_retries or deadline.expired:
            flog.record("fail", cause, time.monotonic() - t0)
            break
        pause = min(policy.delay_s(attempt + 1), deadline.remaining())
        flog.record("retry", cause, time.monotonic() - t0, backoff_s=pause)
        if pause > 0:
            sleep(pause)

    r.resilience_msg = flog.summary()
    session.log_row(r, journal_key=journal_key)
    return r


def summary_table(results: List[CaseResult]) -> str:
    """Unicode box-drawing summary (common_test_utils.sh:133-178 analogue)."""
    headers = ["Variant", "Config", "NP", "Batch", "St", "Time(ms)", "Shape", "First values"]
    rows = []
    for r in results:
        rows.append(
            [
                r.variant,
                r.config_key,
                str(r.np),
                str(r.batch),
                STATUS_SYMBOL.get(r.status, "?"),
                f"{r.time_ms:.3f}" if r.time_ms is not None else "-",
                r.shape or "-",
                (r.first5[:28] or r.run_msg[:28]) or "-",
            ]
        )
    widths = [max(len(h), *(len(row[i]) for row in rows)) if rows else len(h) for i, h in enumerate(headers)]

    def line(l: str, m: str, r_: str) -> str:
        return l + m.join("─" * (w + 2) for w in widths) + r_

    def fmt(cells: List[str]) -> str:
        return "│" + "│".join(f" {c:<{w}} " for c, w in zip(cells, widths)) + "│"

    out = [line("┌", "┬", "┐"), fmt(headers), line("├", "┼", "┤")]
    out += [fmt(row) for row in rows]
    out.append(line("└", "┴", "┘"))
    return "\n".join(out)


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="cuda_mpi_gpu_cluster_programming_tpu.harness")
    p.add_argument(
        "--configs",
        default=(
            "v1_jit,v2.1_replicated,v2.2_sharded,v3_pallas,v4_hybrid,v5_collective,"
            "v6_full_jit,v6_full_pallas,v6_full_sharded,v7_tp"
        ),
        help="comma-separated config keys (default: full V1-V7 matrix incl. V6 full-AlexNet)",
    )
    p.add_argument("--shards", default="1,2,4", help="comma-separated shard counts (np sweep)")
    p.add_argument("--batches", default="1", help="comma-separated batch sizes")
    p.add_argument(
        "--computes",
        default="fp32",
        help="comma-separated precision policies to sweep "
        "(fp32,bf16,int8w — docs/PRECISION.md)",
    )
    p.add_argument("--timeout", type=float, default=300.0, help="per-case timeout seconds")
    p.add_argument(
        "--fake-devices",
        type=int,
        default=0,
        help="run cases on N virtual CPU devices (mpirun --oversubscribe analogue); "
        "0 = use the real backend",
    )
    p.add_argument("--log-root", default="logs", help="session log directory root")
    p.add_argument("--height", type=int, default=227)
    p.add_argument("--width", type=int, default=227)
    p.add_argument("--repeats", type=int, default=10)
    p.add_argument(
        "--max-retries",
        type=int,
        default=1,
        help="bounded per-case retries on TIMEOUT "
        "(0 = the historical one-shot behavior)",
    )
    p.add_argument(
        "--retry-backoff",
        type=float,
        default=2.0,
        help="base backoff seconds before the first retry (doubles per retry, jittered)",
    )
    p.add_argument(
        "--deadline-s",
        type=float,
        default=0.0,
        help="whole-sweep wall-clock budget; retries and per-case timeouts "
        "never outlive it (0 = unbounded)",
    )
    p.add_argument(
        "--fallback-chain",
        default="",
        help="forwarded to the run CLI: comma-separated fallback config keys, "
        "or 'auto' for the canonical tier ladder; failed cases re-run on the "
        "next tier and triage as DEGRADED instead of failing",
    )
    p.add_argument(
        "--plan",
        default="",
        help="TunePlan JSON path forwarded to every case's run CLI; each "
        "row's PlanHash column records the plan it actually measured under "
        "(docs/TUNING.md)",
    )
    p.add_argument(
        "--supervise",
        action="store_true",
        help="forwarded to every case's run CLI: run under the elastic "
        "supervisor (in-graph digest screening + shard-ladder re-planning); "
        "each row's SupervisorMsg column records the incident trail, and a "
        "case that finished on a lower rung triages as DEGRADED "
        "(docs/RESILIENCE.md). Blocks 1-2 configs only",
    )
    p.add_argument(
        "--resume",
        default="",
        metavar="SESSION_DIR",
        help="resume an interrupted sweep: path to its logs/<session> "
        "directory. Journaled-complete cases are replayed from the journal "
        "without re-running; interrupted/missing ones run normally and "
        "append to the same CSV (docs/RESILIENCE.md)",
    )
    return p


def main(argv=None) -> int:
    args = make_parser().parse_args(argv)
    from .configs import REGISTRY

    configs = [c.strip() for c in args.configs.split(",") if c.strip()]
    shard_counts = [int(s) for s in args.shards.split(",")]
    batches = [int(b) for b in args.batches.split(",")]
    computes = [c.strip() for c in args.computes.split(",") if c.strip()]
    bad = [c for c in computes if c not in ("fp32", "bf16", "int8w")]
    if bad:
        print(f"unknown compute modes: {bad}", file=sys.stderr)
        return 2
    unknown = [c for c in configs if c not in REGISTRY]
    if unknown:
        print(f"unknown configs: {unknown}", file=sys.stderr)
        return 2

    if args.resume:
        sdir = Path(args.resume)
        if not sdir.is_dir():
            print(f"--resume: no such session directory {sdir}", file=sys.stderr)
            return 2
        session = Session(
            log_root=sdir.parent, session_id=sdir.name, resume=True
        )
        print(
            f"Resuming session {session.session_id}: "
            f"{len(session.completed)} journaled-complete case(s) will be skipped"
        )
    else:
        session = Session(log_root=Path(args.log_root))
    print(f"Session: {session.session_id} (commit {session.commit})")
    print(f"Logs:    {session.dir}")

    extra = ["--height", str(args.height), "--width", str(args.width), "--repeats", str(args.repeats)]
    if args.fallback_chain:
        extra += ["--fallback-chain", args.fallback_chain]
    if args.plan:
        extra += ["--plan", args.plan]
    if args.supervise:
        extra += ["--supervise"]
    policy = RetryPolicy(max_retries=max(0, args.max_retries), base_delay_s=args.retry_backoff)
    deadline = Deadline.after(args.deadline_s or None)
    results: List[CaseResult] = []
    for key in configs:
        variant = REGISTRY[key].version_name
        single = REGISTRY[key].strategy == "single"
        for np_ in [1] if single else shard_counts:
            for batch in batches:
                for compute in computes:
                    # --oversubscribe semantics: with --fake-devices, grow the
                    # virtual mesh to fit np_ so every sweep point actually runs.
                    fake = max(args.fake_devices, np_) if args.fake_devices else 0
                    # Non-fp32 rows get a distinct variant name so the
                    # analysis warehouse keeps the modes separate
                    # (analysis.md:69-92 canonical-name discipline).
                    vname = variant if compute == "fp32" else f"{variant} {compute}"
                    # Full-AlexNet rows use seeded-random init: constant init
                    # is degenerate there (identical weights per channel ->
                    # all 1000 logits equal), so its printed first-5 verifies
                    # nothing. Seed 0's golden is committed in tests/oracle.py
                    # (V6_RANDOM_SEED0_BATCH1_FIRST10).
                    init_args = (
                        ["--init", "random", "--seed", "0"]
                        if REGISTRY[key].model == "alexnet_full"
                        else []
                    )
                    case_key = f"{key}|np={np_}|b={batch}|{compute}"
                    if case_key in session.completed:
                        r = case_result_from_row(
                            session.completed[case_key].get("row", {})
                        )
                        results.append(r)
                        print(
                            f"[{key} np={np_} b={batch} {compute}] "
                            f"{STATUS_SYMBOL.get(r.status, '?')} {r.status} "
                            "(journaled, skipped)"
                        )
                        continue
                    print(f"[{key} np={np_} b={batch} {compute}] ...", end="", flush=True)
                    r = run_case(
                        session,
                        key,
                        vname,
                        np_,
                        batch,
                        timeout_s=args.timeout,
                        fake_devices=fake,
                        # int8w rides the policy flag (the legacy --compute
                        # spelling stays fp32|bf16-only for old scripts).
                        extra_args=extra
                        + (
                            ["--dtype", compute]
                            if compute == "int8w"
                            else ["--compute", compute]
                        )
                        + init_args,
                        # Distinct log file per compute mode — both sweeps of
                        # one (config, np, batch) point must keep their logs.
                        log_tag=compute if len(computes) > 1 else "",
                        retry_policy=policy,
                        deadline=deadline,
                        journal_key=case_key,
                    )
                    results.append(r)
                    tail = f"{r.time_ms:.1f} ms" if r.time_ms is not None else r.run_msg
                    print(f" {STATUS_SYMBOL.get(r.status, '?')} {r.status} {tail}")

    print()
    print(summary_table(results))
    print(f"\nCSV: {session.csv_path}")
    # Warnings don't fail the suite (common_test_utils.sh exit semantics).
    worst = {CRITICAL: 4, FAIL: 1, TIMEOUT: 2}
    return max((worst.get(r.status, 0) for r in results), default=0)


if __name__ == "__main__":
    raise SystemExit(main())
