"""Deploy-and-collect executor: sync code, launch every host, gather logs.

The execution layer of the multi-host story — the analogue of the parts of
``scripts/2_final_multi_machine.sh`` that actually *do* things rather than
render them: SSH reachability validation (:229-238), rsync code sync
(:258-287), per-host mpirun launches with log capture (:393-410, :502-517)
and per-version output parsing into a summary (:525-548). ``distributed.
launch_plan`` renders the per-host commands; this module runs them.

Transport rules:

- Remote hosts use ``ssh`` (BatchMode, so a missing trust setup fails fast
  instead of prompting) and ``rsync -az --delete`` for code sync.
- Hosts that resolve to this machine (``localhost``/``127.0.0.1``/our own
  hostname) run through a local shell and sync via ``shutil.copytree`` —
  the degenerate single-machine cluster the reference exercises with
  ``mpirun --oversubscribe`` on localhost, and what CI uses here (this
  image ships neither sshd nor rsync).
- ``dry_run`` renders every command (ssh/rsync included) without executing
  anything — the printable launch plan, end to end.

Every deployment writes a session directory ``<log_root>/deploy_<id>/``
with one ``host<i>_<name>.log`` per host plus a ``summary.csv`` the
analysis warehouse ingests like any harness session.
"""

from __future__ import annotations

import csv
import dataclasses
import os
import re
import shlex
import shutil
import signal
import socket
import subprocess
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from ..resilience import chaos
from ..resilience.journal import JOURNAL_NAME, Journal, atomic_writer
from ..resilience.policy import Deadline, DegradedEvent, FaultLog, RetryPolicy
from .distributed import ClusterConfig, HostSpec, launch_plan

_SYNC_EXCLUDES = (".git", "__pycache__", ".warehouse", "logs", ".pytest_cache", "*.so")

# Transport default: 2 bounded retries with 1 s/2 s backoff — enough to ride
# out ordinary ssh/rsync transients without turning
# a dead host into a multi-minute stall.
TRANSPORT_POLICY = RetryPolicy(max_retries=2, base_delay_s=1.0, max_delay_s=15.0)


def _transport_run(
    argv,
    *,
    site: str,
    timeout_s: float,
    policy: Optional[RetryPolicy] = None,
    deadline: Optional[Deadline] = None,
    shell: bool = False,
    sleep=time.sleep,
    **kw,
) -> Tuple[Optional[subprocess.CompletedProcess], FaultLog]:
    """Every ssh/rsync execution routes through here: bounded retry with
    backoff, deadline propagation (per-attempt timeout never outlives the
    budget), a per-attempt FaultLog, and the chaos injection point for the
    ``ssh``/``rsync`` sites.

    Returns ``(proc, fault_log)`` where ``proc`` is the LAST attempt (or
    None if it raised). A FileNotFoundError (no ssh/rsync binary) is
    permanent and re-raised immediately; a TimeoutExpired on the final
    attempt is re-raised so call sites keep their historical handling."""
    policy = policy or TRANSPORT_POLICY
    deadline = deadline or Deadline.after(None)
    flog = FaultLog(site=site)
    for attempt in range(max(0, policy.max_retries) + 1):
        t0 = time.monotonic()
        exc: Optional[BaseException] = None
        proc: Optional[subprocess.CompletedProcess] = None
        ch = chaos.active()
        if ch and ch.draw(site):
            proc = subprocess.CompletedProcess(
                argv, 255, stdout="", stderr=f"chaos: injected {site} transient"
            )
        else:
            try:
                # The retrying transport's own bounded execution.
                proc = subprocess.run(  # noqa: raw-subprocess
                    argv,
                    shell=shell,
                    timeout=deadline.remaining(cap=timeout_s),
                    **kw,
                )
            except FileNotFoundError:
                raise  # no transport binary: permanent, never retryable
            except (subprocess.TimeoutExpired, OSError) as e:
                exc = e
        if proc is not None and proc.returncode == 0:
            flog.record("ok", duration_s=time.monotonic() - t0)
            return proc, flog
        cause = (
            f"{type(exc).__name__}" if exc is not None
            else f"exit {proc.returncode}: {str(proc.stderr or '').strip()[:120]}"
        )
        if attempt >= policy.max_retries or deadline.expired:
            flog.record("fail", cause, time.monotonic() - t0)
            if exc is not None:
                raise exc
            return proc, flog
        pause = min(policy.delay_s(attempt + 1), deadline.remaining())
        flog.record("retry", cause, time.monotonic() - t0, backoff_s=pause)
        if pause > 0:
            sleep(pause)
    raise AssertionError("unreachable")  # pragma: no cover

# Result-line contract of the per-host workloads (selftest/examples print
# "... -> PASSED|FAILED"; the run CLI prints the timing contract lines).
_RE_VERDICT = re.compile(r"->\s*(PASSED|FAILED)")
_RE_TIME = re.compile(r"completed in ([0-9.]+) ms")

OK, FAIL, TIMEOUT, UNREACHABLE, SKIPPED = "OK", "FAIL", "TIMEOUT", "UNREACHABLE", "DRY"


def _local_names() -> set:
    names = {"localhost", "127.0.0.1", "::1"}
    try:
        names.add(socket.gethostname())
    except OSError:  # pragma: no cover
        pass
    return names


def _resolve(name: str) -> set:
    try:
        return {ai[4][0] for ai in socket.getaddrinfo(name, None)}
    except OSError:
        return set()


_OWN_ADDRS: Optional[set] = None  # process-invariant; getfqdn can block on DNS


def _own_addrs() -> set:
    global _OWN_ADDRS
    if _OWN_ADDRS is None:
        local = {"127.0.0.1", "::1"}
        for n in (socket.gethostname(), socket.getfqdn()):
            local |= _resolve(n)
        _OWN_ADDRS = local
    return _OWN_ADDRS


def is_local(host: HostSpec) -> bool:
    """True when this inventory entry addresses THIS machine.

    Beyond the literal localhost spellings, resolve the entry and compare
    against our own addresses — an inventory written with this machine's IP
    or FQDN must use the local transport, not ssh (which this sshd-less CI
    image cannot serve)."""
    if host.host in _local_names():
        return True
    addrs = _resolve(host.host)
    return bool(addrs) and bool(addrs & _own_addrs())


@dataclasses.dataclass
class HostResult:
    """One host's outcome (the per-version parse rows of :525-548)."""

    host: str
    process_id: int
    status: str
    returncode: Optional[int] = None
    time_ms: Optional[float] = None
    verdict: str = ""
    log_file: str = ""
    tail: str = ""


def check_reachable(
    cluster: ClusterConfig,
    timeout_s: float = 10.0,
    dry_run: bool = False,
    policy: Optional[RetryPolicy] = None,
    deadline: Optional[Deadline] = None,
) -> List[Tuple[str, bool, str]]:
    """SSH reachability sweep before deploying (:229-238 analogue), with
    bounded per-host retry: a transient ssh exit must not cost a host its
    slot in the deployment."""
    out = []
    for h in cluster.hosts:
        if is_local(h):
            out.append((h.host, True, "local"))
            continue
        cmd = ["ssh", "-o", "BatchMode=yes", "-o", f"ConnectTimeout={int(timeout_s)}", h.ssh_target, "true"]
        if dry_run:
            out.append((h.host, True, "DRY: " + " ".join(cmd)))
            continue
        try:
            proc, flog = _transport_run(
                cmd, site="ssh", timeout_s=timeout_s + 5,
                policy=policy, deadline=deadline, capture_output=True,
            )
            ok = proc is not None and proc.returncode == 0
            msg = "ok" if ok else f"ssh exit {proc.returncode}"
            if ok and flog.retried:
                msg = f"ok after {flog.n_attempts} attempts"
            out.append((h.host, ok, msg))
        except (subprocess.TimeoutExpired, FileNotFoundError) as e:
            out.append((h.host, False, type(e).__name__))
    return out


def sync_code(
    cluster: ClusterConfig,
    src: str,
    workdir: str,
    dry_run: bool = False,
    policy: Optional[RetryPolicy] = None,
    deadline: Optional[Deadline] = None,
    on_error: str = "raise",
) -> List[Tuple[str, str]]:
    """Push the code tree to every host's workdir (:258-287 analogue).

    Remote hosts get ``rsync -az --delete`` through the retrying transport;
    local hosts a copytree (skipped entirely when src == workdir, the
    run-in-place case). Returns (host, action) pairs. ``on_error="report"``
    records a terminally failed host as ``"SYNC_FAILED: ..."`` instead of
    raising — the quorum-degradation path in ``deploy_and_collect`` drops
    such hosts and keeps the rest of the cluster."""
    if on_error not in ("raise", "report"):
        raise ValueError(f"on_error must be raise|report, got {on_error!r}")
    src = str(Path(src).resolve())
    actions = []
    for h in cluster.hosts:
        if is_local(h):
            dst = str(Path(workdir).resolve())
            if dst == src:
                actions.append((h.host, "in-place (src == workdir)"))
                continue
            if dry_run:
                actions.append((h.host, f"DRY: copytree {src} -> {dst}"))
                continue
            ignore = shutil.ignore_patterns(*_SYNC_EXCLUDES)
            shutil.copytree(src, dst, ignore=ignore, dirs_exist_ok=True)
            actions.append((h.host, f"copytree -> {dst}"))
        else:
            excludes = " ".join(f"--exclude={shlex.quote(e)}" for e in _SYNC_EXCLUDES)
            cmd = f"rsync -az --delete {excludes} {shlex.quote(src + '/')} {h.ssh_target}:{shlex.quote(workdir + '/')}"
            if dry_run:
                actions.append((h.host, "DRY: " + cmd))
                continue
            try:
                proc, flog = _transport_run(
                    cmd, site="rsync", timeout_s=600.0, policy=policy,
                    deadline=deadline, shell=True, capture_output=True, text=True,
                )
            except (subprocess.TimeoutExpired, FileNotFoundError) as e:
                if on_error == "report":
                    actions.append((h.host, f"SYNC_FAILED: {type(e).__name__}"))
                    continue
                raise RuntimeError(f"rsync to {h.host} failed: {type(e).__name__}") from e
            if proc.returncode != 0:
                detail = str(proc.stderr or "").strip()[:200]
                if on_error == "report":
                    actions.append((h.host, f"SYNC_FAILED: {detail}"))
                    continue
                raise RuntimeError(f"rsync to {h.host} failed: {detail}")
            actions.append(
                (h.host, "rsync ok" + (f" after {flog.n_attempts} attempts" if flog.retried else ""))
            )
    return actions


def _parse_log(text: str) -> Tuple[str, Optional[float]]:
    verdicts = _RE_VERDICT.findall(text)
    verdict = verdicts[-1] if verdicts else ""
    t = _RE_TIME.search(text)
    return verdict, (float(t.group(1)) if t else None)


def deploy_and_collect(
    cluster: ClusterConfig,
    script: str,
    script_args: Sequence[str] = (),
    workdir: str = "/root/repo",
    log_root: str = "logs",
    timeout_s: float = 300.0,
    extra_env: Optional[Dict[str, str]] = None,
    sync_from: Optional[str] = None,
    dry_run: bool = False,
    session_tag: str = "",
    quorum: float = 1.0,
    transport_policy: Optional[RetryPolicy] = None,
    deadline: Optional[Deadline] = None,
    lost_hosts: Sequence[Tuple[str, str]] = (),
) -> List[HostResult]:
    """The whole pipeline: (validate ->) sync -> launch all hosts
    concurrently -> wait -> capture per-host logs -> parse -> summary CSV.

    One command launches the inventory and returns the parsed per-host
    results — the capability of :393-410/:502-548 in one call.

    ``quorum`` < 1.0 enables partial-cluster graceful degradation: a host
    whose code sync terminally fails is DROPPED (reported as an UNREACHABLE
    row) and the launch plan re-renders for the surviving mesh, provided at
    least ``quorum`` of the inventory survives; the default 1.0 keeps the
    historical any-failure-raises behavior. ``lost_hosts`` carries hosts a
    caller already dropped (e.g. the CLI's reachability quorum) so they land
    in the same summary CSV instead of vanishing.
    """
    session = f"deploy_{session_tag or time.strftime('%Y%m%d_%H%M%S')}"
    session_dir = Path(log_root) / session

    if dry_run:
        cmds = launch_plan(cluster, script, script_args, workdir=workdir, extra_env=extra_env)
        if sync_from:
            for host, action in sync_code(cluster, sync_from, workdir, dry_run=True):
                print(f"sync {host}: {action}")
        for (h, cmd) in zip(cluster.hosts, cmds):
            print(f"[{h.host}] {cmd}")
        return [
            HostResult(host=h.host, process_id=i, status=SKIPPED)
            for i, h in enumerate(cluster.hosts)
        ]

    lost: List[HostResult] = [
        HostResult(host=host, process_id=-1, status=UNREACHABLE, tail=reason)
        for host, reason in lost_hosts
    ]
    if sync_from:
        actions = sync_code(
            cluster, sync_from, workdir, policy=transport_policy,
            deadline=deadline, on_error="report" if quorum < 1.0 else "raise",
        )
        for host, action in actions:
            print(f"sync {host}: {action}")
        failed = {host for host, action in actions if action.startswith("SYNC_FAILED")}
        if failed:
            alive = tuple(h for h in cluster.hosts if h.host not in failed)
            total = len(cluster.hosts) + len(lost)
            if not alive or len(alive) / total < quorum:
                raise RuntimeError(
                    f"quorum lost: {len(alive)}/{total} hosts alive after sync "
                    f"failures on {sorted(failed)} (quorum {quorum:.2f})"
                )
            print(DegradedEvent(
                f"cluster n={len(cluster.hosts)}", f"n={len(alive)}",
                "code sync failed on " + ", ".join(sorted(failed)),
            ))
            lost += [
                HostResult(host=h.host, process_id=-1, status=UNREACHABLE,
                           tail="code sync failed")
                for h in cluster.hosts if h.host in failed
            ]
            # Mesh shrink: the launch plan re-renders below with the new
            # process ids/count; a lost coordinator slot just promotes the
            # next host (host 0 of the shrunk inventory).
            cluster = dataclasses.replace(cluster, hosts=alive)

    cmds = launch_plan(cluster, script, script_args, workdir=workdir, extra_env=extra_env)
    session_dir.mkdir(parents=True, exist_ok=True)
    # 5-tuples: the open log handle rides along so it stays open until after
    # wait() (the child writes through it) and is closed before the parse.
    procs: List[Tuple[int, HostSpec, subprocess.Popen, Path, "object"]] = []
    for pid, (h, cmd) in enumerate(zip(cluster.hosts, cmds)):
        log_path = session_dir / f"host{pid}_{h.host.replace(':', '_')}.log"
        # launch_plan renders pid 0 bare (assumed-local coordinator) and
        # pid>0 with ssh; re-derive the transport from what the host IS:
        # local hosts run through a shell, remote ones through ssh —
        # whichever form launch_plan rendered.
        if is_local(h):
            if cmd.startswith("ssh "):
                cmd = shlex.split(cmd)[-1]
            argv = ["bash", "-c", cmd]
        elif cmd.startswith("ssh "):
            argv = shlex.split(cmd)
        else:  # remote host in slot 0: wrap the bare command ourselves
            argv = ["ssh", "-o", "BatchMode=yes", h.ssh_target, cmd]
        f = open(log_path, "w")
        f.write(f"$ {cmd}\n")
        f.flush()
        try:
            # New session so a timeout can kill the whole process group
            # (bash/ssh wrapper AND the python worker beneath it). Not a
            # transport: the workload launch itself, deadline-killed below.
            p = subprocess.Popen(  # noqa: raw-subprocess
                argv, stdout=f, stderr=subprocess.STDOUT, text=True,
                start_new_session=True,
            )
        except FileNotFoundError as e:  # e.g. no ssh binary on this machine
            f.write(f"launch failed: {e}\n")
            f.close()
            p = None
        procs.append((pid, h, p, log_path, f))

    results: List[HostResult] = []
    deadline = time.monotonic() + timeout_s
    for pid, h, p, log_path, f in procs:
        if p is None:
            text = log_path.read_text(errors="replace")
            results.append(
                HostResult(
                    host=h.host, process_id=pid, status=UNREACHABLE,
                    log_file=str(log_path),
                    tail="\n".join(text.strip().splitlines()[-3:]),
                )
            )
            continue
        left = max(0.1, deadline - time.monotonic())
        try:
            rc = p.wait(timeout=left)
            status = OK if rc == 0 else FAIL
        except subprocess.TimeoutExpired:
            try:
                os.killpg(os.getpgid(p.pid), signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                p.kill()
            p.wait()
            rc, status = None, TIMEOUT
            if not is_local(h):
                # Killing the local ssh client does NOT kill the remote
                # workload it launched; an orphan would keep holding the
                # coordinator port and poison the next deploy. Best-effort
                # remote teardown: match the interpreter invocation of THIS
                # script, regex-escaped and anchored so '.'/'+' in a module
                # path can't over-match. Residual risk: a concurrent deploy
                # of the SAME script on the same host is also matched —
                # acceptable for the single-operator inventories this
                # targets, and narrower than leaking the orphan.
                pat = f"-m {re.escape(script)}( |$)"
                try:
                    # Best-effort one-shot teardown, bounded at 15 s: a
                    # retry here would stall every remaining host's collect.
                    subprocess.run(  # noqa: raw-subprocess
                        ["ssh", "-o", "BatchMode=yes", h.ssh_target,
                         f"pkill -f -- {shlex.quote(pat)}"],
                        capture_output=True,
                        timeout=15,
                    )
                    f.write(f"# TIMEOUT: issued remote pkill -f {pat}\n")
                    f.flush()
                except (subprocess.TimeoutExpired, OSError):
                    pass
        f.close()
        text = log_path.read_text(errors="replace")
        verdict, time_ms = _parse_log(text)
        if status == OK and verdict == "FAILED":
            status = FAIL  # exit 0 but self-verification failed
        results.append(
            HostResult(
                host=h.host,
                process_id=pid,
                status=status,
                returncode=rc,
                time_ms=time_ms,
                verdict=verdict,
                log_file=str(log_path),
                tail="\n".join(text.strip().splitlines()[-3:]),
            )
        )

    # Lost hosts (reachability/sync quorum drops) are REPORTED, not erased:
    # they ride the same results list and summary CSV as UNREACHABLE rows.
    results += lost
    # Journal every host's terminal state (crash-consistent, fsync'd): a
    # deploy killed between wait() and the summary write still leaves a
    # durable per-host record an operator/resume tool can read.
    with Journal(session_dir / JOURNAL_NAME) as jr:
        for r in results:
            jr.append(
                "host",
                key=f"{r.process_id}:{r.host}",
                status=r.status,
                returncode=r.returncode,
                verdict=r.verdict,
                time_ms=r.time_ms,
                log_file=r.log_file,
            )
    # Summary schema follows the harness/analysis contract (Variant + Status
    # columns) so analysis._csv_kind recognizes it and deploy sessions land
    # in the warehouse like any other session; Host/ProcessID/Verdict are
    # extra columns the ingester carries through r.get() untouched. Written
    # atomically: readers (warehouse ingest) never see a torn CSV.
    variant = f"MultiHost {script.rsplit('.', 1)[-1]}"
    with atomic_writer(session_dir / "summary.csv", "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(
            ["SessionID", "MachineID", "Variant", "NP", "Status",
             "ExecutionTime_ms", "LogFile", "Host", "ProcessID", "ReturnCode", "Verdict"]
        )
        for r in results:
            w.writerow(
                [session, r.host, variant, cluster.num_processes, r.status,
                 r.time_ms, r.log_file, r.host, r.process_id, r.returncode, r.verdict]
            )
    return results


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(prog="cuda_mpi_gpu_cluster_programming_tpu.parallel.deploy")
    p.add_argument("--hosts", nargs="+", required=True, metavar="HOST", help="'user@host arch' inventory entries")
    p.add_argument("--script", default="cuda_mpi_gpu_cluster_programming_tpu.parallel.distributed")
    p.add_argument("--script-args", nargs="*", default=[])
    p.add_argument("--workdir", default=os.getcwd())
    p.add_argument("--sync-from", help="source tree to push to every host before launching")
    p.add_argument("--log-root", default="logs")
    p.add_argument("--timeout", type=float, default=300.0)
    p.add_argument("--fake-devices", type=int, default=0, help="run every host on N virtual CPU devices")
    p.add_argument("--dry-run", action="store_true")
    p.add_argument("--skip-reachability", action="store_true")
    p.add_argument("--port", type=int, default=0, help="coordinator port (0 = pick a free one)")
    p.add_argument(
        "--max-retries",
        type=int,
        default=TRANSPORT_POLICY.max_retries,
        help="bounded retries per ssh/rsync transport call",
    )
    p.add_argument(
        "--quorum",
        type=float,
        default=1.0,
        help="minimum fraction of the inventory that must be reachable/"
        "synced to proceed on a shrunk cluster (1.0 = historical all-or-"
        "abort); lost hosts are reported as UNREACHABLE rows",
    )
    p.add_argument(
        "--deadline-s",
        type=float,
        default=0.0,
        help="wall-clock budget for the transport phase (reach+sync retries "
        "never outlive it; 0 = unbounded)",
    )
    args = p.parse_args(argv)

    port = args.port
    if not port:
        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
    if not 0.0 < args.quorum <= 1.0:
        print(f"--quorum must be in (0, 1], got {args.quorum}")
        return 2
    policy = RetryPolicy(max_retries=max(0, args.max_retries), base_delay_s=1.0, max_delay_s=15.0)
    deadline = Deadline.after(args.deadline_s or None)
    cluster = ClusterConfig.parse(args.hosts, port=port)
    lost: List[Tuple[str, str]] = []
    if not args.skip_reachability:
        checks = check_reachable(
            cluster, dry_run=args.dry_run, policy=policy, deadline=deadline
        )
        for host, ok, msg in checks:
            print(f"reach {host}: {'ok' if ok else 'FAILED'} ({msg})")
        dead = [(host, msg) for host, ok, msg in checks if not ok]
        if dead:
            alive_frac = (len(checks) - len(dead)) / len(checks)
            if args.quorum >= 1.0 or alive_frac < args.quorum:
                return 2
            dead_names = {h for h, _ in dead}
            alive = tuple(h for h in cluster.hosts if h.host not in dead_names)
            print(DegradedEvent(
                f"cluster n={len(cluster.hosts)}", f"n={len(alive)}",
                "unreachable: " + ", ".join(sorted(dead_names)),
            ))
            cluster = dataclasses.replace(cluster, hosts=alive)
            lost = [(h, f"unreachable: {m}") for h, m in dead]

    extra_env = None
    if args.fake_devices:
        extra_env = {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={args.fake_devices}",
        }
    results = deploy_and_collect(
        cluster,
        args.script,
        args.script_args,
        workdir=args.workdir,
        log_root=args.log_root,
        timeout_s=args.timeout,
        extra_env=extra_env,
        sync_from=args.sync_from,
        dry_run=args.dry_run,
        quorum=args.quorum,
        transport_policy=policy,
        deadline=deadline,
        lost_hosts=lost,
    )
    for r in results:
        t = f" {r.time_ms:.1f} ms" if r.time_ms is not None else ""
        v = f" [{r.verdict}]" if r.verdict else ""
        print(f"host{r.process_id} {r.host}: {r.status}{t}{v}  ({r.log_file})")
    if args.dry_run:
        return 0
    # Quorum-dropped hosts (process_id < 0) degrade the deploy, they don't
    # fail it — the surviving mesh's own outcomes decide the exit code.
    launched = [r for r in results if r.process_id >= 0]
    return 0 if launched and all(r.status == OK for r in launched) else 1


if __name__ == "__main__":
    raise SystemExit(main())
