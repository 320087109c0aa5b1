"""Journal-replay fleet simulator (ISSUE 12).

The determinism contract: a serve journal records its own inputs
(``serve_config`` conditions + per-request ``serve_submit`` arrivals +
the ``sup_trip``/``mesh_shrink`` chaos schedule), and replaying it
against its own conditions through a LIVE server must close per-class
accounting identically and land journal-derived p50/p99 within the
nearest-rank estimator's resolution. Knobs (``--traffic-mult``,
``--devices``, ``--slo-scale``) turn the same harness into a capacity
what-if whose accounting still closes."""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import (  # noqa: E402
    BLOCKS12,
)
from cuda_mpi_gpu_cluster_programming_tpu.observability.replay import (  # noqa: E402
    RecordedSubmit,
    ReplayKnobs,
    expand_schedule,
    load_recorded_run,
    percentile_resolution,
    replay_recorded,
)
from cuda_mpi_gpu_cluster_programming_tpu.resilience.journal import (  # noqa: E402
    Journal,
)
from cuda_mpi_gpu_cluster_programming_tpu.serving.loadgen import (  # noqa: E402
    run_shaped_load,
)
from cuda_mpi_gpu_cluster_programming_tpu.serving.server import (  # noqa: E402
    InferenceServer,
    ServeConfig,
)
from cuda_mpi_gpu_cluster_programming_tpu.serving.traffic import (  # noqa: E402
    default_class_mix,
    slo_policy,
)

ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
}


def _small_cfg():
    return dataclasses.replace(BLOCKS12, in_height=63, in_width=63)


def _record_shaped(journal_path, *, rate=60.0, duration=0.9, seed=0):
    """One seeded, journaled shaped-load run: the canonical recording the
    replay tests re-drive. Generous deadlines so the recorded accounting
    is all-OK (the determinism assertion is then exact, not racy)."""
    mix = list(default_class_mix([1, 2, 4]))
    scfg = ServeConfig(
        config="v1_jit",
        max_batch=4,
        journal_path=str(journal_path),
        model_cfg=_small_cfg(),
        default_deadline_s=30.0,
        slo=slo_policy(mix),
    )
    srv = InferenceServer(scfg)
    srv.start()
    try:
        report = run_shaped_load(
            srv, shape="steady", rate_rps=rate, duration_s=duration,
            classes=mix, seed=seed,
        )
    finally:
        srv.stop()
    assert report.closed and report.n_shed == 0 and report.n_failed == 0
    return report


@pytest.fixture(scope="module")
def recorded_journal(tmp_path_factory):
    jp = tmp_path_factory.mktemp("replay") / "recorded.jsonl"
    report = _record_shaped(jp)
    return jp, report


# ---------------------------------------------------------------------------
# schema + schedule reconstruction


def test_journal_records_schedule_and_conditions(recorded_journal):
    """The replay schema: one serve_config header with the run's
    conditions, one serve_submit per offered request carrying the
    arrival offset / size / class / resolved deadline."""
    jp, report = recorded_journal
    recs = Journal.load(jp)
    configs = [r for r in recs if r["kind"] == "serve_config"]
    assert len(configs) == 1
    c = configs[0]
    assert c["config"] == "v1_jit" and c["buckets"] == [1, 2, 4]
    assert c["height"] == 63 and c["width"] == 63 and c["channels"] == 3
    assert c["supervise"] is False and c["slo"]["classes"]
    submits = [r for r in recs if r["kind"] == "serve_submit"]
    assert len(submits) == report.n_requests
    assert all(s["admitted"] for s in submits)
    # arrival offsets are monotone non-decreasing (FIFO submission) and
    # classes draw from the mix; deadlines resolved per class
    ts = [s["t_ms"] for s in submits]
    assert ts == sorted(ts)
    assert {s["cls"] for s in submits} <= {"interactive", "batch", "bulk"}
    # the RESOLVED deadline is recorded (explicit > class > server default):
    # bulk has no class deadline, so it lands on the 30 s server default
    for s in submits:
        if s["cls"] == "bulk":
            assert s["deadline_s"] == 30.0
        elif s["cls"] == "interactive":
            assert s["deadline_s"] == pytest.approx(4.0)
    rec = load_recorded_run(jp)
    assert len(rec.submits) == report.n_requests
    assert rec.config["max_batch"] == 4
    assert sum(c["offered"] for c in rec.accounting.values()) == report.n_requests
    assert rec.faults == [] and rec.unreplayed == {}


def test_unreplayable_journals_refused_attributably(tmp_path):
    """Pre-PR12 journals refuse loudly: no serve_submit records, or no
    serve_config header — each names what is missing and how to re-record."""
    jp = tmp_path / "old.jsonl"
    j = Journal(jp)
    j.append("serve_batch", key="batch:0", bucket=2, batch_ms=3.0,
             req_lat_ms={"r1": 4.0})
    with pytest.raises(ValueError, match="no serve_submit records"):
        load_recorded_run(jp)
    j.append("serve_submit", key="sub:1", rid="r1", t_ms=0.0, n=1, cls="",
             deadline_s=None, admitted=True, reason="")
    with pytest.raises(ValueError, match="no serve_config record"):
        load_recorded_run(jp)
    # and a reused journal mixing two DIFFERENT server configs refuses
    # too — there is no single set of conditions to replay under
    j.append("serve_config", key="config", config="v1_jit", n_shards=1,
             max_batch=4, buckets=[1, 2, 4])
    j.append("serve_config", key="config", config="v2.2_sharded", n_shards=2,
             max_batch=4, buckets=[1, 2, 4])
    with pytest.raises(ValueError, match="differing serve_config"):
        load_recorded_run(jp)


def test_expand_schedule_deterministic_and_validated():
    subs = [
        RecordedSubmit(
            t_ms=float(i), rid=f"r{i:06d}", n=1, cls="interactive",
            deadline_s=4.0, admitted=True, reason="",
        )
        for i in range(40)
    ]
    assert len(expand_schedule(subs, 1.0)) == 40
    doubled = expand_schedule(subs, 2.0)
    assert len(doubled) == 80
    assert [s.t_ms for s in doubled] == sorted(s.t_ms for s in doubled)
    # fractional multiples select by a stable hash: identical across calls
    once = expand_schedule(subs, 1.5)
    again = expand_schedule(subs, 1.5)
    assert [dataclasses.astuple(s) for s in once] == [
        dataclasses.astuple(s) for s in again
    ]
    assert 40 < len(once) < 80
    with pytest.raises(ValueError, match="traffic_mult"):
        expand_schedule(subs, 0.0)


def test_percentile_resolution_floor_and_bracket():
    # empty / tight samples sit at the floor
    assert percentile_resolution([], 99) == 50.0
    assert percentile_resolution([5.0, 5.1, 5.2], 50) == 50.0
    # a spread sample's resolution is the half-bracket around the rank
    xs = [1.0, 10.0, 1000.0]
    assert percentile_resolution(xs, 50, floor=0.0) == pytest.approx(
        (1000.0 - 1.0) / 2
    )
    assert percentile_resolution(xs, 99, floor=0.0) == pytest.approx(
        (1000.0 - 10.0) / 2
    )


# ---------------------------------------------------------------------------
# the determinism contract (acceptance)


def test_neutral_replay_closes_accounting_identically(recorded_journal, tmp_path):
    """ISSUE 12 acceptance: replaying a recorded journal against its own
    conditions reproduces per-class accounting EXACTLY and journal
    percentiles within the estimator's resolution."""
    jp, report = recorded_journal
    rec = load_recorded_run(jp)
    rjp = tmp_path / "replay.jsonl"
    out = replay_recorded(rec, ReplayKnobs(journal_path=str(rjp)))
    # accounting: exact per-class identity, not aggregate equality
    assert out.accounting_matches and out.accounting_closed
    for cls, want in rec.accounting.items():
        assert out.per_class[cls] == want, cls
    # percentiles: both sides measured, within nearest-rank resolution
    for q in (50, 99):
        recorded_p, replayed_p = out.percentile_pair(q)
        assert recorded_p is not None and replayed_p is not None
        assert out.percentile_within_resolution(q) is True, (
            q, recorded_p, replayed_p,
        )
    assert out.diverged is False
    assert out.cache_misses == 0  # the bucket discipline survives replay
    # the replay journal is itself a complete recording: same schedule,
    # same conditions — replayable all the way down
    rec2 = load_recorded_run(rjp)
    assert len(rec2.submits) == len(rec.submits)
    assert rec2.config["buckets"] == rec.config["buckets"]
    assert {
        c: v["offered"] for c, v in rec2.accounting.items()
    } == {c: v["offered"] for c, v in rec.accounting.items()}


def test_what_if_doubled_traffic_half_devices_sheds_more(tmp_path):
    """The capacity what-if: --traffic-mult 2 at half the devices with
    SLO budgets tightened produces a HIGHER shed count than the recorded
    run (zero), while per-class accounting still closes — and the
    unbounded bulk class is never SLO-shed."""
    jp = tmp_path / "recorded.jsonl"
    mix = list(default_class_mix([1, 2, 4]))
    scfg = ServeConfig(
        config="v2.2_sharded", n_shards=2, max_batch=4, supervise=True,
        journal_path=str(jp), model_cfg=_small_cfg(),
        default_deadline_s=30.0, slo=slo_policy(mix),
    )
    srv = InferenceServer(scfg)
    srv.start()
    try:
        report = run_shaped_load(
            srv, shape="steady", rate_rps=50, duration_s=0.8, classes=mix,
            seed=2,
        )
    finally:
        srv.stop()
    assert report.closed and report.n_shed == 0
    rec = load_recorded_run(jp)
    out = replay_recorded(
        rec,
        ReplayKnobs(
            traffic_mult=2.0,
            devices=1,
            slo_scale=0.002,  # interactive budget 1000ms -> 2ms: saturates
            journal_path=str(tmp_path / "whatif.jsonl"),
        ),
    )
    assert out.n_offered == 2 * report.n_requests
    assert out.n_shed > report.n_shed  # the what-if answer: it would shed
    assert out.accounting_closed  # no silent loss even past capacity
    assert out.diverged is False  # what-ifs are never "divergence"
    # every class's books close individually, not just in aggregate
    for cls, c in out.per_class.items():
        assert (
            c["ok"] + c["shed"] + c["failed"] + c["rejected"] == c["offered"]
        ), cls


def test_replay_redrives_recorded_chaos_schedule(tmp_path):
    """The chaos half of the contract: a recorded mesh-shrink drill
    replays with the SAME victim device ids lost at the same supervised
    step (scripted, not re-drawn), producing the same incident shape in
    the replay journal — and accounting still matches identically."""
    from cuda_mpi_gpu_cluster_programming_tpu.resilience import chaos
    from cuda_mpi_gpu_cluster_programming_tpu.serving.loadgen import run_load

    jp = tmp_path / "drill.jsonl"
    scfg = ServeConfig(
        config="v2.2_sharded", n_shards=2, max_batch=4, supervise=True,
        journal_path=str(jp), model_cfg=_small_cfg(),
        default_deadline_s=30.0,
    )
    saved = os.environ.get(chaos.CHAOS_ENV)
    os.environ[chaos.CHAOS_ENV] = "seed=3,mesh_shrink=1"
    chaos.reset()
    try:
        srv = InferenceServer(scfg)
        srv.start()
        try:
            report = run_load(srv, rate_rps=30, duration_s=0.7, seed=1)
        finally:
            srv.stop()
    finally:
        if saved is None:
            os.environ.pop(chaos.CHAOS_ENV, None)
        else:
            os.environ[chaos.CHAOS_ENV] = saved
        chaos.reset()
    assert report.n_ok == report.n_requests
    recorded_shrinks = [
        r for r in Journal.load(jp) if r["kind"] == "mesh_shrink"
    ]
    assert len(recorded_shrinks) == 1
    rec = load_recorded_run(jp)
    assert len(rec.faults) == 1
    assert rec.faults[0].kind == "mesh_shrink"
    assert tuple(rec.faults[0].lost) == tuple(recorded_shrinks[0]["lost"])

    rjp = tmp_path / "replay.jsonl"
    out = replay_recorded(rec, ReplayKnobs(journal_path=str(rjp)))
    rrecs = Journal.load(rjp)
    replayed_shrinks = [r for r in rrecs if r["kind"] == "mesh_shrink"]
    assert [r["lost"] for r in replayed_shrinks] == [
        recorded_shrinks[0]["lost"]
    ]
    trips = [r for r in rrecs if r["kind"] == "sup_trip"]
    assert [t["sdc_kind"] for t in trips] == ["mesh_shrink"]
    assert trips[0]["step"] == rec.faults[0].step
    assert out.scripted_faults == 1
    assert out.accounting_matches and out.accounting_closed
    # incident replays gate on accounting; percentile pairs still report
    assert out.diverged is False


def test_replay_refuses_incident_trail_without_supervision(tmp_path):
    """A journal whose incident trail cannot be re-driven (recorded
    unsupervised) refuses attributably instead of silently replaying a
    loss-free run."""
    jp = tmp_path / "j.jsonl"
    j = Journal(jp)
    j.append("serve_config", key="config", config="v1_jit", n_shards=1,
             compute="fp32", max_batch=4, buckets=[1, 2, 4], max_pending=64,
             poll_s=0.02, default_deadline_s=30.0, supervise=False,
             height=63, width=63, channels=3, slo=None, devices=1)
    j.append("serve_submit", key="sub:1", rid="r1", t_ms=0.0, n=1, cls="",
             deadline_s=30.0, admitted=True, reason="")
    j.append("mesh_shrink", key="shrink:8->7", before=8, after=7, lost=[3],
             cause="chaos:mesh_shrink")
    j.append("sup_trip", key="trip:1", sdc_kind="mesh_shrink", step=0,
             entry="halo@2:reference", cause="x")
    with pytest.raises(ValueError, match="not supervised"):
        replay_recorded(load_recorded_run(jp))


# ---------------------------------------------------------------------------
# CLI exit codes (documented: 0 clean / 2 usage / 3 divergence-regression)


def test_replay_cli_missing_and_unreplayable_exit_2(tmp_path):
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "cuda_mpi_gpu_cluster_programming_tpu.observability",
            "replay", "--journal", str(tmp_path / "nope.jsonl"),
        ],
        capture_output=True, text=True, cwd=ROOT, timeout=120, env=ENV,
    )
    assert proc.returncode == 2 and "no journal" in proc.stderr
    jp = tmp_path / "old.jsonl"
    Journal(jp).append("serve_batch", key="batch:0", bucket=1, batch_ms=1.0)
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "cuda_mpi_gpu_cluster_programming_tpu.observability",
            "replay", "--journal", str(jp),
        ],
        capture_output=True, text=True, cwd=ROOT, timeout=120, env=ENV,
    )
    assert proc.returncode == 2
    assert "unreplayable journal" in proc.stderr
    assert "serve_submit" in proc.stderr  # names WHAT is missing


def test_replay_cli_neutral_roundtrip(recorded_journal, tmp_path):
    """`observability replay --journal <recorded>` exits 0 and prints the
    machine-readable report; --json parses with the contract fields."""
    jp, _report = recorded_journal
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "cuda_mpi_gpu_cluster_programming_tpu.observability",
            "replay", "--journal", str(jp), "--json",
            "--journal-out", str(tmp_path / "rj.jsonl"),
        ],
        capture_output=True, text=True, cwd=ROOT, timeout=300, env=ENV,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    obj = json.loads(proc.stdout.strip().splitlines()[-1])
    assert obj["neutral"] is True
    assert obj["accounting_matches"] is True
    assert obj["diverged"] is False
    assert obj["p50_ms"] > 0 and obj["recorded_p50_ms"] > 0


def test_run_cli_serve_replay(recorded_journal, tmp_path):
    """run --serve-replay prints the machine-parsed Replay:/Replay class:
    lines and exits 0 on a clean neutral replay."""
    jp, report = recorded_journal
    proc = subprocess.run(
        [
            sys.executable, "-m", "cuda_mpi_gpu_cluster_programming_tpu.run",
            "--serve-replay", str(jp),
            "--replay-journal", str(tmp_path / "rj.jsonl"),
        ],
        capture_output=True, text=True, cwd=ROOT, timeout=300, env=ENV,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    replay_line = next(
        l for l in proc.stdout.splitlines() if l.startswith("Replay: ")
    )
    assert f"offered={report.n_requests}" in replay_line
    assert "accounting_matches=True" in replay_line
    assert "diverged=False" in replay_line
    assert any(
        l.startswith("Replay class: ") for l in proc.stdout.splitlines()
    )
    # bad knob -> usage
    proc = subprocess.run(
        [
            sys.executable, "-m", "cuda_mpi_gpu_cluster_programming_tpu.run",
            "--serve-replay", str(jp), "--replay-mult", "0",
        ],
        capture_output=True, text=True, cwd=ROOT, timeout=120, env=ENV,
    )
    assert proc.returncode == 2


# ---------------------------------------------------------------------------
# serve_fail class attribution (the schema satellite)


def test_serve_fail_record_carries_req_cls(tmp_path):
    """A terminally failed batch journals rid->class like serve_batch, so
    replay accounting attributes failures per class."""
    jp = tmp_path / "fail.jsonl"
    scfg = ServeConfig(
        config="v1_jit", max_batch=4, journal_path=str(jp),
        model_cfg=_small_cfg(), default_deadline_s=30.0,
    )
    srv = InferenceServer(scfg)
    srv._ensure_built()

    def boom(params, x):
        raise RuntimeError("broken forward (test)")

    srv._fwd = boom
    h1 = srv.submit(np.ones((1, 63, 63, 3), np.float32), cls="interactive")
    h2 = srv.submit(np.ones((1, 63, 63, 3), np.float32), cls="bulk")
    srv.run_until_drained()
    assert h1.status == "FAILED" and h2.status == "FAILED"
    fails = [r for r in Journal.load(jp) if r["kind"] == "serve_fail"]
    assert fails
    seen = {}
    for r in fails:
        seen.update(r["req_cls"])
    assert sorted(seen.values()) == ["bulk", "interactive"]
    # and the journal round-trips into per-class failed counts
    rec = load_recorded_run(jp)
    assert rec.accounting["interactive"]["failed"] == 1
    assert rec.accounting["bulk"]["failed"] == 1
