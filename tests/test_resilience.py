"""Resilience subsystem tests — all CPU-only and deterministic.

Covers the policy core (backoff/jitter/deadline math, retry_call, FaultLog),
every chaos injector (seeded CHAOS_SPEC), the Degrader fallback chains
(v5 -> v4 -> v2.2 -> v1 and Pallas -> XLA), the harness's bounded
timeout re-capture (exactly one committed row), the run CLI's
--fallback-chain degradation, and the deploy layer's retrying transports +
quorum degradation.
"""

import csv
import subprocess
import time

import pytest

from cuda_mpi_gpu_cluster_programming_tpu import harness
from cuda_mpi_gpu_cluster_programming_tpu.resilience import chaos
from cuda_mpi_gpu_cluster_programming_tpu.resilience.policy import (
    Deadline,
    DegradationExhausted,
    Degrader,
    FaultLog,
    RetryPolicy,
    retry_call,
    tier_fallback_chain,
)


@pytest.fixture(autouse=True)
def _fresh_chaos(monkeypatch):
    """Every test starts chaos-off with fresh injector counters."""
    monkeypatch.delenv(chaos.CHAOS_ENV, raising=False)
    chaos.reset()
    yield
    chaos.reset()


# ---------------------------------------------------------------- policy ---


def test_backoff_schedule_deterministic_and_bounded():
    p = RetryPolicy(max_retries=5, base_delay_s=1.0, backoff=2.0, max_delay_s=5.0, jitter=0.1)
    a = [p.delay_s(k) for k in range(1, 6)]
    b = [p.delay_s(k) for k in range(1, 6)]
    assert a == b  # seeded jitter: same policy -> same schedule
    # exponential growth within +-10% jitter, capped at max_delay_s * 1.1
    for k, d in enumerate(a, 1):
        nominal = min(5.0, 1.0 * 2.0 ** (k - 1))
        assert 0.9 * nominal <= d <= 1.1 * nominal
    assert p.delay_s(0) == 0.0
    # a different seed moves the jitter
    assert RetryPolicy(seed=1, jitter=0.1).delay_s(1) != p.delay_s(1)


def test_backoff_no_jitter_exact():
    p = RetryPolicy(base_delay_s=0.5, backoff=2.0, max_delay_s=30.0, jitter=0.0)
    assert [p.delay_s(k) for k in (1, 2, 3)] == [0.5, 1.0, 2.0]


def test_deadline_unbounded_and_expiry():
    d = Deadline.after(None)
    assert d.unbounded and not d.expired
    assert d.remaining() == float("inf")
    assert d.remaining(cap=7.0) == 7.0
    d2 = Deadline.after(1000.0)
    assert not d2.expired
    assert 0 < d2.remaining(cap=5.0) <= 5.0
    d3 = Deadline.after(1e-9)
    time.sleep(0.01)
    assert d3.expired and d3.remaining() == 0.0
    assert Deadline.after(0).unbounded  # 0 = no deadline (CLI default)


def test_retry_call_recovers_and_logs():
    calls = {"n": 0}
    slept = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError(f"transient {calls['n']}")
        return "ok"

    flog = FaultLog(site="unit")
    out = retry_call(
        flaky,
        policy=RetryPolicy(max_retries=3, base_delay_s=0.01, jitter=0.0),
        fault_log=flog,
        sleep=slept.append,
    )
    assert out == "ok" and calls["n"] == 3
    assert [a.outcome for a in flog.attempts] == ["retry", "retry", "ok"]
    assert flog.retried and "transient 1" in flog.summary()
    assert slept == [0.01, 0.02]


def test_retry_call_exhaustion_raises_last():
    flog = FaultLog()
    with pytest.raises(RuntimeError, match="always"):
        retry_call(
            lambda: (_ for _ in ()).throw(RuntimeError("always")),
            policy=RetryPolicy(max_retries=2, base_delay_s=0, jitter=0.0),
            fault_log=flog,
            sleep=lambda s: None,
        )
    assert [a.outcome for a in flog.attempts] == ["retry", "retry", "fail"]


def test_retry_call_respects_retry_on_and_deadline():
    # non-retryable error: no second attempt
    calls = {"n": 0}

    def bad():
        calls["n"] += 1
        raise ValueError("permanent")

    with pytest.raises(ValueError):
        retry_call(
            bad,
            policy=RetryPolicy(max_retries=5, base_delay_s=0, jitter=0.0),
            retry_on=lambda e: not isinstance(e, ValueError),
            sleep=lambda s: None,
        )
    assert calls["n"] == 1
    # expired deadline: no second attempt either
    calls["n"] = 0
    with pytest.raises(ValueError):
        retry_call(
            bad,
            policy=RetryPolicy(max_retries=5, base_delay_s=0, jitter=0.0),
            deadline=Deadline.after(1e-9),
            sleep=lambda s: None,
        )
    assert calls["n"] == 1


def test_fault_log_summary_single_attempt_empty():
    flog = FaultLog()
    flog.record("ok")
    assert flog.summary() == "" and not flog.retried


# ----------------------------------------------------------------- chaos ---


def test_chaos_spec_parse():
    sp = chaos.ChaosSpec.parse("seed=7, ssh=2, collective=p0.5,rsync=1")
    assert sp.seed == 7
    assert sp.counts == {"ssh": 2, "rsync": 1}
    assert sp.probs == {"collective": 0.5}
    assert chaos.ChaosSpec.parse("").empty
    with pytest.raises(ValueError):
        chaos.ChaosSpec.parse("sshtransient")
    with pytest.raises(ValueError):
        chaos.ChaosSpec.parse("collective=p1.5")


def test_chaos_count_injector_burns_down_then_heals():
    inj = chaos.ChaosInjector(chaos.ChaosSpec.parse("ssh=2"))
    assert [inj.draw("ssh") for _ in range(4)] == [True, True, False, False]
    assert inj.fired == {"ssh": 2}
    assert not inj.draw("rsync")  # unknown site never fires


def test_chaos_probabilistic_injector_deterministic_per_seed():
    def stream(seed):
        inj = chaos.ChaosInjector(chaos.ChaosSpec.parse(f"seed={seed},collective=p0.5"))
        return [inj.draw("collective") for _ in range(20)]

    assert stream(3) == stream(3)  # same seed -> same stream
    assert stream(3) != stream(4)  # different seed -> different stream
    assert any(stream(3)) and not all(stream(3))  # p=0.5 actually mixes


def test_chaos_maybe_raise_and_every_known_site():
    spec = ",".join(f"{s}=1" for s in chaos.KNOWN_SITES)
    inj = chaos.ChaosInjector(chaos.ChaosSpec.parse(spec))
    for site in chaos.KNOWN_SITES:
        with pytest.raises(chaos.InjectedFault, match=site):
            inj.maybe_raise(site)
        inj.maybe_raise(site)  # healed: no raise


def test_chaos_known_sites_include_sdc_and_nan_loss():
    assert "sdc" in chaos.KNOWN_SITES
    assert "nan_loss" in chaos.KNOWN_SITES
    assert "mesh_shrink" in chaos.KNOWN_SITES  # PR 8: elastic-mesh drills
    # ISSUE 10: grow-back drills — validated vocabulary, so a typo'd heal
    # drill fails loudly instead of silently never healing.
    assert "device_rejoin" in chaos.KNOWN_SITES
    assert "flap" in chaos.KNOWN_SITES


def test_chaos_grow_back_sites_drain_with_mesh_shrink_semantics():
    """device_rejoin/flap counts are MAGNITUDES consumed as one event via
    drain (heal k devices at once / k lose->heal cycles), exactly the
    mesh_shrink contract — and the streams are per-site deterministic."""
    inj = chaos.ChaosInjector(
        chaos.ChaosSpec.parse("seed=3,device_rejoin=2,flap=3")
    )
    assert inj.drain("device_rejoin") == 2
    assert inj.drain("device_rejoin") == 0  # one event, not two
    assert inj.drain("flap") == 3
    assert inj.drain("flap") == 0
    assert inj.fired == {"device_rejoin": 2, "flap": 3}
    # probabilistic spelling stays on the seeded per-site draw stream
    a = chaos.ChaosInjector(chaos.ChaosSpec.parse("seed=7,device_rejoin=p0.5"))
    b = chaos.ChaosInjector(chaos.ChaosSpec.parse("seed=7,device_rejoin=p0.5"))
    draws_a = [a.draw("device_rejoin") for _ in range(32)]
    draws_b = [b.draw("device_rejoin") for _ in range(32)]
    assert draws_a == draws_b and any(draws_a) and not all(draws_a)
    assert a.drain("device_rejoin") == 0  # drain never touches p-streams


def test_chaos_drain_consumes_count_as_one_magnitude():
    """``drain`` hands the whole remaining count to ONE event (the
    mesh_shrink=k 'drop k devices at once' semantics) and leaves
    probabilistic streams to ``draw``."""
    inj = chaos.ChaosInjector(chaos.ChaosSpec.parse("mesh_shrink=3,ssh=1"))
    assert inj.drain("mesh_shrink") == 3
    assert inj.drain("mesh_shrink") == 0  # consumed: one event, not three
    assert not inj.draw("mesh_shrink")
    assert inj.fired == {"mesh_shrink": 3}
    assert inj.draw("ssh")  # other sites untouched


def test_chaos_unknown_fault_kind_is_value_error_listing_valid_kinds():
    """A typo'd site must fail loudly with the valid vocabulary, not parse
    fine and silently never fire."""
    with pytest.raises(ValueError) as ei:
        chaos.ChaosSpec.parse("ssh_transient=1")
    msg = str(ei.value)
    assert "ssh_transient" in msg
    for site in chaos.KNOWN_SITES:
        assert site in msg


def test_chaos_active_env_gated(monkeypatch):
    assert chaos.active() is None
    monkeypatch.setenv(chaos.CHAOS_ENV, "ssh=1")
    inj = chaos.active()
    assert inj is not None and chaos.active() is inj  # cached, counters persist
    assert inj.draw("ssh") and not inj.draw("ssh")
    monkeypatch.setenv(chaos.CHAOS_ENV, "ssh=1,seed=9")
    assert chaos.active() is not inj  # spec change -> fresh injector
    monkeypatch.delenv(chaos.CHAOS_ENV)
    assert chaos.active() is None


# -------------------------------------------------------------- degrader ---


def test_degrader_first_tier_success_no_events():
    d = Degrader(["a", "b"])
    assert d.run(lambda t: t.upper()) == ("a", "A")
    assert not d.degraded and d.events == []


def test_degrader_walks_chain_and_emits_events():
    seen = []
    d = Degrader(["v5_collective", "v4_hybrid", "v1_jit"], on_event=seen.append)
    tier, out = d.run(
        lambda t: 42 if t == "v1_jit" else (_ for _ in ()).throw(RuntimeError(f"{t} down"))
    )
    assert (tier, out) == ("v1_jit", 42)
    assert [(e.from_tier, e.to_tier) for e in d.events] == [
        ("v5_collective", "v4_hybrid"), ("v4_hybrid", "v1_jit"),
    ]
    assert seen == d.events
    assert "DEGRADED(v5_collective -> v4_hybrid)" in str(seen[0])
    assert "v5_collective down" in str(seen[0])


def test_degrader_should_degrade_gate_reraises():
    d = Degrader(["a", "b"], should_degrade=lambda e: not isinstance(e, ValueError))
    with pytest.raises(ValueError):
        d.run(lambda t: (_ for _ in ()).throw(ValueError("real bug")))
    assert not d.degraded


def test_degrader_exhausted():
    d = Degrader(["a", "b"])
    with pytest.raises(DegradationExhausted) as ei:
        d.run(lambda t: (_ for _ in ()).throw(RuntimeError(f"{t} down")))
    assert ei.value.chain == ["a", "b"]
    assert "b down" in str(ei.value)
    assert len(ei.value.events) == 1  # a -> b recorded before exhaustion


def test_tier_fallback_chains():
    assert tier_fallback_chain("v5_collective") == [
        "v5_collective", "v4_hybrid", "v2.2_sharded", "v1_jit",
    ]
    assert tier_fallback_chain("v3_pallas") == ["v3_pallas", "v1_jit"]
    assert tier_fallback_chain("v6_full_pallas") == ["v6_full_pallas", "v6_full_jit"]
    assert tier_fallback_chain("v1_jit") == ["v1_jit"]


# ------------------------------------------------------- harness re-capture ---

_HEALTHY_STDOUT = (
    "Compile time: 812.0 ms\n"
    "Final Output Shape: 13x13x256\n"
    "Final Output (first 10 values): 29.2932 25.9153 23.3255 1 2 3 4 5 6 7\n"
    "AlexNet TPU Forward Pass completed in 1.234 ms (amortized over 10 fenced passes; 810.4 img/s)\n"
)


def _fake_proc(rc=0, stdout=_HEALTHY_STDOUT, stderr=""):
    return subprocess.CompletedProcess(["fake"], rc, stdout=stdout, stderr=stderr)


def test_harness_timeout_retry_commits_one_healthy_row(tmp_path, monkeypatch):
    """The first attempt times out; the retry re-runs and the ONE committed
    row is the healthy one, tagged with attempt metadata."""
    outcomes = [subprocess.TimeoutExpired(["fake"], 1.0), _fake_proc()]

    def run(*a, **k):
        out = outcomes.pop(0)
        if isinstance(out, Exception):
            raise out
        return out

    session = harness.Session(log_root=tmp_path)  # before the run() stub: git_commit
    monkeypatch.setattr(harness.subprocess, "run", run)
    slept = []
    r = harness.run_case(
        session, "v1_jit", "V1 Serial", 1, 1, fake_devices=2,
        retry_policy=RetryPolicy(max_retries=2, base_delay_s=0.5, jitter=0.0),
        sleep=slept.append,
    )
    assert r.status == harness.OK
    assert r.attempts == 2 and slept == [0.5]
    assert r.time_ms == 1.234
    assert "TIMEOUT" in r.resilience_msg
    with open(session.csv_path) as f:
        rows = list(csv.reader(f))
    assert len(rows) == 2  # header + exactly ONE committed row
    assert rows[1][15] == "1.234"  # ExecutionTime_ms
    assert rows[1][20] == "2"  # Attempts
    # both attempts' logs survive on disk
    assert (session.dir / "run_v1_jit_np1_b1.log").exists()
    assert (session.dir / "run_v1_jit_np1_b1_try1.log").exists()


def test_harness_backend_init_failure_is_a_fail_not_retried(tmp_path, monkeypatch):
    """A run asked for the TPU that cannot initialise it FAILs — no warning
    class excuses a missing device, and nothing retries it."""
    calls = {"n": 0}

    def run(*a, **k):
        calls["n"] += 1
        return _fake_proc(
            rc=1, stdout="", stderr="RuntimeError: Unable to initialize backend 'tpu'"
        )

    session = harness.Session(log_root=tmp_path)  # before the run() stub: git_commit
    monkeypatch.setattr(harness.subprocess, "run", run)
    r = harness.run_case(
        session, "v1_jit", "V1 Serial", 1, 1,
        retry_policy=RetryPolicy(max_retries=2, base_delay_s=0.01, jitter=0.0),
        sleep=lambda s: None,
    )
    assert r.status == harness.FAIL and r.attempts == 1 and calls["n"] == 1


def test_harness_no_retry_on_genuine_fail(tmp_path, monkeypatch):
    """FAIL (a real bug) is NOT retryable — one attempt, one row."""
    calls = {"n": 0}

    def run(*a, **k):
        calls["n"] += 1
        return _fake_proc(rc=1, stdout="", stderr="ValueError: actual bug")

    session = harness.Session(log_root=tmp_path)  # before the run() stub: git_commit
    monkeypatch.setattr(harness.subprocess, "run", run)
    r = harness.run_case(
        session, "v1_jit", "V1 Serial", 1, 1, fake_devices=2,
        retry_policy=RetryPolicy(max_retries=3, base_delay_s=0.01, jitter=0.0),
        sleep=lambda s: None,
    )
    assert r.status == harness.FAIL and r.attempts == 1 and calls["n"] == 1


def test_harness_degraded_triage_from_run_log(tmp_path, monkeypatch):
    """A run that fell back (the run CLI printed a DEGRADED event) triages
    as DEGRADED — a warning with the fallback recorded, not an OK row
    masquerading as the requested tier."""
    out = "DEGRADED(v5_collective -> v1_jit): InjectedFault: chaos\n" + _HEALTHY_STDOUT
    monkeypatch.setattr(harness.subprocess, "run", lambda *a, **k: _fake_proc(stdout=out))
    session = harness.Session(log_root=tmp_path)
    r = harness.run_case(session, "v5_collective", "V5 MPI+CUDA-Aware", 2, 1, fake_devices=2)
    assert r.status == harness.DEGRADED
    assert "v5_collective -> v1_jit" in r.degraded_msg
    assert r.time_ms == 1.234  # the degraded tier's numbers still recorded
    with open(session.csv_path) as f:
        rows = list(csv.reader(f))
    assert rows[1][14] == harness.DEGRADED
    # DEGRADED is a warning: the sweep exit code treats it like OK
    assert harness.STATUS_SYMBOL[harness.DEGRADED] == "↓"


# ------------------------------------------------------ run CLI degradation ---


def test_run_cli_degrades_pallas_to_xla(tmp_path, monkeypatch, capsys):
    """CHAOS kernel-compile failure on v3_pallas degrades to v1_jit via
    --fallback-chain auto and still prints the full stdout contract."""
    from cuda_mpi_gpu_cluster_programming_tpu import run as run_cli

    monkeypatch.setenv(chaos.CHAOS_ENV, "kernel_compile=1")
    chaos.reset()
    rc = run_cli.main([
        "--config", "v3_pallas", "--fallback-chain", "auto",
        "--height", "63", "--width", "63", "--repeats", "1", "--warmup", "1",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "DEGRADED(v3_pallas -> v1_jit): InjectedFault" in out
    assert "Final Output Shape: 2x2x256" in out
    assert "completed in" in out


def test_run_cli_degrades_collective_chain(tmp_path, monkeypatch, capsys):
    """A transient collective fault at v5_collective falls to v4_hybrid
    (the injector heals after one draw) — one DEGRADED step, not a crash."""
    from cuda_mpi_gpu_cluster_programming_tpu import run as run_cli

    monkeypatch.setenv(chaos.CHAOS_ENV, "collective=1")
    chaos.reset()
    rc = run_cli.main([
        "--config", "v5_collective", "--shards", "2", "--fallback-chain", "auto",
        "--height", "63", "--width", "63", "--repeats", "1", "--warmup", "1",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "DEGRADED(v5_collective -> v4_hybrid): InjectedFault" in out
    assert "Final Output Shape: 2x2x256" in out


def test_run_cli_retry_recovers_without_degrading(monkeypatch, capsys):
    """--max-retries alone rides out a transient collective fault on the
    SAME tier: no DEGRADED event, same config runs."""
    from cuda_mpi_gpu_cluster_programming_tpu import run as run_cli

    monkeypatch.setenv(chaos.CHAOS_ENV, "collective=1")
    chaos.reset()
    # v2.1_replicated: a non-single strategy (so the collective site fires)
    # that still builds on this jax version — the sharded family's
    # shard_map import is broken at seed, which is a degradation test, not
    # a retry test.
    rc = run_cli.main([
        "--config", "v2.1_replicated", "--shards", "2", "--max-retries", "1",
        "--height", "63", "--width", "63", "--repeats", "1", "--warmup", "1",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "DEGRADED" not in out
    assert "Final Output Shape: 2x2x256" in out


def test_run_cli_rejects_cross_model_chain(capsys):
    from cuda_mpi_gpu_cluster_programming_tpu import run as run_cli

    rc = run_cli.main([
        "--config", "v1_jit", "--fallback-chain", "v6_full_jit",
        "--height", "63", "--width", "63",
    ])
    assert rc == 2
    assert "crosses model families" in capsys.readouterr().err


# ------------------------------------------------------- deploy transports ---


def test_transport_run_retries_injected_ssh_transient(monkeypatch):
    from cuda_mpi_gpu_cluster_programming_tpu.parallel import deploy

    monkeypatch.setenv(chaos.CHAOS_ENV, "ssh=1")
    chaos.reset()
    slept = []
    proc, flog = deploy._transport_run(
        ["true"], site="ssh", timeout_s=10,
        policy=RetryPolicy(max_retries=2, base_delay_s=0.01, jitter=0.0),
        sleep=slept.append, capture_output=True,
    )
    assert proc.returncode == 0
    assert flog.n_attempts == 2 and flog.retried
    assert "chaos: injected ssh transient" in flog.attempts[0].cause
    assert slept == [0.01]


def test_transport_run_exhaustion_returns_last_proc(monkeypatch):
    from cuda_mpi_gpu_cluster_programming_tpu.parallel import deploy

    monkeypatch.setenv(chaos.CHAOS_ENV, "ssh=9")
    chaos.reset()
    proc, flog = deploy._transport_run(
        ["true"], site="ssh", timeout_s=10,
        policy=RetryPolicy(max_retries=1, base_delay_s=0.01, jitter=0.0),
        sleep=lambda s: None, capture_output=True,
    )
    assert proc.returncode == 255
    assert [a.outcome for a in flog.attempts] == ["retry", "fail"]


def test_check_reachable_retries_injected_ssh_transient(monkeypatch):
    """A host whose first ssh probe is injected-dead recovers on retry (the
    retried success is labeled); local hosts bypass the transport."""
    from cuda_mpi_gpu_cluster_programming_tpu.parallel import deploy
    from cuda_mpi_gpu_cluster_programming_tpu.parallel.distributed import ClusterConfig

    monkeypatch.setenv(chaos.CHAOS_ENV, "ssh=1")
    chaos.reset()
    # stand in for the ssh binary this image doesn't ship; the chaos draw
    # happens in the transport BEFORE this is reached
    monkeypatch.setattr(
        deploy.subprocess, "run",
        lambda *a, **k: subprocess.CompletedProcess(a, 0, stdout=b"", stderr=b""),
    )
    cluster = ClusterConfig.parse(["localhost", "myko@far-host"])
    checks = deploy.check_reachable(
        cluster, policy=RetryPolicy(max_retries=2, base_delay_s=0.0, jitter=0.0)
    )
    assert checks[0] == ("localhost", True, "local")
    assert checks[1] == ("far-host", True, "ok after 2 attempts")


def test_sync_code_reports_lost_host_on_rsync_exhaustion(tmp_path, monkeypatch):
    from cuda_mpi_gpu_cluster_programming_tpu.parallel import deploy
    from cuda_mpi_gpu_cluster_programming_tpu.parallel.distributed import ClusterConfig

    monkeypatch.setenv(chaos.CHAOS_ENV, "rsync=9")
    chaos.reset()
    cluster = ClusterConfig.parse(["fake@unreachable-host"])
    policy = RetryPolicy(max_retries=1, base_delay_s=0.0, jitter=0.0)
    # on_error="report": the lost host is an action row, not an exception
    actions = deploy.sync_code(
        cluster, str(tmp_path), "/tmp/elsewhere", policy=policy, on_error="report"
    )
    assert actions[0][0] == "unreachable-host"
    assert actions[0][1].startswith("SYNC_FAILED:")
    # default on_error="raise" keeps the historical contract
    chaos.reset()
    with pytest.raises(RuntimeError, match="rsync to unreachable-host failed"):
        deploy.sync_code(cluster, str(tmp_path), "/tmp/elsewhere", policy=policy)


def test_deploy_quorum_degradation_end_to_end(tmp_path, monkeypatch, capsys):
    """A 2-host inventory loses its remote to terminal rsync faults; with
    quorum 0.5 the deploy shrinks to the surviving local host, launches it,
    and the summary reports the lost host as UNREACHABLE."""
    from cuda_mpi_gpu_cluster_programming_tpu.parallel import deploy
    from cuda_mpi_gpu_cluster_programming_tpu.parallel.distributed import ClusterConfig

    monkeypatch.setenv(chaos.CHAOS_ENV, "rsync=9")
    chaos.reset()
    src = tmp_path / "src"
    (src / "pkg").mkdir(parents=True)
    (src / "pkg" / "a.py").write_text("x = 1\n")
    workdir = tmp_path / "work"
    workdir.mkdir()
    cluster = ClusterConfig.parse(["localhost", "fake@lost-host"])
    results = deploy.deploy_and_collect(
        cluster,
        "platform",  # `python -m platform`: trivial, jax-free, exits 0
        workdir=str(workdir),
        log_root=str(tmp_path / "logs"),
        timeout_s=60.0,
        sync_from=str(src),
        quorum=0.5,
        transport_policy=RetryPolicy(max_retries=0, base_delay_s=0.0, jitter=0.0),
    )
    out = capsys.readouterr().out
    assert "DEGRADED(cluster n=2 -> n=1)" in out
    by_host = {r.host: r for r in results}
    assert by_host["lost-host"].status == deploy.UNREACHABLE
    assert by_host["lost-host"].process_id == -1
    assert by_host["localhost"].status == deploy.OK
    # the lost host rides the summary CSV, not just stdout
    session_dir = next((tmp_path / "logs").iterdir())
    summary = (session_dir / "summary.csv").read_text()
    assert "UNREACHABLE" in summary and "lost-host" in summary


def test_deploy_quorum_not_met_raises(tmp_path, monkeypatch):
    from cuda_mpi_gpu_cluster_programming_tpu.parallel import deploy
    from cuda_mpi_gpu_cluster_programming_tpu.parallel.distributed import ClusterConfig

    monkeypatch.setenv(chaos.CHAOS_ENV, "rsync=9")
    chaos.reset()
    src = tmp_path / "src"
    src.mkdir()
    cluster = ClusterConfig.parse(["fake@a", "fake@b"])
    with pytest.raises(RuntimeError, match="quorum lost"):
        deploy.deploy_and_collect(
            cluster,
            "platform",
            workdir=str(tmp_path / "w"),
            log_root=str(tmp_path / "logs"),
            sync_from=str(src),
            quorum=0.9,
            transport_policy=RetryPolicy(max_retries=0, base_delay_s=0.0, jitter=0.0),
        )


# ------------------------------------------------- SDC + Degrader ordering ---


def test_degrader_sdc_mid_chain_no_skip_no_double_degrade():
    """An SDC fault firing mid-chain must degrade exactly ONE tier per trip
    (no tier skipped, no double event) and land on the first healthy tier."""
    from cuda_mpi_gpu_cluster_programming_tpu.resilience.sentinel import SDC

    attempts = []

    def build(tier):
        attempts.append(tier)
        if tier == "v5_collective":
            raise SDC("norm_spike", step=3, detail="loss=1e9")
        if tier == "v4_hybrid":
            raise RuntimeError("v4_hybrid down")
        return f"ok:{tier}"

    d = Degrader(
        ["v5_collective", "v4_hybrid", "v2.2_sharded"],
        should_degrade=lambda e: isinstance(e, (SDC, RuntimeError)),
    )
    tier, out = d.run(build)
    assert (tier, out) == ("v2.2_sharded", "ok:v2.2_sharded")
    # Every tier attempted exactly once, in chain order — no skip.
    assert attempts == ["v5_collective", "v4_hybrid", "v2.2_sharded"]
    # One DEGRADED event per failing tier — no double-degrade.
    assert [(e.from_tier, e.to_tier) for e in d.events] == [
        ("v5_collective", "v4_hybrid"), ("v4_hybrid", "v2.2_sharded"),
    ]
    assert "SDC(norm_spike) at step 3" in d.events[0].cause


def test_degrader_sdc_rejected_by_gate_reraises_structured():
    """A should_degrade gate that rejects SDC re-raises the ORIGINAL
    structured fault (kind/step intact) — quarantine upstream needs it."""
    from cuda_mpi_gpu_cluster_programming_tpu.resilience.sentinel import SDC

    d = Degrader(["a", "b"], should_degrade=lambda e: not isinstance(e, SDC))
    with pytest.raises(SDC) as ei:
        d.run(lambda t: (_ for _ in ()).throw(SDC("nan_loss", step=1)))
    assert ei.value.kind == "nan_loss" and ei.value.step == 1
    assert not d.degraded


# ------------------------------------------------------- harness --resume ---

_RESUME_STDOUT = (
    "Compile time: 10.0 ms\n"
    "Final Output Shape: 13x13x256\n"
    "Final Output (first 10 values): 1 2 3 4 5 6 7 8 9 10\n"
    "AlexNet TPU Forward Pass completed in 2.000 ms (amortized over 2 fenced passes; 500.0 img/s)\n"
)


def _fake_run_once_factory(calls, die_on=None):
    """A _run_once stand-in: records (config, np, batch) per launch, writes a
    healthy log, and optionally simulates a kill at the Nth launch."""

    def fake(r, cmd, env, log_path, timeout_s):
        calls.append((r.config_key, r.np, r.batch))
        if die_on is not None and len(calls) == die_on:
            raise KeyboardInterrupt  # the sweep process dies mid-case
        log_path.write_text(_RESUME_STDOUT)
        r.run_status = harness.OK
        harness.parse_run_log(_RESUME_STDOUT, r)

    return fake


def test_harness_resume_skips_journaled_and_reruns_interrupted(tmp_path, monkeypatch):
    """Kill a sweep mid-case, relaunch with --resume: journaled-complete
    cases are skipped, the interrupted case re-runs, and the final CSV holds
    every case exactly once — identical to an uninterrupted sweep's rows
    modulo attempt metadata."""
    args = [
        "--configs", "v1_jit,v3_pallas", "--shards", "1", "--batches", "1,2",
        "--log-root", str(tmp_path),
    ]
    calls1 = []
    monkeypatch.setattr(harness, "_run_once", _fake_run_once_factory(calls1, die_on=3))
    with pytest.raises(KeyboardInterrupt):
        harness.main(args)
    assert len(calls1) == 3  # died inside the 3rd case
    (sdir,) = [d for d in tmp_path.iterdir() if d.is_dir()]

    calls2 = []
    monkeypatch.setattr(harness, "_run_once", _fake_run_once_factory(calls2))
    rc = harness.main(args + ["--resume", str(sdir)])
    assert rc == 0
    # Only the interrupted case and the never-started one ran.
    assert calls2 == [("v3_pallas", 1, 1), ("v3_pallas", 1, 2)]

    with open(sdir / "summary.csv", newline="") as f:
        rows = list(csv.DictReader(f))
    keys = [(r["ConfigKey"], r["NP"], r["Batch"], r["Status"]) for r in rows]
    assert sorted(keys) == sorted([
        ("v1_jit", "1", "1", "OK"), ("v1_jit", "1", "2", "OK"),
        ("v3_pallas", "1", "1", "OK"), ("v3_pallas", "1", "2", "OK"),
    ])
    # The journaled rows replay with their original measured values.
    v1_rows = [r for r in rows if r["ConfigKey"] == "v1_jit"]
    assert all(r["ExecutionTime_ms"] == "2.000" for r in v1_rows)


def test_harness_resume_on_complete_session_runs_nothing(tmp_path, monkeypatch):
    args = [
        "--configs", "v1_jit", "--shards", "1", "--batches", "1",
        "--log-root", str(tmp_path),
    ]
    calls1 = []
    monkeypatch.setattr(harness, "_run_once", _fake_run_once_factory(calls1))
    assert harness.main(args) == 0
    (sdir,) = [d for d in tmp_path.iterdir() if d.is_dir()]
    calls2 = []
    monkeypatch.setattr(harness, "_run_once", _fake_run_once_factory(calls2))
    assert harness.main(args + ["--resume", str(sdir)]) == 0
    assert calls2 == []  # everything journaled: nothing re-runs
    with open(sdir / "summary.csv", newline="") as f:
        assert len(list(csv.DictReader(f))) == 1  # no duplicate rows


def test_harness_resume_missing_dir_rejected(tmp_path, capsys):
    assert harness.main(["--resume", str(tmp_path / "nope")]) == 2
    assert "no such session" in capsys.readouterr().err


def test_harness_resume_drops_torn_csv_row(tmp_path, monkeypatch):
    """A kill between the CSV append and the journal append leaves an orphan
    CSV row; --resume rebuilds the CSV from the journal, dropping it, and
    re-runs that case (no double-count)."""
    args = [
        "--configs", "v1_jit", "--shards", "1", "--batches", "1",
        "--log-root", str(tmp_path),
    ]
    calls1 = []
    monkeypatch.setattr(harness, "_run_once", _fake_run_once_factory(calls1))
    assert harness.main(args) == 0
    (sdir,) = [d for d in tmp_path.iterdir() if d.is_dir()]
    # Simulate the torn state: keep the CSV row, erase the journal's case
    # record (as if the kill landed between the two appends).
    jpath = sdir / "journal.jsonl"
    recs = [l for l in jpath.read_text().splitlines() if '"case_start"' in l]
    jpath.write_text("\n".join(recs) + "\n")

    calls2 = []
    monkeypatch.setattr(harness, "_run_once", _fake_run_once_factory(calls2))
    assert harness.main(args + ["--resume", str(sdir)]) == 0
    assert calls2 == [("v1_jit", 1, 1)]  # interrupted case re-ran
    with open(sdir / "summary.csv", newline="") as f:
        assert len(list(csv.DictReader(f))) == 1  # orphan row was dropped
