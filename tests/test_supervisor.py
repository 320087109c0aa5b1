"""Elastic supervisor + in-graph sentinel tests — CPU, virtual 8-device mesh.

Covers the whole tentpole surface: ladder ordering, the StageDigests
checker's trip kinds, the seeded CPU drills (``stage_sdc`` into the sp
forward, ``device_loss`` into the tp forward) with trip → re-plan → replay
matching the uninjected oracle, journal record idempotence, ladder
exhaustion, the run CLI ``--supervise`` path, the harness's SupervisorMsg
CSV surfacing, and the digest taps of the sequence-parallel forwards.
"""

import dataclasses

import jax
import numpy as np
import pytest

from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import (
    BLOCKS12,
    forward_blocks12,
)
from cuda_mpi_gpu_cluster_programming_tpu.models.init import (
    init_params_random,
    random_input,
)
from cuda_mpi_gpu_cluster_programming_tpu.resilience import chaos
from cuda_mpi_gpu_cluster_programming_tpu.resilience.journal import Journal
from cuda_mpi_gpu_cluster_programming_tpu.resilience.policy import (
    DegradationExhausted,
)
from cuda_mpi_gpu_cluster_programming_tpu.resilience.sentinel import (
    SDC,
    SentinelConfig,
    StageDigests,
)
from cuda_mpi_gpu_cluster_programming_tpu.resilience.supervisor import (
    LadderEntry,
    Supervisor,
    default_ladder,
)


CFG = dataclasses.replace(BLOCKS12, in_height=63, in_width=63)


@pytest.fixture()
def small_case():
    kp, kx = jax.random.split(jax.random.PRNGKey(0))
    params = init_params_random(kp, CFG)
    x = random_input(kx, 2, CFG)
    want = np.asarray(jax.jit(lambda p, x: forward_blocks12(p, x, CFG))(params, x))
    return params, x, want


def _chaos(monkeypatch, spec):
    if spec is None:
        monkeypatch.delenv(chaos.CHAOS_ENV, raising=False)
    else:
        monkeypatch.setenv(chaos.CHAOS_ENV, spec)
    chaos.reset()


@pytest.fixture(autouse=True)
def _chaos_off(monkeypatch):
    _chaos(monkeypatch, None)
    yield
    chaos.reset()


# ------------------------------------------------------------- ladders ---


def test_default_ladder_ordering_halo():
    keys = [e.key for e in default_ladder("halo", "reference", 4)]
    assert keys == [
        "halo@4:reference",
        "halo@2:reference",
        "replicated@4:reference",
        "single@1:reference",
    ]


def test_default_ladder_ordering_tp_and_pallas_floor():
    keys = [e.key for e in default_ladder("tp", "pallas", 8)]
    assert keys == [
        "tp@8:pallas",
        "tp@4:pallas",
        "tp@2:pallas",
        "replicated@8:reference",
        "single@1:reference",
    ]
    # A pallas single degrades to the XLA reference floor; a reference
    # single IS the floor (one rung, nothing below it).
    assert [e.key for e in default_ladder("single", "pallas", 1)] == [
        "single@1:pallas",
        "single@1:reference",
    ]
    assert [e.key for e in default_ladder("single", "reference", 1)] == [
        "single@1:reference"
    ]


def test_default_ladder_unknown_strategy_raises():
    with pytest.raises(ValueError, match="no supervisor ladder"):
        default_ladder("fsdp", "reference", 4)


# -------------------------------------------------------- StageDigests ---


def test_stage_digests_clean_pass_returns_host_copies():
    c = StageDigests()
    host = c.check(0, {"conv1": np.ones(4), "pool1": np.full(4, 2.0)})
    assert set(host) == {"conv1", "pool1"}
    assert c.trips == []


def test_stage_digests_nonfinite_trips_stage_digest():
    c = StageDigests(site="sp")
    with pytest.raises(SDC) as ei:
        c.check(3, {"conv2": np.array([1.0, np.nan, 1.0, 1.0])})
    assert ei.value.kind == "stage_digest"
    assert ei.value.step == 3
    assert "sp/conv2" in ei.value.detail
    assert c.trips == [ei.value]


def test_stage_digests_replicated_spread_trips_shard_divergence():
    c = StageDigests(SentinelConfig(divergence_tol=0.0))
    c.check(0, {"out": np.full(4, 5.0)}, replicated=True)  # identical: clean
    with pytest.raises(SDC) as ei:
        c.check(1, {"out": np.array([5.0, 5.0, 5.0, 5.5])}, replicated=True)
    assert ei.value.kind == "shard_divergence"


def test_stage_digests_expect_mismatch_trips():
    c = StageDigests()
    ref = {"out": np.full(2, 7.0)}
    c.check(0, {"out": np.full(2, 7.0)}, expect=ref)  # exact replay: clean
    with pytest.raises(SDC) as ei:
        c.check(1, {"out": np.array([7.0, 7.1])}, expect=ref)
    assert ei.value.kind == "stage_digest"
    # and a tolerance admits honest tier-change noise
    c.check(2, {"out": np.array([7.0, 7.1])}, expect=ref, rtol=0.1)


# ----------------------------------------------------------- supervisor ---


def test_clean_supervised_run_matches_oracle(small_case):
    params, x, want = small_case
    sup = Supervisor(CFG, default_ladder("halo", "reference", 4))
    out = sup.execute(params, x)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
    assert sup.attempts == 1 and sup.trips == [] and sup.events == []
    assert sup.entry.key == "halo@4:reference"


def test_stage_sdc_drill_sp_forward_trips_degrades_replays(
    small_case, monkeypatch, tmp_path
):
    """The acceptance drill: stage_sdc into the sp (row-sharded) forward.
    The supervisor must trip stage_digest, degrade one rung, replay the
    SAME batch, and match the uninjected oracle."""
    params, x, want = small_case
    _chaos(monkeypatch, "seed=3,stage_sdc=1")
    sup = Supervisor(
        CFG,
        default_ladder("halo", "reference", 4),
        journal=Journal(tmp_path / "sup.jsonl"),
    )
    out = sup.execute(params, x)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
    assert [t.kind for t in sup.trips] == ["stage_digest"]
    assert [(e.from_tier, e.to_tier) for e in sup.events] == [
        ("halo@4:reference", "halo@2:reference")
    ]
    assert sup.attempts == 2  # trip + replay
    kinds = [r["kind"] for r in Journal.load(tmp_path / "sup.jsonl")]
    # PR 8: the degrade additionally journals the live reshard onto the
    # landed rung's mesh and the replay itself, before the sup_ok.
    # PR 15: every first call of an executable at a new shape journals a
    # compile_event — one on the tripped rung (the batch compiled, then
    # screening tripped), one when the replay compiles the landed rung.
    assert kinds == [
        "sup_build", "compile_event", "sup_trip", "sup_degrade",
        "sup_build", "sup_reshard", "sup_replay", "compile_event",
        "sup_ok",
    ]


def test_stage_sdc_replay_bit_identical_to_uninjected_rung(
    small_case, monkeypatch
):
    """trip -> re-plan -> replay: the degraded rung's replay output is
    BIT-identical to an uninjected run of that same rung (reference tier,
    same batch, same plan — nothing about the trip may leak into data)."""
    params, x, _ = small_case
    _chaos(monkeypatch, "seed=3,stage_sdc=1")
    sup = Supervisor(CFG, default_ladder("halo", "reference", 4))
    out = np.asarray(sup.execute(params, x))
    assert sup.entry.key == "halo@2:reference"
    _chaos(monkeypatch, None)
    clean = Supervisor(
        CFG, [LadderEntry("halo", "reference", 2)]
    ).execute(params, x)
    assert np.array_equal(out, np.asarray(clean))


def test_device_loss_drill_tp_forward(small_case, monkeypatch):
    """The acceptance drill: device_loss into the tp forward — the
    supervisor classifies the mesh-shrink fault, re-plans, and the replay
    matches the uninjected oracle."""
    params, x, want = small_case
    _chaos(monkeypatch, "seed=3,device_loss=1")
    sup = Supervisor(CFG, default_ladder("tp", "reference", 4))
    out = sup.execute(params, x)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
    assert [t.kind for t in sup.trips] == ["device_loss"]
    assert sup.events[0].from_tier == "tp@4:reference"


def test_persistent_trips_walk_ladder_to_floor_then_exhaust(
    small_case, monkeypatch
):
    params, x, want = small_case
    ladder = default_ladder("halo", "reference", 4)
    # Enough injections to trip every rung once: the floor's trip exhausts.
    _chaos(monkeypatch, f"seed=3,stage_sdc={len(ladder)}")
    sup = Supervisor(CFG, ladder)
    with pytest.raises(DegradationExhausted) as ei:
        sup.execute(params, x)
    assert len(sup.trips) == len(ladder)
    assert [e.from_tier for e in sup.events] == [e.key for e in ladder[:-1]]
    assert isinstance(ei.value.last, SDC)
    # One injection fewer heals exactly at the floor.
    _chaos(monkeypatch, f"seed=3,stage_sdc={len(ladder) - 1}")
    sup2 = Supervisor(CFG, default_ladder("halo", "reference", 4))
    out = sup2.execute(params, x)
    assert sup2.entry.key == "single@1:reference"
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)


def test_journal_records_are_replay_idempotent(small_case, monkeypatch, tmp_path):
    """Two identically-seeded drills journal identical transition records
    (no timestamps, no volatile fields) — the journal is a deterministic
    replayable transcript, and Journal.load tolerates re-reading it."""
    params, x, _ = small_case
    records = []
    for name in ("a", "b"):
        _chaos(monkeypatch, "seed=3,stage_sdc=1")
        sup = Supervisor(CFG, default_ladder("halo", "reference", 4),
                         journal=Journal(tmp_path / f"{name}.jsonl"))
        sup.execute(params, x)
        records.append(Journal.load(tmp_path / f"{name}.jsonl"))
    # compile_event records are MEASUREMENTS (wall ms, like sup_warm.ms):
    # the measured value varies run to run by design; everything else —
    # order, keys, shapes, dtype, cost-analysis flops — must be identical.
    def _stable(recs):
        return [
            {k: v for k, v in r.items() if k != "ms"}
            if r["kind"] == "compile_event"
            else r
            for r in recs
        ]

    assert _stable(records[0]) == _stable(records[1])
    # Replaying the journal through the idempotence primitive: later
    # records win per key, loading twice is stable.
    done = Journal.completed(records[0], "sup_ok")
    assert set(done) == {"ok:0"}


def test_replicated_output_divergence_screen(small_case, monkeypatch):
    """The replicated rung's cross-shard compare: a forced spread in the
    replicated output trips shard_divergence and falls to the floor."""
    params, x, want = small_case
    import cuda_mpi_gpu_cluster_programming_tpu.resilience.supervisor as smod

    sup = Supervisor(CFG, default_ladder("replicated", "reference", 4))
    monkeypatch.setattr(smod, "replicated_shard_spread", lambda tree: 1.0)
    out = sup.execute(params, x)
    assert [t.kind for t in sup.trips] == ["shard_divergence"]
    assert sup.entry.key == "single@1:reference"
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------- sequence-parallel taps ---


def test_ring_and_ulysses_digest_taps():
    q = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 4, 16))
    from cuda_mpi_gpu_cluster_programming_tpu.parallel.sequence_parallel import (
        ring_attention,
        ulysses_attention,
    )

    for fn in (ring_attention, ulysses_attention):
        want = np.asarray(fn(q, q, q, n_shards=2))
        out, digs = fn(q, q, q, n_shards=2, with_digests=True)
        assert np.array_equal(np.asarray(out), want)  # taps don't move data
        assert set(digs) == {"qkv", "out"}
        for v in digs.values():
            v = np.asarray(v)
            assert v.shape == (2,) and np.isfinite(v).all()
        StageDigests(site=fn.__name__).check(0, digs)  # screens clean


# ------------------------------------------------------------- run CLI ---


def test_run_cli_supervise_drill(monkeypatch, capsys):
    """End-to-end CLI drill on the sp forward: the DEGRADED event and the
    machine-parsed 'Supervisor:' line both reach stdout, and the golden
    first-values survive the re-plan."""
    from cuda_mpi_gpu_cluster_programming_tpu import run as run_cli

    _chaos(monkeypatch, "seed=3,stage_sdc=1")
    rc = run_cli.main([
        "--config", "v2.2_sharded", "--shards", "2", "--supervise",
        "--height", "63", "--width", "63", "--repeats", "1", "--warmup", "1",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "DEGRADED(halo@2:reference -> replicated@2:reference)" in out
    assert "Supervisor: attempts=" in out and "kinds=stage_digest" in out
    assert "Final Output (first 10 values): 29.2931" in out


def test_run_cli_supervise_rejects_v6_and_fallback_chain(capsys):
    from cuda_mpi_gpu_cluster_programming_tpu import run as run_cli

    rc = run_cli.main(["--config", "v6_full_jit", "--supervise"])
    assert rc == 2
    assert "Blocks 1-2" in capsys.readouterr().err
    rc = run_cli.main(
        ["--config", "v2.2_sharded", "--supervise", "--fallback-chain", "auto"]
    )
    assert rc == 2
    assert "degradation ladder" in capsys.readouterr().err


# ------------------------------------------------------------- harness ---


def test_harness_supervisor_msg_column_roundtrip(tmp_path):
    from cuda_mpi_gpu_cluster_programming_tpu import harness

    assert "SupervisorMsg" in harness.CSV_COLUMNS
    text = (
        "DEGRADED(halo@4:reference -> halo@2:reference): SDC(stage_digest): x\n"
        "Supervisor: attempts=2 trips=1 degradations=1 "
        "entry=halo@2:reference kinds=stage_digest\n"
        "Compile time: 10.0 ms\n"
        "Final Output Shape: 2x2x256\n"
        "Final Output (first 10 values): 29.2931\n"
        "AlexNet TPU Forward Pass completed in 1.000 ms\n"
    )
    m = harness._RE_SUPERVISOR.search(text)
    assert m and m.group(1).startswith("attempts=2")
    session = harness.Session(log_root=tmp_path)
    r = harness.CaseResult(
        variant="V2.2", config_key="v2.2_sharded", np=2, batch=1,
        run_status=harness.OK,
    )
    harness.parse_run_log(text, r)
    r.supervisor_msg = m.group(1)
    r.degraded_msg = harness._RE_DEGRADED.search(text).group(0)
    session.log_row(r, journal_key="k")
    import csv

    with open(session.csv_path) as f:
        rows = list(csv.DictReader(f))
    assert rows[0]["SupervisorMsg"].startswith("attempts=2")
    assert rows[0]["Status"] == harness.DEGRADED  # lower rung != requested tier
    rebuilt = harness.case_result_from_row(rows[0])
    assert rebuilt.supervisor_msg == r.supervisor_msg
