"""Continuous-batching serving subsystem tests — CPU, virtual 8-device mesh.

Covers the tentpole surface (docs/SERVING.md): admission-queue FIFO +
backpressure, the bucket-assembly invariants (every dispatched batch's
padded size is a member of the configured bucket set; no request is ever
lost or reordered), explicit deadline shedding (SHED status + journal
record, never a silent drop), the TunePlan-derived bucket set, the
zero-cache-miss dispatch discipline, the seeded ``device_loss`` chaos
drill (in-flight requests finish via supervisor replay, bit-identical to
an unfaulted run pinned to the degraded rung), the Poisson load generator,
and the CLI surface ``run --serve`` (the tier-1 CPU-mesh serve smoke).
"""

import dataclasses
import json
import os
import random
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import (
    BLOCKS12,
    forward_blocks12,
)
from cuda_mpi_gpu_cluster_programming_tpu.models.init import (
    init_params_deterministic,
)
from cuda_mpi_gpu_cluster_programming_tpu.resilience import chaos
from cuda_mpi_gpu_cluster_programming_tpu.resilience.journal import Journal
from cuda_mpi_gpu_cluster_programming_tpu.serving.batcher import (
    Batcher,
    bucket_for,
    power_of_two_buckets,
)
from cuda_mpi_gpu_cluster_programming_tpu.serving.loadgen import (
    percentile,
    poisson_arrivals,
    run_load,
)
from cuda_mpi_gpu_cluster_programming_tpu.serving.queue import (
    OK,
    SHED,
    AdmissionQueue,
    QueueFull,
)
from cuda_mpi_gpu_cluster_programming_tpu.serving.server import (
    InferenceServer,
    ServeConfig,
    request_latencies_from_journal,
)

ROOT = Path(__file__).resolve().parent.parent

CFG = dataclasses.replace(BLOCKS12, in_height=63, in_width=63)


def _img(v: float = 1.0, n: int = 1) -> np.ndarray:
    return np.full((n, CFG.in_height, CFG.in_width, CFG.in_channels), v, np.float32)


@pytest.fixture(autouse=True)
def _chaos_off(monkeypatch):
    monkeypatch.delenv(chaos.CHAOS_ENV, raising=False)
    chaos.reset()
    yield
    chaos.reset()


# ------------------------------------------------------------- buckets ---


def test_power_of_two_buckets():
    assert power_of_two_buckets(1) == (1,)
    assert power_of_two_buckets(8) == (1, 2, 4, 8)
    # a non-power-of-two ceiling is itself a legal dispatch shape
    assert power_of_two_buckets(6) == (1, 2, 4, 6)
    with pytest.raises(ValueError):
        power_of_two_buckets(0)


def test_bucket_for_picks_smallest_fit_and_rejects_oversize():
    assert bucket_for(1, (1, 2, 4)) == 1
    assert bucket_for(3, (1, 2, 4)) == 4
    with pytest.raises(ValueError, match="fit no bucket"):
        bucket_for(5, (1, 2, 4))


# --------------------------------------------------------------- queue ---


def test_queue_fifo_order_and_backpressure():
    q = AdmissionQueue(max_pending=2)
    h1 = q.submit(_img(1.0))
    h2 = q.submit(_img(2.0))
    with pytest.raises(QueueFull):
        q.submit(_img(3.0))
    taken, shed = q.pop_ready(max_images=8)
    assert [r.handle for r in taken] == [h1, h2] and shed == []
    assert len(q) == 0


def test_pop_ready_sheds_expired_explicitly():
    q = AdmissionQueue()
    expired = q.submit(_img(1.0), deadline_s=1e-9)
    live = q.submit(_img(2.0))
    import time

    time.sleep(0.01)
    taken, shed = q.pop_ready(max_images=8)
    # the expired request is returned for journaling AND its handle is
    # completed SHED — counted, attributed, never silently dropped
    assert [r.handle for r in shed] == [expired]
    assert expired.status == SHED and "deadline" in expired.error
    assert [r.handle for r in taken] == [live]


def test_queue_rejects_bad_rank():
    q = AdmissionQueue()
    with pytest.raises(ValueError, match="request input"):
        q.submit(np.zeros((4, 4)))


# ------------------------------------------------------------- batcher ---


def test_batch_assembly_invariants_random_streams():
    """THE bucket invariant: over seeded random request streams, every
    assembled batch's padded size is in the bucket set, requests stay in
    FIFO order, and each request lands in exactly one batch."""
    rng = random.Random(7)
    for trial in range(5):
        q = AdmissionQueue()
        buckets = power_of_two_buckets(rng.choice([4, 8, 6]))
        batcher = Batcher(q, buckets)
        handles = [
            q.submit(_img(float(i), n=rng.randint(1, buckets[-1])))
            for i in range(rng.randint(3, 12))
        ]
        seen = []
        while len(q):
            batch, shed = batcher.next_batch(wait_s=0.0)
            assert shed == []
            assert batch is not None
            assert batch.bucket in buckets  # the invariant
            assert batch.n_images <= batch.bucket
            assert batch.padded_input().shape[0] == batch.bucket
            seen.extend(r.handle for r in batch.requests)
        assert seen == handles  # FIFO, nothing lost, nothing duplicated


def test_padded_input_zero_pads_after_payload():
    q = AdmissionQueue()
    q.submit(_img(3.0, n=3))
    batch, _ = Batcher(q, (1, 2, 4)).next_batch(wait_s=0.0)
    xb = batch.padded_input()
    assert xb.shape[0] == 4 and batch.pad == 1
    assert (xb[:3] == 3.0).all() and (xb[3:] == 0.0).all()


# ------------------------------------------------------------- loadgen ---


def test_poisson_arrivals_deterministic_and_bounded():
    a = poisson_arrivals(100.0, 1.0, seed=3)
    b = poisson_arrivals(100.0, 1.0, seed=3)
    assert a == b and all(0 < t < 1.0 for t in a)
    assert a == sorted(a)
    assert poisson_arrivals(100.0, 1.0, seed=4) != a
    assert poisson_arrivals(0.0, 1.0) == []
    # law of large numbers sanity: ~rate*duration arrivals
    n = len(poisson_arrivals(200.0, 5.0, seed=0))
    assert 800 < n < 1200


def test_percentile_nearest_rank():
    xs = [10.0, 20.0, 30.0, 40.0]
    assert percentile(xs, 50) == 20.0
    assert percentile(xs, 99) == 40.0
    assert percentile(xs, 0) == 10.0
    assert percentile([], 50) is None
    assert percentile([5.0], 99) == 5.0


# -------------------------------------------------- TunePlan bucket set ---


def test_plan_batches_derives_bucket_set(tmp_path):
    from cuda_mpi_gpu_cluster_programming_tpu.tuning.plan import (
        code_rev,
        plan_batches,
        plan_key,
        shape_key,
    )

    rev = code_rev()
    sk = shape_key(CFG)
    plans = {
        plan_key("cpu", sk, 2, "fp32", rev): {"batch": 2},
        plan_key("cpu", sk, 8, "fp32", rev): {"batch": 8},
        # stale rev: winners describe old kernels — excluded
        plan_key("cpu", sk, 4, "fp32", "deadbeefdead"): {"batch": 4},
        # other dtype/device points — excluded
        plan_key("cpu", sk, 16, "bf16", rev): {"batch": 16},
        plan_key("TPU v5 lite", sk, 32, "fp32", rev): {"batch": 32},
        # malformed entry — skipped, not fatal
        plan_key("cpu", sk, 64, "fp32", rev): {"batch": "nope"},
    }
    path = tmp_path / "plan.json"
    path.write_text(json.dumps({"version": 1, "plans": plans}))
    assert plan_batches(
        path, device_kind="cpu", model_cfg=CFG, dtype="fp32"
    ) == [2, 8]
    assert plan_batches(
        path, device_kind="cpu", model_cfg=CFG, dtype="int8"
    ) == []
    assert plan_batches(
        tmp_path / "missing.json", device_kind="cpu", model_cfg=CFG, dtype="fp32"
    ) == []


def test_server_buckets_from_plan(tmp_path):
    from cuda_mpi_gpu_cluster_programming_tpu.tuning.plan import (
        code_rev,
        plan_key,
        shape_key,
    )

    rev, sk = code_rev(), shape_key(CFG)
    kind = jax.devices()[0].device_kind
    plans = {
        plan_key(kind, sk, b, "fp32", rev): {"batch": b} for b in (2, 4, 16)
    }
    path = tmp_path / "plan.json"
    path.write_text(json.dumps({"version": 1, "plans": plans}))
    srv = InferenceServer(
        ServeConfig(max_batch=8, plan_path=str(path), model_cfg=CFG)
    )
    # tuned batches <= max_batch become the bucket set; 16 is filtered
    assert srv.buckets == (2, 4)
    # no matching plan -> powers-of-two fallback
    srv2 = InferenceServer(
        ServeConfig(max_batch=8, plan_path=str(tmp_path / "none.json"), model_cfg=CFG)
    )
    assert srv2.buckets == (1, 2, 4, 8)


# -------------------------------------------------------------- server ---


def test_serve_roundtrip_matches_reference(tmp_path):
    jpath = tmp_path / "serve.jsonl"
    srv = InferenceServer(
        ServeConfig(config="v1_jit", max_batch=4, model_cfg=CFG,
                    journal_path=str(jpath))
    )
    sizes = [1, 3, 2, 1, 4]
    handles = [srv.submit(_img(1.0 + 0.1 * i, n=n)) for i, n in enumerate(sizes)]
    srv.run_until_drained()
    params = init_params_deterministic(CFG)
    fwd = jax.jit(lambda p, x: forward_blocks12(p, x, CFG))
    for i, (h, n) in enumerate(zip(handles, sizes)):
        assert h.status == OK and h.result.shape[0] == n
        want = np.asarray(fwd(params, _img(1.0 + 0.1 * i, n=n)))
        np.testing.assert_allclose(h.result, want, rtol=1e-5, atol=1e-5)
    # zero post-warmup compiles: every dispatched shape was a warmed bucket
    assert srv.stats.cache_misses == 0
    assert srv.stats.warmup_compiles == len(srv.buckets)
    recs = Journal.load(jpath)
    batches = [r for r in recs if r["kind"] == "serve_batch"]
    assert batches and all(r["bucket"] in srv.buckets for r in batches)
    assert sum(r["n_requests"] for r in batches) == len(sizes)
    # journaled per-request latencies cover every completed request
    assert len(request_latencies_from_journal(jpath)) == len(sizes)
    warm = [r for r in recs if r["kind"] == "serve_warm"]
    assert [r["bucket"] for r in warm] == list(srv.buckets)


def test_deadline_shed_is_explicit_and_journaled(tmp_path):
    jpath = tmp_path / "serve.jsonl"
    srv = InferenceServer(
        ServeConfig(config="v1_jit", max_batch=4, model_cfg=CFG,
                    journal_path=str(jpath))
    )
    import time

    doomed = [srv.submit(_img(), deadline_s=1e-9) for _ in range(3)]
    live = [srv.submit(_img()) for _ in range(2)]
    time.sleep(0.01)
    srv.run_until_drained()
    assert all(h.status == SHED for h in doomed)
    assert all(h.status == OK for h in live)
    # accounting closes: every submitted request is ok or shed, and the
    # journal carries one serve_shed record per shed request
    assert srv.stats.n_ok + srv.stats.n_shed == len(doomed) + len(live)
    recs = Journal.load(jpath)
    assert len([r for r in recs if r["kind"] == "serve_shed"]) == len(doomed)


def test_submit_rejects_wider_than_largest_bucket():
    srv = InferenceServer(ServeConfig(max_batch=4, model_cfg=CFG))
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        srv.submit(_img(n=5))


def test_chaos_device_loss_drill_replays_in_flight_bit_identical(
    tmp_path, monkeypatch
):
    """The acceptance drill through the serving stack: a device loss mid-
    load trips the supervisor, the service re-plans down the ladder,
    re-warms every bucket on the new rung, REPLAYS the in-flight batch,
    and every request finishes with outputs bit-identical to an unfaulted
    server pinned to the degraded rung. Zero cache misses throughout."""
    jpath = tmp_path / "serve.jsonl"
    scfg = ServeConfig(config="v2.2_sharded", n_shards=2, max_batch=4,
                       supervise=True, model_cfg=CFG, journal_path=str(jpath))
    imgs = [_img(1.0 + 0.01 * i) for i in range(6)]

    monkeypatch.setenv(chaos.CHAOS_ENV, "seed=3,device_loss=1")
    chaos.reset()
    faulted = InferenceServer(scfg)
    handles = [faulted.submit(im) for im in imgs]
    faulted.run_until_drained()
    monkeypatch.delenv(chaos.CHAOS_ENV)
    chaos.reset()

    assert all(h.status == OK for h in handles)  # nobody 500s
    assert [t.kind for t in faulted.sup.trips] == ["device_loss"]
    assert faulted.sup.entry.key == "replicated@2:reference"
    assert faulted.stats.cache_misses == 0  # re-warm keeps the discipline
    kinds = [r["kind"] for r in Journal.load(jpath)]
    assert "sup_trip" in kinds and "serve_rewarm" in kinds
    assert kinds.index("serve_rewarm") < kinds.index("serve_batch")

    clean = InferenceServer(
        dataclasses.replace(scfg, journal_path=""),
        ladder=[faulted.sup.entry],
    )
    clean_handles = [clean.submit(im) for im in imgs]
    clean.run_until_drained()
    for a, b in zip(handles, clean_handles):
        assert b.status == OK
        assert np.array_equal(a.result, b.result)


def test_chaos_mesh_shrink_drill_server_survives_with_zero_misses(
    tmp_path, monkeypatch
):
    """ISSUE 8 serving drill: a seeded mesh_shrink ACTUALLY drops devices
    mid-load; the supervisor rebuilds the rung over the survivors,
    live-reshards the params, re-warms every bucket, and replays — the
    server finishes with completed == n_requests and ZERO post-rewarm
    cache misses, bit-identical to a clean server pinned to the landed
    rung."""
    jpath = tmp_path / "serve.jsonl"
    scfg = ServeConfig(config="v2.2_sharded", n_shards=4, max_batch=4,
                       supervise=True, model_cfg=CFG, journal_path=str(jpath))
    imgs = [_img(1.0 + 0.01 * i) for i in range(6)]

    monkeypatch.setenv(chaos.CHAOS_ENV, "seed=3,mesh_shrink=2")
    chaos.reset()
    shrunk = InferenceServer(scfg)
    handles = [shrunk.submit(im) for im in imgs]
    shrunk.run_until_drained()
    monkeypatch.delenv(chaos.CHAOS_ENV)
    chaos.reset()

    assert sum(1 for h in handles if h.status == OK) == len(imgs)
    assert [t.kind for t in shrunk.sup.trips] == ["mesh_shrink"]
    assert shrunk.sup.pool.n_total == 8 and shrunk.sup.pool.n_alive == 6
    assert shrunk.sup.entry.key == "halo@2:reference"  # the surviving rung
    assert shrunk.stats.cache_misses == 0  # zero post-rewarm misses
    assert shrunk.stats.rewarm_ms > 0
    kinds = [r["kind"] for r in Journal.load(jpath)]
    assert "mesh_shrink" in kinds  # the pool's shrink record
    assert kinds.index("serve_rewarm") < kinds.index("serve_batch")

    clean = InferenceServer(
        dataclasses.replace(scfg, journal_path=""),
        ladder=[shrunk.sup.entry],
    )
    clean_handles = [clean.submit(im) for im in imgs]
    clean.run_until_drained()
    for a, b in zip(handles, clean_handles):
        assert b.status == OK
        assert np.array_equal(a.result, b.result)


def test_grow_back_drill_promotes_with_zero_misses_bit_identical(
    tmp_path, monkeypatch
):
    """ISSUE 10 serving drill: a seeded mesh shrink degrades the service;
    healing the lost device puts it in probation; after N clean batches it
    graduates and the dispatch loop PROMOTES back to the original rung
    between batches — completed == offered end to end, ZERO cache misses
    (every bucket re-warmed at the higher rung before cutover), and every
    wave's outputs bit-identical to a clean server pinned to that wave's
    topology."""
    jpath = tmp_path / "serve.jsonl"
    scfg = ServeConfig(config="v2.2_sharded", n_shards=4, max_batch=4,
                       supervise=True, model_cfg=CFG, journal_path=str(jpath))
    imgs = [_img(1.0 + 0.01 * i) for i in range(6)]

    def _wave(server):
        handles = [server.submit(im) for im in imgs]
        server.run_until_drained()
        return handles

    srv = InferenceServer(scfg)
    offered, results = 0, []
    wave_pre = _wave(srv)  # clean wave at halo@4
    monkeypatch.setenv(chaos.CHAOS_ENV, "seed=3,mesh_shrink=1")
    chaos.reset()
    wave_loss = _wave(srv)  # seeded loss: trip -> degrade -> replay
    monkeypatch.delenv(chaos.CHAOS_ENV)
    chaos.reset()
    degraded = srv.sup.entry.key
    assert [t.kind for t in srv.sup.trips] == ["mesh_shrink"]
    assert srv.sup.pool.n_alive == 7
    srv.sup.pool.heal(srv.sup.pool.recently_lost(1), cause="drill:heal")
    assert srv.sup.pool.n_probation == 1
    # One wave = two clean batches = the full probation (N=2). Promotion
    # must NOT fire inside it — the device graduates on its last batch.
    wave_prob = _wave(srv)
    assert srv.sup.promotions == 0  # hysteresis: nothing during probation
    assert srv.sup.pool.n_probation == 0  # ...but the device graduated
    assert srv.sup.entry.key == degraded
    wave_post = _wave(srv)  # first step promotes, then dispatches at halo@4
    assert srv.sup.promotions == 1 and srv.stats.promotions == 1
    assert srv.sup.entry.key == "halo@4:reference"
    assert srv.sup.pool.summary() == "8/8"
    # accounting + the zero-miss discipline across the WHOLE lifecycle
    all_handles = [wave_pre, wave_loss, wave_prob, wave_post]
    assert all(h.status == OK for wave in all_handles for h in wave)
    assert srv.stats.cache_misses == 0
    kinds = [r["kind"] for r in Journal.load(jpath)]
    for a, b in [("mesh_shrink", "mesh_probation"),
                 ("mesh_probation", "sup_promote")]:
        assert kinds.index(a) < kinds.index(b)
    # the promotion's re-warm lands BEFORE the first post-promotion batch
    assert (
        len([k for k in kinds if k == "serve_rewarm"]) == 2
    )  # one per degrade, one per promote
    # every wave bit-identical to a clean server pinned to its topology
    for wave, entry_key in [(wave_pre, "halo@4:reference"),
                            (wave_loss, degraded),
                            (wave_post, "halo@4:reference")]:
        from cuda_mpi_gpu_cluster_programming_tpu.resilience.supervisor import (
            LadderEntry,
        )

        strategy, rest = entry_key.split("@")
        n, tier = rest.split(":")
        clean = InferenceServer(
            dataclasses.replace(scfg, journal_path=""),
            ladder=[LadderEntry(strategy, tier, int(n))],
        )
        clean_handles = [clean.submit(im) for im in imgs]
        clean.run_until_drained()
        for a, b in zip(wave, clean_handles):
            assert b.status == OK
            assert np.array_equal(a.result, b.result)


def test_threaded_poisson_load_accounts_for_every_request(tmp_path):
    from cuda_mpi_gpu_cluster_programming_tpu.observability.metrics import (
        registry,
    )

    jpath = tmp_path / "serve.jsonl"
    registry().reset()
    srv = InferenceServer(
        ServeConfig(config="v1_jit", max_batch=4, model_cfg=CFG,
                    journal_path=str(jpath))
    ).start()
    try:
        report = run_load(srv, rate_rps=60.0, duration_s=0.4, seed=1)
    finally:
        srv.stop()
    assert report.n_requests > 0
    assert (
        report.n_ok + report.n_shed + report.n_failed + report.n_rejected
        == report.n_requests
    )
    assert report.n_ok == report.n_requests  # unloaded CPU: nothing sheds
    assert report.p50_ms is not None and report.p99_ms >= report.p50_ms
    assert report.sustained_img_s > 0
    assert srv.stats.cache_misses == 0
    # the journaled latencies are the same population the report saw
    assert len(request_latencies_from_journal(jpath)) == report.n_ok
    # and so is the process registry's: one count per answered request,
    # one batch time per dispatch
    metrics = registry().summary()
    assert metrics["serve.ok"] == report.n_ok
    assert metrics["serve.batch_ms"]["count"] >= 1
    assert metrics["serve.batch_ms"]["p50"] > 0
    # same estimator over the same population: the registry's p99 is the
    # journal's (which rounds each latency to a microsecond)
    reg_p99 = registry().histogram("serve.request_ms").percentile(99)
    j_p99 = percentile(request_latencies_from_journal(jpath), 99)
    assert abs(reg_p99 - j_p99) <= 1e-3


# ----------------------------------------------------------- CLI surfaces ---


def test_run_cli_serve_smoke(tmp_path):
    jpath = tmp_path / "serve.jsonl"
    proc = subprocess.run(
        [sys.executable, "-m", "cuda_mpi_gpu_cluster_programming_tpu.run",
         "--config", "v1_jit", "--serve", "--serve-rate", "30",
         "--serve-duration", "0.4", "--serve-max-batch", "4",
         "--height", "63", "--width", "63",
         "--serve-journal", str(jpath)],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    load = next(l for l in lines if l.startswith("Serve load: "))
    serve = next(l for l in lines if l.startswith("Serve: "))
    assert "p50_ms=" in load and "img_s=" in load
    assert "cache_misses=0" in serve and "buckets=1,2,4" in serve
    assert request_latencies_from_journal(jpath)


def test_run_cli_serve_rejects_full_model():
    proc = subprocess.run(
        [sys.executable, "-m", "cuda_mpi_gpu_cluster_programming_tpu.run",
         "--config", "v6_full_jit", "--serve"],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 2
    assert "Blocks 1-2 configs only" in proc.stderr
