"""Network serving front end tests (ISSUE 11, docs/SERVING.md "Network
front end & SLOs") — CPU, virtual 8-device mesh.

Covers the tentpole surface: the HTTP transport honoring the admission
queue contract exactly (429 backpressure, 413 oversize, 400 malformed,
504 explicit shed, 200 with reference-exact outputs), per-request
``serve.transport`` spans + ``serve_transport``/``serve_reject`` journal
records, traffic shapes (seeded diurnal/burst/flash arrivals, heavy-
tailed class mixes), SLO-aware shed-by-class under a flash crowd with
per-class CLOSED accounting, the ``QueueStats.oldest_wait_ms`` gauge,
and the chaos drills riding through the front end unchanged.
"""

import dataclasses
import http.client
import json
import os
import subprocess
import sys
import time
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
from cuda_mpi_gpu_cluster_programming_tpu.observability.export import (
    to_trace_events,
)
from cuda_mpi_gpu_cluster_programming_tpu.observability.metrics import (
    registry as metrics_registry,
)
from cuda_mpi_gpu_cluster_programming_tpu.observability.trace import (
    Tracer,
    set_tracer,
)
from cuda_mpi_gpu_cluster_programming_tpu.resilience import chaos
from cuda_mpi_gpu_cluster_programming_tpu.resilience.journal import Journal
from cuda_mpi_gpu_cluster_programming_tpu.serving.frontend import (
    ServingFrontend,
    http_fleet_load,
)
from cuda_mpi_gpu_cluster_programming_tpu.serving.loadgen import (
    percentile,
    run_shaped_load,
)
from cuda_mpi_gpu_cluster_programming_tpu.serving.queue import (
    OK,
    SHED,
    AdmissionQueue,
)
from cuda_mpi_gpu_cluster_programming_tpu.serving.server import (
    InferenceServer,
    ServeConfig,
    class_latencies_from_journal,
)
from cuda_mpi_gpu_cluster_programming_tpu.serving.slo import SLOClass, SLOPolicy
from cuda_mpi_gpu_cluster_programming_tpu.serving.traffic import (
    RequestClass,
    default_class_mix,
    parse_shape,
    shaped_arrivals,
    slo_policy,
)

ROOT = Path(__file__).resolve().parent.parent

CFG = dataclasses.replace(BLOCKS12, in_height=63, in_width=63)
IMG_SHAPE = (CFG.in_height, CFG.in_width, CFG.in_channels)


def _img(v: float = 1.0, n: int = 1) -> np.ndarray:
    return np.full((n, *IMG_SHAPE), v, np.float32)


@pytest.fixture(autouse=True)
def _clean_process_state(monkeypatch):
    monkeypatch.delenv(chaos.CHAOS_ENV, raising=False)
    chaos.reset()
    metrics_registry().reset()
    yield
    set_tracer(None)
    chaos.reset()


def _post(fe, payload, timeout=60.0):
    conn = http.client.HTTPConnection(fe.host, fe.port, timeout=timeout)
    try:
        conn.request(
            "POST", "/v1/infer", json.dumps(payload),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def _get(fe, path, timeout=30.0):
    conn = http.client.HTTPConnection(fe.host, fe.port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def _wait_records(jpath, kind, n, timeout_s=10.0):
    """Journal writes land in @off_timed_path finishers AFTER the client
    already has its response — poll (bounded) so assertions read a
    settled trail instead of racing the writer thread."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        recs = [r for r in Journal.load(jpath) if r["kind"] == kind]
        if len(recs) >= n:
            return recs
        time.sleep(0.01)
    return [r for r in Journal.load(jpath) if r["kind"] == kind]


# ------------------------------------------------------------ transport ---


def test_http_roundtrip_matches_reference():
    """An inference request over the wire returns EXACTLY what the
    in-process forward returns — the transport adds a socket, never a
    numeric."""
    srv = InferenceServer(ServeConfig(config="v1_jit", max_batch=4, model_cfg=CFG))
    srv.start()
    fe = ServingFrontend(srv).start()
    try:
        x = _img(1.25, n=2)
        code, body = _post(
            fe,
            {
                "shape": list(x.shape),
                "data": x.reshape(-1).tolist(),
                "return_output": True,
            },
        )
        assert code == 200 and body["status"] == OK
        params = init_params_deterministic(CFG)
        want = np.asarray(jax.jit(lambda p, a: forward_blocks12(p, a, CFG))(params, x))
        got = np.asarray(body["output"], np.float32).reshape(body["output_shape"])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        assert body["latency_ms"] > 0
    finally:
        fe.stop()
        srv.stop()
    assert srv.stats.cache_misses == 0


def test_http_healthz_and_stats_expose_queue_gauges():
    srv = InferenceServer(ServeConfig(config="v1_jit", max_batch=4, model_cfg=CFG))
    fe = ServingFrontend(srv).start()
    try:
        srv.submit(_img())  # parked: dispatch loop not running
        time.sleep(0.02)
        code, body = _get(fe, "/healthz")
        assert code == 200 and body["status"] == "ok"
        qs = body["queue"]
        assert qs["depth"] == 1 and qs["pending_images"] == 1
        assert qs["oldest_wait_ms"] > 0  # saturation visible pre-shed
        code, body = _get(fe, "/stats")
        assert code == 200 and "queue" in body and "http" in body
        # no controller configured => no controller key: the pre-ISSUE-18
        # probe payload shape, exactly
        assert "controller" not in body
        code, _ = _get(fe, "/nope")
        assert code == 404
    finally:
        fe.stop()


def test_http_healthz_and_stats_expose_controller_state():
    """ISSUE 18 satellite: with the Autopilot attached, /healthz and
    /stats carry its state snapshot (mode, level, active overrides, last
    action + age) — the router's probes see degraded-but-healthy instead
    of inferring it from latency."""
    from cuda_mpi_gpu_cluster_programming_tpu.serving.controller import (
        ControllerConfig,
    )
    from cuda_mpi_gpu_cluster_programming_tpu.serving.traffic import (
        default_class_mix,
        slo_policy,
    )

    mix = list(default_class_mix([1, 2, 4]))
    srv = InferenceServer(ServeConfig(
        config="v1_jit", max_batch=4, model_cfg=CFG,
        slo=slo_policy(mix), controller=ControllerConfig(),
    ))
    fe = ServingFrontend(srv).start()
    try:
        for path in ("/healthz", "/stats"):
            code, body = _get(fe, path)
            assert code == 200
            ctl = body["controller"]
            assert ctl["mode"] == "steady" and ctl["level"] == 0
            assert ctl["overrides"] == [] and ctl["last_action"] is None
        # a degraded controller is visible through the same window
        for _ in range(srv.controller.cfg.min_completed):
            srv.controller.note_shed("interactive")
        srv.controller.evaluate(now=1e9)
        code, body = _get(fe, "/healthz")
        ctl = body["controller"]
        assert ctl["mode"] == "degraded" and ctl["level"] == 1
        assert ctl["overrides"][0]["action"] == "tighten_admission"
        assert ctl["last_action"]["action"] == "tighten_admission"
        assert "age_s" in ctl["last_action"]
    finally:
        fe.stop()


def test_http_metrics_prometheus_exposition(tmp_path):
    """ISSUE 13 satellite: GET /metrics serves the process-wide registry
    in Prometheus text exposition (counters, gauges, histogram summaries
    with the repo's nearest-rank percentiles), and the scrape is
    journaled as a serve_transport record like every POST exchange."""
    jp = tmp_path / "serve.jsonl"
    srv = InferenceServer(
        ServeConfig(
            config="v1_jit", max_batch=4, model_cfg=CFG,
            journal_path=str(jp),
        )
    )
    srv.start()
    fe = ServingFrontend(srv).start()
    try:
        code, body = _post(fe, {"shape": [1, *IMG_SHAPE], "fill": 1.0})
        assert code == 200
        conn = http.client.HTTPConnection(fe.host, fe.port, timeout=30)
        try:
            conn.request("GET", "/metrics")
            resp = conn.getresponse()
            text = resp.read().decode()
            assert resp.status == 200
            assert resp.getheader("Content-Type").startswith(
                "text/plain; version=0.0.4"
            )
        finally:
            conn.close()
        lines = text.splitlines()
        assert "# TYPE serve_ok counter" in lines
        assert any(l.startswith("serve_ok ") for l in lines)
        assert "# TYPE serve_request_ms summary" in lines
        assert any('serve_request_ms{quantile="0.5"}' in l for l in lines)
        assert any(l.startswith("serve_request_ms_count") for l in lines)
        # the registry's dotted names sanitize to the exposition grammar
        assert not any("." in l.split("{")[0].split(" ")[0] for l in lines
                       if l and not l.startswith("#"))
    finally:
        fe.stop()
        srv.stop()
    recs = _wait_records(jp, "serve_transport", 2)
    assert any(r.get("status") == "METRICS" for r in recs)


def test_http_backpressure_oversize_and_malformed():
    """The admission contract on the wire: QueueFull -> 429 (+Retry-After),
    wider than the largest bucket -> 413, malformed body -> 400; every
    refusal journals a serve_reject record."""
    import tempfile

    jpath = tempfile.mktemp(suffix=".jsonl")
    srv = InferenceServer(
        ServeConfig(config="v1_jit", max_batch=2, max_pending=1,
                    model_cfg=CFG, journal_path=jpath)
    )
    fe = ServingFrontend(srv).start()
    try:
        srv.submit(_img())  # fills max_pending=1; dispatch loop not running
        code, body = _post(fe, {"shape": [1, *IMG_SHAPE], "fill": 1.0})
        assert code == 429 and body["status"] == "REJECTED"
        assert "max_pending" in body["error"]
        code, body = _post(fe, {"shape": [5, *IMG_SHAPE], "fill": 1.0})
        assert code == 413 and "largest bucket" in body["error"]
        code, body = _post(fe, {"shape": "nope"})
        assert code == 400 and body["status"] == "REJECTED"
        code, body = _post(fe, {"shape": [1, *IMG_SHAPE], "data": [1.0, 2.0]})
        assert code == 400  # wrong element count
    finally:
        fe.stop()
    rejects = _wait_records(jpath, "serve_reject", 4)
    assert sorted(r["http"] for r in rejects) == [400, 400, 413, 429]


def test_http_shed_answers_504_with_reason():
    """A queue shed is an explicit wire verdict: 504 + the reason — the
    client always learns what happened, nothing is silently dropped."""
    srv = InferenceServer(
        ServeConfig(config="v1_jit", max_batch=4, model_cfg=CFG)
    ).start()
    fe = ServingFrontend(srv).start()
    try:
        code, body = _post(
            fe, {"shape": [1, *IMG_SHAPE], "fill": 1.0, "deadline_s": 1e-6}
        )
        assert code == 504
        assert body["status"] == SHED and body["reason"] == "deadline"
    finally:
        fe.stop()
        srv.stop()


def test_http_fleet_diurnal_burst_end_to_end(tmp_path):
    """THE acceptance drill: a threaded HTTP client fleet drives a
    diurnal+burst shape through the front end — per-class accounting
    closes, zero post-warmup cache misses, per-class p99s come out of the
    journal, every exchange has a serve.transport span + serve_transport
    record, and the whole journal exports into one Perfetto timeline."""
    jpath = tmp_path / "serve.jsonl"
    mix = list(default_class_mix((1, 2, 4)))
    scfg = ServeConfig(config="v1_jit", max_batch=4, model_cfg=CFG,
                       journal_path=str(jpath), slo=slo_policy(mix))
    srv = InferenceServer(scfg)
    tracer = Tracer(journal=srv.journal)
    set_tracer(tracer)
    srv.start()
    fe = ServingFrontend(srv).start()
    try:
        report = http_fleet_load(
            fe.url, IMG_SHAPE,
            shape="diurnal:amp=0.8,period=0.6+burst:every=0.3,mult=4",
            rate_rps=35.0, duration_s=0.6, classes=mix, seed=11, n_workers=6,
        )
    finally:
        fe.stop()
        srv.stop()
        set_tracer(None)
    assert report.n_requests > 0 and report.n_ok > 0
    assert report.closed  # ok+shed+failed+rejected == offered, PER CLASS
    assert srv.stats.cache_misses == 0
    _wait_records(
        jpath, "serve_transport",
        report.n_ok + report.n_shed + report.n_failed,
    )
    recs = Journal.load(jpath)
    # per-class p99s from the journal: every OK request's latency lands
    # under its class
    by_cls = class_latencies_from_journal(jpath)
    assert sum(len(v) for v in by_cls.values()) == report.n_ok
    for name, stats in report.per_class.items():
        if stats.ok:
            lats = by_cls[name]
            assert len(lats) == stats.ok
            assert percentile(lats, 99) > 0
    # transport records: one per non-rejected HTTP exchange, spans beside
    transports = [r for r in recs if r["kind"] == "serve_transport"]
    assert len(transports) == report.n_ok + report.n_shed + report.n_failed
    assert all(r["span_id"] for r in transports)
    span_names = {r["name"] for r in recs if r["kind"] == "span"}
    assert "serve.transport" in span_names and "serve.dispatch" in span_names
    # the export stitches the new kinds onto the serve lane
    trace = to_trace_events(recs)
    names = {ev["name"] for ev in trace["traceEvents"]}
    assert "serve.transport" in names and "serve_transport" in names


def test_http_chaos_device_loss_drill_through_frontend(tmp_path, monkeypatch):
    """The PR 6 chaos drill with the front end ATTACHED: a seeded device
    loss mid-load trips the supervisor, the in-flight batch replays down
    the ladder, and every HTTP client still gets a 200 — degradation
    stays invisible to the wire except in latency."""
    jpath = tmp_path / "serve.jsonl"
    monkeypatch.setenv(chaos.CHAOS_ENV, "seed=3,device_loss=1")
    chaos.reset()
    srv = InferenceServer(
        ServeConfig(config="v2.2_sharded", n_shards=2, max_batch=4,
                    supervise=True, model_cfg=CFG, journal_path=str(jpath))
    ).start()
    fe = ServingFrontend(srv).start()
    try:
        codes = []
        for i in range(4):
            code, body = _post(
                fe,
                {"shape": [1, *IMG_SHAPE], "fill": 1.0 + 0.01 * i,
                 "class": "interactive"},
                timeout=120.0,
            )
            codes.append((code, body["status"]))
    finally:
        fe.stop()
        srv.stop()
    monkeypatch.delenv(chaos.CHAOS_ENV)
    chaos.reset()
    assert codes == [(200, OK)] * 4  # nobody 500s through a device loss
    assert [t.kind for t in srv.sup.trips] == ["device_loss"]
    assert srv.stats.cache_misses == 0  # re-warm kept the discipline
    kinds = [r["kind"] for r in Journal.load(jpath)]
    assert "sup_trip" in kinds and "serve_rewarm" in kinds
    assert kinds.index("serve_rewarm") < kinds.index("serve_batch")


# ------------------------------------------------------- traffic shapes ---


def test_shaped_arrivals_deterministic_and_sorted():
    for spec in ("steady", "diurnal", "burst", "flash", "diurnal+burst"):
        a = shaped_arrivals(spec, 80.0, 1.0, seed=5)
        assert a == shaped_arrivals(spec, 80.0, 1.0, seed=5)
        assert a == sorted(a) and all(0 <= t < 1.0 for t in a)
        assert shaped_arrivals(spec, 80.0, 1.0, seed=6) != a
    assert shaped_arrivals("steady", 0.0, 1.0) == []


def test_shaped_arrivals_shapes_actually_shape():
    # diurnal phased to start at the trough: the second half of one
    # period carries more arrivals than the first
    a = shaped_arrivals("diurnal:amp=0.9,period=4", 200.0, 4.0, seed=0)
    assert len([t for t in a if t < 2.0]) < len([t for t in a if t >= 2.0])
    # burst/flash ADD traffic on top of the steady base
    base = len(shaped_arrivals("steady", 100.0, 2.0, seed=1))
    burst = len(shaped_arrivals("burst:every=0.5,mult=6,width=0.1", 100.0, 2.0, seed=1))
    flash = len(shaped_arrivals("flash:at=0.5,mult=10,width=0.2", 100.0, 2.0, seed=1))
    assert burst > base and flash > base
    # the flash crowd clumps around its epicenter
    fa = shaped_arrivals("flash:at=0.5,mult=20,width=0.1", 50.0, 2.0, seed=2)
    in_window = [t for t in fa if 1.0 <= t <= 1.3]
    assert len(in_window) > len(fa) / 2


def test_parse_shape_rejects_typos_loudly():
    with pytest.raises(ValueError, match="unknown traffic shape"):
        parse_shape("diurnall")
    with pytest.raises(ValueError, match="not key=number"):
        parse_shape("burst:every=lots")
    assert [c.kind for c in parse_shape("diurnal+burst")] == ["diurnal", "burst"]


def test_default_class_mix_is_heavy_tailed_over_buckets():
    mix = default_class_mix((1, 2, 4, 8))
    assert [c.name for c in mix] == ["interactive", "batch", "bulk"]
    inter, batch, bulk = mix
    assert inter.weight > batch.weight > bulk.weight
    assert inter.sizes == (1,) and bulk.sizes == (8,)
    assert set(batch.sizes) == {2, 4}
    assert inter.slo_ms < batch.slo_ms
    assert bulk.slo_ms == 0.0  # unbounded: never SLO-shed


# ------------------------------------------------------------ SLO layer ---


def test_slo_policy_sheds_by_class_not_by_age():
    pol = SLOPolicy(
        [SLOClass("tight", slo_ms=50.0), SLOClass("loose", slo_ms=5000.0)]
    )
    # same age, different verdicts: the class (not the age alone) decides
    assert pol.should_shed("tight", 80.0) == "slo"
    assert pol.should_shed("loose", 80.0) is None
    assert pol.should_shed("tight", 10.0) is None
    # unknown/unclassed requests keep PR 6 semantics: never SLO-shed
    assert pol.should_shed("", 1e9) is None
    assert pol.should_shed("mystery", 1e9) is None
    assert pol.deadline_for("tight") is None
    pol2 = SLOPolicy([SLOClass("d", slo_ms=100.0, deadline_s=0.5)])
    assert pol2.deadline_for("d") == 0.5


def test_queue_stats_oldest_wait_gauge():
    """ISSUE 11 satellite: saturation is observable BEFORE the first shed
    — depth, pending images, per-class depths, and the FIFO head's age."""
    q = AdmissionQueue()
    assert q.stats().oldest_wait_ms == 0.0 and q.stats().depth == 0
    q.submit(_img(n=2), cls="batch")
    q.submit(_img(), cls="interactive")
    time.sleep(0.02)
    qs = q.stats()
    assert qs.depth == 2 and qs.pending_images == 3
    assert qs.per_class == {"batch": 1, "interactive": 1}
    assert qs.oldest_wait_ms >= 20.0  # the head has waited at least the sleep
    obj = qs.to_obj()
    assert obj["oldest_wait_ms"] == round(qs.oldest_wait_ms, 3)
    q.pop_ready(max_images=8)
    qs2 = q.stats()
    assert qs2.depth == 0 and qs2.pending_images == 0
    assert qs2.oldest_wait_ms == 0.0 and qs2.per_class == {}


def test_flash_crowd_sheds_by_class_accounting_closes(tmp_path):
    """ISSUE 11 satellite: under a flash crowd, the tight-SLO class sheds
    (reason="slo", journaled with its class) while the unbounded class
    completes — and accounting closes PER CLASS: ok + shed + failed +
    rejected == offered for every class."""
    jpath = tmp_path / "serve.jsonl"
    mix = [
        RequestClass("tight", 0.6, (1,), (1.0,), deadline_s=None, slo_ms=40.0),
        RequestClass("loose", 0.4, (2,), (1.0,), deadline_s=None, slo_ms=0.0),
    ]
    scfg = ServeConfig(config="v1_jit", max_batch=4, model_cfg=CFG,
                       journal_path=str(jpath), slo=slo_policy(mix))
    srv = InferenceServer(scfg).start()
    try:
        report = run_shaped_load(
            srv, shape="flash:at=0.2,mult=40,width=0.2", rate_rps=40.0,
            duration_s=0.5, classes=mix, seed=9,
        )
    finally:
        srv.stop()
    assert report.closed  # the satellite's acceptance: closes per class
    tight, loose = report.per_class["tight"], report.per_class["loose"]
    assert tight.offered > 0 and loose.offered > 0
    assert tight.shed > 0  # the flash crowd blew the 40 ms budget
    assert loose.shed == 0 and loose.failed == 0  # unbounded class rode it out
    assert srv.stats.cache_misses == 0
    sheds = [r for r in Journal.load(jpath) if r["kind"] == "serve_shed"]
    assert len(sheds) == report.n_shed
    assert all(r["reason"] == "slo" and r["cls"] == "tight" for r in sheds)
    assert all(r["waited_ms"] > 40.0 for r in sheds)
    # the metrics registry saw it too, attributably
    assert metrics_registry().counter("serve.shed_slo").value == report.n_shed
    # and saturation was observable before the shed: the gauge moved
    gauge = metrics_registry().gauge("serve.queue_oldest_wait_ms")
    assert gauge.value is not None


# ----------------------------------------------------------- CLI surfaces ---


def test_run_cli_serve_frontend_traffic_shape_smoke(tmp_path):
    """run --serve --serve-frontend 0 --traffic-shape: the whole network
    path from socket to shard_map under a shaped HTTP client fleet, with
    the machine-parsed frontend/class/transport lines."""
    jpath = tmp_path / "serve.jsonl"
    proc = subprocess.run(
        [sys.executable, "-m", "cuda_mpi_gpu_cluster_programming_tpu.run",
         "--config", "v1_jit", "--serve", "--serve-frontend", "0",
         "--traffic-shape", "diurnal+burst", "--serve-rate", "25",
         "--serve-duration", "0.5", "--serve-max-batch", "4",
         "--height", "63", "--width", "63",
         "--serve-journal", str(jpath)],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    assert any(l.startswith("Serve frontend: url=http://") for l in lines)
    load = next(l for l in lines if l.startswith("Serve load: "))
    assert "shape=diurnal+burst" in load and "rejected=" in load
    cls_lines = [l for l in lines if l.startswith("Serve class: ")]
    assert len(cls_lines) == 3  # interactive / batch / bulk
    assert any(l.startswith("Serve transport: http_200=") for l in lines)
    serve = next(l for l in lines if l.startswith("Serve: "))
    assert "cache_misses=0" in serve
    # the journal carries the transport records beside the batches
    kinds = {r["kind"] for r in Journal.load(jpath)}
    assert "serve_transport" in kinds and "serve_batch" in kinds


def test_run_cli_rejects_bad_traffic_shape():
    proc = subprocess.run(
        [sys.executable, "-m", "cuda_mpi_gpu_cluster_programming_tpu.run",
         "--config", "v1_jit", "--serve", "--traffic-shape", "tsunami"],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 2
    assert "unknown traffic shape" in proc.stderr
