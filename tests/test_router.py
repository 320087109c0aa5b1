"""Fleet router tests (ISSUE 16, docs/SERVING.md "Fleet router") — CPU.

Covers the tentpole surface: deterministic crc32 routing with class-aware
spillover (no-spill classes get a first-class ``unroutable`` verdict),
the probe-driven backend health machine with the ElasticPool's anti-flap
hysteresis (K misses down, M clean probes re-admit, flaps-in-window
quarantine sticky), retry-with-redirect on 429/504/connection-failure
under the request's deadline budget with every hop journaled, per-class
accounting CLOSED at the router, the ``host_loss`` chaos site, and the
process-boundary acceptance drill: SIGKILL a real backend process
mid-load, redirect within budget, restart, re-admit through probation,
and stitch every journal into one valid Perfetto timeline with the
outage folded into a phase-decomposed backend_down incident.

Fast tests drive stub backends (programmable wire verdicts) in-process;
the acceptance drill and the CLI smoke spawn real fleets.
"""

import http.client
import json
import os
import subprocess
import sys
import threading
import time
import zlib
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import pytest

from cuda_mpi_gpu_cluster_programming_tpu.observability.export import (
    load_records,
    to_trace_events,
)
from cuda_mpi_gpu_cluster_programming_tpu.observability.health import (
    BACKEND_DOWN_PHASES,
    health_from_records,
    incidents_from_records,
)
from cuda_mpi_gpu_cluster_programming_tpu.observability.metrics import (
    registry as metrics_registry,
)
from cuda_mpi_gpu_cluster_programming_tpu.resilience import chaos
from cuda_mpi_gpu_cluster_programming_tpu.resilience.journal import Journal
from cuda_mpi_gpu_cluster_programming_tpu.resilience.policy import RetryPolicy
from cuda_mpi_gpu_cluster_programming_tpu.serving.fleet import (
    BackendFleet,
    maybe_host_loss,
)
from cuda_mpi_gpu_cluster_programming_tpu.serving.frontend import (
    http_fleet_load,
)
from cuda_mpi_gpu_cluster_programming_tpu.serving.router import (
    DOWN,
    PROBATION,
    QUARANTINED,
    UP,
    FleetRouter,
    RouterConfig,
)
from cuda_mpi_gpu_cluster_programming_tpu.serving.batcher import (
    power_of_two_buckets,
)
from cuda_mpi_gpu_cluster_programming_tpu.serving.traffic import (
    default_class_mix,
)

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _clean_process_state(monkeypatch):
    monkeypatch.delenv(chaos.CHAOS_ENV, raising=False)
    chaos.reset()
    metrics_registry().reset()
    yield
    chaos.reset()


# ------------------------------------------------------------- stubs ---


class _StubHandler(BaseHTTPRequestHandler):
    backend: "StubBackend"
    protocol_version = "HTTP/1.1"

    def log_message(self, *args):
        pass

    def _send(self, code, payload, ctype="application/json"):
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        if code == 429:
            self.send_header("Retry-After", "1")
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        b = self.backend
        if self.path == "/healthz":
            if b.healthz_ok:
                self._send(200, {"status": "ok", "queue": {"depth": 0}})
            else:
                self._send(503, {"status": "down"})
        elif self.path == "/metrics":
            body = b"# TYPE serve_ok counter\nserve_ok 0\n"
            self.send_response(200 if b.metrics_ok else 500)
            self.send_header("Content-Type", "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        else:
            self._send(404, {"error": "no route"})

    def do_POST(self):
        b = self.backend
        length = int(self.headers.get("Content-Length") or 0)
        req = json.loads(self.rfile.read(length) or b"{}")
        b.hits.append(str(req.get("rid", "")))
        code = b.next_code()
        if code == 200:
            self._send(200, {"rid": req.get("rid"), "status": "OK",
                             "latency_ms": 1.0})
        elif code == 429:
            self._send(429, {"status": "REJECTED", "error": "queue full"})
        elif code == 504:
            self._send(504, {"rid": req.get("rid"), "status": "SHED"})
        else:
            self._send(code, {"status": "FAILED"})


class StubBackend:
    """A programmable backend speaking just enough of the front-end wire
    contract for router tests: scripted /v1/infer verdicts (then 200
    forever), toggleable /healthz + /metrics."""

    def __init__(self, codes=()):
        self.codes = list(codes)
        self.healthz_ok = True
        self.metrics_ok = True
        self.hits = []
        self._lock = threading.Lock()
        handler = type("BoundStub", (_StubHandler,), {"backend": self})
        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), handler)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )
        self._thread.start()

    @property
    def url(self):
        return f"http://127.0.0.1:{self.port}"

    def next_code(self):
        with self._lock:
            return self.codes.pop(0) if self.codes else 200

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(5.0)


@pytest.fixture
def stub_trio():
    backends = [StubBackend() for _ in range(3)]
    yield backends
    for b in backends:
        b.stop()


def _router(urls, tmp_path=None, **kw):
    """A router with the probe thread OFF (tests step probe_once/route
    directly) and a journal when tmp_path is given."""
    kw.setdefault("probe_interval_s", 0)
    kw.setdefault("retry", RetryPolicy(
        max_retries=3, base_delay_s=0.01, max_delay_s=0.05, jitter=0.0,
    ))
    if tmp_path is not None:
        kw.setdefault("journal_path", str(tmp_path / "router.jsonl"))
    return FleetRouter(urls, RouterConfig(**kw))


def _close(router):
    router.stop()
    router._httpd.server_close()


def _rid_homed(router, idx, cls=""):
    """A rid whose crc32 home is backend ``idx`` — routing is a pure
    function, so tests can pick their victim deterministically."""
    for i in range(10_000):
        rid = f"{cls}rid{i}"
        if router.home(rid) == idx:
            return rid
    raise AssertionError(f"no rid homes on {idx}")


def _post(host, port, payload, timeout=60.0):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request(
            "POST", "/v1/infer", json.dumps(payload),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def _wait_records(jpath, kind, n, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        recs = [r for r in Journal.load(jpath) if r["kind"] == kind]
        if len(recs) >= n:
            return recs
        time.sleep(0.01)
    return [r for r in Journal.load(jpath) if r["kind"] == kind]


# ------------------------------------------------------ deterministic ---


def test_home_and_candidates_are_deterministic(stub_trio):
    urls = [b.url for b in stub_trio]
    r1 = _router(urls)
    r2 = _router(urls)
    try:
        for i in range(40):
            rid = f"req{i}"
            assert r1.home(rid) == zlib.crc32(rid.encode()) % 3
            # Pure function of (rid, cls, N): two routers agree, repeat
            # calls agree, and the spill order covers every backend.
            order = r1.candidates(rid, "interactive")
            assert order == r2.candidates(rid, "interactive")
            assert order == r1.candidates(rid, "interactive")
            assert sorted(order) == [0, 1, 2]
            assert order[0] == r1.home(rid)
    finally:
        _close(r1)
        _close(r2)


def test_no_spill_classes_get_home_only(stub_trio):
    r = _router([b.url for b in stub_trio])
    try:
        for i in range(10):
            rid = f"bulk{i}"
            assert r.candidates(rid, "bulk") == [r.home(rid)]
            assert len(r.candidates(rid, "batch")) == 3
    finally:
        _close(r)


# ------------------------------------------------------ health machine ---


def test_probe_machine_k_down_m_readmit(stub_trio, tmp_path):
    """fail_k consecutive misses take a backend down (detect latency
    attributed); a heal enters probation; readmit_m clean probes — and
    only probes, probation gets no traffic — re-admit."""
    urls = [b.url for b in stub_trio]
    r = _router(urls, tmp_path, fail_k=2, readmit_m=2)
    try:
        stub_trio[1].healthz_ok = False
        r.probe_once()
        assert r.backend_states()["b1"] == UP  # 1 miss < K
        r.probe_once()
        assert r.backend_states()["b1"] == DOWN
        stub_trio[1].healthz_ok = True
        r.probe_once()  # heal -> probation, clean streak starts at 0
        assert r.backend_states()["b1"] == PROBATION
        # Probation is NOT routable: it earns readmission through clean
        # probes, never through live traffic.
        assert r._pick([1], avoid=None) is None
        r.probe_once()
        assert r.backend_states()["b1"] == PROBATION  # 1 clean < M
        r.probe_once()
        assert r.backend_states()["b1"] == UP
        recs = _wait_records(tmp_path / "router.jsonl", "router_backend_state", 3)
        downs = [x for x in recs if x["to"] == DOWN]
        assert downs and downs[0]["frm"] == UP
        assert downs[0]["consec_fail"] == 2 and downs[0]["detect_ms"] >= 0
        readmits = [x for x in recs if x["reason"] == "readmit"]
        assert readmits and readmits[0]["clean_probes"] == 2
        assert readmits[0]["down_ms"] >= readmits[0]["probation_ms"]
    finally:
        _close(r)


def test_probation_miss_goes_back_down(stub_trio):
    urls = [b.url for b in stub_trio]
    r = _router(urls, fail_k=1, readmit_m=3)
    try:
        stub_trio[0].healthz_ok = False
        r.probe_once()
        assert r.backend_states()["b0"] == DOWN
        down_since = r.slots[0].down_since
        stub_trio[0].healthz_ok = True
        r.probe_once()
        assert r.backend_states()["b0"] == PROBATION
        stub_trio[0].healthz_ok = False
        r.probe_once()
        # Back down — and the ORIGINAL down_since survives, so the
        # folded incident wall covers the whole outage.
        assert r.backend_states()["b0"] == DOWN
        assert r.slots[0].down_since == down_since
    finally:
        _close(r)


def test_flapping_backend_quarantined_sticky(stub_trio):
    """quarantine_flaps heals inside flap_window_s quarantine the host
    sticky: further probes skip it and it never re-enters the ring."""
    urls = [b.url for b in stub_trio]
    r = _router(urls, fail_k=1, readmit_m=5, quarantine_flaps=2,
                flap_window_s=60.0)
    try:
        for _ in range(2):  # two lose->heal half-cycles inside the window
            stub_trio[2].healthz_ok = False
            r.probe_once()
            assert r.backend_states()["b2"] == DOWN
            stub_trio[2].healthz_ok = True
            r.probe_once()
        assert r.backend_states()["b2"] == QUARANTINED
        r.probe_once()  # sticky: probing does not resurrect it
        assert r.backend_states()["b2"] == QUARANTINED
    finally:
        _close(r)


def test_metrics_scrape_failure_is_a_health_miss(stub_trio):
    """The /metrics scrape rides every probe: a wedged exporter is a
    health failure, not a monitoring gap."""
    r = _router([b.url for b in stub_trio], fail_k=1)
    try:
        stub_trio[0].metrics_ok = False
        r.probe_once()
        assert r.backend_states()["b0"] == DOWN
    finally:
        _close(r)


# ----------------------------------------------- redirect + accounting ---


def test_redirect_on_429_lands_elsewhere_and_is_journaled(stub_trio, tmp_path):
    urls = [b.url for b in stub_trio]
    r = _router(urls, tmp_path)
    try:
        rid = _rid_homed(r, 1)
        stub_trio[1].codes = [429, 429, 429, 429]  # home refuses all day
        res = r.route(rid, "interactive", 5.0, json.dumps(
            {"rid": rid, "shape": [1, 63, 63, 3], "fill": 1.0}).encode())
        assert res.code == 200 and res.verdict == "ok"
        assert res.redirects >= 1 and res.backend != "b1"
        assert rid in stub_trio[1].hits  # home was tried first
        recs = _wait_records(tmp_path / "router.jsonl", "router_redirect", 1)
        assert recs[0]["rid"] == rid and recs[0]["frm"] == "b1"
        assert recs[0]["reason"] == "http_429"
    finally:
        _close(r)


def test_retry_budget_is_the_request_deadline(stub_trio):
    """Every backend refusing: the router keeps redirecting only while
    the request's own deadline has budget, then surfaces the last real
    backend verdict (429 -> rejected, 504 -> shed) — bounded, never a
    hang, never a silent drop."""
    urls = [b.url for b in stub_trio]
    r = _router(urls, retry=RetryPolicy(
        max_retries=50, base_delay_s=0.05, max_delay_s=0.1, jitter=0.0,
    ))
    try:
        for b in stub_trio:
            b.codes = [429] * 200
        t0 = time.monotonic()
        res = r.route("rbudget", "interactive", 0.4, b"{}")
        wall = time.monotonic() - t0
        assert res.code == 429 and res.verdict == "rejected"
        assert wall < 5.0  # deadline-bounded, not max_retries-bounded
        for b in stub_trio:
            b.codes = [504] * 200
        res = r.route("rshed", "interactive", 0.3, b"{}")
        assert res.code == 504 and res.verdict == "shed"
    finally:
        _close(r)


def test_unroutable_and_closed_accounting_northbound(stub_trio, tmp_path):
    """The wire story: a no-spill request whose home is down gets an
    attributed 503 UNROUTABLE; spillable traffic rides over; the
    router's per-class ledger closes with the fifth bucket."""
    urls = [b.url for b in stub_trio]
    r = _router(urls, tmp_path).start()
    try:
        bulk_rid = _rid_homed(r, 2, cls="b")
        r.slots[2].state = DOWN  # host lost; probes haven't healed it
        code, body = _post(r.host, r.port, {
            "rid": bulk_rid, "class": "bulk", "shape": [1, 63, 63, 3],
            "fill": 1.0,
        })
        assert code == 503 and body["status"] == "UNROUTABLE"
        inter_rid = _rid_homed(r, 2, cls="i")
        code, body = _post(r.host, r.port, {
            "rid": inter_rid, "class": "interactive",
            "shape": [1, 63, 63, 3], "fill": 1.0,
        })
        assert code == 200  # spillable class rode over the dead home
        rep = r.report()
        assert rep.closed and rep.n_unroutable == 1
        assert rep.per_class["bulk"].unroutable == 1
        assert rep.per_class["interactive"].ok == 1
        assert "unroutable=1" in rep.summary()
        conn = http.client.HTTPConnection(r.host, r.port, timeout=10)
        try:
            conn.request("POST", "/v1/infer", b"not json",
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 400  # malformed: rejected at the router
            resp.read()
        finally:
            conn.close()
        assert r.report().closed
        recs = _wait_records(tmp_path / "router.jsonl", "router_route", 3)
        verdicts = {x["rid"]: x["verdict"] for x in recs if x["rid"]}
        assert verdicts[bulk_rid] == "unroutable"
        assert verdicts[inter_rid] == "ok"
    finally:
        _close(r)


def test_request_path_conn_failure_feeds_health_machine(stub_trio):
    """A dead host is detected by the traffic it kills: the failed hop
    feeds the probe machine (fail_k=1 downs it immediately) and the
    request still lands elsewhere within its budget."""
    urls = [b.url for b in stub_trio]
    r = _router(urls, fail_k=1)
    try:
        rid = _rid_homed(r, 0)
        stub_trio[0].stop()  # SIGKILL stand-in: connection refused
        res = r.route(rid, "interactive", 5.0, json.dumps(
            {"rid": rid, "shape": [1, 63, 63, 3], "fill": 1.0}).encode())
        assert res.code == 200 and res.redirects >= 1
        assert r.backend_states()["b0"] == DOWN
    finally:
        _close(r)


def test_router_healthz_and_stats_endpoints(stub_trio):
    r = _router([b.url for b in stub_trio]).start()
    try:
        conn = http.client.HTTPConnection(r.host, r.port, timeout=10)
        try:
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            body = json.loads(resp.read())
            assert resp.status == 200 and body["routable"] == 3
            conn.request("GET", "/stats")
            resp = conn.getresponse()
            stats = json.loads(resp.read())
            assert resp.status == 200 and stats["accounting_closed"]
        finally:
            conn.close()
    finally:
        _close(r)


# ------------------------------------------------------------- chaos ---


def test_host_loss_is_a_known_chaos_site(monkeypatch):
    assert "host_loss" in chaos.KNOWN_SITES

    class _FakeFleet:
        n = 3

        def __init__(self):
            self.killed = []

        def kill(self, idx):
            self.killed.append(idx)

    fleet = _FakeFleet()
    monkeypatch.setenv(chaos.CHAOS_ENV, "seed=4,host_loss=1")
    chaos.reset()
    assert maybe_host_loss(fleet) == 4 % 3  # victim = seed % n
    assert fleet.killed == [1]
    assert maybe_host_loss(fleet) is None  # budget burned: fires once
    assert fleet.killed == [1]
    monkeypatch.delenv(chaos.CHAOS_ENV)
    chaos.reset()
    assert maybe_host_loss(fleet) is None  # chaos off: never fires


# --------------------------------------------------- journal stitching ---


def _synthetic_outage_records():
    """A hand-built outage trail: b1 downs at t=1000ms (detected 40ms
    after first miss), traffic redirects away, heals into probation at
    t=3000ms, re-admits at t=4000ms."""
    return [
        {"kind": "router_config", "n_backends": 2, "t_ms": 0.0},
        {"kind": "router_backend_state", "backend": "b1", "url": "u",
         "frm": "up", "to": "down", "reason": "conn:ConnectionRefusedError",
         "consec_fail": 2, "detect_ms": 40.0, "t_ms": 1000.0},
        {"kind": "router_redirect", "rid": "r1", "frm": "b1", "to": "b0",
         "attempt": 1, "reason": "conn:ConnectionRefusedError",
         "t_ms": 1200.0},
        {"kind": "router_redirect", "rid": "r2", "frm": "b1", "to": "b0",
         "attempt": 1, "reason": "conn:ConnectionRefusedError",
         "t_ms": 1500.0},
        {"kind": "router_backend_state", "backend": "b1", "url": "u",
         "frm": "down", "to": "probation", "reason": "heal",
         "probes_needed": 2, "t_ms": 3000.0},
        {"kind": "router_backend_state", "backend": "b1", "url": "u",
         "frm": "probation", "to": "up", "reason": "readmit",
         "clean_probes": 2, "probation_ms": 1000.0, "down_ms": 3000.0,
         "t_ms": 4000.0},
        {"kind": "router_route", "rid": "r1", "cls": "interactive",
         "verdict": "ok", "backend": "b0", "attempts": 2, "redirects": 1,
         "http": 200, "ms": 12.0, "t_ms": 1212.0},
    ]


def test_health_folds_backend_down_incident_phases_sum_to_wall():
    recs = _synthetic_outage_records()
    incidents = [
        i for i in incidents_from_records(recs) if i.kind == "backend_down"
    ]
    assert len(incidents) == 1
    inc = incidents[0]
    assert inc.entry == "b1" and inc.cause == "conn:ConnectionRefusedError"
    # t0 = detection start (first miss), close = readmission: the wall
    # covers the whole outage and the phases decompose it exactly.
    assert inc.wall_ms == pytest.approx(4000.0 - (1000.0 - 40.0))
    assert tuple(inc.phases) == BACKEND_DOWN_PHASES
    assert inc.phase_sum_ms == pytest.approx(inc.wall_ms)
    assert inc.phases["detect"] == pytest.approx(40.0)
    # last redirect in the outage window, relative to the down mark
    assert inc.phases["redirect"] == pytest.approx(500.0)
    assert inc.phases["readmit"] == pytest.approx(1000.0)
    assert "backend_down b1" in inc.render()
    rep = health_from_records(recs)
    assert rep.probation_enters >= 1 and rep.probation_passes >= 1


def test_export_renders_router_lane(tmp_path):
    """The stitched directory (router + backend journals) exports into
    one valid Perfetto timeline with the router's own process lane."""
    jr = Journal(str(tmp_path / "router.jsonl"))
    for rec in _synthetic_outage_records():
        kind = rec.pop("kind")
        jr.append(kind, **rec)
    jb = Journal(str(tmp_path / "backend_0.jsonl"))
    jb.append("serve_transport", rid="r1", status="OK", http=200, ms=2.0)
    recs = load_records(tmp_path)
    assert any(r["kind"] == "router_route" for r in recs)
    obj = to_trace_events(recs)
    events = obj["traceEvents"]
    names = {
        e["args"]["name"] for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    assert "router" in names
    router_pid = next(
        e["pid"] for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
        and e["args"]["name"] == "router"
    )
    kinds_on_lane = {
        e["name"] for e in events
        if e.get("pid") == router_pid and e.get("ph") in ("X", "i", "I")
    }
    assert {"router_route", "router_redirect", "router_backend_state"} & kinds_on_lane
    json.dumps(obj)  # serializable end to end


# ------------------------------------------ one process for each chip ---


def test_fleet_on_a_tpu_host_refuses_up_front(tmp_path, monkeypatch):
    """On a TPU host the launcher pins one chip per backend: more backends
    than local chips, or a parent that already holds the chips (it has
    initialised a JAX backend, as this test process has), is refused at
    once with the reason — never a wait for the spawn timeout."""
    from cuda_mpi_gpu_cluster_programming_tpu.serving import fleet as fleet_mod

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setattr(fleet_mod, "local_tpu_chips", lambda: 2)
    t0 = time.monotonic()
    with pytest.raises(fleet_mod.FleetError, match="needs 3 local TPU chips"):
        BackendFleet(3, tmp_path).start()
    with pytest.raises(fleet_mod.FleetError, match="already initialised a JAX backend"):
        BackendFleet(2, tmp_path).start()
    assert time.monotonic() - t0 < 5.0
    # children held to the CPU are not pinned, whatever the host has
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert BackendFleet(3, tmp_path)._chips == 0


def test_local_tpu_chips_counts_only_vfio_groups_that_hold_a_tpu(tmp_path):
    """A /dev/vfio/N node is a chip only if IOMMU group N holds a Google PCI
    function; a chip in sysfs without its group node is not counted."""
    from cuda_mpi_gpu_cluster_programming_tpu.serving.fleet import local_tpu_chips

    dev, groups = tmp_path / "dev", tmp_path / "iommu_groups"
    (dev / "vfio").mkdir(parents=True)
    for node in ("vfio", "1", "7"):
        (dev / "vfio" / node).touch()
    for group, vendor in (("0", "0x1ae0"), ("1", "0x1ae0"), ("7", "0x8086")):
        fn = groups / group / "devices" / f"0000:00:0{group}.0"
        fn.mkdir(parents=True)
        (fn / "vendor").write_text(vendor + "\n")
    assert local_tpu_chips(str(dev), str(groups)) == 1  # group 1 only
    (dev / "accel0").touch()
    (dev / "accel1").touch()
    assert local_tpu_chips(str(dev), str(groups)) == 2
    assert local_tpu_chips(str(tmp_path / "none"), str(groups)) == 0


def test_jax_backend_initialised_private_api_exists():
    """API-drift guard: the launcher's parent check names a private jax
    function; a jax upgrade that moves it must surface here, not as a
    launcher that silently stops refusing (this process has a backend)."""
    import jax

    from cuda_mpi_gpu_cluster_programming_tpu.serving.fleet import (
        jax_backend_initialised,
    )

    jax.devices()
    assert jax_backend_initialised() is True


def test_chip_pin_env_gives_each_backend_its_own_chip_and_ports():
    from cuda_mpi_gpu_cluster_programming_tpu.serving.fleet import chip_pin_env

    envs = [chip_pin_env(i) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    for key in ("TPU_MESH_CONTROLLER_PORT", "TPU_RUNTIME_METRICS_PORTS"):
        assert len({e[key] for e in envs}) == 4  # no two backends collide


# ------------------------------------------------- acceptance drill ---


def test_host_loss_drill_across_process_boundary(tmp_path, monkeypatch):
    """THE acceptance drill (ISSUE 16): 3 real backend processes behind
    the router; the seeded chaos host_loss SIGKILLs one mid-run; the
    router detects via the traffic it kills, redirects within budget,
    keeps its per-class ledger closed, and re-admits the restarted
    process only through probation. The shared directory then stitches
    into one valid timeline and folds one phase-decomposed backend_down
    incident."""
    monkeypatch.setenv(chaos.CHAOS_ENV, "seed=1,host_loss=1")
    chaos.reset()
    fleet = BackendFleet(3, tmp_path, height=63, width=63, max_batch=4)
    router = None
    try:
        fleet.start()
        router = FleetRouter(
            fleet.urls(),
            RouterConfig(
                probe_interval_s=0.1,
                probe_timeout_s=2.0,
                fail_k=2,
                readmit_m=2,
                retry=RetryPolicy(
                    max_retries=3, base_delay_s=0.02, max_delay_s=0.25,
                    jitter=0.1,
                ),
                default_deadline_s=30.0,
                journal_path=str(tmp_path / "router.jsonl"),
            ),
        ).start()
        mix = list(default_class_mix(power_of_two_buckets(4)))
        shape = (63, 63, 3)
        pre = http_fleet_load(
            router.url, shape, shape="steady", rate_rps=25,
            duration_s=1.0, classes=mix, seed=0,
        )
        assert pre.n_ok > 0 and pre.n_failed == 0
        killed = maybe_host_loss(fleet)
        assert killed == 1  # seed=1 % 3 — deterministic victim
        assert not fleet.backends[killed].alive
        post = http_fleet_load(
            router.url, shape, shape="steady", rate_rps=25,
            duration_s=1.2, classes=mix, seed=1,
        )
        # The fleet survives the loss: traffic still lands (the dead
        # host's share redirects within each request's budget).
        assert post.n_ok > 0
        assert router.backend_states()["b1"] == DOWN
        # Restart = replacement host: same ring slot, new port, and
        # re-admission ONLY through probation.
        router.replace_backend(killed, fleet.restart(killed))
        deadline = time.monotonic() + 60.0
        saw_probation = False
        while time.monotonic() < deadline:
            st = router.backend_states()["b1"]
            saw_probation = saw_probation or st == PROBATION
            if st == UP:
                break
            time.sleep(0.05)
        assert router.backend_states()["b1"] == UP
        assert saw_probation  # never straight to UP
        rep = router.report()
        assert rep.closed, rep.summary()
        assert rep.n_offered == pre.n_requests + post.n_requests
        router.stop()
        # Journal trail: the outage is attributable end to end.
        recs = load_records(tmp_path)
        states = [r for r in recs if r["kind"] == "router_backend_state"]
        assert any(
            r["backend"] == "b1" and r["to"] == DOWN for r in states
        )
        assert any(
            r["backend"] == "b1" and r["reason"] == "readmit" for r in states
        )
        assert any(
            r["backend"] == "b1" and r["reason"] == "endpoint_replaced"
            for r in states
        )
        incidents = [
            i for i in incidents_from_records(recs)
            if i.kind == "backend_down" and i.entry == "b1"
        ]
        assert len(incidents) == 1
        inc = incidents[0]
        assert inc.phase_sum_ms == pytest.approx(inc.wall_ms, rel=1e-6)
        assert tuple(inc.phases) == BACKEND_DOWN_PHASES
        # One stitched timeline over every journal in the directory:
        # backend serve records AND the router's four kinds.
        kinds = {r["kind"] for r in recs}
        assert "router_config" in kinds
        assert any(k.startswith("serve_") for k in kinds)  # backend trail
        obj = to_trace_events(recs)
        assert obj["traceEvents"]
        json.dumps(obj)
    finally:
        if router is not None:
            router.stop()
        fleet.stop()


# --------------------------------------------------------------- CLI ---


def test_run_route_cli_smoke(tmp_path):
    """run.py --serve --route N: fleet + router + shaped load through
    the router, machine-parseable Route:/Health: lines, closed
    accounting."""
    proc = subprocess.run(
        [
            sys.executable, "-m", "cuda_mpi_gpu_cluster_programming_tpu.run",
            "--config", "v1_jit", "--serve", "--route", "2",
            "--height", "63", "--width", "63", "--serve-max-batch", "4",
            "--serve-rate", "15", "--serve-duration", "1.0",
            "--route-dir", str(tmp_path / "route"),
        ],
        cwd=ROOT, capture_output=True, text=True, timeout=420,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    out = proc.stdout
    assert "Route fleet: n=2" in out
    route_line = next(
        l for l in out.splitlines() if l.startswith("Route: ")
    )
    assert "closed=True" in route_line
    assert "b0=up b1=up" in route_line
    assert "Health: " in out
    assert (tmp_path / "route" / "router.jsonl").exists()
    assert (tmp_path / "route" / "backend_0.jsonl").exists()
