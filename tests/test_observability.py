"""Observability subsystem coverage (ISSUE 9): span tracing over the
journal, the metrics registry, per-stage attribution at the sentinel tap
boundaries, Perfetto export round-trips, and the wired drill surfaces
(supervisor trip span trees, serve queue-wait/dispatch correlation)."""

import dataclasses
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from cuda_mpi_gpu_cluster_programming_tpu.observability import (  # noqa: E402
    MetricsRegistry,
    Tracer,
    current_ids,
    registry,
    set_tracer,
    span,
)
from cuda_mpi_gpu_cluster_programming_tpu.observability.export import (  # noqa: E402
    export_trace,
    to_trace_events,
)
from cuda_mpi_gpu_cluster_programming_tpu.resilience.journal import (  # noqa: E402
    Journal,
)


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    set_tracer(None)
    yield
    set_tracer(None)


# ---------------------------------------------------------------------------
# trace


def test_span_ids_nesting_and_journal_roundtrip(tmp_path):
    jp = tmp_path / "j.jsonl"
    tr = Tracer(journal=Journal(jp), seed=0)
    set_tracer(tr)
    assert current_ids() == {"trace_id": tr.trace_id}
    with span("run.outer", phase="x") as outer:
        assert current_ids() == {
            "trace_id": tr.trace_id, "span_id": outer.span_id,
        }
        with span("run.inner") as inner:
            assert inner.parent_id == outer.span_id
        outer.set(result=1)
    recs = Journal.load(jp)
    assert [r["kind"] for r in recs] == ["span", "span"]
    inner_rec, outer_rec = recs  # inner closes (and persists) first
    assert inner_rec["parent_id"] == outer_rec["span_id"]
    assert outer_rec["parent_id"] == ""
    assert outer_rec["attrs"] == {"phase": "x", "result": 1}
    for r in recs:
        assert r["trace_id"] == tr.trace_id
        assert r["dur_ms"] >= 0 and r["t0_ms"] >= 0


def test_span_records_error_and_reraises(tmp_path):
    tr = Tracer(journal=Journal(tmp_path / "j.jsonl"), seed=0)
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("no")
    assert tr.spans[0]["attrs"]["error"].startswith("ValueError")


def test_emit_explicit_bounds_and_threads():
    tr = Tracer(seed=0)
    t0 = tr.clock()
    sid = tr.emit("serve.dispatch", t0, t0 + 0.005, track="dispatch", bucket=4)
    rec = tr.spans[0]
    assert rec["span_id"] == sid and rec["track"] == "dispatch"
    assert abs(rec["dur_ms"] - 5.0) < 1.0
    # per-thread parent stacks: a span open on the main thread is not the
    # parent of a span on another thread
    seen = {}

    def other():
        with tr.span("t2.span") as sp:
            seen["parent"] = sp.parent_id

    with tr.span("main.span"):
        th = threading.Thread(target=other)
        th.start()
        th.join()
    assert seen["parent"] == ""
    tids = {r["tid"] for r in tr.spans}
    assert len(tids) == 2  # one tid per thread


def test_untraced_sites_are_noops():
    with span("anything") as sp:
        assert sp is None
    assert current_ids() == {}


# ---------------------------------------------------------------------------
# metrics


def test_metrics_counter_gauge_histogram_and_summary():
    reg = MetricsRegistry()
    reg.counter("serve.ok").inc(3)
    reg.counter("serve.ok").inc()
    reg.gauge("pool.devices").set(8)
    h = reg.histogram("batch_ms")
    for v in [1.0, 2.0, 3.0, 4.0, 100.0]:
        h.observe(v)
    # nearest-rank: the serving estimator — an OBSERVED value, never
    # interpolated
    from cuda_mpi_gpu_cluster_programming_tpu.serving.loadgen import percentile

    assert h.percentile(50) == percentile([1.0, 2.0, 3.0, 4.0, 100.0], 50) == 3.0
    assert h.percentile(99) == 100.0
    s = reg.summary()
    assert s["serve.ok"] == 4
    assert s["pool.devices"] == 8
    assert s["batch_ms"]["count"] == 5 and s["batch_ms"]["p50"] == 3.0


def test_metrics_type_conflict_and_reset():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")
    reg.reset()
    reg.gauge("x")  # fine after reset


def test_metrics_export_atomic_jsonl(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a").inc(2)
    reg.histogram("b").observe(1.5)
    out = tmp_path / "metrics.jsonl"
    reg.export(out)
    lines = [json.loads(l) for l in out.read_text().splitlines()]
    assert {l["name"] for l in lines} == {"a", "b"}
    by = {l["name"]: l for l in lines}
    assert by["a"]["type"] == "counter" and by["a"]["value"] == 2
    assert by["b"]["type"] == "histogram" and by["b"]["p50"] == 1.5
    # no tmp litter (the atomic_open contract)
    assert [p.name for p in tmp_path.iterdir()] == ["metrics.jsonl"]


def test_process_registry_is_shared():
    registry().counter("test.obs.shared").inc()
    assert registry().summary()["test.obs.shared"] == 1


# ---------------------------------------------------------------------------
# stages


def test_sentinel_stage_names_match_tap_boundaries():
    from cuda_mpi_gpu_cluster_programming_tpu.observability.stages import (
        SENTINEL_STAGES,
        sentinel_stage_fns,
    )

    assert SENTINEL_STAGES == ("conv1", "pool1", "conv2", "pool2", "lrn2")
    assert [n for n, _f in sentinel_stage_fns()] == list(SENTINEL_STAGES)


def _small_cfg():
    from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import BLOCKS12

    return dataclasses.replace(BLOCKS12, in_height=63, in_width=63)


def test_stage_attribution_sums_to_total_within_tolerance():
    """The acceptance contract: per-stage ms sum EXACTLY to the attributor's
    measured total (renormalized prefix-diffs), and that total agrees with
    an independently measured full forward within the 15% CPU-mesh budget."""
    from cuda_mpi_gpu_cluster_programming_tpu.configs import (
        REGISTRY,
        build_forward,
    )
    from cuda_mpi_gpu_cluster_programming_tpu.models.init import (
        deterministic_input,
        init_params_deterministic,
    )
    from cuda_mpi_gpu_cluster_programming_tpu.observability.stages import (
        attribute_stages,
    )
    from cuda_mpi_gpu_cluster_programming_tpu.utils.timing import (
        amortized_stats,
    )

    cfg = _small_cfg()
    params = init_params_deterministic(cfg)
    x = deterministic_input(4, cfg)
    fwd = build_forward(REGISTRY["v1_jit"], cfg)
    # Two independent timing passes on a shared CPU container can land a
    # scheduler hiccup apart; re-measure (bounded) before judging the 15%
    # budget. The sums-to-total identity is asserted on every attempt.
    for attempt in range(3):
        att = attribute_stages(params, x, cfg, repeats=3, warmup=1)
        assert [n for n, _ in att.stages] == list(
            ("conv1", "pool1", "conv2", "pool2", "lrn2")
        )
        assert all(ms >= 0 for _n, ms in att.stages)
        assert att.stage_sum_ms == pytest.approx(att.total_ms, rel=1e-6)
        st = amortized_stats(fwd, params, x, n_small=1, n_large=4)
        if att.stage_sum_ms == pytest.approx(st.per_call_ms, rel=0.15):
            break
    assert att.stage_sum_ms == pytest.approx(st.per_call_ms, rel=0.15)
    obj = att.to_obj()
    assert obj["method"] == "prefix-diff"
    assert obj["stage_sum_ms"] == pytest.approx(obj["total_ms"], abs=0.01)
    assert set(obj["stages"]) == {"conv1", "pool1", "conv2", "pool2", "lrn2"}


def test_stage_attribution_bf16_and_int8w_refusal():
    from cuda_mpi_gpu_cluster_programming_tpu.models.init import (
        deterministic_input,
        init_params_deterministic,
    )
    from cuda_mpi_gpu_cluster_programming_tpu.observability.stages import (
        attribute_stages,
    )

    cfg = _small_cfg()
    params = init_params_deterministic(cfg)
    x = deterministic_input(2, cfg)
    att = attribute_stages(params, x, cfg, compute="bf16", repeats=2, warmup=1)
    assert att.compute == "bf16" and att.total_ms > 0
    with pytest.raises(ValueError, match="fp32|bf16"):
        attribute_stages(params, x, cfg, compute="int8w")


# ---------------------------------------------------------------------------
# export


def _validate_nesting(trace):
    """Chrome trace invariants: ints/floats where required, and X slices
    sharing one (pid, tid) must properly nest (contained or disjoint)."""
    events = trace["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    for e in xs:
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["ts"] >= 0 and e["dur"] > 0
    by_lane = {}
    for e in xs:
        by_lane.setdefault((e["pid"], e["tid"]), []).append(e)
    for lane in by_lane.values():
        lane.sort(key=lambda e: (e["ts"], -e["dur"]))
        open_stack = []
        for e in lane:
            # an end is a float sum (ts + dur): a sibling that ends where this
            # one starts may read 2e-10 later, so the same slack as below
            while open_stack and open_stack[-1] <= e["ts"] + 1e-6:
                open_stack.pop()
            if open_stack:
                assert e["ts"] + e["dur"] <= open_stack[-1] + 1e-6, (
                    "mis-nested slice", e)
            open_stack.append(e["ts"] + e["dur"])
    return xs


def test_export_spans_and_synthetic_journal_roundtrip(tmp_path):
    """ISSUE 9 satellite: spans + a synthetic journal (serve_batch /
    sup_trip / sup_replay / gate_fail) round-trip into a Perfetto JSON
    whose nesting, pids/tids, and timestamps validate."""
    jp = tmp_path / "j.jsonl"
    tr = Tracer(journal=Journal(jp), seed=3)
    with tr.span("sup.trip", kind="device_loss"):
        with tr.span("sup.degrade"):
            time.sleep(0.002)
        with tr.span("sup.replay"):
            time.sleep(0.001)
    j = Journal(jp)
    j.append("serve_batch", key="batch:0", bucket=2, batch_ms=3.25,
             req_lat_ms={"r1": 4.0})
    j.append("sup_trip", key="trip:1", sdc_kind="device_loss", step=0)
    j.append("sup_replay", key="replay:1", step=0, entry="halo@2:reference")
    j.append("gate_fail", key="gate:bf16", policy="bf16")
    out = tmp_path / "trace.json"
    info = export_trace(jp, out)
    assert info["spans"] == 3 and info["records"] == 7
    trace = json.loads(out.read_text())
    xs = _validate_nesting(trace)
    names = {e["name"] for e in trace["traceEvents"]}
    # spans render as slices; uncorrelated records land on the synthetic
    # timeline (serve_batch as a slice via batch_ms, the rest as instants)
    assert {"sup.trip", "sup.degrade", "sup.replay", "serve_batch"} <= {
        e["name"] for e in xs
    }
    assert {"sup_trip", "sup_replay", "gate_fail"} <= names
    # children nest inside the trip span on the same lane
    trip = next(e for e in xs if e["name"] == "sup.trip")
    for child in ("sup.degrade", "sup.replay"):
        ev = next(e for e in xs if e["name"] == child)
        assert (ev["pid"], ev["tid"]) == (trip["pid"], trip["tid"])
        assert trip["ts"] <= ev["ts"]
        assert ev["ts"] + ev["dur"] <= trip["ts"] + trip["dur"] + 1e-6
    # process metadata names every used pid
    meta_pids = {
        e["pid"] for e in trace["traceEvents"]
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert {e["pid"] for e in xs} <= meta_pids


def test_export_grow_back_records_on_incident_lane(tmp_path):
    """ISSUE 10 satellite: the four grow-back record kinds render on the
    supervisor (incident) lane — sup_promote and a probation "pass" as
    SLICES (they carry ms), probation "enter"/quarantine/refusal as
    instants — so an exported incident reads trip -> degrade -> heal ->
    probation -> promote end to end. Journals without them (pre-ISSUE-10)
    export unchanged, which the older roundtrip tests pin."""
    jp = tmp_path / "j.jsonl"
    j = Journal(jp)
    j.append("sup_trip", key="trip:1", sdc_kind="mesh_shrink", step=0)
    j.append("mesh_shrink", key="shrink:8->7", before=8, after=7, lost=[3])
    j.append("mesh_probation", key="probation:3", event="enter", devices=[3],
             probation_steps=2, cause="chaos:device_rejoin")
    j.append("mesh_probation", key="probation-pass:3", event="pass",
             devices=[3], ms=12.5)
    j.append("sup_promote_refused", key="promote-refused:halo@4:reference",
             frm="halo@2:reference", to="halo@4:reference", devices=8,
             cause="sentinel spot-check mismatch")
    j.append("sup_promote", key="promote:1", frm="halo@2:reference",
             to="halo@4:reference", devices=8, step=3, ms=41.0)
    j.append("mesh_quarantine", key="quarantine:5", device=5, flaps=3,
             window=64, cause="chaos:flap")
    trace = to_trace_events(Journal.load(jp))
    _validate_nesting(trace)
    evs = {e["name"]: e for e in trace["traceEvents"] if e["ph"] in "Xi"}
    sup_pid = evs["sup_trip"]["pid"]
    for kind in ("mesh_probation", "mesh_quarantine", "sup_promote",
                 "sup_promote_refused"):
        assert evs[kind]["pid"] == sup_pid, kind  # one incident lane
    assert evs["sup_promote"]["ph"] == "X"  # ms -> slice
    assert evs["sup_promote"]["dur"] == pytest.approx(41.0 * 1e3)
    assert evs["sup_promote"]["args"]["frm"] == "halo@2:reference"
    assert evs["mesh_quarantine"]["ph"] == "i"
    assert evs["sup_promote_refused"]["ph"] == "i"
    # the probation pair: enter is an instant, pass a slice via its ms
    probations = [e for e in trace["traceEvents"]
                  if e["name"] == "mesh_probation"]
    assert sorted(e["ph"] for e in probations) == ["X", "i"]


def test_export_controller_actions_on_their_own_lane(tmp_path):
    """ISSUE 18 satellite: controller_action records render on their own
    "controller" lane — per-action SLICES via their ms with the full
    evidence payload in args — so an exported incident reads signal ->
    action -> recovery beside the serve/sup lanes. Journals without them
    (pre-ISSUE-18) export unchanged: no controller lane appears."""
    from cuda_mpi_gpu_cluster_programming_tpu.observability.export import (
        _PIDS,
    )

    jp = tmp_path / "j.jsonl"
    j = Journal(jp)
    j.append("serve_batch", key="batch:0", bucket=2, batch_ms=3.0,
             req_lat_ms={"r1": 4.0})
    # pre-ISSUE-18 journal: no controller lane in events or metadata
    trace = to_trace_events(Journal.load(jp))
    assert all(
        e["pid"] != _PIDS["controller"] for e in trace["traceEvents"]
    )
    j.append(
        "controller_action", key="ctl:1", action="tighten_admission",
        target="bulk", actuated=True, reversal=False, level=1, ms=2.5,
        evidence={"burn": {"interactive": 64.0}, "oldest_wait_ms": 900.0},
    )
    j.append(
        "controller_action", key="ctl:2", action="relax_admission",
        target="bulk", actuated=True, reversal=True, level=0, ms=1.0,
        evidence={"burn": {"interactive": 0.0}, "oldest_wait_ms": 0.0},
    )
    trace = to_trace_events(Journal.load(jp))
    _validate_nesting(trace)
    acts = [e for e in trace["traceEvents"]
            if e["name"] == "controller_action"]
    assert len(acts) == 2
    for ev in acts:
        assert ev["pid"] == _PIDS["controller"]
        assert ev["ph"] == "X"  # ms -> slice
        assert ev["args"]["evidence"]["burn"]["interactive"] is not None
    assert acts[0]["dur"] == pytest.approx(2.5 * 1e3)
    assert {a["args"]["action"] for a in acts} == {
        "tighten_admission", "relax_admission"
    }
    meta = {
        e["pid"]: e["args"]["name"] for e in trace["traceEvents"]
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert meta[_PIDS["controller"]] == "controller"


def test_export_correlated_record_pins_to_span(tmp_path):
    jp = tmp_path / "j.jsonl"
    tr = Tracer(journal=Journal(jp), seed=1)
    t0 = tr.clock()
    sid = tr.emit("serve.dispatch", t0, t0 + 0.004, track="dispatch")
    Journal(jp).append(
        "serve_batch", key="batch:1", trace_id=tr.trace_id, span_id=sid,
        batch_ms=4.0,
    )
    trace = to_trace_events(Journal.load(jp))
    disp = next(
        e for e in trace["traceEvents"] if e["name"] == "serve.dispatch"
    )
    inst = next(e for e in trace["traceEvents"] if e["name"] == "serve_batch")
    assert inst["ph"] == "i"
    assert (inst["pid"], inst["tid"]) == (disp["pid"], disp["tid"])
    assert inst["ts"] == pytest.approx(disp["ts"] + disp["dur"], abs=1.0)


def test_export_cli_subprocess(tmp_path):
    jp = tmp_path / "j.jsonl"
    tr = Tracer(journal=Journal(jp), seed=0)
    with tr.span("run.measure"):
        pass
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "cuda_mpi_gpu_cluster_programming_tpu.observability",
            "export", "--journal", str(jp),
            "--out", str(tmp_path / "t.json"),
        ],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "Trace exported:" in proc.stdout and "spans=1" in proc.stdout
    trace = json.loads((tmp_path / "t.json").read_text())
    assert any(e.get("name") == "run.measure" for e in trace["traceEvents"])
    # directory form stitches every *.jsonl
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "cuda_mpi_gpu_cluster_programming_tpu.observability",
            "export", "--journal", str(tmp_path),
        ],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
    )
    assert proc.returncode == 0 and "spans=1" in proc.stdout


# ---------------------------------------------------------------------------
# wired drills (the acceptance shape, in-process on the CPU mesh)


def test_serve_device_loss_drill_trip_span_tree(tmp_path):
    """The acceptance timeline: a seeded device-loss drill under a traced
    server produces ONE parent sup.trip span containing degrade / rewarm /
    reshard / replay descendants, and per-request queue-wait + dispatch
    spans carry the same trace id as their serve_batch journal records."""
    from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import BLOCKS12
    from cuda_mpi_gpu_cluster_programming_tpu.resilience import chaos
    from cuda_mpi_gpu_cluster_programming_tpu.serving.queue import OK
    from cuda_mpi_gpu_cluster_programming_tpu.serving.server import (
        InferenceServer,
        ServeConfig,
    )

    jp = tmp_path / "serve.jsonl"
    m = dataclasses.replace(BLOCKS12, in_height=63, in_width=63)
    scfg = ServeConfig(
        config="v2.2_sharded", n_shards=2, max_batch=4, supervise=True,
        journal_path=str(jp), model_cfg=m,
    )
    saved = os.environ.get(chaos.CHAOS_ENV)
    os.environ[chaos.CHAOS_ENV] = "seed=3,device_loss=1"
    chaos.reset()
    try:
        srv = InferenceServer(scfg)
        tr = Tracer(journal=srv.journal, seed=1)
        set_tracer(tr)
        handles = [
            srv.submit(np.full((1, 63, 63, 3), 1.0 + 0.01 * i, np.float32))
            for i in range(4)
        ]
        srv.run_until_drained()
    finally:
        set_tracer(None)
        if saved is None:
            os.environ.pop(chaos.CHAOS_ENV, None)
        else:
            os.environ[chaos.CHAOS_ENV] = saved
        chaos.reset()
    assert [h.status for h in handles] == [OK] * 4
    assert [t.kind for t in srv.sup.trips] == ["device_loss"]
    recs = Journal.load(jp)
    spans = {r["span_id"]: r for r in recs if r["kind"] == "span"}

    def descendants(sid):
        out = []
        for r in spans.values():
            if r["parent_id"] == sid:
                out.append(r["name"])
                out.extend(descendants(r["span_id"]))
        return out

    trips = [r for r in spans.values() if r["name"] == "sup.trip"]
    assert len(trips) == 1
    desc = descendants(trips[0]["span_id"])
    for required in ("sup.degrade", "serve.rewarm", "sup.reshard", "sup.replay"):
        assert required in desc, (required, desc)
    # the trip journal record carries the trip span's ids
    trip_rec = next(r for r in recs if r["kind"] == "sup_trip")
    assert trip_rec["trace_id"] == tr.trace_id
    assert trip_rec["span_id"] == trips[0]["span_id"]
    # per-request queue-wait + dispatch spans share the trace id with
    # their serve_batch records, which point at their dispatch span
    batches = [r for r in recs if r["kind"] == "serve_batch"]
    assert batches and all(r["trace_id"] == tr.trace_id for r in batches)
    dispatch_ids = {
        r["span_id"] for r in spans.values() if r["name"] == "serve.dispatch"
    }
    assert all(r["span_id"] in dispatch_ids for r in batches)
    assert sum(
        1 for r in spans.values() if r["name"] == "serve.queue_wait"
    ) == 4
    # and the whole journal exports into a valid nested timeline
    out = tmp_path / "trace.json"
    export_trace(jp, out)
    _validate_nesting(json.loads(out.read_text()))


def test_supervised_train_steps_journal_carries_trace(tmp_path):
    """train.py --supervise-steps installs a tracer over the work-dir
    journal: step records carry the trace id and the Trace: line is
    machine-parseable."""
    work = tmp_path / "work"
    proc = subprocess.run(
        [
            sys.executable, "-m", "cuda_mpi_gpu_cluster_programming_tpu.train",
            "--steps", "2", "--batch", "2", "--height", "35", "--width", "35",
            "--checkpoint-every", "2", "--supervise-steps",
            "--work-dir", str(work),
        ],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env={
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        },
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    trace_line = next(
        l for l in proc.stdout.splitlines() if l.startswith("Trace: ")
    )
    trace_id = trace_line.split("id=")[1].split()[0]
    recs = Journal.load(work / "journal.jsonl")
    steps = [r for r in recs if r["kind"] == "step"]
    assert steps and all(r.get("trace_id") == trace_id for r in steps)
    assert any(
        r["kind"] == "span" and r["name"] == "train.step" for r in recs
    )


def test_tune_sweep_emits_candidate_spans(tmp_path):
    """The autotuner under a tracer records one span per timed candidate
    (with its measured ms) and one per layer sweep."""
    from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import BLOCKS12
    from cuda_mpi_gpu_cluster_programming_tpu.tuning.autotune import (
        autotune_model,
    )

    cfg = dataclasses.replace(BLOCKS12, in_height=63, in_width=63)
    calls = []

    def fake_timer(g, v, dtype, batch, repeats, warmup):
        calls.append(v)
        return 1.0 + 0.1 * len(calls), 0.01, 3

    jp = tmp_path / "tune.jsonl"
    tr = Tracer(journal=Journal(jp), seed=0)
    set_tracer(tr)
    try:
        autotune_model(
            cfg, dtype="fp32", batch=2, timer=fake_timer,
            log=lambda s: None, device_kind="cpu-test",
        )
    finally:
        set_tracer(None)
    spans = [r for r in Journal.load(jp) if r["kind"] == "span"]
    layers = [r for r in spans if r["name"] == "tune.layer"]
    cands = [r for r in spans if r["name"] == "tune.candidate"]
    assert len(layers) == 2  # conv1, conv2 tuning units
    assert len(cands) == len(calls) and len(cands) > 0
    assert all(r["attrs"]["ms"] > 0 for r in cands)
    layer_ids = {r["span_id"] for r in layers}
    assert all(r["parent_id"] in layer_ids for r in cands)
