"""Stitch spans + journal records into one Chrome trace-event timeline.

``python -m cuda_mpi_gpu_cluster_programming_tpu.observability export
--journal <dir|file.jsonl>`` reads every journal in sight and writes a
Perfetto-loadable JSON object (the Chrome trace-event format:
``{"traceEvents": [...]}``, ``ts``/``dur`` in microseconds) so a
``run --serve`` session, a supervised training run, or a tuning sweep
opens in https://ui.perfetto.dev as one correlated timeline:

- ``kind="span"`` records (``observability.trace``) become complete
  ("X") events; nesting comes from their shared monotonic clock, and a
  greedy lane assigner splits genuinely-overlapping spans (concurrent
  queue waits) onto separate tids so Perfetto never renders a
  mis-nested slice.
- journal records carrying a ``span_id`` (the correlation fields the
  wired call sites merge in) become instant events pinned to their
  span's lane at the span's end — the ``serve_batch`` row sits ON its
  dispatch span.
- uncorrelated records (old journals, other processes) land on a
  synthetic per-kind timeline ordered by append index; records with a
  duration field (``batch_ms``/``ms``) still render as slices, so even
  a pre-observability journal produces a readable trace.

Process rows group by subsystem (span-name prefix / record kind):
serving, supervisor, tuning, train, journal. ``M`` metadata events name
every pid/tid.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Tuple

from ..resilience.journal import Journal, atomic_write_text

# Subsystem -> pid. Span names are namespaced "<subsystem>.<what>"; journal
# record kinds map via _KIND_PID below.
_PIDS = {
    "run": 1,
    "serve": 2,
    "sup": 3,
    "tune": 4,
    "train": 5,
    "stages": 6,
    "journal": 7,
    # Folded incidents (ISSUE 15, observability.health): each trip /
    # grow-back renders as a parent slice whose per-phase children tile
    # it end to end — the MTTR decomposition drawn to scale.
    "incident": 8,
    # Fleet router tier (ISSUE 16, serving.router): route verdicts,
    # journaled redirects, and backend state transitions on their own
    # lane — stitched beside each backend's serve lane when the shared
    # journal DIRECTORY is exported.
    "router": 9,
    # Autopilot lane (ISSUE 18, serving.controller): every closed-loop
    # action/reversal/refusal renders as its own slice with the full
    # triggering evidence as args — the timeline shows WHY the serve
    # lane's behavior changed mid-run.
    "controller": 10,
    # Fleet control plane (ISSUE 20, serving.fleet_controller): the
    # router's per-probe scrapes (rung/burn/depth per backend) plus
    # every cross-backend arbitration — token grants/refusals, drains,
    # readmits, forecast pre-shedding — on one lane, so the timeline
    # shows WHY a backend stopped receiving traffic before it ever
    # went unhealthy.
    "fleet": 11,
}
_KIND_PID = {
    "serve_batch": "serve", "serve_shed": "serve", "serve_fail": "serve",
    "serve_miss": "serve", "serve_warm": "serve", "serve_rewarm": "serve",
    # Live resource telemetry (ISSUE 13, docs/OBSERVABILITY.md "Roofline
    # attribution"): periodic queue-saturation gauges and device-memory
    # snapshots render as Perfetto COUNTER tracks (ph "C") on the serve
    # lane — see _COUNTER_KINDS. Old journals without them export
    # unchanged.
    "serve_gauges": "serve", "mem_snapshot": "serve",
    # Network front end records (ISSUE 11, docs/SERVING.md "Network front
    # end & SLOs") land on the serve lane: one serve_transport per HTTP
    # exchange (span-correlated when traced — it pins ONTO its
    # serve.transport span), one serve_reject per 429/413 refusal. Old
    # journals without them export unchanged.
    "serve_transport": "serve", "serve_reject": "serve",
    # Replay-schema records (ISSUE 12, docs/OBSERVABILITY.md "Replay"):
    # the run-conditions header and the per-request
    # arrival records land on the serve lane as instants, so an exported
    # timeline shows the offered schedule beside its dispatches. Old
    # journals without them export unchanged.
    "serve_config": "serve", "serve_submit": "serve",
    "sup_build": "sup", "sup_trip": "sup", "sup_degrade": "sup",
    "sup_ok": "sup", "sup_warm": "sup", "sup_reshard": "sup",
    "sup_replay": "sup", "sup_step": "sup", "mesh_shrink": "sup",
    # Grow-back records (ISSUE 10, docs/RESILIENCE.md "Grow-back &
    # hysteresis") land on the same incident lane as the trip/degrade
    # family, so one timeline reads trip -> degrade -> heal -> probation ->
    # promote end to end. Old journals without them export unchanged.
    "mesh_probation": "sup", "mesh_quarantine": "sup",
    "sup_promote": "sup", "sup_promote_refused": "sup",
    # Compile-cost records (ISSUE 15, observability.health): every XLA
    # compile renders as a slice on the supervisor lane (correlated ones
    # pin inside the warmup/rewarm span that paid for them, on a
    # "compile" sub-lane). Old journals without them export unchanged.
    "compile_event": "sup",
    # Fleet router records (ISSUE 16, docs/SERVING.md "Fleet router"):
    # one router_route per northbound request (its ms renders as a
    # slice), instants for redirects/backend state transitions, and the
    # config header. Old journals without them export unchanged.
    "router_config": "router", "router_route": "router",
    "router_redirect": "router", "router_backend_state": "router",
    # Autopilot records (ISSUE 18, docs/SERVING.md "Autopilot"): one
    # controller_action per ladder transition (escalation, reversal, or
    # journaled refusal), its actuation wall ms as the slice duration
    # and its evidence (signals + thresholds + hysteresis state) riding
    # as args. Old journals without them export unchanged.
    "controller_action": "controller",
    # Fleet control records (ISSUE 20, docs/SERVING.md "Fleet control
    # plane"): one fleet_action per arbitration (its actuation ms as
    # the slice), fleet_refusal/router_probe as instants with the full
    # fleet evidence as args. Old journals without them export
    # unchanged (the lane's process meta only emits when it has
    # events).
    "fleet_action": "fleet", "fleet_refusal": "fleet",
    "router_probe": "fleet",
    "gate_pass": "tune", "gate_fail": "tune",
    "step": "train", "ckpt": "train", "rollback": "train", "resume": "train",
    "wedge_detected": "journal", "recycle": "journal", "reprobe": "journal",
}
# Duration field per record kind for uncorrelated records that still carry
# a measured wall time — they render as slices, not instants.
_KIND_DUR_FIELD = {
    "serve_batch": "batch_ms",
    "serve_warm": "ms",
    "serve_rewarm": "ms",
    # An uncorrelated serve_transport (untraced run) still renders as a
    # slice — its ms is the whole HTTP exchange.
    "serve_transport": "ms",
    "sup_warm": "ms",
    # A committed promotion carries its wall ms (spot-check + reshard +
    # re-warm); a probation "pass" record carries the ms the device waited
    # — both render as slices on the incident lane.
    "sup_promote": "ms",
    "mesh_probation": "ms",
    "compile_event": "ms",
    # A routed request's full router-side wall (receive -> response).
    "router_route": "ms",
    # A controller action's actuation wall (screen + rebuild + re-warm
    # for the dtype rung; near-zero for a policy swap).
    "controller_action": "ms",
    # A fleet action's actuation wall (a drain/preshed flag flip —
    # near-zero, but the slice keeps the action/refusal vocabulary
    # uniform with the controller lane).
    "fleet_action": "ms",
}
# Gauge-bearing record kinds -> the numeric fields that become counter
# series. Each record emits one "C" (counter) event per listed field, so
# Perfetto draws queue depth / oldest wait / memory-in-use as stepped
# counter tracks beside the slices (the Chrome trace-event counter
# phase). Records missing a field simply skip that series.
_COUNTER_KINDS = {
    # ctl_level (ISSUE 20) rides the gauge record on controlled servers
    # — pre-20 records lack the field and skip the series.
    "serve_gauges": (
        "depth", "pending_images", "oldest_wait_ms", "ctl_level",
    ),
    "mem_snapshot": ("bytes_in_use", "peak_bytes_in_use"),
}


def load_records(path) -> List[dict]:
    """All journal records under ``path``: one ``.jsonl`` file, or every
    ``*.jsonl`` in a directory (sorted by name so replays are stable)."""
    p = Path(path)
    if p.is_dir():
        records: List[dict] = []
        for f in sorted(p.glob("*.jsonl")):
            records.extend(Journal.load(f))
        return records
    return Journal.load(p)


def _span_pid(name: str) -> int:
    return _PIDS.get(name.split(".", 1)[0], _PIDS["run"])


def _kind_pid(kind: str) -> int:
    return _PIDS[_KIND_PID.get(kind, "journal")]


class _Lanes:
    """Greedy interval-partitioning of slices into lanes (exported tids):
    a slice joins a lane if it nests inside the lane's innermost open
    slice or starts after everything on the lane ended. Keeps Chrome's
    same-tid containment invariant true by construction."""

    def __init__(self):
        self._lanes: List[List[float]] = []  # per lane: stack of open end-times

    def place(self, t0: float, t1: float) -> int:
        for i, stack in enumerate(self._lanes):
            while stack and stack[-1] <= t0:
                stack.pop()
            if not stack or t1 <= stack[-1]:
                stack.append(t1)
                return i
        self._lanes.append([t1])
        return len(self._lanes) - 1


def to_trace_events(records: List[dict]) -> dict:
    """Stitch journal records into ``{"traceEvents": [...]}`` (µs)."""
    spans = [r for r in records if r.get("kind") == "span"]
    others = [r for r in records if r.get("kind") != "span"]

    events: List[dict] = []
    # (pid, track-group) -> lane allocator; exported tid = stable index of
    # (pid, group, lane) so every lane gets its own named thread row.
    lanes: Dict[Tuple[int, str], _Lanes] = {}
    tid_map: Dict[Tuple[int, str, int], int] = {}
    tid_names: Dict[Tuple[int, int], str] = {}

    def _tid_for(pid: int, group: str, t0: float, t1: float) -> int:
        lane = lanes.setdefault((pid, group), _Lanes()).place(t0, t1)
        key = (pid, group, lane)
        if key not in tid_map:
            tid_map[key] = len(tid_map) + 1
            tid_names[(pid, tid_map[key])] = (
                f"{group}" + (f" [{lane}]" if lane else "")
            )
        return tid_map[key]

    # Spans: sorted by start so lane assignment sees intervals in order.
    span_loc: Dict[str, Tuple[int, int, float, float]] = {}  # sid -> pid,tid,t0,t1
    for rec in sorted(spans, key=lambda r: (r.get("t0_ms", 0.0), -r.get("dur_ms", 0.0))):
        t0 = float(rec.get("t0_ms", 0.0)) * 1e3  # ms -> µs
        dur = max(1.0, float(rec.get("dur_ms", 0.0)) * 1e3)
        pid = _span_pid(str(rec.get("name", "")))
        group = str(rec.get("track") or f"t{rec.get('tid', 0)}")
        tid = _tid_for(pid, group, t0, t0 + dur)
        args = {
            k: rec[k]
            for k in ("trace_id", "span_id", "parent_id")
            if rec.get(k)
        }
        args.update(rec.get("attrs") or {})
        events.append(
            {
                "ph": "X", "name": rec.get("name", "span"), "cat": "span",
                "ts": round(t0, 1), "dur": round(dur, 1),
                "pid": pid, "tid": tid, "args": args,
            }
        )
        if rec.get("span_id"):
            span_loc[rec["span_id"]] = (pid, tid, t0, t0 + dur)

    # Incident lane (observability.health): fold the trail into incidents
    # and draw every span-timed one as a parent slice whose per-phase
    # children tile it end to end — the MTTR decomposition to scale.
    # Span-less incidents (old/untraced journals) have no wall-clock
    # placement and are skipped; the journal otherwise exports unchanged.
    from .health import incidents_from_records

    for inc in incidents_from_records(records):
        if inc.t0_ms is None or inc.wall_ms <= 0:
            continue
        pid = _PIDS["incident"]
        p_ts = round(inc.t0_ms * 1e3, 1)
        p_end = round((inc.t0_ms + inc.wall_ms) * 1e3, 1)
        if p_end <= p_ts:
            continue
        tid = _tid_for(pid, inc.kind, p_ts, p_end)
        events.append(
            {
                "ph": "X", "name": f"incident.{inc.kind}",
                "cat": "incident", "ts": p_ts,
                "dur": round(p_end - p_ts, 1), "pid": pid, "tid": tid,
                "args": {
                    "entry": inc.entry, "cause": inc.cause,
                    "wall_ms": round(inc.wall_ms, 3),
                },
            }
        )
        cursor = p_ts
        for pname, v in inc.phases.items():
            if not v or v <= 0:
                continue
            end = min(p_end, round(cursor + v * 1e3, 1))
            dur = round(end - cursor, 1)
            if dur <= 0:
                continue
            events.append(
                {
                    "ph": "X", "name": f"phase.{pname}",
                    "cat": "incident", "ts": round(cursor, 1),
                    "dur": dur, "pid": pid, "tid": tid,
                    "args": {"ms": round(v, 3)},
                }
            )
            cursor = end

    # Journal records: correlated ones pin to their span; the rest get a
    # synthetic per-kind timeline that preserves append order.
    synth_clock: Dict[str, float] = {}
    for idx, rec in enumerate(others):
        kind = str(rec.get("kind", "record"))
        args = {k: v for k, v in rec.items() if k != "kind"}
        sid = rec.get("span_id")
        if sid and sid in span_loc:
            pid, tid, s_t0, t1 = span_loc[sid]
            ms = rec.get("ms")
            if (
                kind == "compile_event"
                and isinstance(ms, (int, float))
                and ms > 0
            ):
                # A correlated compile renders as a SLICE ending where its
                # enclosing warmup/rewarm span ends (the compile is the
                # tail of the timed first call), on a "compile" sub-lane
                # of the span's process row — several buckets compiling
                # under one rewarm span would mis-nest on the span's own
                # lane.
                dur = float(ms) * 1e3
                ts = max(s_t0, t1 - dur)
                ctid = _tid_for(pid, "compile", ts, t1)
                events.append(
                    {
                        "ph": "X", "name": kind, "cat": "journal",
                        "ts": round(ts, 1), "dur": round(t1 - ts, 1),
                        "pid": pid, "tid": ctid, "args": args,
                    }
                )
                continue
            events.append(
                {
                    "ph": "i", "name": kind, "cat": "journal",
                    "ts": round(t1, 1), "pid": pid, "tid": tid,
                    "s": "t", "args": args,
                }
            )
            continue
        pid = _kind_pid(kind)
        if kind in _COUNTER_KINDS:
            # Counter tracks: one "C" event per gauge field. The synthetic
            # append-order clock keeps the series monotonic alongside the
            # other uncorrelated records.
            t0 = max(synth_clock.get(kind, 0.0), float(idx) * 1e3)
            for field in _COUNTER_KINDS[kind]:
                v = rec.get(field)
                if isinstance(v, (int, float)):
                    events.append(
                        {
                            "ph": "C", "name": f"{kind}.{field}",
                            "cat": "journal", "ts": round(t0, 1),
                            "pid": pid, "tid": 0,
                            "args": {field: v},
                        }
                    )
            synth_clock[kind] = t0 + 1.0
            continue
        dur_field = _KIND_DUR_FIELD.get(kind)
        dur_ms = rec.get(dur_field) if dur_field else None
        t0 = max(synth_clock.get(kind, 0.0), float(idx) * 1e3)  # µs, ordered
        if isinstance(dur_ms, (int, float)) and dur_ms > 0:
            dur = float(dur_ms) * 1e3
            tid = _tid_for(pid, kind, t0, t0 + dur)
            events.append(
                {
                    "ph": "X", "name": kind, "cat": "journal",
                    "ts": round(t0, 1), "dur": round(dur, 1),
                    "pid": pid, "tid": tid, "args": args,
                }
            )
            synth_clock[kind] = t0 + dur
        else:
            tid = _tid_for(pid, kind, t0, t0 + 1.0)
            events.append(
                {
                    "ph": "i", "name": kind, "cat": "journal",
                    "ts": round(t0, 1), "pid": pid, "tid": tid,
                    "s": "t", "args": args,
                }
            )
            synth_clock[kind] = t0 + 1.0

    meta: List[dict] = []
    for name, pid in sorted(_PIDS.items(), key=lambda kv: kv[1]):
        if any(ev["pid"] == pid for ev in events):
            meta.append(
                {
                    "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                    "args": {"name": name},
                }
            )
    for (pid, tid), tname in sorted(tid_names.items()):
        meta.append(
            {
                "ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
                "args": {"name": tname},
            }
        )
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def export_trace(journal_path, out_path) -> dict:
    """Load, stitch, atomically write. Returns a summary dict (the CLI
    prints it machine-readably)."""
    records = load_records(journal_path)
    trace = to_trace_events(records)
    atomic_write_text(out_path, json.dumps(trace))
    n_spans = sum(1 for r in records if r.get("kind") == "span")
    return {
        "out": str(out_path),
        "records": len(records),
        "spans": n_spans,
        "events": len(trace["traceEvents"]),
    }

