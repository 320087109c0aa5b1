"""Autopilot closed-loop controller tests (ISSUE 18, docs/SERVING.md
"Autopilot").

The contract under test: the controller folds the PR 15 error-budget
math incrementally over the live outcome stream, walks a fixed pressure
ladder (shed bulk -> shed batch -> narrow buckets -> dtype downshift /
supervised degrade) only when the protected class burns or the queue
wait nears the saturation knee, journals EVERY transition as a
``controller_action`` record carrying its triggering evidence, and is
hysteresis-bounded (cooldown between actions, min-dwell before
de-escalating). No silent actuation: the dtype rung only fires after a
journaled ToleranceGate pass, refusals are journaled and the rung is
blocked, every action is reversible and every reversal journaled.

The acceptance halves: a saturating drill (bulk shed FIRST, interactive
never tightened, accounting closed) and the ``replay --controller
on|off`` A/B over one recorded saturating trace (books closed both
ways, actions journaled with evidence on the on side, protected-class
burn strictly lower with the controller on, calm trace => zero
actions).
"""

import dataclasses
import types
from pathlib import Path

import pytest

from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import BLOCKS12
from cuda_mpi_gpu_cluster_programming_tpu.observability.health import (
    health_from_journal,
)
from cuda_mpi_gpu_cluster_programming_tpu.observability.replay import (
    ReplayKnobs,
    load_recorded_run,
    replay_recorded,
)
from cuda_mpi_gpu_cluster_programming_tpu.resilience import chaos
from cuda_mpi_gpu_cluster_programming_tpu.resilience.journal import Journal
from cuda_mpi_gpu_cluster_programming_tpu.serving.controller import (
    AutopilotController,
    ControllerConfig,
)
from cuda_mpi_gpu_cluster_programming_tpu.serving.loadgen import (
    run_shaped_load,
    saturating_rate,
)
from cuda_mpi_gpu_cluster_programming_tpu.serving.server import (
    InferenceServer,
    ServeConfig,
)
from cuda_mpi_gpu_cluster_programming_tpu.serving.traffic import (
    default_class_mix,
    slo_policy,
)

ROOT = Path(__file__).resolve().parent.parent

CFG = dataclasses.replace(BLOCKS12, in_height=63, in_width=63)

# Unit-drill knobs: no eval throttle (every evaluate() call decides),
# explicit cooldown/dwell driven through evaluate(now=...) injection,
# a small trusted-burn window, and ONLY the admission rungs enabled so
# the pure-policy drills never touch a compiled forward.
UNIT = ControllerConfig(
    eval_s=0.0,
    window=16,
    min_completed=5,
    cooldown_s=1.0,
    min_dwell_s=2.0,
    enable_buckets=False,
    enable_dtype=False,
    enable_degrade=False,
)

# CI-cadence controller for the live drills: production thresholds and
# ladder, dwell/cooldown shrunk to sub-second load windows.
SNAPPY = ControllerConfig(
    eval_s=0.05, cooldown_s=0.2, min_dwell_s=0.3, min_completed=10
)


@pytest.fixture(autouse=True)
def _chaos_off(monkeypatch):
    monkeypatch.delenv(chaos.CHAOS_ENV, raising=False)
    chaos.reset()
    yield
    chaos.reset()


def _server(tmp_path, name, *, controller, slo=True, **kw):
    mix = list(default_class_mix([1, 2, 4]))
    scfg = ServeConfig(
        config=kw.pop("config", "v1_jit"),
        max_batch=kw.pop("max_batch", 4),
        journal_path=str(tmp_path / name),
        model_cfg=CFG,
        default_deadline_s=30.0,
        slo=slo_policy(mix) if slo else None,
        controller=controller,
        **kw,
    )
    return InferenceServer(scfg), mix


def _actions(journal_path):
    return [
        r for r in Journal.load(journal_path)
        if r["kind"] == "controller_action"
    ]


def _feed(ctl, cls, n, late):
    slo_ms = ctl.base_slo.class_for(cls).slo_ms
    for _ in range(n):
        ctl.note_ok(cls, slo_ms * (2.0 if late else 0.1))


# --------------------------------------------------------- unit drills ---


def test_inert_without_slo_policy(tmp_path):
    """No SLO policy => no burn, no knee: the controller never journals
    and never actuates, by design (docs/SERVING.md 'Autopilot')."""
    srv, _ = _server(tmp_path, "j.jsonl", controller=UNIT, slo=False)
    ctl = srv.controller
    assert ctl is not None and ctl.base_slo is None
    ctl.note_shed("interactive")
    assert ctl.evaluate(now=100.0) is None
    assert ctl.mode == "steady" and _actions(srv.cfg.journal_path) == []


def test_no_action_below_threshold(tmp_path):
    """A healthy signal fold (burn 0, empty queue) never actuates — the
    calm-path half of the acceptance contract."""
    srv, _ = _server(tmp_path, "j.jsonl", controller=UNIT)
    ctl = srv.controller
    _feed(ctl, "interactive", 16, late=False)
    for t in (100.0, 101.0, 102.0):
        assert ctl.evaluate(now=t) is None
    assert ctl.mode == "steady" and _actions(srv.cfg.journal_path) == []


def test_untrusted_window_does_not_actuate(tmp_path):
    """Fewer than min_completed outcomes => burn is None (noise must not
    actuate), even when every one of them violated."""
    srv, _ = _server(tmp_path, "j.jsonl", controller=UNIT)
    ctl = srv.controller
    _feed(ctl, "interactive", UNIT.min_completed - 1, late=True)
    assert ctl.burn("interactive") is None
    assert ctl.evaluate(now=100.0) is None
    assert _actions(srv.cfg.journal_path) == []


def test_escalation_sheds_bulk_first_with_journaled_evidence(tmp_path):
    """Protected-class burn >= burn_high escalates rung 1: bulk admission
    tightens to the protected class's SLO budget on the queue's pop-time
    path — base policy untouched — and the journaled record carries the
    full triggering evidence."""
    srv, _ = _server(tmp_path, "j.jsonl", controller=UNIT)
    ctl = srv.controller
    _feed(ctl, "interactive", 8, late=True)
    rec = ctl.evaluate(now=100.0)
    assert rec is not None
    assert rec["action"] == "tighten_admission" and rec["target"] == "bulk"
    assert rec["actuated"] is True and rec["reversal"] is False
    assert rec["level"] == 1 and ctl.mode == "degraded"
    # the live policy moved; the base (product) policy did not. The
    # tightened cut lands BELOW the protected budget (tighten_factor) —
    # at an equal cut the shared queue wait sheds everyone alike and
    # the protected class gains nothing.
    protected_slo = ctl.base_slo.class_for("interactive").slo_ms
    tightened_cut = protected_slo * UNIT.tighten_factor
    assert srv.queue.slo.class_for("bulk").shed_cut_ms == tightened_cut
    assert ctl.base_slo.class_for("bulk").shed_cut_ms == 0.0
    assert srv.queue.slo.class_for("interactive").slo_ms == protected_slo
    # evidence: the signals, the thresholds they crossed, the hysteresis
    ev = rec["evidence"]
    assert ev["burn"]["interactive"] >= ev["burn_high"]
    for k in ("oldest_wait_ms", "depth", "knee_frac", "cooldown_s",
              "min_dwell_s", "completed"):
        assert k in ev
    recs = _actions(srv.cfg.journal_path)
    assert len(recs) == 1 and recs[0]["action"] == "tighten_admission"
    assert recs[0]["evidence"]["burn"]["interactive"] == ev["burn"]["interactive"]


def test_cooldown_blocks_flapping(tmp_path):
    """A still-hot signal inside cooldown_s does NOT stack a second rung;
    after the cooldown it does (batch — the shed order is bulk first)."""
    srv, _ = _server(tmp_path, "j.jsonl", controller=UNIT)
    ctl = srv.controller
    _feed(ctl, "interactive", 8, late=True)
    assert ctl.evaluate(now=100.0)["target"] == "bulk"
    assert ctl.evaluate(now=100.5) is None  # cooling
    rec = ctl.evaluate(now=101.2)
    assert rec["action"] == "tighten_admission" and rec["target"] == "batch"
    assert rec["evidence"]["since_last_action_s"] == pytest.approx(1.2)
    assert ctl.level == 2


def test_min_dwell_blocks_immediate_deescalate_and_reversal_journaled(
    tmp_path,
):
    """Recovery reverses LIFO — but only after min_dwell_s at the level,
    and the reversal is journaled like any action."""
    srv, _ = _server(tmp_path, "j.jsonl", controller=UNIT)
    ctl = srv.controller
    base = srv.queue.slo
    _feed(ctl, "interactive", 8, late=True)
    assert ctl.evaluate(now=100.0) is not None
    _feed(ctl, "interactive", 16, late=False)  # flush the window clean
    assert ctl.burn("interactive") == 0.0
    assert ctl.evaluate(now=101.2) is None  # cooled, but not dwelled
    rec = ctl.evaluate(now=102.5)
    assert rec["action"] == "relax_admission" and rec["reversal"] is True
    assert rec["actuated"] is True and rec["target"] == "bulk"
    assert rec["evidence"]["dwell_s"] == pytest.approx(2.5)
    assert ctl.mode == "steady" and ctl.level == 0
    assert srv.queue.slo is base  # the exact pre-action policy object
    kinds = [(r["action"], r["reversal"]) for r in _actions(srv.cfg.journal_path)]
    assert kinds == [("tighten_admission", False), ("relax_admission", True)]


def test_knee_trigger_without_burn(tmp_path):
    """The queue-wait knee escalates BEFORE any SLO is blown — the
    early-warning half of the trigger (oldest_wait vs the tightest shed
    cut), independent of the burn windows."""
    srv, _ = _server(tmp_path, "j.jsonl", controller=UNIT)
    ctl = srv.controller
    knee = min(
        c.shed_cut_ms for c in ctl.base_slo.classes.values() if c.shed_cut_ms
    )
    stats = srv.queue.stats()
    srv.queue.stats = lambda: dataclasses.replace(
        stats, depth=9, oldest_wait_ms=0.9 * knee
    )
    rec = ctl.evaluate(now=100.0)
    assert rec is not None and rec["action"] == "tighten_admission"
    assert rec["evidence"]["oldest_wait_ms"] == pytest.approx(0.9 * knee)
    assert rec["evidence"]["knee_ms"] == knee


def test_gate_refused_downshift_is_journaled_and_blocked(tmp_path, monkeypatch):
    """No silent dtype adoption: a failed ToleranceGate screen journals
    ``downshift_refused`` (actuated=False, cause from the gate), the
    compute is untouched, and the rung is blocked — never retried
    blind."""
    ctl_cfg = dataclasses.replace(
        UNIT, enable_admission=False, enable_dtype=True
    )
    srv, _ = _server(tmp_path, "j.jsonl", controller=ctl_cfg, compute="bf16")
    ctl = srv.controller
    monkeypatch.setattr(
        AutopilotController,
        "_screen_dtype",
        lambda self, compute: types.SimpleNamespace(
            passed=False, margin=float("-inf"), reason=lambda: "stub fail"
        ),
    )
    _feed(ctl, "interactive", 8, late=True)
    # a refusal is journaled but never RETURNED: evaluate only returns
    # actuations, and the ladder had nothing else to try
    assert ctl.evaluate(now=100.0) is None
    recs = _actions(srv.cfg.journal_path)
    assert len(recs) == 1
    rec = recs[0]
    assert rec["action"] == "downshift_refused" and rec["actuated"] is False
    assert "gate refused" in rec["cause"]
    assert srv.current_compute == "bf16" and ctl.mode == "steady"
    # blocked: the still-hot signal finds no rung left — exactly one
    # refusal in the journal, no second attempt
    assert ctl.evaluate(now=105.0) is None
    assert [r["action"] for r in _actions(srv.cfg.journal_path)] == [
        "downshift_refused"
    ]


def test_real_gate_downshift_and_upshift_roundtrip(tmp_path):
    """The dtype rung end to end on a real (unstarted) server: a REAL
    ToleranceGate screen passes (gate_pass journaled under the
    controller's key), the forward rebuilds at int8w and re-warms, and
    the recovery reversal restores the configured compute."""
    ctl_cfg = dataclasses.replace(
        UNIT, enable_admission=False, enable_dtype=True
    )
    srv, _ = _server(tmp_path, "j.jsonl", controller=ctl_cfg)
    srv._ensure_built()
    srv.warmup()
    ctl = srv.controller
    _feed(ctl, "interactive", 8, late=True)
    rec = ctl.evaluate(now=100.0)
    assert rec is not None
    assert rec["action"] == "downshift_dtype" and rec["actuated"] is True
    assert srv.current_compute == "int8w"
    assert srv.cfg.compute == "fp32"  # config untouched: it's an override
    _feed(ctl, "interactive", 16, late=False)
    rev = ctl.evaluate(now=103.0)
    assert rev["action"] == "upshift_dtype" and rev["reversal"] is True
    assert srv.current_compute == "fp32"
    kinds = [r["kind"] for r in Journal.load(srv.cfg.journal_path)]
    assert "gate_pass" in kinds  # the screen's own journal trail
    rewarms = [
        r for r in Journal.load(srv.cfg.journal_path)
        if r["kind"] == "serve_rewarm"
    ]
    assert len(rewarms) == 2  # downshift + upshift each re-warmed


def test_supervised_degrade_and_promote_rung(tmp_path):
    """On a supervised server the capacity rung degrades through the
    Supervisor ladder as a journaled capacity DECISION (cause
    ``requested:``), and the reversal is the sentinel-verified explicit
    promotion."""
    ctl_cfg = dataclasses.replace(
        UNIT, enable_admission=False, enable_degrade=True
    )
    srv, _ = _server(
        tmp_path, "j.jsonl", controller=ctl_cfg,
        config="v2.2_sharded", n_shards=2, supervise=True,
    )
    srv._ensure_built()
    ctl = srv.controller
    entry0 = srv.sup.entry.key
    _feed(ctl, "interactive", 8, late=True)
    rec = ctl.evaluate(now=100.0)
    assert rec is not None
    assert rec["action"] == "degrade_capacity" and rec["actuated"] is True
    assert rec["frm"] == entry0 and rec["to"] == srv.sup.entry.key
    assert srv.sup.entry.key != entry0
    degrades = [
        r for r in Journal.load(srv.cfg.journal_path)
        if r["kind"] == "sup_degrade"
    ]
    assert degrades and degrades[-1]["cause"].startswith("requested:")
    _feed(ctl, "interactive", 16, late=False)
    rev = ctl.evaluate(now=103.0)
    assert rev["action"] == "promote_capacity" and rev["reversal"] is True
    assert srv.sup.entry.key == entry0


def test_bucket_narrow_and_widen_rewarm(tmp_path):
    """The bucket rung drops the widest bucket and the reversal re-warms
    it before it can compile on the request path."""
    ctl_cfg = dataclasses.replace(
        UNIT, enable_admission=False, enable_buckets=True
    )
    srv, _ = _server(tmp_path, "j.jsonl", controller=ctl_cfg)
    srv._ensure_built()
    srv.warmup()
    ctl = srv.controller
    assert srv.buckets == (1, 2, 4)
    _feed(ctl, "interactive", 8, late=True)
    rec = ctl.evaluate(now=100.0)
    assert rec["action"] == "narrow_buckets" and srv.buckets == (1, 2)
    _feed(ctl, "interactive", 16, late=False)
    rev = ctl.evaluate(now=103.0)
    assert rev["action"] == "widen_buckets" and srv.buckets == (1, 2, 4)
    assert 4 in srv._warmed  # re-warmed on widen, not lazily


def test_controller_config_roundtrip_and_state_obj(tmp_path):
    """ControllerConfig round-trips through to_obj/from_obj (the
    serve_config record replay rebuilds from; unknown keys ignored), and
    state_obj carries what /healthz exposes."""
    cfg = ControllerConfig(burn_high=2.0, shed_order=("batch",))
    obj = cfg.to_obj()
    assert ControllerConfig.from_obj({**obj, "novel_knob": 1}) == cfg
    srv, _ = _server(tmp_path, "j.jsonl", controller=UNIT)
    srv._ensure_built()  # writes the serve_config header
    ctl = srv.controller
    _feed(ctl, "interactive", 8, late=True)
    ctl.evaluate(now=100.0)
    st = ctl.state_obj(now=101.0)
    assert st["mode"] == "degraded" and st["level"] == 1
    assert st["overrides"] == [
        {"action": "tighten_admission", "target": "bulk"}
    ]
    assert st["last_action"]["action"] == "tighten_admission"
    assert st["last_action"]["age_s"] == pytest.approx(1.0)
    assert st["actions"] == {"tighten_admission": 1}
    # the serve_config header carries the controller knobs for replay
    hdr = next(
        r for r in Journal.load(srv.cfg.journal_path)
        if r["kind"] == "serve_config"
    )
    assert hdr["controller"]["burn_high"] == UNIT.burn_high


# ------------------------------------------------- acceptance: live drill ---


@pytest.fixture(scope="module")
def sat_rate(tmp_path_factory):
    """The saturating request rate for the live drill and the A/B
    recording, derived from a short SATURATED, SLO-free capacity probe
    (loadgen.saturating_rate). A fixed rate flakes on hosts whose speed
    varies 3x: too low and nothing burns (vacuous drill), too high and
    both A/B sides peg at the burn cap — the usable regime
    oversubscribes ~1.5x while the protected class alone still fits."""
    jp = tmp_path_factory.mktemp("autopilot_probe") / "probe.jsonl"
    mix = list(default_class_mix([1, 2, 4]))
    scfg = ServeConfig(
        config="v1_jit",
        max_batch=4,
        journal_path=str(jp),
        model_cfg=CFG,
        default_deadline_s=30.0,
    )
    srv = InferenceServer(scfg)
    srv.start()
    try:
        run_shaped_load(
            srv, shape="steady", rate_rps=2000.0, duration_s=0.3,
            classes=mix, seed=0,
        )
    finally:
        srv.stop()
    return saturating_rate(str(jp), mix)


@pytest.fixture(scope="module")
def saturating_drill(tmp_path_factory, sat_rate):
    """One saturating controller-ON run: rate past the probed 63x63 CPU
    capacity with SLOs scaled tight, so the ladder demonstrably walks."""
    jp = tmp_path_factory.mktemp("autopilot") / "drill.jsonl"
    mix = list(default_class_mix([1, 2, 4]))
    scfg = ServeConfig(
        config="v1_jit",
        max_batch=4,
        journal_path=str(jp),
        model_cfg=CFG,
        default_deadline_s=30.0,
        slo=slo_policy(mix).scaled(0.15),
        controller=SNAPPY,
    )
    srv = InferenceServer(scfg)
    srv.start()
    try:
        report = run_shaped_load(
            srv, shape="steady", rate_rps=sat_rate, duration_s=1.2,
            classes=mix, seed=0,
        )
    finally:
        srv.stop()
    return jp, report, srv.controller.state_obj()


def test_saturating_drill_bulk_shed_first_interactive_preserved(
    saturating_drill,
):
    """The live acceptance drill: the controller acts (journaled, with
    evidence), bulk is tightened before anything else, the protected
    class's admission is NEVER tightened, and per-class accounting
    closes despite the actuation."""
    jp, report, state = saturating_drill
    recs = _actions(jp)
    actuated = [r for r in recs if r["actuated"]]
    assert actuated, "saturating drill journaled no controller actions"
    assert actuated[0]["action"] == "tighten_admission"
    assert actuated[0]["target"] == "bulk"
    assert all(
        r["target"] != "interactive"
        for r in recs
        if r["action"] == "tighten_admission"
    )
    for r in recs:
        ev = r["evidence"]
        assert "burn" in ev and "oldest_wait_ms" in ev and "depth" in ev
    assert report.closed  # every class: offered == ok+shed+failed+rejected
    assert state["actions"] and sum(state["actions"].values()) == len(recs)


def test_health_report_counts_controller_actions(saturating_drill):
    """ISSUE 18 satellite: the fleet-health fold counts controller
    actions and splits protected-class burn at the first actuation (the
    did-it-help attribution); --fail-on-budget-burn semantics ride the
    same classes as before."""
    jp, _, _ = saturating_drill
    rep = health_from_journal(jp)
    ctl = rep.controller
    assert ctl["total"] == len(_actions(jp)) and ctl["actions"]
    assert "burn_after" in ctl
    assert "controller" in rep.to_obj()
    assert any("Autopilot" in ln for ln in rep.render().splitlines())


def test_health_report_without_controller_records_unchanged(tmp_path):
    """Old-journal pin: a journal with no controller_action records folds
    into a HealthReport whose to_obj has NO controller key — pre-ISSUE-18
    tooling sees an unchanged schema."""
    jp = tmp_path / "old.jsonl"
    j = Journal(jp)
    j.append("serve_config", key="config", config="v1_jit", n_shards=1,
             max_batch=4, buckets=[1, 2, 4])
    j.append("serve_batch", key="batch:0", bucket=2, batch_ms=3.0,
             req_lat_ms={"r1": 4.0})
    rep = health_from_journal(jp)
    assert rep.controller == {} and "controller" not in rep.to_obj()


# -------------------------------------------------- acceptance: replay A/B ---


@pytest.fixture(scope="module")
def recorded_saturating(tmp_path_factory, sat_rate):
    """A controller-OFF saturating recording — the trace both replay
    sides re-drive."""
    jp = tmp_path_factory.mktemp("autopilot_ab") / "recorded.jsonl"
    mix = list(default_class_mix([1, 2, 4]))
    scfg = ServeConfig(
        config="v1_jit",
        max_batch=4,
        journal_path=str(jp),
        model_cfg=CFG,
        default_deadline_s=30.0,
        slo=slo_policy(mix),
    )
    srv = InferenceServer(scfg)
    srv.start()
    try:
        run_shaped_load(
            srv, shape="steady", rate_rps=sat_rate, duration_s=1.2,
            classes=mix, seed=0,
        )
    finally:
        srv.stop()
    return jp


def test_replay_ab_controller_lowers_protected_burn(
    recorded_saturating, tmp_path
):
    """THE tier-1 A/B gate: one recorded saturating trace re-driven with
    ``--controller off`` then ``--controller on`` under equal SLO
    pressure. Both sides close per-class accounting and report no
    divergence; the on side journals actions with evidence and lands a
    STRICTLY lower protected-class error-budget burn."""
    recorded = load_recorded_run(recorded_saturating)
    reports = {}
    for mode in ("off", "on"):
        reports[mode] = replay_recorded(
            recorded,
            ReplayKnobs(
                controller=mode,
                controller_cfg=SNAPPY.to_obj(),
                slo_scale=0.15,
                journal_path=str(tmp_path / f"replay_{mode}.jsonl"),
            ),
        )
    off, on = reports["off"], reports["on"]
    for rep in (off, on):
        assert rep.accounting_closed and not rep.diverged
    assert not off.controller_active and on.controller_active
    on_actions = _actions(on.journal_path)
    assert on_actions and any(r["actuated"] for r in on_actions)
    assert all("evidence" in r for r in on_actions)
    assert _actions(off.journal_path) == []

    def burn(path):
        for c in health_from_journal(path).classes:
            if c.name == SNAPPY.protected_cls:
                return c.burn
        return None

    b_off, b_on = burn(off.journal_path), burn(on.journal_path)
    assert b_off is not None and b_on is not None
    assert b_on < b_off, f"controller on did not help: {b_on} vs {b_off}"
    # the on-side replay report carries the controller state
    assert on.to_obj()["controller_state"]["actions"]


def test_calm_trace_replays_with_zero_actions(tmp_path):
    """Calm-path acceptance: a controller-ON recording far below capacity
    journals ZERO actions, and replaying it as-recorded (controller
    rebuilt from the serve_config header) also journals zero actions and
    never reports divergence."""
    jp = tmp_path / "calm.jsonl"
    mix = list(default_class_mix([1, 2, 4]))
    scfg = ServeConfig(
        config="v1_jit",
        max_batch=4,
        journal_path=str(jp),
        model_cfg=CFG,
        default_deadline_s=30.0,
        slo=slo_policy(mix),
        controller=SNAPPY,
    )
    srv = InferenceServer(scfg)
    srv.start()
    try:
        report = run_shaped_load(
            srv, shape="steady", rate_rps=10.0, duration_s=0.6,
            classes=mix, seed=0,
        )
    finally:
        srv.stop()
    assert report.closed and _actions(jp) == []
    assert srv.controller.state_obj()["mode"] == "steady"
    rep = replay_recorded(
        load_recorded_run(jp),
        ReplayKnobs(journal_path=str(tmp_path / "calm_replay.jsonl")),
    )
    assert rep.controller_active  # rebuilt from the recorded header
    assert rep.controller_state["mode"] == "steady"
    assert _actions(rep.journal_path) == []
    assert rep.accounting_closed and not rep.diverged
