"""Elastic supervisor: in-graph sentinel screening + degradation-ladder
re-planning for any built forward.

PR 1's ``Degrader`` walks a fallback chain at BUILD time (a tier that fails
to compile falls to the next); PR 3's ``Sentinel`` screens the host-side
training loop. Neither sees a bit flip, a diverged replica, or a lost chip
*inside* a sharded forward mid-fleet. The supervisor closes that gap:

- every ladder entry builds its forward with the in-graph digest taps
  (``with_digests=True`` — per-stage ``tree_digest`` scalars compiled
  inside the shard_map bodies of ``parallel.sharded`` /
  ``parallel.tensor_parallel``), so screening costs zero host syncs in the
  hot loop — the digests are device scalars riding beside the output;
- :meth:`Supervisor.execute` runs a batch, then screens the digest tree
  host-side via :class:`~.sentinel.StageDigests`, strictly OFF the timed
  path (:func:`~.sentinel.off_timed_path` marks it; staticcheck's
  ``host-sync-in-hot-loop`` rule enforces it);
- a trip — ``stage_digest``, ``shard_divergence``, or ``device_loss`` —
  re-plans to the next entry of the degradation ladder (fewer shards →
  replicated → single-device reference), re-executes the SAME batch on the
  new plan, and journals every transition (``sup_trip`` / ``sup_degrade``
  / ``sup_ok`` records via ``resilience.journal``), reusing PR 1's
  ``DegradedEvent`` vocabulary so harness triage needs no new grammar;
- the single-device floor builds through ``configs.build_forward`` so a
  PR 2 tuning plan keeps its env > plan > default precedence on the way
  down the ladder.

Since PR 8 the re-plan is a TRUE elastic rebuild (parallel.elastic): the
supervisor owns an :class:`~..parallel.elastic.ElasticPool`, every sharded
rung's Mesh/shard_map closures are built over the pool's SURVIVING device
set (re-queried at build time, never a cached list — staticcheck's
``stale-device-set`` rule), a ``mesh_shrink``/``device_loss`` trip
reshards live params (and, on the training path, optimizer state) onto
the new mesh via ``jax.device_put`` before the replay, and
:meth:`Supervisor.supervise_step` extends the same trip→re-plan→replay
contract from forwards to TRAINING steps — step-level replay of the same
batch (journaled ``sup_step``/``sup_replay``) instead of whole-checkpoint
rollback, with the checkpoint rollback remaining the floor
(train.py ``--supervise-steps`` / ``--max-rollbacks``).

Since PR 10 the ladder is a closed loop — degradation has an inverse
(docs/RESILIENCE.md "Grow-back & hysteresis"): :meth:`Supervisor.promote`
climbs BACK up when the pool's eligible count satisfies a higher rung
(a healed device rejoined, sat out its probation, and graduated). A
promotion rebuilds the higher rung's Mesh/shard_map closures over the
re-queried eligible set, live-reshards params (and opt-state) UP, and —
before switching — verifies the candidate rung against the CURRENT rung's
output on a sentinel input: a promotion that changes results is refused
and journaled (``sup_promote_refused``), never silently adopted. The whole
transition runs under one ``sup.recover`` span so an exported incident
timeline reads trip → degrade → heal → probation → promote end to end.
Consumers drive it between batches/steps via :meth:`maybe_promote`
(serving dispatch loop, ``train.py --supervise-steps``).

Every recovery path is drillable on CPU: ``CHAOS_SPEC="stage_sdc=1"``
corrupts a seeded stage digest before screening, ``device_loss=1`` raises
the mesh-shrink signature before the forward runs, ``mesh_shrink=k``
actually drops k seeded devices from the pool so the rebuild lands on a
genuinely smaller mesh, ``device_rejoin=k`` heals the k most recently
lost devices back through probation, and ``flap=k`` bounces ONE seeded
device through k lose→heal cycles — which must end in quarantine, never
mesh oscillation (docs/RESILIENCE.md).
"""

from __future__ import annotations

import dataclasses
import math
import random
import time
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

from ..observability.trace import current_ids, span as obs_span
from . import chaos
from .journal import Journal
from .policy import DegradationExhausted, DegradedEvent
from .sentinel import (
    SDC,
    SentinelConfig,
    StageDigests,
    off_timed_path,
    replicated_shard_spread,
)

# Mesh-shrink signatures a real device loss surfaces as (jax raises plain
# RuntimeError/ValueError quoting device counts; chaos mimics the same
# message so triage sees one grammar).
_DEVICE_LOSS_MARKERS = ("device_loss", "mesh_shrink", "devices, have", "), have ")


def _loss_kind(e: BaseException) -> str:
    """Which SDC kind a classified device-loss exception carries: an
    actual pool shrink vs. a transient single-device loss signature."""
    return "mesh_shrink" if "mesh_shrink" in str(e) else "device_loss"


@dataclasses.dataclass(frozen=True)
class LadderEntry:
    """One rung of the degradation ladder: how to build the forward."""

    strategy: str  # "halo" | "staged_halo" | "tp" | "replicated" | "single"
    tier: str = "reference"  # "reference" | "pallas"
    n_shards: int = 1

    @property
    def key(self) -> str:
        return f"{self.strategy}@{self.n_shards}:{self.tier}"


def default_ladder(strategy: str, tier: str, n_shards: int) -> List[LadderEntry]:
    """The canonical recovery ladder for a (strategy, tier, shards) point:
    the requested plan, then the same strategy at halved shard counts (a
    lost chip shrinks the mesh), then replicate-all (every device redundant
    — survives any single-shard divergence), then the single-device
    reference floor that is always buildable. Mirrors
    ``policy.tier_fallback_chain`` but over SHARD topology rather than
    config keys, which is what a mid-fleet device loss actually changes."""
    entries: List[LadderEntry] = []
    if strategy in ("halo", "staged_halo", "tp"):
        n = n_shards
        while n >= 2:
            entries.append(LadderEntry(strategy, tier, n))
            n //= 2
        if n_shards >= 2:
            entries.append(LadderEntry("replicated", "reference", n_shards))
    elif strategy == "replicated":
        entries.append(LadderEntry("replicated", "reference", max(1, n_shards)))
    elif strategy == "single":
        if tier != "reference":
            entries.append(LadderEntry("single", tier, 1))
    else:
        raise ValueError(f"no supervisor ladder for strategy {strategy!r}")
    entries.append(LadderEntry("single", "reference", 1))
    return entries


def train_ladder(sp_shards: int = 0, tp_shards: int = 0) -> List[LadderEntry]:
    """The TRAINING-step ladder: the requested sharded strategy at halved
    shard counts down to 2, then the single-device reference floor.
    ``replicated`` is an inference-only rung (every device redundantly
    running the same optimizer step buys no divergence screen the sentinel
    doesn't already provide, at N× the FLOPs), so training skips it."""
    if sp_shards and tp_shards:
        raise ValueError("sp_shards and tp_shards are mutually exclusive strategies")
    strategy = "halo" if sp_shards else ("tp" if tp_shards else "single")
    entries: List[LadderEntry] = []
    n = sp_shards or tp_shards or 1
    while n >= 2:
        entries.append(LadderEntry(strategy, "reference", n))
        n //= 2
    entries.append(LadderEntry("single", "reference", 1))
    return entries


def _is_device_loss(e: BaseException) -> bool:
    msg = str(e)
    return isinstance(e, (RuntimeError, ValueError, chaos.InjectedFault)) and any(
        m in msg for m in _DEVICE_LOSS_MARKERS
    )


@dataclasses.dataclass
class ScriptedFault:
    """One deterministically scheduled device-loss incident
    (:meth:`Supervisor.script_fault`): at supervised step ``step``, lose
    exactly ``device_ids`` from the pool (empty = transient loss, no
    topology change) and raise the device-loss signature so the ordinary
    trip machinery recovers. The replay harness builds these from a
    recorded journal's ``sup_trip``/``mesh_shrink`` records — same steps,
    same victims, no seeded re-draw that could diverge from the record."""

    step: int
    kind: str = "device_loss"  # "device_loss" | "mesh_shrink"
    device_ids: Tuple[int, ...] = ()
    cause: str = "scripted"
    fired: bool = False


class Supervisor:
    """Wrap a degradation ladder of digest-tapped forwards with trip
    handling. ``execute(params, x)`` always returns the batch's output from
    SOME rung (or raises :class:`DegradationExhausted` when every rung is
    spent); ``attempts``/``trips``/``events`` carry the incident trail the
    CLIs surface the way PR 1's resilience columns do."""

    def __init__(
        self,
        model_cfg,
        ladder: List[LadderEntry],
        *,
        plan=None,
        sentinel_cfg: SentinelConfig = SentinelConfig(),
        journal: Optional[Journal] = None,
        on_event: Optional[Callable[[DegradedEvent], None]] = None,
        on_rebuild: Optional[Callable[[LadderEntry], None]] = None,
        pool=None,
        step_builder: Optional[Callable] = None,
        site: str = "supervisor",
        promote_rtol: float = 1e-5,
    ):
        if not ladder:
            raise ValueError("Supervisor needs a non-empty ladder")
        self.model_cfg = model_cfg
        self.ladder = list(ladder)
        self.plan = plan
        self.journal = journal
        self.on_event = on_event
        # Called after a degrade lands on a freshly BUILT rung, before the
        # failed batch replays on it — the serving layer re-warms its batch
        # buckets here so even the replay hits a compiled shape and the
        # zero-cache-miss dispatch discipline survives degradation.
        self.on_rebuild = on_rebuild
        if pool is None:
            from ..parallel.elastic import ElasticPool

            pool = ElasticPool(journal=journal, site=site)
        # The surviving-device set every sharded rung builds its mesh over;
        # a mesh_shrink trip loses devices here, and an unsatisfiable rung
        # (needs more devices than survive) fails its eager build and is
        # skipped by the degrade loop.
        self.pool = pool
        # ``step_builder(entry, mesh) -> step_fn`` puts the supervisor in
        # TRAINING mode (supervise_step): step_fn has the make_train_step
        # contract (params, opt_state, x, y) -> (params', opt_state',
        # loss[, grad_norm]). See training.make_elastic_step_builder.
        self.step_builder = step_builder
        self.site = site
        # The grow-back sentinel bar: a promotion candidate whose
        # spot-check output deviates from the current rung by more than
        # this oracle-max-normalized budget is refused (shard-count
        # reduction reordering costs ~1 ulp; a broken device costs orders
        # of magnitude more).
        self.promote_rtol = float(promote_rtol)
        self.checker = StageDigests(sentinel_cfg, site=site)
        self.trips: List[SDC] = []
        self.events: List[DegradedEvent] = []
        self.attempts = 0
        self.replays = 0  # batches/steps re-run on a new rung after a trip
        self.promotions = 0  # grow-back climbs committed (maybe_promote)
        self.compile_ms: Optional[float] = None
        # Per-(rung, input shape) compile ledger: every first call of the
        # CURRENT executable at a new shape is an XLA compile and journals
        # a compile_event (observability.health); the ledger resets
        # whenever the executable does (_advance / promote), so every
        # post-trip and post-promotion recompile is measured — not just
        # the first one in the supervisor's lifetime.
        self._compiled: set = set()
        self._idx = 0
        self._fwd: Optional[Callable] = None
        self._sfn: Optional[Callable] = None
        self._step = 0
        # Promotion hysteresis floor: the pool's eligible count recorded at
        # the last degrade (or refused/committed promotion). maybe_promote
        # fires only when the eligible count GROWS past it — a transient
        # device_loss trip (pool unchanged) or a refused candidate never
        # re-promotes every batch.
        self._promote_floor_alive: Optional[int] = None
        # chaos `flap` drill state: the one seeded device being bounced and
        # the remaining lose->heal cycles / last step a transition ran.
        self._flap_cycles = 0
        self._flap_device = None
        self._flap_last_step: Optional[int] = None
        # The step whose trip is still being recovered: the chaos rejoin
        # defers past it so a heal never lands inside the same step's
        # replay (drills stay deterministic step-for-step).
        self._rejoin_blocked_step: Optional[int] = None
        # Scripted faults (observability.replay): deterministic re-drives
        # of a RECORDED incident trail — unlike the seeded chaos sites,
        # each entry names the exact step and device ids to lose, so a
        # replayed run trips where the recorded run tripped.
        self._scripted_faults: List[ScriptedFault] = []

    # ------------------------------------------------------------ building

    @property
    def entry(self) -> LadderEntry:
        return self.ladder[self._idx]

    def _journal(self, kind: str, key: str, **payload) -> None:
        if self.journal is not None:
            # Optional trace correlation (observability.trace): a trip
            # record written inside the trip span carries that span's ids;
            # untraced runs journal exactly the PR 5 schema.
            self.journal.append(kind, key=key, **{**current_ids(), **payload})

    @off_timed_path
    def _note_compile(
        self, *, shape, dtype, ms, cache_hit, fn=None, args=()
    ) -> None:
        """Journal one ``compile_event`` for the current rung (the shared
        instrumentation point — observability.health builds the payload,
        including the best-effort XLA ``cost_analysis`` probe on misses).
        Also keeps the legacy ``compile_ms`` attribute: first-ever
        compile, what run.py's one-shot ``--supervise`` path prints."""
        if not cache_hit and self.compile_ms is None:
            self.compile_ms = ms
        if self.journal is None:
            return
        from ..observability.health import compile_event

        entry = self.entry
        rec = compile_event(
            site=self.site,
            entry=entry.key,
            shape=shape,
            dtype=dtype,
            ms=ms,
            cache_hit=cache_hit,
            # Partition degree for the flops cross-check: XLA bills the
            # per-shard module on partitioned strategies; a replicated
            # rung runs the FULL pass per device.
            n_shards=(
                entry.n_shards
                if entry.strategy in ("halo", "staged_halo", "tp")
                else 1
            ),
            fn=fn,
            args=args,
        )
        self._journal(
            "compile_event",
            key=f"compile:{self.site}:{self.entry.key}:b{rec['batch']}",
            **rec,
        )

    def _entry_mesh(self, entry: LadderEntry):
        """The surviving-device mesh this rung runs on (None for the
        single floor) — built through the pool so a post-shrink rebuild
        can never route a collective through a lost device."""
        if entry.strategy == "single" or entry.n_shards < 2:
            return None
        axis = "tp" if entry.strategy == "tp" else "sp"
        return self.pool.mesh_for(entry.n_shards, axis_name=axis)

    def _build_entry(self, entry: LadderEntry) -> Callable:
        cfg = self.model_cfg
        if entry.strategy in ("halo", "staged_halo"):
            from ..parallel.sharded import build_sharded_forward

            # plan= rides into the SHARDED pallas builder too (PR 5
            # leftover closed): a degrade re-plan keeps its tuned per-layer
            # variants instead of silently reverting to defaults.
            return build_sharded_forward(
                cfg,
                entry.n_shards,
                mesh=self._entry_mesh(entry),
                tier=entry.tier,
                staged=(entry.strategy == "staged_halo"),
                with_digests=True,
                plan=self.plan,
            )
        if entry.strategy == "tp":
            from ..parallel.tensor_parallel import build_tp_forward

            return build_tp_forward(
                cfg, entry.n_shards, mesh=self._entry_mesh(entry), with_digests=True
            )
        if entry.strategy == "replicated":
            from ..parallel.replicated import build_replicated_forward

            return self._wrap_digest(
                build_replicated_forward(
                    cfg, entry.n_shards, mesh=self._entry_mesh(entry)
                )
            )
        if entry.strategy == "single":
            # Through configs.build_forward so a PR 2 TunePlan keeps its
            # env > plan > default variant precedence on the pallas floor.
            from ..configs import REGISTRY, build_forward

            key = "v3_pallas" if entry.tier == "pallas" else "v1_jit"
            return self._wrap_digest(
                build_forward(REGISTRY[key], cfg, plan=self.plan)
            )
        raise ValueError(f"unknown ladder strategy {entry.strategy!r}")

    @staticmethod
    def _wrap_digest(base: Callable) -> Callable:
        """Output-digest tap for tiers without an in-body shard_map tap."""
        import jax

        from .sentinel import tree_digest

        @jax.jit
        def fwd(p, x):
            out = base(p, x)
            return out, {"out": tree_digest(out)[None]}

        return fwd

    def fwd(self) -> Callable:
        """The current rung's compiled ``(params, x) -> (out, digests)`` —
        what a timing harness should measure (taps included, no host
        syncs). Builds lazily on first use."""
        if self._fwd is None:
            self._fwd = self._build_entry(self.entry)
            self._journal("sup_build", key=self.entry.key, entry=self.entry.key)
        return self._fwd

    def step_fn(self) -> Callable:
        """The current rung's TRAINING step (training mode only): built by
        ``step_builder(entry, mesh)`` against the surviving-device mesh,
        lazily, journaled like the forward builds."""
        if self.step_builder is None:
            raise ValueError(
                "supervise_step needs Supervisor(step_builder=...) — see "
                "training.make_elastic_step_builder"
            )
        if self._sfn is None:
            entry = self.entry
            self._sfn = self.step_builder(entry, self._entry_mesh(entry))
            self._journal("sup_build", key=f"step:{entry.key}", entry=entry.key)
        return self._sfn

    def _build_current(self) -> None:
        """Eagerly build the current rung's executable — the step in
        training mode, the forward otherwise (the degrade loop uses this
        to prove a rung buildable before landing on it)."""
        if self.step_builder is not None:
            self.step_fn()
        else:
            self.fwd()

    @off_timed_path
    def reshard(self, tree):
        """Live-reshard a pytree onto the CURRENT rung's surviving-device
        mesh (``jax.device_put`` under the replicated ``P()`` layout; the
        single floor gets a 1-device mesh over the first survivor). The
        degrade path calls this on params/opt-state so a replay never
        touches buffers homed on a lost device — and never round-trips
        through a checkpoint."""
        from ..parallel.elastic import reshard_tree

        entry = self.entry
        n = entry.n_shards if entry.strategy != "single" else 1
        with obs_span("sup.reshard", entry=entry.key, devices=self.pool.n_alive):
            mesh = self.pool.mesh_for(max(1, n))
            self._journal(
                "sup_reshard",
                key=f"reshard:{entry.key}:{self.pool.summary()}",
                entry=entry.key,
                devices=self.pool.n_alive,
            )
            return reshard_tree(tree, mesh)

    @off_timed_path
    def warm(self, params, x) -> float:
        """Compile + run the current rung on one input shape, outside the
        screened/chaos-drawn execute path (warmup must neither consume a
        drill's fault budget nor count as a screened batch). Returns the
        wall ms — first call per shape is the compile; the serving layer
        warms every batch bucket through here so dispatch never compiles.
        Journaled as ``sup_warm`` so the warmup/steady-state boundary is
        auditable in the same trail as the trips."""
        import jax

        shape = tuple(int(d) for d in x.shape)
        hit = (self.entry.key, shape) in self._compiled
        t0 = time.perf_counter()
        fwd = self.fwd()
        out, _ = fwd(params, x)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) * 1e3
        self._compiled.add((self.entry.key, shape))
        self._note_compile(
            shape=shape,
            dtype=str(x.dtype),
            ms=ms,
            cache_hit=hit,
            fn=None if hit else fwd,
            args=(params, x),
        )
        self._journal(
            "sup_warm",
            key=f"warm:{self.entry.key}:b{int(x.shape[0])}",
            entry=self.entry.key,
            batch=int(x.shape[0]),
            ms=round(ms, 3),
        )
        return ms

    # ----------------------------------------------------------- execution

    def script_fault(
        self,
        step: int,
        kind: str = "device_loss",
        device_ids: Iterable[int] = (),
        cause: str = "scripted",
    ) -> ScriptedFault:
        """Schedule a deterministic device-loss incident at supervised
        step ``step`` — the replay harness's re-drive hook
        (observability.replay, docs/OBSERVABILITY.md "Replay"). Unlike the
        seeded chaos sites this names the EXACT step
        and victim ids a recorded run lost, so a replayed journal trips
        where — and loses what — the record says it did. The fault rides
        the ordinary trip path (``_trip_and_recover``): the replay run
        journals the same ``mesh_shrink``/``sup_trip`` incident shape."""
        f = ScriptedFault(
            step=int(step), kind=kind, device_ids=tuple(device_ids), cause=cause
        )
        self._scripted_faults.append(f)
        return f

    def _maybe_scripted_fault(self, entry: LadderEntry) -> None:
        for f in self._scripted_faults:
            if f.fired or f.step != self._step:
                continue
            f.fired = True
            lost: List[int] = []
            if f.device_ids:
                alive = {d.id for d in self.pool.alive()}
                # Only ids still alive, and never the whole pool — the
                # single-device floor needs somewhere to land, same rule
                # as ElasticPool.lose itself.
                lost = [i for i in f.device_ids if i in alive]
                if len(lost) >= len(alive):
                    lost = lost[: len(alive) - 1]
                if lost:
                    self.pool.lose(lost, cause=f.cause)
            if f.kind == "mesh_shrink" and lost:
                raise chaos.InjectedFault(
                    "mesh_shrink",
                    f"scripted ({f.cause}): lost {len(lost)} device(s) "
                    f"{sorted(lost)}; entry {entry.key} mesh is stale — "
                    f"{self.pool.n_alive} of {self.pool.n_total} devices "
                    "survive",
                )
            raise chaos.InjectedFault(
                "device_loss",
                f"scripted ({f.cause}): entry {entry.key} needs "
                f"{entry.n_shards} devices, have {max(entry.n_shards - 1, 0)}",
            )

    def _maybe_chaos_device_loss(self, entry: LadderEntry) -> None:
        ch = chaos.active()
        if ch is None or entry.n_shards <= 1:
            return
        if ch.draw("device_loss"):
            raise chaos.InjectedFault(
                "device_loss",
                f"entry {entry.key} needs {entry.n_shards} devices, have "
                f"{entry.n_shards - 1}",
            )

    def _maybe_chaos_mesh_shrink(self, entry: LadderEntry) -> None:
        """The ``mesh_shrink=k`` drill: ACTUALLY lose k seeded devices from
        the pool (one event carrying the whole count — chaos.drain), then
        raise the device-loss signature so the trip path rebuilds over the
        survivors. Unlike ``device_loss`` this is not transient: every
        later mesh build sees the smaller pool."""
        ch = chaos.active()
        if ch is None or entry.n_shards <= 1 or self.pool.n_alive <= 1:
            return
        k = ch.drain("mesh_shrink")
        if k == 0 and ch.draw("mesh_shrink"):
            k = 1
        if k == 0:
            return
        from ..parallel.elastic import seeded_victims

        victims = seeded_victims(self.pool, k, ch.spec.seed)
        if not victims:
            return
        self.pool.lose(victims, cause="chaos:mesh_shrink")
        raise chaos.InjectedFault(
            "mesh_shrink",
            f"lost {len(victims)} device(s) {sorted(d.id for d in victims)}; "
            f"entry {entry.key} mesh is stale — {self.pool.n_alive} of "
            f"{self.pool.n_total} devices survive",
        )

    def _maybe_chaos_device_rejoin(self) -> None:
        """The ``device_rejoin=k`` drill: heal the k most recently lost
        devices back into the pool (verified against a fresh device
        re-query, so they land in probation — never straight into a mesh).
        No-op until something is actually lost, so a combined
        ``mesh_shrink=1,device_rejoin=1`` spec sequences lose-then-heal
        deterministically across steps without consuming the heal early
        (a step's own replay never consumes the rejoin either)."""
        ch = chaos.active()
        if ch is None or self.pool.n_lost == 0:
            return
        if self._step == self._rejoin_blocked_step:
            return
        k = ch.drain("device_rejoin")
        if k == 0 and ch.draw("device_rejoin"):
            k = 1
        if k == 0:
            return
        self.pool.heal(self.pool.recently_lost(k), cause="chaos:device_rejoin")

    def _maybe_chaos_flap(self, entry: LadderEntry) -> None:
        """The ``flap=k`` drill: ONE seeded device bounces through k
        lose→heal cycles, one half-cycle per supervised step. The first
        lose hits a device inside the active mesh and trips; every later
        bounce happens while the device is probationary — excluded from
        every mesh — so the ladder must stay put until the pool
        quarantines the flapper (the anti-flap acceptance)."""
        ch = chaos.active()
        if ch is None:
            return
        self._flap_cycles += ch.drain("flap")
        if self._flap_cycles <= 0:
            return
        if self._flap_last_step == self._step:
            return  # one transition per step, not per replay attempt
        pool = self.pool
        if self._flap_device is None:
            from ..parallel.elastic import seeded_victims

            victims = seeded_victims(pool, 1, ch.spec.seed, site="flap")
            if not victims:
                self._flap_cycles = 0
                return
            self._flap_device = victims[0]
        d = self._flap_device
        if pool.is_quarantined(d):
            self._flap_cycles = 0  # hysteresis won: the bounce is over
            return
        self._flap_last_step = self._step
        if pool.is_lost(d):
            pool.heal([d], cause="chaos:flap")
            self._flap_cycles -= 1
            return
        was_probationary = pool.is_probationary(d)
        pool.lose([d], cause="chaos:flap")
        if not was_probationary and entry.n_shards > 1:
            # The device was part of the active mesh: this lose is a real
            # topology change and must trip like any other device loss.
            raise chaos.InjectedFault(
                "mesh_shrink",
                f"flap: lost device {d.id}; entry {entry.key} mesh is stale "
                f"— {pool.n_alive} of {pool.n_total} devices survive",
            )

    def _maybe_chaos_stage_sdc(self, digests: Dict) -> Dict:
        ch = chaos.active()
        if ch is None or not digests:
            return digests
        if ch.draw("stage_sdc"):
            stages = sorted(digests)
            pick = random.Random(f"{ch.spec.seed}:stage_sdc").choice(stages)
            corrupt = dict(digests)
            corrupt[pick] = np.full_like(
                np.asarray(digests[pick], np.float64), np.nan
            )
            return corrupt
        return digests

    @off_timed_path
    def _screen(self, out, digests) -> None:
        """Host-side digest screening — between timed regions by contract
        (the off_timed_path annotation is what staticcheck checks)."""
        entry = self.entry
        digests = self._maybe_chaos_stage_sdc(digests)
        self.checker.check(
            self._step, digests, replicated=(entry.strategy == "replicated")
        )
        if entry.strategy == "replicated":
            # Replicated buffers must be bit-identical across shards —
            # PR 3's host-side checksum, reused as the cross-shard compare.
            spread = replicated_shard_spread(out)
            if spread > self.checker.cfg.divergence_tol:
                raise SDC(
                    "shard_divergence",
                    self._step,
                    f"{self.site}/{entry.key}: replicated output spread "
                    f"{spread:.6e} > tol {self.checker.cfg.divergence_tol:g}",
                )

    def _advance(self, cause: str, last: BaseException):
        """Move to the next buildable rung, journaling each DEGRADED hop."""
        while True:
            if self._idx + 1 >= len(self.ladder):
                raise DegradationExhausted(
                    [e.key for e in self.ladder], self.events, last
                ) from last
            ev = DegradedEvent(
                self.ladder[self._idx].key, self.ladder[self._idx + 1].key, cause
            )
            self.events.append(ev)
            if self.on_event is not None:
                self.on_event(ev)
            self._journal(
                "sup_degrade",
                key=f"degrade:{len(self.events)}",
                frm=ev.from_tier,
                to=ev.to_tier,
                cause=ev.cause,
            )
            self._idx += 1
            self._fwd = None
            self._sfn = None
            # Executable dropped ⇒ compile ledger with it: the landed
            # rung's first calls are real XLA compiles and must journal.
            self._compiled.clear()
            try:
                # Build eagerly: an unbuildable rung degrades again — which
                # now includes "needs more devices than survive the shrink"
                # (pool.mesh_for raises the mesh-needs-N ValueError).
                self._build_current()
                if self.on_rebuild is not None:
                    self.on_rebuild(self.entry)
                return
            except Exception as e:  # noqa — next hop carries the cause
                last = e
                cause = f"build failed: {type(e).__name__}: {e}"[:200]

    @off_timed_path
    def execute(self, params, x, step: Optional[int] = None):
        """Run one batch with screening + trip handling; returns ``out``.

        On a trip the failed batch is REPLAYED on the next rung — callers
        never see a half-screened result. Bounded by the ladder length
        (each rung gets one attempt per incident; a rung that keeps
        tripping keeps degrading until the floor, then
        :class:`DegradationExhausted` propagates).
        """
        import jax

        if step is not None:
            self._step = step
        while True:
            self.attempts += 1
            entry = self.entry
            try:
                fwd = self.fwd()
            except Exception as e:  # noqa — unbuildable rung: degrade, as
                # PR 1's Degrader does for a chain tier that fails to build.
                self._advance(f"build failed: {type(e).__name__}: {e}"[:200], e)
                continue
            try:
                self._maybe_scripted_fault(entry)
                self._maybe_chaos_device_rejoin()
                self._maybe_chaos_flap(entry)
                self._maybe_chaos_mesh_shrink(entry)
                self._maybe_chaos_device_loss(entry)
                shape = tuple(int(d) for d in x.shape)
                first = (entry.key, shape) not in self._compiled
                t0 = time.perf_counter()
                out, digests = fwd(params, x)
                jax.block_until_ready(out)
                if first:
                    # First call of THIS executable at THIS shape — the
                    # XLA compile. The old single-shot `if self.compile_ms
                    # is None:` measured exactly one compile per supervisor
                    # lifetime; the ledger measures every rung rebuild
                    # after a trip or promotion too.
                    self._compiled.add((entry.key, shape))
                    self._note_compile(
                        shape=shape,
                        dtype=str(x.dtype),
                        ms=(time.perf_counter() - t0) * 1e3,
                        cache_hit=False,
                        fn=fwd,
                        args=(params, x),
                    )
                self._screen(out, digests)
            except SDC as e:
                params = self._trip_and_recover(
                    e, entry.key, str(e)[:200],
                    f"SDC({e.kind}): {e.detail}"[:200], params,
                )
                continue
            except Exception as e:  # noqa — classified below
                if not _is_device_loss(e):
                    raise
                kind = _loss_kind(e)
                sdc = SDC(kind, self._step, str(e)[:200])
                params = self._trip_and_recover(
                    sdc, entry.key, str(e)[:200],
                    f"SDC({kind}): {e}"[:200], params,
                )
                continue
            self._journal(
                "sup_ok",
                key=f"ok:{self._step}",
                entry=self.entry.key,
                attempts=self.attempts,
            )
            # One clean batch: the probation clock ticks (grow-back
            # hysteresis) — a rejoined device graduates only after N of
            # these, never on the heal itself.
            self.pool.note_clean_batch()
            self._step += 1
            return out

    @off_timed_path
    def _replay_state(self, tree):
        """Post-degrade, pre-replay bookkeeping: live-reshard the state
        onto the landed rung's surviving-device mesh and journal the
        replay — the record that distinguishes step-level recovery from a
        checkpoint rollback in the incident trail."""
        self.replays += 1
        with obs_span("sup.replay", step=self._step, entry=self.entry.key):
            tree = self.reshard(tree)
            self._journal(
                "sup_replay",
                key=f"replay:{self.replays}",
                step=self._step,
                entry=self.entry.key,
            )
        return tree

    def _trip_and_recover(self, sdc: SDC, entry_key: str, journal_cause: str,
                          advance_cause: str, tree):
        """One trip's full recovery under a parent ``sup.trip`` span: the
        journaled trip record, the degrade walk (child ``sup.degrade`` —
        the serving layer's re-warm hook and its ``serve.rewarm`` span
        fire inside), then the live reshard + replay bookkeeping (child
        ``sup.replay`` containing ``sup.reshard``). Returns the resharded
        state the caller replays the batch/step with; spans are no-ops
        when no tracer is installed. The shared tail of every trip site
        (execute x2, supervise_step x2, trip_external)."""
        self.trips.append(sdc)
        with obs_span(
            "sup.trip", kind=sdc.kind, step=sdc.step, entry=entry_key
        ):
            self._journal(
                "sup_trip",
                key=f"trip:{len(self.trips)}",
                sdc_kind=sdc.kind,
                step=sdc.step,
                entry=entry_key,
                cause=journal_cause,
            )
            with obs_span("sup.degrade", frm=entry_key):
                self._advance(advance_cause, sdc)
            # Arm the grow-back path: promotion requires the eligible count
            # to GROW past what this degrade landed with — a transient trip
            # that lost no pool device can never oscillate back up.
            self._promote_floor_alive = self.pool.n_alive
            self._rejoin_blocked_step = self._step
            return self._replay_state(tree)

    @off_timed_path
    def supervise_step(self, params, opt_state, x, y, step: Optional[int] = None):
        """Run ONE training step under supervision; returns the step_fn
        output tuple ``(new_params, new_opt_state, loss[, grad_norm])``
        from SOME rung.

        The training twin of :meth:`execute`: a device loss / mesh shrink
        mid-step, or a non-finite loss/grad-norm, trips → the supervisor
        re-plans down the ladder (skipping rungs the surviving pool cannot
        satisfy), **reshards live params AND optimizer state** onto the
        new mesh, and REPLAYS the same ``(x, y)`` batch — step-level
        recovery, no checkpoint consumed. ``sup_step`` journals each
        committed step; ``sup_replay`` each replay. Raises
        :class:`DegradationExhausted` when the ladder is spent (the
        caller's checkpoint rollback is the floor below this)."""
        import jax

        if self.step_builder is None:
            raise ValueError(
                "supervise_step needs Supervisor(step_builder=...) — see "
                "training.make_elastic_step_builder"
            )
        if step is not None:
            self._step = step
        while True:
            self.attempts += 1
            entry = self.entry
            try:
                fn = self.step_fn()
            except Exception as e:  # noqa — unbuildable rung: degrade
                self._advance(f"build failed: {type(e).__name__}: {e}"[:200], e)
                params, opt_state = self._replay_state((params, opt_state))
                continue
            try:
                self._maybe_chaos_device_rejoin()
                self._maybe_chaos_flap(entry)
                self._maybe_chaos_mesh_shrink(entry)
                self._maybe_chaos_device_loss(entry)
                shape = tuple(int(d) for d in x.shape)
                first = (f"step:{entry.key}", shape) not in self._compiled
                t0 = time.perf_counter()
                out = fn(params, opt_state, x, y)
                jax.block_until_ready(out[2])
                if first:
                    # Training twin of execute()'s ledger: first step of
                    # this rung's step_fn at this batch shape is the
                    # compile (step_fn keys are disjoint from forward
                    # keys — a rung can hold both executables).
                    self._compiled.add((f"step:{entry.key}", shape))
                    self._note_compile(
                        shape=shape,
                        dtype=str(x.dtype),
                        ms=(time.perf_counter() - t0) * 1e3,
                        cache_hit=False,
                        fn=fn,
                        args=(params, opt_state, x, y),
                    )
                loss = float(out[2])
                gnorm = float(out[3]) if len(out) > 3 else None
                for name, v in (("loss", loss), ("grad_norm", gnorm)):
                    if v is not None and not math.isfinite(v):
                        raise SDC(
                            "step_nonfinite",
                            self._step,
                            f"{self.site}/{entry.key}: {name} = {v}",
                        )
            except SDC as e:
                params, opt_state = self._trip_and_recover(
                    e, entry.key, str(e)[:200],
                    f"SDC({e.kind}): {e.detail}"[:200], (params, opt_state),
                )
                continue
            except Exception as e:  # noqa — classified below
                if not _is_device_loss(e):
                    raise
                kind = _loss_kind(e)
                sdc = SDC(kind, self._step, str(e)[:200])
                params, opt_state = self._trip_and_recover(
                    sdc, entry.key, str(e)[:200],
                    f"SDC({kind}): {e}"[:200], (params, opt_state),
                )
                continue
            self._journal(
                "sup_step",
                key=f"sstep:{self._step}",
                entry=entry.key,
                attempts=self.attempts,
                replays=self.replays,
            )
            self.pool.note_clean_batch()  # grow-back probation clock
            self._step += 1
            return out

    def trip_external(self, e: SDC, params, opt_state):
        """An out-of-band trip from the caller's host-side screening (the
        train loop's Sentinel: norm spikes, param bit-flips, injected
        nan_loss) routed into the same degrade→reshard→replay path a
        supervised step takes. Returns the resharded ``(params,
        opt_state)`` the caller replays the batch with; raises
        :class:`DegradationExhausted` when the ladder is spent — at which
        point checkpoint rollback remains the floor."""
        return self._trip_and_recover(
            e, self.entry.key, str(e)[:200],
            f"SDC({e.kind}): {e.detail}"[:200], (params, opt_state),
        )

    @off_timed_path
    def request_degrade(self, cause: str) -> bool:
        """A VOLUNTARY one-rung degrade — capacity decision, not fault
        response (the serving autopilot's load-pressure rung,
        docs/SERVING.md "Autopilot"). Same walk as a trip: ``_advance``
        journals ``sup_degrade`` (cause ``"requested: ..."``), builds the
        rung eagerly, and fires ``on_rebuild`` so the serving layer
        re-warms before the next dispatch. The grow-back floor is pinned
        at the CURRENT alive count afterwards, so ``maybe_promote``
        cannot flap straight back on an unchanged pool — climbing again
        is the caller's explicit :meth:`request_promote`. False when the
        ladder is already at (or degrades through to) the floor."""
        if self._idx + 1 >= len(self.ladder):
            return False
        try:
            self._advance(
                f"requested: {cause}"[:200], RuntimeError(cause)
            )
        except DegradationExhausted:
            return False
        self._promote_floor_alive = self.pool.n_alive
        return True

    @off_timed_path
    def request_promote(self, params, opt_state=None):
        """The voluntary grow-back half: one rung UP, bypassing the
        alive-count hysteresis floor (the capacity judgment is the
        caller's) but keeping every safety check :meth:`promote` makes —
        the candidate still builds over the eligible pool and still must
        match the current rung on the sentinel input (a refusal journals
        ``sup_promote_refused``). Returns the resharded state, or None
        when nothing was adopted."""
        if self._idx == 0:
            return None
        return self.promote(
            params, opt_state=opt_state, target_idx=self._idx - 1
        )

    # ------------------------------------------------------------ grow-back

    def _spot_batch(self):
        """The deterministic sentinel input a promotion is verified on (and,
        in training mode, a fixed target so the loss is well-defined)."""
        from ..models.alexnet import output_shape
        from ..models.init import deterministic_input

        x = deterministic_input(1, self.model_cfg)
        oh, ow, oc = output_shape(self.model_cfg)
        y = np.zeros((1, oh, ow, oc), np.float32)
        return x, y

    def _promotion_target(self) -> Optional[int]:
        """The highest rung above the current one the ELIGIBLE pool
        satisfies (probationary/quarantined devices do not count — the
        hysteresis contract), or None."""
        for j in range(self._idx):
            entry = self.ladder[j]
            if entry.strategy == "single" or entry.n_shards <= self.pool.n_alive:
                return j
        return None

    @off_timed_path
    def maybe_promote(self, params, opt_state=None):
        """The consumers' between-batches grow-back hook: retry pending
        heals against a fresh device re-query, tick nothing (clean batches
        tick via execute/supervise_step), and — when the eligible count has
        GROWN past the last degrade's floor and a higher rung is
        satisfiable — run the full supervised promotion. Returns None when
        nothing changed; otherwise the live state resharded onto the
        promoted rung (``params``, or ``(params, opt_state)`` when
        ``opt_state`` is given)."""
        self.pool.rejoin_check()
        if self._idx == 0:
            return None
        if (
            self._promote_floor_alive is None
            or self.pool.n_alive <= self._promote_floor_alive
        ):
            return None
        target = self._promotion_target()
        if target is None or target >= self._idx:
            return None
        return self.promote(params, opt_state=opt_state, target_idx=target)

    @off_timed_path
    def promote(self, params, opt_state=None, target_idx: Optional[int] = None):
        """The inverse of a trip, as one supervised transition under a
        parent ``sup.recover`` span: rebuild the target rung's closures
        over the re-queried eligible devices, live-reshard the state UP
        (``reshard_tree``/``reshard_train_state`` semantics via
        :meth:`reshard`), verify the candidate against the CURRENT rung's
        output on a sentinel input, and only then switch. A candidate that
        fails to build falls to the next rung down; one that changes
        results is refused and journaled ``sup_promote_refused`` — never
        silently adopted. Returns the resharded state, or None when no
        rung was adopted."""
        if target_idx is None:
            target_idx = self._promotion_target()
        if target_idx is None or target_idx >= self._idx:
            return None
        training = self.step_builder is not None and opt_state is not None
        cur = self.entry
        state = (params, opt_state) if training else params
        t_start = time.perf_counter()
        with obs_span(
            "sup.recover", frm=cur.key, pool=self.pool.summary()
        ) as sp:
            for j in range(target_idx, self._idx):
                entry = self.ladder[j]
                if entry.strategy != "single" and entry.n_shards > self.pool.n_alive:
                    continue
                try:
                    ok, refused_reason, built = self._verify_candidate(
                        entry, params, opt_state, training
                    )
                except Exception as e:  # noqa — unbuildable candidate: the
                    # next rung down may still fit the eligible set.
                    continue
                if not ok:
                    # The sentinel caught a promotion that changes results:
                    # refuse it attributably and raise the hysteresis floor
                    # so this candidate is not retried every batch.
                    self._journal(
                        "sup_promote_refused",
                        key=f"promote-refused:{entry.key}",
                        frm=cur.key,
                        to=entry.key,
                        devices=self.pool.n_alive,
                        cause=refused_reason[:200],
                    )
                    if sp is not None:
                        sp.set(refused=entry.key)
                    self._promote_floor_alive = self.pool.n_alive
                    return None
                # Adopt: switch the rung, then reshard the live state onto
                # its mesh (journaled sup_reshard) and let the consumer
                # re-warm (serving compiles every bucket here, BEFORE the
                # next dispatch — zero post-promotion cache misses).
                self._idx = j
                if training:
                    self._sfn, self._fwd = built, None
                else:
                    self._fwd, self._sfn = built, None
                # New executable, new compile ledger: the re-warm below
                # (on_rebuild) measures this rung's per-bucket compiles.
                self._compiled.clear()
                with obs_span("sup.promote", frm=cur.key, to=entry.key):
                    state = self.reshard(state)
                    if self.on_rebuild is not None:
                        self.on_rebuild(entry)
                    self.promotions += 1
                    self._promote_floor_alive = self.pool.n_alive
                    self._journal(
                        "sup_promote",
                        key=f"promote:{self.promotions}",
                        frm=cur.key,
                        to=entry.key,
                        devices=self.pool.n_alive,
                        step=self._step,
                        ms=round((time.perf_counter() - t_start) * 1e3, 3),
                    )
                return state
        return None

    @off_timed_path
    def _rel_err(self, a, b) -> float:
        """Oracle-max-normalized deviation (the precision-gate metric):
        max|a-b| / max|a|, over trees or arrays. Promotion-path only —
        contractually between timed regions."""
        import jax

        worst = 0.0
        la = jax.tree_util.tree_leaves(a)
        lb = jax.tree_util.tree_leaves(b)
        if len(la) != len(lb):
            return float("inf")
        for x, y in zip(la, lb):
            x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
            if x.shape != y.shape:
                return float("inf")
            scale = max(float(np.max(np.abs(x))), 1e-30)
            worst = max(worst, float(np.max(np.abs(x - y))) / scale)
        return worst

    def _verify_candidate(self, entry: LadderEntry, params, opt_state, training):
        """Build the candidate rung and spot-check it against the CURRENT
        rung on the sentinel batch. Returns ``(ok, reason, built)`` where
        ``built`` is the candidate executable (step_fn in training mode,
        forward otherwise). The bar is ``promote_rtol`` (default 1e-5,
        sentinel-tight): a different shard count legitimately reorders
        float reductions by an ulp or two, but a rejoined device that
        computes WRONG results — the fault promotion must never re-adopt —
        misses by orders of magnitude. Outputs stay bit-identical against
        topology-PINNED references (the PR 8 contract; the drills assert
        both)."""
        import jax

        from ..parallel.elastic import reshard_tree

        x, y = self._spot_batch()
        mesh = self.pool.mesh_for(
            max(1, entry.n_shards if entry.strategy != "single" else 1)
        )
        if training:
            cand = self.step_builder(entry, self._entry_mesh(entry))
            cur_fn = self.step_fn()
            p2, o2 = reshard_tree((params, opt_state), mesh)
            a = cur_fn(params, opt_state, x, y)
            b = cand(p2, o2, x, y)
            jax.block_until_ready(b[2])
            rel = max(
                self._rel_err(a[0], b[0]),
                self._rel_err(np.float64(a[2]), np.float64(b[2])),
            )
        else:
            cand = self._build_entry(entry)
            cur_fn = self.fwd()
            p2 = reshard_tree(params, mesh)
            a, _ = cur_fn(params, x)
            b, _ = cand(p2, x)
            rel = self._rel_err(a, b)
        if rel > self.promote_rtol:
            return False, (
                f"sentinel spot-check mismatch: candidate {entry.key} "
                f"diverges from {self.entry.key} by rel {rel:.3e} "
                f"(> promote_rtol {self.promote_rtol:g})"
            ), cand
        return True, "", cand

    # ------------------------------------------------------------ surfacing

    @property
    def degraded(self) -> bool:
        return bool(self.events)

    def summary(self) -> str:
        """One machine-parseable line for the run CLI ('Supervisor: ...' —
        harness._RE_SUPERVISOR greps it into the SupervisorMsg CSV col)."""
        kinds = ",".join(t.kind for t in self.trips) or "none"
        return (
            f"attempts={self.attempts} trips={len(self.trips)} "
            f"degradations={len(self.events)} entry={self.entry.key} "
            f"kinds={kinds} replays={self.replays} "
            f"promotions={self.promotions} pool={self.pool.summary()} "
            f"quarantined={self.pool.n_quarantined}"
        )
