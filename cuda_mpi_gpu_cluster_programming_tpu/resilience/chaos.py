"""Deterministic, seed-driven fault injection (chaos engineering lite).

Enabled via the ``CHAOS_SPEC`` environment variable so every recovery path
in the resilience subsystem is exercisable on CPU, in-process, in tier-1
tests — no real fault required. The spec is a comma-separated list:

    CHAOS_SPEC="seed=7,ssh=2,kernel_compile=1,collective=p0.5"

- ``seed=N``     — RNG seed for probabilistic sites (default 0).
- ``<site>=N``   — fail the first N draws at that site, then heal
                   (the transient-fault model: retry/degrade paths must
                   recover exactly at draw N+1).
- ``<site>=pX``  — each draw at that site fails with probability X from a
                   per-site stream seeded by (seed, site): deterministic
                   for a given spec, order-independent across sites.

Known sites (consumers listed; an unknown site in a spec is a hard
ValueError naming the valid kinds — a typo'd drill that silently never
fires would report "recovery path exercised" without exercising anything):

    collective        run CLI build step (sharded strategies) — transient
                      collective/ICI failure.
    device_loss       run CLI build step AND resilience.supervisor — mesh
                      shrink (needs N, have M); the supervisor treats it as
                      an SDC(device_loss) and re-plans down its ladder.
    stage_sdc         resilience.supervisor digest screening — a seeded
                      stage of the in-graph digest tree is corrupted to NaN
                      before screening, so the StageDigests checker must
                      trip stage_digest and the supervisor must degrade,
                      replay the batch, and match the uninjected oracle.
    mesh_shrink       resilience.supervisor / parallel.elastic — drop k
                      seeded devices from the elastic pool mid-run. The
                      count is a MAGNITUDE consumed as one event
                      (``mesh_shrink=2`` = one shrink losing 2 devices,
                      via ``ChaosInjector.drain``); ``mesh_shrink=pX``
                      drops 1 device per fired draw. The supervisor must
                      rebuild Mesh/shard_map closures over the survivors,
                      reshard live state, and replay the failed batch/step.
    device_rejoin     resilience.supervisor grow-back — heal the k most
                      recently lost devices (magnitude via ``drain``, like
                      mesh_shrink). A heal is verified against a fresh
                      ``jax.devices()`` re-query and lands in PROBATION,
                      never straight into a mesh; the site no-ops (without
                      consuming its budget) until something is lost, so
                      ``mesh_shrink=1,device_rejoin=1`` sequences
                      lose-then-heal deterministically.
    flap              resilience.supervisor grow-back — bounce ONE seeded
                      device through k lose->heal cycles (magnitude via
                      ``drain``), one half-cycle per supervised step. The
                      pool must quarantine the flapper (``mesh_quarantine``)
                      instead of oscillating the mesh.
    host_loss         serving.fleet (router tier) — SIGKILL one seeded
                      backend PROCESS mid-load (victim = seed % n, via
                      ``fleet.maybe_host_loss``). The router must fail the
                      dead host's in-flight requests attributably, redirect
                      subsequent traffic within each request's retry
                      budget, and re-admit the restarted backend only
                      through probation — the process-boundary half of the
                      device_loss story.
    fleet_pressure    serving.loadgen.maybe_fleet_pressure (fleet control
                      tier) — swap the drill's load for a correlated
                      diurnal swell that saturates EVERY backend at once
                      (the failure mode N uncoordinated Autopilots
                      all-degrade under). The FleetController must keep
                      max-simultaneously-degraded below the fleet size
                      via staggered downshift tokens + forecast
                      pre-shedding, with accounting closed both ways.
    kernel_compile    run CLI build step (pallas tier) — Mosaic lowering
                      failure; degrades Pallas -> XLA reference tier.
    ssh               parallel.deploy transports — transient ssh exit.
    rsync             parallel.deploy transports — transient rsync exit.
    sdc               train loop — seeded single-bit param corruption
                      (resilience.sentinel.inject_bit_flip); the sentinel
                      must detect, roll back, and re-enter.
    nan_loss          train loop — the step's loss is replaced with NaN;
                      the sentinel must trip on the same step.

Counters are per-process; CHAOS_SPEC rides the environment into harness/
deploy children, where each child gets its own deterministic stream.
"""

from __future__ import annotations

import dataclasses
import os
import random
from typing import Dict, Optional

CHAOS_ENV = "CHAOS_SPEC"

# Every injectable fault kind, in consumer order (see module docstring).
# ``ChaosSpec.parse`` validates against this list so a typo'd drill fails
# loudly instead of silently never firing.
KNOWN_SITES = (
    "collective",
    "device_loss",
    "kernel_compile",
    "ssh",
    "rsync",
    "sdc",
    "nan_loss",
    "stage_sdc",
    "mesh_shrink",
    "device_rejoin",
    "flap",
    "host_loss",
    "fleet_pressure",
)


class InjectedFault(RuntimeError):
    """A fault manufactured by the chaos layer — never raised by real code,
    so recovery paths can tell drills from genuine failures in logs."""

    def __init__(self, site: str, detail: str = ""):
        super().__init__(f"chaos: injected {site} fault" + (f" ({detail})" if detail else ""))
        self.site = site


@dataclasses.dataclass(frozen=True)
class ChaosSpec:
    """Parsed CHAOS_SPEC: count-based and probabilistic sites."""

    seed: int = 0
    counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    probs: Dict[str, float] = dataclasses.field(default_factory=dict)

    @classmethod
    def parse(cls, text: str) -> "ChaosSpec":
        seed, counts, probs = 0, {}, {}
        for item in (text or "").split(","):
            item = item.strip()
            if not item:
                continue
            if "=" not in item:
                raise ValueError(f"malformed CHAOS_SPEC item {item!r} (want site=N|pX)")
            site, _, val = item.partition("=")
            site, val = site.strip(), val.strip()
            if site != "seed" and site not in KNOWN_SITES:
                raise ValueError(
                    f"unknown CHAOS_SPEC fault kind {site!r} "
                    f"(valid kinds: seed, {', '.join(KNOWN_SITES)})"
                )
            if site == "seed":
                seed = int(val)
            elif val.startswith("p"):
                p = float(val[1:])
                if not 0.0 <= p <= 1.0:
                    raise ValueError(f"CHAOS_SPEC {site}={val}: probability outside [0,1]")
                probs[site] = p
            else:
                counts[site] = int(val)
        return cls(seed=seed, counts=counts, probs=probs)

    @property
    def empty(self) -> bool:
        return not self.counts and not self.probs


class ChaosInjector:
    """Stateful per-process injector over a ChaosSpec."""

    def __init__(self, spec: ChaosSpec):
        self.spec = spec
        self._remaining = dict(spec.counts)
        self._rng = {
            site: random.Random(f"{spec.seed}:{site}") for site in spec.probs
        }
        self.fired: Dict[str, int] = {}

    def draw(self, site: str) -> bool:
        """True = inject a fault at this site now. Count-based sites burn
        down; probabilistic sites draw from their seeded stream."""
        hit = False
        if self._remaining.get(site, 0) > 0:
            self._remaining[site] -= 1
            hit = True
        elif site in self._rng:
            hit = self._rng[site].random() < self.spec.probs[site]
        if hit:
            self.fired[site] = self.fired.get(site, 0) + 1
        return hit

    def drain(self, site: str) -> int:
        """Consume and return ALL remaining count-based hits at ``site``
        (0 when none). For sites where the spec's count is a magnitude one
        event carries (``mesh_shrink=k`` drops k devices in ONE shrink)
        rather than N separate transient faults. Probabilistic sites are
        untouched — their per-draw stream still fires via ``draw``."""
        n = self._remaining.pop(site, 0)
        if n > 0:
            self.fired[site] = self.fired.get(site, 0) + n
        return n

    def maybe_raise(self, site: str, detail: str = "") -> None:
        if self.draw(site):
            raise InjectedFault(site, detail)


# Process-wide injector, cached per CHAOS_SPEC value so counters persist
# across call sites within one process but a test's monkeypatched env takes
# effect immediately.
_cached: Optional[tuple] = None  # (spec_text, injector)


def active() -> Optional[ChaosInjector]:
    """The process injector, or None when CHAOS_SPEC is unset/empty —
    callers guard with ``ch = active();  if ch and ch.draw(...)`` so the
    chaos-off hot path costs one env read."""
    global _cached
    text = os.environ.get(CHAOS_ENV, "")
    if not text.strip():
        _cached = None
        return None
    if _cached is None or _cached[0] != text:
        _cached = (text, ChaosInjector(ChaosSpec.parse(text)))
    return _cached[1]


def reset() -> None:
    """Forget the cached injector (tests: fresh counters per case)."""
    global _cached
    _cached = None
