"""True elastic meshes: surviving-device pools + live resharding.

PR 5's supervisor answers a device loss by re-planning down its ladder,
but every rebuilt rung still constructs its Mesh from the FULL device pool
(``make_mesh`` slices ``jax.devices()[:n]``) — re-planning on the same
device set that just lost a member. The reference's V4 hybrid has the same
gap one layer down: an MPI rank death kills the whole row-scatter job
(v4_mpi_cuda/src/main_mpi_cuda.cpp — no communicator shrink, no respawn).
This module makes the shrink real:

- :class:`ElasticPool` tracks which devices are lost and **re-queries**
  ``jax.devices()`` at every mesh build — never a module-cached list
  (staticcheck's ``stale-device-set`` rule pins exactly this discipline:
  a device list cached at import time keeps naming the dead chip inside
  every later rebuild).
- :meth:`ElasticPool.mesh_for` builds shard_map-compatible meshes over the
  SURVIVORS, so a degrade rung's collectives never route through a lost
  device.
- :func:`reshard_tree` / :func:`reshard_train_state` move live params /
  optimizer state onto the new mesh via ``jax.device_put`` with the new
  sharding — a degrade re-homes state directly instead of round-tripping
  through a checkpoint (the checkpoint stays the floor, not the fast
  path; see utils/checkpoint.py reshard-on-load for the restore side).

Every shrink is journaled (``mesh_shrink`` records) and drillable on CPU:
``CHAOS_SPEC="seed=3,mesh_shrink=k"`` drops k seeded devices mid-run
(docs/RESILIENCE.md "True elastic meshes").

Since PR 10 the shrink has an inverse — grow-back with anti-flap
hysteresis (docs/RESILIENCE.md "Grow-back & hysteresis"):

- :meth:`ElasticPool.heal` / :meth:`ElasticPool.rejoin_check` move a lost
  device back toward eligibility, but ONLY after it reappears in a fresh
  ``jax.devices()`` re-query — the stale-device-set discipline applies to
  rejoin exactly as it does to shrink (an id healed on the operator's say-so
  that the runtime cannot actually see would put a ghost in the next mesh).
- A rejoined device does NOT immediately count toward :meth:`mesh_for`: it
  sits in a journaled probation state (``mesh_probation`` records,
  ``probation_steps`` clean supervised steps/batches ticked via
  :meth:`note_clean_batch`) before graduating back into ``alive()``.
- A device that completes ``quarantine_flaps`` lose→heal cycles within
  ``flap_window`` clean-step ticks is quarantined attributably
  (``mesh_quarantine`` record) instead of oscillating the mesh — the
  supervisor's promotion path never sees it again.
"""

from __future__ import annotations

import random
import time
from typing import Dict, Iterable, List, Optional, Set, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import make_mesh

PyTree = object


class ElasticPool:
    """The surviving-device set, queried fresh at every mesh build.

    ``alive()`` filters the CURRENT ``jax.devices()`` against the lost-id
    set rather than caching a device list — the pool owns the *exclusions*,
    the runtime owns the *roster*, so a rebuild after any runtime-side
    change (a device re-enumerating, a restarted backend) sees the truth
    of that moment.
    """

    def __init__(
        self,
        journal=None,
        site: str = "elastic",
        probation_steps: int = 2,
        quarantine_flaps: int = 3,
        flap_window: int = 64,
    ):
        self.journal = journal
        self.site = site
        # Anti-flap hysteresis knobs (docs/RESILIENCE.md "Grow-back &
        # hysteresis"): N clean supervised steps/batches a rejoined device
        # waits in probation, K lose->heal cycles within `flap_window`
        # clean-step ticks that quarantine it.
        self.probation_steps = max(0, int(probation_steps))
        self.quarantine_flaps = max(1, int(quarantine_flaps))
        self.flap_window = max(1, int(flap_window))
        self._lost_ids: Set[int] = set()
        self._lost_order: List[int] = []  # loss recency (most recent last)
        self._probation: Dict[int, int] = {}  # id -> clean steps remaining
        self._probation_t0: Dict[int, float] = {}  # id -> monotonic entry time
        self._quarantined: Set[int] = set()
        self._heal_pending: Set[int] = set()  # healed ids not yet re-enumerated
        self._flaps: Dict[int, List[int]] = {}  # id -> clock of each heal
        self._clock = 0  # clean-batch ticks; the flap window's time base
        self.shrinks: List[dict] = []  # one record per lose() call

    # ------------------------------------------------------------ queries

    def alive(self) -> List[jax.Device]:
        """ELIGIBLE devices, re-queried from the runtime NOW: the roster
        minus lost, quarantined, and still-probationary ids. Probationary
        devices are healthy hardware but do not count toward a mesh until
        they graduate (the anti-flap contract)."""
        excluded = self._lost_ids | self._quarantined | set(self._probation)
        return [d for d in jax.devices() if d.id not in excluded]

    @property
    def n_total(self) -> int:
        return len(jax.devices())

    @property
    def n_alive(self) -> int:
        return len(self.alive())

    @property
    def n_lost(self) -> int:
        return len(self._lost_ids)

    @property
    def n_probation(self) -> int:
        return len(self._probation)

    @property
    def n_quarantined(self) -> int:
        return len(self._quarantined)

    def is_lost(self, device) -> bool:
        return (device if isinstance(device, int) else device.id) in self._lost_ids

    def is_probationary(self, device) -> bool:
        return (device if isinstance(device, int) else device.id) in self._probation

    def is_quarantined(self, device) -> bool:
        return (device if isinstance(device, int) else device.id) in self._quarantined

    def recently_lost(self, k: int) -> List[int]:
        """The k most recently lost ids, most recent first — what a
        ``device_rejoin`` drill heals (the device that just blipped is the
        one that comes back)."""
        return list(reversed(self._lost_order))[: max(0, int(k))]

    def summary(self) -> str:
        return f"{self.n_alive}/{self.n_total}"

    # ------------------------------------------------------------- shrink

    def lose(self, devices: Iterable, cause: str = "device_loss") -> dict:
        """Mark devices (``jax.Device``s or integer ids) as lost.

        Refuses to lose the LAST device — the single-device reference floor
        must keep somewhere to land (a fleet with zero survivors has no
        recovery story; that is a page, not a degrade). Journals a
        ``mesh_shrink`` record naming before/after/lost so the incident
        trail shows the topology change next to the supervisor's trips.
        """
        ids = {d if isinstance(d, int) else d.id for d in devices}
        survivors = [d for d in self.alive() if d.id not in ids]
        if not survivors:
            raise ValueError(
                f"refusing to lose all {self.n_alive} surviving devices "
                f"(ids {sorted(ids)}): the single-device floor needs one"
            )
        before = self.n_alive
        # Losing a probationary device is a FLAP half-cycle: it leaves
        # probation and re-enters the lost set (its flap history survives,
        # so the next heal can see it is oscillating). It was not eligible,
        # so before == after for such a record — attributable, not a shrink.
        for i in ids:
            self._probation.pop(i, None)
            self._probation_t0.pop(i, None)
            if i in self._lost_order:
                self._lost_order.remove(i)
            self._lost_order.append(i)
        self._lost_ids |= ids
        record = {
            "before": before,
            "after": self.n_alive,
            "lost": sorted(ids),
            "cause": cause,
        }
        self.shrinks.append(record)
        if self.journal is not None:
            # Optional trace correlation (observability.trace): a shrink
            # journaled during a traced run carries the run's trace id so
            # the exporter places it on the incident timeline.
            from ..observability.trace import current_ids

            self.journal.append(
                "mesh_shrink",
                key=f"shrink:{before}->{self.n_alive}",
                site=self.site,
                **current_ids(),
                **record,
            )
        return record

    # ------------------------------------------------------------ grow-back

    def _journal(self, kind: str, key: str, **payload) -> None:
        if self.journal is not None:
            from ..observability.trace import current_ids

            self.journal.append(kind, key=key, site=self.site,
                                **current_ids(), **payload)

    def heal(self, devices: Iterable, cause: str = "device_rejoin") -> dict:
        """Report devices as healed. A healed id leaves the exclusion set
        only after it reappears in a fresh ``jax.devices()`` re-query; an
        id the runtime cannot see yet stays lost and is retried by every
        later :meth:`rejoin_check`. A verified rejoin enters probation
        (``mesh_probation`` record) — or quarantine (``mesh_quarantine``)
        when this heal completes the K-th flap inside the window. Returns
        the transition record (``probation``/``absent``/``quarantined``
        id lists)."""
        ids = sorted({d if isinstance(d, int) else d.id for d in devices})
        return self._rejoin(ids, cause)

    def rejoin_check(self, cause: str = "rejoin_check") -> dict:
        """Re-run the fresh-roster check over every heal still pending —
        the consumers' between-batches hook (a returning device may take a
        while to re-enumerate)."""
        if not self._heal_pending:
            return {"probation": [], "absent": [], "quarantined": []}
        return self._rejoin(sorted(self._heal_pending), cause)

    def _rejoin(self, ids: List[int], cause: str) -> dict:
        roster = {d.id for d in jax.devices()}  # fresh re-query, never cached
        probation: List[int] = []
        absent: List[int] = []
        quarantined: List[int] = []
        for i in ids:
            if i in self._quarantined:
                # Quarantine is sticky: a flapping device does not get to
                # oscillate the mesh by asking again.
                self._heal_pending.discard(i)
                quarantined.append(i)
                continue
            if i not in self._lost_ids:
                self._heal_pending.discard(i)  # already eligible/probationary
                continue
            if i not in roster:
                self._heal_pending.add(i)
                absent.append(i)
                continue
            # Verified rejoin: this completes one lose->heal flap cycle.
            flaps = [t for t in self._flaps.get(i, [])
                     if self._clock - t <= self.flap_window]
            flaps.append(self._clock)
            self._flaps[i] = flaps
            self._lost_ids.discard(i)
            self._lost_order.remove(i)
            self._heal_pending.discard(i)
            if len(flaps) >= self.quarantine_flaps:
                self._quarantined.add(i)
                quarantined.append(i)
                self._journal(
                    "mesh_quarantine",
                    key=f"quarantine:{i}",
                    device=i,
                    flaps=len(flaps),
                    window=self.flap_window,
                    cause=cause,
                )
            else:
                self._probation[i] = self.probation_steps
                self._probation_t0[i] = time.monotonic()
                probation.append(i)
        record = {"probation": probation, "absent": absent,
                  "quarantined": quarantined}
        if probation:
            self._journal(
                "mesh_probation",
                key=f"probation:{','.join(map(str, probation))}",
                event="enter",
                devices=probation,
                probation_steps=self.probation_steps,
                cause=cause,
            )
            if self.probation_steps == 0:
                # N=0 disables the hysteresis: graduate immediately.
                self.note_clean_batch(0)
        return record

    def note_clean_batch(self, n: int = 1) -> List[int]:
        """One clean supervised step/batch elapsed: advance the flap-window
        clock and tick every probation counter. Devices reaching 0 graduate
        back into ``alive()`` (journaled ``mesh_probation`` event="pass" —
        the record a promotion decision is allowed to build on). Returns
        the graduated ids."""
        self._clock += max(0, int(n))
        passed: List[int] = []
        for i in list(self._probation):
            self._probation[i] -= n
            if self._probation[i] <= 0:
                del self._probation[i]
                passed.append(i)
        if passed:
            ms = max(
                (time.monotonic() - self._probation_t0.pop(i, time.monotonic()))
                * 1e3
                for i in passed
            )
            self._journal(
                "mesh_probation",
                key=f"probation-pass:{','.join(map(str, passed))}",
                event="pass",
                devices=sorted(passed),
                ms=round(ms, 3),
            )
        return passed

    # -------------------------------------------------------------- build

    def mesh_for(self, n_shards: int, axis_name: str = "sp", dp: int = 1) -> Mesh:
        """A mesh over the first ``dp * n_shards`` SURVIVORS.

        Raises the standard ``mesh needs N devices, have M`` ValueError
        when the pool has shrunk below the request — the supervisor's
        eager-build degrade loop treats that as "rung unsatisfiable" and
        keeps walking the ladder.
        """
        return make_mesh(
            max(1, int(n_shards)), axis_name=axis_name, dp=dp, devices=self.alive()
        )


def seeded_victims(pool: ElasticPool, k: int, seed, site: str = "mesh_shrink") -> List[jax.Device]:
    """k seeded victims among the pool's survivors, clamped so at least one
    device survives. Deterministic per (seed, site, surviving set). ANY
    survivor — the lowest-id/default device included — is a legal victim:
    the single@1 floor builds over ``pool.alive()[0]`` re-queried at trip
    time (ROADMAP item 3 leftover (d)), so no drill needs to spare it."""
    alive = pool.alive()
    k = max(0, min(int(k), len(alive) - 1))
    if k == 0:
        return []
    rng = random.Random(f"{seed}:{site}")
    return rng.sample(alive, k)


def reshard_tree(tree: PyTree, mesh: Mesh, spec: Optional[P] = None) -> PyTree:
    """``jax.device_put`` a live pytree onto ``mesh`` under ``spec``
    (default ``P()`` — fully replicated, the framework's params-replicated
    discipline for the sp/tp training and serving paths). Values are
    untouched; only placement changes — buffers on a lost device are
    re-materialized from a surviving replica."""
    return jax.device_put(tree, NamedSharding(mesh, spec if spec is not None else P()))


def reshard_train_state(
    params: PyTree, opt_state: PyTree, mesh: Mesh, spec: Optional[P] = None
) -> Tuple[PyTree, PyTree]:
    """Reshard live (params, opt_state) onto ``mesh`` in one call — the
    supervisor's step-replay path re-homes BOTH before re-running a batch,
    so the optimizer update never mixes placements."""
    placed = reshard_tree((params, opt_state), mesh, spec)
    return placed[0], placed[1]


def tree_device_ids(tree: PyTree) -> Set[int]:
    """All device ids any leaf of ``tree`` currently lives on (test /
    assertion surface for the reshard contract)."""
    ids: Set[int] = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        devs = getattr(leaf, "devices", None)
        if callable(devs):
            ids |= {d.id for d in devs()}
    return ids
