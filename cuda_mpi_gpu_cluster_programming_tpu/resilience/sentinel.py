"""Step-level silent-data-corruption (SDC) sentinel.

PR 1's resilience layer handles *loud* faults — nonzero exits, timeouts,
unreachable hosts. This module detects the *silent* ones the large-scale TPU
training literature treats as routine (bit-flipped params, NaN/Inf losses,
diverging replicas) and classifies a trip as a structured :class:`SDC` fault
so the training loop can roll back to the last-good checkpoint and re-enter
through the existing ``RetryPolicy``/``FaultLog`` machinery instead of
committing garbage steps:

- **Non-finite detection** — loss/grad-norm/param trees are screened for
  NaN/Inf every step (``check_scalar``/``check_tree``).
- **Norm-spike detection** — each watched scalar keeps a rolling window;
  a value ``spike_factor`` times the window median trips (a single
  high-exponent bit flip moves a float32 by ~2^64, far past any honest
  optimizer step).
- **Cross-replica divergence checksums** — per-shard digests over the
  dp/sp/tp shard_map paths must agree: ``replica_spread`` (inside
  shard_map: pmax - pmin of per-shard digests, the psum-agreement test) and
  ``replicated_shard_spread`` (host-side: per-device buffers of a
  replicated leaf must be bit-identical across addressable shards).
- **In-graph stage digests** — the sharded/tp/sequence-parallel forwards
  can compile per-stage activation digest taps INSIDE their shard_map
  bodies (``with_digests=True``); :class:`StageDigests` screens the
  returned digest tree host-side, strictly off the timed path (the
  :func:`off_timed_path` annotation marks — and staticcheck enforces —
  that screening never runs inside a timed loop).
- **Golden-oracle spot checks** — ``oracle_spot_check`` periodically re-runs
  a tiny conv through the framework op stack against the hand-written numpy
  oracle in ``tests/oracle.py``; a mismatch means the compute stack itself
  (not the training state) is corrupting values.

``inject_bit_flip`` is the seeded corruption the chaos layer's ``sdc`` site
uses so every recovery path runs on CPU in CI (``CHAOS_SPEC="sdc=1"``).

This module imports jax/numpy (it digests device trees); the stdlib-only
policy/chaos/journal layers stay import-light.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import math
import random
import statistics
from collections import deque
from pathlib import Path
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class SDC(RuntimeError):
    """A detected silent-data-corruption event: structured (kind, step,
    detail) so quarantine policy and fault logs can triage without string
    matching. Kinds: ``nan_loss``, ``nonfinite``, ``norm_spike``,
    ``replica_divergence``, ``oracle_mismatch``, plus the in-graph /
    supervisor family: ``stage_digest`` (a per-stage activation digest from
    inside a shard_map forward is non-finite or deviates from its
    reference), ``shard_divergence`` (shards that should hold identical
    values digest differently), ``device_loss`` (a device/shard vanished
    mid-fleet; the supervisor re-plans down its ladder)."""

    def __init__(self, kind: str, step: int, detail: str = ""):
        super().__init__(
            f"SDC({kind}) at step {step}" + (f": {detail}" if detail else "")
        )
        self.kind = kind
        self.step = step
        self.detail = detail


def off_timed_path(fn):
    """Annotate a function as NEVER called inside a timed region.

    Identity decorator, but statically meaningful: the staticcheck
    ``host-sync-in-hot-loop`` rule exempts loops/syncs inside functions
    carrying it (digest screening and oracle spot checks are host round
    trips BY DESIGN — the contract is that they run between timed regions,
    not that they avoid syncs). Decorating a function that IS on a timed
    path defeats the gate; treat the decorator like a ``# noqa`` with a
    wider span and the same review bar."""
    fn.__off_timed_path__ = True
    return fn


@dataclasses.dataclass(frozen=True)
class SentinelConfig:
    """Knobs (docs/RESILIENCE.md): ``window`` is the rolling history length
    per watched scalar, ``warmup`` how many observations arm spike detection
    (the first steps of a fresh run legitimately move orders of magnitude),
    ``spike_factor`` the trip ratio against the window median,
    ``divergence_tol`` the max cross-replica digest spread, ``oracle_every``
    runs the golden-oracle spot check every N-th ``check_tree`` (0 = off)."""

    window: int = 8
    warmup: int = 2
    spike_factor: float = 1e3
    divergence_tol: float = 0.0
    oracle_every: int = 0
    oracle_tol: float = 1e-3


class Sentinel:
    """Stateful per-run watcher; every ``check_*`` raises :class:`SDC` on a
    trip and otherwise records the observation. Trips are kept on
    ``self.trips`` so the quarantine layer can report the full incident
    trail after rollback."""

    def __init__(self, cfg: SentinelConfig = SentinelConfig(), site: str = "train"):
        self.cfg = cfg
        self.site = site
        self.trips: List[SDC] = []
        self._hist: Dict[str, Deque[float]] = {}
        self._tree_checks = 0

    def _trip(self, kind: str, step: int, detail: str) -> None:
        e = SDC(kind, step, detail)
        self.trips.append(e)
        raise e

    def check_scalar(self, step: int, value, name: str = "loss") -> float:
        """Screen one scalar (loss, grad norm, param norm) for NaN/Inf and
        window-median spikes. Returns the float value on a clean check. The
        tripped value is NOT added to history — a rollback re-enters with
        the pre-corruption window intact."""
        v = float(value)
        if not math.isfinite(v):
            self._trip(
                "nan_loss" if name == "loss" else "nonfinite",
                step,
                f"{name}={v}",
            )
        hist = self._hist.setdefault(name, deque(maxlen=self.cfg.window))
        if len(hist) >= self.cfg.warmup:
            ref = statistics.median(hist)
            if abs(v) > self.cfg.spike_factor * max(abs(ref), 1e-12):
                self._trip(
                    "norm_spike",
                    step,
                    f"{name}={v:.6e} vs window median {ref:.6e} "
                    f"(factor {self.cfg.spike_factor:g})",
                )
        hist.append(v)
        return v

    def check_tree(self, step: int, tree, name: str = "params") -> float:
        """Screen a pytree: any NaN/Inf leaf value trips ``nonfinite``; the
        global L2 norm rides the scalar spike detector under
        ``{name}_norm``. Returns the norm. Also drives the periodic
        golden-oracle spot check when ``oracle_every`` is set."""
        leaves = [jnp.asarray(leaf) for leaf in jax.tree_util.tree_leaves(tree)]
        if leaves:
            bad = sum(
                int(jnp.sum(~jnp.isfinite(leaf.astype(jnp.float32)))) for leaf in leaves
            )
            if bad:
                self._trip("nonfinite", step, f"{name}: {bad} non-finite value(s)")
            norm = float(
                jnp.sqrt(
                    sum(jnp.sum(jnp.square(leaf.astype(jnp.float32))) for leaf in leaves)
                )
            )
        else:
            norm = 0.0
        self.check_scalar(step, norm, name=f"{name}_norm")
        self._tree_checks += 1
        if self.cfg.oracle_every and self._tree_checks % self.cfg.oracle_every == 0:
            self.oracle_check(step)
        return norm

    def check_divergence(self, step: int, tree, name: str = "params") -> float:
        """Cross-replica digest agreement for a tree whose leaves are
        replicated across devices; a spread above ``divergence_tol`` trips
        ``replica_divergence``. Returns the spread."""
        spread = replicated_shard_spread(tree)
        if spread > self.cfg.divergence_tol:
            self._trip(
                "replica_divergence",
                step,
                f"{name}: replica digest spread {spread:.6e} "
                f"> tol {self.cfg.divergence_tol:g}",
            )
        return spread

    @off_timed_path
    def oracle_check(self, step: int) -> None:
        """Golden-oracle spot check (tests/oracle.py): a tiny conv through
        the framework op must match the hand-written numpy loops. A
        mismatch indicts the compute stack itself. Silently skipped when
        the oracle module is not on disk (installed-package deployments)."""
        err = oracle_spot_check(tol=self.cfg.oracle_tol)
        if err is not None and err > self.cfg.oracle_tol:
            self._trip(
                "oracle_mismatch",
                step,
                f"framework conv deviates from numpy oracle by {err:.3e} "
                f"(tol {self.cfg.oracle_tol:g})",
            )


class StageDigests:
    """Screen the auxiliary digest tree an in-graph-tapped forward returns.

    The sharded/tp/sequence-parallel builders (``with_digests=True``)
    compile one activation digest per pipeline stage INSIDE the shard_map
    body — a per-shard scalar riding alongside the output, so taps cost no
    host sync in the hot loop. ``check`` pulls those device scalars ONCE,
    between timed regions, and raises :class:`SDC` when:

    - any stage digest is non-finite (``stage_digest``): a NaN/Inf anywhere
      in a stage's activations poisons its digest, so corruption inside the
      shard_map is visible without materializing the activations;
    - ``expect`` is given and a stage's digest vector deviates from the
      recorded reference beyond ``rtol`` (``stage_digest``): the replay /
      golden-reference comparison the supervisor uses after a re-plan;
    - ``replicated=True`` and the per-shard digests of a stage disagree
      beyond ``divergence_tol`` (``shard_divergence``): shards holding the
      SAME logical values (replicated tiers, dp replicas) must digest
      bit-identically.

    ``check`` returns ``{stage: np.ndarray}`` (the host copies) so callers
    can journal or diff them without a second device fetch.
    """

    def __init__(self, cfg: SentinelConfig = SentinelConfig(), site: str = "forward"):
        self.cfg = cfg
        self.site = site
        self.trips: List[SDC] = []
        self.last: Dict[str, np.ndarray] = {}

    def _trip(self, kind: str, step: int, detail: str) -> None:
        e = SDC(kind, step, detail)
        self.trips.append(e)
        raise e

    @off_timed_path
    def check(
        self,
        step: int,
        digests,
        replicated: bool = False,
        expect: Optional[Dict[str, np.ndarray]] = None,
        rtol: float = 0.0,
    ) -> Dict[str, np.ndarray]:
        host: Dict[str, np.ndarray] = {}
        for stage in sorted(digests):
            vec = np.asarray(digests[stage], np.float64).reshape(-1)
            host[stage] = vec
            if not np.all(np.isfinite(vec)):
                self._trip(
                    "stage_digest",
                    step,
                    f"{self.site}/{stage}: non-finite stage digest {vec.tolist()}",
                )
            if replicated and vec.size > 1:
                spread = float(vec.max() - vec.min())
                if spread > self.cfg.divergence_tol:
                    self._trip(
                        "shard_divergence",
                        step,
                        f"{self.site}/{stage}: per-shard digest spread "
                        f"{spread:.6e} > tol {self.cfg.divergence_tol:g}",
                    )
            if expect is not None and stage in expect:
                want = np.asarray(expect[stage], np.float64).reshape(-1)
                scale = max(float(np.max(np.abs(want))) if want.size else 0.0, 1e-12)
                err = (
                    float(np.max(np.abs(vec - want))) if vec.shape == want.shape
                    else float("inf")
                )
                if err > rtol * scale:
                    self._trip(
                        "stage_digest",
                        step,
                        f"{self.site}/{stage}: digest deviates from reference "
                        f"by {err:.6e} (rtol {rtol:g}, scale {scale:.3e})",
                    )
        self.last = host
        return host


# ------------------------------------------------------------- digests ---


def tree_digest(tree):
    """Order-sensitive float32 digest of a pytree, computable inside jit /
    shard_map: per-leaf weighted sum + abs-sum so a sign flip, a swap, or a
    single bit flip all move it. NOT a cryptographic hash — it only needs to
    disagree when replicas disagree."""
    leaves = jax.tree_util.tree_leaves(tree)
    acc = jnp.zeros((), jnp.float32)
    for i, leaf in enumerate(leaves):
        x = jnp.asarray(leaf, jnp.float32)
        acc = acc + (i + 1) * jnp.sum(x) + jnp.sum(jnp.abs(x))
    return acc


def replica_spread(tree, axis_name: str):
    """Inside shard_map/pmap: max - min of the per-shard digests over
    ``axis_name`` — zero iff every replica computed identical values (the
    psum-agreement test: if spread is 0, psum(digest) == n * digest on
    every shard). Traceable; compare against a tolerance outside."""
    d = tree_digest(tree)
    return jax.lax.pmax(d, axis_name) - jax.lax.pmin(d, axis_name)


def cross_replica_digests(x, mesh, axis_name: str) -> np.ndarray:
    """Host entry for the shard_map paths: digest each ``axis_name`` shard
    of ``x`` (a leading-axis-sharded array or pytree of them) and return one
    digest per shard. Rows that SHOULD be replicas (same logical content per
    shard) must digest identically; ``max - min`` of the result is the
    divergence checksum for the dp/sp/tp paths."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    f = shard_map(
        lambda t: tree_digest(t)[None],
        mesh=mesh,
        in_specs=(P(axis_name),),
        out_specs=P(axis_name),
    )
    return np.asarray(f(x))


def replicated_shard_spread(tree) -> float:
    """Host-side replica checksum: for each leaf, digest every addressable
    shard and compare shards that cover the SAME index (replicas). On
    healthy hardware replicated buffers are bit-identical, so any spread is
    corruption, not roundoff. Single-device / fully-sharded leaves
    contribute nothing."""
    worst = 0.0
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if not shards or len(shards) < 2:
            continue
        by_index: Dict[str, List[float]] = {}
        for s in shards:
            digest = float(np.float64(np.asarray(s.data, np.float32).sum()))
            by_index.setdefault(str(s.index), []).append(digest)
        for digests in by_index.values():
            if len(digests) > 1:
                worst = max(worst, max(digests) - min(digests))
    return worst


# ------------------------------------------------------ oracle spot check ---

_ORACLE_PATH = Path(__file__).resolve().parent.parent.parent / "tests" / "oracle.py"
_oracle_mod = None


def _load_oracle():
    """tests/oracle.py, loaded by file path (the tests package is not an
    installed import); None when absent so deployments degrade to skipping
    the spot check rather than crashing the loop."""
    global _oracle_mod
    if _oracle_mod is None and _ORACLE_PATH.exists():
        spec = importlib.util.spec_from_file_location("_sdc_oracle", _ORACLE_PATH)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _oracle_mod = mod
    return _oracle_mod


def oracle_spot_check(tol: float = 1e-3, _corrupt: bool = False) -> Optional[float]:
    """Max abs deviation of the framework conv from the numpy oracle on a
    tiny fixed case, or None when the oracle module is unavailable.
    ``_corrupt`` perturbs the framework output (tests exercise the trip
    path without faking a real miscompile)."""
    oracle = _load_oracle()
    if oracle is None:
        return None
    from ..ops.reference import conv2d

    rng = np.random.default_rng(0)
    x = rng.standard_normal((9, 9, 3)).astype(np.float32)
    w = rng.standard_normal((3, 3, 3, 4)).astype(np.float32)
    b = rng.standard_normal((4,)).astype(np.float32)
    want = oracle.conv2d_np(x, w, b, stride=2, padding=1)
    got = np.asarray(
        conv2d(jnp.asarray(x)[None], jnp.asarray(w), jnp.asarray(b), stride=2, padding=1)
    )[0]
    if _corrupt:
        got = got + 1.0
    return float(np.max(np.abs(got - np.asarray(want, np.float32))))


# ------------------------------------------------------- chaos injection ---


def inject_bit_flip(
    tree, seed: int = 0, bit: int = 30
) -> Tuple[object, Optional[Tuple[int, int]]]:
    """Seeded single-bit corruption of one float32 leaf element — the
    ``sdc`` chaos site's payload. Flips ``bit`` (default 30, a high exponent
    bit: the value moves by ~2^64, the classic detectable-SDC signature) of
    a seeded nonzero element. Returns ``(corrupted_tree, (leaf_idx,
    elem_idx))``, or ``(tree, None)`` when no flippable leaf exists."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    rng = random.Random(f"sdc:{seed}")
    order = list(range(len(leaves)))
    rng.shuffle(order)
    for li in order:
        arr = np.array(leaves[li])  # owned copy
        if arr.dtype != np.float32 or arr.size == 0:
            continue
        flat = arr.reshape(-1)
        idx = rng.randrange(flat.size)
        for k in range(flat.size):  # walk to a nonzero element: a flipped
            j = (idx + k) % flat.size  # zero exponent stays small/undetected
            if flat[j] != 0.0:
                idx = j
                break
        else:
            continue
        flat.view(np.uint32)[idx] ^= np.uint32(1 << bit)
        leaves[li] = jnp.asarray(arr)
        return jax.tree_util.tree_unflatten(treedef, leaves), (li, idx)
    return tree, None
