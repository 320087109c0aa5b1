"""Persistent kernel-tuning plans: on-disk winners of the variant sweep.

A ``TunePlan`` records, for one (device kind, model geometry, batch, dtype,
code revision) point, the per-layer ``KernelVariants`` the autotuner
measured fastest (the compilation-cache + sweep pattern of SNIPPETS.md [1]
and the AutoTVM/Triton-style searchers in PAPERS.md). Plan files hold many
plans keyed by the full point, so one ``perf/tune_plan.json`` serves CPU
CI and the v5e alike.

File format (docs/TUNING.md):

    {
      "version": 1,
      "plans": {
        "<device_kind>|<shape_key>|b<batch>|<dtype>|rev=<code_rev>": {
          "device_kind": ..., "shape_key": ..., "batch": ..., "dtype": ...,
          "code_rev": ..., "degraded": "", "created": "...",
          "layers":  {"conv1": {"conv": "vcol", "row_block": 64, "fuse": "none"}},
          "stats":   {"conv1": {"best_ms": ..., "default_ms": ..., ...}}
        }
      }
    }

Staleness: ``code_rev`` hashes the kernel/lowering sources; a plan tuned
against different kernel code is a MISS (re-sweep), never silently reused.
So is a plan that names a lowering field or value the code no longer has
(``k_block``, ``pool``, ``conv="pairs"``: plans written before PR 42).
Either miss is reported as a ``RuntimeWarning`` that names why.

Which lowering runs (one implementation, ``effective_layer_variants``): the
loaded plan's per-layer winner, else the code default ``KernelVariants()``.
"""

from __future__ import annotations

import dataclasses
import datetime
import hashlib
import json
import warnings
from pathlib import Path
from typing import Dict, Optional, Tuple

from ..ops.pallas_kernels import KernelVariants, LayerVariants

PLAN_VERSION = 1

# Sources whose drift invalidates tuned winners: the kernels themselves,
# the model chain that decides fusion adjacency, the candidate space, and
# the quantized lowering (an int8w plan tuned against a different rescale
# path is as stale as one tuned against different kernels).
_REV_FILES = (
    "../ops/pallas_kernels.py",
    "../ops/pallas_model.py",
    "../ops/megakernel.py",
    "space.py",
    "../precision/quantize.py",
)


def code_rev() -> str:
    """12-hex digest of the kernel/lowering sources — the plan-staleness key."""
    h = hashlib.sha256()
    here = Path(__file__).resolve().parent
    for rel in _REV_FILES:
        h.update((here / rel).read_bytes())
    return h.hexdigest()[:12]


def _input_dims(model_cfg) -> Tuple[int, int, int]:
    return model_cfg.in_height, model_cfg.in_width, model_cfg.in_channels


def shape_key(model_cfg) -> str:
    """Geometry identity of a model config: family + input dims (the layer
    chain is derived from these by the shared traversal)."""
    family = "alexnet_full" if hasattr(model_cfg, "blocks12") else "blocks12"
    h, w, c = _input_dims(model_cfg)
    return f"{family}_{h}x{w}x{c}"


def plan_key(device_kind: str, shape_k: str, batch: int, dtype: str, rev: str) -> str:
    return f"{device_kind}|{shape_k}|b{batch}|{dtype}|rev={rev}"


@dataclasses.dataclass(frozen=True)
class TunePlan:
    """Winners of one autotune sweep (or the default-plan degradation)."""

    device_kind: str
    shape_key: str
    batch: int
    dtype: str  # a precision policy name: "fp32" | "bf16" | "int8w"
    code_rev: str
    layers: Tuple[Tuple[str, KernelVariants], ...]
    stats: Dict[str, dict] = dataclasses.field(default_factory=dict)
    # Non-empty = the sweep could not finish (deadline/chaos/faults) and fell
    # back to defaults for the listed layers — visible, never silent.
    degraded: str = ""

    @property
    def key(self) -> str:
        return plan_key(
            self.device_kind, self.shape_key, self.batch, self.dtype, self.code_rev
        )

    def plan_hash(self) -> str:
        """10-hex identity of (key, winners) — the CSV row label that
        makes tuned measurements attributable to one exact plan."""
        payload = json.dumps(
            {"key": self.key, "layers": {n: dataclasses.asdict(v) for n, v in self.layers}},
            sort_keys=True,
        )
        return hashlib.sha256(payload.encode()).hexdigest()[:10]

    def to_obj(self) -> dict:
        return {
            "device_kind": self.device_kind,
            "shape_key": self.shape_key,
            "batch": self.batch,
            "dtype": self.dtype,
            "code_rev": self.code_rev,
            "degraded": self.degraded,
            "layers": {n: dataclasses.asdict(v) for n, v in self.layers},
            "stats": self.stats,
        }

    @classmethod
    def from_obj(cls, obj: dict) -> "TunePlan":
        layers = tuple(
            (n, KernelVariants(**fields)) for n, fields in obj["layers"].items()
        )
        return cls(
            device_kind=obj["device_kind"],
            shape_key=obj["shape_key"],
            batch=int(obj["batch"]),
            dtype=obj["dtype"],
            code_rev=obj["code_rev"],
            layers=layers,
            stats=obj.get("stats", {}),
            degraded=obj.get("degraded", ""),
        )


def _read_file(path) -> dict:
    """The whole plan file as a dict (``plans`` + the sibling ``policies``
    section); a missing/torn file degrades to empty sections."""
    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, ValueError):
        obj = {}
    if not isinstance(obj, dict):
        obj = {}
    if not isinstance(obj.get("plans"), dict):
        obj["plans"] = {}
    if not isinstance(obj.get("policies"), dict):
        obj["policies"] = {}
    obj["version"] = PLAN_VERSION
    return obj


def _read_plans(path) -> dict:
    return _read_file(path)["plans"]


def _write_file(path, obj: dict) -> None:
    # Atomic replace: the plan cache is a committed run artifact; a crash
    # mid-save must leave the previous (complete) file, never a torn one.
    from ..resilience.journal import atomic_write_text

    atomic_write_text(path, json.dumps(obj, indent=2) + "\n")


def save_plan(plan: TunePlan, path) -> str:
    """Merge one plan into the file under its key (read-modify-write; other
    device/dtype/batch points AND the policy records are preserved).
    Returns the key written."""
    path = Path(path)
    obj = _read_file(path)
    entry = plan.to_obj()
    entry["created"] = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y-%m-%dT%H:%MZ"
    )
    obj["plans"][plan.key] = entry
    _write_file(path, obj)
    return plan.key


def _plan_or_stale(key: str, obj: dict) -> Optional[TunePlan]:
    """The plan stored under ``key``, or None with a warning when it names a
    lowering field or value this code no longer has."""
    try:
        return TunePlan.from_obj(obj)
    except (TypeError, ValueError) as e:
        warnings.warn(
            f"tune plan {key} is stale ({e}); not applied",
            RuntimeWarning, stacklevel=3,
        )
        return None


def load_plan(
    path,
    *,
    device_kind: str,
    model_cfg,
    dtype: str,
    batch: int,
    rev: Optional[str] = None,
    match_any_batch: bool = True,
) -> Optional[TunePlan]:
    """The plan for this exact point, or None (= tune or run untuned).

    A different ``code_rev`` is a MISS even when everything else matches —
    stale winners must never be applied to changed kernels — and so is a
    plan whose winners this code cannot build; both warn, naming why. With
    ``match_any_batch`` a same-device/geometry/dtype plan tuned at another
    batch is accepted as the nearest usable point (the variants space is
    geometry-dominated; the returned plan keeps its own batch so consumers
    can see the approximation).
    """
    plans = _read_plans(path)
    if not plans:
        return None
    rev = rev or code_rev()
    sk = shape_key(model_cfg)
    key = plan_key(device_kind, sk, batch, dtype, rev)
    if key in plans:
        return _plan_or_stale(key, plans[key])
    prefix = f"{device_kind}|{sk}|b"
    suffix = f"|{dtype}|rev={rev}"
    if match_any_batch:
        for k in sorted(plans):
            if k.startswith(prefix) and k.endswith(suffix):
                plan = _plan_or_stale(k, plans[k])
                if plan is not None:
                    return plan
    other_revs = sorted({
        k.rsplit("|rev=", 1)[1] for k in plans
        if k.startswith(prefix) and f"|{dtype}|rev=" in k and not k.endswith(suffix)
    })
    if other_revs:
        warnings.warn(
            f"tune plan for {device_kind}|{sk}|{dtype} is stale: tuned at "
            f"code_rev {', '.join(other_revs)}, the kernels are at {rev}; "
            "not applied",
            RuntimeWarning, stacklevel=2,
        )
    return None


def plan_batches(
    path,
    *,
    device_kind: str,
    model_cfg,
    dtype: str,
    rev: Optional[str] = None,
) -> list:
    """Batch sizes the plan file holds CURRENT tuned winners for at this
    (device kind, geometry, dtype, code-rev) point, sorted ascending.

    This is the serving bucket-set derivation (docs/SERVING.md): the
    continuous-batching dispatcher pads every batch to one of these sizes,
    so every shape it hands the persistent compile cache is a shape the
    autotuner already swept — tuned winners apply and the cache hits.
    Stale-rev entries are excluded for the same reason ``load_plan``
    misses on them: their winners no longer describe the current kernels.
    Empty when the file is missing/unmatched — callers fall back to the
    powers-of-two default set."""
    plans = _read_plans(path)
    if not plans:
        return []
    rev = rev or code_rev()
    prefix = f"{device_kind}|{shape_key(model_cfg)}|b"
    suffix = f"|{dtype}|rev={rev}"
    batches = set()
    for key, obj in plans.items():
        if not (key.startswith(prefix) and key.endswith(suffix)):
            continue
        try:
            batches.add(int(obj["batch"]))
        except (KeyError, TypeError, ValueError):
            continue  # malformed entry: not a usable bucket, skip it
    return sorted(batches)


def policy_key(device_kind: str, shape_k: str, batch: int, rev: str) -> str:
    """Key of a dtype-policy record — the plan key WITHOUT the dtype field
    (the record's whole job is to say which dtype won at this point)."""
    return f"{device_kind}|{shape_k}|b{batch}|rev={rev}"


def save_policy(
    path,
    *,
    device_kind: str,
    model_cfg,
    batch: int,
    dtype: str,
    rev: Optional[str] = None,
    swept=(),
    pruned: Optional[Dict[str, str]] = None,
    gates: Optional[Dict[str, dict]] = None,
) -> str:
    """Persist the dtype-sweep winner for one (device, geometry, batch,
    code-rev) point into the plan file's ``policies`` section (sibling of
    ``plans``; the per-dtype kernel winners stay under their own keys).

    ``pruned`` records every gate-failed dtype with its attributable
    reason; ``gates`` the full per-dtype gate verdicts (margin and all).
    The gate's journaled
    ``gate_pass`` record is written by the gate itself at screening time;
    this record points at the same verdict."""
    path = Path(path)
    obj = _read_file(path)
    rev = rev or code_rev()
    key = policy_key(device_kind, shape_key(model_cfg), batch, rev)
    obj["policies"][key] = {
        "device_kind": device_kind,
        "shape_key": shape_key(model_cfg),
        "batch": batch,
        "code_rev": rev,
        "dtype": dtype,
        "swept": list(swept),
        "pruned": dict(pruned or {}),
        "gates": dict(gates or {}),
        "created": datetime.datetime.now(datetime.timezone.utc).strftime(
            "%Y-%m-%dT%H:%MZ"
        ),
    }
    _write_file(path, obj)
    return key


def load_policy(
    path,
    *,
    device_kind: str,
    model_cfg,
    batch: int,
    rev: Optional[str] = None,
    match_any_batch: bool = True,
) -> Optional[dict]:
    """The dtype-policy record for this point, or None. Same staleness and
    nearest-batch semantics as ``load_plan``: a different code_rev is a
    MISS, and with ``match_any_batch`` a same-device/geometry record tuned
    at another batch is the nearest usable point."""
    policies = _read_file(path)["policies"]
    if not policies:
        return None
    rev = rev or code_rev()
    sk = shape_key(model_cfg)
    exact = policies.get(policy_key(device_kind, sk, batch, rev))
    if exact is not None:
        return exact
    if not match_any_batch:
        return None
    prefix = f"{device_kind}|{sk}|b"
    suffix = f"|rev={rev}"
    for key in sorted(policies):
        if key.startswith(prefix) and key.endswith(suffix):
            return policies[key]
    return None


def effective_layer_variants(plan: Optional[TunePlan]) -> LayerVariants:
    """The lowering a forward builder closes over: each layer's tuned
    winner, the default ``KernelVariants()`` for every layer the plan does
    not cover — and for all of them when there is no plan."""
    return LayerVariants(layers=plan.layers if plan is not None else ())
