"""ToleranceGate: no lowered-precision candidate wins without beating the
fp32 oracle within budget.

The paper's staged-parallelism study treats the fp32 serial pass as ground
truth; this module is the machine-checkable form of that contract for the
precision subsystem. A gate screening runs the candidate policy and the
fp32 reference THROUGH THE SAME staged forward (per-layer taps at every
conv/pool/LRN boundary), compares each stage against its budget, and
journals a ``gate_pass``/``gate_fail`` record — the autotuner refuses to
let a non-fp32 dtype win (or even be swept) without a pass.

Trust chain: before trusting the on-device fp32 forward as the oracle, the
gate preflights ``resilience.sentinel.oracle_spot_check`` — the numpy
loop-nest oracle from ``tests/oracle.py`` (the reference's serial layer
semantics, hand-checkable) must agree with the device fp32 conv first. A
device whose fp32 path is itself off (the SDC class the sentinel hunts)
fails the gate for every candidate rather than blessing a matching error.

Budgets are per-stage max-abs / max-rel pairs; ``rel`` is normalized by
the oracle stage's max-|value| (elementwise relative error explodes near
zeros — LRN outputs cross zero). ``margin`` is the fraction of budget left
(1.0 = exact, 0.0 = at budget, negative = fail): the number a tuned
plan keeps with each verdict under ``gates``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import numpy as np

from ..models.alexnet import BLOCKS12
from .policy import DtypePolicy, jdt, resolve_policy

# Per-policy, per-stage budgets; "*" is the any-stage default. bf16 carries
# ~2^-8 operand rounding through two convs; int8w adds <= scale/2 per
# weight (~0.4% of the channel max) on top — budgets leave ~4x headroom
# over the observed CPU/TPU error so a genuine SDC or broken lowering
# (not rounding) is what trips them.
@dataclasses.dataclass(frozen=True)
class StageBudget:
    max_abs: float = math.inf
    max_rel: float = math.inf


DEFAULT_BUDGETS: Dict[str, Dict[str, StageBudget]] = {
    "fp32": {
        "*": StageBudget(max_abs=1e-4, max_rel=1e-5),
        # Block-granularity rows (the megakernel screen): the "*" budget is
        # calibrated for the oracle comparing against ITSELF through the
        # same staged ops; the fused fp32 kernel is a different lowering
        # whose fp32 MACs accumulate in a different order (~1 ulp per step,
        # observed 1.3e-4 abs through conv1's 363-term dots at the full 227
        # geometry) — still ~7x headroom below these, so a broken fusion
        # (not reassociation) is what trips them.
        "block1": StageBudget(max_abs=1e-3, max_rel=1e-4),
        "block2": StageBudget(max_abs=1e-3, max_rel=1e-4),
    },
    "bf16": {"*": StageBudget(max_rel=2e-2)},
    "int8w": {"*": StageBudget(max_rel=6e-2)},
}


@dataclasses.dataclass
class StageCheck:
    stage: str
    max_abs: float
    max_rel: float  # |cand-oracle|max / |oracle|max
    abs_budget: float
    rel_budget: float

    @property
    def passed(self) -> bool:
        return self.max_abs <= self.abs_budget and self.max_rel <= self.rel_budget

    @property
    def margin(self) -> float:
        """Fraction of budget unspent; the binding (smaller) of abs/rel."""
        m = 1.0
        if math.isfinite(self.abs_budget) and self.abs_budget > 0:
            m = min(m, 1.0 - self.max_abs / self.abs_budget)
        if math.isfinite(self.rel_budget) and self.rel_budget > 0:
            m = min(m, 1.0 - self.max_rel / self.rel_budget)
        return m

    def to_obj(self) -> dict:
        return {
            "stage": self.stage,
            "max_abs": float(self.max_abs),
            "max_rel": float(self.max_rel),
            "abs_budget": self.abs_budget if math.isfinite(self.abs_budget) else None,
            "rel_budget": self.rel_budget if math.isfinite(self.rel_budget) else None,
            "passed": self.passed,
            "margin": round(self.margin, 6),
        }


@dataclasses.dataclass
class GateResult:
    policy: str
    stages: List[StageCheck] = dataclasses.field(default_factory=list)
    oracle_fault: str = ""  # non-empty: the fp32 oracle itself failed preflight

    @property
    def passed(self) -> bool:
        return not self.oracle_fault and all(s.passed for s in self.stages)

    @property
    def margin(self) -> float:
        if self.oracle_fault:
            return -math.inf
        return min((s.margin for s in self.stages), default=1.0)

    @property
    def worst_stage(self) -> str:
        if not self.stages:
            return ""
        return min(self.stages, key=lambda s: s.margin).stage

    def reason(self) -> str:
        """Attributable verdict line — what a pruned dtype's record says."""
        if self.oracle_fault:
            return f"{self.policy}: {self.oracle_fault}"
        if self.passed:
            return ""
        s = min(self.stages, key=lambda s: s.margin)
        parts = []
        if s.max_rel > s.rel_budget:
            parts.append(f"max_rel {s.max_rel:.3e} > budget {s.rel_budget:.1e}")
        if s.max_abs > s.abs_budget:
            parts.append(f"max_abs {s.max_abs:.3e} > budget {s.abs_budget:.1e}")
        return f"{self.policy}: stage {s.stage} " + ", ".join(parts)

    def to_obj(self) -> dict:
        return {
            "policy": self.policy,
            "passed": self.passed,
            "margin": None if self.margin == -math.inf else round(self.margin, 6),
            "worst_stage": self.worst_stage,
            "oracle_fault": self.oracle_fault,
            "reason": self.reason(),
            "stages": [s.to_obj() for s in self.stages],
        }


def staged_policy_outputs(params, x, cfg=BLOCKS12, policy="fp32") -> Dict[str, np.ndarray]:
    """fp32 copies of every layer-boundary activation under ``policy`` —
    the comparison surface both gate sides run through.

    The fp32 policy IS the oracle side (reference ops, ``Precision.
    HIGHEST`` true-fp32 MACs, the tier every golden number was minted on).
    bf16 casts operands per layer and pins fp32 accumulation via
    ``preferred_element_type``; int8w delegates to the quantized forward's
    taps (one implementation — the gate screens the path that ships)."""
    import jax.numpy as jnp

    from ..ops import reference as ops

    pol = resolve_policy(policy)
    if pol.quantized:
        from .quantize import forward_blocks12_int8w

        _out, stages = forward_blocks12_int8w(
            params, x, cfg, tier="reference", taps=True
        )
        return {k: np.asarray(v) for k, v in stages.items()}

    from jax import lax

    stages: Dict[str, np.ndarray] = {}
    cur = x
    c1, p1, c2, p2, n2 = cfg.conv1, cfg.pool1, cfg.conv2, cfg.pool2, cfg.lrn2
    for cname, cspec, pname, pspec in (
        ("conv1", c1, "pool1", p1),
        ("conv2", c2, "pool2", p2),
    ):
        lp = pol.layer(cname)
        cdt, adt = jdt(lp.compute), jdt(lp.accumulate)
        w = params[cname]["w"].astype(jdt(lp.params))
        b = params[cname]["b"]
        cur = ops.conv2d(
            cur.astype(cdt),
            w,
            b.astype(adt),
            stride=cspec.stride,
            padding=cspec.padding,
            precision=(
                lax.Precision.HIGHEST if lp.compute == "float32"
                else lax.Precision.DEFAULT
            ),
            preferred_element_type=adt,
        )
        cur = ops.relu(cur).astype(cdt)
        stages[cname] = np.asarray(cur, np.float32)
        cur = ops.maxpool(cur, window=pspec.window, stride=pspec.stride)
        stages[pname] = np.asarray(cur, np.float32)
    out = ops.lrn(
        cur.astype(jnp.float32),
        size=n2.size, alpha=n2.alpha, beta=n2.beta, k=n2.k,
        alpha_over_size=n2.alpha_over_size,
    )
    stages["lrn2"] = np.asarray(out, np.float32)
    return stages


# The fused megakernel's comparison surface: each block's single output,
# joined to the staged oracle at the block BOUNDARY stages (a fused block
# has no interior taps to compare — block granularity is the honest one).
BLOCK_BOUNDARIES = (("block1", "pool1"), ("block2", "lrn2"))


def megakernel_block_outputs(
    params, x, cfg=BLOCKS12, policy="fp32", variants=None
) -> Dict[str, np.ndarray]:
    """fp32 copies of the fused megakernel's block outputs under ``policy``
    — the candidate side of :meth:`ToleranceGate.screen_blocks`. Runs both
    blocks through ``ops.megakernel`` (whole image per program, the only
    regime block fusion has), int8w via the dequant-free epilogue-rescale
    variant."""
    import jax.numpy as jnp

    from ..ops import megakernel as mk
    from ..ops import pallas_kernels as pk

    pol = resolve_policy(policy)
    c1, p1, c2, p2, n2 = cfg.conv1, cfg.pool1, cfg.conv2, cfg.pool2, cfg.lrn2
    v = variants if variants is not None else pk.KernelVariants()
    conv_v = v.conv if v.conv in ("taps", "vcol") else "vcol"
    out: Dict[str, np.ndarray] = {}
    blocks = (("block1", "conv1", c1, p1, None), ("block2", "conv2", c2, p2, n2))
    if pol.quantized:
        from .quantize import quantize_conv_params

        qp = quantize_conv_params(params)
        cur = x.astype(jnp.bfloat16)
        for bname, cname, cspec, pspec, lrn in blocks:
            ho = (
                cur.shape[1] + 2 * cspec.padding - cspec.filter_size
            ) // cspec.stride + 1
            e = qp[cname]
            cur = mk.int8w_conv_block_pallas(
                cur, e["q"], e["scale"], e["b"],
                stride=cspec.stride, padding=cspec.padding,
                pool_window=pspec.window, pool_stride=pspec.stride,
                lrn=lrn, variant=conv_v, row_block=max(v.row_block, ho),
            )
            out[bname] = np.asarray(cur, np.float32)
        return out
    cur = x
    for bname, cname, cspec, pspec, lrn in blocks:
        lp = pol.layer(cname)
        cdt = jdt(lp.compute)
        ho = (
            cur.shape[1] + 2 * cspec.padding - cspec.filter_size
        ) // cspec.stride + 1
        cur = mk.conv_block_pallas(
            cur.astype(cdt),
            params[cname]["w"].astype(jdt(lp.params)),
            params[cname]["b"].astype(cdt),
            stride=cspec.stride, padding=cspec.padding,
            pool_window=pspec.window, pool_stride=pspec.stride,
            lrn=lrn, variant=conv_v, row_block=max(v.row_block, ho),
        )
        out[bname] = np.asarray(cur, np.float32)
    return out


class ToleranceGate:
    """Screen a candidate policy against the fp32 oracle, stage by stage.

    ``budgets``: ``{policy_name: {stage_or_"*": StageBudget}}`` overrides
    (missing entries fall back to :data:`DEFAULT_BUDGETS`). ``journal``: a
    ``resilience.journal.Journal`` receiving one fsync'd ``gate_pass`` /
    ``gate_fail`` record per screening — the durable evidence the
    autotuner's persistence keys on."""

    def __init__(self, budgets=None, journal=None, preflight: bool = True):
        self.budgets = dict(DEFAULT_BUDGETS)
        if budgets:
            self.budgets.update(budgets)
        self.journal = journal
        self.preflight = preflight

    def budget_for(self, policy: str, stage: str) -> StageBudget:
        table = self.budgets.get(policy, {})
        return table.get(stage) or table.get("*") or StageBudget()

    def screen(
        self,
        policy,
        params,
        x,
        model_cfg=BLOCKS12,
        *,
        key: str = "",
        candidate_params=None,
    ) -> GateResult:
        """One screening: oracle and candidate staged forwards, per-stage
        compare, journaled verdict.

        ``candidate_params``: optional distinct param tree for the
        candidate side — the SDC-drill surface (a corrupted replica gated
        against the clean oracle must fail)."""
        pol: DtypePolicy = resolve_policy(policy)
        res = GateResult(policy=pol.name)
        if self.preflight:
            from ..resilience.sentinel import oracle_spot_check

            err = oracle_spot_check()
            if err is not None and err > 1e-3:
                res.oracle_fault = (
                    f"fp32 oracle failed preflight: device fp32 conv deviates "
                    f"from the tests/oracle.py loop oracle by {err:.3e}"
                )
                self._journal(res, key)
                return res
        oracle = staged_policy_outputs(params, x, model_cfg, "fp32")
        if pol.name == "fp32" and candidate_params is None:
            # The oracle trivially matches itself; record exact stages so
            # the margin/journal schema stays uniform.
            for stage in oracle:
                b = self.budget_for("fp32", stage)
                res.stages.append(
                    StageCheck(stage, 0.0, 0.0, b.max_abs, b.max_rel)
                )
            self._journal(res, key)
            return res
        cand = staged_policy_outputs(
            candidate_params if candidate_params is not None else params,
            x, model_cfg, pol,
        )
        for stage, want in oracle.items():
            got = cand[stage]
            diff = float(np.max(np.abs(got - want))) if want.size else 0.0
            denom = float(np.max(np.abs(want))) if want.size else 0.0
            rel = diff / denom if denom > 0 else (0.0 if diff == 0.0 else math.inf)
            b = self.budget_for(pol.name, stage)
            res.stages.append(StageCheck(stage, diff, rel, b.max_abs, b.max_rel))
        self._journal(res, key)
        return res

    def screen_blocks(
        self,
        policy,
        params,
        x,
        model_cfg=BLOCKS12,
        *,
        variants=None,
        key: str = "",
    ) -> GateResult:
        """Screen the fused megakernel at BLOCK granularity: each block's
        single output vs the fp32 staged oracle at the block-boundary
        stages (``BLOCK_BOUNDARIES``). This is the screen that guards the
        ``fuse="block"`` candidates — a fused block has no interior taps,
        so per-stage comparison would be fake; the honest surface is the
        block output, and the budgets are the boundary stage's (falling
        back to the policy's "*" row). Journals ``gate_pass``/``gate_fail``
        like :meth:`screen`."""
        pol: DtypePolicy = resolve_policy(policy)
        res = GateResult(policy=pol.name)
        if self.preflight:
            from ..resilience.sentinel import oracle_spot_check

            err = oracle_spot_check()
            if err is not None and err > 1e-3:
                res.oracle_fault = (
                    f"fp32 oracle failed preflight: device fp32 conv deviates "
                    f"from the tests/oracle.py loop oracle by {err:.3e}"
                )
                self._journal(res, key)
                return res
        oracle = staged_policy_outputs(params, x, model_cfg, "fp32")
        try:
            cand = megakernel_block_outputs(
                params, x, model_cfg, pol, variants=variants
            )
        except Exception as e:  # noqa — an unlowerable megakernel must fail, not wedge
            b = self.budget_for(pol.name, "block1")
            res.stages.append(
                StageCheck(
                    f"megakernel-error:{type(e).__name__}",
                    math.inf, math.inf, b.max_abs, b.max_rel,
                )
            )
            self._journal(res, key)
            return res
        for bname, boundary in BLOCK_BOUNDARIES:
            want, got = oracle[boundary], cand[bname]
            diff = float(np.max(np.abs(got - want))) if want.size else 0.0
            denom = float(np.max(np.abs(want))) if want.size else 0.0
            rel = diff / denom if denom > 0 else (0.0 if diff == 0.0 else math.inf)
            b = self.budget_for(pol.name, bname)
            res.stages.append(StageCheck(bname, diff, rel, b.max_abs, b.max_rel))
        self._journal(res, key)
        return res

    def screen_sharded(
        self,
        policy,
        params,
        x,
        model_cfg=BLOCKS12,
        *,
        n_shards: int,
        tier: str = "reference",
        staged: bool = False,
        key: str = "",
    ) -> GateResult:
        """Per-rung screen for the sharded tier: the full sharded forward's
        FINAL output under ``policy`` (int8w runs the quantized sharded
        path) vs the fp32 staged oracle's lrn2 boundary. Shard count is
        part of the journaled key — the halo/mask machinery must hold the
        budget at EVERY rung, not just n=1."""
        import jax.numpy as jnp

        from ..parallel.sharded import build_sharded_forward

        pol: DtypePolicy = resolve_policy(policy)
        res = GateResult(policy=pol.name)
        if self.preflight:
            from ..resilience.sentinel import oracle_spot_check

            err = oracle_spot_check()
            if err is not None and err > 1e-3:
                res.oracle_fault = (
                    f"fp32 oracle failed preflight: device fp32 conv deviates "
                    f"from the tests/oracle.py loop oracle by {err:.3e}"
                )
                self._journal(res, key or f"gate-sharded:{pol.name}|n{n_shards}")
                return res
        want = staged_policy_outputs(params, x, model_cfg, "fp32")["lrn2"]
        # bf16 rung: the same casts configs.build_forward ships.
        bf16 = not (pol.quantized or pol.name == "fp32")
        fwd = build_sharded_forward(
            model_cfg, n_shards, tier=tier, staged=staged,
            quantized=pol.quantized,
            compute_dtype=jnp.bfloat16 if bf16 else None,
        )
        got = np.asarray(fwd(params, x), np.float32)
        stage = f"lrn2@n{n_shards}"
        diff = float(np.max(np.abs(got - want))) if want.size else 0.0
        denom = float(np.max(np.abs(want))) if want.size else 0.0
        rel = diff / denom if denom > 0 else (0.0 if diff == 0.0 else math.inf)
        b = self.budget_for(pol.name, "lrn2")
        res.stages.append(StageCheck(stage, diff, rel, b.max_abs, b.max_rel))
        self._journal(res, key or f"gate-sharded:{pol.name}|n{n_shards}")
        return res

    def _journal(self, res: GateResult, key: str) -> None:
        if self.journal is not None:
            # Optional trace correlation (observability.trace): a verdict
            # screened inside a traced tuning sweep carries the sweep
            # span's ids; untraced runs journal the PR 7 schema unchanged.
            from ..observability.trace import current_ids

            self.journal.append(
                "gate_pass" if res.passed else "gate_fail",
                key=key or f"gate:{res.policy}",
                **current_ids(),
                **res.to_obj(),
            )
