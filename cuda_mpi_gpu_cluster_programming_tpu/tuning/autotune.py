"""The kernel autotuner: sweep, time, pick, persist.

Per conv layer (the ``_conv_then_pool`` lowering unit) the sweep times every
legal ``KernelVariants`` candidate with the repo's chained-timing discipline
(``utils.timing.amortized_stats``: warmup chain, repeat chain, CI95 on the
median — the same estimator every committed headline uses, so tuned-vs-
default deltas are apples-to-apples) and persists the winners as a
``TunePlan`` keyed by (device kind, geometry, batch, dtype, code revision).

Resilience contract (PR-1 layer): the whole sweep runs under a ``Deadline``
and every candidate is a chaos-injectable ``kernel_compile`` site. A
candidate that fails to compile/lower is recorded and skipped; a layer whose
candidates ALL fail, or that the deadline cuts off, degrades to the DEFAULT
variants — the plan says so in ``degraded`` and per-layer stats, and the
caller gets a usable plan instead of a wedge.
"""

from __future__ import annotations

import dataclasses
import functools
from pathlib import Path
from typing import Callable, Dict, Optional, Tuple

from ..observability.trace import span as obs_span
from ..ops.pallas_kernels import KernelVariants
from ..ops.vma import interpret_mode
from ..resilience import chaos
from ..resilience.policy import Deadline
from .plan import (
    TunePlan,
    code_rev,
    load_plan,
    load_policy,
    save_plan,
    save_policy,
    shape_key,
)
from .space import ConvGeometry, candidate_space, layer_tuning_units

# The dtype dimension of the sweep — precision policy names, reference
# floor first (also the deterministic tie-break order).
DTYPES = ("fp32", "bf16", "int8w")

# timer(geometry, variants, dtype, batch, repeats, warmup) -> (ms, ci95, n).
# Injectable so tier-1 tests sweep deterministically without timing jax.
Timer = Callable[[ConvGeometry, KernelVariants, str, int, int, int],
                 Tuple[float, float, int]]


def _default_timer(
    g: ConvGeometry, v: KernelVariants, dtype: str, batch: int,
    repeats: int, warmup: int,
) -> Tuple[float, float, int]:
    """Time one candidate on the real backend via the production lowering
    path (``_conv_then_pool``, or its quantized counterpart for int8w —
    the same gates the model forward runs)."""
    import jax
    import jax.numpy as jnp

    from ..models.alexnet import ConvSpec, LrnSpec, PoolSpec
    from ..ops import pallas_kernels as pk
    from ..ops.pallas_model import _conv_then_pool
    from ..utils.timing import amortized_stats

    cspec = ConvSpec(g.out_channels, g.filter_size, g.stride, g.padding)
    pspec = PoolSpec(g.pool_window, g.pool_stride) if g.has_pool else None
    # The block's trailing LRN (when the model has one) is timed for EVERY
    # candidate — fused candidates fold it in-kernel, staged candidates run
    # it as the trailing launch — so fused-vs-staged compare equal work.
    lrn = LrnSpec(*g.lrn) if g.lrn else None
    # The scope names of this conv's block: its pool and LRN share its index.
    idx = g.name[len("conv"):]
    names = (g.name, f"pool{idx}", f"lrn{idx}")[: 3 if lrn else 2]
    n_small = max(1, warmup)
    if dtype == "int8w":
        # The quantized lowering unit: bf16 activations, int8-valued bf16
        # weights, fp32 accumulate, per-channel rescale + bias + ReLU
        # between conv and pool (precision.quantize.int8w_conv_then_pool).
        from ..precision.quantize import int8w_conv, int8w_conv_then_pool

        x = jnp.full((batch, g.in_h, g.in_w, g.in_channels), 1.0, jnp.bfloat16)
        q = jnp.ones(
            (g.filter_size, g.filter_size, g.in_channels, g.out_channels),
            jnp.int8,
        )
        s = jnp.full((g.out_channels,), 0.01, jnp.float32)
        b = jnp.zeros((g.out_channels,), jnp.float32)
        if pspec is not None:
            fn = jax.jit(
                lambda x, q, s, b: int8w_conv_then_pool(
                    x, q, s, b, cspec, pspec, names, v, tier="pallas", lrn=lrn
                )
            )
        else:
            fn = jax.jit(
                lambda x, q, s, b: int8w_conv(
                    x, q, s, b, stride=g.stride, padding=g.padding,
                    tier="pallas", variants=v,
                )
            )
        st = amortized_stats(
            fn, x, q, s, b,
            n_small=n_small, n_large=n_small + max(1, repeats),
            min_samples=2, max_samples=4,
        )
        return st.per_call_ms, st.ci95_ms, st.n_samples
    jdt = jnp.bfloat16 if dtype == "bf16" else jnp.float32
    x = jnp.full((batch, g.in_h, g.in_w, g.in_channels), 1.0, jdt)
    w = jnp.full(
        (g.filter_size, g.filter_size, g.in_channels, g.out_channels), 0.01, jdt
    )
    b = jnp.zeros((g.out_channels,), jdt)
    if g.has_pool:
        fn = jax.jit(
            lambda x, w, b: _conv_then_pool(x, w, b, cspec, pspec, v, names, lrn=lrn)
        )
    else:
        fn = jax.jit(
            functools.partial(
                pk.conv2d_pallas, stride=g.stride, padding=g.padding, relu=True,
                variant=v.conv, row_block=v.row_block, k_block=v.k_block,
            )
        )
    st = amortized_stats(
        fn, x, w, b,
        n_small=n_small, n_large=n_small + max(1, repeats),
        min_samples=2, max_samples=4,
    )
    return st.per_call_ms, st.ci95_ms, st.n_samples


def tune_layer(
    g: ConvGeometry,
    *,
    dtype: str,
    batch: int,
    deadline: Deadline,
    repeats: int,
    warmup: int,
    timer: Timer,
    log: Callable[[str], None],
    interpret: Optional[bool] = None,
    block_screen: str = "",
) -> Tuple[KernelVariants, dict, str]:
    """Sweep one layer; returns (winner, stats, degraded_reason).

    ``block_screen``: a non-empty string prunes every ``fuse="block"``
    candidate with that reason BEFORE timing — the dtype sweep passes the
    ToleranceGate's block-screen failure here, so a megakernel that fails
    its fp32-oracle screen never spends timing budget and its fate is
    attributable in the plan record (``pruned_reasons``), exactly like a
    geometry prune."""
    interpret = interpret_mode() if interpret is None else interpret
    default = KernelVariants().bind(g.out_channels)
    pruned: list = []
    cands = candidate_space(
        g, interpret=interpret, dtype=dtype,
        on_prune=lambda v, why: pruned.append(why),
    )
    if block_screen:
        kept = []
        for v in cands:
            if v.fuse == "block":
                pruned.append(block_screen)
            else:
                kept.append(v)
        cands = kept
    ch = chaos.active()
    timed: list = []   # (ms, ci95, n, variants)
    failed: list = []  # (variants, cause)
    cut = ""
    for v in cands:
        if deadline.expired:
            cut = (
                f"deadline expired after {len(timed) + len(failed)}/"
                f"{len(cands)} candidates"
            )
            break
        try:
            if ch is not None:
                ch.maybe_raise("kernel_compile", f"tune {g.name} [{v.label()}]")
            # One span per timed candidate (observability.trace; no-op
            # untraced): the sweep's wall time becomes attributable
            # per-candidate in the exported timeline.
            with obs_span(
                "tune.candidate", layer=g.name, variant=v.label(), dtype=dtype
            ) as sp:
                ms, ci, n = timer(g, v, dtype, batch, repeats, warmup)
                if sp is not None:
                    sp.set(ms=round(ms, 4), ci95_ms=round(ci, 4), n=n)
            timed.append((ms, ci, n, v))
            log(f"tune {g.name}: {v.label()} -> {ms:.3f} ms (ci95 {ci:.3f}, n={n})")
        except Exception as e:  # noqa — a broken candidate must not kill the sweep
            cause = f"{type(e).__name__}: {e}"[:120]
            failed.append((v, cause))
            log(f"tune {g.name}: {v.label()} FAILED ({cause})")
    # Attributable prunes in the persisted record: reason -> count, so a
    # plan says WHY every dropped candidate (geometry, dtype policy, or a
    # gate-failed megakernel) is absent — not just how many.
    reasons: dict = {}
    for why in pruned:
        reasons[why] = reasons.get(why, 0) + 1
    stats = {
        "geometry": g.describe(),
        "candidates": len(cands),
        "pruned": len(pruned),
        "pruned_reasons": reasons,
        "timed": len(timed),
        "failed": len(failed),
    }
    if not timed:
        reason = cut or (
            f"all {len(cands)} candidates failed" if failed else "no legal candidates"
        )
        stats["degraded"] = reason
        log(f"tune {g.name}: DEGRADED to defaults ({reason})")
        return default, stats, reason
    best_ms, best_ci, best_n, winner = min(timed, key=lambda t: t[0])
    stats.update(
        best_ms=round(best_ms, 4), best_ci95_ms=round(best_ci, 4), best_n=best_n
    )
    # The default lowering's time, for the tuned-vs-default story — matched
    # by effective signature (rb=64 and rb=32 can be the same lowering on a
    # 27-row image; either row is THE default's measurement).
    from .space import _effective_signature

    dsig = _effective_signature(default, g)
    for ms, _ci, _n, v in timed:
        if _effective_signature(v, g) == dsig:
            stats["default_ms"] = round(ms, 4)
            break
    if cut:
        stats["degraded"] = cut  # partial sweep: winner stands, but say so
    log(
        f"tune {g.name}: winner {winner.label()} at {best_ms:.3f} ms"
        + (f" (default {stats['default_ms']:.3f} ms)" if "default_ms" in stats else "")
    )
    return winner, stats, cut


def autotune_model(
    model_cfg,
    *,
    dtype: str,
    batch: int,
    deadline: Optional[Deadline] = None,
    repeats: int = 5,
    warmup: int = 2,
    timer: Optional[Timer] = None,
    log: Callable[[str], None] = print,
    device_kind: Optional[str] = None,
    block_screen: str = "",
) -> TunePlan:
    """Sweep every conv layer of ``model_cfg`` and return the TunePlan."""
    deadline = deadline or Deadline.after(None)
    timer = timer or _default_timer
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    layers: list = []
    stats: dict = {}
    notes: list = []
    for name, g in layer_tuning_units(model_cfg):
        if deadline.expired:
            # Degrade, don't wedge: remaining layers get the defaults and the
            # plan says which and why.
            layers.append((name, KernelVariants().bind(g.out_channels)))
            stats[name] = {
                "geometry": g.describe(),
                "degraded": "deadline expired before sweep",
            }
            notes.append(f"{name}: deadline expired before sweep")
            continue
        with obs_span("tune.layer", layer=name, dtype=dtype, batch=batch):
            winner, lstats, degraded = tune_layer(
                g, dtype=dtype, batch=batch, deadline=deadline,
                repeats=repeats, warmup=warmup, timer=timer, log=log,
                block_screen=block_screen,
            )
        layers.append((name, winner))
        stats[name] = lstats
        if degraded:
            notes.append(f"{name}: {degraded}")
    return TunePlan(
        device_kind=device_kind,
        shape_key=shape_key(model_cfg),
        batch=batch,
        dtype=dtype,
        code_rev=code_rev(),
        layers=tuple(layers),
        stats=stats,
        degraded="; ".join(notes),
    )


def autotune(
    path,
    model_cfg,
    *,
    dtype: str,
    batch: int,
    force: bool = False,
    deadline: Optional[Deadline] = None,
    repeats: int = 5,
    warmup: int = 2,
    timer: Optional[Timer] = None,
    log: Callable[[str], None] = print,
    device_kind: Optional[str] = None,
    block_screen: str = "",
) -> Tuple[TunePlan, bool]:
    """Cached sweep: a fresh on-disk plan for this exact point (same device,
    geometry, batch, dtype, code revision) short-circuits the whole sweep —
    ``(plan, True)``. Otherwise sweep, persist, ``(plan, False)``."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    if not force:
        cached = load_plan(
            path, device_kind=device_kind, model_cfg=model_cfg,
            dtype=dtype, batch=batch, match_any_batch=False,
        )
        if cached is not None:
            return cached, True
    plan = autotune_model(
        model_cfg, dtype=dtype, batch=batch, deadline=deadline,
        repeats=repeats, warmup=warmup, timer=timer, log=log,
        device_kind=device_kind, block_screen=block_screen,
    )
    save_plan(plan, path)
    return plan, False


# --------------------------------------------------------- dtype sweep ----


@dataclasses.dataclass
class PrecisionResult:
    """Outcome of one dtype-swept autotune: per-dtype kernel plans, the
    winning policy, and the attributable fate of every pruned dtype."""

    winner: str
    plans: Dict[str, TunePlan]
    pruned: Dict[str, str]  # dtype -> gate reason (attributable, journaled)
    gates: Dict[str, dict]  # dtype -> GateResult.to_obj()
    cached: bool = False

    @property
    def plan(self) -> Optional[TunePlan]:
        return self.plans.get(self.winner)

    def summary(self) -> str:
        parts = []
        for dt in DTYPES:
            if dt in self.pruned:
                parts.append(f"{dt}=gate-pruned")
            elif dt in self.plans:
                s = _plan_score(self.plans[dt])
                label = f"{s:.3f}ms" if s != float("inf") else "degraded"
                parts.append(f"{dt}={label}" + (" *" if dt == self.winner else ""))
        return " ".join(parts)


def _plan_score(plan: TunePlan) -> float:
    """Total best-candidate time across the plan's layers — the number the
    dtype race is decided on. A layer that degraded without a timed winner
    makes the whole dtype unscoreable (inf): an untimed dtype must not win."""
    total = 0.0
    for name, _v in plan.layers:
        ms = plan.stats.get(name, {}).get("best_ms")
        if not isinstance(ms, (int, float)):
            return float("inf")
        total += ms
    return total


def autotune_precision(
    path,
    model_cfg,
    *,
    batch: int,
    dtypes: Tuple[str, ...] = DTYPES,
    force: bool = False,
    deadline: Optional[Deadline] = None,
    repeats: int = 5,
    warmup: int = 2,
    timer: Optional[Timer] = None,
    log: Callable[[str], None] = print,
    device_kind: Optional[str] = None,
    gate=None,
    gate_journal: str = "",
    gate_batch: int = 2,
    seed: int = 0,
) -> PrecisionResult:
    """ONE sweep covering {fp32, bf16, int8w} x kernel variants per conv
    layer (ROADMAP item 2's first half).

    Per dtype: every non-fp32 candidate is first screened by the
    :class:`~..precision.gate.ToleranceGate` against the fp32 oracle on
    params/input drawn from the seeded init stream — a gate failure prunes
    the WHOLE dtype with an attributable reason (journaled ``gate_fail``)
    before a second of timing budget is spent on it. Surviving dtypes get
    the full per-layer kernel-variant sweep (``autotune`` — each dtype's
    plan lands under its own key, so fp32 is always kept as the reference
    floor). The winner is the dtype with the lowest summed best-candidate
    time; its policy record is persisted next to the plans
    (``plan.save_policy``) — by construction a non-fp32 winner exists only
    with a journaled ``gate_pass`` record.

    Blocks 1-2 geometries only (the gate's staged oracle is the Blocks 1-2
    chain); full-AlexNet callers keep using single-dtype ``autotune``."""
    if hasattr(model_cfg, "blocks12"):
        raise ValueError(
            "dtype-swept autotune supports Blocks 1-2 configs only "
            "(the tolerance gate screens the Blocks 1-2 staged oracle); "
            "use autotune(dtype=...) for alexnet_full"
        )
    unknown = [dt for dt in dtypes if dt not in DTYPES]
    if unknown:
        raise ValueError(f"unknown sweep dtypes {unknown} (valid: {DTYPES})")
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind

    # Cache: a fresh policy record covering the SAME dtype set, plus a
    # fresh plan per surviving dtype, short-circuits gate + sweep alike.
    if not force:
        rec = load_policy(
            path, device_kind=device_kind, model_cfg=model_cfg, batch=batch,
            match_any_batch=False,
        )
        if rec is not None and set(rec.get("swept", [])) == set(dtypes):
            plans = {}
            complete = True
            for dt in dtypes:
                if dt in rec.get("pruned", {}):
                    continue
                cached_plan = load_plan(
                    path, device_kind=device_kind, model_cfg=model_cfg,
                    dtype=dt, batch=batch, match_any_batch=False,
                )
                if cached_plan is None:
                    complete = False
                    break
                plans[dt] = cached_plan
            if complete and rec.get("dtype") in plans:
                return PrecisionResult(
                    winner=rec["dtype"],
                    plans=plans,
                    pruned=dict(rec.get("pruned", {})),
                    gates=dict(rec.get("gates", {})),
                    cached=True,
                )

    if gate is None:
        from ..precision.gate import ToleranceGate
        from ..resilience.journal import Journal

        jpath = gate_journal or str(Path(path).with_name(
            Path(path).stem + "_gate.jsonl"
        ))
        gate = ToleranceGate(journal=Journal(jpath))

    # Gate inputs come from the seeded init stream — the keyed random init
    # (constant init is degenerate for per-channel scales: every channel
    # identical), reproducible across processes from the seed alone.
    import jax

    from ..models.init import init_params_random, random_input

    kp, kx = jax.random.split(jax.random.PRNGKey(seed))
    params = init_params_random(kp, model_cfg)
    x = random_input(kx, gate_batch, model_cfg)

    sk = shape_key(model_cfg)
    plans: Dict[str, TunePlan] = {}
    pruned: Dict[str, str] = {}
    gates: Dict[str, dict] = {}
    inner_cached: list = []
    for dt in dtypes:
        with obs_span("tune.gate", dtype=dt):
            res = gate.screen(
                dt, params, x, model_cfg,
                key=f"gate:{dt}|{device_kind}|{sk}|b{batch}",
            )
        gates[dt] = res.to_obj()
        if not res.passed:
            # fp32 failing means the ORACLE CHAIN is broken (preflight or
            # budgets) — prune it like any other dtype; the caller sees an
            # attributable reason instead of a silently-blessed floor.
            pruned[dt] = res.reason()
            log(f"tune dtype {dt}: GATE-PRUNED ({res.reason()})")
            continue
        log(
            f"tune dtype {dt}: gate pass (margin {res.margin:.3f}, "
            f"worst stage {res.worst_stage or '-'})"
        )
        # Second screen, block granularity: the megakernel's fused block
        # outputs vs the fp32 oracle's block boundaries. A failure prunes
        # ONLY the fuse="block" candidates for this dtype (journaled,
        # reason lands in the plan's pruned_reasons) — the staged chain
        # already passed its per-stage screen above. Injectable gates
        # without the method (test stubs) skip the screen.
        block_screen = ""
        if hasattr(gate, "screen_blocks"):
            with obs_span("tune.gate_blocks", dtype=dt):
                bres = gate.screen_blocks(
                    dt, params, x, model_cfg,
                    key=f"gate-blocks:{dt}|{device_kind}|{sk}|b{batch}",
                )
            if not bres.passed:
                block_screen = (
                    f"fuse=block gate-pruned for {dt}: {bres.reason()}"
                )
                log(f"tune dtype {dt}: megakernel {block_screen}")
            else:
                log(
                    f"tune dtype {dt}: megakernel block gate pass "
                    f"(margin {bres.margin:.3f})"
                )
        plan, was_cached = autotune(
            path, model_cfg, dtype=dt, batch=batch, force=force,
            deadline=deadline, repeats=repeats, warmup=warmup, timer=timer,
            log=log, device_kind=device_kind, block_screen=block_screen,
        )
        plans[dt] = plan
        inner_cached.append(was_cached)
        log(
            f"tune dtype {dt}: plan {'cache' if was_cached else 'swept'} "
            f"hash={plan.plan_hash()}"
        )

    if not plans:
        raise RuntimeError(
            "every sweep dtype was gate-pruned: "
            + "; ".join(f"{d}: {r}" for d, r in pruned.items())
        )
    scores = {dt: _plan_score(p) for dt, p in plans.items()}
    finite = {dt: s for dt, s in scores.items() if s != float("inf")}
    if finite:
        winner = min(finite, key=lambda dt: (finite[dt], DTYPES.index(dt)))
    else:
        # Nothing timed anywhere (deadline/chaos): the reference floor
        # stands if present; otherwise the first surviving dtype.
        winner = "fp32" if "fp32" in plans else next(iter(plans))
    if len(dtypes) > 1:
        # Single-dtype (pinned) sweeps must not clobber the full-sweep
        # policy record with a race they never ran.
        save_policy(
            path, device_kind=device_kind, model_cfg=model_cfg, batch=batch,
            dtype=winner, swept=dtypes, pruned=pruned, gates=gates,
        )
    result = PrecisionResult(
        winner=winner, plans=plans, pruned=pruned, gates=gates,
        # A pinned (single-dtype) re-run whose every inner sweep hit the
        # plan cache is a cache outcome too, even though no policy record
        # short-circuited it (only multi-dtype sweeps write the record).
        cached=bool(inner_cached) and all(inner_cached) and not force,
    )
    log(f"tune dtype winner: {winner} ({result.summary()})")
    return result
