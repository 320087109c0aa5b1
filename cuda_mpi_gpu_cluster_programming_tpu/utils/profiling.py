"""Profiling: per-layer breakdown and trace capture.

The reference's only profiling is one wall-clock print per pass — the
"completed in X ms" line its harness regexes (SURVEY §5.1: "timing
print-format IS the profiling API") — while per-phase breakdowns and real
profilers are documented as future work (reference README.md:233,720-735).
This module ships them:

- :func:`layer_breakdown` — fenced per-layer wall timing (each prefix of the
  layer chain jitted separately; per-layer cost by differencing is wrong on
  an async device, so each stage is timed end-to-end on its own).
- :func:`trace` — ``jax.profiler.trace`` wrapper writing a TensorBoard-able
  trace directory. The production forwards name every layer themselves
  (``jax.named_scope``, ``ops/scopes.py``), so a trace of ``run.py --profile
  DIR`` attributes device time per layer with no second copy of the forward.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Callable, List, Tuple

import jax

from ..models.alexnet import BLOCKS12, ConvSpec, LrnSpec, Params, PoolSpec
from ..ops import reference as ops
from .timing import amortized_stats


def _fc_stage(name: str, relu_after: bool):
    def fn(p, x):
        x = x.reshape(x.shape[0], -1)
        out = x @ p[name]["w"] + p[name]["b"]
        return ops.relu(out) if relu_after else out

    return fn


def stage_fns(
    cfg=BLOCKS12,
    tier: str = "reference",
) -> List[Tuple[str, Callable[[Params, jax.Array], jax.Array]]]:
    """(name, fn) per layer; each fn maps that layer's input to its output.

    Accepts a ``Blocks12Config`` (relu is its own stage, matching the
    reference's 7-layer print chain) or an ``AlexNetConfig`` (relu fused
    into each conv stage as in ``alexnet_full.forward_spatial``, plus the
    FC6-8 head stages).

    ``tier='pallas'`` times the hand-written kernels instead of the
    XLA-op tier — the per-layer attribution that located the pool
    bottleneck in round 3 required measuring the Pallas ops directly
    (docs/PALLAS_PERF.md); conv stages fuse ReLU (the kernel's epilogue),
    so the chain has 5 stages, matching forward_blocks12_pallas.
    """
    conv, pool, lrn, fused_relu = _tier_ops(tier)
    full = hasattr(cfg, "blocks12")  # AlexNetConfig
    stages: List[Tuple[str, Callable]] = []
    if full:
        for name, spec in cfg.layer_chain():
            if isinstance(spec, ConvSpec):
                stages.append((name, functools.partial(conv, name=name, spec=spec, relu=True)))
            elif isinstance(spec, PoolSpec):
                stages.append((name, functools.partial(pool, spec=spec)))
            elif isinstance(spec, LrnSpec):
                stages.append((name, functools.partial(lrn, spec=spec)))
        stages.append(("fc6", _fc_stage("fc6", relu_after=True)))
        stages.append(("fc7", _fc_stage("fc7", relu_after=True)))
        stages.append(("fc8", _fc_stage("fc8", relu_after=False)))
        return stages
    c1, p1, c2, p2, n2 = cfg.conv1, cfg.pool1, cfg.conv2, cfg.pool2, cfg.lrn2
    if fused_relu:  # pallas: relu lives in the conv kernel epilogue
        return [
            ("conv1+relu", functools.partial(conv, name="conv1", spec=c1, relu=True)),
            ("pool1", functools.partial(pool, spec=p1)),
            ("conv2+relu", functools.partial(conv, name="conv2", spec=c2, relu=True)),
            ("pool2", functools.partial(pool, spec=p2)),
            ("lrn2", functools.partial(lrn, spec=n2)),
        ]
    return [
        ("conv1", functools.partial(conv, name="conv1", spec=c1, relu=False)),
        ("relu1", lambda p, x: ops.relu(x)),
        ("pool1", functools.partial(pool, spec=p1)),
        ("conv2", functools.partial(conv, name="conv2", spec=c2, relu=False)),
        ("relu2", lambda p, x: ops.relu(x)),
        ("pool2", functools.partial(pool, spec=p2)),
        ("lrn2", functools.partial(lrn, spec=n2)),
    ]


def _tier_ops(tier: str):
    """(conv, pool, lrn, fused_relu) stage ops for one tier — ONE chain
    walk in stage_fns serves both tiers (they previously diverged as two
    near-identical walks). Each op takes (params, x, *, ...spec kwargs).
    """
    if tier == "reference":
        def conv(p, x, *, name, spec, relu):
            out = ops.conv2d(
                x, p[name]["w"], p[name]["b"], stride=spec.stride, padding=spec.padding
            )
            return ops.relu(out) if relu else out

        def pool(p, x, *, spec):
            return ops.maxpool(x, window=spec.window, stride=spec.stride)

        def lrn(p, x, *, spec):
            return ops.lrn(
                x, size=spec.size, alpha=spec.alpha, beta=spec.beta, k=spec.k,
                alpha_over_size=spec.alpha_over_size,
            )

        return conv, pool, lrn, False
    if tier == "pallas":
        from ..ops import pallas_kernels as pk

        def conv(p, x, *, name, spec, relu):
            return pk.conv2d_pallas(
                x, p[name]["w"], p[name]["b"], stride=spec.stride,
                padding=spec.padding, relu=relu,
            )

        def pool(p, x, *, spec):
            return pk.maxpool_pallas(x, window=spec.window, stride=spec.stride)

        def lrn(p, x, *, spec):
            return pk.lrn_pallas(
                x, size=spec.size, alpha=spec.alpha, beta=spec.beta, k=spec.k,
                alpha_over_size=spec.alpha_over_size,
            )

        return conv, pool, lrn, True
    raise ValueError(f"tier must be reference|pallas, got {tier!r}")


def layer_breakdown(
    params: Params,
    x: jax.Array,
    cfg=BLOCKS12,
    repeats: int = 10,
    warmup: int = 3,
    compute: str = "fp32",
    tier: str = "reference",
) -> List[Tuple[str, float, Tuple[int, ...]]]:
    """Fenced per-layer timing: [(layer, ms, output_shape), ...].

    Each layer is timed on its *actual* input (the previous layer's output,
    computed once outside the timed region), jitted standalone, with the
    same amortized fence protocol as the headline timing. ``compute='bf16'``
    casts params and activations to bfloat16 so the breakdown matches the
    headline timing's numerics (configs.build_forward's bf16 mode).
    """
    if compute == "bf16":
        import jax.numpy as jnp

        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        x = x.astype(jnp.bfloat16)
    elif compute != "fp32":
        raise ValueError(f"unknown compute mode {compute!r} (fp32|bf16)")
    rows: List[Tuple[str, float, Tuple[int, ...]]] = []
    cur = x
    for name, fn in stage_fns(cfg, tier=tier):
        # Each iteration jits a DIFFERENT stage fn exactly once (per-layer
        # attribution is the point) — not the retrace-per-iteration footgun.
        jfn = jax.jit(fn)  # noqa: jit-in-loop
        # Work-floor stats (median of >=3 chains): per-layer times are
        # sub-ms, exactly the regime where a single amortized sample
        # carried ~40% relay noise (round-3 verdict).
        ms = amortized_stats(
            jfn, params, cur,
            n_small=max(1, warmup), n_large=max(1, warmup) + max(1, repeats),
        ).per_call_ms
        cur = jax.block_until_ready(jfn(params, cur))
        rows.append((name, ms, tuple(cur.shape)))
    return rows


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a jax.profiler trace of the enclosed region into ``log_dir``."""
    with jax.profiler.trace(log_dir):
        yield
