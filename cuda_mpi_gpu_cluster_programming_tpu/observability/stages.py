"""Per-stage latency attribution for the Blocks 1-2 forward.

The reference repo's headline artifact is a staged per-phase breakdown —
scatter/halo/compute/gather ms per block — while a timed run reports
one ``per_pass_ms``. This module attributes that total across the EXACT
stage boundaries the in-graph sentinel taps (``with_digests=True``
compiles digests at conv1/pool1/conv2/pool2/lrn2 inside the shard_map
bodies — docs/RESILIENCE.md), so the attribution and the SDC screen
speak the same stage vocabulary.

Method: **timed staged re-execution, off the timed path**. The hot loop
stays sync-free — attribution never instruments the production forward.
Instead, :func:`attribute_stages` re-executes the staged chain as five
jitted *prefixes* (conv1; conv1+pool1; ...; the full chain) under the
repo's amortized work-floor estimator and attributes
``stage_k = t(prefix_k) - t(prefix_{k-1})``. The differences telescope,
so the per-stage breakdown sums EXACTLY to the measured full-chain time
(noise-negative diffs clamp to zero, then the stages renormalize onto
the measured total) — the sums-to-total contract of the breakdown
object (:meth:`StageAttribution.to_obj`).
Per-stage timing of each stage in isolation (``utils.profiling.
layer_breakdown``) cannot make that promise: XLA fuses across stage
boundaries, so isolated stages systematically over-count.

``@off_timed_path`` by contract (staticcheck's ``host-sync-in-hot-loop``
scope covers this file): every call here is a measurement pass between
timed regions, never inside one.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Tuple

from ..ops.scopes import BLOCKS12_LAYERS
from .trace import off_timed_path, span

# The sentinel tap boundaries (parallel.sharded / tensor_parallel
# with_digests=True) — conv stages include their ReLU, exactly as the
# in-graph digest taps bound them and as the forwards scope them: the
# names are the scopes' own.
SENTINEL_STAGES = BLOCKS12_LAYERS


def sentinel_stage_fns(cfg=None, tier: str = "reference") -> List[Tuple[str, Callable]]:
    """(name, fn) per sentinel stage; each fn maps the previous stage's
    output to this stage's output. Conv stages fuse ReLU (the tap is
    after activation on both op tiers)."""
    from ..models.alexnet import BLOCKS12
    from ..utils.profiling import _tier_ops

    cfg = cfg if cfg is not None else BLOCKS12
    conv, pool, lrn, _fused = _tier_ops(tier)
    return [
        ("conv1", functools.partial(conv, name="conv1", spec=cfg.conv1, relu=True)),
        ("pool1", functools.partial(pool, spec=cfg.pool1)),
        ("conv2", functools.partial(conv, name="conv2", spec=cfg.conv2, relu=True)),
        ("pool2", functools.partial(pool, spec=cfg.pool2)),
        ("lrn2", functools.partial(lrn, spec=cfg.lrn2)),
    ]


@dataclasses.dataclass(frozen=True)
class StageAttribution:
    """One attribution pass: per-stage ms (telescoped prefix differences),
    the raw prefix times, and the full-chain total the stages sum to.

    ``granularity``: "stage" (the five sentinel boundaries) or "block"
    (block1/block2 — the honest vocabulary for ``fuse="block"`` rows,
    where a fused pass has no interior boundaries to tap; the sub-object
    names its source so a block row can never be mistaken for a faked
    per-stage split)."""

    stages: Tuple[Tuple[str, float], ...]  # (name, attributed ms), in order
    prefix_ms: Tuple[float, ...]  # t(prefix_1) .. t(prefix_5) == total
    total_ms: float  # full staged chain, the reported per-pass analogue
    batch: int
    tier: str
    compute: str
    granularity: str = "stage"

    @property
    def stage_sum_ms(self) -> float:
        return sum(ms for _n, ms in self.stages)

    def to_obj(self) -> dict:
        """The breakdown as a JSON-ready object (per-stage ms)."""
        return {
            "stages": {name: round(ms, 4) for name, ms in self.stages},
            "stage_sum_ms": round(self.stage_sum_ms, 4),
            "total_ms": round(self.total_ms, 4),
            "method": (
                "prefix-diff" if self.granularity == "stage"
                else "prefix-diff/megakernel-blocks"
            ),
            "tier": self.tier,
            "compute": self.compute,
            "batch": self.batch,
            "granularity": self.granularity,
        }


@off_timed_path
def attribute_stages(
    params,
    x,
    cfg=None,
    *,
    tier: str = "reference",
    compute: str = "fp32",
    repeats: int = 3,
    warmup: int = 1,
) -> StageAttribution:
    """Measure the staged Blocks 1-2 chain and attribute per-stage ms.

    ``compute`` follows ``configs.build_forward``'s fp32/bf16 casting
    (bf16 casts params and activations, matching the headline numerics);
    ``int8w`` has no staged-chain analogue here and raises — callers on
    the quantized path degrade visibly instead of mislabeling fp32
    numbers as int8w attribution.
    """
    import jax

    from ..utils.timing import amortized_stats

    if compute == "bf16":
        import jax.numpy as jnp

        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        x = x.astype(jnp.bfloat16)
    elif compute != "fp32":
        raise ValueError(
            f"stage attribution supports fp32|bf16, got {compute!r} "
            "(the int8w lowering has no staged-chain analogue)"
        )
    stage_list = sentinel_stage_fns(cfg, tier=tier)

    def _prefix(k: int) -> Callable:
        fns = [fn for _n, fn in stage_list[:k]]

        def run(p, xin):
            cur = xin
            for fn in fns:
                cur = fn(p, cur)
            return cur

        return run

    n_small = max(1, warmup)
    prefix_ms: List[float] = []
    with span("stages.attribute", tier=tier, compute=compute, batch=int(x.shape[0])):
        for k in range(1, len(stage_list) + 1):
            # One jit per distinct prefix — per-stage attribution is the
            # point, not a retrace of one function.
            jfn = jax.jit(_prefix(k))  # noqa: jit-in-loop
            st = amortized_stats(
                jfn, params, x,
                n_small=n_small, n_large=n_small + max(1, repeats),
            )
            prefix_ms.append(st.per_call_ms)
    stages: List[Tuple[str, float]] = []
    prev = 0.0
    for (name, _fn), t in zip(stage_list, prefix_ms):
        stages.append((name, max(0.0, t - prev)))
        prev = t
    # A noise-negative diff (a longer prefix timing faster — sub-ms stages
    # under fusion jitter) clamps to 0 but leaves the clamped sum above the
    # measured total; renormalize onto the total so the sums-to-total
    # contract holds exactly. The raw prefix times stay on the result for
    # audit.
    clamped_sum = sum(ms for _n, ms in stages)
    if clamped_sum > 0 and abs(clamped_sum - prefix_ms[-1]) > 1e-12:
        scale = prefix_ms[-1] / clamped_sum
        stages = [(name, ms * scale) for name, ms in stages]
    return StageAttribution(
        stages=tuple(stages),
        prefix_ms=tuple(prefix_ms),
        total_ms=prefix_ms[-1],
        batch=int(x.shape[0]),
        tier=tier,
        compute=compute,
    )


@off_timed_path
def attribute_blocks(
    params,
    x,
    cfg=None,
    *,
    compute: str = "fp32",
    variants=None,
    repeats: int = 3,
    warmup: int = 1,
) -> StageAttribution:
    """Block-granularity attribution for ``fuse="block"`` (megakernel)
    rows: the same telescoped prefix-diff method as
    :func:`attribute_stages`, but the prefixes are the two FUSED passes
    (block1; block1+block2) — the only boundaries a megakernel row
    honestly has. The result carries ``granularity="block"`` and a
    method string naming the source, so downstream consumers can
    never mistake it for a per-stage
    split the fused pass did not measure.

    ``variants``: the per-layer plan the row ran under (conv variant and
    row_block govern the megakernel lowering; ``fuse`` itself is implied).
    fp32/bf16 only, like :func:`attribute_stages`."""
    import jax

    from ..models.alexnet import BLOCKS12
    from ..ops import megakernel as mk
    from ..ops import pallas_kernels as pk
    from ..ops.pallas_model import _layer_variants
    from ..utils.timing import amortized_stats

    cfg = cfg if cfg is not None else BLOCKS12
    if compute == "bf16":
        import jax.numpy as jnp

        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        x = x.astype(jnp.bfloat16)
    elif compute != "fp32":
        raise ValueError(
            f"block attribution supports fp32|bf16, got {compute!r} "
            "(the int8w megakernel rides the quantized bench path)"
        )
    v = variants if variants is not None else pk.KernelVariants()
    c1, p1, c2, p2, n2 = cfg.conv1, cfg.pool1, cfg.conv2, cfg.pool2, cfg.lrn2

    def _block(cur, p, name, cspec, pspec, lrn):
        lv = _layer_variants(v, name)
        conv_v = lv.conv if lv.conv in ("taps", "vcol") else "vcol"
        ho = (
            cur.shape[1] + 2 * cspec.padding - cspec.filter_size
        ) // cspec.stride + 1
        return mk.conv_block_pallas(
            cur, p[name]["w"], p[name]["b"],
            stride=cspec.stride, padding=cspec.padding,
            pool_window=pspec.window, pool_stride=pspec.stride,
            lrn=lrn, variant=conv_v, row_block=max(lv.row_block, ho),
        )

    def _prefix(k: int):
        def run(p, xin):
            cur = _block(xin, p, "conv1", c1, p1, None)
            if k >= 2:
                cur = _block(cur, p, "conv2", c2, p2, n2)
            return cur

        return run

    n_small = max(1, warmup)
    prefix_ms: List[float] = []
    with span(
        "stages.attribute_blocks", compute=compute, batch=int(x.shape[0])
    ):
        for k in (1, 2):
            jfn = jax.jit(_prefix(k))  # noqa: jit-in-loop
            st = amortized_stats(
                jfn, params, x,
                n_small=n_small, n_large=n_small + max(1, repeats),
            )
            prefix_ms.append(st.per_call_ms)
    stages: List[Tuple[str, float]] = []
    prev = 0.0
    for name, t in zip(("block1", "block2"), prefix_ms):
        stages.append((name, max(0.0, t - prev)))
        prev = t
    clamped_sum = sum(ms for _n, ms in stages)
    if clamped_sum > 0 and abs(clamped_sum - prefix_ms[-1]) > 1e-12:
        scale = prefix_ms[-1] / clamped_sum
        stages = [(name, ms * scale) for name, ms in stages]
    return StageAttribution(
        stages=tuple(stages),
        prefix_ms=tuple(prefix_ms),
        total_ms=prefix_ms[-1],
        batch=int(x.shape[0]),
        tier="pallas",
        compute=compute,
        granularity="block",
    )
