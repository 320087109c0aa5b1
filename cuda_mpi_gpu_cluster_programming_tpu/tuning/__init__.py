"""Kernel autotuning: candidate space, sweep, and persistent TunePlans.

A Pallas lowering is the code's default (``KernelVariants()``) or a tuned
plan's per-layer winner (``effective_layer_variants``) — see docs/TUNING.md.
"""

from .autotune import autotune, autotune_model, tune_layer
from .plan import (
    TunePlan,
    code_rev,
    effective_layer_variants,
    load_plan,
    plan_key,
    save_plan,
    shape_key,
)
from .space import ConvGeometry, candidate_space, conv_geometries, prune_reason

__all__ = [
    "ConvGeometry",
    "TunePlan",
    "autotune",
    "autotune_model",
    "candidate_space",
    "code_rev",
    "conv_geometries",
    "effective_layer_variants",
    "load_plan",
    "plan_key",
    "prune_reason",
    "save_plan",
    "shape_key",
    "tune_layer",
]
