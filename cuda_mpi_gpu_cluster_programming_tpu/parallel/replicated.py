"""Replicate-all execution: the reference's V2.1 anti-baseline.

V2.1 broadcasts the full input and all parameters to every rank and has
every rank redundantly compute the complete forward pass
(2.1_broadcast_all/src/main.cpp:49-87); it exists to demonstrate *negative*
scaling (BASELINE.md: 0.702→0.793 s as np goes 1→4). The TPU analogue:
fully-replicated ``NamedSharding`` on an N-device mesh — under SPMD every
device executes the whole computation on its own replica. ``device_put`` of
the replicated operands is the Bcast analogue.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.alexnet import BLOCKS12, Blocks12Config, forward_blocks12
from ..ops import scopes
from .mesh import make_mesh


def build_replicated_forward(
    model_cfg: Blocks12Config = BLOCKS12,
    n_shards: int = 1,
    mesh: Optional[Mesh] = None,
    quantized: bool = False,
) -> Callable:
    """``quantized``: run the int8w policy on every replica — the quantized
    forward (in-graph calibration from the fp32 tree,
    precision.quantize.forward_blocks12_int8w) replaces the fp32 pass under
    the same replicate-everything shardings, so each replica quantizes the
    identical param tree to identical int8 values/scales."""
    mesh = mesh or make_mesh(n_shards)
    repl = NamedSharding(mesh, P())
    if quantized:
        from ..precision.quantize import forward_blocks12_int8w

        model_fwd = lambda p, x: forward_blocks12_int8w(  # noqa: E731
            p, x, model_cfg, tier="reference"
        )
    else:
        model_fwd = lambda p, x: forward_blocks12(p, x, model_cfg)  # noqa: E731

    @jax.jit
    def fwd(params, x):
        with scopes.scatter():  # the Bcast: everything to every device
            params = jax.lax.with_sharding_constraint(params, repl)
            x = jax.lax.with_sharding_constraint(x, repl)
        out = model_fwd(params, x)
        with scopes.gather():
            return jax.lax.with_sharding_constraint(out, repl)

    return fwd
