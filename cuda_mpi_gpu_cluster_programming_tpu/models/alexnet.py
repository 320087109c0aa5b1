"""AlexNet Blocks 1-2: the single model definition shared by every tier.

The reference maintains five divergent copies of this network (one per
parallelization stage); here there is exactly one functional definition and
the stages are execution configs. Hyperparameters default to the reference's
hard-coded values (v1_serial/src/main.cpp:21-43,
v2_mpi_only/2.2_scatter_halo/src/main.cpp:35-47):

    227x227x3 -Conv1(K=96,F=11,S=4,P=0)-> 55x55x96 -Pool1(3,2)-> 27x27x96
             -Conv2(K=256,F=5,S=1,P=2)-> 27x27x256 -Pool2(3,2)-> 13x13x256
             -LRN2(N=5, a=1e-4, b=0.75, k=2.0)-> 13x13x256
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax

from ..ops import reference as ops
from ..ops import scopes
from ..ops.shapes import conv_out_dim, pool_out_dim

Params = Dict[str, Dict[str, Any]]


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    out_channels: int
    filter_size: int
    stride: int
    padding: int


@dataclasses.dataclass(frozen=True)
class PoolSpec:
    window: int
    stride: int


@dataclasses.dataclass(frozen=True)
class LrnSpec:
    size: int
    alpha: float
    beta: float
    k: float
    # False = the reference's CUDA form (k + alpha*sum — the headline golden
    # numbers); True = its CPU form (k + alpha*sum/size). See ops.reference.lrn.
    alpha_over_size: bool = False


@dataclasses.dataclass(frozen=True)
class Blocks12Config:
    """AlexNet Blocks 1-2 hyperparameters (reference defaults)."""

    in_height: int = 227
    in_width: int = 227
    in_channels: int = 3
    conv1: ConvSpec = ConvSpec(96, 11, 4, 0)
    pool1: PoolSpec = PoolSpec(3, 2)
    conv2: ConvSpec = ConvSpec(256, 5, 1, 2)
    pool2: PoolSpec = PoolSpec(3, 2)
    lrn2: LrnSpec = LrnSpec(5, 1e-4, 0.75, 2.0)

    def layer_chain(self) -> Tuple[Tuple[str, Any], ...]:
        """The spatial layer sequence (used by the shard planner), under
        the names every forward scopes its layers with."""
        specs = (self.conv1, self.pool1, self.conv2, self.pool2, self.lrn2)
        return tuple(zip(scopes.BLOCKS12_LAYERS, specs))


BLOCKS12 = Blocks12Config()


def layer_dims(cfg):
    """Walk the layer chain once, yielding ``(name, spec, in_dims, out_dims)``
    with dims as (H, W, C) — the ONE output-shape traversal shared by
    ``output_shape``, the FLOP counters and the kernel autotuner (each used
    to re-implement this loop; tuning geometry must not drift from the FLOP
    accounting it is judged by).

    Works for any config exposing ``layer_chain()`` plus input dims
    (Blocks12Config and the full AlexNetConfig's spatial chain alike).
    Mirrors the dim chain at v2_mpi_only/2.2_scatter_halo/src/main.cpp:49-58.
    """
    h, w, c = cfg.in_height, cfg.in_width, cfg.in_channels
    for name, spec in cfg.layer_chain():
        hin, win, cin = h, w, c
        if isinstance(spec, ConvSpec):
            h = conv_out_dim(h, spec.filter_size, spec.padding, spec.stride)
            w = conv_out_dim(w, spec.filter_size, spec.padding, spec.stride)
            c = spec.out_channels
        elif isinstance(spec, PoolSpec):
            h = pool_out_dim(h, spec.window, spec.stride)
            w = pool_out_dim(w, spec.window, spec.stride)
        yield name, spec, (hin, win, cin), (h, w, c)


def output_shape(cfg: Blocks12Config = BLOCKS12) -> Tuple[int, int, int]:
    """(H, W, C) of the final output — 13x13x256 for the defaults."""
    dims = cfg.in_height, cfg.in_width, cfg.in_channels
    for _name, _spec, _in, dims in layer_dims(cfg):
        pass
    return dims


def stage_flops(cfg: Blocks12Config = BLOCKS12):
    """Per-stage ``(name, flops, matmul_flops)`` for ONE image — the
    stage-level FLOP ledger shared by :func:`flops_per_image`,
    :func:`matmul_flops_per_image` and the roofline attribution layer
    (``observability.roofline``). Both totals sum over this generator, so
    a per-stage ledger and the whole-pass count can never drift apart.

    ``flops`` counts everything (conv MACs x2 + bias + ReLU, pool window
    compares, LRN window sums/scale); ``matmul_flops`` counts only the
    MXU work (conv MACs x2) — the conventional MFU numerator.
    """
    for name, spec, (_hi, _wi, c_in), (h, w, c_out) in layer_dims(cfg):
        if isinstance(spec, ConvSpec):
            macs = h * w * c_out * spec.filter_size**2 * c_in
            yield name, 2 * macs + h * w * c_out, 2 * macs  # +bias, +ReLU
        elif isinstance(spec, PoolSpec):
            yield name, h * w * c_out * spec.window**2, 0  # max compares
        elif isinstance(spec, LrnSpec):
            # per element: ~size multiplies + adds for the window sum, plus
            # the scale power and divide
            yield name, h * w * c_out * (2 * spec.size + 2), 0


def flops_per_image(cfg: Blocks12Config = BLOCKS12) -> int:
    """Exact FLOPs for one image through Blocks 1-2 (MAC = 2 FLOPs).

    Counts conv MACs plus the elementwise ReLU/pool/LRN work. For the default
    config this is ~1.12 GFLOP — note the reference's summary.md:29-45 claims
    "~0.33 GFLOPs" for the same workload; that figure undercounts (it is not
    reproducible from the layer dims), so we derive from the config instead.
    """
    return sum(f for _name, f, _mm in stage_flops(cfg))


def matmul_flops_per_image(cfg: Blocks12Config = BLOCKS12) -> int:
    """Matmul-only FLOPs (conv MACs x2) for one image through Blocks 1-2.

    The conventional MFU numerator: only work the MXU executes. Pool
    compares, LRN window sums, bias adds and ReLU are excluded —
    ``flops_per_image`` keeps the all-in count for throughput accounting.
    """
    return sum(mm for _name, _f, mm in stage_flops(cfg))


def forward_chain(params: Params, x: jax.Array, chain) -> jax.Array:
    """Run a ``layer_chain()`` on the XLA op tier, each layer under its own
    ``jax.named_scope`` (``ops.scopes``; ReLU inside its convolution's), so
    that a device trace of any program built on this forward splits by layer.
    The one chain walk behind ``forward_blocks12`` and
    ``alexnet_full.forward_spatial``."""
    for name, spec in chain:
        with scopes.layer(name):
            if isinstance(spec, ConvSpec):
                p = params[name]
                x = ops.conv2d(x, p["w"], p["b"], stride=spec.stride, padding=spec.padding)
                x = ops.relu(x)
            elif isinstance(spec, PoolSpec):
                x = ops.maxpool(x, window=spec.window, stride=spec.stride)
            elif isinstance(spec, LrnSpec):
                x = ops.lrn(
                    x, size=spec.size, alpha=spec.alpha, beta=spec.beta, k=spec.k,
                    alpha_over_size=spec.alpha_over_size,
                )
    return x


def forward_blocks12(params: Params, x: jax.Array, cfg: Blocks12Config = BLOCKS12) -> jax.Array:
    """Forward pass Conv1→ReLU→Pool1→Conv2→ReLU→Pool2→LRN2.

    Functional replacement for the reference's ping-pong double-buffer
    orchestrator (v1_serial/src/alexnet_serial.cpp:67-186). ``x`` is NHWC;
    params is ``{"conv1": {"w","b"}, "conv2": {"w","b"}}`` with HWIO weights.
    """
    return forward_chain(params, x, cfg.layer_chain())
