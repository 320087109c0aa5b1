"""Row-sharded forward pass: shard_map over a 1-D mesh with exact ownership.

The TPU rebuild of the reference's scatter+halo pipeline
(2.2_scatter_halo/src/main.cpp:100-249 and the V4 hybrid,
v4_mpi_cuda/src/main_mpi_cuda.cpp:20-140), with its compute-then-trim
replaced by the exact-ownership planner (see parallel.plan): each shard
computes exactly the output rows it owns, every layer, so there is nothing
to trim and the np>1 under-gather bug class (v4_np{2,4}.log) cannot occur.

Structure per spatial layer, inside ``shard_map``:

1. halo-exchange the block (``ppermute``; or the all_gather staged variant);
2. ``dynamic_slice`` the conv/pool window run — start is affine in
   ``lax.axis_index`` (plan.s0_coef/s0_const), size static;
3. run the op VALID on H (W padding stays inside the op);
4. re-mask rows beyond the owned range to zero (the mask invariant that
   makes halo zeros coincide with global conv padding).

MPI-primitive correspondence: Scatterv -> sharded array construction;
Irecv/Isend halo -> ppermute; Gatherv -> out_specs concatenation + final
slice; Barrier/Wtime -> block_until_ready + host timing.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..models.alexnet import BLOCKS12, Blocks12Config
from ..ops import reference as ops
from ..ops import scopes
from ..ops.vma import kernel_check_vma
from .halo import exchange
from .mesh import make_mesh
from .plan import LayerPlan, make_shard_plan

AXIS = "sp"


def _row_mask(block_rows: int, b_out: int, l_out: int, axis_name: str, dtype) -> jax.Array:
    """(block_rows, 1) 1/0 mask of rows this shard owns at a layer's output."""
    i = lax.axis_index(axis_name)
    g = i * b_out + lax.broadcasted_iota(jnp.int32, (block_rows, 1), 0)
    return (g < l_out).astype(dtype)


def _apply_spatial(
    lp: LayerPlan,
    x: jax.Array,
    params,
    spec,
    axis_name: str,
    n: int,
    conv_fn: Callable,
    pool_fn: Callable,
    staged: bool,
) -> jax.Array:
    """One conv/pool layer on a per-shard block (N, b_in, W, C)."""
    ex = exchange(staged)
    with scopes.halo(lp.name):
        padded = ex(x, lp.h_top, lp.h_bot, axis_name, n)
    if lp.pad_bot:
        padded = jnp.pad(padded, ((0, 0), (0, lp.pad_bot), (0, 0), (0, 0)))
    i = lax.axis_index(axis_name)
    s0 = i * lp.s0_coef + lp.s0_const
    win = lax.dynamic_slice_in_dim(padded, s0, lp.win_rows, axis=1)
    if lp.kind == "conv":
        p = params[lp.name]
        if "scale" in p:
            # int8w conv on this shard's rows: dequant-free (int8-valued
            # weights cast to bf16, exact), fp32 rescale + bias between
            # the conv and the mask. Ordering invariant: rescale and bias
            # land BEFORE the row mask (mask zeroes non-owned rows and
            # relu(0)=0 keeps them zero — bias after the mask would
            # resurrect them), mirroring the fp32 path where conv_fn adds
            # the bias itself.
            zb = jnp.zeros(p["b"].shape, jnp.bfloat16)
            out = conv_fn(
                win.astype(jnp.bfloat16), p["w"].astype(jnp.bfloat16), zb,
                stride=spec.stride, padding_w=spec.padding,
            ).astype(jnp.float32)
            out = out * p["scale"] + p["b"].astype(jnp.float32)
        else:
            w, b = p["w"], p["b"]
            out = conv_fn(win, w, b, stride=spec.stride, padding_w=spec.padding)
    else:
        out = pool_fn(win, window=spec.window, stride=spec.stride)
    # out has exactly b_out rows: (win_rows - F)//S + 1 == b_out
    mask = _row_mask(lp.b_out, lp.b_out, lp.l_out, axis_name, out.dtype)
    return out * mask.reshape(1, lp.b_out, 1, 1)


def _conv_hvalid(x, w, b, *, stride: int, padding_w: int, precision=lax.Precision.HIGHEST):
    """Conv VALID on H (halo machinery supplies H context), padded on W."""
    out = lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride, stride),
        padding=[(0, 0), (padding_w, padding_w)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision,
    )
    return out + b.astype(out.dtype)


def _pool_hvalid(x, *, window: int, stride: int):
    return ops.maxpool(x, window=window, stride=stride)


def build_sharded_forward(
    model_cfg: Blocks12Config = BLOCKS12,
    n_shards: int = 1,
    mesh: Optional[Mesh] = None,
    tier: str = "reference",
    staged: bool = False,
    with_digests: bool = False,
    plan=None,
    quantized: bool = False,
) -> Callable:
    """Jitted ``(params, x) -> out`` running row-sharded over ``n_shards``.

    ``x`` is the full (N, H, W, C) array; output is the full
    (N, H', W', C') array — scatter/gather are implicit in the shardings.

    ``with_digests``: additionally return a per-stage activation digest
    tree, ``(out, {layer_name: (n_shards,) float32})`` — one
    ``tree_digest`` per Conv1/Pool1/Conv2/Pool2/LRN2 boundary, computed
    INSIDE the shard_map body (the in-graph SDC sentinel taps). The digests
    are device scalars riding alongside the output: nothing syncs to host
    until a screener (``resilience.sentinel.StageDigests``) fetches them
    off the timed path, so the hot loop stays free of host round trips.

    ``plan``: a ``tuning.plan.TunePlan`` — the pallas tier runs each conv
    layer (and the pool it feeds) under the plan's per-layer winners, with
    the same env > plan > default knob precedence as the single-device
    builders (``tuning.plan.effective_layer_variants``). The ``fuse`` knob
    does not apply on this path (the hvalid lowering has no fused epilogue
    to hang an hpool stage off) and is ignored; reference tier ignores the
    whole plan, as everywhere else.

    ``quantized``: run the int8w policy sharded. Conv params quantize
    IN-GRAPH from the fp32 tree (calibration == the seeded init stream, the
    same contract as ``precision.quantize.forward_blocks12_int8w``), so the
    returned function keeps the ``(params, x) -> out`` shape; the int8
    values and their per-channel scales replicate to every shard with the
    rest of the param tree, each shard rescales its own rows before the
    ownership mask, activations ride bf16 between stages, and LRN/final
    output compute in fp32 — shard-count-invariant and screened per rung by
    ``precision.gate.ToleranceGate.screen_sharded``.
    """
    mesh = mesh or make_mesh(n_shards, axis_name=AXIS)
    n = n_shards
    splan = make_shard_plan(model_cfg, n)
    if with_digests:
        from ..resilience.sentinel import tree_digest

    if tier == "pallas":
        from ..ops.pallas_kernels import (
            KernelVariants,
            conv2d_pallas_hvalid,
            lrn_pallas,
            maxpool_pallas,
        )

        # vma-tagged out_shapes (ops.vma) let this shard_map keep
        # check_vma=True — previously the pallas tier forced the checker
        # off for the whole body, halo ppermutes included. Variants resolve
        # eagerly at build time (same footgun fix as configs.build_forward);
        # a TunePlan overlays per-layer winners (env knobs still win).
        kv = KernelVariants.resolve()
        lv = None
        if plan is not None:
            from ..tuning.plan import effective_layer_variants

            lv = effective_layer_variants(plan, base=kv)

        def _fns(v):
            return (
                functools.partial(
                    conv2d_pallas_hvalid, vma=(AXIS,), variant=v.conv,
                    row_block=v.row_block, k_block=v.k_block,
                ),
                functools.partial(maxpool_pallas, vma=(AXIS,), variant=v.pool),
            )

        # Per-layer kernel fns: a conv's tuned variants also govern the
        # pool it feeds (same adjacency contract as _conv_then_pool).
        layer_fns = {}
        governing = kv
        for lp in splan.layers:
            if lp.kind == "conv":
                governing = lv.for_layer(lp.name) if lv is not None else kv
            layer_fns[lp.name] = _fns(governing)
        # The Pallas tier runs the Pallas LRN per shard too, as the
        # single-device Pallas forward does: on a TPU the XLA LRN is
        # last-ulps off it (different window sum and pow), which broke the
        # within-tier bitwise contract on chips (v5e, PR 21). The int8w
        # contract keeps the XLA op on fp32 (forward_blocks12_int8w).
        lrn_fn = (
            ops.lrn if quantized else functools.partial(lrn_pallas, vma=(AXIS,))
        )
    else:
        layer_fns = None
        lrn_fn = ops.lrn

    specs = dict(model_cfg.layer_chain())

    def layer_body(lp, spec, params, cur):
        if lp.kind == "pointwise":
            # int8w contract: LRN computes in fp32 (squares + pow need
            # the headroom) — same as forward_blocks12_int8w.
            return lrn_fn(
                cur.astype(jnp.float32) if quantized else cur,
                size=spec.size,
                alpha=spec.alpha,
                beta=spec.beta,
                k=spec.k,
                alpha_over_size=spec.alpha_over_size,
            )
        conv_fn, pool_fn = (
            layer_fns[lp.name]
            if layer_fns is not None
            else (_conv_hvalid, _pool_hvalid)
        )
        cur = _apply_spatial(
            lp, cur, params, spec, AXIS, n, conv_fn, pool_fn, staged
        )
        if lp.kind == "conv":
            cur = ops.relu(cur)
            if quantized:
                # activations ride bf16 between quantized stages
                cur = cur.astype(jnp.bfloat16)
        return cur

    def shard_body(params, xb):
        # xb: (N, b0, W, C) — this shard's rows (zero-padded past H)
        cur = xb
        digs = {}
        for lp in splan.layers:
            spec = specs[lp.name]
            with scopes.layer(lp.name):
                cur = layer_body(lp, spec, params, cur)
                if with_digests:
                    # In-graph sentinel tap: one float32 digest of this
                    # shard's block at the layer boundary. Shard-varying
                    # (each shard digests its own rows) — concatenated to
                    # (n,) by out_specs.
                    digs[lp.name] = tree_digest(cur)[None]
        return (cur, digs) if with_digests else cur

    out_spec = P(None, AXIS, None, None)
    if with_digests:
        out_specs = (out_spec, {lp.name: P(AXIS) for lp in splan.layers})
    else:
        out_specs = out_spec
    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(), P(None, AXIS, None, None)),
        out_specs=out_specs,
        # Pallas tier: checker ON wherever the kernels can tag their
        # out_shapes with vma (real TPU — ops.vma.kernel_check_vma); the
        # disable now only survives in interpret mode. Reference tier:
        # always on.
        check_vma=(tier != "pallas" or kernel_check_vma()),
    )

    h_pad = n * splan.layers[0].b_in  # SPMD needs equal blocks: pad H to n*b0
    l_final = splan.l_final

    @jax.jit
    def fwd(params, x):
        if quantized:
            from ..precision.quantize import quantize_conv_params

            # In-graph quantization keeps the (fp32_params, x) -> out shape
            # every builder expects; "w" carries the int8 values so the
            # shard body's param access pattern is unchanged, "scale"
            # marks the entry quantized.
            with scopes.cast_in():
                params = {
                    name: {"w": e["q"], "scale": e["scale"], "b": e["b"]}
                    for name, e in quantize_conv_params(params).items()
                }
                x = x.astype(jnp.bfloat16)
        pad = h_pad - x.shape[1]
        if pad:
            with scopes.scatter():
                x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        out = sharded(params, x)  # (N, n*b_final, W', C') [, digests]
        if with_digests:
            out, digs = out
        with scopes.gather():
            out = out[:, :l_final]
        return (out, digs) if with_digests else out

    return fwd
