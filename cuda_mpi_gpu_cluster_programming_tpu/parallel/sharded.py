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

MPI-primitive correspondence: Scatterv -> ``ppermute`` of row blocks from
the device that holds the batch (:class:`RowScatteredForward`); Irecv/Isend
halo -> ppermute; Gatherv -> out_specs concatenation + final slice;
Barrier/Wtime -> block_until_ready + host timing.

What happens to ``x`` depends on where it lives, which the code can see:

- a concrete array on ONE device (committed or not): the step program
  itself scatters it. The program's argument is a batch-sharded global
  array whose shard on the holder is ``x`` as it stands (no copy) and whose
  other shards are stand-ins that are never read; inside, the holder cuts
  the ``H`` real rows into the ``n`` row blocks one by one, its own last,
  casts each to the compute type in the same pass (``cast_in``) and sends
  block ``j`` to device ``j`` by a ``ppermute`` with itself as the one
  source; the block of a device two hops off that two of the holder's
  neighbours reach in one (a v5e 2x2's diagonal) goes first, in parts that
  those neighbours forward (:func:`scatter_routes`); a block short of
  ``b0`` rows (the last, where ``n*b0 > H``) is zero-padded where it
  arrives, so no zero row travels. One
  dispatch a step, and nothing is replicated (a jitted multi-device program
  treats a single-device argument as replicated: the whole float32 batch
  went from the holder to every device before every step);
- a host (``numpy``) array: cut into row blocks on the host, each block
  copied straight to its owner, cast there by the step program;
- an array that already has the row sharding ``P(None, "sp")`` over the
  padded height: straight into the step program;
- a tracer (the forward called inside an outer ``jit``: training, the
  full-AlexNet sharded forward) or an array laid out any other way: the
  in-graph pad and slice, one program.

Parameters are replicated, and placed on every device once per tree.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.alexnet import BLOCKS12, Blocks12Config
from ..observability.metrics import registry as metrics_registry
from ..ops import reference as ops
from ..ops import scopes
from ..ops.vma import kernel_check_vma
from .breakdown import comm_compute_breakdown
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


def _coords(device):
    """Where ``device`` sits in the chips' interconnect, or None (the CPU's
    devices have no coordinates)."""
    return getattr(device, "coords", None)


def _hops(a, b) -> Optional[int]:
    """Links between two devices by their coordinates, or None."""
    ca, cb = _coords(a), _coords(b)
    if ca is None or cb is None:
        return None
    return sum(abs(p - q) for p, q in zip(ca, cb))


def scatter_routes(devices, src: int) -> list:
    """``[(j, vias)]``: each device ``j`` that the holder ``src`` sends a row
    block to, in the order the holder sends them, and the neighbours its
    block goes through. A device two hops off that two neighbours of the
    holder each reach in one (on a v5e 2x2, the diagonal) gets its block in
    parts, one through each neighbour, and first: a link serves its
    transfers in the order it is handed them, so the parts reach the
    neighbours before their own blocks take the links, and each of the
    holder's two links carries 81.5 of the 163 rows that leave it where one
    carried 99. Every other block goes straight (``vias == ()``), in mesh
    order, as every block does where the devices have no coordinates."""
    devices = list(devices)
    routes = []
    for j in range(len(devices)):
        if j == src:
            continue
        vias = ()
        if _hops(devices[src], devices[j]) == 2:
            vias = tuple(
                k for k in range(len(devices))
                if _hops(devices[src], devices[k]) == 1 and _hops(devices[k], devices[j]) == 1
            )
        routes.append((j, vias if len(vias) > 1 else ()))
    return sorted(routes, key=lambda route: not route[1])


def _from_wire(wire: jax.Array, shape) -> jax.Array:
    """The (N, r, W, C) block of ``shape`` back from the form it travels in
    (``cut`` in :func:`build_sharded_forward`)."""
    nb, r, w, c = shape
    return wire.reshape(r, c, -1, nb)[:, :, :w].transpose(3, 0, 2, 1)


# Rows of a TPU memory tile: W is padded to a multiple of it on the wire.
WIRE_TILE_ROWS = 8


def build_sharded_forward(
    model_cfg: Blocks12Config = BLOCKS12,
    n_shards: int = 1,
    mesh: Optional[Mesh] = None,
    tier: str = "reference",
    staged: bool = False,
    with_digests: bool = False,
    plan=None,
    quantized: bool = False,
    compute_dtype=None,
) -> Callable:
    """``(params, x) -> out`` running row-sharded over ``n_shards``.

    ``x`` is the full (N, H, W, C) array; output is the full
    (N, H', W', C') array. With one shard the result is one jitted program.
    With more it is a :class:`RowScatteredForward`, which brings ``x`` to
    its owners by where it lives (module docstring): scattered in row blocks
    from the one device that holds it, inside the step program; cut on the
    host and copied block by block if it is a host array; passed straight
    through if it already has the row sharding over the padded height;
    padded and sliced in-graph if it is a tracer (the forward called inside
    an outer ``jit``) or laid out any other way. ``.lower(params, x)`` gives
    the step program lowered for the public signature.

    ``compute_dtype``: the compute type of ``configs.build_forward``'s bf16
    mode. Parameters and ``x`` are cast to it inside the program under the
    ``cast_in`` scope (``x`` on its holder, each block as it is cut, so that
    the blocks travel in the compute type), and the output comes back float32.
    ``None`` computes in the dtype of what arrives.

    ``with_digests``: additionally return a per-stage activation digest
    tree, ``(out, {layer_name: (n_shards,) float32})`` — one
    ``tree_digest`` per Conv1/Pool1/Conv2/Pool2/LRN2 boundary, computed
    INSIDE the shard_map body (the in-graph SDC sentinel taps). The digests
    are device scalars riding alongside the output: nothing syncs to host
    until a screener (``resilience.sentinel.StageDigests``) fetches them
    off the timed path, so the hot loop stays free of host round trips.

    ``plan``: a ``tuning.plan.TunePlan`` — the pallas tier runs each conv
    layer (and the pool it feeds) under the plan's per-layer winners, the
    defaults where it has none, as the single-device builders do
    (``tuning.plan.effective_layer_variants``). The ``fuse`` knob
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
            conv2d_pallas_hvalid,
            lrn_pallas,
            maxpool_pallas,
        )
        from ..tuning.plan import effective_layer_variants

        # vma-tagged out_shapes (ops.vma) let this shard_map keep
        # check_vma=True — previously the pallas tier forced the checker
        # off for the whole body, halo ppermutes included.
        lv = effective_layer_variants(plan)
        pool_fn = functools.partial(maxpool_pallas, vma=(AXIS,))
        # Per-layer kernel fns: each conv under its own tuned variants; every
        # pool runs the one sep2 lowering.
        layer_fns = {}
        for lp in splan.layers:
            v = lv.for_layer(lp.name)
            conv_fn = functools.partial(
                conv2d_pallas_hvalid, vma=(AXIS,), variant=v.conv,
                row_block=v.row_block,
            )
            layer_fns[lp.name] = (conv_fn, pool_fn)
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

    b0 = splan.layers[0].b_in
    h_pad = n * b0  # SPMD needs equal blocks: pad H to n*b0
    l_final = splan.l_final
    # What x is cast to before the first layer: int8w runs bf16 activations,
    # a compute type is the caller's, None leaves x as it comes.
    x_dtype = jnp.bfloat16 if quantized else compute_dtype

    def cast_params(params):
        if quantized:
            from ..precision.quantize import quantize_conv_params

            # In-graph quantization keeps the (fp32_params, x) -> out shape
            # every builder expects; "w" carries the int8 values so the
            # shard body's param access pattern is unchanged, "scale"
            # marks the entry quantized.
            with scopes.cast_in():
                return {
                    name: {"w": e["q"], "scale": e["scale"], "b": e["b"]}
                    for name, e in quantize_conv_params(params).items()
                }
        if compute_dtype is None:
            return params
        with scopes.cast_in():
            return jax.tree.map(lambda a: a.astype(compute_dtype), params)

    def cast_x(x):
        if x_dtype is None or x.dtype == x_dtype:
            return x
        with scopes.cast_in():
            return x.astype(x_dtype)

    def cut(x, lo: int, hi: int, wire: bool):
        """Rows ``lo:hi`` of ``x`` (N, H, W, C) in the compute type, under
        ``cast_in`` (the compiler folds the cast into the cut). ``wire``: as
        the block leaves its holder, the batch last and W padded to whole
        tiles, flat to (r*C*W', N). That is the order a TPU holds the float32
        batch in (the batch in lanes; H, C, then W above it), so one fusion
        reads and casts the rows, a pad fills W, and the collective sends the
        result as it stands; a 4-D block went out in a layout the compiler
        picked to spare its padding, one more pass over it on the holder."""
        with scopes.cast_in():
            rows = x[:, lo:hi].astype(x_dtype or x.dtype)
            if not wire:
                return rows
            nb, r, w, c = rows.shape
            wp = -(-w // WIRE_TILE_ROWS) * WIRE_TILE_ROWS
            t = jnp.pad(jnp.transpose(rows, (1, 3, 2, 0)), ((0, 0), (0, 0), (0, wp - w), (0, 0)))
            return t.reshape(r * c * wp, nb)

    def pad_rows(x):
        pad = h_pad - x.shape[1]
        if pad:
            with scopes.scatter():
                x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x

    def step(params, xb):
        # xb: (N, n*b0, W, C), zero rows past H; row-sharded where the
        # input sharding is declared
        xb = cast_x(xb)
        if n > 1:
            # set as the program is traced: the plan's halo rows times the
            # block widths, for this batch and compute type
            halo = comm_compute_breakdown(
                model_cfg, n, batch=xb.shape[0], dtype_bytes=xb.dtype.itemsize, staged=staged
            )
            metrics_registry().gauge(HALO_BYTES).set(sum(r.halo_bytes for r in halo))
        out = sharded(cast_params(params), xb)  # (N, n*b_final, W', C') [, digests]
        if with_digests:
            out, digs = out
        with scopes.gather():
            out = out[:, :l_final]
        if compute_dtype is not None:
            out = out.astype(jnp.float32)
        return (out, digs) if with_digests else out

    def fwd(params, x):
        return step(params, pad_rows(cast_x(x)))

    if n == 1:
        return jax.jit(fwd)

    def scatter_step_from(src: int):
        routes = [*scatter_routes(mesh.devices.flat, src), (src, ())]

        def scatter_body(xl):
            # xl (N, H, W, C): the batch on device src, its stand-in elsewhere
            nb, h, w, c = xl.shape
            arrived = [None] * n
            first_hops = []  # the relayed parts as the neighbours received them
            relayed = 0

            def take(lo, hi, wire):
                # rows lo:hi cut and cast, each block in a pass of its own:
                # the next is cut from an x that waits on this one, so the
                # casts keep the routes' order and the compiler cannot merge
                # them back into one cast of the whole x
                nonlocal xl
                xl, block = lax.optimization_barrier((xl, cut(xl, lo, hi, wire)))
                return block

            with scopes.scatter():
                for j, vias in routes:
                    # the real rows of block j alone; zeros only where it lands
                    lo, hi = min(j * b0, h), min((j + 1) * b0, h)
                    if j == src or hi == lo:
                        block = take(lo, hi, wire=False)
                    elif vias:
                        cuts = [lo + (hi - lo) * i // len(vias) for i in range(len(vias) + 1)]
                        parts = []
                        for via, a, b in zip(vias, cuts, cuts[1:]):
                            part = lax.ppermute(take(a, b, wire=True), AXIS, perm=[(src, via)])
                            first_hops.append(part)
                            part = lax.ppermute(part, AXIS, perm=[(via, j)])
                            parts.append(_from_wire(part, (nb, b - a, w, c)))
                        block = jnp.concatenate(parts, axis=1)
                        relayed += nb * (hi - lo) * w * c
                    else:
                        # the holder's links take this block once the relayed
                        # parts are through them: a link sends in the order
                        # it is handed its transfers
                        wire, *_ = lax.optimization_barrier((take(lo, hi, wire=True), *first_hops))
                        wire = lax.ppermute(wire, AXIS, perm=[(src, j)])
                        block = _from_wire(wire, (nb, hi - lo, w, c))
                    short = b0 - block.shape[1]
                    arrived[j] = jnp.pad(block, ((0, 0), (0, short), (0, 0), (0, 0)))
                # set as the program is traced, as sharding.halo_bytes is
                metrics_registry().gauge(RELAYED_BYTES).set(
                    relayed * np.dtype(x_dtype or xl.dtype).itemsize
                )
                return lax.select_n(lax.axis_index(AXIS), *arrived)

        scatter = shard_map(
            scatter_body, mesh=mesh, in_specs=P(AXIS), out_specs=P(None, AXIS, None, None)
        )

        def scatter_step(params, xg):
            return step(params, scatter(xg))

        return jax.jit(scatter_step, in_shardings=(replicated, NamedSharding(mesh, P(AXIS))))

    replicated = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(None, AXIS, None, None))
    return RowScatteredForward(
        whole=jax.jit(fwd),
        step=jax.jit(step, in_shardings=(replicated, rows)),
        scatter_step=functools.cache(scatter_step_from),
        rows=rows,
        b0=b0,
        x_dtype=x_dtype,
    )


SCATTERED_CALLS = "sharding.scatter.scattered_calls"
PLACED_CALLS = "sharding.scatter.placed_calls"
IN_GRAPH_CALLS = "sharding.scatter.in_graph_calls"
SCATTERED_BYTES = "sharding.scatter.bytes_off_holder"
HALO_BYTES = "sharding.halo_bytes"
RELAYED_BYTES = "sharding.scatter.relayed_bytes"


class RowScatteredForward:
    """``(params, x) -> out`` over a row-sharded step, with ``x`` brought to
    its owners by where it lives (module docstring). Three programs, each
    built when its kind of argument first comes: ``scatter_step(src)``
    (x on mesh device ``src``: the scatter and the step in one), ``step``
    (x row-sharded already, or cut on the host) and ``whole`` (the in-graph
    pad and slice).

    Counts in the process-wide registry (``observability.metrics``), host
    integers only: ``sharding.scatter.scattered_calls`` (x arrived on one
    device, or on the host, and went out in row blocks),
    ``sharding.scatter.bytes_off_holder`` (the bytes that left the device,
    or host, that held x, summed over those calls: the real rows of the
    blocks that leave a device, the padded blocks that leave the host; a
    constant per shape and call), ``sharding.scatter.placed_calls`` (x
    already had the row sharding) and ``sharding.scatter.in_graph_calls``
    (x a tracer, counted once per trace, or laid out some other way). The
    gauge ``sharding.halo_bytes`` is set as a step program is traced: the
    bytes one interior chip receives by halo in one step
    (``breakdown.comm_compute_breakdown``'s total for that batch and type);
    ``sharding.scatter.relayed_bytes`` as a scatter program is traced: the
    bytes of the real rows that reach their owner through a neighbour in one
    call (:func:`scatter_routes`; 0 where the devices have no coordinates).
    """

    def __init__(self, *, whole, step, scatter_step, rows: NamedSharding, b0: int, x_dtype):
        self.whole, self.step, self.scatter_step = whole, step, scatter_step
        self.rows, self.b0, self.x_dtype = rows, b0, x_dtype
        self.devices = tuple(rows.mesh.devices.flat)  # block i's owner
        self.h_pad = len(self.devices) * b0
        self.replicated = NamedSharding(rows.mesh, P())
        self.batch_sharded = NamedSharding(rows.mesh, P(AXIS))
        self._stand_ins = {}  # (shape, dtype, src) -> x's stand-ins on the other devices
        self._placed_params = ((), None)  # (the leaves last seen, their tree on every device)

    def _is_placed(self, x) -> bool:
        sharding = getattr(x, "sharding", None)
        return (
            sharding is not None
            and x.shape[1] == self.h_pad
            and sharding.is_equivalent_to(self.rows, len(x.shape))
        )

    def _on_every_device(self, params):
        """``params`` replicated over the mesh, placed once for a tree that
        comes again: left where a caller holds them (on one device,
        uncommitted) the runtime would send every leaf to every device before
        every step, 2 ms of host time a call on four v5e chips (PERF.md,
        PR 26). Arrays are immutable, so the same leaves are the same values;
        a tree with host leaves is left to the runtime."""
        leaves = jax.tree.leaves(params)
        seen, placed = self._placed_params
        if len(leaves) == len(seen) and all(a is b for a, b in zip(leaves, seen)):
            return placed
        if not all(isinstance(a, jax.Array) for a in leaves):
            return params
        placed = jax.device_put(params, self.replicated)
        self._placed_params = (leaves, placed)
        return placed

    def __call__(self, params, x):
        reg = metrics_registry()
        if any(isinstance(a, jax.core.Tracer) for a in jax.tree.leaves((params, x))):
            reg.counter(IN_GRAPH_CALLS).inc()
            return self.whole(params, x)
        params = self._on_every_device(params)
        if self._is_placed(x):
            reg.counter(PLACED_CALLS).inc()
            return self.step(params, x)
        if not isinstance(x, jax.Array):
            blocks = self._host_rows(np.asarray(x))
            reg.counter(SCATTERED_CALLS).inc()
            reg.counter(SCATTERED_BYTES).inc(sum(b.nbytes for b in blocks))
            xb = jax.make_array_from_single_device_arrays(
                (x.shape[0], self.h_pad, *x.shape[2:]),
                self.rows,
                list(jax.device_put(blocks, self.devices)),
            )
            return self.step(params, xb)
        if len(x.sharding.device_set) != 1:
            reg.counter(IN_GRAPH_CALLS).inc()
            return self.whole(params, x)
        (holder,) = x.sharding.device_set
        n = len(self.devices)
        if holder in self.devices:
            src = self.devices.index(holder)
            itemsize = np.dtype(self.x_dtype or x.dtype).itemsize
            # every real row but those of the holder's own block
            own = max(0, min((src + 1) * self.b0, x.shape[1]) - src * self.b0)
            off_holder = x.shape[0] * (x.shape[1] - own) * math.prod(x.shape[2:]) * itemsize
        else:  # x leaves its holder whole, for the first device of the mesh
            src, off_holder = 0, x.nbytes
            x = jax.device_put(x, self.devices[0])
        reg.counter(SCATTERED_CALLS).inc()
        reg.counter(SCATTERED_BYTES).inc(off_holder)
        shards = list(self._stand_ins_for(x, src))
        shards.insert(src, x)
        xg = jax.make_array_from_single_device_arrays(
            (n * x.shape[0], *x.shape[1:]), self.batch_sharded, shards
        )
        return self.scatter_step(src)(params, xg)

    def _stand_ins_for(self, x, src: int):
        """One array of x's shape on every device but ``src``: an SPMD
        program's argument has a shard on each device, and only ``src``'s is
        read (the others send nothing). Made once per shape, on the device
        that holds x and copied from there, so the host makes nothing."""
        key = (x.shape, x.dtype, src)
        if key not in self._stand_ins:
            others = [d for i, d in enumerate(self.devices) if i != src]
            self._stand_ins[key] = tuple(jax.device_put([jnp.zeros_like(x)] * len(others), others))
        return self._stand_ins[key]

    def _host_rows(self, x):
        """The row blocks of a host array, zero rows past H: views, but for
        the blocks that reach past the image's end."""
        blocks = []
        for i in range(len(self.devices)):
            block = x[:, i * self.b0 : (i + 1) * self.b0]
            short = self.b0 - block.shape[1]
            if short:
                block = np.pad(block, ((0, 0), (0, short), (0, 0), (0, 0)))
            blocks.append(block)
        return tuple(blocks)

    def lower(self, params, x):
        """The step program lowered for this ``(params, x)`` of the public
        signature, concrete or ``ShapeDtypeStruct``: the one that holds the
        scatter, the halos, the layers and the gather, on the abstract input
        that ``__call__`` would hand it."""
        if self._is_placed(x) or isinstance(x, np.ndarray):
            xb = jax.ShapeDtypeStruct(
                (x.shape[0], self.h_pad, *x.shape[2:]), x.dtype, sharding=self.rows
            )
            return self.step.lower(params, xb)
        holders = getattr(getattr(x, "sharding", None), "device_set", ())
        src = next((self.devices.index(d) for d in holders if d in self.devices), 0)
        xg = jax.ShapeDtypeStruct(
            (len(self.devices) * x.shape[0], *x.shape[1:]), x.dtype, sharding=self.batch_sharded
        )
        return self.scatter_step(src).lower(params, xg)
