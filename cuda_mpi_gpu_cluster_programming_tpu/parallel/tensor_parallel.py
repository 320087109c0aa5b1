"""Tensor parallelism: conv output-channel (K-axis) filter decomposition.

The parallelism family the reference names but never builds — "filter
decomposition" is listed as the alternative to its row decomposition
(reference README.md:638; SURVEY §2.2 marks TP "no — optional extension:
shard K axis of conv"). Where ``parallel.sharded`` splits the *spatial* H
axis (halos in image rows), this splits the *filter bank*: each shard owns
K/n output channels of every conv layer, so weights — not activations — are
what's partitioned. The two strategies are duals:

- row-sharding: activations sharded, weights replicated, halos in H;
- TP: weights sharded, activations replicated at layer boundaries, the
  "halo" rotated onto the channel axis (the LRN's cross-channel window
  needs ``size//2`` neighbor channels — exchanged with the same paired
  ``ppermute`` shifts the row pipeline uses for image rows).

Boundary collectives: one ``all_gather`` over channels after block 1
(conv2 consumes *all* of conv1's channels), one channel-halo ``ppermute``
pair before the LRN, and the shard_map output sharding assembles the final
channel-sharded result. Everything rides ICI.

Numerics: each output channel's dot products are computed by exactly one
shard with the same reduction order as the single-device pass, so the TP
forward is bit-exact vs ``forward_blocks12`` (tested at n ∈ {1,2,4,8} —
the same shard-vs-single discipline as the row pipeline).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..models.alexnet import BLOCKS12, Blocks12Config
from ..ops import scopes
from ..ops.reference import conv2d, lrn, maxpool, relu
from .mesh import make_mesh


def _channel_halo(z: jax.Array, half: int, axis_name: str, n_shards: int) -> jax.Array:
    """Attach ``half`` neighbor channels on each side of the local slice.

    Ring-edge shards receive ppermute's zero fill — equivalent to the LRN's
    clipped-window edge semantics, since the window sums squares and the
    zero channels contribute nothing (ops.reference.lrn edge behavior).
    """
    fwd = [(i, i + 1) for i in range(n_shards - 1)]  # shard i -> i+1
    bwd = [(i + 1, i) for i in range(n_shards - 1)]
    left = lax.ppermute(z[..., -half:], axis_name, fwd)  # prev shard's last channels
    right = lax.ppermute(z[..., :half], axis_name, bwd)  # next shard's first channels
    return jnp.concatenate([left, z, right], axis=-1)


def build_tp_forward(
    model_cfg: Blocks12Config = BLOCKS12,
    n_shards: int = 1,
    mesh: Optional[Mesh] = None,
    axis_name: str = "tp",
    with_digests: bool = False,
) -> Callable:
    """Jitted ``(params, x) -> out`` with conv filters K-sharded n ways.

    ``with_digests``: return ``(out, {layer: (n_shards,) float32})`` with
    one in-graph activation digest per Conv1/Pool1/Conv2/Pool2/LRN2
    boundary, taken on each shard's LOCAL channel slice inside the
    shard_map body (the SDC sentinel taps — see ``parallel.sharded``).
    """
    cfg = model_cfg
    for name, spec in (("conv1", cfg.conv1), ("conv2", cfg.conv2)):
        if spec.out_channels % n_shards:
            raise ValueError(
                f"{name} K={spec.out_channels} not divisible by {n_shards} TP shards"
            )
    half = cfg.lrn2.size // 2
    local2 = cfg.conv2.out_channels // n_shards
    if n_shards > 1 and local2 < half:
        raise ValueError(
            f"LRN window half-width {half} exceeds the {local2} local channels "
            f"at {n_shards} shards — channel halo would need multi-hop"
        )
    if mesh is None:
        mesh = make_mesh(n_shards, axis_name=axis_name)
    else:
        axis_name = mesh.axis_names[-1]
        axis_size = mesh.devices.shape[-1]
        if axis_size != n_shards:
            raise ValueError(
                f"mesh axis {axis_name!r} has {axis_size} devices but "
                f"tp n_shards={n_shards}; the filter slices would not line up"
            )

    if with_digests:
        from ..resilience.sentinel import tree_digest

    def local(params, x):
        p1, p2 = params["conv1"], params["conv2"]
        digs = {}

        def tap(name, v):
            # In-graph sentinel tap on the shard-LOCAL channel slice; one
            # float32 scalar per shard, concatenated to (n,) by out_specs.
            if with_digests:
                digs[name] = tree_digest(v)[None]
            return v

        conv1, pool1, conv2, pool2, lrn2 = scopes.BLOCKS12_LAYERS
        # Block 1 on this shard's filter slice: (B, h, w, K1/n).
        with scopes.layer(conv1):
            y = tap(conv1, relu(conv2d(x, p1["w"], p1["b"], stride=cfg.conv1.stride, padding=cfg.conv1.padding)))
        with scopes.layer(pool1):
            y = tap(pool1, maxpool(y, window=cfg.pool1.window, stride=cfg.pool1.stride))
        with scopes.layer(conv2):
            # conv2 needs every conv1 channel: gather the channel axis (the
            # TP boundary collective — activations are small here, 27x27x96).
            with scopes.halo(conv2):
                y = lax.all_gather(y, axis_name, axis=3, tiled=True)
            z = tap(conv2, relu(conv2d(y, p2["w"], p2["b"], stride=cfg.conv2.stride, padding=cfg.conv2.padding)))
        with scopes.layer(pool2):
            z = tap(pool2, maxpool(z, window=cfg.pool2.window, stride=cfg.pool2.stride))
        with scopes.layer(lrn2):
            # LRN crosses channels: exchange `half` neighbor channels,
            # normalize, keep the owned slice. The slab is laid into zeros
            # of the full channel width at its own offset first: the LRN's
            # window sum is a matmul over channels, and a backend may group
            # a contraction's terms by its width (XLA:CPU does), so each
            # term must meet the index it meets on one device for the
            # bitwise claim above to hold; the zeros add exactly.
            if n_shards > 1:
                with scopes.halo(lrn2):
                    zp = _channel_halo(z, half, axis_name, n_shards)
                k2 = cfg.conv2.out_channels
                start = lax.axis_index(axis_name) * local2
                wide = jnp.zeros((*z.shape[:-1], k2 + 2 * half), z.dtype)
                wide = lax.dynamic_update_slice_in_dim(wide, zp, start, axis=-1)
                zp = wide[..., half : half + k2]
            else:
                zp = z
            zl = lrn(
                zp,
                size=cfg.lrn2.size,
                alpha=cfg.lrn2.alpha,
                beta=cfg.lrn2.beta,
                k=cfg.lrn2.k,
                alpha_over_size=cfg.lrn2.alpha_over_size,
            )
            out = lax.dynamic_slice_in_dim(zl, start, local2, axis=-1) if n_shards > 1 else zl
        tap(lrn2, out)
        return (out, digs) if with_digests else out

    wspec = P(None, None, None, axis_name)  # HWIO: shard the O axis
    pspec = {
        "conv1": {"w": wspec, "b": P(axis_name)},
        "conv2": {"w": wspec, "b": P(axis_name)},
    }
    out_spec = P(None, None, None, axis_name)
    stages = scopes.BLOCKS12_LAYERS
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=(
            (out_spec, {s: P(axis_name) for s in stages})
            if with_digests
            else out_spec
        ),
    )
    return jax.jit(fn)


# --- Megatron-style tensor parallelism for the transformer LM family ----
#
# The conv TP above hand-writes its collectives (channel-halo LRN needs
# them); the LM's matmuls need none by hand — the classic Megatron layout
# (column-parallel wqkv/w_up, row-parallel wo/w_down, heads implicitly
# split with wqkv) is expressed as GSPMD shardings and XLA inserts the two
# all-reduces per block on its own. Scaling-book recipe: pick the mesh,
# annotate, let the compiler place collectives.

_LM_TP_SPECS = {
    "wqkv": ("tp_col",),    # (D, 3, D) -> shard the last (per-projection) dim
    "w_up": ("tp_col",),    # (D, F)    -> shard output dim
    "wo": ("tp_row",),      # (D, D)    -> shard input dim
    "w_down": ("tp_row",),  # (F, D)    -> shard input dim
}


def shard_lm_params_tp(params, mesh=None, *, n_shards: int = 0, axis_name: str = "tp"):
    """device_put transformer-LM params in the Megatron TP layout.

    Column-parallel matrices shard their LAST dim, row-parallel their
    FIRST; embeddings, position table, norms (and MoE expert stacks, whose
    parallel axis is "ep", not "tp") stay replicated. Works on dense-FFN
    configs; per-matrix divisibility is validated eagerly.
    """
    from jax.sharding import NamedSharding

    from .mesh import make_mesh as _make_mesh

    if mesh is None:
        mesh = _make_mesh(n_shards, axis_name=axis_name)
    tp = mesh.shape[axis_name]

    def put(path, leaf):
        key = getattr(path[-1], "key", None) if path else None
        kind = _LM_TP_SPECS.get(key)
        # ndim rules keep this strictly the DENSE Megatron layout: wqkv is
        # the (D, 3, D) rank-3 exception (column shard on the last,
        # per-projection dim so q/k/v boundaries stay aligned). MoE expert
        # stacks share the w_up/w_down key names at rank 3 but belong to
        # the "ep" axis (parallel/expert.py), so they fall through to
        # replication here, as documented.
        if key == "wqkv" and leaf.ndim == 3:
            dim = 2
        elif kind is not None and leaf.ndim == 2:
            dim = 1 if kind[0] == "tp_col" else 0
        else:
            return jax.device_put(leaf, NamedSharding(mesh, P()))
        if leaf.shape[dim] % tp:
            raise ValueError(
                f"{key} dim {dim} size {leaf.shape[dim]} not divisible by "
                f"{tp} '{axis_name}' shards"
            )
        spec_axes = [None] * leaf.ndim
        spec_axes[dim] = axis_name
        return jax.device_put(leaf, NamedSharding(mesh, P(*spec_axes)))

    return jax.tree_util.tree_map_with_path(put, params)
