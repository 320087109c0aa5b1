"""Pipeline parallelism: GPipe-style microbatch rotation over a ``pp`` axis.

The reference has no pipeline tier (SURVEY §2.2 marks PP "no"); this module
completes the framework's parallelism zoo (dp / sp-cp / tp / pp / ep) the
TPU-native way: the layer stack is split into S stages, stage s's params
live on mesh slot s (``shard_map`` over the "pp" axis), and microbatches
rotate stage-to-stage via ``lax.ppermute`` — ICI neighbor traffic, the same
collective that carries the conv halo and the ring-attention K/V blocks.

Schedule: classic GPipe fill-and-drain. With M microbatches and S stages
the loop runs M + S - 1 steps; at step t, stage 0 ingests microbatch t
(while t < M) and stage S-1 emits microbatch t - (S-1) (once t >= S-1).
The whole schedule is a single ``lax.scan`` — compiled program size is
O(1) in both M and S — and is differentiable end to end, so the same code
path serves training (activations are rematerialized by scan's transpose,
GPipe's per-microbatch checkpointing for free).

No deviation from the math: pipelining reorders *scheduling*, not
arithmetic — per-microbatch outputs are bit-identical to the sequential
forward (enforced in tests/test_pipeline.py).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import make_mesh

Params = Any


def stack_layers(layers: List[Params]) -> Params:
    """List of per-layer pytrees -> one pytree with a stacked leading axis.

    The stacked axis is what ``pipeline_apply`` shards over "pp" (and what
    the stage body scans over), so S stages of L/S layers each see leaves
    of shape (S, L/S, ...)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)


def _reshape_stages(stacked: Params, n_stages: int) -> Params:
    def r(x):
        n_layers = x.shape[0]
        if n_layers % n_stages:
            raise ValueError(f"{n_layers} layers not divisible by {n_stages} stages")
        return x.reshape(n_stages, n_layers // n_stages, *x.shape[1:])

    return jax.tree_util.tree_map(r, stacked)


def pipeline_apply(
    layer_fn: Callable[[Params, jax.Array], jax.Array],
    stacked_layers: Params,
    x: jax.Array,
    *,
    n_stages: int,
    n_microbatches: int,
    mesh: Optional[Mesh] = None,
    axis_name: str = "pp",
    dp_axis: Optional[str] = None,
) -> jax.Array:
    """Run ``layer_fn`` over every layer of ``stacked_layers`` on ``x``,
    layers split into ``n_stages`` pipeline stages over the mesh.

    ``layer_fn(layer_params, x) -> x`` applies ONE layer; stages scan it
    over their layers-per-stage block. ``x`` is (B, ...) with B divisible
    by ``n_microbatches``. Returns the same (B, ...) as the sequential
    ``for layer: x = layer_fn(layer, x)`` composition.

    ``dp_axis`` composes the pipeline with data parallelism on a 2-D mesh
    (e.g. ``Mesh(..., ("dp", "pp"))``): each dp row runs the full pipeline
    on its microbatch slice — stage params replicated over dp, microbatch
    dim sharded over dp, ppermute/psum confined to the pp axis. The caller
    shards B over dp outside (or relies on shard_map's split here).
    """
    b = x.shape[0]
    m = n_microbatches
    if b % m:
        raise ValueError(f"batch {b} not divisible by {m} microbatches")
    if mesh is None:
        if dp_axis is not None:
            raise ValueError(
                "dp_axis requires an explicit 2-D mesh containing that axis "
                "(the auto-built default mesh is pp-only)"
            )
        mesh = make_mesh(n_stages, axis_name=axis_name)
    if dp_axis is not None:
        if dp_axis not in mesh.axis_names:
            raise ValueError(
                f"dp_axis {dp_axis!r} not in mesh axes {mesh.axis_names}"
            )
        dp = mesh.shape[dp_axis]
        if (b // m) % dp:
            raise ValueError(
                f"microbatch size {b // m} not divisible by dp axis {dp}"
            )
    staged = _reshape_stages(stacked_layers, n_stages)
    x_mb = x.reshape(m, b // m, *x.shape[1:])

    def stage_body(stage_params, x_all):
        """One device's life: S + M - 1 scan steps of its own stage."""
        me = lax.axis_index(axis_name)
        s = n_stages

        def apply_stage(inp):
            # stage_params leaves are (1, L/S, ...) after shard_map split.
            def one_layer(h, lp):
                return layer_fn(lp, h), None

            squeezed = jax.tree_util.tree_map(lambda p: p[0], stage_params)
            out, _ = lax.scan(one_layer, inp, squeezed)
            return out

        def step(carry, t):
            state = carry
            # Stage 0 ingests microbatch t (clamped; steps past M re-feed
            # the last microbatch, but their outputs are never collected).
            feed = lax.dynamic_index_in_dim(
                x_all, jnp.clip(t, 0, m - 1), axis=0, keepdims=False
            )
            inp = jnp.where(me == 0, feed, state)
            out = apply_stage(inp)
            # Last stage's output at step t is microbatch t-(S-1): collect
            # it there, zeros elsewhere; psum after the scan replicates.
            emit = jnp.where((me == s - 1) & (t >= s - 1), out, jnp.zeros_like(out))
            # Rotate every stage's output one hop down the pipeline.
            nxt = lax.ppermute(
                out, axis_name, [(i, (i + 1) % s) for i in range(s)]
            )
            return nxt, emit

        state0 = jnp.zeros_like(x_all[0])
        _, emitted = lax.scan(step, state0, jnp.arange(m + s - 1))
        # emitted: (M+S-1, mb, ...); microbatch j lives at step S-1+j on the
        # last stage and is zero everywhere else -> psum replicates it.
        y = lax.psum(emitted[s - 1 :], axis_name)
        return y

    if dp_axis is None:
        in_specs = (P(axis_name), P())  # stage axis sharded; input replicated
        out_specs = P()
    else:
        # dp x pp: stage params replicated over dp; the microbatch dim (dim 1
        # of x_mb) and of the output sharded over dp.
        in_specs = (P(axis_name), P(None, dp_axis))
        out_specs = P(None, dp_axis)
    fn = shard_map(
        stage_body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        # noqa: check-vma-disabled — the psum-of-zeros collection trick
        # produces a genuinely replicated result the checker can't prove.
        check_vma=False,
    )
    y = fn(staged, x_mb)
    return y.reshape(b, *x.shape[1:])


def pipeline_lm_forward(
    params: Params,
    tokens: jax.Array,
    cfg,
    *,
    n_stages: int,
    n_microbatches: int,
    mesh: Optional[Mesh] = None,
    dp_axis: Optional[str] = None,
) -> jax.Array:
    """Transformer-LM forward with the decoder stack pipelined over "pp".

    Embedding and the weight-tied head run replicated outside the pipeline
    (they are a tiny fraction of the FLOPs); the n_layers decoder blocks
    are staged. Numerically identical to ``forward_lm`` — enforced in
    tests/test_pipeline.py.
    """
    from ..models.transformer import decoder_block, rmsnorm

    b, l = tokens.shape
    if l > cfg.max_len:
        raise ValueError(f"sequence length {l} exceeds max_len {cfg.max_len}")
    x = params["embed"][tokens] + params["pos"][:l][None]
    stacked = stack_layers(params["layers"])
    x = pipeline_apply(
        functools.partial(decoder_block, cfg=cfg),
        stacked,
        x,
        n_stages=n_stages,
        n_microbatches=n_microbatches,
        mesh=mesh,
        dp_axis=dp_axis,
    )
    x = rmsnorm(x, params["final_norm"]["g"])
    return x @ params["embed"].T


def pipeline_lm_loss(
    params: Params,
    tokens: jax.Array,
    cfg,
    *,
    n_stages: int,
    n_microbatches: int,
    mesh: Optional[Mesh] = None,
    dp_axis: Optional[str] = None,
) -> jax.Array:
    """Next-token cross-entropy through the pipelined forward."""
    logits = pipeline_lm_forward(
        params,
        tokens[:, :-1],
        cfg,
        n_stages=n_stages,
        n_microbatches=n_microbatches,
        mesh=mesh,
        dp_axis=dp_axis,
    ).astype(jnp.float32)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)
