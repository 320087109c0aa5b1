"""Minimal distributed training step over the Blocks 1-2 model.

The reference is inference-only, but the framework exposes a training
capability as the natural extension point (SURVEY §7.2 step 8 "future work"):
MSE regression loss, optax SGD, data-parallel gradient psum implied by
sharding constraints — XLA inserts the collectives (GSPMD) from the
annotations, the idiomatic TPU replacement for hand-written MPI reductions.
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .models.alexnet import BLOCKS12, Blocks12Config, forward_blocks12


def make_train_step(
    cfg: Blocks12Config = BLOCKS12,
    mesh: Mesh | None = None,
    optimizer: optax.GradientTransformation | None = None,
    lr: float = 1e-3,
    sp_shards: int = 0,
    tp_shards: int = 0,
    remat: bool = False,
    with_grad_norm: bool = False,
) -> Tuple[Callable, Callable]:
    """Build ``(init_fn, step_fn)`` for any optax optimizer (default SGD).

    ``init_fn(params) -> opt_state``;
    ``step_fn(params, opt_state, x, y) -> (new_params, new_opt_state, loss)``.

    ``with_grad_norm=True`` appends the global gradient L2 norm to the step
    output (``(new_params, new_opt_state, loss, grad_norm)``) — computed
    inside the jitted step so the SDC sentinel screens gradients without a
    second device round-trip.

    When ``mesh`` is given, activations are constrained to shard batch over
    "dp" (if present); params stay replicated, so XLA emits the all-reduce
    for the gradient sum automatically.

    ``sp_shards >= 1`` instead routes the forward through the explicit
    shard_map + ppermute halo pipeline (parallel.sharded) over a 1-D "sp"
    mesh — spatial/context-parallel training. This path is used *instead of*
    GSPMD H-axis annotation because the latter produces wrong conv weight
    gradients in this JAX build (see x_spec note below); shard_map's
    collectives have exact transposes (ppermute^T = reverse permute,
    replicated-in^T = psum), so gradients here are correct by construction.

    ``tp_shards >= 1`` routes the forward through the K-axis filter
    decomposition (parallel.tensor_parallel): conv weights sharded over the
    mesh's last axis, gradients flow through the same explicit collectives
    (all_gather^T = dynamic-slice+psum, channel-ppermute^T = reverse shift).
    """
    if sp_shards and tp_shards:
        raise ValueError("sp_shards and tp_shards are mutually exclusive strategies")
    opt = optimizer if optimizer is not None else optax.sgd(lr)

    def _build_step(loss_fn, pre=None, post=None):
        return _jit_step(opt, loss_fn, pre, post, with_grad_norm=with_grad_norm)

    if sp_shards and sp_shards >= 1:
        from .parallel.sharded import build_sharded_forward

        if mesh is not None:
            sp_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get("sp")
            if sp_size != sp_shards:
                raise ValueError(
                    f"mesh 'sp' axis has {sp_size} devices but sp_shards={sp_shards}; "
                    "the halo/ownership plan would be built for the wrong shard count"
                )
        sharded_fwd = build_sharded_forward(cfg, n_shards=sp_shards, mesh=mesh)
        if remat:
            sharded_fwd = jax.checkpoint(sharded_fwd)

        def sp_loss_fn(params, x, y):
            return jnp.mean((sharded_fwd(params, x) - y) ** 2)

        return opt.init, _build_step(sp_loss_fn)

    if tp_shards and tp_shards >= 1:
        from .parallel.tensor_parallel import build_tp_forward

        tp_fwd = build_tp_forward(cfg, n_shards=tp_shards, mesh=mesh)
        if remat:
            tp_fwd = jax.checkpoint(tp_fwd)

        def tp_loss_fn(params, x, y):
            return jnp.mean((tp_fwd(params, x) - y) ** 2)

        return opt.init, _build_step(tp_loss_fn)

    def base_fwd(params, x):
        return forward_blocks12(params, x, cfg)

    if remat:
        # Trade FLOPs for memory: recompute activations in the backward pass.
        base_fwd = jax.checkpoint(base_fwd)

    def loss_fn(params, x, y):
        return jnp.mean((base_fwd(params, x) - y) ** 2)

    pre, post = _dp_pre_post(mesh)
    return opt.init, _build_step(loss_fn, pre=pre, post=post)


def make_elastic_step_builder(
    cfg: Blocks12Config = BLOCKS12,
    optimizer: optax.GradientTransformation | None = None,
    lr: float = 1e-3,
    remat: bool = False,
    with_grad_norm: bool = False,
) -> Callable:
    """``(entry, mesh) -> step_fn`` for the supervisor's step-replay path
    (``resilience.supervisor.Supervisor(step_builder=...)``).

    Maps a ladder rung onto :func:`make_train_step`'s strategies, building
    against the SURVIVING-device mesh the supervisor passes after a shrink
    — never a mesh of its own (the stale-device-set discipline). ONE
    optimizer instance is shared across every rung, so the opt-state tree
    stays structurally identical through a degrade and the live reshard is
    a pure ``jax.device_put`` — no state translation, no checkpoint
    round-trip.
    """
    opt = optimizer if optimizer is not None else optax.sgd(lr)

    def build(entry, mesh) -> Callable:
        if entry.strategy in ("halo", "staged_halo") and entry.n_shards >= 2:
            return make_train_step(
                cfg, mesh=mesh, optimizer=opt, sp_shards=entry.n_shards,
                remat=remat, with_grad_norm=with_grad_norm,
            )[1]
        if entry.strategy == "tp" and entry.n_shards >= 2:
            return make_train_step(
                cfg, mesh=mesh, optimizer=opt, tp_shards=entry.n_shards,
                remat=remat, with_grad_norm=with_grad_norm,
            )[1]
        if entry.strategy in ("single", "replicated") or entry.n_shards == 1:
            return make_train_step(
                cfg, optimizer=opt, remat=remat, with_grad_norm=with_grad_norm
            )[1]
        raise ValueError(f"no elastic training step for ladder entry {entry.key}")

    return build


def _jit_step(opt, loss_fn, pre=None, post=None, with_grad_norm=False) -> Callable:
    """The shared update scaffold: (optional pre-constraints) ->
    value_and_grad -> opt.update -> apply_updates -> (optional post) —
    ONE home for the step discipline every trainable uses."""

    @jax.jit
    def step(params, opt_state, x, y):
        if pre is not None:
            params, x = pre(params, x)
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        updates, new_opt_state = opt.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        if post is not None:
            new_params = post(new_params)
        if with_grad_norm:
            return new_params, new_opt_state, loss, optax.global_norm(grads)
        return new_params, new_opt_state, loss

    return step


def _dp_pre_post(mesh: Mesh | None):
    """(pre, post) sharding-constraint pair for the replicated-params /
    dp-sharded-batch discipline; (None, None) without a mesh.

    Batch (dp) sharding only. Spatial-parallel training goes through the
    explicitly-differentiable shard_map + ppermute halo path in
    parallel.sharded (the framework's explicit-collectives design, the
    reference's MPI-halo analogue) rather than a GSPMD "sp" annotation on
    the H axis. Round 1 additionally observed wrong conv *weight*
    gradients from the GSPMD partitioner with an H-axis annotation;
    round 2 could NOT reproduce that on cpu/jax==0.9.0 (minimal conv,
    full model, remat, dp x sp all give correct grads — see
    scripts/gspmd_conv_grad_repro.py and tests/test_gspmd_repro.py, which
    will fail loudly if the bug (re)appears). Behavior on the TPU backend
    is still unverified.
    """
    if mesh is None:
        return None, None
    spec = P("dp" if "dp" in mesh.axis_names else None)

    def pre(params, x):
        return (
            jax.lax.with_sharding_constraint(params, NamedSharding(mesh, P())),
            jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec)),
        )

    def post(new_params):
        return jax.lax.with_sharding_constraint(new_params, NamedSharding(mesh, P()))

    return pre, post


def make_classifier_train_step(
    cfg,
    mesh: Mesh | None = None,
    optimizer: optax.GradientTransformation | None = None,
    lr: float = 1e-3,
    remat: bool = False,
) -> Tuple[Callable, Callable]:
    """(init_fn, step_fn) for FULL-AlexNet classification training.

    The reference's extension task (conv3-5 + FC6-8, summary.md:29-45) made
    trainable: cross-entropy over the FC8 logits,
    ``step_fn(params, opt_state, x, labels)``. With a mesh containing "dp",
    the batch is sharded over it and params stay replicated (GSPMD emits
    the gradient all-reduce), same discipline as make_train_step.
    """
    from .models.alexnet_full import forward_alexnet

    opt = optimizer if optimizer is not None else optax.adam(lr)

    fwd = forward_alexnet
    if remat:
        fwd = jax.checkpoint(fwd, static_argnums=(2,))

    def loss_fn(params, x, labels):
        logits = fwd(params, x, cfg).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))

    pre, post = _dp_pre_post(mesh)
    return opt.init, _jit_step(opt, loss_fn, pre, post)
