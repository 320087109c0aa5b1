"""A chip's share of a mixture-of-experts layer, a sigmoid router for it, and
what the decoder families built on it have in common: the float32 RMSNorm, the
product in the parameters' type, SwiGLU, the seeded draw of a parameter tree
and the routing statistics.

- *Router*: ``s = sigmoid(u W_r)`` in float32; selection on ``s + bias``: a
  group's score is the sum of its top 2, the top ``topk_group`` groups are
  kept, the top ``num_experts_per_tok`` experts among them chosen (one group
  of all the experts is the plain top-k); weights are the unbiased ``s`` of
  the chosen, normalised to sum 1, times ``routed_scaling_factor``.
- *MoE*: ``sum_i w_i Expert_i(u) + Shared(u)`` (``_moe``). A model with a
  router of its own hands its ``(chosen, weights)`` to ``_routed`` and adds
  what else its layer has: the routed sum needs no shared expert.

**The chip's share.** The layer is told which experts it holds
(``[experts_first, experts_first + experts_held)``). It routes over all
``n_routed_experts``, computes only the (token, expert) pairs whose expert it
holds — dropless, no capacity: the pairs are sorted by expert, each expert's
rows padded to whole tiles, ``ops.grouped_matmul`` runs over as many chunks of
rows as were routed here, and every token collects the rows of its own pairs,
weighted (``ops.moe_combine``: one kernel that moves only the rows that were
computed, or one gather per place where the shapes leave it nothing to save)
— adds the shared expert, and that partial sum goes on to the next layer.
An index outside the held experts, whatever it stands for (another
chip's expert, a router's output that means "no expert"), is a pair of no
work. What the absent experts would add arrives, in a deployment, by the
exchange of ``parallel.expert``; nothing here stands in for it.

Nothing here knows which model calls it: a model hands over its parameters and
a configuration with the fields of :class:`MoeShareConfig`
(``models.mla_moe.MlaMoeConfig``, ``models.kda_moe.KdaMoeConfig``) or, for the
routed sum alone, of :class:`RoutedShareConfig`.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, List, Optional, Protocol, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import scopes
from ..ops.flash_attention import causal_plan
from ..ops.grouped_matmul import grouped_matmul
from ..ops.moe_combine import moe_combine, moe_combine_reference, row_slab, worth_a_kernel
from ..ops.reference import mxu_precision

Params = Dict[str, Any]


class RoutedShareConfig(Protocol):
    """What the routed sum (``_routed``, ``check_share``, the statistics)
    reads of a model's configuration."""

    n_routed_experts: int  # the router's width: every expert of the layer
    num_experts_per_tok: int
    experts_held: int  # the experts this chip holds ...
    experts_first: int  # ... are [experts_first, experts_first + experts_held)
    expert_tile_rows: int  # rows of one tile of the grouped product
    expert_chunk_rows: int  # rows gathered and multiplied at a time
    expert_span_rows: int  # rows of results held until their tokens gather them back


class MoeShareConfig(RoutedShareConfig, Protocol):
    """What ``route`` and ``_moe`` read besides: the sigmoid router's groups,
    the norm and the shared expert."""

    rms_norm_eps: float
    n_group: int
    topk_group: int
    routed_scaling_factor: float
    n_shared_experts: int


def check_share(cfg: RoutedShareConfig) -> None:
    """What a configuration's ``__post_init__`` asks of its share."""
    if not 0 <= cfg.experts_first <= cfg.n_routed_experts - cfg.experts_held:
        raise ValueError("the experts held must lie inside the router's width")
    if cfg.expert_chunk_rows % cfg.expert_tile_rows:
        raise ValueError("expert_chunk_rows must be whole tiles")
    if cfg.expert_span_rows % cfg.expert_chunk_rows:
        raise ValueError("expert_span_rows must be whole chunks")


def check_sigmoid_moe(cfg: MoeShareConfig) -> None:
    """What ``_moe`` asks besides: whole groups, and the one shared expert it adds."""
    check_share(cfg)
    if cfg.n_routed_experts % cfg.n_group:
        raise ValueError("n_routed_experts must divide into n_group groups")
    if cfg.n_shared_experts != 1:
        raise ValueError("one shared expert is what this model computes")


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def swiglu_shapes(d: int, width: int, lead: Tuple[int, ...] = ()) -> Params:
    return {
        "gate": ((*lead, d, width), d),
        "up": ((*lead, d, width), d),
        "down": ((*lead, width, d), width),
    }


def moe_shapes(d: int, width: int, cfg: MoeShareConfig) -> Params:
    """The MoE part of a layer as ``(shape, fan_in)`` leaves; ``fan_in`` -1
    marks the router's selection bias."""
    return {
        "router": ((d, cfg.n_routed_experts), d),
        "bias": ((cfg.n_routed_experts,), -1),
        "experts": swiglu_shapes(d, width, (cfg.experts_held,)),
        "shared": swiglu_shapes(d, width),
    }


def _is_leaf(node) -> bool:
    return isinstance(node, tuple) and len(node) == 2 and isinstance(node[1], int)


# The selection bias is drawn small: enough that selection (biased) and
# weighting (unbiased) differ, little enough that it unbalances no expert's
# load by more than a tenth (a trained bias is there to balance the load).
# The scale is a SIGMOID router's, whose scores lie about 0.5; a softmax
# score over hundreds of outputs is a hundred times smaller, and a family
# with such a router draws its bias at a scale of its own (``draw_leaf``).
BIAS_SCALE = 0.005


def _draw_leaf(key, shape, fan_in, dtype):
    if fan_in == 0:
        return jnp.ones(shape, dtype)
    scale = BIAS_SCALE if fan_in < 0 else fan_in**-0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _draw_tree(key, shapes, dtype, draw_leaf=_draw_leaf):
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [draw_leaf(k, shape, fan_in, dtype) for k, (shape, fan_in) in zip(keys, leaves)]
    )


def init_by_layer(key, shapes: Params, kinds: Sequence[str], dtype, draw_leaf=_draw_leaf) -> Params:
    """Seeded parameters stored in ``dtype`` for a tree of ``(shape, fan_in)``
    leaves whose ``"layers"`` are of the given ``kinds``: normal weights of
    scale ``fan_in**-0.5``, norm gains 1 (``fan_in`` 0), a small selection bias
    (-1); ``draw_leaf`` may know further marks. One jitted draw per layer (one
    program per kind of layer) and one for everything outside the layers, so
    the draw's peak is a layer and never the model."""
    shapes = dict(shapes)
    layer_shapes = shapes.pop("layers")
    draw = {
        kind: jax.jit(functools.partial(
            _draw_tree, shapes=layer_shapes[kinds.index(kind)], dtype=dtype, draw_leaf=draw_leaf
        ))
        for kind in set(kinds)
    }
    keys = jax.random.split(key, len(layer_shapes) + 1)
    params = jax.jit(functools.partial(_draw_tree, shapes=shapes, dtype=dtype, draw_leaf=draw_leaf))(keys[0])
    params["layers"] = [draw[kind](k) for kind, k in zip(kinds, keys[1:])]
    return params


def init_stacked(key, shapes: Params, num_layers: int, dtype, draw_leaf=_draw_leaf) -> Params:
    """As :func:`init_by_layer` for a tree whose ``"layers"`` is ONE layer's
    tree with the layers as every leaf's first axis (what a ``lax.scan`` over
    the depth takes): the layers are drawn one at a time inside one program
    (``lax.map`` over the layers' keys, each straight into its place in the
    stack), so the draw's peak is the stack and one layer, never two models."""
    shapes = dict(shapes)
    one_layer = jax.tree.map(lambda leaf: (leaf[0][1:], leaf[1]), shapes.pop("layers"), is_leaf=_is_leaf)
    draw = functools.partial(_draw_tree, dtype=dtype, draw_leaf=draw_leaf)
    k_rest, k_layers = jax.random.split(key)
    params = jax.jit(functools.partial(draw, shapes=shapes))(k_rest)
    params["layers"] = jax.jit(
        lambda keys: lax.map(functools.partial(draw, shapes=one_layer), keys)
    )(jax.random.split(k_layers, num_layers))
    return params


def stacked(layer: Params, num_layers: int) -> Params:
    """One layer's ``(shape, fan_in)`` tree with ``num_layers`` as every leaf's first axis."""
    return jax.tree.map(lambda leaf: ((num_layers, *leaf[0]), leaf[1]), layer, is_leaf=_is_leaf)


def count(shapes: Params) -> int:
    leaves = jax.tree.leaves(shapes, is_leaf=_is_leaf)
    return sum(math.prod(shape) for shape, _fan_in in leaves)


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def _rms_norm(x, gain, eps: float):
    """RMSNorm with float32 statistics; float32 out."""
    xf = x.astype(jnp.float32)
    return xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps) * gain.astype(jnp.float32)


def _mm(spec: str, x, w):
    """``einsum`` with the operands in the parameters' type and a float32 result."""
    return jnp.einsum(
        spec, x.astype(w.dtype), w,
        preferred_element_type=jnp.float32, precision=mxu_precision(w.dtype),
    )


def _swiglu(p: Params, u):
    """``down(silu(gate u) * up u)``; ``u`` in the parameters' type, float32 out."""
    hidden = jax.nn.silu(_mm("td,df->tf", u, p["gate"])) * _mm("td,df->tf", u, p["up"])
    return _mm("tf,fd->td", hidden, p["down"])


def route(p: Params, u, cfg: MoeShareConfig):
    """``(chosen experts (T, k) int32, their weights (T, k) float32)`` of the
    tokens ``u (T, D)``: sigmoid scores, selection on the biased scores
    limited to the best groups, weights from the unbiased ones."""
    scores = jax.nn.sigmoid(_mm("td,de->te", u, p["router"]))
    biased = scores + p["bias"].astype(jnp.float32)
    groups = biased.reshape(-1, cfg.n_group, cfg.n_routed_experts // cfg.n_group)
    group_score = lax.top_k(groups, 2)[0].sum(axis=-1)  # (T, n_group)
    _best, kept = lax.top_k(group_score, cfg.topk_group)
    keep = jnp.any(kept[..., None] == jnp.arange(cfg.n_group), axis=1)  # (T, n_group)
    candidates = jnp.where(keep[..., None], groups, -jnp.inf).reshape(biased.shape)
    _top, chosen = lax.top_k(candidates, cfg.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20) * cfg.routed_scaling_factor
    return chosen.astype(jnp.int32), weights


def route_softmax(p: Params, u, cfg):
    """``(chosen outputs (T, k) int32, their weights (T, k) float32)`` of the
    tokens ``u (T, D)`` for a router that is a softmax over all its outputs
    (``p["router"]``'s columns: experts, and whatever else the family routes
    to), no groups: the top ``num_experts_per_tok`` of ``scores + bias``,
    weighted by their unbiased scores times ``routed_scaling_factor``, not
    renormalised."""
    scores = jax.nn.softmax(_mm("td,de->te", u, p["router"]), axis=-1)
    _top, chosen = lax.top_k(scores + p["bias"].astype(jnp.float32), cfg.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, chosen, axis=-1) * cfg.routed_scaling_factor
    return chosen.astype(jnp.int32), weights


def balanced_bias(bias, choose: Callable[[Any], Any], rounds: int = 8, step: float = 0.04):
    """The selection bias that balances the load of a router's outputs, one
    per entry of ``bias``, where ``choose(bias) -> chosen (T, k)`` is the
    router's choice for some tokens under a bias (``route``'s first result, or
    a model's own). A trained bias is there to balance the load (it is nudged
    against each expert's excess, step after step of training); seeded weights
    have none,
    and where the stream carries a token-independent part the router then
    sends several times the mean to some experts. ``rounds`` of ``bias -=
    step * ln(load / mean)`` from the bias given (where 8 of 320 sigmoid
    scores are chosen, ``ln(load)`` moves by about 19 per unit of bias, so
    ``step`` 0.04 is a damped Newton step); only the selection moves, the
    weights of the chosen stay their unbiased scores."""
    outputs = jnp.arange(bias.shape[0])

    def one_round(b, _):
        load = jnp.sum(choose(b)[..., None] == outputs, axis=(0, 1)).astype(jnp.float32)
        return b - step * jnp.log((load + 1.0) / (load.mean() + 1.0)), None

    balanced, _ = lax.scan(one_round, bias.astype(jnp.float32), None, length=rounds)
    return balanced.astype(bias.dtype)


def _dispatch(chosen, cfg: RoutedShareConfig):
    """The (token, expert) pairs whose expert is held here, sorted by expert:
    ``(order (P,), sizes, start, pad_start, pad_end, row (P,))`` with
    ``P = T * k`` pairs in all; ``order`` lists pair indices expert by expert
    (pairs of absent experts last), ``sizes[e]`` counts expert ``e``'s pairs,
    ``start`` is its first place in ``order`` and ``[pad_start, pad_end)`` its
    rows once every expert's rows are padded to whole tiles; ``row[p]`` is the
    padded row of pair ``p``, -1 where its expert is absent."""
    local = chosen.reshape(-1) - cfg.experts_first
    key = jnp.where((local >= 0) & (local < cfg.experts_held), local, cfg.experts_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    mine = key[:, None] == jnp.arange(cfg.experts_held)  # (P, held)
    sizes = jnp.sum(mine, axis=0, dtype=jnp.int32)
    tm = cfg.expert_tile_rows
    padded = (sizes + tm - 1) // tm * tm
    pad_end = jnp.cumsum(padded)
    pad_start = pad_end - padded
    # the sort is stable, so a pair's rank among its expert's is its count so far
    rank = jnp.cumsum(mine, axis=0, dtype=jnp.int32) - 1
    row = jnp.sum(jnp.where(mine, pad_start[None, :] + rank, 0), axis=1) - (key == cfg.experts_held)
    return order, sizes, jnp.cumsum(sizes) - sizes, pad_start, pad_end, row.astype(jnp.int32)


def _routed_experts(p: Params, u, weights, dispatch, cfg: RoutedShareConfig, group_base=None):
    """``sum_i w_i Expert_i(u)`` over the pairs whose expert is held here,
    float32 ``(T, D)``. ``p`` holds the held experts' matrices ``(held, ...)``
    or, with ``group_base``, a stack of several layers' ``(layers * held,
    ...)`` of which this layer's begin at ``group_base`` (a loop over layers
    then hands every layer the one stack, and no layer's experts are copied
    out of it for the kernel). Span by span of the padded rows (one span holds a
    usual load): chunk by chunk, gather the rows' tokens and run the three
    grouped products into the span's results; then the combine adds to every
    token the rows of its own pairs, weighted, in place order. Where a row is
    wide and a token has many places (``worth_a_kernel``, from the shapes
    alone) that is ``moe_combine``: the results are kept as slabs, a row in
    whole tiles of its own, and the kernel fetches each held row by one DMA,
    so a span costs the rows it holds and not ``k`` gathers over every token;
    else the gathers stand (one a place, and a float32 multiply-add after
    it). A scatter-add of rows this wide costs the chip many times more than
    either.

    Four phases, named inside the caller's ``moe.experts`` (``scopes.PHASES``):
    ``experts.gather`` a chunk's index arithmetic and the gather of its rows'
    tokens, ``experts.products`` the three grouped products with silu and the
    multiply, ``experts.layout`` the rows cast and laid out as the combine
    wants them, their place in the span's results and both zero fills,
    ``experts.combine`` the combine."""
    order, _sizes, start, pad_start, pad_end, row = dispatch
    tm, chunk, span, k = cfg.expert_tile_rows, cfg.expert_chunk_rows, cfg.expert_span_rows, cfg.num_experts_per_tok
    n_pairs, rows_all, last = order.shape[0], pad_end[-1], cfg.experts_held - 1
    row = row.reshape(-1, k)
    if worth_a_kernel(u.shape[0], k, u.shape[1], span):
        combine, slab = moe_combine, row_slab(u.shape[1])  # a row as whole tiles of its own: what one DMA moves
    else:
        combine, slab = moe_combine_reference, u.shape[1:]
    origin = (0,) * len(slab)

    def chunk_results(first_row):
        with scopes.phase("experts.gather"):
            tile_first = first_row + jnp.arange(chunk // tm, dtype=jnp.int32) * tm
            # the expert whose padded rows hold the tile: how many experts end at or before it
            tile_group = jnp.minimum(
                jnp.sum(tile_first[:, None] >= pad_end[None, :], axis=1, dtype=jnp.int32), last
            )
            group = jnp.repeat(tile_group, tm)
            rank = first_row + jnp.arange(chunk, dtype=jnp.int32) - pad_start[group]
            # a padding row computes some token's row again; no pair points at it
            pair = order[jnp.clip(start[group] + rank, 0, n_pairs - 1)]
            x_rows = u[pair // k]
            matrix = tile_group if group_base is None else tile_group + group_base
        with scopes.phase("experts.products"):
            hidden = jax.nn.silu(
                grouped_matmul(x_rows, p["gate"], matrix, tile_rows=tm)
            ) * grouped_matmul(x_rows, p["up"], matrix, tile_rows=tm)
            rows = grouped_matmul(hidden.astype(u.dtype), p["down"], matrix, tile_rows=tm)
        with scopes.phase("experts.layout"):
            return rows.astype(u.dtype).reshape(chunk, *slab)

    def one_span(s, y):
        base = s * span
        n_chunks = jnp.minimum((rows_all - base + chunk - 1) // chunk, span // chunk)

        def put(c, held):
            rows = chunk_results(base + c * chunk)
            with scopes.phase("experts.layout"):
                return lax.dynamic_update_slice(held, rows, (c * chunk, *origin))

        with scopes.phase("experts.layout"):
            empty = jnp.zeros((span, *slab), u.dtype)
        results = lax.fori_loop(0, n_chunks, put, empty)
        with scopes.phase("experts.combine"):
            return combine(results, row, weights, y, base)

    n_spans = (rows_all + span - 1) // span
    with scopes.phase("experts.layout"):
        nothing = jnp.zeros((u.shape[0], *slab), jnp.float32)
    y = lax.fori_loop(0, n_spans, one_span, nothing)
    with scopes.phase("experts.layout"):
        return y.reshape(u.shape)


def _routed(experts: Params, u, chosen, weights, cfg: RoutedShareConfig, group_base=None):
    """``(sum_i w_i Expert_i(u) over the pairs whose expert is held here,
    float32 (T, D); the held experts' pair counts)`` for the tokens ``u (T,
    D)`` and any router's ``chosen (T, k) int32`` and ``weights (T, k)``: the
    sort and the index arithmetic under the scope ``moe.route``, as its phase
    ``route.sort`` (the router before it is the caller's ``route.score``); the
    rest under ``moe.experts``, in the four phases ``_routed_experts`` names
    (``experts.gather``, ``experts.products``, ``experts.layout``,
    ``experts.combine``). ``group_base`` as ``_routed_experts`` says."""
    with scopes.layer("moe.route"), scopes.phase("route.sort"):
        dispatch = _dispatch(chosen, cfg)
    with scopes.layer("moe.experts"):
        return _routed_experts(experts, u, weights, dispatch, cfg, group_base), dispatch[1]


def _moe(p: Params, h, cfg: MoeShareConfig, with_sizes: bool = False):
    """``h + routed + shared`` on the float32 residual stream ``(B, S, D)``."""
    dt = p["router"].dtype
    flat = h.reshape(-1, h.shape[-1])
    with scopes.layer("moe.route"), scopes.phase("route.score"):
        u = _rms_norm(flat, p["ffn_norm"], cfg.rms_norm_eps).astype(dt)
        chosen, weights = route(p, u, cfg)
    routed, sizes = _routed(p["experts"], u, chosen, weights, cfg)
    with scopes.layer("moe.shared"):
        out = (flat + routed + _swiglu(p["shared"], u)).reshape(h.shape)
    return (out, sizes) if with_sizes else out


# ---------------------------------------------------------------------------
# Routing statistics: read back outside any hot loop
# ---------------------------------------------------------------------------


@jax.jit
def embed_tokens(table, ids):
    """The float32 residual stream the layers start from."""
    return table[ids].astype(jnp.float32)


def routing_statistics(
    params: Params, ids, cfg: RoutedShareConfig, block: Callable[[Params, Any], Tuple[Any, Optional[Any]]]
) -> Dict[str, float]:
    """Route ``ids`` layer by layer through ``block(layer parameters, x) ->
    (x, the held experts' pair counts or None)`` (the model's own block,
    jitted once per kind of layer, outside any hot loop), count the pairs that
    fell to the experts held here and fill the metrics registry
    (``set_routing_gauges``): ``moe.pairs_held``, ``moe.pairs_all`` (tokens x
    experts per token, over the MoE layers), ``moe.expert_load_max_over_mean``
    (the fullest held expert's pairs over the held experts' mean) and
    ``moe.rows_padded``. Returns the four values."""
    x = embed_tokens(params["embed"], ids)
    loads: List[np.ndarray] = []
    for p in params["layers"]:
        x, sizes = block(p, x)
        if sizes is not None:
            loads.append(np.asarray(sizes, np.int64))
    return set_routing_gauges(loads, ids.size, cfg)


def set_routing_gauges(loads: Sequence[np.ndarray], tokens: int, cfg: RoutedShareConfig) -> Dict[str, float]:
    """Fill the four ``moe.*`` routing gauges from ``loads``, the held
    experts' pair counts of each MoE layer that routed ``tokens`` tokens, and
    return their values. ``moe.rows_padded`` is what ``_dispatch`` calls
    ``pad_end[-1]``, summed over the layers: every held expert's pairs rounded
    up to whole tiles of ``expert_tile_rows``, the rows the grouped products
    run."""
    from ..observability import metrics

    held = np.sum(loads, axis=0, dtype=np.int64) if len(loads) else np.zeros(cfg.experts_held, np.int64)
    tm = cfg.expert_tile_rows
    padded = sum(int(np.sum((np.asarray(load, np.int64) + tm - 1) // tm * tm)) for load in loads)
    stats = {
        metrics.MOE_PAIRS_HELD: float(held.sum()),
        metrics.MOE_PAIRS_ALL: float(len(loads) * tokens * cfg.num_experts_per_tok),
        metrics.MOE_EXPERT_LOAD_MAX_OVER_MEAN: float(held.max() / held.mean()) if held.sum() else 0.0,
        metrics.MOE_ROWS_PADDED: float(padded),
    }
    for name, value in stats.items():
        metrics.registry().gauge(name).set(value)
    return stats


def set_attention_gauge(seq_len: int, attn_block: int) -> Dict[str, float]:
    """Fill ``flash.masked_score_share`` — of the scores ``flash_fwd`` computes
    for one head of ``seq_len`` tokens at blocks of ``attn_block``, the share
    the causal mask throws away (``flash_attention.causal_plan``: the shapes
    alone decide it) — and return it under its name."""
    from ..observability import metrics

    share = causal_plan(seq_len, attn_block, attn_block).masked_score_share
    metrics.registry().gauge(metrics.FLASH_MASKED_SCORE_SHARE).set(share)
    return {metrics.FLASH_MASKED_SCORE_SHARE: share}
