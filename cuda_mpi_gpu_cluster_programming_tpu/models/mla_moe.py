"""Latent-attention mixture-of-experts decoder, as one chip of an
expert-parallel deployment holds it.

The layer equations are DeepSeek-V3's (multi-head latent attention, YaRN
rotary embedding, leading dense SwiGLU layers, then layers of sigmoid-scored,
group-limited top-k routed experts beside a shared expert):

- *Block*: ``h = x + MLA(RMSNorm(x))``; ``y = h + FFN(RMSNorm(h))``; a final
  RMSNorm, then the output head.
- *MLA* (the prefill form, keys and values expanded; the absorbed decode form
  is not built here): ``c_q = RMSNorm(u W_qa)``; ``q = c_q W_qb`` -> heads x
  (nope + rope); ``[c_kv, k_r] = u W_kva``; ``[k_nope, v] = RMSNorm(c_kv) W_kvb``;
  rotary embedding on ``q_rope`` and on the one ``k_r`` all heads share; scores
  ``(q_nope.k_nope + q_rope.k_r) * softmax_scale``, causal, softmax in float32.
- *Router*: ``s = sigmoid(u W_r)`` in float32; selection on ``s + bias``: a
  group's score is the sum of its top 2, the top ``topk_group`` groups are
  kept, the top ``num_experts_per_tok`` experts among them chosen; weights are
  the unbiased ``s`` of the chosen, normalised to sum 1, times
  ``routed_scaling_factor``.
- *MoE*: ``sum_i w_i Expert_i(u) + Shared(u)``.

**The chip's share.** The layer is told which experts it holds
(``[experts_first, experts_first + experts_held)``). It routes over all
``n_routed_experts``, computes only the (token, expert) pairs whose expert it
holds — dropless, no capacity: the pairs are sorted by expert, each expert's
rows padded to whole tiles, ``ops.grouped_matmul`` runs over as many chunks of
rows as were routed here, and every token gathers its experts' rows back and
weights them — adds the shared expert, and that partial sum goes on to the
next layer. What the absent experts would add arrives, in
a deployment, by the exchange of ``parallel.expert``; nothing here stands in
for it. The vocabulary is the chip's slice: ids are drawn from it and the
logits are over it.

Numerics follow the parameters' type. Stored in bf16, operands go to the MXU
in bf16 and every product accumulates in float32
(``ops.reference.mxu_precision``); RMSNorm statistics, the rotary embedding,
softmax, router scores and the top-k bookkeeping are float32, and so is the
residual stream. Stored in float32, every product runs at HIGHEST.

``forward`` syncs nothing to the host. ``routing_statistics`` is the one
place the routing is read back, outside any hot loop, into the metrics
registry (``moe.pairs_held``, ``moe.pairs_all``, ``moe.expert_load_max_over_mean``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import scopes
from ..ops.flash_attention import flash_forward_bhld
from ..ops.grouped_matmul import grouped_matmul
from ..ops.reference import mxu_precision

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    """Every key of the published configuration that shapes the model, under
    the publisher's names, plus the share this chip holds. The defaults are
    the small preset of the CPU tests and ``run.py``."""

    vocab_size: int = 512  # rows of the embedding and the head held here
    hidden_size: int = 64
    num_attention_heads: int = 4
    q_lora_rank: int = 32
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    first_k_dense_replace: int = 1  # leading dense layers
    num_moe_layers: int = 2  # the layers after them
    intermediate_size: int = 128  # dense SwiGLU width
    moe_intermediate_size: int = 32  # width of one expert and of the shared one
    n_routed_experts: int = 16  # the router's width: every expert of the layer
    n_group: int = 4
    topk_group: int = 2
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.5
    n_shared_experts: int = 1
    experts_held: int = 4  # the experts this chip holds ...
    experts_first: int = 0  # ... are [experts_first, experts_first + experts_held)
    attn_block: int = 512  # rows of a query or key block of the attention kernel
    expert_tile_rows: int = 8  # rows of one tile of the grouped product
    expert_chunk_rows: int = 16  # rows gathered and multiplied at a time
    expert_span_rows: int = 32  # rows of results held until their tokens gather them back

    def __post_init__(self):
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_routed_experts must divide into n_group groups")
        if not 0 <= self.experts_first <= self.n_routed_experts - self.experts_held:
            raise ValueError("the experts held must lie inside the router's width")
        if self.expert_chunk_rows % self.expert_tile_rows:
            raise ValueError("expert_chunk_rows must be whole tiles")
        if self.expert_span_rows % self.expert_chunk_rows:
            raise ValueError("expert_span_rows must be whole chunks")
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert is what this model computes")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def num_layers(self) -> int:
        return self.first_k_dense_replace + self.num_moe_layers

    def layer_kinds(self) -> Tuple[str, ...]:
        return ("dense",) * self.first_k_dense_replace + ("moe",) * self.num_moe_layers


SMALL = MlaMoeConfig()

# The published widths of a 61-layer, 256-expert model of this family as ONE of
# 16 expert-parallel chips holds them: 16 experts of each layer, an eighth of
# the vocabulary, one leading dense layer and four MoE layers (4.566B
# parameters, 9.13 GB in bf16). The benchmark's configuration file says the
# same, key for key (tests/benchmark hold the two together).
EP16_SHARE = MlaMoeConfig(
    vocab_size=16160, hidden_size=7168, num_attention_heads=128, q_lora_rank=1536,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    first_k_dense_replace=1, num_moe_layers=4, intermediate_size=18432,
    moe_intermediate_size=2048, n_routed_experts=256, n_group=8, topk_group=4,
    num_experts_per_tok=8, experts_held=16, experts_first=0,
    attn_block=1024, expert_tile_rows=256, expert_chunk_rows=1024, expert_span_rows=8192,
)

# preset -> (configuration, batch, sequence length) of ``run.py``'s one-shot
PRESETS = {"small": (SMALL, 2, 32), "ep16_share": (EP16_SHARE, 2, 4096)}


# ---------------------------------------------------------------------------
# Rotary embedding (YaRN) and the score scale: closed forms, static
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg: MlaMoeConfig) -> float:
    """``qk_head_dim**-0.5 * m**2`` with ``m = 0.1 * mscale_all_dim * ln(factor) + 1``."""
    return cfg.qk_head_dim**-0.5 * yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2


def yarn_inv_freq(cfg: MlaMoeConfig) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` rotary frequencies: the base's own below the
    ``beta_fast`` correction dimension, divided by ``factor`` above the
    ``beta_slow`` one, a linear ramp between."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta

    def correction_dim(rotations: float) -> float:
        return dim * math.log(cfg.rope_original_max_position_embeddings / (rotations * 2 * math.pi)) / (
            2 * math.log(base)
        )

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / cfg.rope_factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def _rope_tables(cfg: MlaMoeConfig, seq: int):
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(cfg))[None, :]
    # cos and sin are scaled by mscale / mscale_all_dim's ratio, 1 as published
    ratio = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / yarn_mscale(
        cfg.rope_factor, cfg.rope_mscale_all_dim
    )
    return jnp.cos(angle) * ratio, jnp.sin(angle) * ratio


def _rope(x, cos, sin):
    """Rotate interleaved pairs ``(x[2i], x[2i+1])`` of the last axis by the
    position's angle; ``x`` is ``(..., S, dim)`` float32, the tables ``(S, dim/2)``."""
    pairs = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def _rope_halves(a, b, cos, sin):
    """The rotation of :func:`_rope` on its pairs' two halves, sequence-minor:
    ``a`` holds every pair's first element and ``b`` its second
    (``(..., dim/2, S)`` float32 each, the tables ``(dim/2, S)``); the rotated
    firsts come out before the rotated seconds, ``(..., dim, S)``. Queries
    and keys laid out alike give the scores of the interleaved form, each the
    same sum in another order."""
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-2)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def param_shapes(cfg: MlaMoeConfig) -> Params:
    """The parameter tree as ``(shape, fan_in)`` leaves; ``fan_in`` 0 marks a
    norm gain (drawn as 1) and -1 the router's selection bias."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    f, fe, e = cfg.intermediate_size, cfg.moe_intermediate_size, cfg.experts_held

    def swiglu(width, lead=()):
        return {
            "gate": ((*lead, d, width), d),
            "up": ((*lead, d, width), d),
            "down": ((*lead, width, d), width),
        }

    def layer(kind: str) -> Params:
        out = {
            "attn_norm": ((d,), 0),
            "q_a": ((d, cfg.q_lora_rank), d),
            "q_norm": ((cfg.q_lora_rank,), 0),
            "q_b": ((cfg.q_lora_rank, h, cfg.qk_head_dim), cfg.q_lora_rank),
            "kv_a": ((d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), d),
            "kv_norm": ((cfg.kv_lora_rank,), 0),
            "kv_b": ((cfg.kv_lora_rank, h, cfg.qk_nope_head_dim + cfg.v_head_dim), cfg.kv_lora_rank),
            "o": ((h, cfg.v_head_dim, d), h * cfg.v_head_dim),
            "ffn_norm": ((d,), 0),
        }
        if kind == "dense":
            out["mlp"] = swiglu(f)
        else:
            out["moe"] = {
                "router": ((d, cfg.n_routed_experts), d),
                "bias": ((cfg.n_routed_experts,), -1),
                "experts": swiglu(fe, (e,)),
                "shared": swiglu(fe),
            }
        return out

    return {
        "embed": ((cfg.vocab_size, d), 1),
        "layers": [layer(kind) for kind in cfg.layer_kinds()],
        "final_norm": ((d,), 0),
        "head": ((d, cfg.vocab_size), d),
    }


def _is_leaf(node) -> bool:
    return isinstance(node, tuple) and len(node) == 2 and isinstance(node[1], int)


# The selection bias is drawn small: enough that selection (biased) and
# weighting (unbiased) differ, little enough that it unbalances no expert's
# load by more than a tenth (a trained bias is there to balance the load).
BIAS_SCALE = 0.005


def _draw_leaf(key, shape, fan_in, dtype):
    if fan_in == 0:
        return jnp.ones(shape, dtype)
    scale = BIAS_SCALE if fan_in < 0 else fan_in**-0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _draw_tree(key, shapes, dtype):
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [_draw_leaf(k, shape, fan_in, dtype) for k, (shape, fan_in) in zip(keys, leaves)]
    )


def init(key, cfg: MlaMoeConfig = SMALL, dtype=jnp.bfloat16) -> Params:
    """Seeded parameters stored in ``dtype``: normal weights of scale
    ``fan_in**-0.5``, norm gains 1, a small selection bias. One jitted draw per
    layer (one program per kind of layer) and one for the embedding, the final
    norm and the head, so the draw's peak is a layer and never the model."""
    shapes = param_shapes(cfg)
    layer_shapes = shapes.pop("layers")
    kinds = cfg.layer_kinds()
    draw = {
        kind: jax.jit(functools.partial(_draw_tree, shapes=layer_shapes[kinds.index(kind)], dtype=dtype))
        for kind in set(kinds)
    }
    keys = jax.random.split(key, len(layer_shapes) + 1)
    params = jax.jit(functools.partial(_draw_tree, shapes=shapes, dtype=dtype))(keys[0])
    params["layers"] = [draw[kind](k) for kind, k in zip(kinds, keys[1:])]
    return params


def param_count(cfg: MlaMoeConfig) -> int:
    leaves = jax.tree.leaves(param_shapes(cfg), is_leaf=_is_leaf)
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


def _mla(p: Params, x, cfg: MlaMoeConfig):
    """``x + MLA(RMSNorm(x))`` on the float32 residual stream ``(B, S, D)``."""
    dt = p["q_a"].dtype
    seq = x.shape[1]
    with scopes.layer("mla.proj"):
        u = _rms_norm(x, p["attn_norm"], cfg.rms_norm_eps).astype(dt)
        c_q = _rms_norm(_mm("bsd,dr->bsr", u, p["q_a"]), p["q_norm"], cfg.rms_norm_eps)
        # The nope and rope parts, and keys and values, come from slices of
        # the (small) weights, not of the (large) activations; the rope
        # columns' evens and odds likewise, so that the rotation runs on two
        # halves and splits no adjacent lanes. The rope parts are
        # sequence-minor, as the kernel takes them: 32 or 64 columns would
        # fill a quarter or a half of every lane tile.
        nope, rank = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        q_nope = _mm("bsr,rhe->bhse", c_q, p["q_b"][..., :nope]).astype(dt)
        kv_a = _mm("bsd,dr->bsr", u, p["kv_a"])  # (B, S, kv_lora + rope)
        c_kv = _rms_norm(kv_a[..., :rank], p["kv_norm"], cfg.rms_norm_eps)
        k_nope = _mm("bsr,rhe->bhse", c_kv, p["kv_b"][..., :nope]).astype(dt)
        v = _mm("bsr,rhe->bhse", c_kv, p["kv_b"][..., nope:]).astype(dt)
        cos, sin = (table.T for table in _rope_tables(cfg, seq))
        q_rope = _rope_halves(
            _mm("bsr,rhe->bhes", c_q, p["q_b"][..., nope::2]),
            _mm("bsr,rhe->bhes", c_q, p["q_b"][..., nope + 1 :: 2]), cos, sin,
        ).astype(dt)
        k_r = jnp.swapaxes(kv_a[..., rank:], 1, 2)  # (B, rope, S): one for all heads, and small
        k_rope = _rope_halves(k_r[:, 0::2], k_r[:, 1::2], cos, sin).astype(dt)
    with scopes.layer("mla.attn"):
        attn, _lse = flash_forward_bhld(
            q_nope, k_nope, v, q_rope=q_rope, k_rope=k_rope, causal=True,
            scale=softmax_scale(cfg), block_q=cfg.attn_block, block_k=cfg.attn_block,
        )
    with scopes.layer("mla.proj"):
        return x + _mm("bhse,hed->bsd", attn, p["o"])


def route(p: Params, u, cfg: MlaMoeConfig):
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


def _dispatch(chosen, cfg: MlaMoeConfig):
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


def _routed_experts(p: Params, u, weights, dispatch, cfg: MlaMoeConfig):
    """``sum_i w_i Expert_i(u)`` over the pairs whose expert is held here,
    float32 ``(T, D)``. Span by span of the padded rows (one span holds a
    usual load): chunk by chunk, gather the rows' tokens and run the three
    grouped products into the span's results; then every token gathers the
    rows of its own pairs back, one gather per place among its experts, and
    weights them. A scatter-add of rows this wide costs the chip many times
    more, and the gathers' cost follows what was routed far less than a
    product over the padded rows does."""
    order, _sizes, start, pad_start, pad_end, row = dispatch
    tm, chunk, span, k = cfg.expert_tile_rows, cfg.expert_chunk_rows, cfg.expert_span_rows, cfg.num_experts_per_tok
    n_pairs, rows_all, last = order.shape[0], pad_end[-1], cfg.experts_held - 1
    row = row.reshape(-1, k)

    def chunk_results(first_row):
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
        hidden = jax.nn.silu(
            grouped_matmul(x_rows, p["gate"], tile_group, tile_rows=tm)
        ) * grouped_matmul(x_rows, p["up"], tile_group, tile_rows=tm)
        return grouped_matmul(hidden.astype(u.dtype), p["down"], tile_group, tile_rows=tm).astype(u.dtype)

    def one_span(s, y):
        base = s * span
        n_chunks = jnp.minimum((rows_all - base + chunk - 1) // chunk, span // chunk)
        results = lax.fori_loop(
            0, n_chunks,
            lambda c, held: lax.dynamic_update_slice(held, chunk_results(base + c * chunk), (c * chunk, 0)),
            jnp.zeros((span, u.shape[1]), u.dtype),
        )
        inside = (row >= base) & (row < base + span)  # (T, k): the pairs whose rows this span holds
        for place in range(k):
            rows = results[jnp.where(inside[:, place], row[:, place] - base, 0)].astype(jnp.float32)
            y = y + jnp.where(inside[:, place, None], rows * weights[:, place, None], 0.0)
        return y

    n_spans = (rows_all + span - 1) // span
    return lax.fori_loop(0, n_spans, one_span, jnp.zeros(u.shape, jnp.float32))


def _moe(p: Params, h, cfg: MlaMoeConfig, with_sizes: bool = False):
    """``h + routed + shared`` on the float32 residual stream ``(B, S, D)``."""
    dt = p["router"].dtype
    flat = h.reshape(-1, h.shape[-1])
    with scopes.layer("moe.route"):
        u = _rms_norm(flat, p["ffn_norm"], cfg.rms_norm_eps).astype(dt)
        chosen, weights = route(p, u, cfg)
        dispatch = _dispatch(chosen, cfg)
    with scopes.layer("moe.experts"):
        routed = _routed_experts(p["experts"], u, weights, dispatch, cfg)
    with scopes.layer("moe.shared"):
        out = (flat + routed + _swiglu(p["shared"], u)).reshape(h.shape)
    return (out, dispatch[1]) if with_sizes else out


def _dense(p: Params, h, cfg: MlaMoeConfig):
    with scopes.layer("dense_mlp"):
        flat = h.reshape(-1, h.shape[-1])
        u = _rms_norm(flat, p["ffn_norm"], cfg.rms_norm_eps).astype(p["mlp"]["gate"].dtype)
        return (flat + _swiglu(p["mlp"], u)).reshape(h.shape)


def _block(p: Params, x, cfg: MlaMoeConfig, with_sizes: bool = False):
    h = _mla(p, x, cfg)
    if "moe" in p:
        return _moe({**p["moe"], "ffn_norm": p["ffn_norm"]}, h, cfg, with_sizes)
    out = _dense(p, h, cfg)
    return (out, None) if with_sizes else out


def forward(params: Params, ids, cfg: MlaMoeConfig = SMALL):
    """``ids (B, S) int32`` from the vocabulary slice -> float32 logits
    ``(B, S, vocab_size)`` over it."""
    with scopes.layer("embed"):
        x = params["embed"][ids].astype(jnp.float32)
    for p in params["layers"]:
        x = _block(p, x, cfg)
    with scopes.layer("head"):
        u = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        return _mm("bsd,dv->bsv", u, params["head"])


# ---------------------------------------------------------------------------
# Routing statistics: read back outside any hot loop
# ---------------------------------------------------------------------------


def routing_statistics(params: Params, ids, cfg: MlaMoeConfig = SMALL) -> Dict[str, float]:
    """Route ``ids`` layer by layer (one jitted program per kind of layer,
    outside any hot loop), count the pairs that fell to the experts held
    here and fill the metrics registry: ``moe.pairs_held``, ``moe.pairs_all``
    (tokens x experts per token, over the MoE layers) and
    ``moe.expert_load_max_over_mean`` (the fullest held expert's pairs over
    the held experts' mean). Returns the three values."""
    from ..observability import metrics

    embed = jax.jit(lambda e, i: e[i].astype(jnp.float32))
    block = jax.jit(functools.partial(_block, cfg=cfg, with_sizes=True))
    x = embed(params["embed"], ids)
    loads: List[np.ndarray] = []
    for p in params["layers"]:
        x, sizes = block(p, x)
        if sizes is not None:
            loads.append(np.asarray(sizes, np.int64))
    held = np.sum(loads, axis=0) if loads else np.zeros(cfg.experts_held, np.int64)
    stats = {
        metrics.MOE_PAIRS_HELD: float(held.sum()),
        metrics.MOE_PAIRS_ALL: float(len(loads) * ids.size * cfg.num_experts_per_tok),
        metrics.MOE_EXPERT_LOAD_MAX_OVER_MEAN: float(held.max() / held.mean()) if held.sum() else 0.0,
    }
    for name, value in stats.items():
        metrics.registry().gauge(name).set(value)
    return stats
