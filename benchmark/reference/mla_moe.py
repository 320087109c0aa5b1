"""The plain reference of the latent-attention mixture-of-experts family:
float32 ``jax.numpy`` at ``precision="highest"``, written from the layer
equations (DeepSeek-V3's, which the configuration follows), with no kernel, no
cache, no batching tricks and nothing imported from the program. It takes the
program's parameter tree and a configuration file's content, and is given the
same share as the program: the experts ``[experts_first, experts_first +
n_routed_experts)`` of ``published.n_routed_experts``, the vocabulary slice,
the layers kept. It routes over every expert and adds up only the held ones'
part; what the absent experts would add is left out here as it is there.

It runs sub-block by sub-block (the latents, a few heads of attention, one
expert, the head), each one small jitted program whose float32 casts of its
weights live only inside the call, so that it fits on the chip beside the bf16
weights of the real configuration; called alone, the sub-blocks run eagerly.

Departures from the published code, each without effect on the values:
- the rotary embedding rotates the interleaved pairs ``(x[2i], x[2i+1])`` in
  place; the published code first moves the evens before the odds. Queries and
  keys are permuted alike, so every score is the same;
- experts of groups that are not kept are masked with ``-inf``, not with 0:
  the same choice wherever ``num_experts_per_tok`` candidates have a positive
  biased score, as sigmoid scores with a small bias do;
- ``mscale == mscale_all_dim`` makes the published scaling of cos and sin 1.

``compute`` (default float32) is the type every weight and activation is cast
to and every product returns: ``jnp.bfloat16`` gives the reading "the nearest
precision below" of PERF.md section 4, which the tolerance must refuse.

``forward_checked`` also gives each token's routing *slack*: how far, in
score, its routing is from going another way on this chip. In one layer it is
the smaller of two distances. Experts: that of the nearest held expert among
the candidates from the boundary between chosen and not chosen (the first
score left out, for a chosen expert; the last score taken, for one that was
not). Groups: half the margin between the last group kept and the first one
dropped, where a group with held experts is kept (any swap there changes whom
the held experts compete with); where none is kept no held expert can be
chosen, and it is half of what such a group lacks to be kept. The token's slack
is the smallest over the MoE layers. A program that computes the same
mathematics with rounded operands may route a token of small slack otherwise,
and a choice that differs swaps a whole expert's contribution; a token of
large slack it may not.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HEAD_BLOCK = 4  # heads whose (S, S) scores are held at a time


def _mm(spec: str, a, b, compute):
    return jnp.einsum(
        spec, a.astype(compute), b.astype(compute),
        precision="highest", preferred_element_type=compute,
    )


def rms_norm(x, gain, eps: float, compute=jnp.float32):
    x = x.astype(compute)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(compute)).astype(compute)


def swiglu(p: Dict, u, compute=jnp.float32):
    """``down(silu(gate u) * up u)`` of one expert or dense layer."""
    hidden = jax.nn.silu(_mm("td,df->tf", u, p["gate"], compute)) * _mm("td,df->tf", u, p["up"], compute)
    return _mm("tf,fd->td", hidden.astype(compute), p["down"], compute)


# ---- rotary embedding (YaRN) ------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg: Dict) -> float:
    rs = cfg["rope_scaling"]
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return width**-0.5 * yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


def yarn_inv_freq(cfg: Dict) -> np.ndarray:
    rs, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]

    def correction_dim(rotations):
        return dim * math.log(rs["original_max_position_embeddings"] / (rotations * 2 * math.pi)) / (
            2 * math.log(base)
        )

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    own = base ** (-2.0 * i / dim)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)  # 0: the base's own, 1: interpolated
    return (own * (1.0 - ramp) + own / rs["factor"] * ramp).astype(np.float32)


def rope(x, positions, inv_freq):
    """Rotate the pairs ``(x[..., 2i], x[..., 2i+1])`` by ``positions * inv_freq[i]``;
    ``x`` is ``(..., S, dim)``."""
    angle = positions[:, None].astype(jnp.float32) * jnp.asarray(inv_freq)[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return out.reshape(x.shape)


# ---- attention ---------------------------------------------------------------


def mla_latents(cfg: Dict, p: Dict, x, compute=jnp.float32):
    """``(c_q, c_kv, k_rope)`` of ``x (B, S, D)``: the two normed latents and the
    one rotated key part all heads share."""
    eps, rank = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    u = rms_norm(x, p["attn_norm"], eps, compute)
    c_q = rms_norm(_mm("bsd,dr->bsr", u, p["q_a"], compute), p["q_norm"], eps, compute)
    kv_a = _mm("bsd,dr->bsr", u, p["kv_a"], compute)
    c_kv = rms_norm(kv_a[..., :rank], p["kv_norm"], eps, compute)
    k_rope = rope(kv_a[..., rank:].astype(jnp.float32), jnp.arange(x.shape[1]), yarn_inv_freq(cfg))
    return c_q, c_kv, k_rope.astype(compute)


def mla_heads(cfg: Dict, q_b, kv_b, o, c_q, c_kv, k_rope, compute=jnp.float32):
    """A few heads' share of the attention's output ``(B, S, D)``: their queries,
    keys and values from the latents, causal softmax in full, their rows of W_o."""
    nope = cfg["qk_nope_head_dim"]
    positions = jnp.arange(c_q.shape[1])
    q = _mm("bsr,rhe->bhse", c_q, q_b, compute)
    q_rope = rope(q[..., nope:].astype(jnp.float32), positions, yarn_inv_freq(cfg)).astype(compute)
    kv = _mm("bsr,rhe->bhse", c_kv, kv_b, compute)
    scores = _mm("bhse,bhte->bhst", q[..., :nope], kv[..., :nope], compute) + _mm(
        "bhse,bte->bhst", q_rope, k_rope, compute
    )
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal, scores.astype(compute) * softmax_scale(cfg), -jnp.inf)
    probs = jax.nn.softmax(scores.astype(compute), axis=-1)
    return _mm("bhse,hed->bsd", _mm("bhst,bhte->bhse", probs, kv[..., nope:], compute), o, compute)


def mla(cfg: Dict, p: Dict, x, compute=jnp.float32, run=None):
    """``MLA(RMSNorm(x))`` for ``x (B, S, D)``, the prefill form, ``HEAD_BLOCK``
    heads at a time."""
    run = run or _blocks(cfg, compute)
    c_q, c_kv, k_rope = run["mla_latents"](p, x)
    out = jnp.zeros(x.shape, compute)
    for h0 in range(0, cfg["num_attention_heads"], HEAD_BLOCK):
        hs = slice(h0, h0 + HEAD_BLOCK)
        out = out + run["mla_heads"](p["q_b"][:, hs], p["kv_b"][:, hs], p["o"][hs], c_q, c_kv, k_rope)
    return out.astype(compute)


# ---- router and experts ------------------------------------------------------


def route(cfg: Dict, router, bias, u, compute=jnp.float32):
    """``(chosen (T, k), weights (T, k), slack (T,))`` for tokens ``u (T, D)``,
    by sorting: sigmoid scores; on the biased scores a group scores the sum of
    its best two, the best ``topk_group`` groups are kept and the best
    ``num_experts_per_tok`` experts among them chosen; the weights are the
    unbiased scores of the chosen, normalised, times the scaling factor."""
    n_all, n_group, k = cfg["published"]["n_routed_experts"], cfg["n_group"], cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(_mm("td,de->te", u, router, compute).astype(compute)).astype(jnp.float32)
    biased = scores + bias.astype(compute).astype(jnp.float32)
    groups = biased.reshape(-1, n_group, n_all // n_group)
    group_score = jnp.sort(groups, axis=-1)[..., -2:].sum(axis=-1)  # (T, n_group)
    by_score = jnp.argsort(-group_score, axis=-1)
    rows = jnp.arange(groups.shape[0])[:, None]
    keep = jnp.zeros(group_score.shape, bool).at[rows, by_score[:, : cfg["topk_group"]]].set(True)
    candidates = jnp.where(jnp.repeat(keep, n_all // n_group, axis=1), biased, -jnp.inf)
    ranked = jnp.argsort(-candidates, axis=-1)
    chosen = ranked[:, :k]
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / weights.sum(axis=-1, keepdims=True) * cfg["routed_scaling_factor"]
    # The slack. Experts: a held candidate's distance from the boundary between
    # chosen and not chosen. Groups: where a group with held experts is kept, any
    # swap of the last group kept for the first one dropped changes whom the
    # held experts compete with; where none is kept, no held expert can be
    # chosen, and only such a group's way back in matters.
    first = cfg["experts_first"]
    held = (jnp.arange(n_all) >= first) & (jnp.arange(n_all) < first + cfg["n_routed_experts"])
    in_order = jnp.take_along_axis(candidates, ranked, axis=-1)
    last_in, first_out = in_order[:, k - 1 : k], in_order[:, k : k + 1]
    is_chosen = jnp.zeros(candidates.shape, bool).at[rows, chosen].set(True)
    distance = jnp.where(is_chosen, candidates - first_out, last_in - candidates)
    distance = jnp.where(held & jnp.isfinite(candidates), distance, jnp.inf).min(axis=-1)
    slack = jnp.full(distance.shape, jnp.inf)
    if n_group > cfg["topk_group"]:
        groups_in_order = -jnp.sort(-group_score, axis=-1)
        last_kept = groups_in_order[:, cfg["topk_group"] - 1 : cfg["topk_group"]]
        first_dropped = groups_in_order[:, cfg["topk_group"] : cfg["topk_group"] + 1]
        holds = jnp.any(held.reshape(n_group, -1), axis=-1)  # (n_group,)
        way_in = jnp.where(holds & ~keep, (last_kept - group_score) / 2, jnp.inf).min(axis=-1)
        slack = jnp.where(jnp.any(keep & holds, axis=-1), (last_kept - first_dropped)[:, 0] / 2, way_in)
    return chosen, weights, jnp.minimum(slack, distance)


def expert_part(cfg: Dict, one: Dict, local, u, chosen, weights, compute=jnp.float32):
    """``(w_e(t) * Expert_e(u_t) for every token, pairs)`` of the held expert
    ``local``: the expert by itself on every token, times the token's weight
    for it, 0 where it was not chosen."""
    hit = chosen == cfg["experts_first"] + local  # (T, k)
    w = jnp.sum(jnp.where(hit, weights, 0.0), axis=-1)  # (T,)
    return swiglu(one, u, compute) * w[:, None].astype(compute), hit.sum()


def routed_experts(cfg: Dict, experts: Dict, u, chosen, weights, compute=jnp.float32, run=None):
    """``sum_i w_i Expert_i(u)`` over the chosen experts that are held here, one
    held expert at a time, and the number of pairs that fell to them."""
    run = run or _blocks(cfg, compute)
    out, pairs = jnp.zeros(u.shape, compute), 0
    for local in range(cfg["n_routed_experts"]):
        one = {name: experts[name][local] for name in ("gate", "up", "down")}
        part, hits = run["expert_part"](one, local, u, chosen, weights)
        out, pairs = out + part, pairs + int(hits)
    return out.astype(compute), pairs


def moe_ffn(cfg: Dict, p: Dict, u, compute=jnp.float32, run=None):
    """``(routed + shared, slack, pairs held)`` for normed tokens ``u (T, D)``."""
    run = run or _blocks(cfg, compute)
    chosen, weights, slack = run["route"](p["router"], p["bias"], u)
    routed, pairs = routed_experts(cfg, p["experts"], u, chosen, weights, compute, run)
    return routed + run["swiglu"](p["shared"], u), slack, pairs


# ---- the model ---------------------------------------------------------------


def _blocks(cfg: Dict, compute) -> Dict:
    """The sub-blocks as functions of arrays alone, the configuration closed over."""
    return {
        "embed": lambda table, ids: table[ids].astype(compute),
        "norm": lambda x, gain: rms_norm(x, gain, cfg["rms_norm_eps"], compute),
        "mla_latents": lambda p, x: mla_latents(cfg, p, x, compute),
        "mla_heads": lambda *arrays: mla_heads(cfg, *arrays, compute),
        "swiglu": lambda p, u: swiglu(p, u, compute),
        "route": lambda router, bias, u: route(cfg, router, bias, u, compute),
        "expert_part": lambda one, local, u, chosen, weights: expert_part(
            cfg, one, local, u, chosen, weights, compute
        ),
        "head": lambda u, head: _mm("bsd,dv->bsv", u, head, compute).astype(jnp.float32),
    }


def _jitted(cfg: Dict, compute) -> Dict:
    """Each sub-block as one jitted program, however many heads, experts and
    layers pass through it: the same plain functions, compiled once each, so
    that the float32 casts of a sub-block's weights live only inside its call."""
    return {name: jax.jit(fn) for name, fn in _blocks(cfg, compute).items()}


def forward_checked(cfg: Dict, params: Dict, ids, compute=jnp.float32) -> Tuple[jax.Array, jax.Array, int]:
    """``(logits (B, S, V), routing slack (B, S), pairs routed to held experts)``."""
    run = _jitted(cfg, compute)
    x = run["embed"](params["embed"], ids)
    slack = jnp.full(ids.shape, jnp.inf)
    pairs_held = 0
    assert len(params["layers"]) == cfg["num_layers"]
    for i, p in enumerate(params["layers"]):
        x = (x + mla(cfg, p, x, compute, run)).astype(compute)
        flat = x.reshape(-1, x.shape[-1])
        u = run["norm"](flat, p["ffn_norm"])
        if i < cfg["first_k_dense_replace"]:
            ffn = run["swiglu"](p["mlp"], u)
        else:
            ffn, layer_slack, pairs = moe_ffn(cfg, p["moe"], u, compute, run)
            pairs_held += pairs
            slack = jnp.minimum(slack, layer_slack.reshape(ids.shape))
        x = (flat + ffn).reshape(x.shape).astype(compute)
    u = run["norm"](x, params["final_norm"])
    return run["head"](u, params["head"]), slack, pairs_held


def forward(cfg: Dict, params: Dict, ids, compute=jnp.float32):
    """Reference logits ``(B, S, V)`` over the vocabulary slice, float32."""
    return forward_checked(cfg, params, ids, compute)[0]
