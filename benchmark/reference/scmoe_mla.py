"""The plain reference of the shortcut-connected mixture-of-experts family over
latent attention: float32 ``jax.numpy`` at ``precision="highest"``, written from
the layer equations (the LongCat-Flash technical report, arXiv:2509.01322:
shortcut-connected MoE, zero-computation experts; the configuration's own keys
for every size), with no kernel, no loop over stacked layers, no batching
tricks and nothing imported from the program. It takes the program's parameter
tree and a configuration file's content, and is given the same share as the
program: the real experts ``[experts_first, experts_first + n_routed_experts)``
of ``published.n_routed_experts``, the vocabulary slice, the layers kept. It
routes over every output and adds up only the held experts' part and the
identity experts' (which hold no parameters and are computed where the token
lives); what the absent experts would add is left out here as it is there.

One layer, on the float32 stream (four norms, two latent attentions, two dense
SwiGLUs, one mixture of experts)::

    h1 = x  + MLA_0(RMSNorm_a0(x))
    u  = RMSNorm_f0(h1)
    m  = MoE(u)
    h2 = h1 + FFN_0(u)
    h3 = h2 + MLA_1(RMSNorm_a1(h2))
    y  = h3 + FFN_1(RMSNorm_f1(h3)) + m

*MLA* (the prefill form): ``c_q = RMSNorm(u W_qa) * sqrt(hidden / q_lora_rank)``;
``q = c_q W_qb``; ``[c_kv, k_r] = u W_kva``; ``[k_nope, v] = (RMSNorm(c_kv) *
sqrt(hidden / kv_lora_rank)) W_kvb`` (the scales where ``mla_scale_q_lora`` /
``mla_scale_kv_lora`` say so; ``k_r`` is never scaled); the rotary embedding on
interleaved pairs at ``rope_theta``, no scaling of positions, on ``q``'s rope
part and on the one ``k_r`` all heads share; scores ``(q_nope.k_nope +
q_rope.k_r) * (nope + rope)**-0.5``, causal, softmax in full.

*Router and MoE*: ``s = softmax(u W_r)`` over ``published.n_routed_experts +
zero_expert_num`` outputs; the top ``moe_topk`` of ``s + b`` are chosen, by
sorting; ``w_i = routed_scaling_factor * s_i``, not renormalised; outputs below
``published.n_routed_experts`` are SwiGLU experts, the rest identities:
``MoE(u) = sum_{chosen, real, held} w_i Expert_i(u) + (sum_{chosen, identity}
w_i) * u``.

It runs sub-block by sub-block (the latents, a few heads of attention, one
expert, a dense SwiGLU, the head), each one small jitted program whose float32
casts of its weights live only inside the call, so that it fits on the chip
beside the bf16 weights of the real configuration.

``compute`` (default float32) is the type every weight and activation is cast
to and every product returns: ``jnp.bfloat16`` gives the reading "the nearest
precision below" of PERF.md section 4, which the tolerance must refuse.

``forward_checked`` also gives each token's routing *slack*: how far, in score,
its routing is from going another way ON THIS CHIP. In one layer it is the
distance from the boundary between the last biased score taken and the first
left out of the nearest output whose crossing changes this chip's result: a
held expert or any identity expert (a swap between two absent real experts
changes nothing here). The token's slack is the smallest over the layers.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import mla_moe as base
from benchmark.reference.mla_moe import _mm, rms_norm, rope, swiglu

HEAD_BLOCK = 4  # heads whose (S, S) scores are held at a time


# ---- attention ---------------------------------------------------------------


def inv_freq(cfg: Dict) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` rotary frequencies ``theta**(-2i / dim)``."""
    dim = cfg["qk_rope_head_dim"]
    return (float(cfg["rope_theta"]) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)


def lora_scales(cfg: Dict) -> Tuple[float, float]:
    """``(scale of c_q, scale of c_kv)``."""
    d = cfg["hidden_size"]
    return (
        (d / cfg["q_lora_rank"]) ** 0.5 if cfg["mla_scale_q_lora"] else 1.0,
        (d / cfg["kv_lora_rank"]) ** 0.5 if cfg["mla_scale_kv_lora"] else 1.0,
    )


def mla_latents(cfg: Dict, p: Dict, x, compute=jnp.float32):
    """``(c_q, c_kv, k_rope)`` of ``x (B, S, D)``: the two normed and scaled
    latents and the one rotated key part all heads share."""
    eps, rank = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    q_scale, kv_scale = lora_scales(cfg)
    u = rms_norm(x, p["attn_norm"], eps, compute)
    c_q = rms_norm(_mm("bsd,dr->bsr", u, p["q_a"], compute), p["q_norm"], eps, compute) * q_scale
    kv_a = _mm("bsd,dr->bsr", u, p["kv_a"], compute)
    c_kv = rms_norm(kv_a[..., :rank], p["kv_norm"], eps, compute) * kv_scale
    k_rope = rope(kv_a[..., rank:].astype(jnp.float32), jnp.arange(x.shape[1]), inv_freq(cfg))
    return c_q.astype(compute), c_kv.astype(compute), k_rope.astype(compute)


def mla_heads(cfg: Dict, q_b, kv_b, o, c_q, c_kv, k_rope, compute=jnp.float32):
    """A few heads' share of the attention's output ``(B, S, D)``: their queries,
    keys and values from the latents, causal softmax in full, their rows of W_o."""
    nope = cfg["qk_nope_head_dim"]
    positions = jnp.arange(c_q.shape[1])
    q = _mm("bsr,rhe->bhse", c_q, q_b, compute)
    q_rope = rope(q[..., nope:].astype(jnp.float32), positions, inv_freq(cfg)).astype(compute)
    kv = _mm("bsr,rhe->bhse", c_kv, kv_b, compute)
    scores = _mm("bhse,bhte->bhst", q[..., :nope], kv[..., :nope], compute) + _mm(
        "bhse,bte->bhst", q_rope, k_rope, compute
    )
    causal = positions[:, None] >= positions[None, :]
    scale = (nope + cfg["qk_rope_head_dim"]) ** -0.5
    scores = jnp.where(causal, scores.astype(compute) * scale, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(compute), axis=-1)
    return _mm("bhse,hed->bsd", _mm("bhst,bhte->bhse", probs, kv[..., nope:], compute), o, compute)


def mla(cfg: Dict, p: Dict, x, compute=jnp.float32, run=None):
    """``MLA(RMSNorm(x))`` for ``x (B, S, D)``, ``HEAD_BLOCK`` heads at a time."""
    run = run or _blocks(cfg, compute)
    c_q, c_kv, k_rope = run["mla_latents"](p, x)
    out = jnp.zeros(x.shape, compute)
    for h0 in range(0, cfg["num_attention_heads"], HEAD_BLOCK):
        hs = slice(h0, h0 + HEAD_BLOCK)
        out = out + run["mla_heads"](p["q_b"][:, hs], p["kv_b"][:, hs], p["o"][hs], c_q, c_kv, k_rope)
    return out.astype(compute)


# ---- router and experts ------------------------------------------------------


def route(cfg: Dict, router, bias, u, compute=jnp.float32):
    """``(chosen (T, k), weights (T, k), slack (T,))`` for normed tokens ``u (T,
    D)``, by sorting: softmax scores over every output; the best ``moe_topk``
    of the biased scores are chosen; the weights are the unbiased scores of
    the chosen times the scaling factor."""
    n_real, k = cfg["published"]["n_routed_experts"], cfg["moe_topk"]
    logits = _mm("td,de->te", u, router, compute)
    scores = jax.nn.softmax(logits.astype(compute), axis=-1).astype(jnp.float32)
    biased = scores + bias.astype(compute).astype(jnp.float32)
    ranked = jnp.argsort(-biased, axis=-1)
    chosen = ranked[:, :k]
    weights = jnp.take_along_axis(scores, chosen, axis=-1) * cfg["routed_scaling_factor"]
    # The slack: of the outputs whose crossing changes this chip's result (a
    # held expert, any identity expert), the nearest one's distance from the
    # boundary between chosen and not chosen (the first score left out, for a
    # chosen output; the last score taken, for one that was not).
    outputs, first = jnp.arange(biased.shape[-1]), cfg["experts_first"]
    matters = ((outputs >= first) & (outputs < first + cfg["n_routed_experts"])) | (outputs >= n_real)
    in_order = jnp.take_along_axis(biased, ranked, axis=-1)
    last_in, first_out = in_order[:, k - 1 : k], in_order[:, k : k + 1]
    rows = jnp.arange(biased.shape[0])[:, None]
    is_chosen = jnp.zeros(biased.shape, bool).at[rows, chosen].set(True)
    distance = jnp.where(is_chosen, biased - first_out, last_in - biased)
    return chosen, weights, jnp.where(matters, distance, jnp.inf).min(axis=-1)


def zero_part(cfg: Dict, u, chosen, weights, compute=jnp.float32):
    """``(sum of the weights of the chosen identity experts) * u``."""
    share = jnp.sum(jnp.where(chosen >= cfg["published"]["n_routed_experts"], weights, 0.0), axis=-1)
    return u.astype(compute) * share[:, None].astype(compute)


def _at(tree: Dict, i: int) -> Dict:
    """Layer ``i`` of a tree whose leaves have the layers as their first axis.
    Taken where it is used, a part at a time: a whole layer of the real
    configuration is 2.5 GB, which the chip does not have twice."""
    return jax.tree.map(lambda leaf: leaf[i], tree)


def held_experts(cfg: Dict, experts: Dict, i: int, u, chosen, weights, compute=jnp.float32, run=None):
    """``sum_e w_e Expert_e(u)`` over the chosen real experts that are held here,
    one held expert of layer ``i`` at a time on every token, and the number of
    pairs that fell to them."""
    run = run or _blocks(cfg, compute)
    out, pairs = jnp.zeros(u.shape, compute), 0
    for local in range(cfg["n_routed_experts"]):
        one = {name: experts[name][i, local] for name in ("gate", "up", "down")}
        part, hits = run["expert_part"](one, local, u, chosen, weights)
        out, pairs = out + part, pairs + int(hits)
    return out.astype(compute), pairs


def moe(cfg: Dict, layers: Dict, i: int, u, compute=jnp.float32, run=None):
    """``(MoE(u), slack, pairs held)`` of layer ``i`` for normed tokens ``u (T,
    D)``: the held experts' part and the identity experts', NOT added to any
    stream."""
    run = run or _blocks(cfg, compute)
    chosen, weights, slack = run["route"](layers["router"][i], layers["bias"][i], u)
    routed, pairs = held_experts(cfg, layers["experts"], i, u, chosen, weights, compute, run)
    return (routed + run["zero_part"](u, chosen, weights)).astype(compute), slack, pairs


# ---- the model ---------------------------------------------------------------


def layer(cfg: Dict, layers: Dict, i: int, x, compute=jnp.float32, run=None):
    """``(y, slack (T,), pairs held)`` of the shortcut-connected layer ``i`` of
    the stacked tree ``layers`` for the stream ``x (B, S, D)``."""
    run = run or _blocks(cfg, compute)
    first, second = ({k: v for k, v in sub.items() if k != "mlp"} for sub in layers["sub"])
    mlp_first, mlp_second = (sub["mlp"] for sub in layers["sub"])
    h1 = (x + mla(cfg, _at(first, i), x, compute, run)).astype(compute)
    flat = h1.reshape(-1, h1.shape[-1])
    u = run["norm"](flat, first["ffn_norm"][i])
    m, slack, pairs = moe(cfg, layers, i, u, compute, run)
    h2 = (flat + run["swiglu"](_at(mlp_first, i), u)).astype(compute).reshape(x.shape)
    h3 = (h2 + mla(cfg, _at(second, i), h2, compute, run)).astype(compute)
    flat3 = h3.reshape(flat.shape)
    y = flat3 + run["swiglu"](_at(mlp_second, i), run["norm"](flat3, second["ffn_norm"][i])) + m
    return y.astype(compute).reshape(x.shape), slack, pairs


def _blocks(cfg: Dict, compute) -> Dict:
    """The sub-blocks as functions of arrays alone, the configuration closed over."""
    return {
        "embed": lambda table, ids: table[ids].astype(compute),
        "norm": lambda x, gain: rms_norm(x, gain, cfg["rms_norm_eps"], compute),
        "mla_latents": lambda p, x: mla_latents(cfg, p, x, compute),
        "mla_heads": lambda *arrays: mla_heads(cfg, *arrays, compute),
        "swiglu": lambda p, u: swiglu(p, u, compute),
        "route": lambda router, bias, u: route(cfg, router, bias, u, compute),
        "zero_part": lambda u, chosen, weights: zero_part(cfg, u, chosen, weights, compute),
        "expert_part": lambda one, local, u, chosen, weights: base.expert_part(
            cfg, one, local, u, chosen, weights, compute
        ),
        "head": lambda u, head: _mm("bsd,dv->bsv", u, head, compute).astype(jnp.float32),
    }


def _jitted(cfg: Dict, compute) -> Dict:
    """Each sub-block as one jitted program, so that the float32 casts of a
    sub-block's weights live only inside its call."""
    return {name: jax.jit(fn) for name, fn in _blocks(cfg, compute).items()}


def forward_checked(cfg: Dict, params: Dict, ids, compute=jnp.float32) -> Tuple[jax.Array, jax.Array, int]:
    """``(logits (B, S, V), routing slack (B, S), pairs routed to held experts)``."""
    run = _jitted(cfg, compute)
    x = run["embed"](params["embed"], ids)
    slack = jnp.full(ids.shape, jnp.inf)
    pairs_held = 0
    assert params["layers"]["router"].shape[0] == cfg["num_layers"]
    for i in range(cfg["num_layers"]):
        x, layer_slack, pairs = layer(cfg, params["layers"], i, x, compute, run)
        pairs_held += pairs
        slack = jnp.minimum(slack, layer_slack.reshape(ids.shape))
    u = run["norm"](x, params["final_norm"])
    return run["head"](u, params["head"]), slack, pairs_held


def forward(cfg: Dict, params: Dict, ids, compute=jnp.float32):
    """Reference logits ``(B, S, V)`` over the vocabulary slice, float32."""
    return forward_checked(cfg, params, ids, compute)[0]
