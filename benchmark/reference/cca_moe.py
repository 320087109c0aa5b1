"""The plain reference of the compressed-convolutional-attention
mixture-of-experts family: float32 ``jax.numpy`` at ``precision="highest"``,
written from the layer equations below, with no kernel, no scan over the
layers, no cache and nothing imported from the program. The norm, SwiGLU and
the one-expert-at-a-time part are those of ``reference/mla_moe.py`` (benchmark
code). It takes the program's parameter tree (every leaf of ``layers`` with
the layers as its first axis; the projections onto heads stored heads-major,
``(heads, head_dim, hidden)``) and a configuration file's content, and is given
the same share as the program: the experts ``[experts_first, experts_first +
num_experts)`` of ``published.num_experts``, the vocabulary slice, the layers
kept (``num_layers``).

Stream: ``x_0 = E[ids]``; sublayers ``n = 0 .. 2L-1``, attention and MoE in
turn; with ``u_n = RMSNorm_n(x_n)`` and ``f_n`` the sublayer's output, ``x_{n+1}
= s^r_n * (x_n + b^r_n) + s^h_n * (f_n + b^h_n)``; ``logits = RMSNorm(x_2L)
E^T`` over the held rows (the embedding is tied).

- **CCA** (arXiv:2510.04476; heads of ``d = head_dim``, query head ``h`` reads
  key/value head ``h // (H / Hk)``): ``q~ = u W_q``, ``k~ = u W_k``, ``c = [q~,
  k~]``; ``c1_t = a_0 c_{t-1} + a_1 c_t + a_b`` per channel; ``c2_t[h] =
  c1_{t-1}[h] M_0[h] + c1_t[h] M_1[h] + m_b[h]``; ``m_q[h] = (q~[h] + k~[h //
  (H / Hk)]) / 2``, ``m_k[g]`` the mean of ``m_q`` over group ``g``; ``q =
  c2[queries] + m_q``, ``k = c2[keys] + m_k``; ``q <- sqrt(d) q / |q|``, ``k <-
  tau_g sqrt(d) k / |k|``; the rotary embedding on channels ``[0,
  partial_rotary_factor * d)`` of each head; ``v_t = [u_t W_v1, u_{t-1}
  W_v2]``; ``a = softmax(q k^T / sqrt(d) + causal mask) v``; ``f = a W_o``.
- **Router and experts** (arXiv:2511.17127): ``r_l = u W_d + b_d + gamma_l
  r_{l-1}`` (``r_{-1} = 0``), handed to layer ``l + 1`` as it stands; ``z = W_3
  gelu(W_2 gelu(W_1 RMSNorm(r_l) + b_1) + b_2)``; ``p = softmax(z)`` over the
  experts and the skip output (the last index); ``e = argmax(p + bias)``, ``w =
  p_e``; ``f = w Expert_e(u)`` where ``e`` is an expert held here, else 0.

Where the equations leave a choice, it is made here and noted:
- the convolutions are written for any number of taps, ``y_t = sum_j w_j
  x_{t-(K-1)+j}``: the LAST tap meets the token itself, zeros before the
  sequence (with ``cca_time0 = cca_time1 = 2`` the lines above);
- ``|x|`` is ``sqrt(sum x^2 + 1e-6)`` over a head's channels;
- the rotary embedding rotates channel ``i`` against channel ``i + rot / 2``
  within the first ``rot`` channels (the "default" rope of the source's
  ``rope_parameters``, halves against each other), at ``rope_theta``;
- gelu is the exact one (erf);
- attention's scores are materialised, the query heads of ONE key/value head
  at a time, so that 4,096 tokens fit; the head is computed ``HEAD_ROWS`` tokens
  at a time and handed to the host block by block, so that the logits of a
  whole sequence over 131,136 ids never stand twice on the device.

``forward_checked`` also gives each token's routing *slack*: how far, in
``p + bias``, its routing is from going another way ON THIS CHIP, the smallest
over the layers. In a layer: where the chosen output is a held expert, its
lead over the best other output; where it is not (another chip's expert, or
the skip), its lead over the best held expert: a swap among outputs that are
not held changes nothing here. A program that computes the same mathematics
with rounded operands may route a token of small slack otherwise, and a choice
that differs swaps the token's only expert; a token of large slack it may not.

``compute`` (default float32) is the type every weight and activation is cast
to and every product returns, the router's too: ``jnp.bfloat16`` gives the
reading "the nearest precision below" of PERF.md section 4, which the
tolerance must refuse.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import mla_moe as base

HEAD_ROWS = 512  # tokens whose logits are computed at a time
QK_NORM_EPS = 1e-6

_mm = base._mm
rms_norm = base.rms_norm


# ---- attention ------------------------------------------------------------------


def causal_conv(x, taps, bias, mix, compute=jnp.float32):
    """``y_t = sum_j x_{t-(K-1)+j} (*) taps[j] + bias`` along the sequence of ``x
    (B, H, S, E)``, zeros before the sequence. ``mix`` false: one weight per
    channel, ``taps (K, H, E)``; ``mix`` true: ``taps (K, H, E, E)`` mixes each
    head's channels, ``x[h] @ taps[j, h]``."""
    n, seq = taps.shape[0], x.shape[2]
    padded = jnp.pad(x.astype(compute), ((0, 0), (0, 0), (n - 1, 0), (0, 0)))
    out = jnp.zeros(x.shape, compute) + bias.astype(compute)[:, None, :]
    for j in range(n):
        window = padded[:, :, j : j + seq]
        if mix:
            out = out + _mm("bhse,hef->bhsf", window, taps[j], compute)
        else:
            out = out + window * taps[j].astype(compute)[:, None, :]
    return out.astype(compute)


def rope(x, theta: float, rot: int):
    """Rotate channel ``i`` of ``x (..., S, E)`` against channel ``i + rot / 2``,
    ``i < rot / 2``, by ``position * theta^(-2 i / rot)``; channels from ``rot``
    on pass."""
    half = rot // 2
    inv_freq = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / rot)
    angle = np.arange(x.shape[-2], dtype=np.float64)[:, None] * inv_freq[None, :]
    cos, sin = jnp.asarray(np.cos(angle), jnp.float32), jnp.asarray(np.sin(angle), jnp.float32)
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def unit(x, compute):
    """``sqrt(d) x / |x|`` over the last axis."""
    x = x.astype(compute)
    norm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + QK_NORM_EPS)
    return (x / norm * x.shape[-1] ** 0.5).astype(compute)


def cca_inputs(cfg: Dict, p: Dict, x, compute=jnp.float32):
    """``(q (B, H, S, d), k (B, Hk, S, d), v (B, Hk, S, d))`` of ``x (B, S, D)``."""
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    group, rot = h // hk, int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    theta = cfg["rope_parameters"]["hybrid"]["rope_theta"]
    u = rms_norm(x, p["attn_norm"], cfg["rms_norm_eps"], compute)
    q_lat = _mm("bsd,hed->bhse", u, p["q"], compute)
    k_lat = _mm("bsd,hed->bhse", u, p["k"], compute)
    c = jnp.concatenate([q_lat, k_lat], axis=1)
    c1 = causal_conv(c, p["conv0"], p["conv0_b"], False, compute)
    c2 = causal_conv(c1, p["conv1"], p["conv1_b"], True, compute)
    m_q = ((q_lat + jnp.repeat(k_lat, group, axis=1)) / 2).astype(compute)
    m_k = jnp.stack([m_q[:, g * group : (g + 1) * group].mean(axis=1) for g in range(hk)], axis=1)
    q = unit(c2[:, :h] + m_q, compute)
    k = unit(c2[:, h:] + m_k.astype(compute), compute) * p["tau"].astype(compute)[:, None, None]
    q, k = (rope(a.astype(jnp.float32), theta, rot).astype(compute) for a in (q, k))
    now = _mm("bsd,ed->bse", u, p["v1"], compute)
    before = _mm("bsd,ed->bse", jnp.pad(u, ((0, 0), (1, 0), (0, 0)))[:, :-1], p["v2"], compute)
    return q, k, jnp.stack([now, before], axis=1)


def cca_group(cfg: Dict, w_o, q, k, v, compute=jnp.float32):
    """The share of the sublayer's output ``(B, S, D)`` of the query heads ``q
    (B, G, S, d)`` of ONE key/value head, whose ``k``, ``v`` are ``(B, S, d)``."""
    positions = jnp.arange(q.shape[2])
    scores = _mm("bhse,bte->bhst", q, k, compute) * cfg["head_dim"] ** -0.5
    scores = jnp.where(positions[:, None] >= positions[None, :], scores.astype(compute), -jnp.inf)
    attn = _mm("bhst,bte->bhse", jax.nn.softmax(scores, axis=-1), v, compute)
    return _mm("bhse,hed->bsd", attn, w_o, compute)


def cca(cfg: Dict, p: Dict, x, compute=jnp.float32, run=None):
    """``CCA(RMSNorm(x))`` for ``x (B, S, D)``."""
    run = run or _blocks(cfg, compute)
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    q, k, v = run["cca_inputs"](p, x)
    out = jnp.zeros(x.shape, compute)
    for g in range(cfg["num_key_value_heads"]):
        hs = slice(g * group, (g + 1) * group)
        out = out + run["cca_group"](p["o"][hs], q[:, hs], k[:, g], v[:, g])
    return out.astype(compute)


def merge(m: Dict, x, f, compute=jnp.float32):
    """``s^r (x + b^r) + s^h (f + b^h)``."""
    s_r, b_r, s_h, b_h = (m[name].astype(compute) for name in ("s_r", "b_r", "s_h", "b_h"))
    return (s_r * (x.astype(compute) + b_r) + s_h * (f.astype(compute) + b_h)).astype(compute)


# ---- router and experts ---------------------------------------------------------


def route(cfg: Dict, p: Dict, u, r, compute=jnp.float32):
    """``(r_l (T, R), chosen (T, 1), weights (T, 1), slack (T,))`` for the normed
    tokens ``u (T, D)`` and the state ``r (T, R)`` of the layer above."""
    cast = lambda name: p[name].astype(compute)
    r = (_mm("td,dr->tr", u, p["w_d"], compute) + cast("b_d") + cast("gamma") * r.astype(compute)).astype(compute)
    hidden = rms_norm(r, p["router_norm"], cfg["rms_norm_eps"], compute)
    hidden = jax.nn.gelu(_mm("tr,rs->ts", hidden, p["w_1"], compute) + cast("b_1"), approximate=False)
    hidden = jax.nn.gelu(_mm("tr,rs->ts", hidden.astype(compute), p["w_2"], compute) + cast("b_2"), approximate=False)
    z = _mm("tr,re->te", hidden.astype(compute), p["w_3"], compute)
    probs = jax.nn.softmax(z.astype(compute), axis=-1).astype(jnp.float32)
    biased = probs + cast("bias").astype(jnp.float32)
    chosen = jnp.argmax(biased, axis=-1)
    weights = jnp.take_along_axis(probs, chosen[:, None], axis=-1)
    # The slack: the chosen output's lead over the best other output if it is a
    # held expert, over the best held expert if it is not.
    outputs = jnp.arange(biased.shape[-1])
    first = cfg["experts_first"]
    held = (outputs >= first) & (outputs < first + cfg["num_experts"])
    top = jnp.max(biased, axis=-1)
    others = jnp.where(outputs[None, :] == chosen[:, None], -jnp.inf, biased)
    rival = jnp.where(held[chosen], others.max(axis=-1), jnp.where(held[None, :], others, -jnp.inf).max(axis=-1))
    return r, chosen[:, None], weights, top - rival


def held_experts(cfg: Dict, experts: Dict, u, chosen, weights, compute=jnp.float32, run=None):
    """``(w_e Expert_e(u) where the token's output ``e`` is an expert held here,
    else 0; the pairs that fell to the held experts)``, one held expert at a
    time on every token."""
    run = run or _blocks(cfg, compute)
    out, pairs = jnp.zeros(u.shape, compute), 0
    for local in range(cfg["num_experts"]):
        one = {name: experts[name][local] for name in ("gate", "up", "down")}
        part, hits = run["expert_part"](one, local, u, chosen, weights)
        out, pairs = out + part, pairs + int(hits)
    return out.astype(compute), pairs


# ---- the model ------------------------------------------------------------------


def _blocks(cfg: Dict, compute) -> Dict:
    """The sub-blocks as functions of arrays alone, the configuration closed over."""
    return {
        "embed": lambda table, ids: table[ids].astype(compute),
        "norm": lambda x, gain: rms_norm(x, gain, cfg["rms_norm_eps"], compute),
        "cca_inputs": lambda p, x: cca_inputs(cfg, p, x, compute),
        "cca_group": lambda *arrays: cca_group(cfg, *arrays, compute),
        "merge": lambda m, x, f: merge(m, x, f, compute),
        "route": lambda p, u, r: route(cfg, p, u, r, compute),
        "expert_part": lambda one, local, u, chosen, weights: base.expert_part(
            cfg, one, local, u, chosen, weights, compute
        ),
        "head": lambda u, table: _mm("bsd,vd->bsv", u, table, compute).astype(jnp.float32),
    }


def _jitted(cfg: Dict, compute) -> Dict:
    """Each sub-block as one jitted program, so that the float32 casts of a
    sub-block's weights live only inside its call."""
    return {name: jax.jit(fn) for name, fn in _blocks(cfg, compute).items()}


def _layer(layers: Dict, i: int) -> Dict:
    """Layer ``i`` of the stacked tree."""
    return jax.tree.map(lambda leaf: leaf[i], layers)


def forward_checked(cfg: Dict, params: Dict, ids, compute=jnp.float32) -> Tuple[np.ndarray, jax.Array, int]:
    """``(logits (B, S, V) on the host, routing slack (B, S), pairs routed to
    held experts)``."""
    run = _jitted(cfg, compute)
    x = run["embed"](params["embed"], ids)
    r = jnp.zeros((ids.size, cfg["router_hidden_size"]), compute)
    slack = jnp.full(ids.shape, jnp.inf)
    pairs_held = 0
    assert params["layers"]["q"].shape[0] == cfg["num_layers"]
    for i in range(cfg["num_layers"]):
        p = _layer(params["layers"], i)
        experts = p.pop("experts")
        x = run["merge"](p["attn_merge"], x, cca(cfg, p, x, compute, run))
        flat = x.reshape(-1, x.shape[-1])
        u = run["norm"](flat, p["ffn_norm"])
        r, chosen, weights, layer_slack = run["route"](p, u, r)
        routed, pairs = held_experts(cfg, experts, u, chosen, weights, compute, run)
        pairs_held += pairs
        slack = jnp.minimum(slack, layer_slack.reshape(ids.shape))
        x = run["merge"](p["moe_merge"], flat, routed).reshape(x.shape)
    u = run["norm"](x, params["final_norm"])
    blocks = [
        np.asarray(run["head"](u[:, s0 : s0 + HEAD_ROWS], params["embed"]))
        for s0 in range(0, u.shape[1], HEAD_ROWS)
    ]
    return np.concatenate(blocks, axis=1), slack, pairs_held


def forward(cfg: Dict, params: Dict, ids, compute=jnp.float32):
    """Reference logits ``(B, S, V)`` over the vocabulary slice, float32."""
    return forward_checked(cfg, params, ids, compute)[0]
