"""The plain reference of the hybrid linear-attention mixture-of-experts
family: float32 ``jax.numpy`` at ``precision="highest"``, written from the
layer equations below, with no kernel, no chunking, no cache and nothing
imported from the program. The norm, SwiGLU, the router with its routing slack
and the one-expert-at-a-time sum are those of ``reference/mla_moe.py``
(benchmark code), whose docstring says what the slack is. It takes the
program's parameter tree (the projections onto heads stored heads-major,
``(heads, head_dim, hidden)``, as the output projection is) and a
configuration file's content, and is given the
same share as the program: the experts ``[experts_first, experts_first +
n_routed_experts)`` of ``published.n_routed_experts``, the vocabulary slice,
the layers kept.

Block, for layer ``l``: ``h = x + Mix_l(RMSNorm(x))``, ``y = h +
MoE(RMSNorm(h))``; ``Mix_l`` is GQA where ``l`` is in ``gqa_layers``, else KDA;
a final RMSNorm and the head. No positional embedding anywhere.

- **GQA** (softmax, no rotary embedding, gated): ``q = u W_q`` (64 heads x 128),
  ``k = u W_k``, ``v = u W_v`` (8 heads; query head ``h`` reads key/value head
  ``h // 8``); ``a = softmax(q k^T / sqrt(128) + causal mask) v``; ``out = (a *
  sigmoid(u W_g)) W_o``. The scores are materialised, a few query heads of one
  key/value head at a time, so that 8,192 tokens fit.
- **KDA** (Kimi Delta Attention, arXiv:2510.26692), per head with ``d = 128``:
  ``q = l2norm(silu(conv4(u W_q)))``, ``k`` likewise, ``v = silu(conv4(u
  W_v))``; ``g_t = -exp(A_log[h]) * softplus((u W_fa) W_fb + dt_bias)`` per key
  channel; ``beta_t = 2 sigmoid(u W_beta)``; the state ``S (d x d)``, zero at
  the start of a sequence, ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1}
  + beta_t k_t v_t^T``, ``o_t = S_t^T q_t / sqrt(d)``: the plain recurrence,
  one token at a time over ``lax.scan``; ``out = (RMSNorm_head(o_t) *
  sigmoid((u W_ga) W_gb)) W_o``.
- **MoE**: sigmoid scores over all the experts in one group, the 8 of largest
  ``s + bias``, weights the unbiased ``s`` normalised to sum 1.

Where the equations leave a choice, it is made here and noted:
- ``conv4(x)_t = sum_j w_j x_{t-3+j}``: the LAST tap meets the token itself
  (the layout of a causal ``conv1d`` weight), history before the sequence zero;
- ``l2norm(x) = x / sqrt(sum x^2 + 1e-6)`` over a head's channels;
- ``RMSNorm_head`` uses the configuration's ``rms_norm_eps`` and one gain
  vector of ``d`` for all heads;
- the state update is computed as written after one step of algebra,
  ``S' = Diag(exp g) S``, then ``S_t = S' + k (beta (v - S'^T k))^T``: the same
  numbers, no ``d x d`` matrix ``I - beta k k^T`` formed.

``compute`` (default float32) is the type every weight and activation is cast
to and every product returns, the scan's state too: ``jnp.bfloat16`` gives the
reading "the nearest precision below" of PERF.md section 4, which the
tolerance must refuse.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference import mla_moe as base

HEAD_BLOCK = 4  # query heads whose (S, S) scores are held at a time
L2_EPS = 1e-6

_mm = base._mm
rms_norm = base.rms_norm


# ---- the softmax layers -------------------------------------------------------


def gqa_keys_values(cfg: Dict, p: Dict, x, compute=jnp.float32):
    """``(u, k, v)`` of ``x (B, S, D)``: the normed input and every key/value head."""
    u = rms_norm(x, p["attn_norm"], cfg["rms_norm_eps"], compute)
    return u, _mm("bsd,hed->bhse", u, p["k"], compute), _mm("bsd,hed->bhse", u, p["v"], compute)


def gqa_heads(cfg: Dict, w_q, w_g, w_o, u, k, v, compute=jnp.float32):
    """A few query heads' share of the layer's output ``(B, S, D)``; ``k``, ``v``
    ``(B, S, e)`` are the ONE key/value head they read."""
    positions = jnp.arange(u.shape[1])
    q = _mm("bsd,hed->bhse", u, w_q, compute)
    scores = _mm("bhse,bte->bhst", q, k, compute) * cfg["head_dim"] ** -0.5
    scores = jnp.where(positions[:, None] >= positions[None, :], scores.astype(compute), -jnp.inf)
    attn = _mm("bhst,bte->bhse", jax.nn.softmax(scores, axis=-1), v, compute)
    gate = jax.nn.sigmoid(_mm("bsd,hed->bhse", u, w_g, compute))
    return _mm("bhse,hed->bsd", (attn * gate).astype(compute), w_o, compute)


def gqa(cfg: Dict, p: Dict, x, compute=jnp.float32, run=None):
    """``GQA(RMSNorm(x))`` for ``x (B, S, D)``."""
    run = run or _blocks(cfg, compute)
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    block = math.gcd(HEAD_BLOCK, group)  # whole blocks inside one key/value head's group
    u, k, v = run["gqa_keys_values"](p, x)
    out = jnp.zeros(x.shape, compute)
    for h0 in range(0, cfg["num_attention_heads"], block):
        hs, kv = slice(h0, h0 + block), h0 // group
        out = out + run["gqa_heads"](p["q"][hs], p["gate"][hs], p["o"][hs], u, k[:, kv], v[:, kv])
    return out.astype(compute)


# ---- the linear layers --------------------------------------------------------


def short_conv(x, taps, compute=jnp.float32):
    """``y_t = sum_j taps[j] x_{t-(K-1)+j}`` along the sequence of ``x (B, H, S, E)``,
    one filter ``taps (K, H, E)`` per channel, zeros before the sequence."""
    n, seq = taps.shape[0], x.shape[2]
    padded = jnp.pad(x.astype(compute), ((0, 0), (0, 0), (n - 1, 0), (0, 0)))
    windows = jnp.stack([padded[:, :, j : j + seq] for j in range(n)])  # (K, B, H, S, E)
    return jnp.sum(windows * taps.astype(compute)[:, None, :, None, :], axis=0).astype(compute)


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda_inputs(cfg: Dict, p: Dict, x, compute=jnp.float32):
    """``(u, q, k, v, g, beta)`` of ``x (B, S, D)``, heads-major ``(B, H, S, d)``
    (``beta (B, H, S)``)."""
    u = rms_norm(x, p["attn_norm"], cfg["rms_norm_eps"], compute)
    project = lambda name: short_conv(_mm("bsd,hed->bhse", u, p[name], compute), p[f"conv_{name}"], compute)
    q, k = (l2norm(jax.nn.silu(project(name))).astype(compute) for name in ("q", "k"))
    v = jax.nn.silu(project("v")).astype(compute)
    rate = _mm("bsr,rhe->bhse", _mm("bsd,dr->bsr", u, p["f_a"], compute), p["f_b"], compute)
    g = -jnp.exp(p["a_log"].astype(compute))[:, None, None] * jax.nn.softplus(
        rate + p["dt_bias"].astype(compute)[:, None, :]
    )
    factor = 2.0 if cfg["kda_allow_neg_eigval"] else 1.0
    beta = factor * jax.nn.sigmoid(_mm("bsd,dh->bhs", u, p["beta"], compute))
    return u, q, k, v, g.astype(compute), beta.astype(compute)


def kda_recurrence(cfg: Dict, q, k, v, g, beta, compute=jnp.float32):
    """``o (B, H, S, d)``: the recurrence, one token at a time."""
    scale = cfg["linear_attn_config"]["head_dim"] ** -0.5

    def step(state, token):  # state (B, H, dk, dv)
        q_t, k_t, v_t, g_t, beta_t = token
        state = (state * jnp.exp(g_t)[..., None]).astype(compute)
        write = beta_t[..., None] * (v_t - _mm("bhk,bhkv->bhv", k_t, state, compute))
        state = (state + k_t[..., None] * write[..., None, :]).astype(compute)
        return state, (_mm("bhk,bhkv->bhv", q_t, state, compute) * scale).astype(compute)

    tokens = tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v, g, beta))
    first = jnp.zeros((*q.shape[:2], q.shape[-1], v.shape[-1]), compute)
    return jnp.moveaxis(jax.lax.scan(step, first, tokens)[1], 0, 2)


def kda_output(cfg: Dict, p: Dict, u, o, compute=jnp.float32):
    gate = jax.nn.sigmoid(_mm("bsr,rhe->bhse", _mm("bsd,dr->bsr", u, p["g_a"], compute), p["g_b"], compute))
    normed = rms_norm(o, p["o_norm"], cfg["rms_norm_eps"], compute)
    return _mm("bhse,hed->bsd", (normed * gate).astype(compute), p["o"], compute)


def kda(cfg: Dict, p: Dict, x, compute=jnp.float32, run=None):
    """``KDA(RMSNorm(x))`` for ``x (B, S, D)``."""
    run = run or _blocks(cfg, compute)
    u, q, k, v, g, beta = run["kda_inputs"](p, x)
    return run["kda_output"](p, u, run["kda_recurrence"](q, k, v, g, beta))


# ---- the model ----------------------------------------------------------------


def _blocks(cfg: Dict, compute) -> Dict:
    """The sub-blocks as functions of arrays alone, the configuration closed
    over: the MoE's are ``reference/mla_moe.py``'s."""
    blocks = base._blocks(cfg, compute)
    blocks.update(
        gqa_keys_values=lambda p, x: gqa_keys_values(cfg, p, x, compute),
        gqa_heads=lambda *arrays: gqa_heads(cfg, *arrays, compute),
        kda_inputs=lambda p, x: kda_inputs(cfg, p, x, compute),
        kda_recurrence=lambda *arrays: kda_recurrence(cfg, *arrays, compute),
        kda_output=lambda p, u, o: kda_output(cfg, p, u, o, compute),
    )
    return blocks


def _jitted(cfg: Dict, compute) -> Dict:
    """Each sub-block as one jitted program, so that the float32 casts of a
    sub-block's weights live only inside its call."""
    return {name: jax.jit(fn) for name, fn in _blocks(cfg, compute).items()}


def _mix_params(p: Dict) -> Dict:
    """A layer's parameters without its experts: what the attention's
    sub-blocks are handed (a jitted call takes every leaf it is given)."""
    return {name: leaf for name, leaf in p.items() if name != "moe"}


def forward_checked(cfg: Dict, params: Dict, ids, compute=jnp.float32) -> Tuple[jax.Array, jax.Array, int]:
    """``(logits (B, S, V), routing slack (B, S), pairs routed to held experts)``."""
    run = _jitted(cfg, compute)
    x = run["embed"](params["embed"], ids)
    slack = jnp.full(ids.shape, jnp.inf)
    pairs_held = 0
    assert len(params["layers"]) == cfg["num_layers"]
    for i, p in enumerate(params["layers"]):
        mix = gqa if i in cfg["gqa_layers"] else kda
        x = (x + mix(cfg, _mix_params(p), x, compute, run)).astype(compute)
        flat = x.reshape(-1, x.shape[-1])
        u = run["norm"](flat, p["ffn_norm"])
        ffn, layer_slack, pairs = base.moe_ffn(cfg, p["moe"], u, compute, run)
        pairs_held += pairs
        slack = jnp.minimum(slack, layer_slack.reshape(ids.shape))
        x = (flat + ffn).reshape(x.shape).astype(compute)
    u = run["norm"](x, params["final_norm"])
    return run["head"](u, params["head"]), slack, pairs_held


def forward(cfg: Dict, params: Dict, ids, compute=jnp.float32):
    """Reference logits ``(B, S, V)`` over the vocabulary slice, float32."""
    return forward_checked(cfg, params, ids, compute)[0]
