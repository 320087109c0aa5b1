"""Compressed-convolutional-attention mixture-of-experts decoder, as one chip
of an expert-parallel deployment holds it: every layer an attention sublayer
whose queries, keys and values live in a latent narrower than the stream and
are mixed along the sequence by two short causal convolutions (CCA,
arXiv:2510.04476), and a MoE sublayer whose router is a small MLP over a state
that is carried down the depth, takes the top-1 of the experts and a *skip*
output, and weights by the unrenormalised probability (arXiv:2511.17127). No
shared expert; the head is the embedding transposed.

- *Stream*: ``x_0 = E[ids]``. Sublayers ``n = 0 .. 2L-1``, attention and MoE in
  turn; with ``u_n = RMSNorm_n(x_n)`` and ``f_n`` the sublayer's output the
  merge is ``x_{n+1} = s^r_n * (x_n + b^r_n) + s^h_n * (f_n + b^h_n)``, four
  learned vectors a sublayer. ``logits = RMSNorm(x_2L) E^T``.
- *CCA* (heads of ``d = head_dim``; query head ``h`` reads key/value head ``h //
  (H / Hk)``): ``q~ = u W_q`` (``H`` heads), ``k~ = u W_k`` (``Hk`` heads), ``c =
  [q~, k~]``; a depthwise causal convolution of ``cca_time0`` taps, ``c1_t =
  sum_j a_j c_{t-(K-1)+j} + a_b`` (the last tap meets the token itself, zero
  history), then one per head that mixes the head's channels, ``cca_time1``
  taps, ``c2_t[h] = sum_j c1_{t-(K-1)+j}[h] M_j[h] + m_b[h]``; the q-k mean from
  the values before the convolutions, ``m_q[h] = (q~[h] + k~[h // (H / Hk)]) /
  2``, ``m_k[g]`` the mean of ``m_q`` over group ``g``'s query heads; ``q =
  c2[queries] + m_q``, ``k = c2[keys] + m_k``; ``q <- sqrt(d) q / |q|``, ``k <-
  tau_g sqrt(d) k / |k|``; the rotary embedding on the first
  ``partial_rotary_factor`` of each head's channels (halves rotated against
  each other), the rest untouched; the values ``v_t = [u_t W_v1, u_{t-1}
  W_v2]``: key/value head 0 from the token, head 1 from the token before;
  ``a = softmax(q k^T / sqrt(d) + causal) v``; ``f = a W_o``.
- *Router and experts* (layer ``l``): ``r_l = u W_d + b_d + gamma_l r_{l-1}``
  (``r_{-1} = 0``; ``r_l`` as it stands is what layer ``l + 1`` receives); ``z =
  W_3 gelu(W_2 gelu(W_1 RMSNorm(r_l) + b_1) + b_2)`` over the ``num_experts``
  experts and the skip output (the last index); ``p = softmax(z)``; ``e =
  argmax(p + bias)``, ``w = p_e``; ``f = w Expert_e(u)`` for an expert, 0 for the
  skip: no pair is dispatched for a token that skips.

**The chip's share** is ``models.moe_share``'s: the experts ``[experts_first,
experts_first + experts_held)`` of ``num_experts``, the vocabulary slice. The
router keeps every output; a token whose choice is another chip's expert, or
the skip, is a pair of no work here.

**The depth** is one ``lax.scan`` over layer-stacked parameters (every leaf of
``params["layers"]`` has the layers as its first axis), under the scope
``layer_loop``: forty identical layers compile as one. The carry between
layers is ``(x, r)``. The held experts of every layer stay one stack that the
loop closes over, and a layer's grouped products index it from the layer's
first matrix on, so no layer's 200 MB of experts are sliced out for the kernel.

Numerics follow the parameters' type: stored in bf16, operands go to the MXU
in bf16 and every product accumulates in float32; the residual stream, the
merge vectors, the norms, the softmaxes, the router from ``W_d``'s output on
(its MLP's products in float32 at HIGHEST), the q/k normalisation and the
rotary tables are float32. ``forward`` syncs nothing to the host.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import scopes
from ..ops.flash_attention import flash_forward_bhld
from ..ops.reference import mxu_precision
from . import moe_share
from .moe_share import Params, _mm, _rms_norm

QK_NORM_EPS = 1e-6  # inside the square root of the q/k normalisation
# Marks of ``param_shapes`` beyond ``moe_share``'s: a merge scale (1 + 0.1 n)
# and a small additive vector (0.02 n): neither an identity a program could drop
MERGE_SCALE, SMALL_BIAS = -2, -3
BALANCE_ROUNDS, BALANCE_STEP = 48, 0.005


@dataclasses.dataclass(frozen=True)
class CcaMoeConfig:
    """Every key of the published configuration that shapes the model, under
    the publisher's names, plus the share this chip holds and the program's
    tiles. The defaults are the small preset of the CPU tests and ``run.py``."""

    vocab_size: int = 128  # rows of the tied embedding held here
    hidden_size: int = 64
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    cca_time0: int = 2  # taps of the depthwise convolution
    cca_time1: int = 2  # taps of the per-head convolution
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5_000_000.0
    rms_norm_eps: float = 1e-5
    num_layers: int = 4  # the layers held here, each an attention and a MoE sublayer
    moe_intermediate_size: int = 32
    num_experts: int = 4  # every expert of a layer; the router has one output more, the skip
    num_experts_per_tok: int = 1
    router_hidden_size: int = 16
    experts_held: int = 2  # the experts this chip holds ...
    experts_first: int = 0  # ... are [experts_first, experts_first + experts_held)
    attn_block: int = 512  # rows of a query or key block of the attention kernel
    expert_tile_rows: int = 8  # rows of one tile of the grouped product
    expert_chunk_rows: int = 16  # rows gathered and multiplied at a time
    expert_span_rows: int = 32  # rows of results held until their tokens gather them back

    def __post_init__(self):
        moe_share.check_share(self)
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide num_attention_heads")
        if self.num_key_value_heads != 2:
            raise ValueError("the value shift is of two key/value heads: one of the token, one of the token before")
        if self.num_experts_per_tok != 1:
            raise ValueError("the router takes the top-1 of the experts and the skip output")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError("partial_rotary_factor must leave an even number of a head's channels")

    @property
    def n_routed_experts(self) -> int:
        """The experts a share is cut from (``moe_share``'s name for them)."""
        return self.num_experts

    @property
    def skip_index(self) -> int:
        """The router's output that sends a token past the experts."""
        return self.num_experts

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)


SMALL = CcaMoeConfig()

# The published widths of a 40-layer, 16-expert model of this family as ONE of
# 2 expert-parallel chips holds them: 8 experts of each layer, half of the
# vocabulary, all 40 layers: 4.545B parameters, 9.09 GB in bf16. The
# benchmark's configuration file says the same, key for key (tests/benchmark
# hold the two together).
ZAYA1_EP2_SHARE = CcaMoeConfig(
    vocab_size=131136, hidden_size=2048, num_attention_heads=8, num_key_value_heads=2, head_dim=128,
    cca_time0=2, cca_time1=2, partial_rotary_factor=0.5, rope_theta=5_000_000.0, num_layers=40,
    moe_intermediate_size=2048, num_experts=16, router_hidden_size=256, experts_held=8, experts_first=0,
    attn_block=1024, expert_tile_rows=320, expert_chunk_rows=1280, expert_span_rows=3840,
)

# preset -> (configuration, batch, sequence length) of ``run.py``'s one-shot
PRESETS = {"small": (SMALL, 2, 64), "zaya1_ep2": (ZAYA1_EP2_SHARE, 1, 4096)}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def param_shapes(cfg: CcaMoeConfig) -> Params:
    """The parameter tree as ``(shape, fan_in)`` leaves, every leaf of
    ``"layers"`` with the layers as its first axis; ``fan_in`` 0 marks a gain
    drawn as 1 (the norms, ``tau``, ``gamma``), -1 the router's selection
    bias, ``MERGE_SCALE`` and ``SMALL_BIAS`` the merge's scales and every
    additive vector. The projections onto heads are stored heads-major,
    ``(heads, head_dim, hidden)``, as the output projection is. ``gamma`` of
    layer 0 is in the stack and meets a zero state. The two matrices that
    write into the stream, ``o`` and the experts' ``down``, count the ``2 L``
    sublayers in their fan-in (``init`` says why)."""
    d, e, r = cfg.hidden_size, cfg.head_dim, cfg.router_hidden_size
    h, hk = cfg.num_attention_heads, cfg.num_key_value_heads
    sublayers = 2 * cfg.num_layers
    experts = moe_share.swiglu_shapes(d, cfg.moe_intermediate_size, (cfg.experts_held,))
    experts["down"] = (experts["down"][0], experts["down"][1] * sublayers)
    merge = lambda: {
        "s_r": ((d,), MERGE_SCALE), "b_r": ((d,), SMALL_BIAS), "s_h": ((d,), MERGE_SCALE), "b_h": ((d,), SMALL_BIAS),
    }
    layer = {
        "attn_norm": ((d,), 0),
        "q": ((h, e, d), d), "k": ((hk, e, d), d), "v1": ((e, d), d), "v2": ((e, d), d),
        "o": ((h, e, d), h * e * sublayers),
        "conv0": ((cfg.cca_time0, h + hk, e), cfg.cca_time0), "conv0_b": ((h + hk, e), SMALL_BIAS),
        "conv1": ((cfg.cca_time1, h + hk, e, e), cfg.cca_time1 * e), "conv1_b": ((h + hk, e), SMALL_BIAS),
        "tau": ((hk,), 0),
        "attn_merge": merge(),
        "ffn_norm": ((d,), 0),
        "w_d": ((d, r), d), "b_d": ((r,), SMALL_BIAS), "gamma": ((1,), 0), "router_norm": ((r,), 0),
        "w_1": ((r, r), r), "b_1": ((r,), SMALL_BIAS), "w_2": ((r, r), r), "b_2": ((r,), SMALL_BIAS),
        "w_3": ((r, cfg.num_experts + 1), r), "bias": ((cfg.num_experts + 1,), -1),
        "experts": experts,
        "moe_merge": merge(),
    }
    return {
        "embed": ((cfg.vocab_size, d), 1), "layers": moe_share.stacked(layer, cfg.num_layers), "final_norm": ((d,), 0),
    }


def _draw_leaf(key, shape, fan_in, dtype):
    if fan_in in (MERGE_SCALE, SMALL_BIAS):
        n = jax.random.normal(key, shape, jnp.float32)
        return (1.0 + 0.1 * n if fan_in == MERGE_SCALE else 0.02 * n).astype(dtype)
    return moe_share._draw_leaf(key, shape, fan_in, dtype)


def init(key, cfg: CcaMoeConfig = SMALL, dtype=jnp.bfloat16) -> Params:
    """Seeded parameters stored in ``dtype``: normal weights and convolution
    filters of scale ``fan_in**-0.5`` (a filter's fan-in counts its taps),
    embedding rows of scale 1, gains 1, merge scales ``1 + 0.1 n``, additive
    vectors ``0.02 n``, a small selection bias. ``o`` and the experts'
    ``down`` are drawn at ``(fan_in * 2 L)**-0.5``, as deep residual models
    are initialised: at ``fan_in**-0.5`` every sublayer's output is as large
    as the stream, the part of it that attention averages over a sequence
    grows by half again every layer, and some fifteen layers down every
    token of a sequence is the same vector, which a router then sends to
    one or two experts whatever its bias. The layers are drawn one at a
    time, straight into the stack (``moe_share.init_stacked``)."""
    return moe_share.init_stacked(key, param_shapes(cfg), cfg.num_layers, dtype, _draw_leaf)


def param_count(cfg: CcaMoeConfig) -> int:
    return moe_share.count(param_shapes(cfg))


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def _back(x, places: int):
    """``x_{t-places}`` along the sequence (axis 2 of ``(B, H, S, E)``), zeros
    before the sequence."""
    if not places:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (places, 0), (0, 0)))[:, :, : x.shape[2]]


def _depthwise_conv(c, taps, bias):
    """``sum_j taps[j] c_{t-(K-1)+j} + bias`` along the sequence of ``c (B, H,
    S, E)``, one filter ``taps (K, H, E)`` per channel, zero history: shifted
    multiply-adds, float32."""
    n = taps.shape[0]
    taps = taps.astype(jnp.float32)
    out = bias.astype(jnp.float32)[:, None, :]
    for back in range(n):  # the token ``back`` places before
        out = out + _back(c, back) * taps[n - 1 - back][:, None, :]
    return out


def _head_conv(c, taps, bias):
    """``sum_j c_{t-(K-1)+j}[h] @ taps[j, h] + bias[h]``: a causal convolution
    that mixes each head's channels, ``taps (K, H, E, E)``: one product per tap,
    batched over the heads, float32 out. The operands are rounded to the
    filters' type and handed over as float32 at that type's precision
    (``mxu_precision``): for bf16 filters one bf16 pass with float32
    accumulation on the MXU, the numbers ``_mm`` gives. Handed over in bf16,
    a product with a batch axis inside a loop is one the CPU backend cannot run
    (jax 0.9.0: ``DotThunk``, BF16 x BF16 = F32)."""
    n, stored = taps.shape[0], taps.dtype
    c = c.astype(stored).astype(jnp.float32)
    out = bias.astype(jnp.float32)[:, None, :]
    for back in range(n):
        out = out + jnp.einsum(
            "bhse,hef->bhsf", _back(c, back), taps[n - 1 - back].astype(jnp.float32),
            precision=mxu_precision(stored),
        )
    return out


def _rope_tables(seq: int, cfg: CcaMoeConfig):
    """``(cos, sin)``, each ``(S, rotary_dim / 2)`` float32."""
    half = cfg.rotary_dim // 2
    inv_freq = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / cfg.rotary_dim)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angle), jnp.sin(angle)


def _rope(x, tables, rotary_dim: int):
    """Rotate the first ``rotary_dim`` channels of ``x (..., S, E)``, channel
    ``i`` against channel ``i + rotary_dim / 2``; the rest pass untouched."""
    cos, sin = tables
    half = rotary_dim // 2
    a, b, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def _unit(x, scale):
    """``scale * sqrt(d) * x / |x|`` over the last axis."""
    d = x.shape[-1]
    return x * (scale * d**0.5 * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + QK_NORM_EPS))


def _merge(m: Params, x, f):
    """``s^r (x + b^r) + s^h (f + b^h)``, float32."""
    s_r, b_r, s_h, b_h = (m[name].astype(jnp.float32) for name in ("s_r", "b_r", "s_h", "b_h"))
    return s_r * (x + b_r) + s_h * (f + b_h)


def _mix(p: Params, q_lat, k_lat, v1, v2, tables, cfg: CcaMoeConfig):
    """``(q, k, v)`` for the attention kernel from the projections' float32
    outputs ``q_lat (B, H, S, E)``, ``k_lat (B, Hk, S, E)``, ``v1``, ``v2 (B, S,
    E)``: the two convolutions, the q-k mean, the normalisation with ``tau``,
    the rotary embedding, the value shift."""
    dt, h, hk = p["q"].dtype, cfg.num_attention_heads, cfg.num_key_value_heads
    group = h // hk
    c = jnp.concatenate([q_lat, k_lat], axis=1)
    c = _head_conv(_depthwise_conv(c, p["conv0"], p["conv0_b"]), p["conv1"], p["conv1_b"])
    m_q = 0.5 * (q_lat + jnp.repeat(k_lat, group, axis=1))
    m_k = jnp.mean(m_q.reshape(m_q.shape[0], hk, group, *m_q.shape[2:]), axis=2)
    q = _rope(_unit(c[:, :h] + m_q, 1.0), tables, cfg.rotary_dim)
    k = _rope(_unit(c[:, h:] + m_k, p["tau"].astype(jnp.float32)[:, None, None]), tables, cfg.rotary_dim)
    v = jnp.stack([v1, _back(v2[:, None], 1)[:, 0]], axis=1)
    return q.astype(dt), k.astype(dt), v.astype(dt)


def _attention(p: Params, x, tables, cfg: CcaMoeConfig):
    """The attention sublayer on the float32 residual stream ``(B, S, D)``."""
    dt = p["q"].dtype
    with scopes.layer("cca.proj"):
        u = _rms_norm(x, p["attn_norm"], cfg.rms_norm_eps).astype(dt)
        q_lat = _mm("bsd,hed->bhse", u, p["q"])
        k_lat = _mm("bsd,hed->bhse", u, p["k"])
        v1, v2 = _mm("bsd,ed->bse", u, p["v1"]), _mm("bsd,ed->bse", u, p["v2"])
    with scopes.layer("cca.mix"):
        q, k, v = _mix(p, q_lat, k_lat, v1, v2, tables, cfg)
    with scopes.layer("cca.attn"):
        attn, _lse = flash_forward_bhld(
            q, k, v, causal=True, scale=cfg.head_dim**-0.5, block_q=cfg.attn_block, block_k=cfg.attn_block
        )
    with scopes.layer("cca.proj"):
        return _merge(p["attn_merge"], x, _mm("bhse,hed->bsd", attn, p["o"]))


def _mm32(spec: str, x, w):
    """A product of the router's MLP: float32 operands at HIGHEST whatever the
    weights are stored in (the router decides a token's only expert)."""
    return jnp.einsum(spec, x, w.astype(jnp.float32), precision=lax.Precision.HIGHEST)


def _router(p: Params, x, r, cfg: CcaMoeConfig):
    """``(u (T, D) in the parameters' type, r_l (T, R), p (T, experts + 1))``
    of the stream ``x (T, D)`` and the state ``r (T, R)`` of the layer above."""
    u = _rms_norm(x, p["ffn_norm"], cfg.rms_norm_eps).astype(p["w_d"].dtype)
    f32 = lambda name: p[name].astype(jnp.float32)
    r = _mm("td,dr->tr", u, p["w_d"]) + f32("b_d") + f32("gamma") * r
    hidden = _rms_norm(r, p["router_norm"], cfg.rms_norm_eps)
    hidden = jax.nn.gelu(_mm32("tr,rs->ts", hidden, p["w_1"]) + f32("b_1"), approximate=False)
    hidden = jax.nn.gelu(_mm32("tr,rs->ts", hidden, p["w_2"]) + f32("b_2"), approximate=False)
    return u, r, jax.nn.softmax(_mm32("tr,re->te", hidden, p["w_3"]), axis=-1)


def _top1(probs, bias):
    """``(chosen (T, 1) int32, weights (T, 1))``: the largest of ``p + bias``,
    weighted by its unbiased probability."""
    chosen = jnp.argmax(probs + bias.astype(jnp.float32), axis=-1).astype(jnp.int32)[:, None]
    return chosen, jnp.take_along_axis(probs, chosen, axis=-1)


def _moe(p: Params, stack: Params, x, r, layer, cfg: CcaMoeConfig, bias_for: Optional[Callable] = None):
    """The MoE sublayer: ``(x, r_l, (selection bias, chosen (T,), the held
    experts' pair counts))`` for the stream ``x (B, S, D)`` and the router
    state ``r (T, R)``; ``stack`` holds every layer's held experts, this
    layer's from matrix ``layer * experts_held`` on. ``bias_for(the layer's
    bias, p) -> bias`` replaces the selection bias by one made from this
    layer's probabilities."""
    flat = x.reshape(-1, x.shape[-1])
    with scopes.layer("moe.route"), scopes.phase("route.score"):
        u, r, probs = _router(p, flat, r, cfg)
        bias = p["bias"] if bias_for is None else bias_for(p["bias"], probs)
        chosen, weights = _top1(probs, bias)
    routed, sizes = moe_share._routed(stack, u, chosen, weights, cfg, group_base=layer * cfg.experts_held)
    with scopes.layer("moe.experts"), scopes.phase("experts.combine"):
        out = _merge(p["moe_merge"], flat, routed).reshape(x.shape)
    return out, r, (bias, chosen[:, 0], sizes)


def _layers(params: Params, ids, cfg: CcaMoeConfig, bias_for: Optional[Callable] = None, with_routing: bool = False):
    """``(x after the last layer, r of the last layer, None or per layer:
    (selection bias (L, experts + 1), chosen (L, T), pair counts (L, held)))``:
    the embedding and one scan over the layers."""
    with scopes.layer("embed"):
        x = params["embed"][ids].astype(jnp.float32)
    with scopes.layer("cca.mix"):
        tables = _rope_tables(ids.shape[1], cfg)
    layers = dict(params["layers"])
    stack = {name: w.reshape(-1, *w.shape[2:]) for name, w in layers.pop("experts").items()}

    def one_layer(carry, inputs):
        (x, r), (p, layer) = carry, inputs
        x = _attention(p, x, tables, cfg)
        x, r, routing = _moe(p, stack, x, r, layer, cfg, bias_for)
        return (x, r), (routing if with_routing else None)

    r = jnp.zeros((ids.size, cfg.router_hidden_size), jnp.float32)
    with scopes.layer("layer_loop"):
        (x, r), routing = lax.scan(one_layer, (x, r), (layers, jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    return x, r, routing


def forward(params: Params, ids, cfg: CcaMoeConfig = SMALL):
    """``ids (B, S) int32`` from the vocabulary slice -> float32 logits
    ``(B, S, vocab_size)`` over it."""
    x, _r, _routing = _layers(params, ids, cfg)
    with scopes.layer("head"):
        u = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        return _mm("bsd,vd->bsv", u, params["embed"])


def balance_routers(params: Params, ids, cfg: CcaMoeConfig = SMALL) -> Params:
    """``params`` with every layer's selection bias replaced by the one that
    balances its router's outputs, the skip among them, on ``ids (B, S)``,
    layer by layer inside the one scan (a layer's input depends on the biases
    before it, not on its own): what ``moe_share.balanced_bias`` says, for
    seeded weights that are to route as trained ones do. The top-1 of a
    softmax over a few outputs moves faster with the bias than the top-8 of
    hundreds of sigmoid scores (``ln(load)`` by about 100 per unit of bias at
    17 outputs: a step of 0.02 swings ever wider, 0.01 settles, 0.005 is a
    quarter of the Newton step), so the steps are smaller and more. One
    program, at set-up."""

    def balanced(start, probs):
        return moe_share.balanced_bias(start, lambda b: _top1(probs, b)[0], BALANCE_ROUNDS, BALANCE_STEP)

    biases = jax.jit(lambda p, i: _layers(p, i, cfg, balanced, with_routing=True)[2][0])(params, ids)
    return {**params, "layers": {**params["layers"], "bias": biases}}


# ---------------------------------------------------------------------------
# Statistics: read back outside any hot loop
# ---------------------------------------------------------------------------


def layer_statistics(params: Params, ids, cfg: CcaMoeConfig = SMALL) -> Dict[str, float]:
    """Run ``ids`` through the layers (one program, outside any hot loop) and
    fill the metrics registry: the four ``moe.*`` routing gauges
    (``moe_share.set_routing_gauges``; ``moe.pairs_all`` is tokens x layers x
    1), ``moe.skip_share`` (the tokens whose top-1 is the skip output over all
    routed tokens, every layer together) and ``router.state_rms_last`` (the
    rms of the last layer's ``r``: what the additions of the carried state
    come to), and ``flash.masked_score_share``
    (``moe_share.set_attention_gauge``). Returns the seven values."""
    from ..observability import metrics

    run = jax.jit(lambda p, i: _layers(p, i, cfg, with_routing=True)[1:])
    r, (_bias, chosen, sizes) = run(params, ids)
    out = moe_share.set_routing_gauges(list(np.asarray(sizes)), ids.size, cfg)
    out[metrics.MOE_SKIP_SHARE] = float(np.mean(np.asarray(chosen) == cfg.skip_index))
    out[metrics.ROUTER_STATE_RMS_LAST] = float(np.sqrt(np.mean(np.square(np.asarray(r, np.float64)))))
    for name in metrics.CCA_GAUGES:
        metrics.registry().gauge(name).set(out[name])
    out.update(moe_share.set_attention_gauge(ids.shape[1], cfg.attn_block))
    return out
