"""Hybrid linear-attention mixture-of-experts decoder, as one chip of an
expert-parallel deployment holds it: layers of gated delta-rule linear
attention with a per-channel decay (KDA, arXiv:2510.26692) with a layer of
gated softmax attention among every few, each over a sigmoid-routed MoE with a
shared expert. No positional embedding anywhere.

- *Block*, for layer ``l``: ``h = x + Mix_l(RMSNorm(x))``; ``y = h +
  MoE(RMSNorm(h))``; ``Mix_l`` is *GQA* where ``l`` is in ``gqa_layers``, else
  *KDA*; a final RMSNorm, then the output head.
- *GQA* (softmax, no rotary embedding, gated): ``q = u W_q`` (``H`` heads),
  ``k = u W_k``, ``v = u W_v`` (``Hk`` heads; query head ``h`` reads key/value
  head ``h // (H / Hk)``); ``a = softmax(q k^T / sqrt(head_dim) + causal) v``,
  softmax in float32; ``out = (a * sigmoid(u W_g)) W_o``.
- *KDA*, per head: ``q = l2norm(silu(conv(u W_q)))``, ``k`` likewise, ``v =
  silu(conv(u W_v))``; ``conv`` is a causal depthwise convolution over the
  sequence with zero history, ``conv(x)_t = sum_j w_j x_{t-(K-1)+j}`` (the last
  tap meets the token itself), ``l2norm(x) = x / sqrt(sum x^2 + 1e-6)``;
  ``g_t = -exp(A_log) * softplus((u W_fa) W_fb + dt_bias)`` per key channel;
  ``beta_t = 2 sigmoid(u W_beta)`` per head (the 2 with
  ``kda_allow_neg_eigval``); the state and the output are
  ``ops.kda``'s recurrence at scale ``head_dim**-0.5``; ``out =
  (RMSNorm_head(o) * sigmoid((u W_ga) W_gb)) W_o``, the norm over each head's
  channels with one gain vector.
- *MoE*: ``models.moe_share``, the router in one group (a plain top-k of the
  biased sigmoid scores) — and the chip's share is the one described there:
  the experts ``[experts_first, experts_first + experts_held)`` of
  ``n_routed_experts``, the vocabulary slice.

Numerics follow the parameters' type. Stored in bf16, operands go to the MXU
in bf16 and every product accumulates in float32; the residual stream, the
norms, softmax, the router, the short convolution, the decays, ``beta``,
l2norm and the scan's state are float32. Stored in float32, every product
runs at HIGHEST.

A KDA layer's ``q``, ``k`` and ``v`` are each made in one pass over the
float32 projection by ``ops.kda_mix``'s kernel (the convolution's shifted
terms, silu and the l2norm in VMEM, the stored type out) where the head's
channels fill whole lanes and the sequence whole registers
(``kda_mix.fits``: the published widths), and by the ``jax.numpy`` form
(``_conv_mix_plain``: the kernel's oracle, the same terms in the same order)
otherwise (the small preset's 16 channels a head).

``forward`` syncs nothing to the host. ``layer_statistics`` reads the routing
and the decays back, outside any hot loop, into the metrics registry.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import kda_mix, scopes
from ..ops.flash_attention import flash_forward_bhld
from ..ops.kda import kda_chunked
from . import moe_share
from .moe_share import Params, _mm, _moe, _rms_norm

L2_EPS = kda_mix.L2_EPS
# Marks of ``param_shapes`` beyond ``moe_share``'s: the decay's rate and its step
A_LOG, DT_BIAS = -2, -3
A_RANGE = (1.0, 16.0)  # A_log = log U(1, 16)
DT_RANGE = (1e-3, 0.1)  # dt_bias = softplus^-1 of a log-uniform step


@dataclasses.dataclass(frozen=True)
class KdaMoeConfig:
    """Every key of the published configuration that shapes the model, under
    the publisher's names (``linear_attn_*`` are the keys of its
    ``linear_attn_config`` group), plus the share this chip holds and the
    program's tiles. The defaults are the small preset of the CPU tests and
    ``run.py``."""

    vocab_size: int = 512  # rows of the embedding and the head held here
    hidden_size: int = 64
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    linear_attn_num_heads: int = 4
    linear_attn_head_dim: int = 16
    short_conv_kernel_size: int = 4
    use_rope: bool = False
    use_gqa_gate: bool = True
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-5
    num_layers: int = 4  # the layers held here ...
    gqa_layers: Tuple[int, ...] = (0,)  # ... of which these are softmax layers
    moe_intermediate_size: int = 32  # width of one expert and of the shared one
    n_routed_experts: int = 16  # the router's width: every expert of the layer
    n_group: int = 1
    topk_group: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    n_shared_experts: int = 1
    experts_held: int = 4  # the experts this chip holds ...
    experts_first: int = 0  # ... are [experts_first, experts_first + experts_held)
    attn_block: int = 512  # rows of a query or key block of the attention kernel
    kda_chunk: int = 16  # tokens of one chunk of the scan
    kda_head_block: int = 2  # heads whose chains one program of the scan interleaves
    expert_tile_rows: int = 8  # rows of one tile of the grouped product
    expert_chunk_rows: int = 16  # rows gathered and multiplied at a time
    expert_span_rows: int = 32  # rows of results held until their tokens gather them back

    def __post_init__(self):
        moe_share.check_sigmoid_moe(self)
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide num_attention_heads")
        if not all(0 <= layer < self.num_layers for layer in self.gqa_layers):
            raise ValueError("gqa_layers must name layers that are held")
        if self.use_rope or self.kda_use_full_proj or not self.use_gqa_gate:
            raise ValueError("built for use_rope false, use_gqa_gate true, kda_use_full_proj false")

    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple("gqa" if layer in self.gqa_layers else "kda" for layer in range(self.num_layers))


SMALL = KdaMoeConfig()

# The published widths of a 48-layer, 320-expert model of this family as ONE of
# 8 expert-parallel chips holds them: 40 experts of each layer, an eighth of the
# vocabulary, one period of the layer pattern (a softmax layer, then three
# linear ones): 3.309B parameters, 6.62 GB in bf16. The benchmark's
# configuration file says the same, key for key (tests/benchmark hold the two
# together).
SOLAR_EP8_SHARE = KdaMoeConfig(
    vocab_size=24576, hidden_size=4096, num_attention_heads=64, num_key_value_heads=8, head_dim=128,
    linear_attn_num_heads=64, linear_attn_head_dim=128, short_conv_kernel_size=4,
    num_layers=4, gqa_layers=(0,), moe_intermediate_size=1280, n_routed_experts=320,
    num_experts_per_tok=8, experts_held=40, experts_first=0,
    attn_block=1024, kda_chunk=128, kda_head_block=4,
    expert_tile_rows=256, expert_chunk_rows=1024, expert_span_rows=24576,
)

# preset -> (configuration, batch, sequence length) of ``run.py``'s one-shot
PRESETS = {"small": (SMALL, 2, 64), "solar_ep8": (SOLAR_EP8_SHARE, 2, 8192)}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def param_shapes(cfg: KdaMoeConfig) -> Params:
    """The parameter tree as ``(shape, fan_in)`` leaves; ``fan_in`` 0 marks a
    norm gain (drawn as 1), -1 the router's selection bias, ``A_LOG`` and
    ``DT_BIAS`` the decay's rate and step. The projections onto heads are
    stored heads-major, ``(heads, head_dim, hidden)``, as the output projection
    is: stored ``(hidden, heads, head_dim)`` each was copied into that order
    before its product in every step (67 MB a matrix, eleven of them)."""
    d = cfg.hidden_size
    h, hk, e = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    lh, le, taps = cfg.linear_attn_num_heads, cfg.linear_attn_head_dim, cfg.short_conv_kernel_size

    def layer(kind: str) -> Params:
        if kind == "gqa":
            mix = {
                "q": ((h, e, d), d), "k": ((hk, e, d), d), "v": ((hk, e, d), d),
                "gate": ((h, e, d), d), "o": ((h, e, d), h * e),
            }
        else:
            mix = {
                "q": ((lh, le, d), d), "k": ((lh, le, d), d), "v": ((lh, le, d), d),
                "conv_q": ((taps, lh, le), taps), "conv_k": ((taps, lh, le), taps),
                "conv_v": ((taps, lh, le), taps),
                "f_a": ((d, le), d), "f_b": ((le, lh, le), le),  # the decay's low-rank pair
                "a_log": ((lh,), A_LOG), "dt_bias": ((lh, le), DT_BIAS),
                "beta": ((d, lh), d),
                "g_a": ((d, le), d), "g_b": ((le, lh, le), le),  # the output gate's low-rank pair
                "o_norm": ((le,), 0), "o": ((lh, le, d), lh * le),
            }
        return {
            "attn_norm": ((d,), 0), **mix, "ffn_norm": ((d,), 0),
            "moe": moe_share.moe_shapes(d, cfg.moe_intermediate_size, cfg),
        }

    return {
        "embed": ((cfg.vocab_size, d), 1),
        "layers": [layer(kind) for kind in cfg.layer_kinds()],
        "final_norm": ((d,), 0),
        "head": ((d, cfg.vocab_size), d),
    }


def _draw_rate(key, shape):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE))


def _draw_step(key, shape):
    lo, hi = (math.log(v) for v in DT_RANGE)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1


def _draw_leaf(key, shape, fan_in, dtype):
    if fan_in in (A_LOG, DT_BIAS):
        return (_draw_rate if fan_in == A_LOG else _draw_step)(key, shape).astype(dtype)
    return moe_share._draw_leaf(key, shape, fan_in, dtype)


def init(key, cfg: KdaMoeConfig = SMALL, dtype=jnp.bfloat16) -> Params:
    """Seeded parameters stored in ``dtype``: normal weights and convolution
    filters of scale ``fan_in**-0.5``, norm gains 1, a small selection bias,
    ``A_log = log U(1, 16)`` and ``dt_bias`` the inverse softplus of a
    log-uniform step in [1e-3, 0.1], drawn layer by layer
    (``moe_share.init_by_layer``)."""
    return moe_share.init_by_layer(key, param_shapes(cfg), cfg.layer_kinds(), dtype, _draw_leaf)


def param_count(cfg: KdaMoeConfig) -> int:
    return moe_share.count(param_shapes(cfg))


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def _gqa(p: Params, x, cfg: KdaMoeConfig):
    """``x + GQA(RMSNorm(x))`` on the float32 residual stream ``(B, S, D)``."""
    dt = p["q"].dtype
    with scopes.layer("gqa.proj"):
        u = _rms_norm(x, p["attn_norm"], cfg.rms_norm_eps).astype(dt)
        q = _mm("bsd,hed->bhse", u, p["q"]).astype(dt)
        k = _mm("bsd,hed->bhse", u, p["k"]).astype(dt)
        v = _mm("bsd,hed->bhse", u, p["v"]).astype(dt)
    with scopes.layer("gqa.attn"):
        attn, _lse = flash_forward_bhld(
            q, k, v, causal=True, scale=cfg.head_dim**-0.5, block_q=cfg.attn_block, block_k=cfg.attn_block
        )
    with scopes.layer("gqa.proj"):
        gated = attn.astype(jnp.float32) * jax.nn.sigmoid(_mm("bsd,hed->bhse", u, p["gate"]))
        return x + _mm("bhse,hed->bsd", gated, p["o"])


def _short_conv(x, taps):
    """``sum_j taps[j] x_{t-(K-1)+j}`` along the sequence of ``x (B, H, S, E)``,
    zero history, one filter ``taps (K, H, E)`` per channel: shifted
    multiply-adds on the projection's output."""
    n, seq = taps.shape[0], x.shape[2]
    taps = taps.astype(jnp.float32)
    out = x * taps[n - 1][:, None, :]
    for back in range(1, n):  # the token ``back`` places before
        shifted = jnp.pad(x, ((0, 0), (0, 0), (back, 0), (0, 0)))[:, :, :seq]
        out = out + shifted * taps[n - 1 - back][:, None, :]
    return out


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _conv_mix_plain(x, taps, dtype, *, l2norm: bool):
    """``silu(conv(x))``, l2-normalised where ``l2norm``, stored in ``dtype``,
    in ``jax.numpy``: a float32 intermediate between its passes."""
    y = jax.nn.silu(_short_conv(x, taps))
    return (_l2norm(y) if l2norm else y).astype(dtype)


def _conv_mix(x, taps, dtype, *, l2norm: bool):
    """:func:`_conv_mix_plain` in one pass of ``ops.kda_mix``'s kernel over the
    float32 projection where its shapes fit the kernel (the same terms in the
    same order), else that form itself."""
    if kda_mix.fits(x.shape[2], x.shape[3], taps.shape[0]):
        return kda_mix.short_conv_mix(x, taps, l2norm=l2norm, out_dtype=dtype)
    return _conv_mix_plain(x, taps, dtype, l2norm=l2norm)


def _kda(p: Params, x, cfg: KdaMoeConfig):
    """``(x + KDA(RMSNorm(x)), g, beta)`` on the float32 residual stream
    ``(B, S, D)``: the layer's output, and the log decays and write strengths
    it ran on (for the statistics; a caller that drops them pays nothing).
    The projections write float32; ``kda.mix`` reads each of q, k, v once
    (``_conv_mix``) and writes it in the parameters' type, and makes the
    decay and ``beta`` elementwise."""
    dt = p["q"].dtype
    with scopes.layer("kda.proj"):
        u = _rms_norm(x, p["attn_norm"], cfg.rms_norm_eps).astype(dt)
        q, k, v = (_mm("bsd,hed->bhse", u, p[name]) for name in ("q", "k", "v"))
        rate = _mm("bsr,rhe->bhse", _mm("bsd,dr->bsr", u, p["f_a"]), p["f_b"])
        write = jnp.swapaxes(_mm("bsd,dh->bsh", u, p["beta"]), 1, 2)  # (B, H, S), and small
    with scopes.layer("kda.mix"):
        q = _conv_mix(q, p["conv_q"], dt, l2norm=True)
        k = _conv_mix(k, p["conv_k"], dt, l2norm=True)
        v = _conv_mix(v, p["conv_v"], dt, l2norm=False)
        g = -jnp.exp(p["a_log"].astype(jnp.float32))[:, None, None] * jax.nn.softplus(
            rate + p["dt_bias"].astype(jnp.float32)[:, None, :]
        )
        beta = (2.0 if cfg.kda_allow_neg_eigval else 1.0) * jax.nn.sigmoid(write)
    with scopes.layer("kda.scan"):
        o = kda_chunked(
            q, k, v, g, beta, chunk=cfg.kda_chunk, head_block=cfg.kda_head_block,
            scale=cfg.linear_attn_head_dim**-0.5,
        )
    with scopes.layer("kda.proj"):
        gate = jax.nn.sigmoid(_mm("bsr,rhe->bhse", _mm("bsd,dr->bsr", u, p["g_a"]), p["g_b"]))
        return x + _mm("bhse,hed->bsd", _rms_norm(o, p["o_norm"], cfg.rms_norm_eps) * gate, p["o"]), g, beta


def _mix(p: Params, x, cfg: KdaMoeConfig):
    """``(x + Mix(RMSNorm(x)), the layer's MoE parameters with its norm, a KDA
    layer's (g, beta) or None)``; the layer's kind is its parameters'
    (``a_log`` marks a KDA layer)."""
    moe = {**p["moe"], "ffn_norm": p["ffn_norm"]}
    if "a_log" not in p:
        return _gqa(p, x, cfg), moe, None
    h, g, beta = _kda(p, x, cfg)
    return h, moe, (g, beta)


def _block(p: Params, x, cfg: KdaMoeConfig):
    h, moe, _decays = _mix(p, x, cfg)
    return _moe(moe, h, cfg)


def _block_with_stats(p: Params, x, cfg: KdaMoeConfig):
    """``(x, the held experts' pair counts, a KDA layer's (most negative log
    decay summed over a chunk, mean beta) or None)``."""
    h, moe, decays = _mix(p, x, cfg)
    stats = None
    if decays is not None:
        g, beta = decays
        chunks = g.reshape(*g.shape[:2], -1, cfg.kda_chunk, g.shape[-1])
        stats = (jnp.min(jnp.sum(chunks, axis=3)), jnp.mean(beta))
    out, sizes = _moe(moe, h, cfg, with_sizes=True)
    return out, sizes, stats


def _block_balancing(p: Params, x, cfg: KdaMoeConfig):
    """``(x, the selection bias that balances the router on this layer's
    input)``, ``x`` computed under that bias."""
    h, moe, _decays = _mix(p, x, cfg)
    u = _rms_norm(h.reshape(-1, h.shape[-1]), moe["ffn_norm"], cfg.rms_norm_eps).astype(moe["router"].dtype)
    bias = moe_share.balanced_bias(moe["bias"], lambda b: moe_share.route({**moe, "bias": b}, u, cfg)[0])
    return _moe({**moe, "bias": bias}, h, cfg), bias


def forward(params: Params, ids, cfg: KdaMoeConfig = SMALL):
    """``ids (B, S) int32`` from the vocabulary slice -> float32 logits
    ``(B, S, vocab_size)`` over it. ``S`` must be whole chunks of the scan."""
    with scopes.layer("embed"):
        x = params["embed"][ids].astype(jnp.float32)
    for p in params["layers"]:
        x = _block(p, x, cfg)
    with scopes.layer("head"):
        u = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        return _mm("bsd,dv->bsv", u, params["head"])


def balance_routers(params: Params, ids, cfg: KdaMoeConfig = SMALL) -> Params:
    """``params`` with every layer's selection bias replaced by the one that
    balances its router on ``ids (B, S)``, layer by layer (a layer's input
    depends on the biases before it, not on its own): what
    ``moe_share.balanced_bias`` says, for seeded weights that are to route as
    trained ones do. One jitted program per kind of layer, at set-up."""
    run = jax.jit(functools.partial(_block_balancing, cfg=cfg))
    x = moe_share.embed_tokens(params["embed"], ids)
    layers = []
    for p in params["layers"]:
        x, bias = run(p, x)
        layers.append({**p, "moe": {**p["moe"], "bias": bias}})
    return {**params, "layers": layers}


# ---------------------------------------------------------------------------
# Statistics: read back outside any hot loop
# ---------------------------------------------------------------------------


def layer_statistics(params: Params, ids, cfg: KdaMoeConfig = SMALL) -> Dict[str, float]:
    """Run ``ids`` layer by layer (one jitted program per kind of layer, outside
    any hot loop) and fill the metrics registry: the four ``moe.*`` routing
    gauges (``moe_share.routing_statistics``), ``kda.chunk_log_decay_min``
    (the most negative log decay summed over one chunk, over every KDA layer,
    head and channel: what no factor of the scan may exponentiate alone),
    ``kda.beta_mean`` and ``kda.mix_fused_layers`` (the KDA layers whose q, k
    and v took ``ops.kda_mix``'s kernel at this batch's shapes: all of them or
    none), and the softmax layers' ``flash.masked_score_share``
    (``moe_share.set_attention_gauge``). Returns the eight values."""
    from ..observability import metrics

    run = jax.jit(functools.partial(_block_with_stats, cfg=cfg))
    kda = []

    def block(p, x):
        x, sizes, stats = run(p, x)
        if stats is not None:
            kda.append(np.asarray(stats, np.float64))
        return x, sizes

    out = moe_share.routing_statistics(params, ids, cfg, block)
    decay_min, beta_mean = (np.min(kda, axis=0)[0], np.mean(kda, axis=0)[1]) if kda else (0.0, 0.0)
    fused = kda_mix.fits(ids.shape[1], cfg.linear_attn_head_dim, cfg.short_conv_kernel_size)
    out.update({
        metrics.KDA_CHUNK_LOG_DECAY_MIN: float(decay_min), metrics.KDA_BETA_MEAN: float(beta_mean),
        metrics.KDA_MIX_FUSED_LAYERS: float(len(kda) if fused else 0),
    })
    for name in metrics.KDA_GAUGES:
        metrics.registry().gauge(name).set(out[name])
    out.update(moe_share.set_attention_gauge(ids.shape[1], cfg.attn_block))
    return out
