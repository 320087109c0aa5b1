"""Shortcut-connected mixture-of-experts decoder over latent attention, as one
chip of an expert-parallel deployment holds it: every layer is TWO sublayers,
each a multi-head latent attention and a dense SwiGLU, and one mixture of
experts that branches off the first sublayer and rejoins the stream at the
layer's end (ScMoE, arXiv:2509.01322); the router is a softmax over the real
experts AND a block of zero-computation experts whose result is the token's
own normed input. No shared expert, no groups, no renormalisation.

- *Stream* (float32): ``x_0 = E[ids]``; ``logits = RMSNorm(x_L) W_head``.
- *One layer* (``x -> y``)::

      h1 = x  + MLA_0(RMSNorm_a0(x))
      u  = RMSNorm_f0(h1)
      m  = MoE(u)                      # the shortcut branch: needed only at the end
      h2 = h1 + FFN_0(u)               # FFN_0 reads the SAME normed u as the MoE
      h3 = h2 + MLA_1(RMSNorm_a1(h2))
      y  = h3 + FFN_1(RMSNorm_f1(h3)) + m

  Nothing between ``m``'s making and its use reads it: the routed experts may
  run anywhere beside the first dense FFN, the second attention and the second
  dense FFN (in a deployment that is where their two exchanges hide).
- *MLA* is ``models.mla_moe._mla`` (the prefill form) with the two normed
  latents scaled, ``c_q`` by ``sqrt(hidden / q_lora_rank)`` and ``c_kv`` by
  ``sqrt(hidden / kv_lora_rank)`` (so queries, keys' nope part and values; not
  ``k_r``), and the plain rotary embedding: the YaRN closed forms at factor 1
  are the base's own frequencies and the score scale ``qk_head_dim**-0.5``.
- *Router and MoE* (``u``: T x D): ``s = softmax(u W_r)`` over
  ``n_routed_experts + zero_expert_num`` outputs in float32; ``chosen = top-k
  of (s + b)``; ``w_i = routed_scaling_factor * s_i`` for the chosen, not
  renormalised (``moe_share.route_softmax``). Outputs below
  ``n_routed_experts`` are SwiGLU experts, the rest identities::

      MoE(u) = sum_{i chosen, real} w_i Expert_i(u) + (sum_{i chosen, identity} w_i) * u

  so a token runs 0 to k real experts.

**The chip's share** is ``models.moe_share``'s: the real experts
``[experts_first, experts_first + experts_held)``, the vocabulary slice. The
router keeps every output; a pair whose output is another chip's expert or an
identity is a pair of no work for the routed sum (``moe_share._routed`` as it
stands). The identity experts hold no parameters and are computed where the
token lives: the identity term is computed here for every token (scope
``moe.zero``), as a shared expert would be.

**The depth** is one ``lax.scan`` over layer-stacked parameters under the
scope ``layer_loop`` (as ``models.cca_moe``): four layers compile as one. The
held experts of every layer stay one stack the loop closes over, indexed from
the layer's first matrix on (``group_base``); a layer's other matrices the
loop slices out of the stack (a copy of each, which on the chip costs the step
nothing measurable beside the unrolled form, and the loop builds 10 s sooner).

Numerics follow the parameters' type, as the sibling families': stored in
bf16, operands go to the MXU in bf16 and every product accumulates in float32;
the stream, the norms, the rotary embedding, both softmaxes, the top-k
bookkeeping, the identity term and ``m`` are float32. ``forward`` syncs
nothing to the host; ``routing_statistics`` is the one place the routing is
read back.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import scopes
from . import mla_moe, moe_share
from .moe_share import Params, _mm, _rms_norm, _swiglu

# The selection bias is drawn at a softmax score's scale: over 768 outputs a
# score is about 1/768 = 0.0013 and the twelfth largest about 0.007, where
# ``ln(load)`` moves by some 370 per unit of bias, so 1e-4 moves an output's
# load by a few parts in a hundred; ``moe_share.BIAS_SCALE`` (a sigmoid
# score's) would BE the selection here.
BIAS_SCALE = 1e-4


@dataclasses.dataclass(frozen=True)
class ScmoeMlaConfig:
    """Every key of the published configuration that shapes the model, under
    the publisher's names, plus the share this chip holds and the program's
    tiles. The defaults are the small preset of the CPU tests and ``run.py``."""

    vocab_size: int = 256  # rows of the embedding and the head held here
    hidden_size: int = 64
    num_attention_heads: int = 4
    q_lora_rank: int = 32
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10_000_000.0
    max_position_embeddings: int = 131072
    num_layers: int = 2  # the layers held here, each two sublayers and one MoE
    ffn_hidden_size: int = 128  # width of each of a layer's two dense SwiGLUs
    expert_ffn_hidden_size: int = 32
    n_routed_experts: int = 8  # every real expert of a layer ...
    zero_expert_num: int = 4  # ... and the identity experts after them in the router's outputs
    moe_topk: int = 3
    routed_scaling_factor: float = 6.0
    experts_held: int = 2  # the real experts this chip holds ...
    experts_first: int = 0  # ... are [experts_first, experts_first + experts_held)
    attn_block: int = 512  # rows of a query or key block of the attention kernel
    expert_tile_rows: int = 8  # rows of one tile of the grouped product
    expert_chunk_rows: int = 16  # rows gathered and multiplied at a time
    expert_span_rows: int = 32  # rows of results held until their tokens gather them back

    # no ``rope_scaling``: the YaRN forms ``mla_moe`` computes, at factor 1
    rope_factor = 1.0
    rope_beta_fast, rope_beta_slow = 32.0, 1.0
    rope_mscale = rope_mscale_all_dim = 1.0

    def __post_init__(self):
        moe_share.check_share(self)
        if self.moe_topk > self.router_outputs:
            raise ValueError("moe_topk must be at most the router's outputs")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def rope_original_max_position_embeddings(self) -> int:
        return self.max_position_embeddings

    @property
    def num_experts_per_tok(self) -> int:
        """``moe_share``'s name for ``moe_topk``."""
        return self.moe_topk

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def q_scale(self) -> float:
        return (self.hidden_size / self.q_lora_rank) ** 0.5 if self.mla_scale_q_lora else 1.0

    @property
    def kv_scale(self) -> float:
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 if self.mla_scale_kv_lora else 1.0


SMALL = ScmoeMlaConfig()

# The published widths of a 28-layer model of this family (512 experts and 256
# identity experts a layer, top-12) as ONE of 32 expert-parallel chips holds
# them: 16 experts of each layer, an eighth of the vocabulary, four layers:
# 5.173B parameters, 10.35 GB in bf16. The benchmark's configuration file
# says the same, key for key (tests/benchmark hold the two together).
EP32_SHARE = ScmoeMlaConfig(
    vocab_size=16384, hidden_size=6144, num_attention_heads=64, q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, num_layers=4, ffn_hidden_size=12288,
    expert_ffn_hidden_size=2048, n_routed_experts=512, zero_expert_num=256, moe_topk=12,
    experts_held=16, experts_first=0,
    attn_block=1024, expert_tile_rows=256, expert_chunk_rows=1024, expert_span_rows=8192,
)

# preset -> (configuration, batch, sequence length) of ``run.py``'s one-shot
PRESETS = {"small": (SMALL, 2, 32), "longcat_ep32": (EP32_SHARE, 2, 4096)}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def param_shapes(cfg: ScmoeMlaConfig) -> Params:
    """The parameter tree as ``(shape, fan_in)`` leaves, every leaf of
    ``"layers"`` with the layers as its first axis; ``fan_in`` 0 marks a norm
    gain (drawn as 1) and -1 the router's selection bias. ``sub`` holds a
    layer's two sublayers, each what ``mla_moe._mla`` and ``mla_moe._dense``
    read. Where a latent is scaled by ``sqrt(hidden / rank)``, the matrix that
    expands it counts ``hidden`` as its fan-in (``init`` says why)."""
    d = cfg.hidden_size

    def sublayer() -> Params:
        mla = mla_moe.mla_shapes(cfg)
        if cfg.mla_scale_q_lora:
            mla["q_b"] = (mla["q_b"][0], d)
        if cfg.mla_scale_kv_lora:
            mla["kv_b"] = (mla["kv_b"][0], d)
        return {**mla, "ffn_norm": ((d,), 0), "mlp": moe_share.swiglu_shapes(d, cfg.ffn_hidden_size)}

    layer = {
        "sub": [sublayer(), sublayer()],
        "router": ((d, cfg.router_outputs), d),
        "bias": ((cfg.router_outputs,), -1),
        "experts": moe_share.swiglu_shapes(d, cfg.expert_ffn_hidden_size, (cfg.experts_held,)),
    }
    return {
        "embed": ((cfg.vocab_size, d), 1),
        "layers": moe_share.stacked(layer, cfg.num_layers),
        "final_norm": ((d,), 0),
        "head": ((d, cfg.vocab_size), d),
    }


def _draw_leaf(key, shape, fan_in, dtype):
    if fan_in < 0:
        return (jax.random.normal(key, shape, jnp.float32) * BIAS_SCALE).astype(dtype)
    return moe_share._draw_leaf(key, shape, fan_in, dtype)


def init(key, cfg: ScmoeMlaConfig = SMALL, dtype=jnp.bfloat16) -> Params:
    """Seeded parameters stored in ``dtype``: normal weights of scale
    ``fan_in**-0.5``, norm gains 1, a selection bias at a softmax score's
    scale (``BIAS_SCALE``), the layers drawn one at a time straight into the
    stack (``moe_share.init_stacked``). The matrices that expand a SCALED
    latent (``q_b``, ``kv_b``) are drawn at ``hidden**-0.5``, not
    ``rank**-0.5``: the scales ``sqrt(hidden / rank)`` exist to make up for
    exactly that (the family initialises every matrix at the model's width,
    and a latent narrower than the model then gives queries and keys too
    small by the ratio). Drawn at ``rank**-0.5`` AND scaled, queries come out
    2 x and keys 3.5 x too large, the scores' spread 7 x, every softmax all
    but one-hot, and the logits move by half their size under a bf16
    rounding."""
    return moe_share.init_stacked(key, param_shapes(cfg), cfg.num_layers, dtype, _draw_leaf)


def param_count(cfg: ScmoeMlaConfig) -> int:
    return moe_share.count(param_shapes(cfg))


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def _zero_experts(u, chosen, weights, cfg: ScmoeMlaConfig):
    """``(sum of the weights of the chosen identity experts) * u``, float32
    ``(T, D)``: one masked sum over the places and one multiply."""
    share = jnp.sum(jnp.where(chosen >= cfg.n_routed_experts, weights, 0.0), axis=-1, keepdims=True)
    return share * u


def _moe(p: Params, stack: Params, h, layer, cfg: ScmoeMlaConfig):
    """``(u (T, D), m (B, S, D), (chosen (T, k), the held experts' pair
    counts))`` for the stream ``h (B, S, D)`` after the first attention: the
    normed tokens ``u`` in the parameters' type (the first dense FFN reads
    them too) and the branch ``m = routed + identity``, float32, NOT added to
    the stream here. ``stack`` holds every layer's held experts, this layer's
    from matrix ``layer * experts_held`` on."""
    with scopes.layer("moe.route"), scopes.phase("route.score"):
        normed = _rms_norm(h.reshape(-1, h.shape[-1]), p["sub"][0]["ffn_norm"], cfg.rms_norm_eps)
        u = normed.astype(p["router"].dtype)
        chosen, weights = moe_share.route_softmax(p, u, cfg)
    routed, sizes = moe_share._routed(stack, u, chosen, weights, cfg, group_base=layer * cfg.experts_held)
    with scopes.layer("moe.zero"):
        m = (routed + _zero_experts(normed, chosen, weights, cfg)).reshape(h.shape)
    return u, m, (chosen, sizes)


def _layer(p: Params, stack: Params, x, layer, cfg: ScmoeMlaConfig):
    """One shortcut-connected layer on the float32 stream ``x (B, S, D)``:
    ``(y, (chosen, pair counts))``; ``p`` is the layer's parameters but for
    its experts, which are ``stack``'s from ``layer * experts_held`` on."""
    first, second = p["sub"]
    h1 = mla_moe._mla(first, x, cfg, cfg.q_scale, cfg.kv_scale)
    u, m, routing = _moe(p, stack, h1, layer, cfg)
    with scopes.layer("dense_mlp"):
        h2 = h1 + _swiglu(first["mlp"], u).reshape(h1.shape)
    h3 = mla_moe._mla(second, h2, cfg, cfg.q_scale, cfg.kv_scale)
    with scopes.layer("dense_mlp"):
        y = mla_moe._dense(second, h3, cfg) + m
    return y, routing


def _layers(params: Params, ids, cfg: ScmoeMlaConfig, with_routing: bool = False):
    """``(x after the last layer, None or per layer: (chosen (L, T, k), pair
    counts (L, held)))``: the embedding and one scan over the layers."""
    with scopes.layer("embed"):
        x = params["embed"][ids].astype(jnp.float32)
    with scopes.layer("layer_loop"):
        layers = dict(params["layers"])
        stack = {name: w.reshape(-1, *w.shape[2:]) for name, w in layers.pop("experts").items()}

        def one_layer(x, inputs):
            y, routing = _layer(inputs[0], stack, x, inputs[1], cfg)
            return y, (routing if with_routing else None)

        return lax.scan(one_layer, x, (layers, jnp.arange(cfg.num_layers, dtype=jnp.int32)))


def forward(params: Params, ids, cfg: ScmoeMlaConfig = SMALL):
    """``ids (B, S) int32`` from the vocabulary slice -> float32 logits
    ``(B, S, vocab_size)`` over it."""
    x, _routing = _layers(params, ids, cfg)
    with scopes.layer("head"):
        u = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        return _mm("bsd,dv->bsv", u, params["head"])


# ---------------------------------------------------------------------------
# Routing statistics: read back outside any hot loop
# ---------------------------------------------------------------------------


def routing_statistics(params: Params, ids, cfg: ScmoeMlaConfig = SMALL) -> Dict[str, float]:
    """Run ``ids`` through the layers (one program, outside any hot loop) and
    fill the metrics registry: the four ``moe.*`` routing gauges
    (``moe_share.set_routing_gauges``; ``moe.pairs_all`` counts every place,
    the identity experts' too), ``moe.zero_pair_share`` (the chosen places
    that are identity experts over all places, every layer together) and
    ``moe.real_experts_per_token_max`` / ``_min`` (the most and the fewest real
    experts one token ran in one layer: how far compute per token varies),
    and ``flash.masked_score_share`` (``moe_share.set_attention_gauge``).
    Returns the eight values."""
    from ..observability import metrics

    chosen, sizes = jax.jit(lambda p, i: _layers(p, i, cfg, with_routing=True)[1])(params, ids)
    real = np.asarray(chosen) < cfg.n_routed_experts  # (L, T, k)
    per_token = real.sum(axis=-1)  # the real experts each token ran in each layer
    out = moe_share.set_routing_gauges(list(np.asarray(sizes)), ids.size, cfg)
    out[metrics.MOE_ZERO_PAIR_SHARE] = float(1.0 - real.mean())
    out[metrics.MOE_REAL_EXPERTS_PER_TOKEN_MAX] = float(per_token.max())
    out[metrics.MOE_REAL_EXPERTS_PER_TOKEN_MIN] = float(per_token.min())
    for name in metrics.SCMOE_GAUGES:
        metrics.registry().gauge(name).set(out[name])
    out.update(moe_share.set_attention_gauge(ids.shape[1], cfg.attn_block))
    return out
