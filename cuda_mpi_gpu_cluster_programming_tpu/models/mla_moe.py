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
- *Router* and *MoE* (``models.moe_share``, which the families share):
  ``s = sigmoid(u W_r)`` in float32; selection on ``s + bias``: a group's score
  is the sum of its top 2, the top ``topk_group`` groups are kept, the top
  ``num_experts_per_tok`` experts among them chosen; weights are the unbiased
  ``s`` of the chosen, normalised to sum 1, times ``routed_scaling_factor``;
  ``sum_i w_i Expert_i(u) + Shared(u)``.

**The chip's share.** The layer is told which experts it holds
(``[experts_first, experts_first + experts_held)``), routes over all
``n_routed_experts`` and computes only the pairs whose expert it holds,
dropless (``models.moe_share`` says how); nothing here stands in for the
absent experts. The vocabulary is the chip's slice: ids are drawn from it and
the logits are over it.

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
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import scopes
from ..ops.flash_attention import flash_forward_bhld
from . import moe_share
from .moe_share import Params, _mm, _moe, _rms_norm, _swiglu
from .moe_share import _is_leaf, route  # noqa: unused-import — the MoE share's, under the names they had here


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
        moe_share.check_sigmoid_moe(self)

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


def mla_shapes(cfg) -> Params:
    """What ``_mla`` reads of a layer, as ``(shape, fan_in)`` leaves."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    return {
        "attn_norm": ((d,), 0),
        "q_a": ((d, cfg.q_lora_rank), d),
        "q_norm": ((cfg.q_lora_rank,), 0),
        "q_b": ((cfg.q_lora_rank, h, cfg.qk_head_dim), cfg.q_lora_rank),
        "kv_a": ((d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), d),
        "kv_norm": ((cfg.kv_lora_rank,), 0),
        "kv_b": ((cfg.kv_lora_rank, h, cfg.qk_nope_head_dim + cfg.v_head_dim), cfg.kv_lora_rank),
        "o": ((h, cfg.v_head_dim, d), h * cfg.v_head_dim),
    }


def param_shapes(cfg: MlaMoeConfig) -> Params:
    """The parameter tree as ``(shape, fan_in)`` leaves; ``fan_in`` 0 marks a
    norm gain (drawn as 1) and -1 the router's selection bias."""
    d = cfg.hidden_size
    f, fe = cfg.intermediate_size, cfg.moe_intermediate_size

    def layer(kind: str) -> Params:
        out = {**mla_shapes(cfg), "ffn_norm": ((d,), 0)}
        if kind == "dense":
            out["mlp"] = moe_share.swiglu_shapes(d, f)
        else:
            out["moe"] = moe_share.moe_shapes(d, fe, cfg)
        return out

    return {
        "embed": ((cfg.vocab_size, d), 1),
        "layers": [layer(kind) for kind in cfg.layer_kinds()],
        "final_norm": ((d,), 0),
        "head": ((d, cfg.vocab_size), d),
    }


def init(key, cfg: MlaMoeConfig = SMALL, dtype=jnp.bfloat16) -> Params:
    """Seeded parameters stored in ``dtype``: normal weights of scale
    ``fan_in**-0.5``, norm gains 1, a small selection bias, drawn layer by
    layer (``moe_share.init_by_layer``)."""
    return moe_share.init_by_layer(key, param_shapes(cfg), cfg.layer_kinds(), dtype)


def param_count(cfg: MlaMoeConfig) -> int:
    return moe_share.count(param_shapes(cfg))


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def _mla(p: Params, x, cfg: MlaMoeConfig, q_scale: float = 1.0, kv_scale: float = 1.0):
    """``x + MLA(RMSNorm(x))`` on the float32 residual stream ``(B, S, D)``.
    ``q_scale`` and ``kv_scale`` multiply the two normed latents ``c_q`` and
    ``c_kv`` (so queries, and keys' nope part and values; not ``k_r``) for a
    family that scales them by ``sqrt(hidden / rank)``; at 1 nothing is
    multiplied and the program built is the one built without them."""
    dt = p["q_a"].dtype
    seq = x.shape[1]
    with scopes.layer("mla.proj"):
        u = _rms_norm(x, p["attn_norm"], cfg.rms_norm_eps).astype(dt)
        c_q = _rms_norm(_mm("bsd,dr->bsr", u, p["q_a"]), p["q_norm"], cfg.rms_norm_eps)
        if q_scale != 1.0:
            c_q = c_q * q_scale
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
        if kv_scale != 1.0:
            c_kv = c_kv * kv_scale
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
    outside any hot loop) and fill the metrics registry's four ``moe.*``
    gauges (``moe_share.routing_statistics``) and ``flash.masked_score_share``
    (``moe_share.set_attention_gauge``). Returns the five values."""
    block = jax.jit(functools.partial(_block, cfg=cfg, with_sizes=True))
    out = moe_share.routing_statistics(params, ids, cfg, block)
    out.update(moe_share.set_attention_gauge(ids.shape[1], cfg.attn_block))
    return out
