"""A decoder-hybrid-decoder (SambaY with differential attention, arXiv:2507.06607)
whole on one chip: a dense model of five kinds of layer, two of which read
what ONE earlier layer made.

- *Stream* (float32): ``x = E[ids]``; every layer is ``x = x + mixer_i(LN(x))``
  then ``x = x + MLP(LN'(x))``; ``logits = LN_f(x) E^T`` (tied, float32, no
  bias). ``LN`` is LayerNorm with mean, gain and bias. No positional encoding.
- *MLP*: ``(g, p) = split(u W_1)``; ``(p * silu(g)) W_2``.
- *The mixer of layer i* (0-indexed, ``L`` layers, ``L / 2`` the hand-off):

  ==============================  =============================================
  even ``i < L/2``                Mamba: ``(x', z) = split(u W_in)``; ``x'' =
                                  silu(conv(x') + b_c)`` (depthwise, causal,
                                  the last tap on the token itself); ``(r, B,
                                  C) = x'' W_x``; ``Delta = softplus(r W_dt +
                                  b_dt)``; ``A = -exp(A_log)``; ``h_t =
                                  exp(Delta_t A) h_{t-1} + (Delta_t x''_t)
                                  B_t^T``; ``y_t = h_t C_t + D x''_t``; out
                                  ``(y * silu(z)) W_out``
  ``i = L/2``                     the same, and ``M = y`` (the scan's output
                                  with the ``D`` term, before the gate) is
                                  handed down
  even ``i > L/2``                gated memory unit: ``(silu(u W_1') * M)
                                  W_2'``: no scan, no convolution
  odd ``i < L/2``                 differential attention over a window: a
                                  query sees itself and ``sliding_window - 1``
                                  tokens before it
  ``i = L/2 + 1``                 differential attention, causal, whose keys
                                  and values are handed down
  odd ``i > L/2 + 1``             differential cross-attention: ``q = u W_q +
                                  b_q`` only, keys and values layer ``L/2 +
                                  1``'s
  ==============================  =============================================

- *Differential attention*: ``(q, k, v) = u W_qkv + b``; the query heads pair
  up ``(q1, q2) = (q[2i], q[2i+1])`` into ``H / 2`` differential heads, the
  key/value heads into ``Hk / 2`` pairs ``(k1, k2) = (k[2p], k[2p+1])`` with
  ``v = [v[2p], v[2p+1]]`` twice a head wide; ``a_j = softmax(q_j k_j^T /
  sqrt(e) + mask) v``; ``o = a_1 - lambda a_2`` with ``lambda = exp(l_q1 .
  l_k1) - exp(l_q2 . l_k2) + lambda_init``, ``lambda_init = 0.8 - 0.6
  exp(-0.3 i)``; ``o = RMSNorm(o) * (1 - lambda_init)`` per differential head
  with one learned gain; out ``concat(o) W_o + b_o``.

**Both softmaxes are ONE call of the flash kernel**: the queries stacked
``[q1 of every head, q2 of every head]``, the keys ``[k1 of every pair, k2 of
every pair]`` and the values ``[v, v]``, so that the kernel's grouped-query
map (query head ``h`` reads key/value head ``h // group``) sends ``q1`` to
``k1`` and ``q2`` to ``k2`` as it stands; the values' copy is 10 MB a layer at
the published size. The keys and values handed down are kept in that layout,
in the stored type.

**The depth** is three loop bodies, not ``L`` unrolled layers: one
``lax.scan`` over the ``L / 4`` (Mamba, windowed attention) pairs, the two
hand-off layers as they stand, one ``lax.scan`` over the ``L / 4 - 1`` (memory
unit, cross-attention) pairs that closes over ``M``, ``k`` and ``v``; both
scans under the scope ``layer_loop`` (``models.cca_moe`` builds 40 scanned
layers in less than unrolled siblings build 5).

Numerics follow the parameters' type, as the sibling families': stored in
bf16, operands go to the MXU in bf16 and every product accumulates in
float32; the stream, the norms, the convolution, ``Delta``, the scan's state
and every decay, ``lambda``, the difference and the per-head norm are
float32. ``forward`` syncs nothing to the host; ``layer_statistics`` is the
one place anything is read back.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import scopes
from ..ops.flash_attention import causal_plan, flash_forward_bhld
from ..ops.selective_scan import mamba_scan
from . import moe_share
from .moe_share import Params, _mm, _rms_norm

# marks of the leaves that are no matrix and no gain (``param_shapes``)
ZERO, BIAS, BIAS_OUT, A_LOG, DT_BIAS, LAMBDA = -2, -3, -4, -5, -6, -7
BIAS_SCALE = {BIAS: 0.1, BIAS_OUT: 0.02, LAMBDA: 0.1}
DT_MIN, DT_MAX = 1e-3, 0.1  # the drawn step before any input moves it


@dataclasses.dataclass(frozen=True)
class SambayConfig:
    """Every key of the published configuration that shapes the model, under
    the publisher's names, the state-space sizes the publisher's code
    defaults, and the program's tiles. The defaults are the small preset of
    the CPU tests and ``run.py``."""

    vocab_size: int = 256
    hidden_size: int = 64
    intermediate_size: int = 128
    num_hidden_layers: int = 8  # 2 pairs, the two hand-off layers, 1 pair
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    sliding_window: int = 8
    layer_norm_eps: float = 1e-5
    mb_per_layer: int = 2  # a state-space kind of mixer at every second layer
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 4  # ceil(hidden_size / 16)
    attn_block: int = 512  # rows of a query or key block of the causal layers
    window_block: int = 8  # ... and of the windowed layers
    scan_chunk: int = 256  # tokens of one step of the scan's sequential grid axis
    scan_channel_block: int = 512

    def __post_init__(self):
        if self.num_hidden_layers % 4 or self.num_hidden_layers < 8 or self.mb_per_layer != 2:
            raise ValueError("pairs of layers either side of the two hand-off layers: a multiple of 4, at least 8")
        if self.num_attention_heads % self.num_key_value_heads or self.num_key_value_heads % 2:
            raise ValueError("query heads and key/value heads pair up, whole groups of queries a key")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must be whole heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def first_pairs(self) -> int:
        """(Mamba, windowed attention) pairs before the hand-off."""
        return self.num_hidden_layers // 4

    @property
    def last_pairs(self) -> int:
        """(memory unit, cross-attention) pairs after it."""
        return self.num_hidden_layers // 4 - 1

    @property
    def sublayers(self) -> int:
        """Additions to the stream: a mixer and an MLP a layer."""
        return 2 * self.num_hidden_layers


SMALL = SambayConfig()

# The published model whole (3.85B parameters, 7.71 GB in bf16): nothing is
# cut. The benchmark's configuration file says the same, key for key
# (tests/benchmark hold the two together).
PHI4_MINI_FLASH = SambayConfig(
    vocab_size=200064, hidden_size=2560, intermediate_size=10240, num_hidden_layers=32,
    num_attention_heads=40, num_key_value_heads=20, sliding_window=512, dt_rank=160,
    attn_block=1024, window_block=512, scan_chunk=256, scan_channel_block=2560,
)

# preset -> (configuration, batch, sequence length) of ``run.py``'s one-shot
PRESETS = {"small": (SMALL, 2, 32), "phi4_mini_flash": (PHI4_MINI_FLASH, 1, 4096)}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _norm_shapes(d: int) -> Params:
    return {"gain": ((d,), 0), "bias": ((d,), ZERO)}


def mlp_shapes(cfg: SambayConfig) -> Params:
    d, width = cfg.hidden_size, cfg.intermediate_size
    return {"norm": _norm_shapes(d), "w1": ((d, 2 * width), d), "w2": ((width, d), width * cfg.sublayers)}


def mamba_shapes(cfg: SambayConfig) -> Params:
    d, di, n, r = cfg.hidden_size, cfg.d_inner, cfg.d_state, cfg.dt_rank
    return {
        "norm": _norm_shapes(d),
        "in": ((d, 2 * di), d),
        "conv": ((cfg.d_conv, di), cfg.d_conv),
        "conv_bias": ((di,), BIAS),
        "x": ((di, r + 2 * n), di),
        "dt": ((r, di), r),
        "dt_bias": ((di,), DT_BIAS),
        "a_log": ((di, n), A_LOG),
        "d": ((di,), 0),
        "out": ((di, d), di * cfg.sublayers),
    }


def gmu_shapes(cfg: SambayConfig) -> Params:
    d, di = cfg.hidden_size, cfg.d_inner
    return {"norm": _norm_shapes(d), "w1": ((d, di), d), "w2": ((di, d), di * cfg.sublayers)}


def attn_shapes(cfg: SambayConfig, cross: bool = False) -> Params:
    """A differential attention layer; a ``cross`` one holds a query
    projection and no key or value parameter."""
    d, e, h, hk = cfg.hidden_size, cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
    width = h * e if cross else (h + 2 * hk) * e
    return {
        "norm": _norm_shapes(d),
        "qkv": ((d, width), d),
        "qkv_bias": ((width,), BIAS),
        "lambda": ((4, e), LAMBDA),  # l_q1, l_k1, l_q2, l_k2
        "subln": ((2 * e,), 0),
        "o": ((h * e, d), h * e * cfg.sublayers),
        "o_bias": ((d,), BIAS_OUT),
    }


def param_shapes(cfg: SambayConfig) -> Params:
    """The parameter tree as ``(shape, fan_in)`` leaves: ``first`` and ``last``
    are one pair of layers' trees with the pairs as every leaf's first axis,
    ``mid`` the two hand-off layers. ``fan_in`` 0 marks a gain (drawn as 1;
    ``D`` too) and the negative marks above what is no matrix. The four
    output-side matrices (``out``, the memory unit's ``w2``, ``o``, the MLP's
    ``w2``) count ``fan_in x sublayers`` (``init`` says why)."""
    first = {"mamba": mamba_shapes(cfg), "mlp_a": mlp_shapes(cfg), "attn": attn_shapes(cfg), "mlp_b": mlp_shapes(cfg)}
    last = {
        "gmu": gmu_shapes(cfg), "mlp_a": mlp_shapes(cfg), "attn": attn_shapes(cfg, cross=True), "mlp_b": mlp_shapes(cfg)
    }
    return {
        "embed": ((cfg.vocab_size, cfg.hidden_size), 1),
        "first": moe_share.stacked(first, cfg.first_pairs),
        "mid": dict(first),
        "last": moe_share.stacked(last, cfg.last_pairs),
        "final_norm": _norm_shapes(cfg.hidden_size),
    }


def _draw_leaf(key, shape, fan_in, dtype):
    if fan_in == ZERO:
        return jnp.zeros(shape, dtype)
    if fan_in in BIAS_SCALE:
        return (jax.random.normal(key, shape, jnp.float32) * BIAS_SCALE[fan_in]).astype(dtype)
    if fan_in == A_LOG:  # A[c, n] = -(n + 1): every channel's states decay at 1 to d_state times its step
        return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)), shape).astype(dtype)
    if fan_in == DT_BIAS:  # the inverse softplus of a log-uniform step in [DT_MIN, DT_MAX]
        low, high = math.log(DT_MIN), math.log(DT_MAX)
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32, low, high))  # noqa: key-reuse (one branch a leaf)
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    return moe_share._draw_leaf(key, shape, fan_in, dtype)


def init(key, cfg: SambayConfig = SMALL, dtype=jnp.bfloat16) -> Params:
    """Seeded parameters stored in ``dtype``: normal weights of scale
    ``fan_in**-0.5`` (embedding rows 1), LayerNorm gains 1 and biases 0, the
    linear biases drawn small and NOT zero (a forgotten one then fails every
    comparison), ``A_log[c, n] = log(n + 1)``, ``dt_bias`` the inverse softplus
    of a log-uniform step, ``D = 1``, the four ``lambda`` vectors ``N(0,
    0.1)``. The four matrices that write to the stream are drawn at ``(fan_in
    x sublayers)**-0.5``: 64 additions of unit size would grow the stream 8 x
    and a part common to every token with it, and some fifteen layers down a
    seeded model's tokens are one vector a sequence (``PERF.md`` section 4,
    PR 33). Each stack is drawn a pair at a time straight into its place
    (``moe_share.init_stacked``)."""
    shapes = param_shapes(cfg)
    k_first, k_last = jax.random.split(key)
    last = shapes.pop("last")
    shapes["layers"] = shapes.pop("first")
    params = moe_share.init_stacked(k_first, shapes, cfg.first_pairs, dtype, _draw_leaf)
    params["first"] = params.pop("layers")
    params["last"] = moe_share.init_stacked(k_last, {"layers": last}, cfg.last_pairs, dtype, _draw_leaf)["layers"]
    return params


def param_count(cfg: SambayConfig) -> int:
    return moe_share.count(param_shapes(cfg))


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def _layer_norm(x, p: Params, eps: float):
    """LayerNorm with float32 statistics, gain and bias; float32 out."""
    xf = x.astype(jnp.float32)
    centred = xf - jnp.mean(xf, axis=-1, keepdims=True)
    scaled = centred * lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps)
    return scaled * p["gain"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def _w1_halves(w1, pair=None):
    """The ``(gate, up)`` column halves of an MLP's first matrix, the
    checkpoint's ``gate_up_proj`` (gate's columns first): of one layer's
    ``(D, 2F)``, or with ``pair`` of that layer of a loop's stack ``(n, D,
    2F)``, each half its own slice of the STACK. Its product then reads it
    there; a layer sliced out whole has the two products for readers, and the
    compiler copies it out first (0.32 ms an MLP; ``PERF.md``, PR 40)."""
    if pair is None:
        return jnp.split(w1, 2, axis=-1)
    _n, d, width = w1.shape
    return [lax.dynamic_slice(w1, (pair, 0, k * width // 2), (1, d, width // 2))[0] for k in (0, 1)]


def _mlp(p: Params, x, cfg: SambayConfig, halves=None):
    """``x + MLP(LN(x))`` on the float32 stream ``(B, S, D)``; ``halves`` are
    ``w1``'s where the caller took them from a stack (:func:`_w1_halves`).
    Each half is a product of its own, so that the gating is the second
    product's epilogue and ``w2`` reads the hidden in the parameters' type: one
    product of both halves leaves a float32 pair that ``w2`` gates in its
    prologue."""
    with scopes.layer("dense_mlp"):
        u = _layer_norm(x, p["norm"], cfg.layer_norm_eps)
        gate_w, up_w = halves or _w1_halves(p["w1"])
        gate, up = _mm("bsd,df->bsf", u, gate_w), _mm("bsd,df->bsf", u, up_w)
        return x + _mm("bsf,fd->bsd", up * jax.nn.silu(gate), p["w2"])


def lambda_init(layer):
    """``0.8 - 0.6 exp(-0.3 i)`` of layer ``i`` (a Python or a traced index)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def diff_lambda(vectors, layer):
    """``exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init`` from the four
    vectors ``(4, e)``; a float32 scalar."""
    l = vectors.astype(jnp.float32)
    return jnp.exp(jnp.sum(l[0] * l[1])) - jnp.exp(jnp.sum(l[2] * l[3])) + lambda_init(layer)


def _halves_major(t, heads: int):
    """``(B, S, heads x e) -> (B, heads, S, e)`` with the first of every pair
    of heads before the second of every pair: ``[0, 2, 4, ..., 1, 3, 5, ...]``."""
    b, s, width = t.shape
    return t.reshape(b, s, heads // 2, 2, width // heads).transpose(0, 3, 2, 1, 4).reshape(b, heads, s, -1)


def _stack_kv(k, v, cfg: SambayConfig):
    """The keys ``[k1 of every pair, k2 of every pair]`` and the values
    ``[v, v]``, heads-major, as the flash kernel's grouped-query map wants
    them; ``k, v (B, S, Hk x e)``."""
    b, s, _ = v.shape
    pairs = cfg.num_key_value_heads // 2
    wide = v.reshape(b, s, pairs, 2 * cfg.head_dim).transpose(0, 2, 1, 3)
    return _halves_major(k, cfg.num_key_value_heads), jnp.concatenate([wide, wide], axis=1)


def _diff_attn(p: Params, x, layer, cfg: SambayConfig, *, window=None, kv=None):
    """``(x + DiffAttn(LN(x)), (k, v))`` on the float32 stream: a layer with
    keys and values of its own (``kv`` None; they are returned, stacked for
    the kernel) or a cross layer that reads the ``kv`` it is given."""
    dt, e, h = p["qkv"].dtype, cfg.head_dim, cfg.num_attention_heads
    b, s, _ = x.shape
    with scopes.layer("diff.proj"):
        u = _layer_norm(x, p["norm"], cfg.layer_norm_eps)
        qkv = (_mm("bsd,de->bse", u, p["qkv"]) + p["qkv_bias"].astype(jnp.float32)).astype(dt)
        q = _halves_major(qkv[..., : h * e], h)
        if kv is None:
            kv = _stack_kv(*jnp.split(qkv[..., h * e :], 2, axis=-1), cfg)
        lam, lam0 = diff_lambda(p["lambda"], layer), lambda_init(layer)
    block = cfg.attn_block if window is None else cfg.window_block
    with scopes.layer("diff.attn_full" if window is None else "diff.attn_window"):
        both, _lse = flash_forward_bhld(q, *kv, causal=True, block_q=block, block_k=block, window=window)
    with scopes.layer("diff.proj"):
        o = both[:, : h // 2].astype(jnp.float32) - lam * both[:, h // 2 :].astype(jnp.float32)
        o = _rms_norm(o, p["subln"], cfg.layer_norm_eps) * (1.0 - lam0)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, h * e)
        return x + _mm("bse,ed->bsd", o, p["o"]) + p["o_bias"].astype(jnp.float32), kv


def _causal_conv(x, taps, bias):
    """Depthwise causal convolution of ``x (B, S, C)`` float32 with ``taps (K,
    C)``, the last tap on the token itself, plus ``bias``."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    taps = taps.astype(jnp.float32)
    return sum(padded[:, j : j + s] * taps[j] for j in range(k)) + bias.astype(jnp.float32)


def _mamba(p: Params, x, cfg: SambayConfig):
    """``(x + Mamba(LN(x)), y, (chunk log decay min, mean Delta))`` on the
    float32 stream: ``y (B, S, d_inner)`` is the scan's output with the ``D``
    term, before the gate, in the stored type."""
    dt, di, n, r = p["in"].dtype, cfg.d_inner, cfg.d_state, cfg.dt_rank
    with scopes.layer("mamba.proj"):
        u = _layer_norm(x, p["norm"], cfg.layer_norm_eps)
        xi, z = jnp.split(_mm("bsd,de->bse", u, p["in"]), 2, axis=-1)
    with scopes.layer("mamba.mix"):
        xc = jax.nn.silu(_causal_conv(xi, p["conv"], p["conv_bias"])).astype(dt)
    with scopes.layer("mamba.proj"):
        low = _mm("bse,er->bsr", xc, p["x"])
        b_in, c_out = low[..., r : r + n], low[..., r + n :]
        step = _mm("bsr,re->bse", low[..., :r], p["dt"])
    with scopes.layer("mamba.mix"):
        delta = jax.nn.softplus(step + p["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(p["a_log"].astype(jnp.float32))
    with scopes.layer("mamba.scan"):
        y = mamba_scan(
            xc, delta, a, b_in, c_out, p["d"], chunk=cfg.scan_chunk, channel_block=cfg.scan_channel_block
        )
    with scopes.layer("mamba.proj"):
        out = x + _mm("bse,ed->bsd", y.astype(jnp.float32) * jax.nn.silu(z), p["out"])
    return out, y, (delta, a)


def _gmu(p: Params, x, memory, cfg: SambayConfig):
    """``x + (silu(LN(x) W_1) * M) W_2`` on the float32 stream."""
    with scopes.layer("gmu"):
        u = _layer_norm(x, p["norm"], cfg.layer_norm_eps)
        gate = jax.nn.silu(_mm("bsd,de->bse", u, p["w1"]))
        return x + _mm("bse,ed->bsd", gate * memory.astype(jnp.float32), p["w2"])


def _scan_statistics(delta, a, cfg: SambayConfig):
    """``(the most negative Delta A summed over one chunk of the scan, mean
    Delta)`` of one Mamba layer: what a chunked form would exponentiate."""
    b, s, di = delta.shape
    chunk = min(cfg.scan_chunk, s)
    per_chunk = delta.reshape(b, s // chunk, chunk, di).sum(axis=2).max(axis=(0, 1))  # (di,)
    return jnp.min(per_chunk[:, None] * a), jnp.mean(delta)


def _layers(params: Params, ids, cfg: SambayConfig, with_statistics: bool = False):
    """``(x after the last layer, None or the Mamba layers' statistics (L/4 +
    1, 2))``: the embedding, the scan over the first pairs, the two hand-off
    layers, the scan over the last pairs."""
    half = cfg.num_hidden_layers // 2
    stats = (lambda delta, a: jnp.stack(_scan_statistics(delta, a, cfg))) if with_statistics else (lambda *_: None)
    with scopes.layer("embed"):
        x = params["embed"][ids].astype(jnp.float32)

    def mlp(stack, p, x, pair):
        """An MLP of a loop: ``p`` is the scan's slice of ``stack`` for this
        ``pair``, of which ``w1`` is not read: its halves come from the stack."""
        return _mlp(p, x, cfg, _w1_halves(stack["w1"], pair))

    def first_pair(x, inputs):
        p, pair = inputs
        x, _y, seen = _mamba(p["mamba"], x, cfg)
        x = mlp(params["first"]["mlp_a"], p["mlp_a"], x, pair)
        x, _kv = _diff_attn(p["attn"], x, 2 * pair + 1, cfg, window=cfg.sliding_window)
        return mlp(params["first"]["mlp_b"], p["mlp_b"], x, pair), stats(*seen)

    with scopes.layer("layer_loop"):
        x, seen_first = lax.scan(first_pair, x, (params["first"], jnp.arange(cfg.first_pairs, dtype=jnp.int32)))
    mid = params["mid"]
    x, memory, seen = _mamba(mid["mamba"], x, cfg)
    x = _mlp(mid["mlp_a"], x, cfg)
    x, kv = _diff_attn(mid["attn"], x, half + 1, cfg)
    x = _mlp(mid["mlp_b"], x, cfg)

    def last_pair(x, inputs):
        p, pair = inputs
        x = mlp(params["last"]["mlp_a"], p["mlp_a"], _gmu(p["gmu"], x, memory, cfg), pair)
        x, _kv = _diff_attn(p["attn"], x, half + 2 * pair + 3, cfg, kv=kv)
        return mlp(params["last"]["mlp_b"], p["mlp_b"], x, pair), None

    with scopes.layer("layer_loop"):
        x, _ = lax.scan(last_pair, x, (params["last"], jnp.arange(cfg.last_pairs, dtype=jnp.int32)))
    if not with_statistics:
        return x, None
    with scopes.layer("mamba.mix"):
        return x, jnp.concatenate([seen_first, stats(*seen)[None]])


def forward(params: Params, ids, cfg: SambayConfig = SMALL):
    """``ids (B, S) int32`` -> float32 logits ``(B, S, vocab_size)``."""
    x, _statistics = _layers(params, ids, cfg)
    with scopes.layer("head"):
        u = _layer_norm(x, params["final_norm"], cfg.layer_norm_eps)
        return _mm("bsd,vd->bsv", u, params["embed"])


# ---------------------------------------------------------------------------
# Layer statistics: read back outside any hot loop
# ---------------------------------------------------------------------------


def layer_statistics(params: Params, ids, cfg: SambayConfig = SMALL) -> Dict[str, float]:
    """Run ``ids`` through the layers (one program, outside any hot loop) and
    fill the metrics registry: ``ssm.chunk_log_decay_min`` (the most negative
    ``Delta A`` summed over one chunk of the scan, any Mamba layer, channel
    and state: what a chunked form would exponentiate, and why the kernel
    exponentiates one token's alone), ``ssm.dt_mean``, ``diff.lambda_min`` /
    ``diff.lambda_max`` over the attention layers, ``flash.masked_score_share``
    for the causal layers (``moe_share.set_attention_gauge``) and
    ``flash.window_masked_score_share`` for the windowed ones (both
    ``causal_plan``'s, from the shapes alone). Returns the six values."""
    from ..observability import metrics

    seen = np.asarray(jax.jit(lambda p, i: _layers(p, i, cfg, with_statistics=True)[1])(params, ids))
    half = cfg.num_hidden_layers // 2
    lambdas = [
        float(diff_lambda(vectors, layer))
        for stack, layers in (
            (params["first"]["attn"]["lambda"], range(1, half, 2)),
            (params["mid"]["attn"]["lambda"][None], [half + 1]),
            (params["last"]["attn"]["lambda"], range(half + 3, cfg.num_hidden_layers, 2)),
        )
        for vectors, layer in zip(stack, layers)
    ]
    window = causal_plan(ids.shape[1], cfg.window_block, cfg.window_block, cfg.sliding_window)
    out = {
        metrics.SSM_CHUNK_LOG_DECAY_MIN: float(seen[:, 0].min()),
        metrics.SSM_DT_MEAN: float(seen[:, 1].mean()),
        metrics.DIFF_LAMBDA_MIN: min(lambdas),
        metrics.DIFF_LAMBDA_MAX: max(lambdas),
        metrics.FLASH_WINDOW_MASKED_SCORE_SHARE: window.masked_score_share,
    }
    for name, value in out.items():
        metrics.registry().gauge(name).set(value)
    out.update(moe_share.set_attention_gauge(ids.shape[1], cfg.attn_block))
    return out
