"""Parameters, operations and bytes of the decoder-hybrid-decoder family, from
a configuration file alone. The rooflines' numerators: kept with the benchmark
so that no PR that claims a gain can change them.

A dense model, whole: every layer, the whole vocabulary. ``L`` layers: ``L/4``
(Mamba, windowed differential attention) pairs, a Mamba layer and a causal
differential attention layer that hand down, ``L/4 - 1`` (gated memory unit,
differential cross-attention) pairs; an MLP after every mixer. Matmul
operations (2 x multiply-accumulates); scores as the mask leaves them (the
causal half; with the window ``sum_q min(q + 1, window)``), both softmaxes of
a differential head, values twice a head wide; parameters at 2 bytes in bf16.

**The scan is counted as the recurrence, not as any algorithm**: per token,
channel and state 6 operations (``Delta A``, the decay times the state,
``Delta x`` times ``B`` and its addition, the state times ``C`` and its
addition into ``y``; the exponential is not counted), and ``x''``, ``y`` in the
stored type, ``Delta`` in float32, ``B`` and ``C`` moved once. It is a
memory-bound yardstick that a scan on the vector units reads low against: the
chip's peaks are the MXU's and the HBM's, and neither binds a token loop.
"""

from __future__ import annotations

from typing import Dict

BYTES = {"bf16": 2, "fp32": 4}
SCAN_OPS = 6  # a token, channel and state


def _ssm(cfg: Dict):
    a = cfg["assumed"]
    return a["expand"] * cfg["hidden_size"], a["d_state"], a["d_conv"], a["dt_rank"]


def head_dim(cfg: Dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


# ---- the layers' counts ------------------------------------------------------


def n_mamba_layers(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] // 4 + 1


def n_gmu_layers(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] // 4 - 1


def n_window_layers(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] // 4


def n_full_layers(cfg: Dict) -> int:
    """Layers whose flash kernel is causal alone: the one that keeps its keys
    and values and the cross layers that read them."""
    return cfg["num_hidden_layers"] // 4


def n_cross_layers(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] // 4 - 1


# ---- parameters ---------------------------------------------------------------


def norm_params(cfg: Dict) -> int:
    """One LayerNorm: gain and bias."""
    return 2 * cfg["hidden_size"]


def mlp_params(cfg: Dict) -> int:
    """W_1 (d -> 2 x width) and W_2 (width -> d), no bias."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def mamba_matmul_params(cfg: Dict) -> int:
    """W_in, W_x, W_dt and W_out."""
    d = cfg["hidden_size"]
    di, n, _taps, r = _ssm(cfg)
    return d * 2 * di + di * (r + 2 * n) + r * di + di * d


def mamba_small_params(cfg: Dict) -> int:
    """The taps and the bias of the convolution, the step's bias, ``A_log``, ``D``."""
    di, n, taps, _r = _ssm(cfg)
    return taps * di + di + di + di * n + di


def gmu_params(cfg: Dict) -> int:
    di = _ssm(cfg)[0]
    return 2 * cfg["hidden_size"] * di


def attn_matmul_params(cfg: Dict, cross: bool = False) -> int:
    """W_qkv (or W_q) and W_o."""
    d, e = cfg["hidden_size"], head_dim(cfg)
    heads = cfg["num_attention_heads"] + (0 if cross else 2 * cfg["num_key_value_heads"])
    return d * heads * e + cfg["num_attention_heads"] * e * d


def attn_small_params(cfg: Dict, cross: bool = False) -> int:
    """The projections' biases, the four lambda vectors, the per-head norm's gain."""
    e = head_dim(cfg)
    heads = cfg["num_attention_heads"] + (0 if cross else 2 * cfg["num_key_value_heads"])
    return heads * e + cfg["hidden_size"] + 4 * e + 2 * e


def param_count(cfg: Dict) -> int:
    """Every parameter: the tied embedding once, the mixers, an MLP a layer,
    a LayerNorm before every mixer, every MLP and the head."""
    layers = cfg["num_hidden_layers"]
    own_kv = n_window_layers(cfg) + 1
    return (
        cfg["vocab_size"] * cfg["hidden_size"]
        + layers * mlp_params(cfg)
        + n_mamba_layers(cfg) * (mamba_matmul_params(cfg) + mamba_small_params(cfg))
        + n_gmu_layers(cfg) * gmu_params(cfg)
        + own_kv * (attn_matmul_params(cfg) + attn_small_params(cfg))
        + n_cross_layers(cfg) * (attn_matmul_params(cfg, True) + attn_small_params(cfg, True))
        + (2 * layers + 1) * norm_params(cfg)
    )


# ---- one layer's scopes -------------------------------------------------------


def _stream_bytes(cfg: Dict, batch: int) -> float:
    """The float32 residual read and written once."""
    return 2.0 * 4 * batch * cfg["seq_len"] * cfg["hidden_size"]


def mlp_flops(cfg: Dict, batch: int) -> float:
    """ONE MLP on every token of a step."""
    return 2.0 * batch * cfg["seq_len"] * mlp_params(cfg)


def mlp_bytes(cfg: Dict, batch: int) -> float:
    """ONE MLP: its matrices read, the float32 residual read and written (the
    hidden activations stay on the chip in a perfect fusion)."""
    return BYTES[cfg["compute"]] * mlp_params(cfg) + _stream_bytes(cfg, batch)


def mamba_proj_flops(cfg: Dict, batch: int) -> float:
    """The four projections of ONE Mamba layer."""
    return 2.0 * batch * cfg["seq_len"] * mamba_matmul_params(cfg)


def mamba_proj_bytes(cfg: Dict, batch: int) -> float:
    """ONE Mamba layer: the residual read and written, the matrices read;
    ``x'`` and ``z`` written in float32, ``x''`` read and ``y`` read in the
    stored type, ``z`` read, ``Delta``'s input written in float32."""
    width, tokens = BYTES[cfg["compute"]], batch * cfg["seq_len"]
    di = _ssm(cfg)[0]
    return _stream_bytes(cfg, batch) + width * mamba_matmul_params(cfg) + tokens * di * (3 * 4 + 2 * width + 4)


def mamba_scan_flops(cfg: Dict, batch: int) -> float:
    """The recurrence of ONE Mamba layer: 6 operations a token, channel and state."""
    di, n, _taps, _r = _ssm(cfg)
    return float(SCAN_OPS) * batch * cfg["seq_len"] * di * n


def mamba_scan_bytes(cfg: Dict, batch: int) -> float:
    """ONE Mamba layer: ``x''`` read and ``y`` written in the stored type,
    ``Delta`` read in float32, ``B`` and ``C`` read once in float32."""
    di, n, _taps, _r = _ssm(cfg)
    return float(batch * cfg["seq_len"] * (di * (2 * BYTES[cfg["compute"]] + 4) + 2 * n * 4))


def gmu_flops(cfg: Dict, batch: int) -> float:
    """Both products of ONE gated memory unit."""
    return 2.0 * batch * cfg["seq_len"] * gmu_params(cfg)


def gmu_bytes(cfg: Dict, batch: int) -> float:
    """ONE unit: its matrices and the memory read, the residual read and written."""
    width = BYTES[cfg["compute"]]
    return width * gmu_params(cfg) + width * batch * cfg["seq_len"] * _ssm(cfg)[0] + _stream_bytes(cfg, batch)


def diff_proj_flops(cfg: Dict, batch: int, cross: bool = False) -> float:
    """The projections of ONE differential attention layer."""
    return 2.0 * batch * cfg["seq_len"] * attn_matmul_params(cfg, cross)


def diff_proj_bytes(cfg: Dict, batch: int, cross: bool = False) -> float:
    """ONE layer: the residual read and written, the matrices read, queries
    (and keys and values) written and both softmaxes' outputs read in the
    stored type."""
    width, tokens, e = BYTES[cfg["compute"]], batch * cfg["seq_len"], head_dim(cfg)
    heads = cfg["num_attention_heads"] + (0 if cross else 2 * cfg["num_key_value_heads"])
    moved = tokens * e * (heads + 2 * cfg["num_attention_heads"])  # q (k, v) out; a_1 and a_2 in, 2e wide a pair of heads
    return _stream_bytes(cfg, batch) + width * attn_matmul_params(cfg, cross) + width * moved


def scores_kept(cfg: Dict, window: int = 0) -> int:
    """The (query, key) pairs ONE softmax of one head keeps over a sequence:
    the causal half, or with a window ``sum_q min(q + 1, window)``."""
    s = cfg["seq_len"]
    seen = min(window, s) if window else s
    return seen * (seen + 1) // 2 + (s - seen) * seen


def diff_attn_flops(cfg: Dict, batch: int, window: int = 0) -> float:
    """Scores and values of ONE layer: every query head's softmax (two a
    differential head) over the pairs the mask leaves, keys ``e`` wide, values
    ``2 e``."""
    e = head_dim(cfg)
    return 2.0 * batch * cfg["num_attention_heads"] * scores_kept(cfg, window) * (e + 2 * e)


def diff_attn_bytes(cfg: Dict, batch: int) -> float:
    """ONE layer: the queries read (``e`` wide) and the outputs written (``2
    e`` wide) per query head, the keys and the values of a pair read once."""
    rows, e = batch * cfg["seq_len"], head_dim(cfg)
    per_token = cfg["num_attention_heads"] * 3 * e + cfg["num_key_value_heads"] * 2 * e
    return float(BYTES[cfg["compute"]] * rows * per_token)


# ---- the whole step -------------------------------------------------------------


def matmul_flops_per_image(cfg: Dict) -> float:
    """Per SEQUENCE of ``seq_len`` tokens (one item of the pile, as an image is
    for AlexNet): every matrix a token passes, the tied head, the scores and
    values the masks leave, the recurrence's own operations."""
    own_kv = n_window_layers(cfg) + 1
    per_token = (
        cfg["num_hidden_layers"] * mlp_params(cfg)
        + n_mamba_layers(cfg) * mamba_matmul_params(cfg)
        + n_gmu_layers(cfg) * gmu_params(cfg)
        + own_kv * attn_matmul_params(cfg) + n_cross_layers(cfg) * attn_matmul_params(cfg, True)
        + cfg["vocab_size"] * cfg["hidden_size"]
    )
    return (
        2.0 * cfg["seq_len"] * per_token
        + n_window_layers(cfg) * diff_attn_flops(cfg, 1, cfg["sliding_window"])
        + n_full_layers(cfg) * diff_attn_flops(cfg, 1)
        + n_mamba_layers(cfg) * mamba_scan_flops(cfg, 1)
    )


def min_bytes_per_step(cfg: Dict, batch: int) -> int:
    """The bytes one forward step cannot avoid moving: every parameter read
    once as it is stored, the ids read, the float32 logits written."""
    tokens = batch * cfg["seq_len"]
    return int(param_count(cfg) * BYTES[cfg["compute"]] + tokens * 4 + tokens * cfg["vocab_size"] * 4)
