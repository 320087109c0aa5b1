"""Parameters, operations and bytes of the compressed-convolutional-attention
mixture-of-experts family, from a configuration file alone. The rooflines'
numerators: kept with the benchmark so that no PR that claims a gain can
change them. Of the equations (``reference/cca_moe.py``), not of any
implementation: a change of tile, of loop or of fusion leaves the yardstick
alone.

Counted as the chip's share runs them: the experts held here (``num_experts``
of ``published.num_experts``), the vocabulary slice (the embedding is tied:
its rows are counted once and read twice, by the gather and by the head), the
layers kept (``num_layers``, each an attention and a MoE sublayer). Matmul
operations (2 x multiply-accumulates); causal scores as the half the mask
leaves; routed experts at the expected pairs a token: the router's top-1 over
``published.num_experts + 1`` outputs (the experts and the skip), of which
``num_experts`` are held, so ``held / (all + 1)`` of the tokens a layer (a
reader that knows the pairs really routed passes them); parameters at 2 bytes
in bf16.
"""

from __future__ import annotations

from typing import Dict

BYTES = {"bf16": 2, "fp32": 4}


def _heads(cfg: Dict):
    return cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]


def cca_proj_params(cfg: Dict) -> int:
    """q and o over every query head, k over the key/value heads, the two
    value projections of one head each."""
    h, hk, e = _heads(cfg)
    return cfg["hidden_size"] * e * (2 * h + hk + 2)


def cca_conv_params(cfg: Dict) -> int:
    """The per-head filters that mix a head's channels: products on the MXU."""
    h, hk, e = _heads(cfg)
    return cfg["cca_time1"] * (h + hk) * e * e


def cca_small_params(cfg: Dict) -> int:
    """The depthwise filter, both convolutions' biases, ``tau``."""
    h, hk, e = _heads(cfg)
    return (cfg["cca_time0"] + 2) * (h + hk) * e + hk


def router_matmul_params(cfg: Dict) -> int:
    """``W_d``, ``W_1``, ``W_2`` and ``W_3`` over the experts and the skip output."""
    r = cfg["router_hidden_size"]
    return cfg["hidden_size"] * r + 2 * r * r + r * (cfg["published"]["num_experts"] + 1)


def router_small_params(cfg: Dict) -> int:
    """``b_d``, ``b_1``, ``b_2``, the state's norm, ``gamma``, the selection bias."""
    return 4 * cfg["router_hidden_size"] + 1 + cfg["published"]["num_experts"] + 1


def expert_params(cfg: Dict) -> int:
    """One routed expert: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def n_moe_layers(cfg: Dict) -> int:
    return cfg["num_layers"]  # every layer has a MoE sublayer


def layer_params(cfg: Dict) -> int:
    """One layer as held here: attention, router, held experts, two norms and
    the eight merge vectors."""
    return (
        cca_proj_params(cfg) + cca_conv_params(cfg) + cca_small_params(cfg)
        + router_matmul_params(cfg) + router_small_params(cfg)
        + cfg["num_experts"] * expert_params(cfg) + 10 * cfg["hidden_size"]
    )


def param_count(cfg: Dict) -> int:
    """Every parameter held here: the layers, the tied embedding over the
    slice (once), the final norm."""
    return cfg["num_layers"] * layer_params(cfg) + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def held_share(cfg: Dict) -> float:
    """Expected share of the tokens whose top-1 falls to an expert held here."""
    return cfg["num_experts"] / (cfg["published"]["num_experts"] + 1)


# ---- one layer's scopes ---------------------------------------------------------


def cca_proj_flops(cfg: Dict, batch: int) -> float:
    """The five projections of ONE attention sublayer."""
    return 2.0 * batch * cfg["seq_len"] * cca_proj_params(cfg)


def cca_proj_bytes(cfg: Dict, batch: int) -> float:
    """ONE attention sublayer: the float32 residual read and written, the
    matrices read, the latent ``q~, k~, v`` written and the attention's output
    read in the stored type."""
    width, tokens = BYTES[cfg["compute"]], batch * cfg["seq_len"]
    h, hk, e = _heads(cfg)
    return float(
        2 * 4 * tokens * cfg["hidden_size"] + width * cca_proj_params(cfg) + width * tokens * e * (2 * h + 2 * hk)
    )


def cca_attn_flops(cfg: Dict, batch: int) -> float:
    """Causal scores and values of ONE attention sublayer: the half the mask
    leaves, every query head at ``head_dim`` wide."""
    h, _hk, e = _heads(cfg)
    s = cfg["seq_len"]
    return 2.0 * batch * h * s * s * (2 * e) / 2


def cca_attn_bytes(cfg: Dict, batch: int) -> float:
    """ONE attention sublayer: the queries read and the output written for
    every query head, keys and values of the key/value heads read once."""
    h, hk, e = _heads(cfg)
    return float(BYTES[cfg["compute"]] * batch * cfg["seq_len"] * e * (2 * h + 2 * hk))


def experts_flops(cfg: Dict, pairs: float) -> float:
    """``pairs`` (token, expert) pairs through one expert each."""
    return 2.0 * pairs * expert_params(cfg)


def experts_bytes(cfg: Dict, pairs: float) -> float:
    """Every held expert of every layer read once; per pair a row gathered in
    the compute type and a float32 row added to the layer's output."""
    width = BYTES[cfg["compute"]]
    weights = n_moe_layers(cfg) * cfg["num_experts"] * expert_params(cfg) * width
    return float(weights + pairs * cfg["hidden_size"] * (width + 2 * 4))


def expected_pairs_per_step(cfg: Dict, batch: int) -> float:
    """Pairs routed to the held experts over every layer of one step, if the
    router's outputs, the skip among them, were chosen uniformly."""
    return n_moe_layers(cfg) * batch * cfg["seq_len"] * cfg["num_experts_per_tok"] * held_share(cfg)


# ---- the whole step -------------------------------------------------------------


def matmul_flops_per_image(cfg: Dict) -> float:
    """Per SEQUENCE of ``seq_len`` tokens (one item of the pile): every matrix
    a token passes (the projections, the per-head filters, the router), the
    routed expert at the expected held share, the head over the slice, and
    causal attention."""
    per_token = (
        cfg["num_layers"] * (
            cca_proj_params(cfg) + cca_conv_params(cfg) + router_matmul_params(cfg)
            + cfg["num_experts_per_tok"] * held_share(cfg) * expert_params(cfg)
        )
        + cfg["vocab_size"] * cfg["hidden_size"]
    )
    return 2.0 * cfg["seq_len"] * per_token + cfg["num_layers"] * cca_attn_flops(cfg, 1)


def min_bytes_per_step(cfg: Dict, batch: int) -> int:
    """The bytes one forward step cannot avoid moving: every parameter read
    once as it is stored, the ids read, the float32 logits written."""
    tokens = batch * cfg["seq_len"]
    return int(param_count(cfg) * BYTES[cfg["compute"]] + tokens * 4 + tokens * cfg["vocab_size"] * 4)
