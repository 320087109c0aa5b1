"""Parameters, operations and bytes of the hybrid linear-attention
mixture-of-experts family, from a configuration file alone. The rooflines'
numerators: kept with the benchmark so that no PR that claims a gain can
change them.

Counted as the chip's share runs them: the experts held here
(``n_routed_experts`` of ``published.n_routed_experts``), the vocabulary
slice, the layers kept (``gqa_layers`` of them softmax layers, the rest
linear). Matmul operations (2 x multiply-accumulates); causal scores as the
half the mask leaves; routed experts at the expected ``num_experts_per_tok x
held / all`` pairs a token (a reader that knows the pairs really routed passes
them); parameters at 2 bytes in bf16.

**The linear layers' scan is counted as the recurrence, not as any chunked
algorithm**: per token and head ``7 d^2`` operations (the state's decay
``d^2``, ``k^T S`` ``2 d^2``, the rank-1 update ``2 d^2``, ``q^T S`` ``2 d^2``),
and ``q, k, v, o`` in the stored type plus ``g`` and ``beta`` in float32 moved
once. A change of chunk size or of algorithm leaves the yardstick alone.
"""

from __future__ import annotations

from typing import Dict

BYTES = {"bf16": 2, "fp32": 4}


def _linear(cfg: Dict):
    linear = cfg["linear_attn_config"]
    return linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"]


def gqa_params(cfg: Dict) -> int:
    """q, gate and o over every query head, k and v over the key/value heads."""
    d, e = cfg["hidden_size"], cfg["head_dim"]
    return 3 * d * cfg["num_attention_heads"] * e + 2 * d * cfg["num_key_value_heads"] * e


def kda_matmul_params(cfg: Dict) -> int:
    """q, k, v, o, the two low-rank pairs (decay, output gate) and beta."""
    d = cfg["hidden_size"]
    h, e, _taps = _linear(cfg)
    return 4 * d * h * e + 2 * (d * e + e * h * e) + d * h


def kda_small_params(cfg: Dict) -> int:
    """Three short filters, ``A_log``, ``dt_bias``, the output norm's gain."""
    h, e, taps = _linear(cfg)
    return 3 * taps * h * e + h + h * e + e


def expert_params(cfg: Dict) -> int:
    """One routed expert, or the shared one: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: Dict) -> int:
    return cfg["hidden_size"] * cfg["published"]["n_routed_experts"]


def n_moe_layers(cfg: Dict) -> int:
    return cfg["num_layers"]  # every layer is a MoE layer: first_k_dense_replace 0


def n_gqa_layers(cfg: Dict) -> int:
    return len(cfg["gqa_layers"])


def n_kda_layers(cfg: Dict) -> int:
    return cfg["num_layers"] - n_gqa_layers(cfg)


def moe_matmul_params(cfg: Dict) -> int:
    """A layer's MoE part as held here: router, shared expert, held experts."""
    return router_params(cfg) + (1 + cfg["n_routed_experts"]) * expert_params(cfg)


def param_count(cfg: Dict) -> int:
    """Every parameter held here: matrices, filters, embedding and head over
    the slice, norm gains, the router's selection bias."""
    layers = (
        n_gqa_layers(cfg) * gqa_params(cfg)
        + n_kda_layers(cfg) * (kda_matmul_params(cfg) + kda_small_params(cfg))
        + cfg["num_layers"] * (moe_matmul_params(cfg) + cfg["published"]["n_routed_experts"])
        + cfg["num_layers"] * 2 * cfg["hidden_size"]
    )
    return layers + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def held_share(cfg: Dict) -> float:
    """Expected share of a token's routed pairs that fall to the experts held here."""
    return cfg["n_routed_experts"] / cfg["published"]["n_routed_experts"]


# ---- one layer's scopes ---------------------------------------------------------


def gqa_attn_flops(cfg: Dict, batch: int) -> float:
    """Causal scores and values of ONE softmax layer: the half the mask leaves,
    every query head at ``head_dim`` wide."""
    s = cfg["seq_len"]
    return 2.0 * batch * cfg["num_attention_heads"] * s * s * (2 * cfg["head_dim"]) / 2


def gqa_attn_bytes(cfg: Dict, batch: int) -> float:
    """ONE softmax layer: the queries read and the output written for every
    query head, keys and values of the key/value heads read once."""
    rows = batch * cfg["seq_len"] * cfg["head_dim"]
    return float(BYTES[cfg["compute"]] * rows * (2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]))


def kda_proj_flops(cfg: Dict, batch: int) -> float:
    """The projections of ONE linear layer."""
    return 2.0 * batch * cfg["seq_len"] * kda_matmul_params(cfg)


def kda_proj_bytes(cfg: Dict, batch: int) -> float:
    """ONE linear layer: the float32 residual read and written, the matrices
    read, ``q, k, v`` written and ``o`` read in the stored type, ``g`` and
    ``beta`` written in float32."""
    width, tokens = BYTES[cfg["compute"]], batch * cfg["seq_len"]
    h, e, _taps = _linear(cfg)
    return float(
        2 * 4 * tokens * cfg["hidden_size"] + width * kda_matmul_params(cfg)
        + tokens * h * (4 * e * width + 4 * e + 4)
    )


def kda_scan_flops(cfg: Dict, batch: int) -> float:
    """The recurrence of ONE linear layer: ``7 d^2`` a token and head."""
    h, e, _taps = _linear(cfg)
    return 7.0 * batch * cfg["seq_len"] * h * e * e


def kda_scan_bytes(cfg: Dict, batch: int) -> float:
    """ONE linear layer: ``q, k, v`` read and ``o`` written in the stored type,
    ``g`` and ``beta`` read in float32, once."""
    h, e, _taps = _linear(cfg)
    return float(batch * cfg["seq_len"] * h * (4 * e * BYTES[cfg["compute"]] + 4 * e + 4))


def experts_flops(cfg: Dict, pairs: float) -> float:
    """``pairs`` (token, expert) pairs through one expert each."""
    return 2.0 * pairs * expert_params(cfg)


def experts_bytes(cfg: Dict, pairs: float) -> float:
    """Every held expert of every MoE layer read once; per pair a row gathered
    in the compute type and a float32 row added to the layer's output."""
    width = BYTES[cfg["compute"]]
    weights = n_moe_layers(cfg) * cfg["n_routed_experts"] * expert_params(cfg) * width
    return float(weights + pairs * cfg["hidden_size"] * (width + 2 * 4))


def expected_pairs_per_step(cfg: Dict, batch: int) -> float:
    """Pairs routed to the held experts over every MoE layer of one step, if
    routing were uniform."""
    return n_moe_layers(cfg) * batch * cfg["seq_len"] * cfg["num_experts_per_tok"] * held_share(cfg)


# ---- the whole step ---------------------------------------------------------------


def matmul_flops_per_image(cfg: Dict) -> float:
    """Per SEQUENCE of ``seq_len`` tokens (one item of the pile): every matrix
    a token passes, the routed experts at the expected held share, the head
    over the slice, causal attention in the softmax layers and the
    recurrence's own operations in the linear ones."""
    per_token = (
        n_gqa_layers(cfg) * gqa_params(cfg) + n_kda_layers(cfg) * kda_matmul_params(cfg)
        + cfg["num_layers"] * (
            router_params(cfg) + expert_params(cfg)
            + cfg["num_experts_per_tok"] * held_share(cfg) * expert_params(cfg)
        )
        + cfg["vocab_size"] * cfg["hidden_size"]
    )
    return (
        2.0 * cfg["seq_len"] * per_token
        + n_gqa_layers(cfg) * gqa_attn_flops(cfg, 1) + n_kda_layers(cfg) * kda_scan_flops(cfg, 1)
    )


def min_bytes_per_step(cfg: Dict, batch: int) -> int:
    """The bytes one forward step cannot avoid moving: every parameter read
    once as it is stored, the ids read, the float32 logits written."""
    tokens = batch * cfg["seq_len"]
    return int(param_count(cfg) * BYTES[cfg["compute"]] + tokens * 4 + tokens * cfg["vocab_size"] * 4)
