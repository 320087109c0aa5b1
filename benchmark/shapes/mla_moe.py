"""Parameters, operations and bytes of the latent-attention mixture-of-experts
family, from a configuration file alone. The rooflines' numerators: kept with
the benchmark so that no PR that claims a gain can change them.

Counted as the chip's share runs them: the experts held here
(``n_routed_experts`` of ``published.n_routed_experts``), the vocabulary
slice, the layers kept. Matmul operations only (2 x multiply-accumulates);
causal scores as the half the mask leaves; routed experts at the expected
``num_experts_per_tok x held / all`` pairs a token (a reader that knows the
pairs really routed passes them); parameters at 2 bytes in bf16.
"""

from __future__ import annotations

from typing import Dict

BYTES = {"bf16": 2, "fp32": 4}


def mla_params(cfg: Dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (
        d * cfg["q_lora_rank"]
        + cfg["q_lora_rank"] * h * qk
        + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
        + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
        + h * cfg["v_head_dim"] * d
    )


def expert_params(cfg: Dict) -> int:
    """One routed expert, or the shared one: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_mlp_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: Dict) -> int:
    return cfg["hidden_size"] * cfg["published"]["n_routed_experts"]


def norm_params(cfg: Dict) -> int:
    """Gains of one layer: before attention, the two latent norms, before the FFN."""
    return 2 * cfg["hidden_size"] + cfg["q_lora_rank"] + cfg["kv_lora_rank"]


def moe_layer_matmul_params(cfg: Dict) -> int:
    """A MoE layer as held here, its matrices only (937.6M at the real widths)."""
    return (
        mla_params(cfg) + expert_params(cfg) + router_params(cfg)
        + cfg["n_routed_experts"] * expert_params(cfg)
    )


def dense_layer_matmul_params(cfg: Dict) -> int:
    return mla_params(cfg) + dense_mlp_params(cfg)


def n_moe_layers(cfg: Dict) -> int:
    return cfg["num_layers"] - cfg["first_k_dense_replace"]


def param_count(cfg: Dict) -> int:
    """Every parameter held here: matrices, embedding and head over the slice,
    norm gains, the router's selection bias."""
    matrices = (
        cfg["first_k_dense_replace"] * dense_layer_matmul_params(cfg)
        + n_moe_layers(cfg) * moe_layer_matmul_params(cfg)
        + 2 * cfg["vocab_size"] * cfg["hidden_size"]
    )
    small = (
        cfg["num_layers"] * norm_params(cfg) + cfg["hidden_size"]
        + n_moe_layers(cfg) * cfg["published"]["n_routed_experts"]
    )
    return matrices + small


def held_share(cfg: Dict) -> float:
    """Expected share of a token's routed pairs that fall to the experts held here."""
    return cfg["n_routed_experts"] / cfg["published"]["n_routed_experts"]


def attn_flops(cfg: Dict, batch: int) -> float:
    """Causal scores and values of ONE layer: the half the mask leaves."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    s = cfg["seq_len"]
    return 2.0 * batch * cfg["num_attention_heads"] * s * s * (qk + cfg["v_head_dim"]) / 2


def attn_bytes(cfg: Dict, batch: int) -> float:
    """ONE layer's queries, keys and values read and its output written once."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    rows = batch * cfg["num_attention_heads"] * cfg["seq_len"]
    return float(BYTES[cfg["compute"]] * rows * (2 * qk + 2 * cfg["v_head_dim"]))


def proj_flops(cfg: Dict, batch: int) -> float:
    """The five projections of ONE layer."""
    return 2.0 * batch * cfg["seq_len"] * mla_params(cfg)


def proj_bytes(cfg: Dict, batch: int) -> float:
    """ONE layer: the float32 residual read and written, the five matrices read,
    queries, keys and values written and the attention's output read."""
    width, tokens = BYTES[cfg["compute"]], batch * cfg["seq_len"]
    return float(2 * 4 * tokens * cfg["hidden_size"] + width * mla_params(cfg) + attn_bytes(cfg, batch))


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


def matmul_flops_per_image(cfg: Dict) -> float:
    """Per SEQUENCE of ``seq_len`` tokens (one item of the pile, as an image is
    for AlexNet): every matrix a token passes, the routed experts at the
    expected held share, causal attention, the head over the slice."""
    per_token = (
        cfg["first_k_dense_replace"] * dense_layer_matmul_params(cfg)
        + n_moe_layers(cfg) * (
            mla_params(cfg) + expert_params(cfg) + router_params(cfg)
            + cfg["num_experts_per_tok"] * held_share(cfg) * expert_params(cfg)
        )
        + cfg["vocab_size"] * cfg["hidden_size"]
    )
    return 2.0 * cfg["seq_len"] * per_token + cfg["num_layers"] * attn_flops(cfg, 1)


def min_bytes_per_step(cfg: Dict, batch: int) -> int:
    """The bytes one forward step cannot avoid moving: every parameter read
    once as it is stored, the ids read, the float32 logits written."""
    tokens = batch * cfg["seq_len"]
    return int(param_count(cfg) * BYTES[cfg["compute"]] + tokens * 4 + tokens * cfg["vocab_size"] * 4)
