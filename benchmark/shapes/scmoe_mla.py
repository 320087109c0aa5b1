"""Parameters, operations and bytes of the shortcut-connected
mixture-of-experts family over latent attention, from a configuration file
alone. The rooflines' numerators: kept with the benchmark so that no PR that
claims a gain can change them.

Counted as the chip's share runs them: the real experts held here
(``n_routed_experts`` of ``published.n_routed_experts``; the router's outputs
are those and ``zero_expert_num`` identity experts, which hold no parameter
and run no product), the vocabulary slice, the layers kept, each layer TWO
latent attentions and TWO dense SwiGLUs. Matmul operations only (2 x
multiply-accumulates); causal scores as the half the mask leaves; routed
experts at the expected ``moe_topk x held / outputs`` pairs a token (a reader
that knows the pairs really routed passes them); parameters at 2 bytes in
bf16. The latent attention's counts are ``shapes/mla_moe.py``'s, by import:
the keys they read are the same.
"""

from __future__ import annotations

from typing import Dict

from benchmark.shapes.mla_moe import BYTES, attn_bytes, attn_flops, mla_params, proj_bytes, proj_flops  # noqa: F401

SUBLAYERS = 2  # latent attentions, and dense SwiGLUs, in one layer


def dense_mlp_params(cfg: Dict) -> int:
    """One of a layer's two dense SwiGLUs: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"]


def expert_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def router_outputs(cfg: Dict) -> int:
    return cfg["published"]["n_routed_experts"] + cfg["zero_expert_num"]


def router_params(cfg: Dict) -> int:
    return cfg["hidden_size"] * router_outputs(cfg)


def norm_params(cfg: Dict) -> int:
    """Gains of one layer: per sublayer before attention, the two latent
    norms, before the FFN."""
    return SUBLAYERS * (2 * cfg["hidden_size"] + cfg["q_lora_rank"] + cfg["kv_lora_rank"])


def layer_matmul_params_outside_experts(cfg: Dict) -> int:
    """A layer's matrices but for its experts (638.84M at the real widths)."""
    return SUBLAYERS * (mla_params(cfg) + dense_mlp_params(cfg)) + router_params(cfg)


def layer_params(cfg: Dict) -> int:
    """Everything of one layer held here: matrices, held experts, norm gains,
    the selection bias."""
    return (
        layer_matmul_params_outside_experts(cfg) + cfg["n_routed_experts"] * expert_params(cfg)
        + norm_params(cfg) + router_outputs(cfg)
    )


def n_moe_layers(cfg: Dict) -> int:
    return cfg["num_layers"]


def n_attentions(cfg: Dict) -> int:
    """Latent attentions of one step, and dense SwiGLUs as many: what a reader
    multiplies ``proj_*``, ``attn_*`` or ``dense_*`` by (in the sibling
    latent-attention family it is ``num_layers``)."""
    return cfg["num_layers"] * SUBLAYERS


def param_count(cfg: Dict) -> int:
    """Every parameter held here: the layers, embedding and head over the
    slice, the final norm."""
    return cfg["num_layers"] * layer_params(cfg) + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def held_share(cfg: Dict) -> float:
    """Expected share of a token's chosen places that fall to the experts held
    here, the router's outputs chosen evenly."""
    return cfg["n_routed_experts"] / router_outputs(cfg)


def expected_pairs_per_step(cfg: Dict, batch: int) -> float:
    """Pairs routed to the held experts over every layer of one step, if
    routing were uniform over the router's outputs."""
    return cfg["num_layers"] * batch * cfg["seq_len"] * cfg["moe_topk"] * held_share(cfg)


def dense_flops(cfg: Dict, batch: int) -> float:
    """ONE dense SwiGLU on every token of a step."""
    return 2.0 * batch * cfg["seq_len"] * dense_mlp_params(cfg)


def dense_bytes(cfg: Dict, batch: int) -> float:
    """ONE dense SwiGLU: its three matrices read, the float32 residual read
    and written (the hidden activations stay on the chip in a perfect fusion)."""
    tokens = batch * cfg["seq_len"]
    return float(BYTES[cfg["compute"]] * dense_mlp_params(cfg) + 2 * 4 * tokens * cfg["hidden_size"])


def experts_flops(cfg: Dict, pairs: float) -> float:
    """``pairs`` (token, expert) pairs through one expert each."""
    return 2.0 * pairs * expert_params(cfg)


def experts_bytes(cfg: Dict, pairs: float) -> float:
    """Every held expert of every layer read once; per pair a row gathered in
    the compute type and a float32 row added to the branch's sum."""
    width = BYTES[cfg["compute"]]
    weights = cfg["num_layers"] * cfg["n_routed_experts"] * expert_params(cfg) * width
    return float(weights + pairs * cfg["hidden_size"] * (width + 2 * 4))


def matmul_flops_per_image(cfg: Dict) -> float:
    """Per SEQUENCE of ``seq_len`` tokens (one item of the pile, as an image is
    for AlexNet): every matrix a token passes (both attentions' projections,
    both dense SwiGLUs, the router), the routed experts at the expected held
    share, causal attention twice a layer, the head over the slice."""
    per_token = (
        cfg["num_layers"] * (
            layer_matmul_params_outside_experts(cfg)
            + cfg["moe_topk"] * held_share(cfg) * expert_params(cfg)
        )
        + cfg["vocab_size"] * cfg["hidden_size"]
    )
    return 2.0 * cfg["seq_len"] * per_token + n_attentions(cfg) * attn_flops(cfg, 1)


def min_bytes_per_step(cfg: Dict, batch: int) -> int:
    """The bytes one forward step cannot avoid moving: every parameter read
    once as it is stored, the ids read, the float32 logits written."""
    tokens = batch * cfg["seq_len"]
    return int(param_count(cfg) * BYTES[cfg["compute"]] + tokens * 4 + tokens * cfg["vocab_size"] * 4)
