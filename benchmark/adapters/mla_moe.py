"""The latent-attention mixture-of-experts family's way into the program: from
a configuration file to the jitted forward, through the program's own entry
points (``configs.REGISTRY``, ``configs.build_forward``) and nothing lower.
The model module is imported here, at the top: a program that lacks it fails
at once, before any device is touched.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from cuda_mpi_gpu_cluster_programming_tpu.models import mla_moe

DTYPES = {"bf16": jnp.bfloat16, "fp32": jnp.float32}


def model_config(cfg: Dict) -> "mla_moe.MlaMoeConfig":
    """The program's model-config object for a configuration file: every
    width under the publisher's key, the share from the file's cuts."""
    rs = cfg["rope_scaling"]
    return mla_moe.MlaMoeConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"],
        rope_factor=rs["factor"],
        rope_original_max_position_embeddings=rs["original_max_position_embeddings"],
        rope_beta_fast=rs["beta_fast"],
        rope_beta_slow=rs["beta_slow"],
        rope_mscale=rs["mscale"],
        rope_mscale_all_dim=rs["mscale_all_dim"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_moe_layers=cfg["num_layers"] - cfg["first_k_dense_replace"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["published"]["n_routed_experts"],
        n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        n_shared_experts=cfg["n_shared_experts"],
        experts_held=cfg["n_routed_experts"],
        experts_first=cfg["experts_first"],
        **cfg.get("program_tiles", {}),
    )


def make_params(cfg: Dict, seed: int):
    """The program's own seeded draw, stored in the configuration's compute
    type, layer by layer on the device. The key is of the ``rbg`` kind: the
    chip's own bit generator compiles in seconds where the default hash of
    4.6e9 counters takes a minute and a half; the same seed still gives the
    same weights."""
    key = jax.random.fold_in(jax.random.key(seed, impl="rbg"), 1)
    return mla_moe.init(key, model_config(cfg), dtype=DTYPES[cfg["compute"]])


def input_shape(cfg: Dict, batch: int):
    return (batch, cfg["seq_len"])


def build_forward(cfg: Dict):
    """The jitted ``(params, ids) -> logits`` the program builds for this
    configuration."""
    from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward

    return build_forward(
        REGISTRY[cfg["exec_config"]], model_config(cfg), n_shards=1, compute=cfg["compute"]
    )


def routing_statistics(cfg: Dict, params, ids) -> Dict[str, float]:
    """The program's routing counters for one batch, filled into its registry."""
    return mla_moe.routing_statistics(params, ids, model_config(cfg))


def registry_summary():
    """The program's process-wide registry of counters and gauges (what the
    image family's adapter hands over under this name)."""
    from cuda_mpi_gpu_cluster_programming_tpu.observability.metrics import registry

    return registry()
