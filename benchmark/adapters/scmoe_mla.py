"""The shortcut-connected mixture-of-experts family's way into the program:
from a configuration file to the jitted forward, through the program's own
entry points (``configs.REGISTRY``, ``configs.build_forward``) and nothing
lower. The model module is imported here, at the top: a program that lacks it
fails at once, before any device is touched.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from cuda_mpi_gpu_cluster_programming_tpu.models import scmoe_mla

DTYPES = {"bf16": jnp.bfloat16, "fp32": jnp.float32}


def model_config(cfg: Dict) -> "scmoe_mla.ScmoeMlaConfig":
    """The program's model-config object for a configuration file: every
    width under the publisher's key, the share from the file's cuts."""
    return scmoe_mla.ScmoeMlaConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        mla_scale_q_lora=cfg["mla_scale_q_lora"],
        mla_scale_kv_lora=cfg["mla_scale_kv_lora"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        max_position_embeddings=cfg["max_position_embeddings"],
        num_layers=cfg["num_layers"],
        ffn_hidden_size=cfg["ffn_hidden_size"],
        expert_ffn_hidden_size=cfg["expert_ffn_hidden_size"],
        n_routed_experts=cfg["published"]["n_routed_experts"],
        zero_expert_num=cfg["zero_expert_num"],
        moe_topk=cfg["moe_topk"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        experts_held=cfg["n_routed_experts"],
        experts_first=cfg["experts_first"],
        **cfg.get("program_tiles", {}),
    )


def make_params(cfg: Dict, seed: int):
    """The program's own seeded draw, stored in the configuration's compute
    type, the layers one at a time on the device, from a key of the ``rbg``
    kind (the chip's own bit generator: its draw compiles in seconds at any
    size); the same seed gives the same weights."""
    key = jax.random.fold_in(jax.random.key(seed, impl="rbg"), 1)
    return scmoe_mla.init(key, model_config(cfg), dtype=DTYPES[cfg["compute"]])


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
    """The program's routing gauges for one batch, filled into its registry."""
    return scmoe_mla.routing_statistics(params, ids, model_config(cfg))


def registry_summary():
    """The program's process-wide registry of counters and gauges."""
    from cuda_mpi_gpu_cluster_programming_tpu.observability.metrics import registry

    return registry()
