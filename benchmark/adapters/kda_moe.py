"""The hybrid linear-attention mixture-of-experts family's way into the
program: from a configuration file to the jitted forward, through the
program's own entry points (``configs.REGISTRY``, ``configs.build_forward``)
and nothing lower. The model module is imported here, at the top: a program
that lacks it fails at once, before any device is touched.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from cuda_mpi_gpu_cluster_programming_tpu.models import kda_moe

DTYPES = {"bf16": jnp.bfloat16, "fp32": jnp.float32}


def model_config(cfg: Dict) -> "kda_moe.KdaMoeConfig":
    """The program's model-config object for a configuration file: every
    width under the publisher's key, the share from the file's cuts."""
    linear = cfg["linear_attn_config"]
    return kda_moe.KdaMoeConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        linear_attn_num_heads=linear["num_heads"],
        linear_attn_head_dim=linear["head_dim"],
        short_conv_kernel_size=linear["short_conv_kernel_size"],
        use_rope=cfg["use_rope"],
        use_gqa_gate=cfg["use_gqa_gate"],
        kda_use_full_proj=cfg["kda_use_full_proj"],
        kda_allow_neg_eigval=cfg["kda_allow_neg_eigval"],
        rms_norm_eps=cfg["rms_norm_eps"],
        num_layers=cfg["num_layers"],
        gqa_layers=tuple(cfg["gqa_layers"]),
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
    type, layer by layer on the device, from a key of the ``rbg`` kind (the
    chip's own bit generator: its draw compiles in seconds at any size), and
    then every router's selection bias balanced on one seeded batch of ids
    (``kda_moe.balance_routers``; the configuration's ``assumed`` says why);
    the same seed gives the same weights."""
    model_cfg = model_config(cfg)
    key = jax.random.key(seed, impl="rbg")
    params = kda_moe.init(jax.random.fold_in(key, 1), model_cfg, dtype=DTYPES[cfg["compute"]])
    ids = jax.random.randint(jax.random.fold_in(key, 3), (2, cfg["seq_len"]), 0, cfg["vocab_size"], jnp.int32)
    return kda_moe.balance_routers(params, ids, model_cfg)


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
    """The program's routing and decay gauges for one batch, filled into its
    registry."""
    return kda_moe.layer_statistics(params, ids, model_config(cfg))


def registry_summary():
    """The program's process-wide registry of counters and gauges."""
    from cuda_mpi_gpu_cluster_programming_tpu.observability.metrics import registry

    return registry()
