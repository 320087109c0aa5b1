"""The compressed-convolutional-attention mixture-of-experts family's way into
the program: from a configuration file to the jitted forward, through the
program's own entry points (``configs.REGISTRY``, ``configs.build_forward``)
and nothing lower. The model module is imported here, at the top: a program
that lacks it fails at once, before any device is touched.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from cuda_mpi_gpu_cluster_programming_tpu.models import cca_moe

DTYPES = {"bf16": jnp.bfloat16, "fp32": jnp.float32}
# Sequences of the one seeded batch the routers are balanced on. Balanced on
# one sequence, an output's share of the traffic is its 1/17 give or take the
# 6% of that sample (241 tokens a layer +- 15), on top of the +- 15 of every
# batch's own draw: loads of 174 to 322 were seen, past the tile of 320 rows an
# expert's rows are padded to. Eight sequences hold the share to 2%.
BALANCE_SEQUENCES = 8


def model_config(cfg: Dict) -> "cca_moe.CcaMoeConfig":
    """The program's model-config object for a configuration file: every
    width under the publisher's key, the share from the file's cuts."""
    rope = cfg["rope_parameters"]["hybrid"]
    return cca_moe.CcaMoeConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        cca_time0=cfg["cca_time0"],
        cca_time1=cfg["cca_time1"],
        partial_rotary_factor=rope["partial_rotary_factor"],
        rope_theta=float(rope["rope_theta"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        num_layers=cfg["num_layers"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["published"]["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        router_hidden_size=cfg["router_hidden_size"],
        experts_held=cfg["num_experts"],
        experts_first=cfg["experts_first"],
        **cfg.get("program_tiles", {}),
    )


def make_params(cfg: Dict, seed: int):
    """The program's own seeded draw, stored in the configuration's compute
    type, the layers one at a time on the device, from a key of the ``rbg``
    kind (the chip's own bit generator: its draw compiles in seconds at any
    size), and then every router's selection bias balanced over all its
    outputs, the skip among them, on one seeded batch of ``BALANCE_SEQUENCES``
    sequences of ids (``cca_moe.balance_routers``; the configuration's
    ``assumed`` says why); the same seed gives the same weights."""
    model_cfg = model_config(cfg)
    key = jax.random.key(seed, impl="rbg")
    params = cca_moe.init(jax.random.fold_in(key, 1), model_cfg, dtype=DTYPES[cfg["compute"]])
    shape = (BALANCE_SEQUENCES, cfg["seq_len"])
    ids = jax.random.randint(jax.random.fold_in(key, 3), shape, 0, cfg["vocab_size"], jnp.int32)
    return cca_moe.balance_routers(params, ids, model_cfg)


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
    """The program's routing and router-state gauges for one batch, filled
    into its registry."""
    return cca_moe.layer_statistics(params, ids, model_config(cfg))


def registry_summary():
    """The program's process-wide registry of counters and gauges."""
    from cuda_mpi_gpu_cluster_programming_tpu.observability.metrics import registry

    return registry()
