"""The decoder-hybrid-decoder family's way into the program: from a
configuration file to the jitted forward, through the program's own entry
points (``configs.REGISTRY``, ``configs.build_forward``) and nothing lower. The
model module is imported here, at the top: a program that lacks it fails at
once, before any device is touched.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from cuda_mpi_gpu_cluster_programming_tpu.models import sambay

DTYPES = {"bf16": jnp.bfloat16, "fp32": jnp.float32}


def model_config(cfg: Dict) -> "sambay.SambayConfig":
    """The program's model-config object for a configuration file: every
    width under the publisher's key, the state-space sizes from ``assumed``."""
    assumed = cfg["assumed"]
    return sambay.SambayConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        sliding_window=cfg["sliding_window"],
        layer_norm_eps=cfg["layer_norm_eps"],
        mb_per_layer=cfg["mb_per_layer"],
        d_state=assumed["d_state"],
        d_conv=assumed["d_conv"],
        expand=assumed["expand"],
        dt_rank=assumed["dt_rank"],
        **cfg.get("program_tiles", {}),
    )


def make_params(cfg: Dict, seed: int):
    """The program's own seeded draw, stored in the configuration's compute
    type, a pair of layers at a time on the device, from a key of the ``rbg``
    kind (the chip's own bit generator: its draw compiles in seconds at any
    size); the same seed gives the same weights."""
    key = jax.random.fold_in(jax.random.key(seed, impl="rbg"), 1)
    return sambay.init(key, model_config(cfg), dtype=DTYPES[cfg["compute"]])


def input_shape(cfg: Dict, batch: int):
    return (batch, cfg["seq_len"])


def build_forward(cfg: Dict):
    """The jitted ``(params, ids) -> logits`` the program builds for this
    configuration."""
    from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward

    return build_forward(
        REGISTRY[cfg["exec_config"]], model_config(cfg), n_shards=1, compute=cfg["compute"]
    )


def layer_statistics(cfg: Dict, params, ids) -> Dict[str, float]:
    """The program's layer gauges for one batch, filled into its registry
    (a dense model routes nothing: there are no routing statistics)."""
    return sambay.layer_statistics(params, ids, model_config(cfg))


def registry_summary():
    """The program's process-wide registry of counters and gauges."""
    from cuda_mpi_gpu_cluster_programming_tpu.observability.metrics import registry

    return registry()
