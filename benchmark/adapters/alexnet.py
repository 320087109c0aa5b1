"""The AlexNet family's way into the program: from a configuration file to
the jitted forward or the server, through the program's own entry points
(``configs.build_forward``, ``serving.InferenceServer``) and nothing lower.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.shapes import alexnet as shapes

PKG = "cuda_mpi_gpu_cluster_programming_tpu"


def model_config(cfg: Dict):
    """The program's model-config object for a configuration file."""
    from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import (
        Blocks12Config, ConvSpec, LrnSpec, PoolSpec,
    )

    def spec(layer):
        if layer["kind"] == "conv":
            return ConvSpec(layer["out_channels"], layer["filter_size"],
                            layer["stride"], layer["padding"])
        if layer["kind"] == "pool":
            return PoolSpec(layer["window"], layer["stride"])
        return LrnSpec(layer["size"], layer["alpha"], layer["beta"], layer["k"])

    by_name = {layer["name"]: spec(layer) for layer in cfg["layers"]}
    blocks12 = Blocks12Config(
        in_height=cfg["in_height"], in_width=cfg["in_width"],
        in_channels=cfg["in_channels"],
        **{n: by_name[n] for n in ("conv1", "pool1", "conv2", "pool2", "lrn2")},
    )
    if cfg["model"] == "blocks12":
        return blocks12
    from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet_full import AlexNetConfig

    fc6, fc7, num_classes = cfg["fc"]
    return AlexNetConfig(
        blocks12=blocks12, fc6=fc6, fc7=fc7, num_classes=num_classes,
        **{n: by_name[n] for n in ("conv3", "conv4", "conv5", "pool5")},
    )


def make_params(cfg: Dict, seed: int):
    """He-scaled normal weights and bias 0.1 in the program's parameter
    tree, float32 (the type the program's forward takes; it casts to its
    compute type itself), drawn on the device in one jitted call."""
    shp = shapes.param_shapes(cfg)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(shp))
        return {
            name: {
                "w": jax.random.normal(k, ws, jnp.float32)
                * (2.0 / math.prod(ws[:-1])) ** 0.5,
                "b": jnp.full(bs, 0.1, jnp.float32),
            }
            for k, (name, (ws, bs)) in zip(keys, shp.items())
        }

    return draw(jax.random.fold_in(jax.random.key(seed), 1))


def input_shape(cfg: Dict, batch: int):
    return (batch, cfg["in_height"], cfg["in_width"], cfg["in_channels"])


def build_forward(cfg: Dict):
    """The jitted ``(params, x) -> out`` the program builds for this
    configuration."""
    from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward

    return build_forward(
        REGISTRY[cfg["exec_config"]], model_config(cfg),
        n_shards=cfg["n_shards"], compute=cfg["compute"],
    )


def build_server(cfg: Dict, params, server_opts: Dict):
    """An ``InferenceServer`` over this configuration, in process: no
    journal, no supervisor, no SLO policy, no deadlines."""
    from cuda_mpi_gpu_cluster_programming_tpu.serving.server import (
        InferenceServer, ServeConfig,
    )

    return InferenceServer(
        ServeConfig(
            config=cfg["exec_config"], n_shards=cfg["n_shards"],
            compute=cfg["compute"], max_batch=int(server_opts["max_batch"]),
            model_cfg=model_config(cfg),
        ),
        params=params,
    )


def registry_summary() -> Dict:
    """The program's process-wide counters and histograms, as it sums them."""
    from cuda_mpi_gpu_cluster_programming_tpu.observability.metrics import registry

    return registry()
