"""Full AlexNet: Blocks 1-2 (reference scope) extended through conv5 + FC.

The reference restricts itself to Blocks 1-2 and tabulates the remaining
dims as an explicit extension task (README.md:19 "Full AlexNet ... is an
extension task"; dim table summary.md:29-45). This module is that extension:

    227x227x3 -Conv1(96,11,s4)->55x55x96 -Pool1(3,2)->27x27x96
      -Conv2(256,5,p2)->27x27x256 -Pool2(3,2)->13x13x256 -LRN2->13x13x256
      -Conv3(384,3,p1)->13x13x384 -Conv4(384,3,p1)->13x13x384
      -Conv5(256,3,p1)->13x13x256 -Pool5(3,2)->6x6x256
      -flatten 9216- FC6(4096) -FC7(4096) -FC8(num_classes) -> logits

Layer ordering through Blocks 1-2 keeps the *reference's* semantics (ReLU
after each conv, LRN only after Pool2 — classic AlexNet also normalises
after conv1, the reference does not), so the Blocks 1-2 prefix of this
model is bit-identical to ``forward_blocks12`` and shares its golden oracle.

ReLU follows every conv and FC6/FC7; dropout (classic p=0.5) is optional and
keyed — inference is deterministic with ``dropout_key=None``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import reference as ops
from ..ops import scopes
from .alexnet import BLOCKS12, Blocks12Config, ConvSpec, PoolSpec, forward_chain

Params = Dict[str, Dict[str, Any]]


@dataclasses.dataclass(frozen=True)
class AlexNetConfig:
    """Full-network hyperparameters; Blocks 1-2 defaults match the reference."""

    blocks12: Blocks12Config = BLOCKS12
    conv3: ConvSpec = ConvSpec(384, 3, 1, 1)
    conv4: ConvSpec = ConvSpec(384, 3, 1, 1)
    conv5: ConvSpec = ConvSpec(256, 3, 1, 1)
    pool5: PoolSpec = PoolSpec(3, 2)
    fc6: int = 4096
    fc7: int = 4096
    num_classes: int = 1000
    dropout_rate: float = 0.5

    def layer_chain(self) -> Tuple[Tuple[str, Any], ...]:
        """Spatial chain (shard-planner compatible: conv/pool/lrn specs)."""
        specs = (self.conv3, self.conv4, self.conv5, self.pool5)
        return self.blocks12.layer_chain() + tuple(
            zip(scopes.ALEXNET_TAIL_LAYERS, specs)
        )

    # Duck-type the fields the shard planner / sharded pipeline read.
    @property
    def in_height(self) -> int:
        return self.blocks12.in_height

    @property
    def in_width(self) -> int:
        return self.blocks12.in_width

    @property
    def in_channels(self) -> int:
        return self.blocks12.in_channels


ALEXNET = AlexNetConfig()


def spatial_output_shape(cfg: AlexNetConfig = ALEXNET) -> Tuple[int, int, int]:
    """(H, W, C) after pool5 — 6x6x256 for the defaults (summary.md:29-45)."""
    from .alexnet import layer_dims

    dims = cfg.in_height, cfg.in_width, cfg.in_channels
    for _name, _spec, _in, dims in layer_dims(cfg):
        pass
    return dims


def forward_spatial(params: Params, x: jax.Array, cfg: AlexNetConfig = ALEXNET) -> jax.Array:
    """Conv1..Pool5 feature extractor; ReLU after every conv."""
    return forward_chain(params, x, cfg.layer_chain())


def fc_head(
    params: Params,
    feats: jax.Array,
    cfg: AlexNetConfig = ALEXNET,
    dropout_key: Optional[jax.Array] = None,
) -> jax.Array:
    """flatten -> FC6(ReLU[,dropout]) -> FC7(ReLU[,dropout]) -> FC8 logits.

    The single FC-head definition shared by every tier (XLA, Pallas, and the
    sharded config's replicated head). FC layers are plain (N, in) x (in, out)
    matmuls — already the MXU's native shape; a hand kernel would add nothing
    over XLA here.
    """
    fc6, fc7, fc8 = scopes.FC_LAYERS
    keys = (
        jax.random.split(dropout_key, 2) if dropout_key is not None else (None, None)
    )
    with scopes.layer(fc6):
        x = feats.reshape(feats.shape[0], -1)
    for name, key in ((fc6, keys[0]), (fc7, keys[1])):
        with scopes.layer(name):
            x = ops.relu(x @ params[name]["w"] + params[name]["b"])
            if key is not None and cfg.dropout_rate > 0:
                keep = 1.0 - cfg.dropout_rate
                mask = jax.random.bernoulli(key, keep, x.shape)
                x = jnp.where(mask, x / keep, 0.0)
    with scopes.layer(fc8):
        return x @ params[fc8]["w"] + params[fc8]["b"]


def forward_alexnet(
    params: Params,
    x: jax.Array,
    cfg: AlexNetConfig = ALEXNET,
    dropout_key: Optional[jax.Array] = None,
) -> jax.Array:
    """Full forward pass -> (N, num_classes) logits."""
    return fc_head(params, forward_spatial(params, x, cfg), cfg, dropout_key)


def predict(params: Params, x: jax.Array, cfg: AlexNetConfig = ALEXNET) -> jax.Array:
    """Class probabilities (softmax over logits)."""
    return jax.nn.softmax(forward_alexnet(params, x, cfg), axis=-1)


def _param_shapes(cfg: AlexNetConfig) -> Dict[str, Tuple[Tuple[int, ...], Tuple[int, ...]]]:
    shapes: Dict[str, Tuple[Tuple[int, ...], Tuple[int, ...]]] = {}
    c_in = cfg.in_channels
    for name, spec in cfg.layer_chain():
        if isinstance(spec, ConvSpec):
            shapes[name] = (
                (spec.filter_size, spec.filter_size, c_in, spec.out_channels),
                (spec.out_channels,),
            )
            c_in = spec.out_channels
    h, w, c = spatial_output_shape(cfg)
    flat = h * w * c
    shapes["fc6"] = ((flat, cfg.fc6), (cfg.fc6,))
    shapes["fc7"] = ((cfg.fc6, cfg.fc7), (cfg.fc7,))
    shapes["fc8"] = ((cfg.fc7, cfg.num_classes), (cfg.num_classes,))
    return shapes


def init_full_deterministic(cfg: AlexNetConfig = ALEXNET, dtype=jnp.float32) -> Params:
    """weights=0.01, biases=0.0 — the cross-tier comparison init extended to
    the full net (2.2_scatter_halo/src/main.cpp:37-47 semantics)."""
    return {
        name: {"w": jnp.full(ws, 0.01, dtype), "b": jnp.zeros(bs, dtype)}
        for name, (ws, bs) in _param_shapes(cfg).items()
    }


def init_full_random(key: jax.Array, cfg: AlexNetConfig = ALEXNET, dtype=jnp.float32) -> Params:
    """He-scaled normal weights (proper for depth — uniform [0,1) explodes
    through 8 layers), bias 0.1 as in V1 (v1_serial/src/alexnet_serial.cpp:51-57)."""
    shapes = _param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    params: Params = {}
    for k, (name, (ws, bs)) in zip(keys, shapes.items()):
        fan_in = math.prod(ws[:-1])
        params[name] = {
            "w": jax.random.normal(k, ws, dtype) * (2.0 / fan_in) ** 0.5,
            "b": jnp.full(bs, 0.1, dtype),
        }
    return params
