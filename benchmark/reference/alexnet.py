"""The plain reference of the AlexNet family: float32 ``jax.numpy`` at
``precision="highest"``, written from the layer equations, with no kernel,
no ``lax.conv``, no ``reduce_window`` and nothing imported from the program
(independent of its ``ops/``). Convolution is a sum over filter taps of
strided slices times the tap's (C, K) matrix; pooling a maximum over
strided slices; LRN a sum over shifted channel slices.

Departures from the paper, as the configuration file lists them: ungrouped
convolutions, one LRN (after pool2, alpha not divided by n), 227x227 input.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def _conv(x, w, b, stride: int, padding: int):
    f = w.shape[0]
    if padding:
        x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    ho = (x.shape[1] - f) // stride + 1
    wo = (x.shape[2] - f) // stride + 1
    out = jnp.zeros((x.shape[0], ho, wo, w.shape[3]), jnp.float32)
    for fy in range(f):
        for fx in range(f):
            tap = x[:, fy : fy + stride * (ho - 1) + 1 : stride,
                    fx : fx + stride * (wo - 1) + 1 : stride, :]
            out = out + jnp.einsum("nhwc,ck->nhwk", tap, w[fy, fx], precision="highest")
    return out + b


def _pool(x, window: int, stride: int):
    ho = (x.shape[1] - window) // stride + 1
    wo = (x.shape[2] - window) // stride + 1
    out = None
    for dy in range(window):
        for dx in range(window):
            tap = x[:, dy : dy + stride * (ho - 1) + 1 : stride,
                    dx : dx + stride * (wo - 1) + 1 : stride, :]
            out = tap if out is None else jnp.maximum(out, tap)
    return out


def _lrn(x, size: int, alpha: float, beta: float, k: float):
    half, c = size // 2, x.shape[3]
    sq = jnp.pad(x * x, ((0, 0), (0, 0), (0, 0), (half, half)))
    ssum = sum(sq[..., j : j + c] for j in range(size))
    return x / (k + alpha * ssum) ** beta


def forward(cfg: Dict, params: Dict, x):
    """Reference forward of ``cfg`` (a configuration file's content) on a
    float32 NHWC batch with float32 ``params`` in the program's tree."""
    x = jnp.asarray(x, jnp.float32)
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    for layer in cfg["layers"]:
        if layer["kind"] == "conv":
            e = p[layer["name"]]
            x = jnp.maximum(_conv(x, e["w"], e["b"], layer["stride"], layer["padding"]), 0.0)
        elif layer["kind"] == "pool":
            x = _pool(x, layer["window"], layer["stride"])
        elif layer["kind"] == "lrn":
            x = _lrn(x, layer["size"], layer["alpha"], layer["beta"], layer["k"])
        else:
            raise ValueError(f"unknown layer kind {layer['kind']!r}")
    n_fc = len(cfg.get("fc") or [])
    if n_fc:
        x = x.reshape(x.shape[0], -1)
        for i in range(n_fc):
            e = p[f"fc{6 + i}"]
            x = jnp.matmul(x, e["w"], precision="highest") + e["b"]
            if i < n_fc - 1:
                x = jnp.maximum(x, 0.0)
    return x
