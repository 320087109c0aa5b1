"""Operations and bytes of the AlexNet family, from a configuration file's
``layers`` and ``fc`` alone. The roofline's numerators: kept with the
benchmark so that no PR that claims a gain can change them. The FLOP ledger
is copied in method from the program's ``models/alexnet.py`` ``stage_flops``
(2 x MACs per convolution); 1,106,625,600 per Blocks 1-2 image.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Tuple


def layer_dims(cfg: Dict) -> Iterator[Tuple[Dict, Tuple[int, int, int], Tuple[int, int, int]]]:
    """``(layer, (H, W, C) in, (H, W, C) out)`` down the spatial chain."""
    h, w, c = cfg["in_height"], cfg["in_width"], cfg["in_channels"]
    for layer in cfg["layers"]:
        before = (h, w, c)
        if layer["kind"] == "conv":
            f, s, p = layer["filter_size"], layer["stride"], layer["padding"]
            h, w, c = (h - f + 2 * p) // s + 1, (w - f + 2 * p) // s + 1, layer["out_channels"]
        elif layer["kind"] == "pool":
            h = (h - layer["window"]) // layer["stride"] + 1
            w = (w - layer["window"]) // layer["stride"] + 1
        yield layer, before, (h, w, c)


def spatial_out(cfg: Dict) -> Tuple[int, int, int]:
    dims = cfg["in_height"], cfg["in_width"], cfg["in_channels"]
    for _layer, _before, dims in layer_dims(cfg):
        pass
    return dims


def fc_dims(cfg: Dict) -> List[Tuple[int, int]]:
    """``(in, out)`` of each fully connected layer, if the model has any."""
    h, w, c = spatial_out(cfg)
    n_in, out = h * w * c, []
    for n_out in cfg.get("fc") or []:
        out.append((n_in, n_out))
        n_in = n_out
    return out


def output_shape(cfg: Dict) -> Tuple[int, ...]:
    """Per-image output: the logits where there is an FC head, else (H, W, C)."""
    fcs = fc_dims(cfg)
    return (fcs[-1][1],) if fcs else spatial_out(cfg)


def param_shapes(cfg: Dict) -> Dict[str, Tuple[Tuple[int, ...], Tuple[int, ...]]]:
    """``{layer: (weight shape, bias shape)}``: HWIO convolutions, (in, out) FC."""
    shapes = {}
    for layer, (_h, _w, c_in), _after in layer_dims(cfg):
        if layer["kind"] == "conv":
            f, k = layer["filter_size"], layer["out_channels"]
            shapes[layer["name"]] = ((f, f, c_in, k), (k,))
    for i, (n_in, n_out) in enumerate(fc_dims(cfg)):
        shapes[f"fc{6 + i}"] = ((n_in, n_out), (n_out,))
    return shapes


def matmul_flops_per_image(cfg: Dict) -> int:
    """MXU work only: 2 x multiply-accumulates of every convolution and FC."""
    flops = 0
    for layer, (_h, _w, c_in), (h, w, c_out) in layer_dims(cfg):
        if layer["kind"] == "conv":
            flops += 2 * h * w * c_out * layer["filter_size"] ** 2 * c_in
    return flops + sum(2 * n_in * n_out for n_in, n_out in fc_dims(cfg))


def param_count(cfg: Dict) -> int:
    total = 0
    for w_shape, b_shape in param_shapes(cfg).values():
        n = 1
        for d in w_shape:
            n *= d
        total += n + b_shape[0]
    return total


def min_bytes_per_step(cfg: Dict, batch: int, input_bytes: int = 4,
                       param_bytes: int = 4, output_bytes: int = 4) -> int:
    """The bytes one forward step cannot avoid moving: the batch read as
    the program is handed it (float32), every parameter read once as it is
    stored (float32), the output written (float32). Intermediate
    activations are not counted: a perfect fusion keeps them on the chip."""
    x = cfg["in_height"] * cfg["in_width"] * cfg["in_channels"]
    y = 1
    for d in output_shape(cfg):
        y *= d
    return batch * (x * input_bytes + y * output_bytes) + param_count(cfg) * param_bytes
