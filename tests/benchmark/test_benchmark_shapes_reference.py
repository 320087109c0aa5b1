"""The shape functions' counts, and the plain reference against the
loop-nest oracle and against the program's float32 forward at a small size."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

import oracle  # noqa: E402  (tests/oracle.py)
from benchmark import harness  # noqa: E402
from benchmark.reference import alexnet as reference  # noqa: E402
from benchmark.shapes import alexnet as shapes  # noqa: E402

B12 = json.loads((REPO / "benchmark" / "configs" / "alexnet_blocks12.json").read_text())
FULL = json.loads((REPO / "benchmark" / "configs" / "alexnet_full.json").read_text())
adapter = harness.load_plugin("adapters", "alexnet")


def test_blocks12_matmul_flops_per_image():
    assert shapes.matmul_flops_per_image(B12) == 1_106_625_600


def test_full_alexnet_matmul_flops_and_parameters():
    assert shapes.matmul_flops_per_image(FULL) == 2_270_512_192
    assert shapes.param_count(FULL) == 62_378_344
    assert shapes.param_count(B12) == 34_944 + 614_656


def test_output_shapes():
    assert shapes.output_shape(B12) == (13, 13, 256)
    assert shapes.spatial_out(FULL) == (6, 6, 256)
    assert shapes.output_shape(FULL) == (1000,)
    assert shapes.fc_dims(FULL) == [(9216, 4096), (4096, 4096), (4096, 1000)]


def test_min_bytes_counts_input_parameters_and_output_once():
    want = 128 * (227 * 227 * 3 * 4 + 13 * 13 * 256 * 4) + (34_944 + 614_656) * 4
    assert shapes.min_bytes_per_step(B12, 128) == want


def test_shape_functions_agree_with_the_programs_ledger():
    from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import matmul_flops_per_image

    assert shapes.matmul_flops_per_image(B12) == matmul_flops_per_image()


def _small(cfg, size):
    return dict(cfg, in_height=size, in_width=size)


def test_reference_agrees_with_the_loop_nest_oracle():
    cfg = _small(B12, 51)  # 51 -> 11 -> 5 -> 5 -> 2
    params = jax.tree.map(np.asarray, adapter.make_params(cfg, seed=3))
    x = np.random.default_rng(0).random((2, 51, 51, 3), np.float32)
    got = np.asarray(reference.forward(cfg, params, x))
    for n in range(2):
        y = x[n].astype(np.float64)
        for layer in cfg["layers"]:
            if layer["kind"] == "conv":
                e = params[layer["name"]]
                y = np.maximum(
                    oracle.conv2d_np(y, e["w"], e["b"], layer["stride"], layer["padding"]), 0
                )
            elif layer["kind"] == "pool":
                y = oracle.maxpool_np(y, layer["window"], layer["stride"])
            else:
                y = oracle.lrn_np(y, layer["size"], layer["alpha"], layer["beta"], layer["k"])
        assert harness.relative_error(got[n], y) < 1e-5


@pytest.mark.parametrize("cfg,size", [(B12, 63), (FULL, 99)], ids=["blocks12", "full"])
def test_reference_agrees_with_the_programs_float32_forward(cfg, size):
    cfg = dict(_small(cfg, size), compute="fp32")
    params = adapter.make_params(cfg, seed=1)
    x = jax.random.uniform(jax.random.key(4), adapter.input_shape(cfg, 2))
    got = adapter.build_forward(cfg)(params, x)
    want = reference.forward(cfg, params, x)
    assert got.shape == (2,) + shapes.output_shape(cfg)
    assert harness.relative_error(got, want) < 1e-5


def test_bf16_forward_is_inside_the_tolerance_and_fp32_far_inside():
    cfg = _small(B12, 63)
    params = adapter.make_params(cfg, seed=1)
    x = jax.random.uniform(jax.random.key(4), adapter.input_shape(cfg, 2))
    want = reference.forward(cfg, params, x)
    err = harness.relative_error(adapter.build_forward(cfg)(params, x), want)
    assert 1e-5 < err < cfg["tolerance"]["rel_max"]  # bf16 is visible, and inside


def test_weights_come_from_the_seed():
    a = adapter.make_params(B12, seed=0)["conv1"]["w"]
    b = adapter.make_params(B12, seed=0)["conv1"]["w"]
    c = adapter.make_params(B12, seed=1)["conv1"]["w"]
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert abs(float(a.mean())) < 0.01 < float(a.std())


@pytest.mark.parametrize(
    "got,want,expect",
    [
        ([1.0, 2.0], [1.0, 2.0], 0.0),
        ([1.0, 2.2], [1.0, 2.0], 0.1),
        ([1.0, float("nan")], [1.0, 2.0], float("inf")),
        ([1.0], [1.0, 2.0], float("inf")),
        ([0.0], [0.0], float("inf")),
    ],
)
def test_relative_error(got, want, expect):
    assert harness.relative_error(np.array(got), np.array(want)) == pytest.approx(expect)


class _Trace:
    def step_durations_ms(self):
        return [1.2002, 1.2002, 1.2003]


class _Ctx:
    """What ``kernels.forward_roofline`` reads, with a step time as the v5e
    showed it (PR 22) in place of a trace."""

    def __init__(self, chips):
        self.trace, self.config, self.shapes = _Trace(), B12, shapes
        self.peaks = harness.peak_row("TPU v5 lite")
        self.counters = {"offline.batch": 128}
        self.devices = [object()] * chips
        self.lines = []

    def log(self, msg):
        self.lines.append(msg)


def test_forward_roofline_is_least_time_by_the_peaks_over_step_time():
    read = harness.load_plugin("layer_metrics", "kernels.forward_roofline").read
    one = _Ctx(chips=1)
    # 128 x 1,106,625,600 FLOP at 197 TFLOP/s = 0.7190 ms; the bytes need 0.127 ms
    assert read(one) == pytest.approx(100 * 0.71903 / 1.2002, rel=1e-4)
    assert "compute-bound" in one.lines[0]
    assert read(_Ctx(chips=4)) == pytest.approx(read(_Ctx(chips=1)) / 4)
    no_batch = _Ctx(chips=1)
    no_batch.counters = {}
    assert read(no_batch) is None  # a reader that finds nothing to read returns nothing
