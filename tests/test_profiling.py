"""Profiling subsystem: breakdown correctness, and the production forward as
the annotated one (it names its layers itself; there is no second copy)."""

import glob
import os

import jax
import numpy as np

from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import BLOCKS12, forward_blocks12
from cuda_mpi_gpu_cluster_programming_tpu.models.init import (
    deterministic_input,
    init_params_deterministic,
)
from cuda_mpi_gpu_cluster_programming_tpu.utils import profiling


def test_annotated_forward_matches_plain():
    """The production forward carries a scope per layer, in order, and the
    scopes change nothing it computes: it equals the bare ops in sequence."""
    from cuda_mpi_gpu_cluster_programming_tpu.ops import reference as ops
    from cuda_mpi_gpu_cluster_programming_tpu.ops import scopes

    def plain(p, x, c=BLOCKS12):
        x = ops.relu(ops.conv2d(x, p["conv1"]["w"], p["conv1"]["b"], stride=c.conv1.stride, padding=c.conv1.padding))
        x = ops.maxpool(x, window=c.pool1.window, stride=c.pool1.stride)
        x = ops.relu(ops.conv2d(x, p["conv2"]["w"], p["conv2"]["b"], stride=c.conv2.stride, padding=c.conv2.padding))
        x = ops.maxpool(x, window=c.pool2.window, stride=c.pool2.stride)
        n = c.lrn2
        return ops.lrn(x, size=n.size, alpha=n.alpha, beta=n.beta, k=n.k, alpha_over_size=n.alpha_over_size)

    params = init_params_deterministic()
    x = deterministic_input(batch=1)
    fwd = jax.jit(forward_blocks12)
    np.testing.assert_array_equal(
        np.asarray(fwd(params, x)), np.asarray(jax.jit(plain)(params, x))
    )
    text = fwd.lower(params, x).compile().as_text()
    at = [text.find(f"/{name}/") for name in scopes.BLOCKS12_LAYERS]
    assert all(i >= 0 for i in at), dict(zip(scopes.BLOCKS12_LAYERS, at))
    assert "/conv1/" not in jax.jit(plain).lower(params, x).compile().as_text()


def test_stage_fns_compose_to_forward():
    params = init_params_deterministic()
    x = deterministic_input(batch=1)
    cur = x
    for _, fn in profiling.stage_fns(BLOCKS12):
        cur = fn(params, cur)
    np.testing.assert_array_equal(
        np.asarray(cur), np.asarray(forward_blocks12(params, x))
    )


def test_layer_breakdown_rows():
    params = init_params_deterministic()
    x = deterministic_input(batch=1)
    rows = profiling.layer_breakdown(params, x, repeats=1, warmup=1)
    names = [r[0] for r in rows]
    assert names == ["conv1", "relu1", "pool1", "conv2", "relu2", "pool2", "lrn2"]
    assert all(ms >= 0.0 for _, ms, _ in rows)
    assert rows[-1][2] == (1, 13, 13, 256)
    assert rows[0][2] == (1, 55, 55, 96)


def test_trace_writes_files(tmp_path):
    params = init_params_deterministic()
    x = deterministic_input(batch=1)
    d = str(tmp_path / "trace")
    with profiling.trace(d):
        jax.block_until_ready(jax.jit(forward_blocks12)(params, x))
    assert glob.glob(os.path.join(d, "**", "*"), recursive=True)


def test_stage_fns_pallas_tier_matches_model():
    """The pallas-tier stage chain composes to forward_blocks12_pallas
    exactly (5 fused stages), so --breakdown attributes cost to the
    kernels actually running under a v3_pallas config."""
    import numpy as np

    from cuda_mpi_gpu_cluster_programming_tpu.models import (
        deterministic_input,
        init_params_deterministic,
    )
    from cuda_mpi_gpu_cluster_programming_tpu.ops.pallas_model import (
        forward_blocks12_pallas,
    )
    from cuda_mpi_gpu_cluster_programming_tpu.utils.profiling import stage_fns

    params = init_params_deterministic()
    x = deterministic_input(batch=1)
    stages = stage_fns(tier="pallas")
    assert [n for n, _ in stages] == ["conv1+relu", "pool1", "conv2+relu", "pool2", "lrn2"]
    cur = x
    for _, fn in stages:
        cur = fn(params, cur)
    np.testing.assert_array_equal(
        np.asarray(cur), np.asarray(forward_blocks12_pallas(params, x))
    )


def test_stage_fns_rejects_unknown_tier():
    import pytest

    from cuda_mpi_gpu_cluster_programming_tpu.utils.profiling import stage_fns

    with pytest.raises(ValueError, match="tier"):
        stage_fns(tier="cuda")


def test_run_cli_breakdown_uses_config_tier(capsys):
    """--breakdown on a pallas config prints the 5 fused kernel stages;
    on an XLA-op config the 7-stage reference chain — the tier the user
    selected is the tier that gets attributed."""
    from cuda_mpi_gpu_cluster_programming_tpu.run import main

    rc = main(["--config", "v3_pallas", "--batch", "1", "--breakdown",
               "--repeats", "1", "--warmup", "1"])
    out = capsys.readouterr().out
    assert rc == 0
    layers = [l for l in out.splitlines() if l.startswith("Layer ")]
    assert len(layers) == 5 and layers[0].startswith("Layer conv1+relu")

    rc = main(["--config", "v1_jit", "--batch", "1", "--breakdown",
               "--repeats", "1", "--warmup", "1"])
    out = capsys.readouterr().out
    assert rc == 0
    layers = [l for l in out.splitlines() if l.startswith("Layer ")]
    assert len(layers) == 7 and layers[0].startswith("Layer conv1")
