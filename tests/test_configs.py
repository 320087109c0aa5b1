import jax
import numpy as np
import pytest

from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward
from cuda_mpi_gpu_cluster_programming_tpu.models import (
    deterministic_input,
    forward_blocks12,
    init_params_deterministic,
)


def test_registry_covers_reference_stages():
    names = {c.version_name for c in REGISTRY.values()}
    # the canonical analysis names of the reference's five stages + V5 must
    # all be present (the V6 full-AlexNet family extends the set).
    assert names >= {
        "V1 Serial",
        "V2.1 BroadcastAll",
        "V2.2 ScatterHalo",
        "V3 CUDA",
        "V4 MPI+CUDA",
        "V5 MPI+CUDA-Aware",
    }


def test_v1_jit_matches_direct_forward():
    params = init_params_deterministic()
    x = deterministic_input(batch=2)
    fwd = build_forward(REGISTRY["v1_jit"])
    np.testing.assert_array_equal(fwd(params, x), jax.jit(forward_blocks12)(params, x))


@pytest.mark.parametrize("n", [1, 2, 4, 8])
def test_replicated_matches_single(n):
    """V2.1 semantics: every device computes the full pass; result equals V1."""
    params = init_params_deterministic()
    x = deterministic_input(batch=1)
    single = build_forward(REGISTRY["v1_jit"])(params, x)
    repl = build_forward(REGISTRY["v2.1_replicated"], n_shards=n)(params, x)
    np.testing.assert_allclose(np.asarray(repl), np.asarray(single), rtol=1e-6)


# The six environment names that chose a Pallas lowering before PR 42, each
# set to a value other than its old default.
OLD_LOWERING_ENV = {
    "TPU_FRAMEWORK_CONV": "taps", "TPU_FRAMEWORK_POOL": "phases",
    "TPU_FRAMEWORK_ROWBLOCK": "8", "TPU_FRAMEWORK_KBLOCK": "128",
    "TPU_FRAMEWORK_FUSE": "hpool", "TPU_FRAMEWORK_CHAIN": "pad128",
}


@pytest.mark.parametrize("key, n_shards", [
    ("v3_pallas", 1), ("v6_full_pallas", 1), ("v5_collective", 2),
])
def test_build_forward_reads_no_environment(monkeypatch, key, n_shards):
    """A Pallas lowering is chosen in code (the default or a tune plan), so
    the program build_forward lowers is the same with the old environment
    names set as without them."""
    from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import Blocks12Config
    from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet_full import (
        AlexNetConfig, init_full_deterministic,
    )

    if REGISTRY[key].model == "alexnet_full":
        cfg = AlexNetConfig(blocks12=Blocks12Config(in_height=99, in_width=99),
                            fc6=32, fc7=32, num_classes=10)
        params = init_full_deterministic(cfg)
    else:
        cfg = Blocks12Config(in_height=63, in_width=63)
        params = init_params_deterministic(cfg)
    x = jax.ShapeDtypeStruct((2, cfg.in_height, cfg.in_width, 3), jax.numpy.float32)

    def lowered():
        fwd = build_forward(REGISTRY[key], cfg, n_shards=n_shards)
        return fwd.lower(params, x).as_text()

    for name in OLD_LOWERING_ENV:
        monkeypatch.delenv(name, raising=False)
    clean = lowered()
    for name, value in OLD_LOWERING_ENV.items():
        monkeypatch.setenv(name, value)
    assert lowered() == clean
