"""chip_smoke.py's phase functions, called small on the CPU mesh.

The script itself only runs on a TPU (through the chip tool); these keep
its phases from rotting between chip runs: the same functions, 63x63
inputs, 1 shard and 4, Pallas kernels interpreted. The within-tier bitwise
check runs at 227x227, the geometry tests/test_bit_exact.py holds it at
(the CPU backend is a last-ulp off bitwise at toy sizes).
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import chip_smoke  # noqa: E402

H = W = 63
BUCKETS = (1, 4)


def test_main_refuses_a_cpu_backend(capsys):
    assert chip_smoke.main() != 0
    captured = capsys.readouterr()
    assert "platform='cpu'" in captured.err
    assert '"ok"' not in captured.out  # no result line off the chip


def test_device_phase_refuses_a_device_outside_the_spec_table():
    from cuda_mpi_gpu_cluster_programming_tpu.observability.specs import (
        UnknownDeviceError,
    )

    with pytest.raises(UnknownDeviceError):
        chip_smoke.device_phase()


def test_phases_one_shard():
    assert "rel_err" in chip_smoke.reference_phase(H, W).detail
    chip_smoke.oneshot_phase("v1_jit", 1, H, W, batch=2, compute="fp32")
    # interpret mode: the lowering must NOT claim a Mosaic custom call
    low = chip_smoke.lowering_phase("v3_pallas", 1, H, W, batch=1)
    assert low.detail == "tpu_custom_call x0"
    served = chip_smoke.served_phase(
        "v1_jit", 1, H, W, BUCKETS, supervise=True, over_socket=True, n_requests=8
    )
    assert "ok=8" in served.detail and "cache_misses=0" in served.detail


def test_phases_four_shards():
    chip_smoke.oneshot_phase("v2.2_sharded", 4, H, W, batch=2, compute="fp32")
    chip_smoke.served_phase(
        "v2.2_sharded", 4, H, W, BUCKETS, supervise=False, over_socket=False,
        n_requests=8,
    )
    res = chip_smoke.sharded_phase("v2.2_sharded", 4, 227, 227, batch=1)
    assert res.detail.startswith("bitwise == v1_jit; 4 rows a device on 4 devices")


def test_sharded_phase_fails_when_a_device_holds_the_whole_image(monkeypatch):
    """The placement check is not satisfied by a gathered output: taps that
    digest the whole image on every device (a run that did not shard) fail."""
    import jax.numpy as jnp

    from cuda_mpi_gpu_cluster_programming_tpu.parallel import sharded
    from cuda_mpi_gpu_cluster_programming_tpu.resilience.sentinel import tree_digest

    real = sharded.build_sharded_forward

    def whole_image_taps(cfg, n, **kw):
        fwd = real(cfg, n, **kw)
        if not kw.get("with_digests"):
            return fwd  # build_forward's untapped product: untouched

        def run(params, x):
            out, taps = fwd(params, x)
            whole = jnp.full_like(taps["lrn2"], tree_digest(out))
            return out, {**taps, "lrn2": whole}

        return run

    monkeypatch.setattr(sharded, "build_sharded_forward", whole_image_taps)
    with pytest.raises(chip_smoke.SmokeFailure, match="digests .* at lrn2"):
        chip_smoke.sharded_phase("v2.2_sharded", 4, 227, 227, batch=1)


def test_a_failed_check_raises_out_of_the_phase(monkeypatch):
    """No phase catches its own failure: an output outside the budget is a
    SmokeFailure the caller sees."""
    import numpy as np

    real = chip_smoke._oracle_out
    monkeypatch.setattr(
        chip_smoke, "_oracle_out", lambda h, w, x: real(h, w, x) + np.float32(1.0)
    )
    with pytest.raises(chip_smoke.SmokeFailure, match="exceeds the fp32 budget"):
        chip_smoke.oneshot_phase("v1_jit", 1, H, W, batch=2, compute="fp32")
