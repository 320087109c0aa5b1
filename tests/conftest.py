"""Test config: run everything on a virtual 8-device CPU mesh.

This is the TPU-world analogue of the reference's ``mpirun --oversubscribe
-np N`` localhost testing (scripts/common_test_utils.sh:274-276): N virtual
XLA host devices stand in for N TPU cores, so sharded paths are exercised
without a pod.

``jax.config.update`` pins the platform whatever JAX_PLATFORMS says (it
wins as long as no backend has been initialized). ``XLA_FLAGS`` is read at
backend-init time, so setting it here works.
"""

import json
import os

import pytest

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

assert jax.device_count() == 8, (
    f"tests require the virtual 8-device CPU mesh, got {jax.devices()}"
)


def pytest_configure(config):
    # Tier-1 runs with -m 'not slow' (ROADMAP); register the marker so the
    # opt-in heavyweight tests (real-timing tuner CLI sweep) don't warn.
    config.addinivalue_line(
        "markers", "slow: heavyweight test excluded from the tier-1 sweep"
    )


_MATMUL_FLOPS = 1106625600  # models.alexnet.matmul_flops_per_image()
_PEAK = 197.0


def _bench_view(img_s: float, compute: str, batch: int, stale: bool) -> dict:
    row = {
        "unit": "img/s",
        "mfu": round(img_s * _MATMUL_FLOPS / (_PEAK * 1e12), 4),
        "compute": compute,
        "per_pass_ms": round(batch / img_s * 1e3, 4),
    }
    row["stale_value" if stale else "value"] = img_s
    return row


@pytest.fixture()
def echo_trail(tmp_path):
    """A synthetic five-round bench trail with the structure the
    regression gate and the roofline CLI must handle (rows bench.py wrote
    before PR 21): r01 crashed, r02 measured nothing, r03 carries a
    first-appearance ``last_good``, r04 re-reports r03's number (the echo),
    r05 carries a different ``last_good`` with a bf16 sub-object. Returns
    the sorted paths."""

    def error_row(batch: int) -> dict:
        return {
            "metric": "alexnet_blocks12_images_per_sec", "value": 0.0,
            "unit": "img/s", "vs_baseline": 0.0,
            "error": "nothing measured", "platform": "unknown",
            "config": "v1_jit", "compute": "fp32", "batch": batch,
        }

    def carry(img_s: float, batch: int, **extra) -> dict:
        return {
            **_bench_view(img_s, "fp32", batch, stale=True),
            "metric": "alexnet_blocks12_images_per_sec",
            "assumed_peak_tflops": _PEAK, "device_kind": "TPU v5 lite",
            "matmul_flops_per_image": _MATMUL_FLOPS, "platform": "tpu",
            "config": "v1_jit", "batch": batch, "stale": True, **extra,
        }

    r03 = {**error_row(256), "last_good": carry(24000.0, 256)}
    # r03 predates the stale_value rename: its carry still says "value"
    r03["last_good"]["value"] = r03["last_good"].pop("stale_value")
    r04 = {
        **error_row(128), "last_good": carry(24000.0, 256),
        "value_last_good": 24000.0,
    }
    r05 = {
        **error_row(128),
        "last_good": carry(
            22000.0, 128, bf16=_bench_view(100000.0, "bf16", 128, stale=True)
        ),
        "value_last_good": 22000.0,
    }
    rounds = [{"rc": 1, "tail": "Traceback"}] + [
        {"rc": 0, "parsed": row} for row in (error_row(128), r03, r04, r05)
    ]
    paths = []
    for i, obj in enumerate(rounds, start=1):
        path = tmp_path / f"BENCH_r0{i}.json"
        path.write_text(json.dumps(obj))
        paths.append(path)
    return paths
