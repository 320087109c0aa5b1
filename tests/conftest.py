"""Test config: run everything on a virtual 8-device CPU mesh.

This is the TPU-world analogue of the reference's ``mpirun --oversubscribe
-np N`` localhost testing (scripts/common_test_utils.sh:274-276): N virtual
XLA host devices stand in for N TPU cores, so sharded paths are exercised
without a pod.

``jax.config.update`` pins the platform whatever JAX_PLATFORMS says (it
wins as long as no backend has been initialized). ``XLA_FLAGS`` is read at
backend-init time, so setting it here works.
"""

import os

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

