"""Environment capture: the pc_v4_environment_info.txt / shell.nix analogue.

The reference pins its toolchain two ways: a nix shell fixing GCC/CUDA/
Open MPI versions (shell.nix:2-36) and a checked-in environment dump from the
dev machine (pc_v4_environment_info.txt — GCC 13.3, Open MPI 4.1.6, CUDA
12.8). Here the equivalents are ``requirements.txt`` (the pin) and this
module (the dump): a machine-readable record of the Python/JAX/TPU toolchain
a benchmark session ran under, written next to the session CSV so analysis
can attribute numbers to environments.
"""

from __future__ import annotations

import importlib.metadata
import json
import os
import platform
import sys
from typing import Dict

PACKAGES = (
    "jax",
    "jaxlib",
    "libtpu",
    "flax",
    "optax",
    "orbax-checkpoint",
    "chex",
    "einops",
    "numpy",
    "pytest",
    "hypothesis",
)


def collect(probe_devices: bool = True) -> Dict[str, object]:
    info: Dict[str, object] = {
        "python": sys.version.split()[0],
        "platform": platform.platform(),
        "machine": platform.node() or "unknown",
        "packages": {},
        "env": {
            k: os.environ.get(k, "")
            for k in ("JAX_PLATFORMS", "XLA_FLAGS", "LIBTPU_INIT_ARGS")
            if os.environ.get(k)
        },
    }
    for pkg in PACKAGES:
        try:
            info["packages"][pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            info["packages"][pkg] = None
    if probe_devices:
        # The nvidia-smi-query analogue (common_test_utils.sh:30-48): record
        # what accelerators this process actually sees.
        try:
            import jax

            info["backend"] = jax.default_backend()
            info["device_count"] = jax.device_count()
            info["devices"] = [d.device_kind for d in jax.devices()]
            info["process_count"] = jax.process_count()
        except Exception as e:  # device probe must never fail the capture
            info["backend_error"] = f"{type(e).__name__}: {e}"
    return info


def _with_device_count(flags: str, n_devices: int) -> str:
    """``flags`` with exactly one host-platform device-count flag (a prior
    one is spliced out — duplicates only work by last-one-wins luck)."""
    import re

    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
    return (flags + f" --xla_force_host_platform_device_count={n_devices}").strip()


def cpu_subprocess_env(n_devices: int) -> Dict[str, str]:
    """Environment for a subprocess that must run on N virtual CPU devices
    (the ``mpirun --oversubscribe`` analogue): the parent's, with
    ``JAX_PLATFORMS=cpu`` and the device-count flag."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = _with_device_count(env.get("XLA_FLAGS", ""), n_devices)
    return env


def force_virtual_cpu(n_devices: int) -> None:
    """In-process twin of :func:`cpu_subprocess_env` for CLIs with a
    ``--fake-devices`` flag. Must run before any JAX backend initializes
    (``jax.config.update`` still wins then, where the JAX_PLATFORMS
    variable was already read at import)."""
    os.environ["XLA_FLAGS"] = _with_device_count(
        os.environ.get("XLA_FLAGS", ""), n_devices
    )
    import jax

    jax.config.update("jax_platforms", "cpu")


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(prog="cuda_mpi_gpu_cluster_programming_tpu.utils.env_info")
    p.add_argument("--out", help="also write the JSON dump to this path")
    p.add_argument("--no-devices", action="store_true", help="skip the device probe")
    args = p.parse_args(argv)
    info = collect(probe_devices=not args.no_devices)
    text = json.dumps(info, indent=2)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
