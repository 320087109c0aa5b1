"""Persistent XLA compilation cache (the prebuilt-binaries analogue).

Reference capability: scripts/build_local_binaries.sh:8-10 caches compiled
executables per machine so harness runs skip the build. Here the build is
XLA jit compilation; utils.compile_cache points every entry point at an
on-disk cache so each harness case subprocess deserializes instead of
recompiling.
"""

import re
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
_RE_COMPILE = re.compile(r"Compile time: ([0-9.]+) ms")


def _run_case(cache_dir: Path) -> float:
    """Run one tiny v1_jit case in a subprocess whose cache is placed from
    outside (JAX_COMPILATION_CACHE_DIR); return its Compile_ms."""
    from cuda_mpi_gpu_cluster_programming_tpu.utils.env_info import cpu_subprocess_env

    env = cpu_subprocess_env(1)
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "cuda_mpi_gpu_cluster_programming_tpu.run",
            "--config", "v1_jit",
            "--batch", "1",
            "--repeats", "1",
            "--warmup", "1",
            "--height", "67",
            "--width", "67",
        ],
        capture_output=True,
        text=True,
        env=env,
        cwd=ROOT,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    m = _RE_COMPILE.search(proc.stdout)
    assert m, proc.stdout
    return float(m.group(1))


def test_cache_populates_and_second_process_hits_it(tmp_path):
    cache = tmp_path / "xla_cache"
    cold_ms = _run_case(cache)
    # The cache directory populated during the first run. Newer jax
    # versions write per-entry "-atime" bookkeeping files whose mtime is
    # rewritten on every cache READ (LRU eviction support) — they are
    # access-tracking, not cache content, so the read-path proof below
    # excludes them; the executable entries themselves must be untouched.
    def snapshot():
        return {
            p.name: (p.stat().st_mtime_ns, p.stat().st_size)
            for p in cache.iterdir()
            if not p.name.endswith("-atime")
        }

    cold = snapshot()
    assert cold, "compilation cache dir stayed empty"
    warm_ms = _run_case(cache)
    # The second process HIT the cache: it deserialized instead of
    # recompiling. A recompile would REWRITE its entry (new mtime) even if
    # the deterministic key gives it the same name — so name+mtime+size
    # equality is a read-path proof, not just a key-determinism proof.
    # (A wall-clock ratio assertion here is load-flaky on a busy CI box.)
    assert snapshot() == cold
    assert cold_ms > 0 and warm_ms > 0


def test_cache_dir_is_placed_from_outside_or_fixed(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, the code sets no directory (JAX
    reads the variable itself); unset, it is the fixed <repo>/.xla_cache."""
    import jax

    from cuda_mpi_gpu_cluster_programming_tpu.utils.compile_cache import (
        enable_persistent_cache,
    )

    before = jax.config.jax_compilation_cache_dir
    try:
        outside = str(tmp_path / "placed")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        jax.config.update("jax_compilation_cache_dir", outside)  # as JAX reads it
        assert enable_persistent_cache() == outside
        assert jax.config.jax_compilation_cache_dir == outside

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_persistent_cache() == str(ROOT / ".xla_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".xla_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
