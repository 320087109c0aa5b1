"""Persistent XLA compilation cache — the prebuilt-binaries analogue.

The reference caches compiled executables per machine so repeated harness
runs skip the build step (scripts/build_local_binaries.sh:8-10,
prebuilt_executables_local/). On TPU the "build" is XLA jit compilation;
the analogue is JAX's persistent compilation cache: the first run of a
(program, shape, backend) point pays the full compile, every later process
— including each harness case subprocess — deserializes the cached
executable instead.

Enabled by every entry point (run.py, train.py, chip_smoke.py,
and ``configs.build_forward`` for library callers). One way to place it:
``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself — when it is set this
module sets no directory. Unset, the cache lives at the fixed
``<repo-root>/.xla_cache`` (git-ignored; fixed because the directory is part
of the cache key, so a path that moves never hits).
"""

from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parent.parent.parent / ".xla_cache"


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Must be called before the first jit compilation to take effect for it
    (later calls still apply to subsequent compilations).
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        DEFAULT_DIR.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    # The workload's jits are small (the whole model compiles in seconds);
    # without floor overrides JAX would skip caching them entirely.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # JAX keys the cache on the program with its metadata stripped, so a
    # cached executable keeps the op names of whichever build wrote it. A
    # profile must carry THIS build's layer names (ops/scopes.py): key on
    # the metadata too.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return jax.config.jax_compilation_cache_dir
