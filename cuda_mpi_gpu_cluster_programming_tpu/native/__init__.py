"""Native (C++) tier: shard-geometry planner + prefetching data pipeline.

The reference keeps its host-side runtime in C++ — the dim helpers and trim
math (v2_mpi_only/2.2_scatter_halo/include/alexnet.hpp:35-44,
v4_mpi_cuda/src/alexnet_mpi_cuda.cu:27-38) and the data-synthesis loops
(v1_serial/src/alexnet_serial.cpp:39-57). This package is the TPU framework's
equivalent native tier: ``csrc/`` is compiled on demand with ``g++`` into one
shared library, bound here via ctypes (no pybind11 in the image).

Public surface:

- :func:`conv_out_dim` / :func:`pool_out_dim` — native shape calculators.
- :func:`make_shard_plan_native` — ShardPlan structurally identical to
  ``parallel.plan.make_shard_plan`` (cross-validated in tests/test_native.py).
- :func:`owned_range_native` — per-shard global output-row ownership.
- :func:`fill_batch` — synchronous synthetic batch (ones / seeded uniform).
- :class:`NativeDataLoader` — multi-threaded prefetching batch iterator whose
  stream depends only on (seed, batch index), never thread timing.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import tempfile
import threading
from pathlib import Path
from typing import Iterator, Optional, Sequence, Tuple

import numpy as np

from ..models.alexnet import Blocks12Config
from ..parallel.plan import LayerPlan, ShardPlan, layer_geometry

_SRC_DIR = Path(__file__).parent / "csrc"
_BUILD_DIR = Path(__file__).parent / "_build"
_LIB_PATH = _BUILD_DIR / "libtpunative.so"

_KIND_CODE = {"conv": 0, "pool": 1, "pointwise": 2}
_KIND_NAME = {v: k for k, v in _KIND_CODE.items()}

_ERRORS = {
    -1: "degenerate layer output (filter cannot fit)",
    -2: "uniform window escapes padded buffer",
    -3: "bad argument",
}

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


class _LayerPlanC(ctypes.Structure):
    _fields_ = [
        ("kind", ctypes.c_int32),
        ("filter_size", ctypes.c_int32),
        ("stride", ctypes.c_int32),
        ("padding", ctypes.c_int32),
        ("l_in", ctypes.c_int32),
        ("l_out", ctypes.c_int32),
        ("b_in", ctypes.c_int32),
        ("b_out", ctypes.c_int32),
        ("h_top", ctypes.c_int32),
        ("h_bot", ctypes.c_int32),
        ("s0_coef", ctypes.c_int32),
        ("s0_const", ctypes.c_int32),
        ("win_rows", ctypes.c_int32),
        ("pad_bot", ctypes.c_int32),
    ]


def _build() -> Path:
    """Compile csrc/*.cpp into libtpunative.so if missing or stale."""
    sources = sorted(_SRC_DIR.glob("*.cpp"))
    if not sources:
        raise RuntimeError(f"no C++ sources under {_SRC_DIR}")
    if _LIB_PATH.exists():
        newest = max(s.stat().st_mtime for s in sources)
        if _LIB_PATH.stat().st_mtime >= newest:
            return _LIB_PATH
    _BUILD_DIR.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=str(_BUILD_DIR))
    os.close(fd)
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
        *map(str, sources), "-o", tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
    except FileNotFoundError as e:
        os.unlink(tmp)
        raise RuntimeError("g++ not found; the native tier needs a C++ toolchain") from e
    except subprocess.CalledProcessError as e:
        os.unlink(tmp)
        raise RuntimeError(f"native build failed:\n{e.stderr}") from e
    os.replace(tmp, _LIB_PATH)  # atomic: concurrent builders race harmlessly
    return _LIB_PATH


def _load() -> ctypes.CDLL:
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        lib = ctypes.CDLL(str(_build()))
        lib.sp_conv_out_dim.restype = ctypes.c_int
        lib.sp_conv_out_dim.argtypes = [ctypes.c_int] * 4
        lib.sp_pool_out_dim.restype = ctypes.c_int
        lib.sp_pool_out_dim.argtypes = [ctypes.c_int] * 3
        lib.sp_plan_chain.restype = ctypes.c_int
        lib.sp_plan_chain.argtypes = [
            ctypes.c_int,
            *(ctypes.POINTER(ctypes.c_int32),) * 4,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(_LayerPlanC),
        ]
        lib.sp_owned_range.restype = None
        lib.sp_owned_range.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ]
        lib.dl_fill.restype = None
        lib.dl_fill.argtypes = [
            ctypes.c_int, ctypes.c_uint64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float),
        ]
        lib.dl_splitmix64.restype = ctypes.c_uint64
        lib.dl_splitmix64.argtypes = [ctypes.c_uint64]
        lib.dl_lcg_next.restype = ctypes.c_uint64
        lib.dl_lcg_next.argtypes = [ctypes.c_uint64]
        lib.dl_lcg_float.restype = ctypes.c_float
        lib.dl_lcg_float.argtypes = [ctypes.c_uint64]
        lib.dl_create.restype = ctypes.c_void_p
        lib.dl_create.argtypes = [
            ctypes.c_int, ctypes.c_uint64, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        ]
        lib.dl_next.restype = ctypes.c_int64
        lib.dl_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_float)]
        lib.dl_destroy.restype = None
        lib.dl_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


# ---------------------------------------------------------------- shard plan


def conv_out_dim(d: int, f: int, p: int, s: int) -> int:
    return _load().sp_conv_out_dim(d, f, p, s)


def pool_out_dim(d: int, f: int, s: int) -> int:
    return _load().sp_pool_out_dim(d, f, s)


def _chain_arrays(cfg: Blocks12Config):
    names, kinds, fs, ss, ps = [], [], [], [], []
    for name, spec in cfg.layer_chain():
        kind, f, s, p = layer_geometry(spec)
        names.append(name)
        kinds.append(_KIND_CODE[kind]); fs.append(f); ss.append(s); ps.append(p)
    return names, kinds, fs, ss, ps


def make_shard_plan_native(cfg: Blocks12Config, n_shards: int) -> ShardPlan:
    """Native twin of ``parallel.plan.make_shard_plan`` (same ShardPlan type)."""
    if n_shards < 1:
        raise ValueError("n_shards must be >= 1")
    lib = _load()
    names, kinds, fs, ss, ps = _chain_arrays(cfg)
    n = len(names)
    arr = lambda xs: (ctypes.c_int32 * n)(*xs)  # noqa: E731
    out = (_LayerPlanC * n)()
    rc = lib.sp_plan_chain(n, arr(kinds), arr(fs), arr(ss), arr(ps), cfg.in_height, n_shards, out)
    if rc != 0:
        raise ValueError(f"native plan failed: {_ERRORS.get(rc, rc)}")
    layers = tuple(
        LayerPlan(
            name=names[i],
            kind=_KIND_NAME[out[i].kind],
            filter_size=out[i].filter_size,
            stride=out[i].stride,
            padding=out[i].padding,
            l_in=out[i].l_in,
            l_out=out[i].l_out,
            b_in=out[i].b_in,
            b_out=out[i].b_out,
            h_top=out[i].h_top,
            h_bot=out[i].h_bot,
            s0_coef=out[i].s0_coef,
            s0_const=out[i].s0_const,
            win_rows=out[i].win_rows,
            pad_bot=out[i].pad_bot,
        )
        for i in range(n)
    )
    return ShardPlan(n_shards=n_shards, layers=layers)


def owned_range_native(b_out: int, l_out: int, i: int) -> Tuple[int, int]:
    start = ctypes.c_int32()
    end = ctypes.c_int32()
    _load().sp_owned_range(b_out, l_out, i, ctypes.byref(start), ctypes.byref(end))
    return start.value, end.value


# --------------------------------------------------------------- data loader

MODES = {"ones": 0, "uniform": 1}


def fill_batch(shape: Sequence[int], mode: str = "ones", seed: int = 0) -> np.ndarray:
    """Synchronously generate one synthetic batch (float32, C order)."""
    out = np.empty(shape, dtype=np.float32)
    _load().dl_fill(
        MODES[mode], ctypes.c_uint64(seed), out.size,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    return out


def lcg_uniform_numpy(seed: int, n: int) -> np.ndarray:
    """Pure-numpy mirror of the native uniform stream (parity oracle)."""
    with np.errstate(over="ignore"):
        x = np.uint64(seed) + np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        s = x ^ (x >> np.uint64(31))
        out = np.empty(n, dtype=np.float32)
        mul = np.uint64(6364136223846793005)
        inc = np.uint64(1442695040888963407)
        for i in range(n):
            s = s * mul + inc
            out[i] = np.float32(s >> np.uint64(40)) * np.float32(1.0 / 16777216.0)
    return out


def batch_seed(seed: int, k: int) -> int:
    """Seed of batch ``k`` in a loader stream (mirrors dataloader.cpp)."""
    return (seed + 0x517CC1B727220A95 * (k + 1)) % (1 << 64)


class NativeDataLoader:
    """Prefetching iterator over synthetic NHWC batches.

    ``depth`` bounds how many finished batches buffer ahead of the consumer;
    ``workers`` fills batches concurrently. Batch ``k`` equals
    ``fill_batch(shape, mode, batch_seed(seed, k))`` regardless of timing.
    """

    def __init__(
        self,
        shape: Sequence[int],
        mode: str = "ones",
        seed: int = 0,
        depth: int = 2,
        workers: int = 2,
    ):
        self._handle = None  # so __del__->close is safe if init raises below
        self._shape = tuple(int(d) for d in shape)
        elems = int(np.prod(self._shape))
        self._handle = _load().dl_create(
            MODES[mode], ctypes.c_uint64(seed), elems, depth, workers
        )
        if not self._handle:
            raise ValueError("dl_create failed (bad shape/depth/workers/mode)")

    def __iter__(self) -> Iterator[np.ndarray]:
        return self

    def __next__(self) -> np.ndarray:
        if self._handle is None:
            raise StopIteration
        out = np.empty(self._shape, dtype=np.float32)
        k = _load().dl_next(
            self._handle, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
        )
        if k < 0:
            raise StopIteration
        return out

    def close(self) -> None:
        if self._handle is not None:
            _load().dl_destroy(self._handle)
            self._handle = None

    def __enter__(self) -> "NativeDataLoader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self) -> None:  # pragma: no cover - GC timing
        self.close()
