"""Device spec table: peak TFLOP/s per dtype + HBM bandwidth per TPU
generation, plus the live device-memory snapshot helper.

This module is the ONE source of truth in the package for what a chip
can do: the roofline attribution layer (``observability.roofline``)
reads it for its compute-vs-HBM-bound classification. The benchmark
keeps a copy of the v5e row with its source (``benchmark/peaks.json``);
``tests/test_specs.py`` holds the two equal.

Numbers come from the public TPU spec sheets, matched against jax's
``device_kind`` string ("v5" matches the "TPU v5 lite" spelling v5e
reports). A device that is not in the table is an error
(:class:`UnknownDeviceError`), never a default: a CPU run gets no MFU at
all rather than one judged against an assumed chip. Per-dtype peaks:

- ``bf16`` — the MXU peak from the table.
- ``fp32`` — ``bf16 / 6``: ``lax.Precision.HIGHEST`` synthesizes true
  fp32 MACs out of 6 bf16 MXU passes (an assumption, labelled as one
  in ``benchmark/peaks.json`` too).
- ``int8w`` — equals the bf16 peak HERE, deliberately: this repo's
  int8w forward is dequant-free bf16-accumulate (docs/PRECISION.md) —
  the MXU executes bf16 operand passes, so the int8 TOPS column of the
  spec sheet is not the ceiling this codebase can reach. ``int8_tops``
  is still recorded on the spec for reference.

Stdlib-only at module scope; :func:`device_memory_stats` imports jax lazily. On the CPU backend, which
exposes no ``memory_stats()``, it reports the process RSS and says so
(``source`` names which reading it is).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# lax.Precision.HIGHEST fp32 synthesis: 6 bf16 MXU passes per fp32 MAC.
FP32_SYNTH_FACTOR = 6.0


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """One TPU generation's roofline-relevant capabilities."""

    marker: str  # substring matched against device_kind.lower()
    name: str
    bf16_tflops: float  # MXU peak, dense bf16
    hbm_gbps: float  # HBM bandwidth, GB/s per chip
    int8_tops: Optional[float] = None  # spec-sheet int8 (reference only)

    def peak_tflops(self, dtype: str = "bf16") -> float:
        """The MXU ceiling a ``dtype`` policy of THIS repo can chase."""
        if dtype == "fp32":
            return self.bf16_tflops / FP32_SYNTH_FACTOR
        # bf16 and int8w both execute bf16 MXU passes here (module doc).
        return self.bf16_tflops

    def to_obj(self) -> dict:
        return {
            "name": self.name,
            "bf16_tflops": self.bf16_tflops,
            "hbm_gbps": self.hbm_gbps,
            "int8_tops": self.int8_tops,
        }


# Ordered: longer/newer markers first so "v5p" wins over "v5".
SPEC_TABLE: Tuple[DeviceSpec, ...] = (
    DeviceSpec("v6", "TPU v6e (Trillium)", 918.0, 1640.0, 1836.0),
    DeviceSpec("v5p", "TPU v5p", 459.0, 2765.0, 918.0),
    DeviceSpec("v5", "TPU v5e", 197.0, 819.0, 394.0),  # kind: "TPU v5 lite"
    DeviceSpec("v4", "TPU v4", 275.0, 1228.0, 275.0),
    DeviceSpec("v3", "TPU v3", 123.0, 900.0, None),
    DeviceSpec("v2", "TPU v2", 45.0, 700.0, None),
)

class UnknownDeviceError(ValueError):
    """``device_kind`` matches no row of :data:`SPEC_TABLE`."""


def spec_for(device_kind: str) -> DeviceSpec:
    """The spec row for a jax ``device_kind`` string; raises
    :class:`UnknownDeviceError` when the kind matches nothing."""
    kind = (device_kind or "").lower()
    for spec in SPEC_TABLE:
        if spec.marker in kind:
            return spec
    raise UnknownDeviceError(
        f"device kind {device_kind!r} is not in the spec table "
        f"({', '.join(s.name for s in SPEC_TABLE)}): no peak to judge against"
    )


def peak_tflops(device_kind: str, dtype: str = "bf16") -> float:
    """Peak TFLOP/s for ``device_kind`` under this repo's ``dtype``
    policies."""
    return spec_for(device_kind).peak_tflops(dtype)


def hbm_gbps(device_kind: str) -> float:
    """HBM bandwidth (GB/s) for ``device_kind``."""
    return spec_for(device_kind).hbm_gbps


# ------------------------------------------------------- live telemetry ---


def device_memory_stats() -> dict:
    """One resource snapshot for the ``mem_snapshot`` journal record.

    jax's per-device ``memory_stats()`` (``source="device"``:
    bytes_in_use / peak_bytes_in_use / bytes_limit summed over local
    devices, with the per-device list alongside). The CPU backend exposes
    none, so there the record carries the process max-RSS
    (``source="rss"``); a TPU that reports no stats is an error.
    """
    import jax

    devices = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats and stats.get("bytes_in_use") is not None:
            devices.append(
                {
                    "device": d.id,
                    "bytes_in_use": int(stats["bytes_in_use"]),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit"),
                }
            )
    if devices:
        def _total(field: str) -> Optional[int]:
            nums = [
                d[field] for d in devices if isinstance(d[field], (int, float))
            ]
            return int(sum(nums)) if nums else None

        return {
            "source": "device",
            "bytes_in_use": _total("bytes_in_use"),
            "peak_bytes_in_use": _total("peak_bytes_in_use"),
            "bytes_limit": _total("bytes_limit"),
            "devices": devices,
        }
    if jax.default_backend() == "tpu":
        raise RuntimeError("TPU devices reported no memory_stats()")
    import resource

    rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return {
        "source": "rss",
        "bytes_in_use": int(rss_kb) * 1024,  # linux reports KB
        "peak_bytes_in_use": None,
        "bytes_limit": None,
    }
