"""Dtype policies: precision as a first-class, named, per-layer axis.

On the v5e bf16 runs Blocks 1-2 4.9x faster than fp32 on the same
kernels (105,5xx against 21,65x img/s: PERF_LEDGER.jsonl, cells
``blocks12_offline`` and ``blocks12_offline_fp32``) — a ceiling the fp32
default leaves on the table — but until this module ``dtype`` was only a
passive key of the tuning plan that every caller had to pin by hand. A
:class:`DtypePolicy` makes the choice explicit and auditable: per layer
it names the dtype operands enter the contraction in
(``compute``), the dtype the contraction accumulates in (``accumulate`` —
threaded as ``preferred_element_type`` so the MXU/XLA accumulation width
is stated, never inferred), and the dtype parameters are stored in
(``params``; ``int8`` means symmetric per-channel quantized weights, see
``precision.quantize``).

Three named presets cover the production points:

- ``fp32``  — the reference floor: fp32 operands, fp32 accumulation, exact
  parity with the paper's serial oracle (``lax.Precision.HIGHEST`` MACs).
- ``bf16``  — the TPU-native fast path: bf16 operands and params, fp32
  accumulation on the MXU, fp32 output.
- ``int8w`` — weight-only int8 quantization: int8 params (per-output-
  channel symmetric scales), bf16 activations, fp32 accumulation, the
  per-channel rescale applied once to the conv OUTPUT (dequant-free — the
  contraction runs on the raw quantized values).

Every non-fp32 policy must clear the fp32-oracle :class:`~.gate.
ToleranceGate` before the autotuner will let it win a sweep
(docs/PRECISION.md).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple, Union

# Names the CLI/bench/tuning surfaces accept, in reference-floor-first
# order (also the deterministic tie-break order of the dtype sweep).
POLICY_NAMES = ("fp32", "bf16", "int8w")


def jdt(name: str):
    """jnp dtype for a policy dtype name (lazy import: policy objects are
    metadata and must stay importable without a backend)."""
    import jax.numpy as jnp

    return {
        "float32": jnp.float32,
        "bfloat16": jnp.bfloat16,
        "int8": jnp.int8,
    }[name]


@dataclasses.dataclass(frozen=True)
class LayerPrecision:
    """One layer's dtype triple.

    ``compute``: dtype operands enter the contraction in. ``accumulate``:
    the contraction's accumulation dtype — threaded into dot/conv as
    ``preferred_element_type`` wherever the policy path mixes precisions
    (the staticcheck ``implicit-upcast`` rule holds hot-path code to this).
    ``params``: parameter storage dtype; ``int8`` selects the quantized
    weight path."""

    compute: str = "float32"
    accumulate: str = "float32"
    params: str = "float32"


@dataclasses.dataclass(frozen=True)
class DtypePolicy:
    """A named per-layer precision assignment.

    ``layers`` overrides the ``default`` triple for specific layer names
    (conv1/conv2/...); un-named layers take the default — the same
    layer-addressing shape as ``ops.pallas_kernels.LayerVariants``."""

    name: str
    default: LayerPrecision = LayerPrecision()
    layers: Tuple[Tuple[str, LayerPrecision], ...] = ()

    def layer(self, layer_name: str) -> LayerPrecision:
        for n, lp in self.layers:
            if n == layer_name:
                return lp
        return self.default

    @property
    def quantized(self) -> bool:
        """True when any layer stores int8 params (the quantize path)."""
        return any(
            lp.params == "int8" for lp in (self.default, *(lp for _n, lp in self.layers))
        )


PRESETS: Dict[str, DtypePolicy] = {
    "fp32": DtypePolicy("fp32", LayerPrecision("float32", "float32", "float32")),
    "bf16": DtypePolicy("bf16", LayerPrecision("bfloat16", "float32", "bfloat16")),
    "int8w": DtypePolicy("int8w", LayerPrecision("bfloat16", "float32", "int8")),
}


def resolve_policy(spec: Union[str, DtypePolicy, None]) -> DtypePolicy:
    """A DtypePolicy from a preset name, a policy object, or None (fp32).

    The one place policy names are validated — ``configs.build_forward``,
    the run CLI and bench all route through here, so an unknown name fails
    identically everywhere."""
    if spec is None:
        return PRESETS["fp32"]
    if isinstance(spec, DtypePolicy):
        return spec
    name = str(spec).strip().lower()
    if name not in PRESETS:
        raise ValueError(
            f"unknown precision policy {spec!r} (known: {'|'.join(POLICY_NAMES)})"
        )
    return PRESETS[name]
