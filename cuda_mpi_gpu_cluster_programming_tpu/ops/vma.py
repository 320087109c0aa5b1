"""Pallas interpret-mode switch and varying-mesh-axes tagging for
pallas_call out_shapes.

``pallas_call`` outputs carry no vma metadata, so a ``shard_map`` caller
with ``check_vma=True`` rejects any body containing a kernel — which
historically forced ``check_vma=False`` on whole bodies, silently losing
the checker on their ppermutes / all_to_alls too (round-3 advisor
finding). Kernels that can run inside shard_map accept a ``vma`` tuple of
mesh axis names and tag their out_shapes here, so callers keep the
checker on end to end.
"""

from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted: everywhere but on a TPU,
    where they lower through Mosaic. The ONE place this is decided — every
    kernel wrapper, the vma tagging below and the tuner's candidate space
    read it."""
    return jax.default_backend() != "tpu"


def vma_struct(shape, dtype, vma=None) -> jax.ShapeDtypeStruct:
    """ShapeDtypeStruct, tagged varying over ``vma`` axes when given.

    ``vma=None`` is the plain single-device call (no metadata — identical
    to the bare constructor). In interpret mode the tag is dropped: the
    HLO interpreter discharges the kernel into jax ops whose internal
    dynamic_slices mix tagged blocks with untagged grid scalars and fail
    the checker ("Primitive dynamic_slice requires varying manual axes to
    match", jax 0.9.0 hlo_interpreter.py) — its own message prescribes
    check_vma=False there, which :func:`kernel_check_vma` implements.
    """
    if vma is None or interpret_mode():
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))


def kernel_check_vma() -> bool:
    """``check_vma`` value for shard_map bodies containing Pallas kernels:
    True on TPU (kernels tag their out_shapes via :func:`vma_struct`, so
    the checker guards the body's collectives end to end), False in
    interpret mode (see :func:`vma_struct`; revisit when jax's interpreter
    propagates vma)."""
    return not interpret_mode()
