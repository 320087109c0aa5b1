"""Scaled dot-product attention — the single-device reference op.

The reference repo has no attention anywhere (SURVEY §2.2: its closest
structural cousin is the halo-ring over the image H axis). This op exists as
the oracle for the framework's long-context sequence-parallel strategies
(``parallel.sequence_parallel``): ring attention and Ulysses all-to-all are
validated shard-vs-single against it, exactly how the sharded conv pipeline
is validated against the single-device pass.

Layout: ``(B, L, H, D)`` — batch, sequence, heads, head_dim. bf16-friendly:
softmax statistics are computed in fp32 regardless of input dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .reference import mxu_precision

NEG_INF = -1e30  # finite mask value: keeps running-max math NaN-free


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
) -> jax.Array:
    """Full O(L^2) attention. q,k,v: (B, L, H, D) -> (B, L, H, D)."""
    b, lq, h, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    # (B, H, Lq, Lk) scores in fp32.
    prec = mxu_precision(q.dtype)
    s = jnp.einsum(
        "blhd,bmhd->bhlm", q.astype(jnp.float32), k.astype(jnp.float32), precision=prec
    ) * scale
    if causal:
        lk = k.shape[1]
        mask = jnp.arange(lq)[:, None] >= jnp.arange(lk)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhlm,bmhd->blhd", p, v.astype(jnp.float32), precision=prec)
    return out.astype(q.dtype)
