"""Distributed matrix multiply with self-verification (the hw1 analogue).

The reference's ``homeworks/hw1/src/template.c`` is a row-scatter MPI matmul:
root scatters rows of A point-to-point (template.c:120-129), broadcasts B
(:132), each rank computes its row block (:195-208), root gathers (:138-146)
and verifies against a serial recompute with tolerance 1e-6 (:220-238). The
course's three homework variants map communication styles: HW1 point-to-point,
HW2 collective, HW3 one-sided.

Here the same study is expressed TPU-natively as three strategies over a 1-D
device mesh — all computing C = A @ B with A row-sharded:

- ``scatter``    (HW1 analogue): explicit ``shard_map`` — A sharded over the
  mesh axis, B fully replicated (the Bcast), local MXU ``dot``, output left
  row-sharded (the gather is the sharded→replicated ``jax.device_get``).
- ``collective`` (HW2 analogue): no manual comms at all — ``jit`` with
  ``NamedSharding`` annotations; XLA chooses and inserts the collectives.
- ``ring``       (HW3 analogue): B stays sharded along its contraction axis;
  each step multiplies the resident block and rotates B one neighbor over ICI
  via ``ppermute`` — the device-initiated-transfer analogue of one-sided RMA,
  and the standard TPU ring-matmul building block.

Initialization matches the reference (integers 0-9, template.c:211-216), which
makes fp32 arithmetic *exact* for n <= 4096 (products <= 81, row sums
<= 4096*81 < 2^24), so the reference's 1e-6 tolerance (:222) is meaningful on
TPU without fp64.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import make_mesh

MAXDIM = 1 << 12  # 4096 (template.c:20)
TOLERANCE = 1e-6  # template.c:222
STRATEGIES = ("scatter", "collective", "ring")


def validate_n(n: int, num_shards: int) -> int:
    """Reference argument contract: positive power of two (template.c:48-55),
    clamped to MAXDIM (:56-63), divisible by the process count (:65-72)."""
    if n <= 0 or (n & (n - 1)) != 0:
        raise ValueError(f"matrix dimension n ({n}) must be a positive power of two")
    if n > MAXDIM:
        n = MAXDIM
    if n % num_shards != 0:
        raise ValueError(
            f"matrix dimension n ({n}) must be divisible by the shard count ({num_shards})"
        )
    return n


def init_data(key: jax.Array, n: int) -> jax.Array:
    """Random integers 0-9 as floats (template.c:211-216) — exact in fp32."""
    return jax.random.randint(key, (n, n), 0, 10).astype(jnp.float32)


def mat_mult_serial(a: jax.Array, b: jax.Array) -> jax.Array:
    """Single-device verification oracle (template.c:195-208 with my_work=n)."""
    return jax.lax.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _local_dot(a_blk: jax.Array, b: jax.Array) -> jax.Array:
    return jax.lax.dot(a_blk, b, precision=jax.lax.Precision.HIGHEST)


@functools.cache
def _build_scatter(mesh: Mesh, axis: str):
    return jax.jit(
        shard_map(
            _local_dot,
            mesh=mesh,
            in_specs=(P(axis, None), P(None, None)),
            out_specs=P(axis, None),
        )
    )


def mat_mult_scatter(a: jax.Array, b: jax.Array, mesh: Mesh, axis: str = "sp") -> jax.Array:
    """HW1 analogue: explicit row scatter + replicated B + local MXU dot."""
    return _build_scatter(mesh, axis)(a, b)


@functools.cache
def _build_collective(mesh: Mesh, axis: str):
    return jax.jit(_local_dot, out_shardings=NamedSharding(mesh, P(axis, None)))


def mat_mult_collective(a: jax.Array, b: jax.Array, mesh: Mesh, axis: str = "sp") -> jax.Array:
    """HW2 analogue: sharding annotations only; XLA inserts the collectives."""
    a = jax.device_put(a, NamedSharding(mesh, P(axis, None)))
    b = jax.device_put(b, NamedSharding(mesh, P(None, None)))
    return _build_collective(mesh, axis)(a, b)


@functools.cache
def _build_ring(mesh: Mesh, axis: str):
    n_shards = mesh.shape[axis]

    def local(a_blk: jax.Array, b_blk: jax.Array) -> jax.Array:
        blk = a_blk.shape[1] // n_shards
        idx = jax.lax.axis_index(axis)
        perm = [(i, (i - 1) % n_shards) for i in range(n_shards)]

        def step(s, carry):
            acc, b_cur = carry
            owner = (idx + s) % n_shards  # whose k-block is resident now
            a_cols = jax.lax.dynamic_slice_in_dim(a_blk, owner * blk, blk, axis=1)
            acc = acc + jax.lax.dot(a_cols, b_cur, precision=jax.lax.Precision.HIGHEST)
            b_nxt = jax.lax.ppermute(b_cur, axis, perm)
            return acc, b_nxt

        # The carry must be marked device-varying over the mesh axis up front
        # (the ppermute output is), or the fori_loop carry types mismatch.
        acc = jax.lax.pcast(
            jnp.zeros((a_blk.shape[0], b_blk.shape[1]), a_blk.dtype),
            (axis,), to="varying",
        )
        acc, _ = jax.lax.fori_loop(0, n_shards, step, (acc, b_blk))
        return acc

    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis, None), P(axis, None)),
            out_specs=P(axis, None),
        )
    )


def mat_mult_ring(a: jax.Array, b: jax.Array, mesh: Mesh, axis: str = "sp") -> jax.Array:
    """HW3 analogue: B stays k-sharded; blocks rotate over ICI via ppermute.

    Device d holds A rows block d and B k-block d. At step s it multiplies
    its A columns [owner*blk : (owner+1)*blk] against the resident B block,
    then passes the block to its ring predecessor — n_shards steps, each a
    dense MXU matmul overlapped with a neighbor transfer.
    """
    return _build_ring(mesh, axis)(a, b)


_IMPLS = {
    "scatter": mat_mult_scatter,
    "collective": mat_mult_collective,
    "ring": mat_mult_ring,
}


def mat_mult_distributed(
    a: jax.Array,
    b: jax.Array,
    n_shards: int,
    strategy: str = "scatter",
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    if strategy not in _IMPLS:
        raise ValueError(f"unknown strategy {strategy!r}; choose from {STRATEGIES}")
    mesh = mesh or make_mesh(n_shards)
    # The shard axis is the innermost mesh axis, whatever the caller named it.
    return _IMPLS[strategy](a, b, mesh, axis=mesh.axis_names[-1])


def check_result(c: jax.Array, d: jax.Array, tolerance: float = TOLERANCE) -> bool:
    """Epsilon compare (template.c:220-238). True = mismatch (their flag)."""
    return bool(jnp.max(jnp.abs(c - d)) > tolerance)


def main(argv=None) -> int:
    import argparse
    import time

    p = argparse.ArgumentParser(prog="cuda_mpi_gpu_cluster_programming_tpu.examples.matmul")
    p.add_argument("n", type=int, nargs="?", default=64, help="matrix dimension (power of two)")
    p.add_argument("--shards", type=int, default=1, help="row-shard count (mpirun -np analogue)")
    p.add_argument("--strategy", choices=STRATEGIES, default="scatter")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    try:
        n = validate_n(args.n, args.shards)
    except ValueError as e:
        print(f"Error: {e}")
        return 1
    if n != args.n:
        print(f"Warning: n ({args.n}) exceeds MAXDIM ({MAXDIM}). Clamping to MAXDIM.")

    my_work = n // args.shards
    print(f"pid=0: num_procs={args.shards} n={n} my_work={my_work} (rows per proc)")

    ka, kb = jax.random.split(jax.random.PRNGKey(args.seed))
    a, b = init_data(ka, n), init_data(kb, n)

    # Warm-up compile outside the timed region (the reference times only the
    # distribute+compute+gather phase, after MPI_Barrier — template.c:114-116).
    c = jax.block_until_ready(mat_mult_distributed(a, b, args.shards, args.strategy))
    t0 = time.perf_counter()
    c = jax.block_until_ready(mat_mult_distributed(a, b, args.shards, args.strategy))
    elapsed = time.perf_counter() - t0
    print(f"pid=0: Parallel computation finished in {elapsed:f} seconds.")

    print("pid=0: Performing serial computation for verification...")
    d = jax.block_until_ready(mat_mult_serial(a, b))
    t0 = time.perf_counter()
    d = jax.block_until_ready(mat_mult_serial(a, b))
    print(f"pid=0: Serial computation finished in {time.perf_counter() - t0:f} seconds.")

    if check_result(c, d):
        print("--------------------------------------")
        print("pid=0: Test: FAILED")
        print("--------------------------------------")
        return 1
    print("--------------------------------------")
    print("pid=0: Test: PASSED")
    print(f"pid=0: Total PARALLEL time: {elapsed:f} seconds.")
    print("--------------------------------------")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
