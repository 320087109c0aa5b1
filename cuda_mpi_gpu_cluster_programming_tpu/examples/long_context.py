"""Long-context attention demo/bench: ring vs Ulysses vs single-device.

The long-context analogue of the reference's staged comparison story: one
workload, multiple parallelization strategies, machine-parseable output
lines for the harness/analysis pipeline (the stdout contract of
scripts/common_test_utils.sh:296-317 applied to sequence parallelism).

    python -m cuda_mpi_gpu_cluster_programming_tpu.examples.long_context \
        --seq-len 4096 --shards 8 --strategy ring

With ring attention each device keeps only ``L/n`` of the sequence; the
printed per-device KV-residency line makes the memory-scaling story visible
the same way the reference's speedup tables make its comm story visible.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="cuda_mpi_gpu_cluster_programming_tpu.examples.long_context"
    )
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--seq-len", type=int, default=4096)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--shards", type=int, default=1)
    p.add_argument(
        "--strategy",
        choices=["single", "flash", "ring", "ulysses"],
        default="ring",
        help="single = O(L^2) reference op; flash = fused Pallas kernel; "
        "ring/ulysses = sequence-parallel over the mesh",
    )
    p.add_argument("--causal", action="store_true", default=True)
    p.add_argument("--no-causal", dest="causal", action="store_false")
    p.add_argument("--dtype", choices=["fp32", "bf16"], default="fp32")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--repeats", type=int, default=10)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument(
        "--engine",
        choices=["einsum", "flash"],
        default="einsum",
        help="within-shard engine for ring/ulysses: einsum = XLA score "
        "blocks; flash = Pallas flash kernel per hop/shard (O(block) "
        "memory) — both differentiable (ring+flash via the joint "
        "(out, lse) VJP)",
    )
    p.add_argument(
        "--verify",
        action="store_true",
        help="also run the single-device oracle and report max |delta| "
        "(hw1-style self-verification, homeworks/hw1/src/template.c:149-176)",
    )
    p.add_argument(
        "--fake-devices",
        type=int,
        default=0,
        help="use N virtual CPU devices (mpirun --oversubscribe analogue)",
    )
    return p


def main(argv=None) -> int:
    args = make_parser().parse_args(argv)
    if args.fake_devices:
        from ..utils.env_info import force_virtual_cpu

        force_virtual_cpu(args.fake_devices)

    from ..ops.attention import attention
    from ..parallel.sequence_parallel import ring_attention, ulysses_attention
    from ..utils.timing import amortized_ms

    dtype = jnp.float32 if args.dtype == "fp32" else jnp.bfloat16
    shape = (args.batch, args.seq_len, args.heads, args.head_dim)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    q = jax.random.normal(kq, shape, dtype)
    k = jax.random.normal(kk, shape, dtype)
    v = jax.random.normal(kv, shape, dtype)

    if args.strategy == "single":
        fn = jax.jit(lambda q, k, v: attention(q, k, v, causal=args.causal))
    elif args.strategy == "flash":
        from ..ops.flash_attention import flash_attention

        fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=args.causal))
    elif args.strategy == "ring":
        fn = jax.jit(
            lambda q, k, v: ring_attention(
                q, k, v, n_shards=args.shards, causal=args.causal,
                engine=args.engine,
            )
        )
    else:
        fn = jax.jit(
            lambda q, k, v: ulysses_attention(
                q, k, v, n_shards=args.shards, causal=args.causal,
                engine=args.engine,
            )
        )

    print(
        f"--- Long-context attention [{args.strategy}] "
        f"(shards={args.shards}, L={args.seq_len}, B={args.batch}, "
        f"H={args.heads}, D={args.head_dim}, {args.dtype}, "
        f"causal={args.causal}) ---"
    )
    print(f"Devices: {jax.device_count()} x {jax.devices()[0].device_kind}")
    # Per-device KV residency: ring keeps L/n tokens x all heads; ulysses
    # keeps all L tokens x H/n heads (post all_to_all); single keeps it all.
    kv_tokens = args.seq_len // (args.shards if args.strategy == "ring" else 1)
    kv_heads = args.heads // (args.shards if args.strategy == "ulysses" else 1)
    bytes_per = 2 * args.batch * kv_tokens * kv_heads * args.head_dim * q.dtype.itemsize
    print(
        f"KV resident per device: {kv_tokens} tokens x {kv_heads} heads "
        f"({bytes_per / 2**20:.2f} MiB)"
    )

    out = jax.block_until_ready(fn(q, k, v))
    n_small = max(1, args.warmup)
    ms = amortized_ms(fn, q, k, v, n_small=n_small, n_large=n_small + max(1, args.repeats))
    toks = args.batch * args.seq_len / (ms / 1e3)
    print(f"Final Output Shape: {'x'.join(str(d) for d in out.shape)}")
    flat = np.asarray(out[0, :, 0, :], np.float32).reshape(-1)
    print("Final Output (first 10 values): " + " ".join(f"{x:.4f}" for x in flat[:10]))
    print(f"Attention completed in {ms:.3f} ms ({toks:.0f} tok/s)")

    if args.verify:
        want = np.asarray(attention(q, k, v, causal=args.causal), np.float32)
        delta = float(np.max(np.abs(want - np.asarray(out, np.float32))))
        # fp32 inputs multiply at true fp32 in both engines and in the
        # reference (ops.reference.mxu_precision), on the MXU as on the CPU.
        tol = 1e-4 if args.dtype == "fp32" else 3e-2
        ok = delta <= tol
        print(f"Verification: max|delta| = {delta:.2e} (tol {tol:.0e}) -> "
              f"{'PASSED' if ok else 'FAILED'}")
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
