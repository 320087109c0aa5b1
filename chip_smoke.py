"""chip_smoke.py — one command that proves the system still starts on the chip.

    python3 chip_smoke.py        # on the TPU, through the chip tool

Drives the repository's main path — AlexNet Blocks 1-2 at its full width,
227x227x3 -> 13x13x256 — once, through the entry points a user calls, in
ONE process (a chip belongs to one process at a time):

- ``run.main([...])`` for the one-shot CLI contract lines, ``v1_jit`` and
  ``v3_pallas`` at b=128 in fp32 and bf16;
- ``InferenceServer.start()/submit()`` and ``ServingFrontend`` over a real
  socket for the served path, unsupervised and under the supervisor;
- with >= 4 devices, ``v2.2_sharded`` and ``v5_collective`` at 4 shards.

Weights and inputs are seeded-random (``run --init random --seed 0``). What
comes out is checked by the repo's own means, as one trust chain
(precision/gate.py uses the same one): the numpy loop-nest oracle
(tests/oracle.py) validates the device's fp32 XLA forward on one full-size
image; that forward is then the oracle for every batch, every served result
and every tier, inside the precision gate's budgets — bitwise within a tier
for the sharded configs (tests/test_bit_exact.py's contract).

It refuses to run anywhere but a TPU, no phase's failure is caught and
carried past (any failed check raises, so the exit code is non-zero and no
result line is printed), and compile seconds are reported apart from run
seconds as set-up time. Neither is a performance figure.

The phases are plain functions of (config, shards, height, width, ...) so
tests/test_chip_smoke.py can call them small on the CPU mesh.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import http.client
import importlib.metadata
import io
import json
import re
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from urllib.parse import urlparse

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT), str(ROOT / "tests")]

SEED = 0
# The reference's printed output for deterministic init (input 1.0, weights
# 0.01, biases 0.0) at 227x227 — tests/test_model_golden.py.
GOLDEN_FIRST10 = (29.2932, 25.9153) + (23.3255,) * 8
_PRINT_QUANTUM = 5e-5  # run.py prints first-10 with four decimals
# Images per served request, cycled: single- and multi-image requests mixed.
_REQUEST_SIZES = (1, 1, 2, 1, 4, 1, 3, 1)


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclasses.dataclass
class PhaseResult:
    name: str
    compile_s: float
    run_s: float
    detail: str = ""


# ---------------------------------------------------------------- oracle ---


def _model_cfg(height: int, width: int):
    from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import BLOCKS12

    return dataclasses.replace(BLOCKS12, in_height=height, in_width=width)


@functools.lru_cache(maxsize=None)
def _params(height: int, width: int):
    """The weights ``run --init random --seed 0`` draws (run.py's split)."""
    import jax

    from cuda_mpi_gpu_cluster_programming_tpu.models.init import init_params_random

    kp, _kx = jax.random.split(jax.random.PRNGKey(SEED))
    return init_params_random(kp, _model_cfg(height, width))


def _input(height: int, width: int, batch: int):
    """The batch ``run --init random --seed 0 --batch N`` draws."""
    import jax

    from cuda_mpi_gpu_cluster_programming_tpu.models.init import random_input

    _kp, kx = jax.random.split(jax.random.PRNGKey(SEED))
    return random_input(kx, batch, _model_cfg(height, width))


@functools.lru_cache(maxsize=None)
def _device_oracle(height: int, width: int):
    """The fp32 XLA forward, jitted: the oracle every later phase compares
    against, once :func:`reference_phase` has tied it to the numpy one."""
    import jax

    from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import forward_blocks12

    cfg = _model_cfg(height, width)
    return jax.jit(lambda p, x: forward_blocks12(p, x, cfg))


def _oracle_out(height: int, width: int, x) -> np.ndarray:
    return np.asarray(_device_oracle(height, width)(_params(height, width), x))


def _numpy_forward(params, image: np.ndarray, cfg) -> np.ndarray:
    """Blocks 1-2 on one (H, W, C) image by tests/oracle.py's loop nests."""
    import oracle

    from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import ConvSpec, PoolSpec

    x = np.asarray(image, np.float64)
    for name, spec in cfg.layer_chain():
        if isinstance(spec, ConvSpec):
            w = np.asarray(params[name]["w"], np.float64)
            b = np.asarray(params[name]["b"], np.float64)
            x = oracle.conv2d_np(x, w, b, spec.stride, spec.padding)
            x = np.maximum(x, 0.0)
        elif isinstance(spec, PoolSpec):
            x = oracle.maxpool_np(x, spec.window, spec.stride)
        else:
            x = oracle.lrn_np(
                x, spec.size, spec.alpha, spec.beta, spec.k, spec.alpha_over_size
            )
    return x


def _agree(got, want, compute: str, what: str, quantum: float = 0.0) -> float:
    """``got`` is finite, shaped like ``want`` and inside ``compute``'s
    precision-gate budget of it (max error over the oracle's max
    magnitude — the gate's normalisation). Returns the relative error."""
    from cuda_mpi_gpu_cluster_programming_tpu.precision.gate import DEFAULT_BUDGETS

    budgets = DEFAULT_BUDGETS[compute]
    max_rel = (budgets.get("block2") or budgets["*"]).max_rel
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(got.shape == want.shape, f"{what}: shape {got.shape}, expected {want.shape}")
    check(bool(np.isfinite(got).all()), f"{what}: non-finite values")
    scale = float(np.max(np.abs(want)))
    err = float(np.max(np.abs(got - want)))
    check(
        err <= max_rel * scale + quantum,
        f"{what}: max error {err:.3e} exceeds the {compute} budget "
        f"{max_rel:g} x {scale:.4g}",
    )
    return err / scale if scale else 0.0


# ---------------------------------------------------------------- phases ---


def device_phase() -> PhaseResult:
    """The device is one the repo can judge: a row of the spec table, and
    memory telemetry read from the device rather than the process."""
    import jax

    from cuda_mpi_gpu_cluster_programming_tpu.observability.specs import (
        device_memory_stats,
        spec_for,
    )

    t0 = time.perf_counter()
    spec = spec_for(jax.devices()[0].device_kind)  # unknown kind raises
    snap = device_memory_stats()
    check(
        snap["source"] == "device",
        f"device_memory_stats source is {snap['source']!r}, not 'device'",
    )
    return PhaseResult(
        "device", 0.0, time.perf_counter() - t0,
        f"spec={spec.name} mem_source={snap['source']} "
        f"bytes_limit={snap.get('bytes_limit')}",
    )


def reference_phase(height: int, width: int) -> PhaseResult:
    """Tie the device's fp32 XLA forward to the numpy loop-nest oracle on
    one full-size image — the root of the trust chain."""
    cfg = _model_cfg(height, width)
    x = _input(height, width, 1)
    t0 = time.perf_counter()
    got = _oracle_out(height, width, x)[0]
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    want = _numpy_forward(_params(height, width), np.asarray(x)[0], cfg)
    rel = _agree(got, want, "fp32", "device fp32 forward vs numpy oracle")
    return PhaseResult(
        "reference", compile_s, time.perf_counter() - t0,
        f"{'x'.join(map(str, got.shape))} rel_err={rel:.2e}",
    )


_RE_SHAPE = re.compile(r"^Final Output Shape: (\S+)$", re.M)
_RE_FIRST = re.compile(r"^Final Output \(first 10 values\): (.+)$", re.M)
_RE_COMPILE = re.compile(r"^Compile time: ([0-9.]+) ms$", re.M)


def oneshot_phase(
    config: str,
    shards: int,
    height: int,
    width: int,
    *,
    batch: int,
    compute: str,
    init: str = "random",
) -> PhaseResult:
    """The one-shot CLI, as a user calls it: ``run.main([...])`` returns 0
    and its contract lines carry the expected shape and first-10 values."""
    from cuda_mpi_gpu_cluster_programming_tpu import run
    from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import output_shape

    argv = [
        "--config", config, "--shards", str(shards), "--batch", str(batch),
        "--compute", compute, "--height", str(height), "--width", str(width),
        "--init", init, "--seed", str(SEED), "--repeats", "3", "--warmup", "1",
    ]
    buf = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(buf):
        rc = run.main(argv)
    wall = time.perf_counter() - t0
    out = buf.getvalue()
    sys.stdout.write(out)
    name = f"oneshot {config} np{shards} b{batch} {compute} {init}"
    check(rc == 0, f"{name}: run.main returned {rc}")
    shape, first, comp = (r.search(out) for r in (_RE_SHAPE, _RE_FIRST, _RE_COMPILE))
    check(bool(shape and first and comp), f"{name}: stdout contract lines missing")
    want_shape = "x".join(map(str, output_shape(_model_cfg(height, width))))
    check(shape.group(1) == want_shape, f"{name}: shape {shape.group(1)} != {want_shape}")
    got = np.array([float(v) for v in first.group(1).split()])
    if init == "deterministic":
        want = np.array(GOLDEN_FIRST10)
    else:
        x0 = _input(height, width, batch)[:1]
        want = _oracle_out(height, width, x0)[0].reshape(-1)[:10]
    rel = _agree(got, want, compute, f"{name}: first-10", quantum=_PRINT_QUANTUM)
    compile_s = float(comp.group(1)) / 1e3
    return PhaseResult(name, compile_s, max(0.0, wall - compile_s), f"rel_err={rel:.2e}")


def lowering_phase(
    config: str, shards: int, height: int, width: int, *, batch: int
) -> PhaseResult:
    """The built forward's kernels really go through Mosaic on a TPU (its
    lowering holds ``tpu_custom_call``) — and are interpreted elsewhere."""
    from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward
    from cuda_mpi_gpu_cluster_programming_tpu.ops.vma import interpret_mode

    t0 = time.perf_counter()
    fwd = build_forward(REGISTRY[config], _model_cfg(height, width), n_shards=shards)
    text = fwd.lower(_params(height, width), _input(height, width, batch)).as_text()
    n = text.count("tpu_custom_call")
    check(
        (n > 0) != interpret_mode(),
        f"{config}: {n} tpu_custom_call(s) in the lowering with "
        f"interpret_mode={interpret_mode()}",
    )
    return PhaseResult(
        f"lowering {config} np{shards}", time.perf_counter() - t0, 0.0,
        f"tpu_custom_call x{n}",
    )


def _post_infer(url: str, rid: str, x: np.ndarray) -> np.ndarray:
    u = urlparse(url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=300)
    try:
        body = json.dumps(
            {
                "shape": list(x.shape), "data": x.reshape(-1).tolist(),
                "rid": rid, "return_output": True,
            }
        )
        conn.request("POST", "/v1/infer", body, {"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = json.loads(resp.read())
    finally:
        conn.close()
    check(resp.status == 200, f"request {rid}: HTTP {resp.status} {payload}")
    return np.asarray(payload["output"], np.float32).reshape(payload["output_shape"])


def served_phase(
    config: str,
    shards: int,
    height: int,
    width: int,
    buckets,
    *,
    supervise: bool,
    over_socket: bool,
    n_requests: int = 32,
) -> PhaseResult:
    """One served window: every request answered OK with the oracle's
    output, nothing failed, shed or compiled on the request path, and —
    supervised — no trip and no degradation hid a broken rung."""
    from cuda_mpi_gpu_cluster_programming_tpu.serving.frontend import ServingFrontend
    from cuda_mpi_gpu_cluster_programming_tpu.serving.queue import OK
    from cuda_mpi_gpu_cluster_programming_tpu.serving.server import (
        InferenceServer,
        ServeConfig,
    )

    buckets = tuple(sorted(buckets))
    sizes = [
        min(_REQUEST_SIZES[i % len(_REQUEST_SIZES)], buckets[-1])
        for i in range(n_requests)
    ]
    pool = np.asarray(_input(height, width, sum(_REQUEST_SIZES)))
    want = _oracle_out(height, width, pool)
    picks, at = [], 0
    for n in sizes:  # distinct images per request, so slicing bugs show
        picks.append([(at + j) % len(pool) for j in range(n)])
        at += n
    server = InferenceServer(
        ServeConfig(
            config=config, n_shards=shards, compute="fp32", buckets=buckets,
            max_batch=buckets[-1], supervise=supervise,
            model_cfg=_model_cfg(height, width),
        ),
        params=_params(height, width),
    )
    name = (
        f"served {config} np{shards} "
        f"{'supervised' if supervise else 'unsupervised'} "
        f"{'socket' if over_socket else 'in-process'}"
    )
    frontend = None
    t0 = time.perf_counter()
    server.start()  # builds and warms every bucket: the compile cost
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    try:
        if over_socket:
            frontend = ServingFrontend(server, port=0).start()
            with ThreadPoolExecutor(max_workers=8) as ex:
                outs = list(
                    ex.map(
                        lambda ip: _post_infer(frontend.url, f"s{ip[0]:03d}", pool[ip[1]]),
                        enumerate(picks),
                    )
                )
        else:
            handles = [server.submit(pool[p]) for p in picks]
            for i, h in enumerate(handles):
                h.wait(300.0)
                check(h.status == OK, f"{name}: request {i} ended {h.status} {h.error}")
            outs = [np.asarray(h.result) for h in handles]
    finally:
        if frontend is not None:
            frontend.stop()
        server.stop()
    run_s = time.perf_counter() - t0
    worst = max(
        _agree(out, want[p], "fp32", f"{name}: request {i}")
        for i, (out, p) in enumerate(zip(outs, picks))
    )
    st = server.stats
    check(
        st.n_ok == n_requests and not (st.n_failed or st.n_shed or st.cache_misses),
        f"{name}: offered={n_requests} {st.summary()}",
    )
    if supervise:
        check(
            not server.sup.trips and not server.sup.events,
            f"{name}: supervisor {server.sup.summary()}",
        )
    return PhaseResult(
        name, compile_s, run_s,
        f"{st.summary()} images/request<={max(sizes)} rel_err={worst:.2e}",
    )


def sharded_phase(
    config: str, shards: int, height: int, width: int, *, batch: int
) -> PhaseResult:
    """The within-tier contract and where the rows live. The built forward's
    output is bitwise equal to its tier's single-device forward
    (tests/test_bit_exact.py, checked on the real devices). That output
    comes back gathered — replicated on every device — so it cannot show
    who computed what; the supervisor's digest-tapped build of the same
    forward can: its last-layer tap is taken inside the shard body, before
    the gather, one scalar per shard. Shard i's scalar must sit on its own
    device and be the digest of output rows [i*b, (i+1)*b) and of no others
    — a device that held the whole image, or none of it, fails here."""
    import jax

    from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward
    from cuda_mpi_gpu_cluster_programming_tpu.parallel.plan import make_shard_plan
    from cuda_mpi_gpu_cluster_programming_tpu.parallel.sharded import (
        build_sharded_forward,
    )

    cfg = _model_cfg(height, width)
    exec_cfg = REGISTRY[config]
    params, x = _params(height, width), _input(height, width, batch)
    tier_single = "v3_pallas" if exec_cfg.tier == "pallas" else "v1_jit"
    t0 = time.perf_counter()
    out = build_forward(exec_cfg, cfg, n_shards=shards)(params, x)
    single = build_forward(REGISTRY[tier_single], cfg)(params, x)
    tapped_out, taps = build_sharded_forward(
        cfg, shards, tier=exec_cfg.tier,
        staged=(exec_cfg.strategy == "staged_halo"), with_digests=True,
    )(params, x)
    jax.block_until_ready((out, single, tapped_out, taps))
    wall = time.perf_counter() - t0
    name = f"sharded {config} np{shards} b{batch}"
    print(f"{name}: x.sharding={x.sharding} out.sharding={out.sharding}")
    single = np.asarray(single)
    check(
        bool(np.array_equal(np.asarray(out), single)),
        f"{name}: not bitwise equal to single-device {tier_single}",
    )
    check(
        bool(np.array_equal(np.asarray(tapped_out), single)),
        f"{name}: the digest-tapped forward is not bitwise equal to {tier_single}",
    )
    last = make_shard_plan(cfg, shards).layers[-1]
    tap = taps[last.name]  # (shards,): entry i is shard i's digest of its block
    print(f"{name}: {last.name} tap sharding={tap.sharding}")
    pieces = sorted(tap.addressable_shards, key=lambda p: p.index[0].start)
    check(
        len(pieces) == shards
        and all(p.data.shape == (1,) for p in pieces)
        and len({p.device for p in pieces}) == shards,
        f"{name}: the per-shard taps sit on "
        f"{len({p.device for p in pieces})} device(s) in {len(pieces)} piece(s), "
        f"expected one on each of {shards}",
    )
    for i, piece in enumerate(pieces):
        # sentinel.tree_digest of one leaf, over the rows shard i owns (rows
        # past the image's end are the plan's dead rows and stay zero)
        rows = single[:, i * last.b_out : (i + 1) * last.b_out].astype(np.float64)
        want = float(rows.sum() + np.abs(rows).sum())
        got = float(np.asarray(piece.data)[0])
        check(
            want > 0 and abs(got - want) <= 1e-4 * want,
            f"{name}: device {piece.device.id} digests {got:.6g} at {last.name}, "
            f"rows [{i * last.b_out}, {(i + 1) * last.b_out}) digest {want:.6g}",
        )
    rel = _agree(out, _oracle_out(height, width, x), "fp32", f"{name} vs oracle")
    return PhaseResult(
        name, wall, 0.0,
        f"bitwise == {tier_single}; {last.b_out} rows a device on {shards} "
        f"devices before the gather; rel_err={rel:.2e}",
    )


# ------------------------------------------------------------------ main ---


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, but JAX found platform={dev.platform!r} "
            f"({dev.device_kind} x{len(jax.devices())}); refusing to run",
            file=sys.stderr,
        )
        return 2
    from cuda_mpi_gpu_cluster_programming_tpu.utils.compile_cache import (
        enable_persistent_cache,
    )

    cache_dir = Path(enable_persistent_cache())
    entries_before = len(list(cache_dir.iterdir())) if cache_dir.is_dir() else 0
    n_dev = len(jax.devices())
    jax_v, jaxlib_v, libtpu_v = map(
        importlib.metadata.version, ("jax", "jaxlib", "libtpu")
    )
    print(
        f"chip_smoke: platform={dev.platform} device_kind={dev.device_kind} "
        f"count={n_dev} jax={jax_v} jaxlib={jaxlib_v} libtpu={libtpu_v} "
        f"compile_cache={cache_dir} ({entries_before} entries)"
    )
    t_start = time.perf_counter()
    h = w = 227
    buckets = (1, 8, 32)
    results = [device_phase(), reference_phase(h, w)]
    results.append(
        oneshot_phase("v1_jit", 1, h, w, batch=128, compute="fp32", init="deterministic")
    )
    for config in ("v1_jit", "v3_pallas"):
        for compute in ("fp32", "bf16"):
            results.append(oneshot_phase(config, 1, h, w, batch=128, compute=compute))
    results.append(lowering_phase("v3_pallas", 1, h, w, batch=128))
    for config in ("v1_jit", "v3_pallas"):
        results.append(
            served_phase(config, 1, h, w, buckets, supervise=False, over_socket=False)
        )
        results.append(
            served_phase(
                config, 1, h, w, buckets, supervise=True, over_socket=True,
                n_requests=16,
            )
        )
    if n_dev >= 4:
        for config in ("v2.2_sharded", "v5_collective"):
            results.append(oneshot_phase(config, 4, h, w, batch=128, compute="fp32"))
            results.append(sharded_phase(config, 4, h, w, batch=128))
            results.append(
                served_phase(config, 4, h, w, buckets, supervise=False, over_socket=False)
            )
            results.append(
                served_phase(
                    config, 4, h, w, buckets, supervise=True, over_socket=True,
                    n_requests=16,
                )
            )
        results.append(lowering_phase("v5_collective", 4, h, w, batch=128))
    else:
        print(
            f"chip_smoke: 4-shard phase NOT RUN — v2.2_sharded/v5_collective at "
            f"4 shards need 4 devices, JAX reports {n_dev}"
        )
    entries_after = len(list(cache_dir.iterdir()))
    print("chip_smoke: phase                                            compile_s   run_s  detail")
    for r in results:
        print(f"chip_smoke: {r.name:<52} {r.compile_s:>9.2f} {r.run_s:>7.2f}  {r.detail}")
    print(
        f"chip_smoke: set-up time, not a performance figure — "
        f"compile_s={sum(r.compile_s for r in results):.1f} "
        f"run_s={sum(r.run_s for r in results):.1f} "
        f"wall_s={time.perf_counter() - t_start:.1f} "
        f"cache_entries={entries_before}->{entries_after}"
    )
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": n_dev,
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
