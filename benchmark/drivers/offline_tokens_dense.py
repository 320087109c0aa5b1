"""Offline token traffic for a dense model whose whole logits are checked:
``drivers/offline_tokens.py``'s closed loop (its pool, its chains, its windows
and its step program built ahead of time, all by import) with a check that
holds EVERY token to the configuration's two limits and works a block of
tokens at a time.

Why a driver of its own: the routed families' check (``offline_tokens.check``)
keeps the timed logits, the reference's and a dozen temporaries of their size
on the host at once (the masked copies, both as float64 in the harness's
``check``, their difference and its absolute value). At 4,096 tokens over
200,064 ids one such array is 3.28 GB and the check's peak 39 GB beside the
process's own, past the 40 GiB a one-chip machine has (my chip run, PR 39: the
run was ended at the last comparison, every number already logged). A dense
model routes nothing, so there is no slack to sort tokens by and nothing to
set aside; the two limits are the same two, computed the same way:

- ``rel_max``: max|got - ref| / max|ref| over every token;
- ``rel_rms``: rms(got - ref) / rms(ref) of the MEDIAN token (each token's
  mean squared error over the vocabulary, over the reference's mean square).

Traffic file: as ``offline_tokens`` (``batch``, ``seq_len``, ``pool_batches``,
``chain_len``, ``sample_sequences``, ``trace_seconds``). The reference is asked
for ``forward(cfg, params, ids)`` on the host; the peak here is the two arrays
and one block's temporaries.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark import loadgen
from benchmark.drivers.offline import log_window, run_chains
from benchmark.drivers.offline_tokens import _Built, make_pool  # noqa: F401 — make_pool: the tools' way to the ids

TOKEN_BLOCK = 256  # tokens whose differences are held at a time


def check(ctx, step, params, ids, n_seq: int) -> bool:
    """The first ``n_seq`` sequences of one batch, as the step program
    computes them inside the whole batch, against the plain reference: every
    token under both limits, a block of tokens at a time."""
    import numpy as np

    tol = ctx.config["tolerance"]
    got = np.asarray(step(params, ids)[:n_seq])
    want = np.asarray(ctx.reference.forward(ctx.config, params, ids[:n_seq]))
    if got.shape != want.shape:
        ctx.log(f"check: NOT CORRECT: the step gives {got.shape}, the reference {want.shape}")
        return False
    got, want = got.reshape(-1, got.shape[-1]), want.reshape(-1, want.shape[-1])
    tokens = got.shape[0]
    worst, squared, ref_squared, ref_max, finite = np.zeros(tokens), np.zeros(tokens), 0.0, 0.0, True
    for t0 in range(0, tokens, TOKEN_BLOCK):
        rows = slice(t0, t0 + TOKEN_BLOCK)
        diff = got[rows].astype(np.float64) - want[rows]
        finite = finite and bool(np.isfinite(diff).all())
        worst[rows] = np.abs(diff).max(axis=-1)
        squared[rows] = np.mean(diff * diff, axis=-1)
        ref_squared += float(np.sum(want[rows].astype(np.float64) ** 2))
        ref_max = max(ref_max, float(np.abs(want[rows]).max()))
    ref_mean_square = ref_squared / want.size
    rel_max = float(worst.max() / ref_max) if finite and ref_max > 0 else float("inf")
    token_ms = squared / ref_mean_square
    rel_rms = float(np.sqrt(np.median(token_ms))) if finite else float("inf")
    ctx.counters["check.ref_tokens"] = float(tokens)
    ctx.counters["check.rel_rms"] = rel_rms
    ctx.counters["check.rel_err"] = rel_max
    ok = rel_max <= float(tol["rel_max"]) and rel_rms <= float(tol["rel_rms"])
    by_worst = np.sort(worst)[::-1][:8] / ref_max if ref_max > 0 else worst[:8]
    ctx.log(
        f"check: every one of the {tokens} tokens of {n_seq} sequences against the plain reference: "
        f"max|diff|/max|ref| = {rel_max:.3e} (tolerance {tol['rel_max']:g}); rms(diff)/rms(ref) of the median token = "
        f"{rel_rms:.3e} (limit {tol['rel_rms']:g}), over every token {float(np.sqrt(token_ms.mean())):.3e}, of the worst "
        f"token {float(np.sqrt(token_ms.max())):.3e}; the worst tokens: {' '.join(f'{e:.3e}' for e in by_worst)} "
        f"-> {'correct' if ok else 'NOT CORRECT'}"
    )
    return ok


def run(ctx) -> Dict:
    """``offline_tokens.run`` with this module's check."""
    import jax

    cfg, traffic, adapter = ctx.config, ctx.traffic, ctx.adapter
    batch, seq_len, chain_len = int(traffic["batch"]), int(traffic["seq_len"]), int(traffic["chain_len"])
    if seq_len != cfg["seq_len"]:
        raise ValueError(f"traffic seq_len {seq_len} is not the configuration's {cfg['seq_len']}")

    with ctx.span("setup.params"):
        params = jax.block_until_ready(adapter.make_params(cfg, ctx.seed))
    with ctx.span("setup.build"):
        fwd = adapter.build_forward(cfg)
    with ctx.span("setup.pool"):
        pool = make_pool(cfg, batch, seq_len, int(traffic["pool_batches"]), ctx.seed)
    with ctx.span("build.compile", shape=str(pool[0].shape)):
        step = fwd.lower(params, pool[0]).compile()
        jax.block_until_ready(step(params, pool[0]))
    ctx.step_hlo_text = step.as_text()
    with ctx.span("setup.warm"):
        run_chains(ctx, step, params, pool, batch, chain_len, 0.0, [])
        ctx.spans.pop("bench.chain", None), ctx.spans.pop("bench.fence", None)
    ctx.log(
        "set-up: " + ", ".join(
            f"{name} {ctx.span_seconds(name):.1f} s"
            for name in ("setup.params", "setup.build", "setup.pool", "build.compile", "setup.warm")
        )
    )
    ctx.setup_done()

    rates: List[float] = []
    if ctx.trace_on:
        with ctx.measured():
            plain = run_chains(ctx, step, params, pool, batch, chain_len, ctx.seconds / 2, rates)
        log_window(ctx, "untraced", plain, rates, chain_len, batch)
        ctx.samples["offline.rate_img_s"] = list(rates)
        ctx.samples["offline.window_rate_img_s"] = [plain["images"] / plain["seconds"]]
        traced: List[float] = []
        with ctx.profile(), ctx.measured():
            done = run_chains(ctx, step, params, pool, batch, chain_len, float(traffic["trace_seconds"]), traced)
        log_window(ctx, "traced", done, traced, chain_len, batch)
        for key in ("attempted", "failed"):
            done[key] += plain[key]
        ctx.name_fusions(_Built(step))
        ctx.log(f"gauges of one batch, by the program: {adapter.layer_statistics(cfg, params, pool[0])}")
    else:
        with ctx.measured():
            done = run_chains(ctx, step, params, pool, batch, chain_len, ctx.seconds, rates)
        log_window(ctx, "measured", done, rates, chain_len, batch)
    ctx.counters["offline.batch"] = batch
    median = loadgen.median(rates)
    ctx.log(
        f"sequences of {seq_len} tokens: median chain rate "
        f"{median if median is None else round(median * seq_len, 1)} tokens/s"
    )
    ctx.log(f"peak device memory before the check {ctx.memory_peak_bytes() / 1e9:.3f} GB")
    ok = check(ctx, step, params, pool[0], int(traffic["sample_sequences"]))
    if ctx.trace_on and ctx.trace is not None and ctx.devices[0].platform == "tpu":
        if ctx.trace.planes_with_work < ctx.cell["chips"]:
            ctx.log(
                f"NOT CORRECT: work on {ctx.trace.planes_with_work} device planes, the cell "
                f"asks for {ctx.cell['chips']} chips"
            )
            ok = False
    return {
        "attempted": done["attempted"],
        "failed": done["failed"],
        "correct": ok,
        "values": {"images_per_s": median},
    }
