"""Offline token traffic: the closed loop of ``drivers/offline.py`` (its
``run_chains`` and ``log_window``, by import) over a pool of distinct seeded
batches of token ids that wait on the device. One item of the pile is one
whole sequence, so ``images_per_s`` counts sequences per second here; the
tokens per second are logged beside it.

Traffic file: ``batch`` (sequences a step), ``seq_len``, ``pool_batches``,
``chain_len``, ``sample_sequences`` (whole sequences of one batch compared with
the plain reference) and ``trace_seconds``.

It differs from the image driver in three things. The pool is int32 ids drawn
from the seed over the configuration's vocabulary slice. The check compares
the logits of whole sampled sequences, as the timed forward produced them at
the timed shape, with the plain reference run on those sequences alone
(attention is causal and batch rows are independent, so the reference needs
no more), under the limits of the configuration's ``tolerance``:

- ``rel_max``: max|got - ref| / max|ref| over the tokens whose routing slack,
  by the reference, is at least ``route_margin`` (``reference/mla_moe.py`` says
  what the slack is), the worst ``flip_share`` of them set aside: a dropped or
  misrouted pair, a wrong weight or a lower precision shows here. The top-k
  is a step function of the scores, so a token near a tie may go another way
  under any rounding and then differs by a whole expert; the slack keeps such
  tokens out, and ``flip_share`` (a few tokens in a thousand) is for the one
  whose scores drifted further than the margin by the last layer. A fault of
  the program's is not that rare: it meets every token alike;
- ``rel_rms``: rms(got - ref) / rms(ref) of the MEDIAN token of clear routing
  (each token's mean squared error over the vocabulary, the median of them):
  a precision below the stated one moves every token and shows here. The mean
  over the clear tokens, and over every token, is logged only: a token whose
  routing flipped perturbs the tokens that attend to it, clear ones too, and
  a few such clusters carry a fifth of the mean in one run and none in the next;
- ``min_clear_share``: at least this share of the tokens must be held to
  ``rel_max``, or the check has lost its teeth.

And the step program is built once, ahead of time, from the jitted forward the
program hands over: its compiled text is ``ctx.step_hlo_text`` (the scopes the
per-layer readers join with the trace) and answers ``ctx.name_fusions`` too.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark import loadgen
from benchmark.drivers.offline import log_window, run_chains

MARGINS = (1e-3, 2e-3, 3e-3, 5e-3, 7e-3, 1e-2, 2e-2)  # logged beside the configuration's own
FLIPPED = 0.05  # a token this far off has lost or gained a whole expert


def make_pool(cfg: Dict, batch: int, seq_len: int, n: int, seed: int) -> list:
    """``n`` distinct batches of uniform int32 ids over the vocabulary slice,
    each made on the device by one run of one small jitted program."""
    import jax
    import jax.numpy as jnp

    draw = jax.jit(lambda key: jax.random.randint(key, (batch, seq_len), 0, cfg["vocab_size"], jnp.int32))
    keys = jax.random.split(jax.random.fold_in(jax.random.key(seed), 2), n)
    pool = [draw(k) for k in keys]
    jax.block_until_ready(pool)
    return pool


class _Built:
    """What ``ctx.name_fusions`` asks of a jitted function, answered by the
    step program that was already built."""

    def __init__(self, compiled):
        self._compiled = compiled

    def lower(self, *_args):
        return self

    def compile(self):
        return self._compiled


def check(ctx, step, params, ids, n_seq: int) -> bool:
    """The first ``n_seq`` sequences of one batch, as the step program
    computes them inside the whole batch, against the plain reference."""
    import numpy as np

    cfg, tol = ctx.config, ctx.config["tolerance"]
    got = np.asarray(step(params, ids)[:n_seq])
    want, slack, pairs = ctx.reference.forward_checked(cfg, params, ids[:n_seq])
    want, slack = np.asarray(want), np.asarray(slack)
    ctx.counters["check.ref_pairs_held"] = float(pairs)
    ctx.counters["check.ref_tokens"] = float(slack.size)
    if got.shape != want.shape or not np.isfinite(got).all():
        return ctx.check(got, want, f"{n_seq} sequences")
    got, want, slack = got.reshape(-1, got.shape[-1]), want.reshape(-1, want.shape[-1]), slack.reshape(-1)
    scale = float(np.abs(want).max())
    per_token = np.abs(got - want).max(axis=-1) / scale
    for margin in MARGINS:
        kept = slack >= margin
        ctx.log(
            f"check: routing slack >= {margin:g}: {100.0 * kept.mean():.1f}% of the tokens, "
            f"max|diff|/max|ref| over them {per_token[kept].max() if kept.any() else float('nan'):.3e}, "
            f"{int((per_token[kept] > FLIPPED).sum())} of them over {FLIPPED:g}"
        )
    clear = slack >= float(tol["route_margin"])

    # per token: its mean squared error over the vocabulary, over the reference's mean square
    token_ms = np.mean((got - want) ** 2, axis=-1) / np.mean(want[clear] ** 2 if clear.any() else want**2)
    rms = float(np.sqrt(np.median(token_ms[clear]))) if clear.any() else float("inf")
    ctx.counters["check.rel_rms"] = rms
    ctx.counters["check.clear_share"] = float(clear.mean())
    # the worst ``flip_share`` of the clear tokens are set aside from ``rel_max``
    worst_first = np.flatnonzero(clear)[np.argsort(-per_token[clear], kind="stable")]
    aside = worst_first[: int(float(tol["flip_share"]) * worst_first.size)]
    held = clear.copy()
    held[aside] = False
    ctx.log(
        f"check: {100.0 * clear.mean():.1f}% of the {slack.size} tokens have routing slack >= "
        f"{tol['route_margin']:g} (at least {100.0 * tol['min_clear_share']:g}%); rms(diff)/rms(ref) of the "
        f"median one = {rms:.3e} (limit {tol['rel_rms']:g}), over all of them "
        f"{np.sqrt(token_ms[clear].mean()) if clear.any() else float('nan'):.3e}, over every token "
        f"{np.sqrt(token_ms.mean()):.3e}; the reference routed {pairs} pairs to the held experts; "
        f"worst token over all {per_token.max():.3e}"
    )
    ctx.log(
        f"check: the worst clear tokens: {' '.join(f'{e:.3e}' for e in per_token[worst_first[:8]])}; "
        f"{aside.size} set aside (flip_share {tol['flip_share']:g}); "
        f"{int((per_token[clear] > float(tol['rel_max'])).sum())} clear tokens over {tol['rel_max']:g}"
    )
    ok = rms <= float(tol["rel_rms"]) and clear.mean() >= float(tol["min_clear_share"])
    # ``got[held]`` against ``want[held]`` is scaled by max|ref| over the held tokens
    return bool(ctx.check(got[held], want[held], f"{int(held.sum())} tokens of {n_seq} sequences") and ok)


def run(ctx) -> Dict:
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
            done = run_chains(
                ctx, step, params, pool, batch, chain_len, float(traffic["trace_seconds"]), traced
            )
        log_window(ctx, "traced", done, traced, chain_len, batch)
        for key in ("attempted", "failed"):
            done[key] += plain[key]
        ctx.name_fusions(_Built(step))
        stats = adapter.routing_statistics(cfg, params, pool[0])
        ctx.log(f"routing of one batch, by the program: {stats}")
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
