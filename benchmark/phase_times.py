"""Device time per phase of a layer: the second, nested level of names the
program gives what runs inside ``moe.experts`` and ``moe.route``
(``jax.named_scope``, as ``moe.experts/experts.products/...`` in an
instruction's ``op_name``), read the way ``layer_times`` reads the layers.

The phase names are this file's own list: the benchmark imports no names from
the program, and a program that names no phase (the parent of the PR that
added this) reads 0.0 from every reader here.

An operation belongs to a phase only inside the layer ``layer_times`` gave it:
its phase is the innermost name of ``PHASES_READ`` in its ``op_name`` (a
fusion's by ``layer_times``' rules: most of its instructions, the root
breaking ties) if that phase stands in the operation's layer, else it is the
layer's ``(no phase)``. So a layer's phases and its ``(no phase)`` partition
exactly the operations ``scope_roofline.body_ms`` counts for the layer, and
every metric that reads the layer from outside reads what it read before.
Times are ``scope_roofline.body_ms``: per step, mean over steps and whole
chips, the ``while``s left out (a loop lasts as long as its body, whose
operations the trace lists too).
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

from benchmark import layer_times, scope_roofline

# layer -> the phases that may stand inside it
PHASES_OF = {
    "moe.experts": ("experts.gather", "experts.products", "experts.layout", "experts.combine"),
    "moe.route": ("route.score", "route.sort"),
}
PHASES_READ = tuple(phase for phases in PHASES_OF.values() for phase in phases)
NO_PHASE = " (no phase)"  # after the layer's name: ``moe.experts (no phase)``


class PhaseTimes:
    """``layer_times.LayerTimes``' scopes with the layers of ``PHASES_OF``
    split into their phases; ``ms`` keeps what it has summed."""

    def __init__(self, ctx, lt, text: Optional[str]):
        fine, self.mixed = (
            layer_times.scope_map(text, layer_times.layer_names(ctx.config) + list(PHASES_READ))
            if text else ({}, {})
        )
        self.scopes: Dict[str, str] = {}
        for instruction, layer in lt.scopes.items():
            if layer in PHASES_OF:
                phase = fine.get(instruction)
                layer = phase if phase in PHASES_OF[layer] else layer + NO_PHASE
            self.scopes[instruction] = layer
        self.named = any(scope in PHASES_READ for scope in self.scopes.values())
        self._ctx, self._ms = ctx, {}

    def largest(self, scope: str, steps: int, n: int = 6) -> List[Tuple[str, float]]:
        """``[(operation, ms per step)]``, the ``n`` largest under ``scope``
        over the whole window (a microsecond a step and more; no loop)."""
        seconds: Dict[str, float] = {}
        for d in self._ctx.trace.devices.values():
            for label, opcode, _start, dur in d["ops"]:
                if opcode not in scope_roofline.CONTAINERS and self.scopes.get(label.split(" ")[0]) == scope:
                    seconds[label] = seconds.get(label, 0.0) + dur / 1e9
        rows = sorted(((label, 1e3 * s / max(steps, 1)) for label, s in seconds.items()), key=lambda r: -r[1])
        return [row for row in rows[:n] if row[1] >= 1e-3]

    def ms(self, scope: str) -> float:
        if scope not in self._ms:
            self._ms[scope] = scope_roofline.body_ms(self._ctx, self, scope)
        return self._ms[scope]


def of(ctx) -> Optional[PhaseTimes]:
    """This run's :class:`PhaseTimes`, made once and kept on the context;
    nothing without a device plane. The step program's text is
    ``layer_times.step_hlo_text``'s, kept on the context too, so that every
    reader here shares one further lowering where the driver left no text."""
    lt = layer_times.of(ctx)
    if lt is None:
        return None
    kept = getattr(ctx, "phase_times", None)
    if kept is not None:
        return kept
    t0 = time.perf_counter()
    text = layer_times.step_hlo_text(ctx)
    if text is not None:
        ctx.step_hlo_text = text
    t1 = time.perf_counter()
    pt = ctx.phase_times = PhaseTimes(ctx, lt, text)
    for layer, phases in PHASES_OF.items():
        whole = scope_roofline.body_ms(ctx, lt, layer)
        rows = [(phase, pt.ms(phase)) for phase in phases] + [("(no phase)", pt.ms(layer + NO_PHASE))]
        for phase, ms_ in rows:
            ctx.log(
                f"phase times: {layer:>12s} {phase:>16s} {ms_:9.4f} ms/step "
                f"{100.0 * ms_ / whole if whole > 0 else 0.0:6.2f}% of the layer"
            )
        ctx.log(
            f"phase times: {layer:>12s} {'phases + (no phase)':>16s} {sum(ms_ for _p, ms_ in rows):9.6f} ms/step "
            f"of {whole:9.6f} (the layer without its loops' own durations)"
        )
        for label, ms_ in pt.largest(layer + NO_PHASE, len(lt.steps)):
            ctx.log(f"phase times: {layer:>12s} {'(no phase)':>16s} {ms_:9.4f} ms/step {label}")
    for fusion, found in sorted(pt.mixed.items()):
        if len([scope for scope in found if scope in PHASES_READ]) > 1:
            ctx.log(f"phase times: {fusion} holds {' + '.join(found)}; counted under {pt.scopes.get(fusion)}")
    ctx.log(
        f"phase times: step program text in {t1 - t0:.2f} s, split and joined with the trace in "
        f"{time.perf_counter() - t1:.2f} s; the program names {'its' if pt.named else 'no'} phases"
    )
    return pt


def ms(ctx, *phases: str) -> Optional[float]:
    """A reader's whole body for a ``*_ms`` metric over one or more phases:
    0.0 where the program names none."""
    pt = of(ctx)
    return None if pt is None else sum(pt.ms(phase) for phase in phases)


def pct(ctx, phase: str, work: Callable[[object, int], Tuple[float, float]]) -> Optional[float]:
    """As ``scope_roofline.pct`` for one phase: ``work(ctx, batch) ->
    (operations, bytes)`` of one whole step in it, asked only where an
    operation carries the phase."""
    pt = of(ctx)
    if pt is None:
        return None
    batch = ctx.counters.get("offline.batch")
    if ctx.peaks is None or not batch:
        return None
    step_ms = pt.ms(phase)
    if step_ms <= 0:
        return 0.0
    chips = len(ctx.devices)
    flops, bytes_ = work(ctx, int(batch))
    t_flops = flops / (ctx.peaks[f"{ctx.config['compute']}_tflops"] * 1e12 * chips)
    t_bytes = bytes_ / (ctx.peaks["hbm_gbps"] * 1e9 * chips)
    ctx.log(
        f"roofline of {phase} on {chips} chip(s): {flops / 1e9:.1f} GFLOP -> {t_flops * 1e3:.4f} ms "
        f"at peak, {bytes_ / 1e6:.1f} MB -> {t_bytes * 1e3:.4f} ms at peak; "
        f"{'compute' if t_flops >= t_bytes else 'memory'}-bound; {step_ms:.4f} ms on the device"
    )
    return 100.0 * max(t_flops, t_bytes) * 1e3 / step_ms


def pairs_held(ctx, batch: int) -> float:
    """The (token, expert) pairs of one step that fall to the held experts, as
    ``kernels.moe_experts_roofline`` takes them: what the plain REFERENCE
    routed there on the checked sequences, scaled to the step's tokens; where
    the check has not run, a uniform router's share."""
    ref_pairs, ref_tokens = ctx.counters.get("check.ref_pairs_held"), ctx.counters.get("check.ref_tokens")
    if ref_pairs and ref_tokens:
        return ref_pairs * batch * ctx.config["seq_len"] / ref_tokens
    return ctx.shapes.expected_pairs_per_step(ctx.config, batch)


def products_work(ctx, batch: int) -> Tuple[float, float]:
    """The grouped products' own work, whatever tile, chunk or kernel runs
    them: the family's ``experts_flops`` for the step's pairs; every held
    expert of every MoE layer read once (``experts_bytes`` of no pair), and
    per pair one row read and one written in the compute type."""
    cfg, shapes = ctx.config, ctx.shapes
    pairs = pairs_held(ctx, batch)
    rows = pairs * cfg["hidden_size"] * 2 * layer_times.BYTES[cfg["compute"]]
    return shapes.experts_flops(cfg, pairs), shapes.experts_bytes(cfg, 0.0) + rows
