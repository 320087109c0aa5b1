"""Device time per layer: the reduced trace joined with the scopes the program
gives its layers (``jax.named_scope``; the program's ``ops/scopes.py``).

The trace names an operation by its instruction (``fusion.12``, ``all-gather.5``);
which layer that instruction belongs to is in the step program's own compiled
HLO text, as ``metadata={op_name="jit(fwd_bf16)/jit(<lambda>)/conv2/conv_general_dilated"}``.
:func:`scope_map` reads it there:

- *A scope* is a path component of ``op_name`` that is a layer of the
  configuration file (``layers``, ``fc``), several of them joined by ``+`` (one
  kernel that covers them: ``conv1+pool1``), ``cast_in``, ``scatter``,
  ``gather`` or ``halo.<layer>``. The innermost one counts, so a halo exchange
  nested in its layer is ``halo.<layer>``. The last component is the primitive's
  own name and is never a scope (``lax.gather`` is not the gather).
- *A fusion* carries only its root's ``op_name``, so its body is read: it
  belongs to the scope of the convolution it holds, else of its reduce-window,
  else of most of its instructions, the root breaking ties. One whose body
  spans several scopes is listed under ``mixed``.
- *A collective with no scope of its own* (the all-gather the compiler puts in
  for the output's sharding) is ``gather``, by its opcode.
- Everything else is ``(unscoped)``, and listed by name and seconds.

The sum over the scopes, ``(unscoped)`` among them, is ``Reduced.op_seconds()``
of the same planes and window. Per-step values are the MEAN over the step
program's runs on every whole chip, so that the scopes of a step add up to its
operations' time. Not the median, as ``step.device_ms`` is: on four chips a
chip waits inside one collective in one step and inside another in the next
(0.2 or 3.3 ms in the all-gather, PR 24's traces), and the median of such a
two-humped reading is neither hump and adds up to a quarter of the step. For a
kernel's own time the two agree to a part in a thousand.

The benchmark imports no list of names from the program: a program that gives
its layers no scope (the parent of the PR that added this) reads as
``kernels.scoped_share`` 0 and every layer's time 0.
"""

from __future__ import annotations

import math
import re
import time
from typing import Callable, Dict, Iterable, List, NamedTuple, Optional, Tuple

from benchmark import trace_reduce

UNSCOPED = "(unscoped)"
CAST_IN, SCATTER, GATHER, HALO = "cast_in", "scatter", "gather", "halo."
BYTES = {"bf16": 2, "fp32": 4}  # per element, by a configuration's ``compute``

_COMPUTATION = re.compile(r"^\s*(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT )?(%?[\w.\-]+ = .*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
# opcodes that do no work of their own inside a fused computation
_PASSIVE = ("parameter", "constant", "bitcast", "get-tuple-element", "tuple")


def layer_names(config: Dict) -> List[str]:
    """The layers a configuration file names: its spatial chain, then the
    fully connected ones (``fc6``... as the family's shape functions name
    their parameters)."""
    spatial = [layer["name"] for layer in config.get("layers", [])]
    return spatial + [f"fc{6 + i}" for i in range(len(config.get("fc") or []))]


def scope_of(op_name: str, layers: Iterable[str]) -> Optional[str]:
    """The innermost scope in an ``op_name`` path, or nothing."""
    layers = set(layers)
    for part in reversed(op_name.split("/")[:-1]):
        if part in (CAST_IN, SCATTER, GATHER) or part in layers:
            return part
        if part.startswith(HALO) and part[len(HALO):] in layers:
            return part
        if "+" in part and set(part.split("+")) <= layers:
            return part
    return None


class Instruction(NamedTuple):
    name: str
    opcode: str
    op_name: Optional[str]
    calls: Optional[str]  # the computation a fusion runs
    root: bool


def _computations(hlo_text: str) -> Dict[str, List[Instruction]]:
    """``{computation: its instructions}`` of a compiled program's HLO text."""
    out: Dict[str, List[Instruction]] = {}
    current = None
    for line in hlo_text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = out.setdefault(m.group(1), [])
        elif line.strip() == "}":
            current = None
        else:
            m = _INSTRUCTION.match(line)
            if m:
                label, opcode = trace_reduce.parse_op(m.group(2))
                op_name, calls = _OP_NAME.search(line), _CALLS.search(line)
                current.append(Instruction(
                    label.split(" ")[0], opcode, op_name.group(1) if op_name else None,
                    calls.group(1) if calls else None, bool(m.group(1)),
                ))
    return out


def _fusion_scope(body: List[Instruction], layers) -> Tuple[Optional[str], List[str]]:
    """``(scope, every scope in the body)`` of one fused computation."""
    scoped = [
        (i, scope_of(i.op_name, layers)) for i in body if i.op_name and i.opcode not in _PASSIVE
    ]
    scoped = [(i, scope) for i, scope in scoped if scope]
    found = sorted({scope for _i, scope in scoped})
    for held in ("convolution", "reduce-window"):
        for i, scope in scoped:
            if i.opcode == held:
                return scope, found
    counts: Dict[str, int] = {}
    for _i, scope in scoped:
        counts[scope] = counts.get(scope, 0) + 1
    if not counts:
        return None, found
    tied = [s for s in found if counts[s] == max(counts.values())]
    roots = [scope for i, scope in scoped if i.root and scope in tied]
    return (roots[0] if roots else tied[0]), found


def scope_map(hlo_text: str, layers: Iterable[str]) -> Tuple[Dict[str, str], Dict[str, List[str]]]:
    """``({instruction: scope}, {fusion: [scopes]} for the mixed ones)`` of a
    compiled program's HLO text. An instruction that is not in the first map
    has no scope."""
    layers = list(layers)
    comps = _computations(hlo_text)
    scopes: Dict[str, str] = {}
    mixed: Dict[str, List[str]] = {}
    for body in comps.values():
        for i in body:
            scope = scope_of(i.op_name, layers) if i.op_name else None
            if i.opcode == "fusion" and i.calls in comps:
                held, found = _fusion_scope(comps[i.calls], layers)
                scope = held or scope
                if len(found) > 1:
                    mixed[i.name] = found
            if scope is None and any(c in i.opcode for c in trace_reduce.COLLECTIVE_CATEGORIES):
                scope = GATHER
            if scope is not None:
                scopes[i.name] = scope
    return scopes, mixed


def exactly(*scopes: str) -> Callable[[str], bool]:
    return lambda scope: scope in scopes


def covers(*layers: str) -> Callable[[str], bool]:
    """Scopes of kernels that hold one of ``layers``: the layer's own, or a
    fused run of layers with it in it; not its halo exchange."""
    return lambda scope: not scope.startswith(HALO) and bool(set(scope.split("+")) & set(layers))


class LayerTimes:
    """One reduced trace split by scope."""

    def __init__(self, reduced, scopes: Dict[str, str], mixed: Optional[Dict[str, List[str]]] = None):
        self.scopes, self.mixed = scopes, mixed or {}
        self.seconds: Dict[str, float] = {}  # scope -> all chips, whole window
        self.unscoped: Dict[str, float] = {}  # operation -> seconds
        for label, _cat, _start, dur in reduced._ops():
            scope = self._scope(label)
            self.seconds[scope] = self.seconds.get(scope, 0.0) + dur / 1e9
            if scope == UNSCOPED:
                self.unscoped[label] = self.unscoped.get(label, 0.0) + dur / 1e9
        self.steps = self._per_step(reduced)

    def _scope(self, label: str) -> str:
        return self.scopes.get(label.split(" ")[0], UNSCOPED)

    def _per_step(self, reduced) -> List[Dict[str, int]]:
        """``{scope: ns}`` of each run of the step program on each whole
        chip: the operations that start inside the run's interval."""
        program = reduced.step_program()
        lo, hi = reduced.window_ns
        rows: List[Dict[str, int]] = []
        for d in reduced.devices.values():
            runs = sorted(
                (start, start + dur) for name, start, dur in d["modules"]
                if name.split("(")[0] == program and start >= lo and start + dur <= hi
            )
            ops = sorted(d["ops"], key=lambda e: e[2])
            i = 0
            for run_start, run_end in runs:
                row: Dict[str, int] = {}
                while i < len(ops) and ops[i][2] < run_start:
                    i += 1
                while i < len(ops) and ops[i][2] < run_end:
                    scope = self._scope(ops[i][0])
                    row[scope] = row.get(scope, 0) + ops[i][3]
                    i += 1
                rows.append(row)
        return rows

    def total_s(self) -> float:
        return sum(self.seconds.values())

    def scoped_share(self) -> Optional[float]:
        """Time of the operations that carry a scope over the time of all
        operations (fraction)."""
        total = self.total_s()
        return None if total <= 0 else 1.0 - self.seconds.get(UNSCOPED, 0.0) / total

    def step_ms(self, select: Callable[[str], bool]) -> float:
        """Milliseconds per step in the scopes ``select`` picks: the mean over
        steps and chips of their sum. 0 where nothing carries them."""
        if not self.steps:
            return 0.0
        total = sum(ns for row in self.steps for scope, ns in row.items() if select(scope))
        return total / 1e6 / len(self.steps)

    def table(self) -> List[Tuple[str, float, float]]:
        """``[(scope, ms per step, seconds over the window)]``, largest first."""
        rows = [(s, self.step_ms(exactly(s)), secs) for s, secs in self.seconds.items()]
        return sorted(rows, key=lambda r: -r[2])


def step_hlo_text(ctx) -> Optional[str]:
    """The step program's compiled HLO text: what the context carries, else,
    in a real run, the cell's forward built again through its adapter and
    lowered on the cell's shapes, after every window is over. Where the
    program keys its compile cache on the metadata too (as it does, so that
    no build reads another's names) this is a compile of its own, 2.5 to 10 s
    on a v5e: the key then covers each operation's Python call stack, and
    this call site is another than the driver's. Nothing where neither can
    be had, or where building it fails: a reader never ends a run."""
    text = getattr(ctx, "step_hlo_text", None)
    if text is not None:
        return text
    adapter, batch = getattr(ctx, "adapter", None), ctx.counters.get("offline.batch")
    if adapter is None or not batch:
        ctx.log("layer times: no step program text to be had here (no adapter, or no offline batch)")
        return None
    try:
        import jax
        import jax.numpy as jnp

        cfg = ctx.config
        params = jax.eval_shape(lambda: adapter.make_params(cfg, 0))
        x = jax.ShapeDtypeStruct(adapter.input_shape(cfg, int(batch)), jnp.float32)
        return adapter.build_forward(cfg).lower(params, x).compile().as_text()
    except Exception as e:  # noqa: BLE001 — a metric's reader must not end the run
        ctx.log(f"layer times: the step program could not be lowered again: {e!r}")
        return None


def of(ctx) -> Optional[LayerTimes]:
    """This run's :class:`LayerTimes`, made once and kept on the context.
    Nothing without a device plane (a CPU rehearsal), before any work."""
    if getattr(ctx, "trace", None) is None or not ctx.trace.devices:
        return None
    kept = getattr(ctx, "layer_times", None)
    if kept is not None:
        return kept
    t0 = time.perf_counter()
    text = step_hlo_text(ctx)
    t1 = time.perf_counter()
    scopes, mixed = scope_map(text, layer_names(ctx.config)) if text else ({}, {})
    lt = ctx.layer_times = LayerTimes(ctx.trace, scopes, mixed)
    ctx.log(
        f"layer times: step program text in {t1 - t0:.2f} s, joined with the trace in "
        f"{time.perf_counter() - t1:.2f} s; {len(lt.steps)} steps on {len(ctx.trace.devices)} "
        f"whole chip(s); scopes + {UNSCOPED} = {lt.total_s():.6f} s of {ctx.trace.op_seconds():.6f} s"
    )
    for scope, ms, secs in lt.table():
        ctx.log(f"layer times: {scope:>16s} {ms:9.4f} ms/step {secs:10.6f} s")
    for fusion, found in sorted(lt.mixed.items()):
        ctx.log(f"layer times: {fusion} holds {' + '.join(found)}; counted under {scopes.get(fusion, UNSCOPED)}")
    listed = [(label, secs) for label, secs in lt.unscoped.items() if secs >= 1e-6]
    for label, secs in sorted(listed, key=lambda kv: -kv[1])[:12]:
        ctx.log(f"layer times: {UNSCOPED} {label}: {secs:.6f} s")
    ctx.log(
        f"layer times: {UNSCOPED} {len(lt.unscoped) - len(listed)} more operations under a "
        f"microsecond each, {sum(lt.unscoped.values()) - sum(s for _l, s in listed):.9f} s together"
    )
    return lt


def ms(ctx, select: Callable[[str], bool]) -> Optional[float]:
    """A reader's whole body for a ``*_ms`` metric."""
    lt = of(ctx)
    return None if lt is None else lt.step_ms(select)


def layer_work(shapes, config: Dict, layers: Iterable[str], batch: int) -> Tuple[float, float]:
    """``(matmul FLOPs, bytes)`` one step of a run of consecutive layers
    cannot avoid: 2 x multiply-accumulates of its convolutions and FC layers;
    the run's input read, its parameters read once and its output written, in
    the configuration's compute type (what the layer's own kernels move: the
    cast from float32 is ``cast_in``'s). Activations between the run's layers
    are not counted: a perfect fusion keeps them on the chip."""
    layers = set(layers)
    flops, first_in, last_out = 0, None, 0
    for layer, (h, w, c_in), (ho, wo, c_out) in shapes.layer_dims(config):
        if layer["name"] in layers:
            if layer["kind"] == "conv":
                flops += 2 * ho * wo * c_out * layer["filter_size"] ** 2 * c_in
            first_in = first_in if first_in is not None else h * w * c_in
            last_out = ho * wo * c_out
    for i, (n_in, n_out) in enumerate(shapes.fc_dims(config)):
        if f"fc{6 + i}" in layers:
            flops += 2 * n_in * n_out
            first_in = first_in if first_in is not None else n_in
            last_out = n_out
    params = sum(
        math.prod(w_shape) + b_shape[0]
        for name, (w_shape, b_shape) in shapes.param_shapes(config).items()
        if name in layers
    )
    width = BYTES[config["compute"]]
    return float(flops * batch), float(width * (batch * ((first_in or 0) + last_out) + params))


def roofline_pct(ctx, *layers: str) -> Optional[float]:
    """A reader's whole body for a ``<layers>_roofline`` metric: the least
    time the peaks of the chips the cell uses allow these layers' step (the
    larger of FLOPs over the compute type's peak and bytes over the HBM peak)
    over the time of the kernels that hold them, in percent. The bound that
    binds is logged. 0 where no kernel carries the layers' names."""
    lt = of(ctx)
    batch = ctx.counters.get("offline.batch")
    if lt is None or ctx.peaks is None or not batch:
        return None
    step_ms = lt.step_ms(covers(*layers))
    if step_ms <= 0:
        return 0.0
    chips = len(ctx.devices)
    flops, bytes_ = layer_work(ctx.shapes, ctx.config, layers, int(batch))
    t_flops = flops / (ctx.peaks[f"{ctx.config['compute']}_tflops"] * 1e12 * chips)
    t_bytes = bytes_ / (ctx.peaks["hbm_gbps"] * 1e9 * chips)
    ctx.log(
        f"roofline of {'+'.join(layers)} on {chips} chip(s): {flops / 1e9:.1f} GFLOP -> "
        f"{t_flops * 1e3:.4f} ms at peak, {bytes_ / 1e6:.1f} MB -> {t_bytes * 1e3:.4f} ms at peak; "
        f"{'compute' if t_flops >= t_bytes else 'memory'}-bound; {step_ms:.4f} ms on the device"
    )
    return 100.0 * max(t_flops, t_bytes) * 1e3 / step_ms
