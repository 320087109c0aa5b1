"""From the profiler's trace to numbers: device busy and idle time, time per
operation and per category, the step program's device time, and the idle
gaps named by what the host was doing in them.

Two steps, so that the arithmetic can be checked on a small recorded trace
(tests/benchmark/fixtures/): :func:`load_xplane` turns the profiler's
``.xplane.pb`` into a plain dictionary (``to_json``/``from_json`` store
it), and :class:`Reduced` computes everything from that dictionary.

What a v5e trace looks like (looked at by hand, PR 22, jax 0.9.0): one plane
per chip named ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one event per
executed HLO operation, named by the operation's whole HLO text (``%fusion.12
= bf16[128,27,27,256]{...} fusion(...), kind=kOutput, calls=...``); there is
no ``hlo_category`` stat, so the category is the opcode parsed from that text,
and what a fusion holds (a convolution, a reduce-window) comes from the
compiled program's own HLO text (:func:`fusion_kinds`). Asynchronous copies
and slices appear there as short ``-start``/``-done`` events and, as spans
that overlap the compute, on the line ``Async XLA Ops``, which is not read.
The line ``XLA Modules`` holds one event per run of a jitted program.
``/host:CPU`` has one line per host thread with the profiler's own events and
the benchmark's ``TraceAnnotation`` spans (``bench.*``); its clock agrees with
the device planes' to within a millisecond or two, not better.
"""

from __future__ import annotations

import gzip
import json
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# Host events shorter than this are not kept: they cannot name a gap worth
# listing, and there are a great many of them.
MIN_HOST_EVENT_NS = 2_000

# Which categories count as what (substrings of the category). On the TPU a
# matrix multiplication is a convolution too, so "convolution" is every
# operation that runs on the MXU. An operation in neither set is "other".
CONV_CATEGORIES = ("convolution",)
COLLECTIVE_CATEGORIES = (
    "all-gather", "all-reduce", "all-to-all", "collective-permute",
    "reduce-scatter", "send", "recv", "collective-broadcast",
)

_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"^\(?([a-z0-9]+\[[0-9,]*\])")


def parse_op(text: str) -> Tuple[str, str]:
    """``(label, opcode)`` of an ``XLA Ops`` event's name. ``%fusion.12 =
    bf16[128,27,27,256]{...} fusion(...)`` gives ``("fusion.12
    bf16[128,27,27,256]", "fusion")``; a name that is no HLO text is its own
    label and has no opcode."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, ""
    opname = head.lstrip("%")
    shape = _SHAPE.match(rest)
    found = _OPCODE.search(rest)
    label = f"{opname} {shape.group(1)}" if shape else opname
    return label, found.group(1) if found else ""


def fusion_kinds(hlo_text: str) -> Dict[str, str]:
    """``{fusion's name: what it holds}`` from a compiled program's HLO text:
    ``convolution`` if the fused computation has one, else ``reduce-window``
    if it has one, else the fusion's ``kind`` (``loop``, ``output``...)."""
    bodies: Dict[str, str] = {}
    current, lines = None, []
    for line in hlo_text.splitlines():
        if current is None:
            m = re.match(r"^\s*%?([\w.\-]+) \(.*\{\s*$", line)
            if m:
                current, lines = m.group(1), []
        elif line.strip() == "}":
            bodies[current], current = "\n".join(lines), None
        else:
            lines.append(line)
    kinds: Dict[str, str] = {}
    for m in re.finditer(
        r"%?([\w.\-]+) = .*? fusion\(.*?kind=k(\w+), calls=%?([\w.\-]+)", hlo_text
    ):
        name, kind, called = m.group(1), m.group(2).lower(), m.group(3)
        body = bodies.get(called, "")
        if " convolution(" in body:
            kinds[name] = "convolution"
        elif " reduce-window(" in body:
            kinds[name] = "reduce-window"
        else:
            kinds[name] = kind
    return kinds


def find_xplane(log_dir) -> Path:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path) -> Dict:
    """Read the profiler's file with JAX's own reader into the plain form:
    ``{"devices": {plane: {"ops": [[label, opcode, start_ns, dur_ns]],
    "modules": [[name, start_ns, dur_ns]]}}, "host": [[thread, name,
    start_ns, dur_ns]]}`` (see :func:`parse_op` for label and opcode)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices: Dict[str, Dict[str, list]] = {}
    host: List[list] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            entry = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        label, opcode = parse_op(ev.name)
                        entry["ops"].append(
                            [label, opcode, int(ev.start_ns), int(ev.duration_ns)]
                        )
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        entry["modules"].append(
                            [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        )
            devices[plane.name] = entry
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns >= MIN_HOST_EVENT_NS or ev.name.startswith("bench."):
                        host.append(
                            [line.name, ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        )
    return {"devices": devices, "host": host}


def cut(trace: Dict, start_ns: int, end_ns: int) -> Dict:
    """The part of a trace inside ``[start_ns, end_ns)``, for a fixture."""
    def keep(start, dur):
        return start >= start_ns and start + dur <= end_ns

    return {
        "devices": {
            name: {
                "ops": [e for e in d["ops"] if keep(e[2], e[3])],
                "modules": [e for e in d["modules"] if keep(e[1], e[2])],
            }
            for name, d in trace["devices"].items()
        },
        "host": [e for e in trace["host"] if e[2] < end_ns and e[2] + e[3] > start_ns],
    }


def to_json(trace: Dict, path) -> None:
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "wt") as f:
        json.dump(trace, f, separators=(",", ":"))


def from_json(path) -> Dict:
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def merge_intervals(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """The union of ``(start, end)`` intervals as sorted disjoint ones."""
    out: List[Tuple[int, int]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def _category_of(cat: str, groups: Sequence[str]) -> bool:
    cat = cat.lower()
    return any(g in cat for g in groups)


def _busiest_program(devices: Dict) -> Optional[str]:
    totals: Dict[str, int] = {}
    for d in devices.values():
        for name, _start, dur in d["modules"]:
            key = name.split("(")[0]
            totals[key] = totals.get(key, 0) + dur
    return max(totals, key=totals.get) if totals else None


class Reduced:
    """Everything the per-layer metrics read from one trace."""

    def __init__(self, trace: Dict, window_ns: Optional[Tuple[int, int]] = None,
                 kinds: Optional[Dict[str, str]] = None):
        self.trace = trace
        self.kinds = kinds or {}  # fusion_kinds() of the step program
        with_work = {
            name: d for name, d in sorted(trace["devices"].items()) if d["ops"]
        }
        # The profiler can lose events on one chip (PR 22: chip 0 of four kept
        # 70 of 256 steps, scattered, in every four-chip trace). A plane that
        # shows under nine tenths of the steps the fullest plane shows is
        # named and left out of every sum, or its missing events would read
        # as idle time; busy time and idle share are then means over the
        # planes that are whole.
        program = _busiest_program(with_work)
        steps = {
            name: sum(1 for m in d["modules"] if m[0].split("(")[0] == program)
            for name, d in with_work.items()
        }
        most = max(steps.values(), default=0)
        self.incomplete = sorted(n for n, k in steps.items() if k < 0.9 * most)
        self.planes_with_work = len(with_work)
        self.devices = {
            n: d for n, d in with_work.items() if n not in self.incomplete
        }
        if window_ns is None:
            starts = [e[2] for d in self.devices.values() for e in d["ops"]]
            ends = [e[2] + e[3] for d in self.devices.values() for e in d["ops"]]
            window_ns = (min(starts), max(ends)) if starts else (0, 0)
        self.window_ns = window_ns
        self._busy = {
            name: merge_intervals(
                (max(e[2], window_ns[0]), min(e[2] + e[3], window_ns[1]))
                for e in d["ops"]
                if e[2] < window_ns[1] and e[2] + e[3] > window_ns[0]
            )
            for name, d in self.devices.items()
        }

    # ---- busy and idle -------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    def busy_s_by_device(self) -> Dict[str, float]:
        return {
            name: sum(end - start for start, end in iv) / 1e9
            for name, iv in self._busy.items()
        }

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the chips."""
        per = self.busy_s_by_device()
        return sum(per.values()) / len(per) if per else 0.0

    def idle_share(self) -> Optional[float]:
        """1 - busy / window, as a fraction: the mean over the chips whose
        plane is whole (``incomplete`` names the others), so that it is
        the share that ``busy_s`` and ``window_s`` give. Nothing only when
        no chip shows work."""
        if not self.devices or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    # ---- operations ----------------------------------------------------

    def category(self, label: str, opcode: str) -> str:
        """The opcode; for a fusion, with what it holds where that is known
        (``convolution fusion``)."""
        if opcode == "fusion":
            kind = self.kinds.get(label.split(" ")[0])
            return f"{kind} fusion" if kind else "fusion"
        return opcode

    def _ops(self):
        lo, hi = self.window_ns
        for d in self.devices.values():
            for label, opcode, start, dur in d["ops"]:
                if start < hi and start + dur > lo:
                    yield label, self.category(label, opcode), start, dur

    def op_seconds(self) -> float:
        """Sum of every operation's own duration, over all chips."""
        return sum(dur for _n, _c, _s, dur in self._ops()) / 1e9

    def category_seconds(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for _name, cat, _start, dur in self._ops():
            out[cat] = out.get(cat, 0.0) + dur / 1e9
        return out

    def share_of(self, groups: Sequence[str]) -> Optional[float]:
        """Time of the operations whose category matches ``groups`` over
        the time of all operations (fraction)."""
        total = self.op_seconds()
        if total <= 0:
            return None
        hit = sum(dur for _n, cat, _s, dur in self._ops() if _category_of(cat, groups))
        return hit / 1e9 / total

    def top_ops(self, n: int = 10) -> List[List]:
        """``[name, seconds]`` of the operations that took most time,
        summed over their runs and the chips; the name is the operation's
        own with its output shape and its category."""
        out: Dict[str, float] = {}
        for label, cat, _start, dur in self._ops():
            key = f"{label} [{cat}]" if cat else label
            out[key] = out.get(key, 0.0) + dur / 1e9
        return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:n]]

    # ---- the step program ----------------------------------------------

    def step_program(self) -> Optional[str]:
        """The jitted program that took most device time in the trace."""
        return _busiest_program(self.devices)

    def step_durations_ms(self, program: Optional[str] = None) -> List[float]:
        program = program or self.step_program()
        lo, hi = self.window_ns
        return [
            dur / 1e6
            for d in self.devices.values()
            for name, start, dur in d["modules"]
            if name.split("(")[0] == program and start >= lo and start + dur <= hi
        ]

    # ---- idle gaps -----------------------------------------------------

    def idle_gaps(self, n: int = 10, device: Optional[str] = None) -> List[List]:
        """``[what the host was doing, seconds]``: the idle time of one
        chip (the first, unless named) summed by the host event that best
        explains each gap — the shortest host event that covers at least
        half of the gap, else the one that overlaps it most."""
        if not self._busy:
            return []
        device = device or next(iter(self._busy))
        busy = self._busy[device]
        lo, hi = self.window_ns
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps = [
            (edges[i], edges[i + 1])
            for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]
        ]
        host = sorted(
            (e for e in self.trace["host"] if e[1] != "bench.window"),
            key=lambda e: e[2],
        )
        out: Dict[str, float] = {}
        active: List[list] = []  # host events that may still overlap a gap
        nxt = 0
        for g0, g1 in gaps:  # in time order: one sweep over both lists
            while nxt < len(host) and host[nxt][2] < g1:
                active.append(host[nxt])
                nxt += 1
            active = [e for e in active if e[2] + e[3] > g0]
            best, best_key = "(no host event)", None
            for _thread, name, start, dur in active:
                overlap = min(g1, start + dur) - max(g0, start)
                if overlap <= 0:
                    continue
                covers = overlap * 2 >= (g1 - g0)
                key = (covers, -dur if covers else overlap)
                if best_key is None or key > best_key:
                    best, best_key = name, key
            out[best] = out.get(best, 0.0) + (g1 - g0) / 1e9
        return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:n]]
