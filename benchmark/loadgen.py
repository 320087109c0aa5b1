"""Traffic generation and the latency arithmetic, owned by the benchmark.

One general generator reads a traffic file's parameters; a new mix is a new
data file. Copied in method from the program's ``serving/loadgen.py`` and
``serving/traffic.py`` (seeded exponential gaps, weighted class and size
draws, nearest-rank percentile) with the two faults repaired here: latency
is taken from the time a request was DUE, and how late the generator sent
it is reported beside it.

Stdlib only: importing this touches neither numpy nor JAX.
"""

from __future__ import annotations

import math
import random
from typing import Dict, List, Optional, Sequence, Tuple


def arrivals(spec: Dict, seconds: float, seed: int) -> List[float]:
    """Sorted due times (seconds from the window's start) of a seeded
    Poisson process at ``spec["rate_rps"]``. An optional ``spec["bursts"]``
    = ``{"every_s", "width_s", "mult"}`` adds, every ``every_s``, a clump
    of ``width_s`` seconds at ``mult`` times the base rate on top."""
    rate = float(spec["rate_rps"])
    if rate <= 0 or seconds <= 0:
        return []
    rng = random.Random(f"benchmark.arrivals:{seed}")
    out = _poisson(rng, rate, 0.0, seconds)
    bursts = spec.get("bursts")
    if bursts:
        every, width = float(bursts["every_s"]), float(bursts["width_s"])
        if every <= 0 or width <= 0:
            raise ValueError(f"bursts need every_s and width_s above 0: {bursts}")
        t0 = every
        while t0 < seconds:
            end = min(t0 + width, seconds)
            out.extend(_poisson(rng, rate * float(bursts["mult"]), t0, end))
            t0 += every
        out.sort()
    return out


def _poisson(rng: random.Random, rate: float, start: float, end: float) -> List[float]:
    t, out = start, []
    while True:
        t += rng.expovariate(rate)
        if t >= end:
            return out
        out.append(t)


def assign_sizes(classes: Sequence[Dict], n: int, seed: int) -> List[Tuple[str, int]]:
    """Seeded ``(class name, images)`` for each of ``n`` arrivals. A class
    is ``{"name", "weight", "sizes", "size_weights"}``; it sets a request's
    size and nothing else (no deadline, no SLO)."""
    rng = random.Random(f"benchmark.classes:{seed}")
    weights = [float(c["weight"]) for c in classes]
    out = []
    for _ in range(n):
        c = rng.choices(classes, weights=weights)[0]
        size = rng.choices(c["sizes"], weights=c["size_weights"])[0]
        out.append((c["name"], int(size)))
    return out


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile, ``q`` in [0, 100]: always a value that was
    observed, never an interpolated one. None for no sample."""
    if not xs:
        return None
    s = sorted(xs)
    rank = math.ceil(q / 100.0 * len(s))
    return s[min(max(rank, 1), len(s)) - 1]


def median(xs: Sequence[float]) -> Optional[float]:
    """The middle value (mean of the middle two for an even count)."""
    if not xs:
        return None
    s = sorted(xs)
    mid = len(s) // 2
    return s[mid] if len(s) % 2 else 0.5 * (s[mid - 1] + s[mid])
