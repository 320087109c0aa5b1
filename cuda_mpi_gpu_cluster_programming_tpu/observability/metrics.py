"""Process-wide metrics registry: counters, gauges, histograms.

Every subsystem so far has grown its own ad-hoc counters
(``ServeStats``, supervisor ``attempts``/``replays``, the retry
``FaultLog``); this registry is the one place a process accumulates
named metrics so the CLI summary lines and the tests read from a
single source:

- :class:`Counter` (monotonic ``inc``), :class:`Gauge` (last ``set``
  wins), :class:`Histogram` (``observe`` + nearest-rank p50/p99 via the
  serving helper — the SAME estimator the journal readers use, so a
  metrics percentile and a journal percentile of the same stream agree).
- :meth:`MetricsRegistry.summary` is the compact dict form;
  :meth:`MetricsRegistry.export` writes one JSON line per
  metric through the PR 3 atomic-write helper (readers see the old
  complete export or the new one, never a torn file).

Registration and observation are thread-safe (the serving dispatch
thread observes while the load thread submits). Import-light: stdlib +
``resilience.journal``; the nearest-rank helper is imported lazily at
percentile time (``serving.loadgen`` pulls numpy).

Observing inside a timed region is the same contract violation as a
journal write there — staticcheck's ``span-write-in-timed-region`` rule
flags ``.observe(``/``.inc(`` in timed loops unless the enclosing
function is ``@off_timed_path``.
"""

from __future__ import annotations

import json
import re
import threading
from typing import Dict, List, Optional

from ..resilience.journal import atomic_write_text

# Routing gauges of the expert tier: the (token, expert) pairs that fell to the
# experts a chip holds, all pairs routed, the fullest held expert's load over
# the held experts' mean, and the rows the grouped products run for those pairs
# (each held expert's pairs rounded up to whole tiles, every MoE layer
# together: ``pairs_held / rows_padded`` of them are no padding).
# ``models.moe_share.set_routing_gauges`` sets them for every decoder family,
# outside any hot loop (the forward itself syncs nothing to the host).
MOE_PAIRS_HELD = "moe.pairs_held"
MOE_PAIRS_ALL = "moe.pairs_all"
MOE_EXPERT_LOAD_MAX_OVER_MEAN = "moe.expert_load_max_over_mean"
MOE_ROWS_PADDED = "moe.rows_padded"
MOE_ROUTING_GAUGES = (MOE_PAIRS_HELD, MOE_PAIRS_ALL, MOE_EXPERT_LOAD_MAX_OVER_MEAN, MOE_ROWS_PADDED)
# Gauges of the linear-attention layers (``models.kda_moe.layer_statistics``,
# one batch, outside any hot loop): the most negative log decay summed over one
# chunk of the scan, over every layer, head and channel (what the scan must
# never exponentiate alone: float32 overflows past 88), the mean write
# strength ``beta``, and the layers whose q, k and v were made by
# ``ops.kda_mix``'s kernel (one pass over each projection) and not by the
# ``jax.numpy`` form: a matter of the batch's shapes alone (``kda_mix.fits``),
# so every linear-attention layer or none.
KDA_CHUNK_LOG_DECAY_MIN = "kda.chunk_log_decay_min"
KDA_BETA_MEAN = "kda.beta_mean"
KDA_MIX_FUSED_LAYERS = "kda.mix_fused_layers"
KDA_GAUGES = (KDA_CHUNK_LOG_DECAY_MIN, KDA_BETA_MEAN, KDA_MIX_FUSED_LAYERS)
# Gauges of the decoder whose router is an MLP over a state carried down the
# depth (``models.cca_moe.layer_statistics``, one batch, outside any hot loop):
# the tokens whose top-1 is the router's skip output over all routed tokens,
# every layer together, and the rms of the last layer's router state (what the
# additions of the carried state come to).
MOE_SKIP_SHARE = "moe.skip_share"
ROUTER_STATE_RMS_LAST = "router.state_rms_last"
CCA_GAUGES = (MOE_SKIP_SHARE, ROUTER_STATE_RMS_LAST)
# Gauges of the decoder whose router also chooses among zero-computation
# (identity) experts (``models.scmoe_mla.routing_statistics``, one batch,
# outside any hot loop): the chosen places that are identity experts over all
# places, every layer together, and the most and the fewest real experts one
# token ran in one layer (how far compute per token varies).
MOE_ZERO_PAIR_SHARE = "moe.zero_pair_share"
MOE_REAL_EXPERTS_PER_TOKEN_MAX = "moe.real_experts_per_token_max"
MOE_REAL_EXPERTS_PER_TOKEN_MIN = "moe.real_experts_per_token_min"
SCMOE_GAUGES = (MOE_ZERO_PAIR_SHARE, MOE_REAL_EXPERTS_PER_TOKEN_MAX, MOE_REAL_EXPERTS_PER_TOKEN_MIN)
# Gauges of the decoder of state-space scans and differential attention
# (``models.sambay.layer_statistics``, one batch, outside any hot loop): the
# most negative ``Delta A`` summed over one chunk of the selective scan, over
# every Mamba layer, channel and state (what a chunked form would exponentiate,
# and why ``ops.selective_scan`` exponentiates one token's alone), the mean
# step ``Delta``, the least and the greatest ``lambda`` of the differential
# attention layers, and ``flash.masked_score_share``'s twin for the windowed
# layers (``flash_attention.causal_plan`` with the window: of the scores the
# band's blocks compute, the share the two masks throw away).
SSM_CHUNK_LOG_DECAY_MIN = "ssm.chunk_log_decay_min"
SSM_DT_MEAN = "ssm.dt_mean"
DIFF_LAMBDA_MIN = "diff.lambda_min"
DIFF_LAMBDA_MAX = "diff.lambda_max"
FLASH_WINDOW_MASKED_SCORE_SHARE = "flash.window_masked_score_share"
SAMBAY_GAUGES = (
    SSM_CHUNK_LOG_DECAY_MIN, SSM_DT_MEAN, DIFF_LAMBDA_MIN, DIFF_LAMBDA_MAX, FLASH_WINDOW_MASKED_SCORE_SHARE
)
# Gauge of the causal attention kernel (``models.moe_share.set_attention_gauge``,
# beside the routing gauges of every decoder family that calls ``flash_fwd``,
# outside any hot loop): of the scores the forward computes for one head, the
# share the mask then throws away. A matter of the sequence length and the
# blocks alone (``ops.flash_attention.causal_plan``): what is left of the
# blocks the diagonal crosses once they are computed in row slabs.
FLASH_MASKED_SCORE_SHARE = "flash.masked_score_share"

# Prometheus metric-name grammar: [a-zA-Z_:][a-zA-Z0-9_:]* — the dotted
# registry names ("serve.ok") sanitize to underscores ("serve_ok").
_PROM_BAD = re.compile(r"[^a-zA-Z0-9_:]")


def _prom_name(name: str) -> str:
    pname = _PROM_BAD.sub("_", name)
    return pname if not pname[:1].isdigit() else f"_{pname}"


def _nearest_rank(xs: List[float], q: float) -> Optional[float]:
    # The serving estimator (serving.loadgen.percentile): nearest-rank, so
    # small samples report an observed value, never an interpolated one.
    # Lazy import — loadgen pulls numpy + the server module.
    from ..serving.loadgen import percentile

    return percentile(xs, q)


class Counter:
    """Monotonic event count."""

    def __init__(self, name: str):
        self.name = name
        self.value = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self.value += n

    def to_obj(self) -> dict:
        return {"name": self.name, "type": "counter", "value": self.value}


class Gauge:
    """Last-write-wins instantaneous value."""

    def __init__(self, name: str):
        self.name = name
        self.value: Optional[float] = None
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self.value = float(v)

    def to_obj(self) -> dict:
        return {"name": self.name, "type": "gauge", "value": self.value}


class Histogram:
    """Value stream with nearest-rank percentiles. Keeps up to ``cap``
    observations (newest win — a bounded reservoir so a week-long serve
    process cannot grow without bound); count/sum stay exact."""

    def __init__(self, name: str, cap: int = 65536):
        self.name = name
        self.cap = cap
        self.count = 0
        self.sum = 0.0
        self._values: List[float] = []
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        v = float(v)
        with self._lock:
            self.count += 1
            self.sum += v
            self._values.append(v)
            if len(self._values) > self.cap:
                del self._values[: len(self._values) - self.cap]

    def percentile(self, q: float) -> Optional[float]:
        with self._lock:
            vals = list(self._values)
        return _nearest_rank(vals, q)

    def to_obj(self) -> dict:
        p50, p99 = self.percentile(50), self.percentile(99)
        return {
            "name": self.name,
            "type": "histogram",
            "count": self.count,
            "sum": round(self.sum, 4),
            "mean": round(self.sum / self.count, 4) if self.count else None,
            "p50": round(p50, 4) if p50 is not None else None,
            "p99": round(p99, 4) if p99 is not None else None,
        }


class MetricsRegistry:
    """Named metrics, one instance per process (module-level
    :func:`registry`); ``counter``/``gauge``/``histogram`` create on first
    use and return the existing instrument after — a name can hold exactly
    one instrument type (mixing is a bug worth failing loudly on)."""

    def __init__(self):
        self._metrics: Dict[str, object] = {}
        self._lock = threading.Lock()

    def _get(self, name: str, cls, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, **kw)
            elif not isinstance(m, cls):
                raise ValueError(
                    f"metric {name!r} already registered as "
                    f"{type(m).__name__}, not {cls.__name__}"
                )
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str, cap: int = 65536) -> Histogram:
        return self._get(name, Histogram, cap=cap)

    def snapshot(self) -> Dict[str, dict]:
        """{name: instrument.to_obj()} for every registered metric."""
        with self._lock:
            items = list(self._metrics.items())
        return {name: m.to_obj() for name, m in sorted(items)}

    def summary(self) -> Dict[str, object]:
        """The compact form: counters/gauges as bare
        values, histograms as {count, mean, p50, p99}."""
        out: Dict[str, object] = {}
        for name, obj in self.snapshot().items():
            if obj["type"] == "histogram":
                out[name] = {
                    k: obj[k] for k in ("count", "mean", "p50", "p99")
                }
            else:
                out[name] = obj["value"]
        return out

    def export(self, path) -> None:
        """Atomic JSONL export: one JSON object per metric (tmp-write,
        fsync, rename — the journal module's artifact contract)."""
        lines = [json.dumps(obj) for _name, obj in sorted(self.snapshot().items())]
        atomic_write_text(path, "\n".join(lines) + ("\n" if lines else ""))

    def prometheus(self) -> str:
        """Prometheus text exposition (format 0.0.4) of every registered
        metric — what the serving front end's ``GET /metrics`` serves so
        the stack is scrapeable (docs/SERVING.md). Counters/gauges map
        directly; histograms expose as summaries (p50/p99 quantile
        samples plus ``_sum``/``_count`` — the same nearest-rank
        percentiles every other surface reports). Metric names sanitize
        ``.`` to ``_`` per the exposition grammar."""
        lines: List[str] = []
        for name, obj in self.snapshot().items():
            pname = _prom_name(name)
            if obj["type"] == "counter":
                lines.append(f"# TYPE {pname} counter")
                lines.append(f"{pname} {obj['value']}")
            elif obj["type"] == "gauge":
                lines.append(f"# TYPE {pname} gauge")
                v = obj["value"]
                lines.append(f"{pname} {v if v is not None else 'NaN'}")
            else:  # histogram -> summary
                lines.append(f"# TYPE {pname} summary")
                for q, key in (("0.5", "p50"), ("0.99", "p99")):
                    if obj.get(key) is not None:
                        lines.append(f'{pname}{{quantile="{q}"}} {obj[key]}')
                lines.append(f"{pname}_sum {obj['sum']}")
                lines.append(f"{pname}_count {obj['count']}")
        return "\n".join(lines) + ("\n" if lines else "")

    def reset(self) -> None:
        with self._lock:
            self._metrics.clear()


_REGISTRY = MetricsRegistry()


def registry() -> MetricsRegistry:
    """The process-wide registry every wired subsystem records into."""
    return _REGISTRY
