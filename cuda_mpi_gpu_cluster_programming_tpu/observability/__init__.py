"""Unified observability: journal-correlated tracing, a metrics registry,
per-stage latency attribution, and Perfetto export (docs/OBSERVABILITY.md).

- ``trace``   — span API (``span(name, **attrs)`` with trace/span/parent
  ids, monotonic clocks) persisting through the PR 3 crash-consistent
  ``Journal``; journal records at wired call sites gain optional
  ``trace_id``/``span_id`` correlation fields.
- ``metrics`` — process-wide counters/gauges/histograms (nearest-rank
  p50/p99) with atomic JSONL export and a ``summary()``.
- ``stages``  — per-stage attribution of the Blocks 1-2 forward at the
  sentinel tap boundaries (conv1/pool1/conv2/pool2/lrn2), via timed
  staged re-execution strictly off the timed path (host clock; the
  benchmark reads per-layer device time from the trace instead).
- ``export``  — stitch spans AND the existing journal schemas
  (``serve_*``, ``sup_*``, ``gate_*``, ``mesh_shrink``, watchdog) into
  one Chrome trace-event / Perfetto JSON timeline.
- ``replay``  — the journal-replay fleet simulator: reconstruct a
  recorded serve run's arrival schedule, request classes/deadlines, and
  chaos schedule from its journal alone and re-drive it through a live
  server on the CPU mesh, with ``traffic_mult``/``devices``/
  ``slo_scale`` what-if knobs; a neutral replay must close per-class
  accounting identically (the determinism contract).
- ``specs``   — the ONE device spec table (peak TFLOP/s per dtype + HBM
  GB/s per TPU generation) plus the live ``device_memory_stats``
  snapshot helper.
- ``roofline`` — per-stage MFU / HBM-traffic attribution: the analytic
  FLOP+byte ledger from ``models.alexnet``, the staged-vs-fused byte
  model predicting each block's fused time floor and MFU ceiling (the
  ROADMAP-1 megakernel judge), and the measured join emitting
  compute/memory-bound verdicts with headroom.

CLI: ``python -m cuda_mpi_gpu_cluster_programming_tpu.observability
export --journal <dir|file> [--out trace.json]``,
``... replay --journal <dir|file> [--traffic-mult K] [--devices N]
[--slo-scale F]``,
``... roofline --live``, and ``... health --journal <dir|file>``
(exit codes: 0 clean / 2 usage or unreplayable / 3 replay divergence
or blown error budget — docs/OBSERVABILITY.md).

Speed is measured by ``benchmark/run.py`` alone (``BENCHMARK.json``,
``benchmark/README.md``, ``PERF.md``); the regression gate is the
driver's ``PERF_LEDGER.jsonl``.

This package init re-exports only the import-light tracing/metrics
surface (stdlib + journal — the wired subsystems pay no jax import);
``stages`` imports jax and is imported as a submodule by its callers.
"""

from .metrics import Counter, Gauge, Histogram, MetricsRegistry, registry
from .trace import Span, Tracer, current_ids, get_tracer, set_tracer, span

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "registry",
    "Span",
    "Tracer",
    "current_ids",
    "get_tracer",
    "set_tracer",
    "span",
]
