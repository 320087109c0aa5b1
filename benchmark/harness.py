"""The harness behind ``run.py``: finds a cell's files by name, runs its
driver, reads its per-layer metrics and prints the last line. See ``run.py``
for the command and the layout, README.md for how to add a cell.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# Set-up is counted from the process's start; run.py sets this to the clock
# it read on its first line.
T_START = time.perf_counter()

EXIT_NO_DEVICE = 3
EXIT_BAD_CELL = 4


class BenchmarkError(RuntimeError):
    """The cell cannot be run as asked (a missing file, a bad name)."""


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_plugin(kind: str, name: str):
    """The module ``<kind>/<name>.py`` beside this file, loaded by path (a
    metric's name may hold dots, which no import statement could spell)."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise BenchmarkError(f"no {kind} file for {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(manifest: Dict, name: str) -> Dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchmarkError(
        f"no workload {name!r} in BENCHMARK.json "
        f"(have: {', '.join(c['name'] for c in manifest['workloads'])})"
    )


def load_config(manifest: Dict, name: str) -> Dict:
    """The content of the configuration file that ``configs[]`` names."""
    entry = next(c for c in manifest["configs"] if c["name"] == name)
    return load_json(ROOT / entry["file"])


def metrics_for(manifest: Dict, group: str, cell: str) -> List[Dict]:
    """The metrics of ``group`` that this cell reports: those that name it
    under ``workloads``, and those that name no cell at all."""
    return [
        m for m in manifest[group]
        if "workloads" not in m or cell in m["workloads"]
    ]


def peak_row(device_kind: str) -> Dict:
    """This chip's row of ``peaks.json``; a kind that is not there is an
    error, never a default."""
    table = load_json(HERE / "peaks.json")["peaks"]
    for row in table:
        if row["match"] in device_kind.lower():
            return row
    raise BenchmarkError(
        f"device kind {device_kind!r} is not in benchmark/peaks.json: "
        "no peak to judge against"
    )


class Context:
    """What a driver and the per-layer readers share for one run."""

    def __init__(self, args, manifest: Dict, cell: Dict):
        self.seed: int = args.seed
        self.seconds: float = args.seconds
        self.trace_on: bool = bool(args.trace)
        self.rehearse: bool = args.rehearse
        self.manifest = manifest
        self.cell = cell
        self.config: Dict = load_config(manifest, cell["config"])
        self.traffic: Dict = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
        family = self.config["family"]
        self.adapter = load_plugin("adapters", family)
        self.reference = load_plugin("reference", family)
        self.shapes = load_plugin("shapes", family)
        self.spans: Dict[str, List[tuple]] = {}  # name -> [(t0, t1)], host clock
        self.counters: Dict[str, float] = {}
        self.samples: Dict[str, List[float]] = {}  # name -> readings
        self.trace = None  # trace_reduce.Reduced after a profiled window
        self.setup_s: Optional[float] = None
        self.devices: list = []
        self.peaks: Optional[Dict] = None
        self._compiles = {"compiled": 0, "cache_hits": 0}
        self.built_in_window = 0

    # ---- lines before the last one -------------------------------------

    def log(self, msg: str) -> None:
        print(f"[bench +{time.perf_counter() - T_START:7.2f}s] {msg}", flush=True)

    # ---- spans, on the host clock and in the profiler's trace ----------

    @contextlib.contextmanager
    def span(self, name: str, **fields):
        """Time a region on the host clock and write it into the profiler's
        trace as a ``TraceAnnotation`` of the same name."""
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name, **fields):
            try:
                yield
            finally:
                self.spans.setdefault(name, []).append((t0, time.perf_counter()))

    def span_seconds(self, name: str) -> float:
        return sum(t1 - t0 for t0, t1 in self.spans.get(name, []))

    # ---- set-up, compilation -------------------------------------------

    def watch_compiles(self) -> None:
        """Count every program JAX compiles or loads from its cache, so
        that a compile inside the measured window shows."""
        import jax

        def on_duration(event: str, _secs: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self._compiles["compiled"] += 1

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self._compiles["cache_hits"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def programs_built(self) -> int:
        """Programs built so far, compiled or loaded from the cache (JAX
        reports the backend-compile event for both, and the hit besides)."""
        return self._compiles["compiled"]

    def setup_done(self) -> None:
        """Called by the driver just before its first measured operation."""
        self.setup_s = time.perf_counter() - T_START
        self.log(
            f"set-up done in {self.setup_s:.3f} s: "
            f"{self._compiles['compiled']} programs built, "
            f"{self._compiles['cache_hits']} of them loaded from the cache"
        )

    @contextlib.contextmanager
    def measured(self):
        """The measured window: nothing may compile inside it."""
        before = self.programs_built()
        try:
            yield
        finally:
            self.built_in_window += self.programs_built() - before

    # ---- the profiled window -------------------------------------------

    @contextlib.contextmanager
    def profile(self):
        """Profile the enclosed window and reduce its trace into
        ``self.trace``. The Python tracer is off: it would slow the host
        path that some cells measure."""
        import jax

        from benchmark import trace_reduce

        log_dir = ROOT / ".bench_trace" / self.cell["name"]
        shutil.rmtree(log_dir, ignore_errors=True)
        log_dir.mkdir(parents=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(log_dir), profiler_options=options)
        try:
            with self.span("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()
        t0 = time.perf_counter()
        raw = trace_reduce.load_xplane(trace_reduce.find_xplane(log_dir))
        self.trace = trace_reduce.Reduced(raw)
        self.log(
            f"trace reduced in {time.perf_counter() - t0:.2f} s: "
            f"{self.trace.planes_with_work} device planes with work "
            f"(incomplete and left out: {self.trace.incomplete or 'none'}), window "
            f"{self.trace.window_s:.3f} s, busy {self.trace.busy_s():.3f} s"
        )

    def name_fusions(self, jitted, *args) -> None:
        """Tell the reduced trace what each fusion of the step program
        holds, from the program's own compiled HLO text (the trace names
        a convolution only ``fusion.<n>``)."""
        from benchmark import trace_reduce

        if self.trace is not None:
            text = jitted.lower(*args).compile().as_text()
            self.trace.kinds = trace_reduce.fusion_kinds(text)

    # ---- the comparison that decides ``correct`` -----------------------

    def check(self, got, want, what: str) -> bool:
        """``got`` against the plain reference's ``want`` under the
        configuration's tolerance; logs the error either way."""
        err = relative_error(got, want)
        tol = float(self.config["tolerance"]["rel_max"])
        self.counters["check.rel_err"] = err
        self.log(
            f"check: {what} against the plain reference: "
            f"max|diff|/max|ref| = {err:.3e} (tolerance {tol:g}) -> "
            f"{'correct' if err <= tol else 'NOT CORRECT'}"
        )
        return err <= tol

    # ---- the device ----------------------------------------------------

    def memory_peak_bytes(self) -> int:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats()
            if stats and stats.get("peak_bytes_in_use") is not None:
                peaks.append(int(stats["peak_bytes_in_use"]))
        return max(peaks) if peaks else 0


def relative_error(got, want) -> float:
    """max|got - want| / max|want|: the comparison that decides ``correct``.
    Not a number (or a shape that differs) is an infinite error."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    scale = float(np.abs(want).max())
    return float(np.abs(got - want).max() / scale) if scale > 0 else float("inf")


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="run on the CPU (JAX_PLATFORMS=cpu) to find faults; reports no metric",
    )
    return ap.parse_args(argv)


def open_cell(args) -> "Context":
    """The cell's context from ``BENCHMARK.json`` and the files it names."""
    try:
        manifest = load_json(ROOT / "BENCHMARK.json")
        cell = find_cell(manifest, args.workload)
        if args.seconds is None:
            args.seconds = float(manifest["run_seconds"])
        return Context(args, manifest, cell)
    except (OSError, KeyError, StopIteration, ValueError) as e:
        raise BenchmarkError(repr(e)) from e


def attach_device(ctx: "Context") -> int:
    """Start JAX with the compile cache inside the checkout and hold the
    run to a TPU with enough chips. Returns 0, or the exit code."""
    # The cache lives at a fixed path inside the checkout (the path is part
    # of the cache's key) unless the environment names another; the
    # program's own switch takes the same variable, so both agree.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".xla_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    platform, chips = devices[0].platform, ctx.cell["chips"]
    if platform != "tpu" and not (
        ctx.rehearse and os.environ.get("JAX_PLATFORMS") == "cpu"
    ):
        print(
            f"benchmark: JAX found no TPU (platform {platform!r}); a CPU run "
            "is a rehearsal and must say so: JAX_PLATFORMS=cpu and --rehearse",
            file=sys.stderr,
        )
        return EXIT_NO_DEVICE
    if len(devices) < chips:
        print(
            f"benchmark: {ctx.cell['name']!r} needs {chips} chips, "
            f"JAX reports {len(devices)}",
            file=sys.stderr,
        )
        return EXIT_NO_DEVICE
    ctx.devices = devices[:chips]
    ctx.device_count = len(devices)  # as JAX reports it, not as the cell uses it
    if platform == "tpu":
        ctx.peaks = peak_row(devices[0].device_kind)
    ctx.watch_compiles()
    ctx.log(
        f"{ctx.cell['name']}: config {ctx.cell['config']}, traffic "
        f"{ctx.cell['traffic']}, seed {ctx.seed}, {ctx.seconds:g} s, trace "
        f"{int(ctx.trace_on)}, {len(devices)} x {devices[0].device_kind}"
    )
    return 0


def main(argv=None, t_start: Optional[float] = None) -> int:
    global T_START
    if t_start is not None:
        T_START = t_start
    args = parse_args(argv)
    try:
        ctx = open_cell(args)
        manifest, cell = ctx.manifest, ctx.cell
        driver = load_plugin("drivers", ctx.traffic["driver"])
        group = "per_layer" if ctx.trace_on else "end_to_end"
        wanted = metrics_for(manifest, group, cell["name"])
        readers = (
            {m["name"]: load_plugin("layer_metrics", m["name"]) for m in wanted}
            if ctx.trace_on else {}
        )
    except BenchmarkError as e:
        print(f"benchmark: cannot run {args.workload!r}: {e}", file=sys.stderr)
        return EXIT_BAD_CELL
    code = attach_device(ctx)
    if code:
        return code
    devices, platform = ctx.devices, ctx.devices[0].platform

    result = driver.run(ctx)
    if ctx.setup_s is None:
        raise BenchmarkError("the driver never called ctx.setup_done()")
    correct = bool(result["correct"])
    if ctx.built_in_window:
        ctx.log(
            f"NOT CORRECT: {ctx.built_in_window} programs were compiled or "
            "loaded inside the measured window"
        )
        correct = False

    values: Dict[str, Any]
    if ctx.trace_on:
        values = {m["name"]: readers[m["name"]].read(ctx) for m in wanted}
    else:
        values = {**result["values"], "setup_s": ctx.setup_s}
    units = {m["name"]: m["unit"] for m in wanted}
    reported = {
        name: {"value": float(values[name]), "unit": units[name]}
        for name in units
        if values.get(name) is not None
    }
    for name in units:
        if name not in reported:
            ctx.log(f"LEFT OUT of the last line: {name} (nothing to read it from)")
    device = {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": ctx.device_count,
        "memory_peak_bytes": ctx.memory_peak_bytes(),
    }
    line: Dict[str, Any] = {
        "correct": correct,
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": reported,
        "device": device,
    }
    if ctx.trace_on and ctx.trace is not None:
        device["busy_s"] = ctx.trace.busy_s()
        device["window_s"] = ctx.trace.window_s
        line["breakdown"] = {
            "device_ops": ctx.trace.top_ops(10),
            "idle_gaps": ctx.trace.idle_gaps(10),
        }
        for name, busy in ctx.trace.busy_s_by_device().items():
            ctx.log(f"{name}: busy {busy:.4f} s of {ctx.trace.window_s:.4f} s")
        ctx.log(f"time by category: {ctx.trace.category_seconds()}")
    ctx.log(f"peak device memory {device['memory_peak_bytes'] / 1e9:.3f} GB (fullest chip)")
    if platform != "tpu":
        # A CPU number never stands under a device metric's name.
        line["rehearsal"] = {k: v["value"] for k, v in reported.items()}
        line["metrics"] = {}
        line.pop("breakdown", None)
    print(json.dumps(line), flush=True)
    return 0

