"""Roofline attribution layer tests (ISSUE 13, docs/OBSERVABILITY.md
"Roofline attribution") — CPU backend.

Covers the tentpole surface: the analytic per-stage ledger summing
EXACTLY to ``models.alexnet.flops_per_image`` (one generator feeds
both), staged-vs-fused byte-model monotonicity with the delta equal to
the intermediates' write+read round-trips, compute/memory-bound
classification against the spec table's ridge point, the CPU-mesh
integration joining a REAL ``attribute_stages`` breakdown into a ranked
report, the CLI's usage exit, the one-source-of-truth spec table,
the serve telemetry records (``serve_gauges``/``mem_snapshot``), the
Perfetto counter tracks, and the Prometheus exposition.
"""

import dataclasses
import inspect
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import (  # noqa: E402
    BLOCKS12,
    flops_per_image,
    matmul_flops_per_image,
    stage_flops,
)
from cuda_mpi_gpu_cluster_programming_tpu.observability import (  # noqa: E402
    specs,
)
from cuda_mpi_gpu_cluster_programming_tpu.observability.roofline import (  # noqa: E402
    BLOCKS,
    attribute_roofline,
    fused_blocks,
    model_stage_split,
    pass_ledger,
    stage_ledger,
)

SMALL = dataclasses.replace(BLOCKS12, in_height=63, in_width=63)
STAGES = ("conv1", "pool1", "conv2", "pool2", "lrn2")


# ---------------------------------------------------------------- ledger ---


def test_stage_flops_ledger_sums_exactly_to_whole_pass_counters():
    """The acceptance contract: the per-stage FLOP ledger and the
    whole-pass counters come from ONE generator, so they agree exactly —
    at the default geometry and a replaced one."""
    for cfg in (BLOCKS12, SMALL):
        rows = list(stage_flops(cfg))
        assert [n for n, _f, _mm in rows] == list(STAGES)
        assert sum(f for _n, f, _mm in rows) == flops_per_image(cfg)
        assert sum(mm for _n, _f, mm in rows) == matmul_flops_per_image(cfg)
        # and the byte ledger carries the same flops, batch-scaled
        for batch in (1, 7):
            entries = pass_ledger(cfg, dtype="fp32", batch=batch)
            assert sum(e.flops for e in entries) == flops_per_image(cfg) * batch
            assert (
                sum(e.matmul_flops for e in entries)
                == matmul_flops_per_image(cfg) * batch
            )


def test_ledger_activation_bytes_chain_and_dtype_policy():
    """Stage k's output activation bytes equal stage k+1's input bytes
    (the staged chain round-trips through HBM between taps), and the
    dtype policy halves activation traffic fp32 -> bf16."""
    fp32 = stage_ledger(BLOCKS12, dtype="fp32")
    bf16 = stage_ledger(BLOCKS12, dtype="bf16")
    for a, b in zip(fp32, fp32[1:]):
        assert a.act_out_bytes == b.act_in_bytes
    for e32, e16 in zip(fp32, bf16):
        assert e32.act_in_bytes == 2 * e16.act_in_bytes
        assert e32.act_out_bytes == 2 * e16.act_out_bytes
    # int8w: int8 weights + fp32 per-channel scales over bf16 activations
    i8 = stage_ledger(BLOCKS12, dtype="int8w")
    c1 = BLOCKS12.conv1
    assert i8[0].act_in_bytes == bf16[0].act_in_bytes
    assert i8[0].param_bytes == (
        c1.filter_size**2 * 3 * c1.out_channels  # int8 weights, 1 byte
        + c1.out_channels * 2  # bf16 bias
        + c1.out_channels * 4  # fp32 scales
    )
    with pytest.raises(ValueError, match="fp32"):
        stage_ledger(BLOCKS12, dtype="fp64")


def test_fused_byte_model_monotone_and_delta_is_intermediate_roundtrips():
    """The satellite contract: fused <= staged for every block and dtype,
    and the delta is EXACTLY the interior boundaries' activations written
    once and read once (2x bytes each)."""
    for dtype in ("fp32", "bf16", "int8w"):
        for batch in (1, 16):
            entries = pass_ledger(BLOCKS12, dtype=dtype, batch=batch)
            by = {e.name: e for e in entries}
            blocks = fused_blocks(entries, 197.0, 819.0)
            assert [b.name for b in blocks] == ["block1", "block2"]
            for b in blocks:
                assert b.fused_bytes <= b.staged_bytes
                # interior boundaries: every stage's output except the last
                interior = sum(
                    by[n].act_out_bytes for n in b.stages[:-1]
                )
                assert b.intermediate_bytes == 2 * interior
                assert b.fused_floor_ms <= b.staged_floor_ms + 1e-12
                assert b.fused_mfu_ceiling is not None
                assert 0 < b.fused_mfu_ceiling <= 1.0


def test_block_structure_matches_the_megakernel_plan():
    assert BLOCKS == (
        ("block1", ("conv1", "pool1")),
        ("block2", ("conv2", "pool2", "lrn2")),
    )


# ----------------------------------------------------------------- specs ---


def test_spec_table_is_the_one_source():
    assert specs.peak_tflops("TPU v5 lite") == 197.0
    assert specs.peak_tflops("TPU v4") == 275.0
    assert [(s.marker, s.bf16_tflops) for s in specs.SPEC_TABLE] == [
        ("v6", 918.0), ("v5p", 459.0), ("v5", 197.0),
        ("v4", 275.0), ("v3", 123.0), ("v2", 45.0),
    ]
    # per-dtype peaks: fp32 is the bf16 peak / 6 (HIGHEST synthesis);
    # int8w runs bf16 MXU passes in this repo (dequant-free forward)
    assert specs.peak_tflops("TPU v5 lite", "fp32") == pytest.approx(197.0 / 6)
    assert specs.peak_tflops("TPU v5 lite", "int8w") == 197.0
    spec = specs.spec_for("TPU v5 lite")
    assert spec.name == "TPU v5e"
    assert spec.hbm_gbps == 819.0
    # v5p must win over the v5 substring
    assert specs.spec_for("TPU v5p").bf16_tflops == 459.0


def test_unknown_device_is_an_error_not_a_default():
    """A device outside the table has no peak: nothing is judged against
    an assumed chip, and no environment variable overrides the table."""
    for kind in ("cpu", "weird-device", ""):
        with pytest.raises(specs.UnknownDeviceError, match="not in the spec table"):
            specs.spec_for(kind)
    with pytest.raises(specs.UnknownDeviceError):
        specs.peak_tflops("weird-device")
    with pytest.raises(specs.UnknownDeviceError):
        specs.hbm_gbps("cpu")
    assert "environ" not in inspect.getsource(specs)  # reads no variable
    assert specs.peak_tflops("TPU v5 lite") == 197.0
    assert specs.hbm_gbps("TPU v5 lite") == 819.0


def test_device_memory_stats_always_reports_a_source():
    snap = specs.device_memory_stats()
    assert snap["source"] in ("device", "rss")
    assert isinstance(snap["bytes_in_use"], int) and snap["bytes_in_use"] > 0


# ------------------------------------------------------------ attribution ---


def test_bound_classification_unit_cases():
    """A stage above the ridge intensity is compute-bound, below it
    memory-bound, and the floors/headroom follow the binding roof."""
    entries = pass_ledger(BLOCKS12, dtype="bf16", batch=128)
    by = {e.name: e for e in entries}
    ridge = 197e12 / 819e9  # ~240 FLOP/byte on the v5e spec
    assert by["conv2"].intensity > ridge  # the MXU stage
    assert by["pool1"].intensity < 1.0  # pure streaming
    rep = attribute_roofline(
        {"conv2": 1.0, "pool1": 1.0},
        dtype="bf16",
        batch=128,
        device_kind="TPU v5 lite",
    )
    verdicts = {s.name: s for s in rep.stages}
    assert verdicts["conv2"].bound == "compute"
    assert verdicts["pool1"].bound == "memory"
    # compute-bound floor = flops/peak; memory-bound floor = bytes/bw
    assert verdicts["conv2"].floor_ms == pytest.approx(
        by["conv2"].flops / 197e12 * 1e3
    )
    assert verdicts["pool1"].floor_ms == pytest.approx(
        by["pool1"].staged_bytes / 819e9 * 1e3
    )
    for s in rep.stages:
        assert s.headroom_ms == pytest.approx(s.ms - s.floor_ms)
    # ranked: biggest reclaimable ms first
    assert [s.headroom_ms for s in rep.stages] == sorted(
        [s.headroom_ms for s in rep.stages], reverse=True
    )


def test_model_stage_split_sums_exactly_to_total():
    entries = pass_ledger(BLOCKS12, dtype="bf16", batch=128)
    split = model_stage_split(5.0, entries, 197.0, 819.0)
    assert set(split) == set(STAGES)
    assert sum(split.values()) == pytest.approx(5.0)
    # the split respects the floors' proportions: conv2 dominates
    assert split["conv2"] == max(split.values())


def test_cpu_mesh_integration_joins_a_real_breakdown():
    """The integration acceptance: a REAL attribute_stages breakdown joins
    into a ranked roofline report — 5 stages, MFU and verdicts present —
    and the report round-trips through JSON. The breakdown is measured on
    the CPU mesh, so the join is only exercised here, against a named
    chip's roof; asked to judge it as the CPU device it is, the layer
    refuses."""
    from cuda_mpi_gpu_cluster_programming_tpu.models.init import (
        deterministic_input,
        init_params_deterministic,
    )
    from cuda_mpi_gpu_cluster_programming_tpu.observability.stages import (
        attribute_stages,
    )

    att = attribute_stages(
        init_params_deterministic(SMALL),
        deterministic_input(4, SMALL),
        SMALL,
        repeats=2,
        warmup=1,
    )
    kwargs = dict(
        dtype="fp32", batch=4, cfg=SMALL, source="breakdown",
        total_ms=att.total_ms,
    )
    with pytest.raises(specs.UnknownDeviceError):
        attribute_roofline(dict(att.stages), device_kind="cpu", **kwargs)
    rep = attribute_roofline(
        dict(att.stages), device_kind="TPU v5 lite", **kwargs
    )
    assert rep.device == "TPU v5e"
    assert rep.source == "breakdown"
    assert {s.name for s in rep.stages} == set(STAGES)
    assert rep.total_ms == pytest.approx(att.total_ms)
    for s in rep.stages:
        assert s.bound in ("compute", "memory")
        assert s.mfu is not None and s.mfu >= 0
        assert s.achieved_gbps >= 0 and s.floor_ms > 0
        if s.ms > 0:  # a clamped-to-zero stage has nothing to reclaim
            # CPU ms vs a TPU roof: headroom is strictly positive
            assert s.headroom_ms > 0
    assert {b.name for b in rep.blocks} == {"block1", "block2"}
    obj = json.loads(json.dumps(rep.to_obj()))
    assert [s["name"] for s in obj["stages"]] == [s.name for s in rep.stages]
    assert obj["fused_pass_mfu_ceiling"] is not None
    assert "roofline" in rep.render() and "fused block1" in rep.render()


# ------------------------------------------------------------------- CLI ---


def test_roofline_cli_usage_rc2(tmp_path):
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "cuda_mpi_gpu_cluster_programming_tpu.observability",
            "roofline",
        ],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
    )
    assert proc.returncode == 2
    assert "pass --live" in proc.stderr
    # the positional rows mode is gone: a path is a usage error too
    bad = tmp_path / "nothing.json"
    bad.write_text("not json at all")
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "cuda_mpi_gpu_cluster_programming_tpu.observability",
            "roofline", str(bad),
        ],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
    )
    assert proc.returncode == 2
    # --live on a device outside the spec table refuses before measuring
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "cuda_mpi_gpu_cluster_programming_tpu.observability",
            "roofline", "--live",
        ],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 2
    assert "not in the spec table" in proc.stderr


# --------------------------------------------------------- live telemetry ---


def test_serve_telemetry_journals_gauges_and_mem_snapshots(tmp_path):
    """The dispatch loop journals serve_gauges (queue saturation trio)
    and mem_snapshot records off the timed path, at the configured
    cadence, with the reading's source named; the mem.* registry gauges
    mirror them."""
    from cuda_mpi_gpu_cluster_programming_tpu.observability.metrics import (
        registry,
    )
    from cuda_mpi_gpu_cluster_programming_tpu.resilience.journal import Journal
    from cuda_mpi_gpu_cluster_programming_tpu.serving.server import (
        InferenceServer,
        ServeConfig,
    )

    tiny = dataclasses.replace(BLOCKS12, in_height=35, in_width=35)
    jp = tmp_path / "serve.jsonl"
    srv = InferenceServer(
        ServeConfig(
            config="v1_jit", max_batch=2, model_cfg=tiny,
            journal_path=str(jp), mem_snapshot_s=0.001,
        )
    )
    for i in range(3):
        srv.submit(np.full((1, 35, 35, 3), 1.0 + i, np.float32))
    srv.run_until_drained()
    recs = Journal.load(jp)
    gauges = [r for r in recs if r["kind"] == "serve_gauges"]
    snaps = [r for r in recs if r["kind"] == "mem_snapshot"]
    assert gauges and snaps
    for g in gauges:
        assert {"depth", "pending_images", "oldest_wait_ms", "t_ms"} <= set(g)
    for s in snaps:
        assert s["source"] in ("device", "rss")
        assert isinstance(s["bytes_in_use"], int) and s["bytes_in_use"] > 0
    assert registry().summary().get("mem.bytes_in_use", 0) > 0
    # mem_snapshot_s=0 disables the records entirely
    jp2 = tmp_path / "quiet.jsonl"
    srv2 = InferenceServer(
        ServeConfig(
            config="v1_jit", max_batch=2, model_cfg=tiny,
            journal_path=str(jp2), mem_snapshot_s=0,
        )
    )
    srv2.submit(np.full((1, 35, 35, 3), 1.0, np.float32))
    srv2.run_until_drained()
    kinds = {r["kind"] for r in Journal.load(jp2)}
    assert "mem_snapshot" not in kinds and "serve_gauges" not in kinds


def test_export_renders_counter_tracks_old_journals_unchanged(tmp_path):
    """Gauge-bearing records export as Perfetto counter ("C") events —
    one series per field — while a journal without them yields no counter
    events at all (the old-journal contract)."""
    from cuda_mpi_gpu_cluster_programming_tpu.observability.export import (
        to_trace_events,
    )
    from cuda_mpi_gpu_cluster_programming_tpu.resilience.journal import Journal

    jp = tmp_path / "j.jsonl"
    j = Journal(jp)
    j.append("serve_gauges", key="g:1", t_ms=1.0, depth=3,
             pending_images=5, oldest_wait_ms=12.5)
    j.append("mem_snapshot", key="m:1", t_ms=1.0, source="rss",
             bytes_in_use=1024, peak_bytes_in_use=None)
    j.append("serve_batch", key="b:1", bucket=2, batch_ms=3.0)
    trace = to_trace_events(Journal.load(jp))
    cs = [e for e in trace["traceEvents"] if e["ph"] == "C"]
    names = {e["name"] for e in cs}
    assert names == {
        "serve_gauges.depth", "serve_gauges.pending_images",
        "serve_gauges.oldest_wait_ms", "mem_snapshot.bytes_in_use",
    }  # the None-valued peak field skips its series
    depth = next(e for e in cs if e["name"] == "serve_gauges.depth")
    assert depth["args"] == {"depth": 3}
    # same pid lane as the serve records; pid named in metadata
    batch = next(
        e for e in trace["traceEvents"] if e["name"] == "serve_batch"
    )
    assert depth["pid"] == batch["pid"]
    # old journal: zero counter events — and (ISSUE 15) zero compile or
    # incident slices, since those render only from compile_event records
    # and reconstructed incidents, neither of which old journals contain.
    jp2 = tmp_path / "old.jsonl"
    Journal(jp2).append("serve_batch", key="b:1", bucket=2, batch_ms=3.0)
    trace2 = to_trace_events(Journal.load(jp2))
    assert not [e for e in trace2["traceEvents"] if e["ph"] == "C"]
    names2 = {e["name"] for e in trace2["traceEvents"]}
    assert not [n for n in names2 if n.startswith(("compile_event",
                                                   "incident.", "phase."))]


def test_export_renders_compile_events_as_slices(tmp_path):
    """ISSUE 15: compile_event records render as duration slices on the
    supervisor lane's compile sub-lane, sized by their measured ms."""
    from cuda_mpi_gpu_cluster_programming_tpu.observability.export import (
        to_trace_events,
    )
    from cuda_mpi_gpu_cluster_programming_tpu.resilience.journal import Journal

    jp = tmp_path / "c.jsonl"
    j = Journal(jp)
    j.append("compile_event", key="compile:sup:halo8:b1", site="sup",
             entry="halo8", shape=[1, 67, 67, 3], batch=1, dtype="fp32",
             n_shards=2, ms=120.0, cache_hit=False, xla_flops=1.0e9,
             xla_bytes=2.0e6, t_ms=500.0)
    j.append("compile_event", key="compile:sup:halo8:b1", site="sup",
             entry="halo8", shape=[1, 67, 67, 3], batch=1, dtype="fp32",
             n_shards=2, ms=0.2, cache_hit=True, xla_flops=None,
             xla_bytes=None, t_ms=900.0)
    trace = to_trace_events(Journal.load(jp))
    slices = [e for e in trace["traceEvents"]
              if e.get("ph") == "X" and e["name"] == "compile_event"]
    assert len(slices) == 2
    big = max(slices, key=lambda e: e["dur"])
    assert big["dur"] >= 120.0 * 1e3 * 0.99  # us, sized by measured ms
    assert big["args"]["cache_hit"] is False


def test_prometheus_exposition_format():
    from cuda_mpi_gpu_cluster_programming_tpu.observability.metrics import (
        MetricsRegistry,
    )

    reg = MetricsRegistry()
    reg.counter("serve.ok").inc(4)
    reg.gauge("serve.queue_depth").set(2)
    h = reg.histogram("serve.request_ms")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    text = reg.prometheus()
    lines = text.splitlines()
    assert "# TYPE serve_ok counter" in lines and "serve_ok 4" in lines
    assert "# TYPE serve_queue_depth gauge" in lines
    assert "serve_queue_depth 2.0" in lines
    assert "# TYPE serve_request_ms summary" in lines
    assert 'serve_request_ms{quantile="0.5"} 2.0' in lines
    assert 'serve_request_ms{quantile="0.99"} 3.0' in lines
    assert "serve_request_ms_sum 6.0" in lines
    assert "serve_request_ms_count 3" in lines
    # dotted names sanitize; an unset gauge renders NaN, not a crash
    reg.gauge("odd.na").to_obj()
    assert "odd_na NaN" in reg.prometheus()
