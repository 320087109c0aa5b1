"""Device time per layer (``benchmark/layer_times.py``): the scope of each
instruction read from a step program's compiled HLO text, and that map joined
with a reduced trace. On text worked out by hand, on the four cells' step
programs as the TPU's compiler builds them for a described v5e:2x2
(fixtures/``v5e_<cell>.step_hlo.txt.gz``, written by
``benchmark/tools/record_step_hlo.py``; nothing here loads that compiler), and
on the two recorded v5e traces of PR 22."""

from __future__ import annotations

import gzip
import importlib.util
import json
import math
import sys
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness, layer_times, trace_reduce  # noqa: E402
from benchmark.trace_reduce import Reduced  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"
MANIFEST = harness.load_json(REPO / "BENCHMARK.json")
NEW = [m for m in MANIFEST["per_layer"] if m["source"] == "program_span"]
RECORDED = ["blocks12_offline", "blocks12_rows4_offline"]  # cells with a recorded trace
LAYERS = ["conv1", "pool1", "conv2", "pool2", "lrn2"]


def _config(cell: str):
    entry = harness.find_cell(MANIFEST, cell)
    return entry, harness.load_config(MANIFEST, entry["config"])


def _text(cell: str) -> str:
    with gzip.open(FIXTURES / f"v5e_{cell}.step_hlo.txt.gz", "rt") as f:
        return f.read()


def _ctx(cell: str, with_text: bool = True, logs=None):
    """The bare context of ``test_benchmark_trace_reduce``'s reader test,
    with the recorded step program's text where asked."""
    entry, config = _config(cell)
    want = json.loads((FIXTURES / f"v5e_{cell}.expected.json").read_text())
    ctx = types.SimpleNamespace(
        trace=Reduced(trace_reduce.from_json(FIXTURES / want["trace"]), kinds=want["kinds"]),
        peaks=harness.peak_row("TPU v5 lite"), config=config,
        shapes=harness.load_plugin("shapes", config["family"]),
        devices=[None] * entry["chips"], counters={"offline.batch": 128},
        samples={}, spans={}, log=(logs.append if logs is not None else lambda msg: None),
    )
    if with_text:
        ctx.step_hlo_text = _text(cell)
    return ctx


# ---- the scope of an op_name ----------------------------------------------

@pytest.mark.parametrize(
    "op_name,want",
    [
        ("jit(fwd_bf16)/jit(<lambda>)/conv2/conv_general_dilated", "conv2"),
        ("jit(fwd_bf16)/cast_in/convert_element_type", "cast_in"),
        ("jit(fwd_bf16)/jit(fwd)/shard_map/conv2/halo.conv2/ppermute", "halo.conv2"),  # the innermost
        ("jit(fwd_bf16)/jit(fwd)/scatter/jit(_pad)/pad", "scatter"),
        ("jit(f)/conv1+pool1/jit(_conv_block)/pallas_call", "conv1+pool1"),  # one kernel, two layers
        ("jit(f)/conv1/gather", "conv1"),  # lax.gather: the last component is the primitive
        ("jit(f)/gather", None),
        ("jit(f)/conv9/conv_general_dilated", None),  # not a layer of this configuration
        ("jit(f)/halo.conv9/ppermute", None),
        ("jit(f)/conv1+relu/x", None),
        ("x", None),
        ("jit(fwd_bf16)/convert_element_type", None),
    ],
)
def test_scope_of_an_op_name(op_name, want):
    assert layer_times.scope_of(op_name, LAYERS) == want


def test_layer_names_come_from_the_configuration_file():
    assert layer_times.layer_names(_config("blocks12_offline")[1]) == LAYERS
    full = layer_times.layer_names(_config("alexnet_full_offline")[1])
    assert full == LAYERS + ["conv3", "conv4", "conv5", "pool5", "fc6", "fc7", "fc8"]
    assert layer_times.layer_names({"family": "toy", "width": 4}) == []


# ---- the scope map of a program -------------------------------------------

HAND = """
HloModule jit_f, is_scheduled=true

%fused_conv (p0: bf16[2], p1: bf16[2]) -> bf16[2] {
  %p0 = bf16[2] parameter(0), metadata={op_name="x"}
  %cv = bf16[2] convert(%p1), metadata={op_name="jit(f)/cast_in/convert_element_type"}
  %c = bf16[2] convolution(%p0, %cv), window={size=1}, metadata={op_name="jit(f)/conv1/conv_general_dilated"}
  %a = bf16[2] add(%c, %c), metadata={op_name="jit(f)/pool1/add"}
  ROOT %m = bf16[2] maximum(%a, %a), metadata={op_name="jit(f)/pool1/max"}
}

%fused_pool (p: bf16[2]) -> bf16[2] {
  %q = bf16[2] multiply(%p, %p), metadata={op_name="jit(f)/lrn2/mul"}
  %r = bf16[2] multiply(%q, %q), metadata={op_name="jit(f)/lrn2/mul"}
  ROOT %w = bf16[2] reduce-window(%r), window={size=3}, metadata={op_name="jit(f)/pool2/reduce_window_max"}
}

%fused_scale (p: bf16[2]) -> f32[2] {
  %s = bf16[2] multiply(%p, %p), metadata={op_name="jit(f)/lrn2/mul"}
  %d = bf16[2] divide(%s, %p), metadata={op_name="jit(f)/lrn2/div"}
  ROOT %o = f32[2] convert(%d), metadata={op_name="jit(f)/convert_element_type"}
}

%fused_tie (p: bf16[2]) -> bf16[2] {
  %t = bf16[2] multiply(%p, %p), metadata={op_name="jit(f)/pool1/mul"}
  ROOT %u = bf16[2] add(%t, %t), metadata={op_name="jit(f)/conv2/add"}
}

%fused_plain (p: f32[2]) -> f32[2] {
  ROOT %n = f32[2] negate(%p)
}

ENTRY %main (x: f32[2]) -> f32[2] {
  %x = f32[2] parameter(0), metadata={op_name="x"}
  %copy.8 = bf16[2] copy(%x), metadata={op_name="x"}
  %fusion.3 = bf16[2] fusion(%copy.8, %copy.8), kind=kOutput, calls=%fused_conv, metadata={op_name="jit(f)/pool1/max"}
  %fusion.17 = bf16[2] fusion(%fusion.3), kind=kOutput, calls=%fused_pool, metadata={op_name="jit(f)/pool2/max"}
  %multiply_convert_fusion = f32[2] fusion(%fusion.17), kind=kLoop, calls=%fused_scale, metadata={op_name="jit(f)/cvt"}
  %fusion.9 = bf16[2] fusion(%fusion.17), kind=kLoop, calls=%fused_tie, metadata={op_name="jit(f)/conv2/add"}
  %fusion.4 = f32[2] fusion(%multiply_convert_fusion), kind=kLoop, calls=%fused_plain
  %collective-permute-start.1 = bf16[2] collective-permute-start(%fusion.9), metadata={op_name="f/conv2/halo.conv2/pp"}
  %all-gather.5 = f32[8] all-gather(%fusion.4), dimensions={0}
  ROOT %slice.34 = f32[2] slice(%all-gather.5), slice={[0:2]}
}
"""


def test_scope_map_rules_on_a_program_worked_out_by_hand():
    scopes, mixed = layer_times.scope_map(HAND, LAYERS)
    # the convolution wins over the majority (two pool1 instructions) and
    # over the fusion's own op_name, which is its root's
    assert scopes["fusion.3"] == "conv1" and mixed["fusion.3"] == ["cast_in", "conv1", "pool1"]
    # else the reduce-window, over the majority (two lrn2 multiplies)
    assert scopes["fusion.17"] == "pool2" and mixed["fusion.17"] == ["lrn2", "pool2"]
    # else most of its instructions: the root, the output cast, has no scope
    assert scopes["multiply_convert_fusion"] == "lrn2" and "multiply_convert_fusion" not in mixed
    # one each: the root breaks the tie
    assert scopes["fusion.9"] == "conv2" and mixed["fusion.9"] == ["conv2", "pool1"]
    assert scopes["collective-permute-start.1"] == "halo.conv2"
    assert scopes["all-gather.5"] == "gather"  # no scope of its own: by its opcode
    for name in ("copy.8", "slice.34", "fusion.4", "x"):  # (unscoped)
        assert name not in scopes


# the instructions the ledger's breakdown of PR 22 names, per recorded program
KNOWN = {
    "blocks12_offline": {
        "fusion.3": "conv1", "fusion": "pool1", "fusion.12": "conv2", "fusion.17": "pool2",
        "reduce_window_sum.0": "lrn2", "multiply_convert_fusion": "lrn2",
        "convert_element_type.10": "cast_in", "copy-start": None, "custom-call": None,
    },
    "blocks12_rows4_offline": {
        "all-gather.5": "gather", "copy.8": None, "slice.34": None, "fusion.3": "scatter",
        "collective-permute-start.1": "halo.conv1", "collective-permute-done": "halo.conv1",
        "collective-permute-done.2": "halo.pool1", "collective-permute-done.3": "halo.conv2",
        "collective-permute-start.5": "halo.pool2", "multiply_maximum_fusion": "conv1",
        "multiply_maximum_fusion.1": "conv2", "fusion.23": "pool1", "fusion.40": "pool2",
        "reduce_window_sum.9": "lrn2", "multiply_convert_fusion": "lrn2",
    },
    "alexnet_full_offline": {
        "fusion.57": "conv2", "fusion.28": "conv3", "fusion.19": "conv4", "fusion.25": "conv5",
        "fusion.74": "pool5", "fusion.52": "fc6", "fusion.46": "fc7", "fusion.62": "fc8",
        "convert_element_type.28": "cast_in",
    },
    "blocks12_offline_fp32": {
        "fusion.3": "conv1", "fusion.15": "conv2", "power_multiply_fusion": "lrn2",
        "reduce_window_sum.7": "lrn2",
    },
}


@pytest.mark.parametrize("cell", sorted(KNOWN))
def test_scope_map_of_the_step_program_compiled_for_v5e(cell):
    scopes, mixed = layer_times.scope_map(_text(cell), layer_times.layer_names(_config(cell)[1]))
    for name, want in KNOWN[cell].items():
        assert scopes.get(name) == want, (name, scopes.get(name))
    if cell == "alexnet_full_offline":  # one fusion holds conv3 and the LRN's scale
        assert {"conv3", "lrn2"} <= set(mixed["fusion.28"])
    if cell == "blocks12_offline":  # the LRN's squares ride pool2's fusion
        assert {"pool2", "lrn2"} <= set(mixed["fusion.17"])


def test_a_program_without_scopes_maps_only_its_collectives():
    tool = _tool()
    scopes, mixed = layer_times.scope_map(tool.strip_metadata(_text("blocks12_rows4_offline")), LAYERS)
    assert set(scopes.values()) == {"gather"} and not mixed
    assert all("all-gather" in n or "collective-permute" in n for n in scopes)


# ---- joined with the recorded traces --------------------------------------

@pytest.mark.parametrize("cell", RECORDED)
def test_scopes_and_unscoped_sum_to_the_operations_total(cell):
    ctx = _ctx(cell)
    lt = layer_times.of(ctx)
    assert lt.total_s() == pytest.approx(ctx.trace.op_seconds(), rel=1e-12)
    assert sum(lt.unscoped.values()) == pytest.approx(lt.seconds[layer_times.UNSCOPED], rel=1e-9)
    assert 100.0 * lt.scoped_share() >= 95.0
    assert len(lt.steps) == len(ctx.trace.step_durations_ms())
    # per step the scopes add up too: each is a mean over steps and chips
    per_step = sum(sum(row.values()) for row in lt.steps) / 1e6 / len(lt.steps)
    assert sum(lt.step_ms(layer_times.exactly(s)) for s in lt.seconds) == pytest.approx(per_step, rel=1e-9)
    assert layer_times.of(ctx) is lt  # made once per run


def test_per_scope_milliseconds_of_the_recorded_one_chip_trace():
    """PERF.md section 5's table of PR 22, matched by hand there."""
    lt = layer_times.of(_ctx("blocks12_offline"))
    want = {"conv2": 0.604, "conv1": 0.294, "cast_in": 0.108, "lrn2": 0.130, "pool1": 0.037, "pool2": 0.027}
    for scope, ms in want.items():
        assert lt.step_ms(layer_times.exactly(scope)) == pytest.approx(ms, abs=0.001), scope
    assert lt.step_ms(layer_times.covers("pool1", "pool2", "pool5")) == pytest.approx(0.0646, abs=0.001)
    assert [row[0] for row in lt.table()][:2] == ["conv2", "conv1"]


def test_four_chip_trace_splits_into_halos_gather_and_the_unscoped_copy():
    logs = []
    ctx = _ctx("blocks12_rows4_offline", logs=logs)
    lt = layer_times.of(ctx)
    assert set(lt.seconds) >= {"gather", "halo.conv1", "halo.pool1", "halo.conv2", "halo.pool2", "scatter"}
    halo = lt.step_ms(lambda s: s.startswith("halo."))
    # a chip waits 0.2 ms in the all-gather in one step and 3.3 in the next: the
    # mean says what a step pays, a median would say one hump or the other
    gathers = sorted(row["gather"] / 1e6 for row in lt.steps)
    assert gathers[0] < 0.3 and gathers[-1] > 3.0
    assert lt.step_ms(layer_times.exactly("gather")) == pytest.approx(sum(gathers) / len(gathers))
    assert halo > 5 * lt.step_ms(layer_times.covers("conv1", "conv2"))  # the waits dwarf the arithmetic
    assert lt.step_ms(layer_times.covers("conv1")) < 0.1  # the layer's kernel, not its halo
    top = max(lt.unscoped, key=lt.unscoped.get)
    assert top.startswith("copy.8 ")  # the whole-input bf16 copy carries the parameter's name
    assert any("copy.8" in line and layer_times.UNSCOPED in line for line in logs)
    assert any("holds" in line for line in logs)  # mixed fusions are listed


@pytest.mark.parametrize("cell", RECORDED)
def test_every_new_reader_reads_the_recorded_trace_with_its_program_text(cell):
    ctx = _ctx(cell)
    values = {
        m["name"]: harness.load_plugin("layer_metrics", m["name"]).read(ctx)
        for m in harness.metrics_for(MANIFEST, "per_layer", cell) if m in NEW
    }
    assert values["kernels.scoped_share"] >= 95.0
    for name, value in values.items():
        assert value is not None and math.isfinite(value) and value > 0, name
        if name.endswith("_roofline"):
            assert value <= 100.0, (name, value)
    if cell == "blocks12_offline":
        assert values["kernels.conv1_roofline"] == pytest.approx(47.4, abs=0.5)
        assert values["kernels.conv2_roofline"] == pytest.approx(96.4, abs=0.5)
        assert values["kernels.cast_in_ms"] == pytest.approx(0.108, abs=0.001)
        assert values["kernels.lrn_ms"] == pytest.approx(0.130, abs=0.001)
    else:
        assert values["sharding.halo_ms"] > 1.0 and values["sharding.gather_ms"] > 1.0


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_new_reader_without_text_reads_zero_and_without_a_device_plane_nothing(metric):
    read = harness.load_plugin("layer_metrics", metric["name"]).read
    logs = []
    cell = (metric.get("workloads") or RECORDED)[-1]
    cell = cell if cell in RECORDED else RECORDED[0]
    # the bare context of the existing reader test: no adapter, no text
    assert read(_ctx(cell, with_text=False, logs=logs)) == 0.0
    assert any("no step program text" in line for line in logs)
    # a CPU rehearsal's trace has no device plane: nothing, before any work
    empty = types.SimpleNamespace(trace=Reduced({"devices": {}, "host": []}), counters={})
    assert read(empty) is None
    assert read(types.SimpleNamespace(trace=None, counters={})) is None


# ---- where the text comes from in a real run ------------------------------

def test_step_program_text_is_built_through_the_adapter_and_names_the_layers():
    _entry, config = _config("blocks12_offline")
    config = dict(config, in_height=63, in_width=63)
    logs = []
    ctx = types.SimpleNamespace(
        config=config, adapter=harness.load_plugin("adapters", config["family"]),
        counters={"offline.batch": 2}, log=logs.append,
    )
    text = layer_times.step_hlo_text(ctx)
    scopes, _mixed = layer_times.scope_map(text, layer_times.layer_names(config))
    assert set(LAYERS) | {"cast_in"} <= set(scopes.values())

    def broken(cfg):
        raise RuntimeError("no such program")

    ctx.adapter = types.SimpleNamespace(
        make_params=ctx.adapter.make_params, input_shape=ctx.adapter.input_shape, build_forward=broken
    )
    assert layer_times.step_hlo_text(ctx) is None  # a reader never ends a run
    assert any("no such program" in line for line in logs)


# ---- operations and bytes of a layer --------------------------------------

def test_layer_work_counts_matmul_flops_and_the_bytes_a_layer_cannot_avoid():
    shapes = harness.load_plugin("shapes", "alexnet")
    _e, b12 = _config("blocks12_offline")
    flops1, bytes1 = layer_times.layer_work(shapes, b12, ["conv1"], 128)
    flops2, _ = layer_times.layer_work(shapes, b12, ["conv2"], 128)
    assert flops1 == 128 * 2 * 55 * 55 * 96 * 11 * 11 * 3
    assert flops1 + flops2 == 128 * shapes.matmul_flops_per_image(b12)
    # input read, output written, parameters read, two bytes each in bf16
    assert bytes1 == 2 * (128 * (227 * 227 * 3 + 55 * 55 * 96) + 11 * 11 * 3 * 96 + 96)
    _e, fp32 = _config("blocks12_offline_fp32")
    assert layer_times.layer_work(shapes, fp32, ["conv1"], 128) == (flops1, 2 * bytes1)
    assert layer_times.layer_work(shapes, b12, ["pool1"], 128)[0] == 0
    _e, full = _config("alexnet_full_offline")
    flops, bytes_ = layer_times.layer_work(shapes, full, ["fc6", "fc7", "fc8"], 256)
    n = 9216 * 4096 + 4096 * 4096 + 4096 * 1000
    assert flops == 256 * 2 * n
    assert bytes_ == 2 * (256 * (9216 + 1000) + n + 4096 + 4096 + 1000)
    # a run of layers: the first's input, the last's output, all their parameters
    f345, b345 = layer_times.layer_work(shapes, full, ["conv3", "conv4", "conv5"], 1)
    assert f345 == 2 * 13 * 13 * 9 * (256 * 384 + 384 * 384 + 384 * 256)
    assert b345 == 2 * (13 * 13 * 256 * 2 + 9 * (256 * 384 + 384 * 384 + 384 * 256) + 384 + 384 + 256)


def test_a_fused_kernel_counts_for_each_layer_it_covers_and_a_halo_for_none():
    assert layer_times.covers("conv1")("conv1+pool1") and layer_times.covers("pool1")("conv1+pool1")
    assert not layer_times.covers("conv1")("halo.conv1")
    assert not layer_times.covers("conv1")(layer_times.UNSCOPED)
    assert layer_times.exactly("gather")("gather") and not layer_times.exactly("gather")("scatter")


# ---- the recording tool ---------------------------------------------------

def _tool():
    spec = importlib.util.spec_from_file_location(
        "record_step_hlo", REPO / "benchmark" / "tools" / "record_step_hlo.py"
    )
    module = importlib.util.module_from_spec(spec)
    path = list(sys.path)
    try:
        spec.loader.exec_module(module)  # importing it describes no topology
    finally:
        sys.path[:] = path
    return module


def test_stripping_metadata_leaves_the_program_and_takes_every_name():
    text = _text("blocks12_offline")
    bare = _tool().strip_metadata(text)
    assert "op_name" not in bare and "FileNames" not in bare and "stack_frame_id" not in bare
    assert "%fusion.12 = " in bare and "ENTRY" in bare
    assert trace_reduce.fusion_kinds(bare) == trace_reduce.fusion_kinds(text)
