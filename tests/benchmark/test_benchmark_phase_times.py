"""Device time per phase of ``moe.experts`` and ``moe.route``
(``benchmark/phase_times.py``) and the five metrics that read it, on a program
text and a trace worked out by hand: the phases partition what
``scope_roofline.body_ms`` counts for their layer, a loop that carries a phase
is left out beside its body, a program that names no phase reads 0.0
everywhere and leaves the layers' table as it was, and the work the products'
roofline is judged by is the family's own for each of the three
configurations. Nothing here loads a compiler or a device."""

from __future__ import annotations

import json
import re
import sys
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import manifest_rules as rules  # noqa: E402
from benchmark import harness, layer_times, phase_times, scope_roofline, trace_reduce  # noqa: E402

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = ["dots_vlm1_prefill_s4096", "solar_open2_prefill_s8192", "zaya1_prefill_s4096"]
SPANS = ["kernels.moe_products_roofline", "kernels.moe_gather_ms", "kernels.moe_combine_ms", "kernels.moe_sort_ms"]
NEW = SPANS + ["moe.tile_fill_share"]
CONFIG = {
    "layers": [{"name": n} for n in ("embed", "moe.route", "moe.experts", "moe.shared")],
    "compute": "bf16", "hidden_size": 8, "seq_len": 4,
}
SHAPES = types.SimpleNamespace(  # a family's shape functions, as far as the products' work asks them
    experts_flops=lambda cfg, pairs: 1e9 * pairs, experts_bytes=lambda cfg, pairs: 1e6 + pairs,
    expected_pairs_per_step=lambda cfg, batch: 3.0,
)

# One MoE layer as the compiler might leave it: a chunk loop inside
# ``moe.experts`` whose body holds the gather, the three products and the
# layout; a fusion that spans two phases of one layer, one of another layer
# with a phase in its body, one that ``layer_times`` gives to ``moe.route``
# (four instructions of seven) and whose commonest name is a phase of
# ``moe.experts`` (three); a ``while`` that itself carries a phase; a zero fill
# the compiler renamed.
RAW = """
HloModule jit_fwd_bf16, is_scheduled=true

%fused_gather (p: bf16[2]) -> bf16[2] {
  %i = s32[2] add(%p, %p), @~/experts.gather/add
  %j = s32[2] clamp(%i, %i, %i), @~/experts.gather/clamp
  ROOT %g = bf16[2] gather(%p, %j), @~/experts.gather/gather
}

%fused_silu (p: f32[2]) -> bf16[2] {
  %s = f32[2] multiply(%p, %p), @~/experts.products/mul
  %t = f32[2] logistic(%s), @~/experts.products/logistic
  ROOT %c = bf16[2] convert(%t), @~/experts.layout/convert_element_type
}

%fused_slab (p: bf16[2]) -> bf16[2] {
  %v = bf16[2] convert(%p), @~/experts.layout/convert_element_type
  ROOT %d = bf16[2] dynamic-update-slice(%p, %v), @~/experts.layout/dynamic_update_slice
}

%fused_first (p: f32[2]) -> f32[2] {
  %a = f32[2] multiply(%p, %p), @moe.experts/experts.combine/mul
  %b = f32[2] add(%a, %p), @moe.shared/add
  %e = f32[2] add(%b, %p), @moe.shared/add
  ROOT %h = f32[2] add(%e, %p), @moe.shared/add
}

%fused_counts (p: s32[2]) -> s32[2] {
  %k = s32[2] compare(%p, %p), @moe.route/route.score/eq
  %q = s32[2] add(%k, %k), @moe.route/route.sort/add
  ROOT %r = s32[2] subtract(%q, %k), @moe.route/route.sort/sub
}

%fused_astray (p: s32[2]) -> s32[2] {
  %w = s32[2] add(%p, %p), @moe.route/route.sort/add
  %x = s32[2] add(%w, %p), @moe.route/route.sort/sub
  %y = s32[2] add(%x, %p), @moe.route/route.score/add
  %z = s32[2] add(%y, %p), @moe.route/route.score/add
  %l = s32[2] add(%z, %p), @~/experts.gather/add
  %m = s32[2] add(%l, %p), @~/experts.gather/add
  ROOT %n = s32[2] add(%m, %p), @~/experts.gather/add
}

%chunk_body (c: (s32[], bf16[2])) -> (s32[], bf16[2]) {
  %fusion.1 = bf16[2] fusion(%c), kind=kLoop, calls=%fused_gather, @~/experts.gather/gather
  %custom-call.1 = f32[2] custom-call(%fusion.1), @~/experts.products/jit(grouped_matmul)/pallas_call
  %custom-call.2 = f32[2] custom-call(%fusion.1), @~/experts.products/jit(grouped_matmul)/pallas_call
  %fusion.2 = bf16[2] fusion(%custom-call.1), kind=kLoop, calls=%fused_silu, @~/experts.products/mul
  %custom-call.3 = f32[2] custom-call(%fusion.2), @~/experts.products/jit(grouped_matmul)/pallas_call
  %fusion.3 = bf16[2] fusion(%custom-call.3), kind=kLoop, calls=%fused_slab, @~/experts.layout/dynamic_update_slice
  %add.7 = s32[] add(%c, %c), @~/add
  ROOT %tuple.1 = (s32[], bf16[2]) tuple(%add.7, %fusion.3)
}

ENTRY %main (ids: s32[2]) -> f32[2] {
  %ids = s32[2] parameter(0), metadata={op_name="ids"}
  %fusion.10 = f32[2] fusion(%ids), kind=kLoop, calls=%fused_plain, @embed/gather
  %fusion.11 = f32[2] fusion(%fusion.10), kind=kOutput, calls=%fused_router, @moe.route/route.score/td,de->te
  %sort.1 = s32[2] sort(%fusion.11), dimensions={0}, @moe.route/route.sort/jit(argsort)/sort
  %fusion.12 = s32[2] fusion(%sort.1), kind=kLoop, calls=%fused_counts, @moe.route/route.sort/sub
  %reduce-window.1 = s32[2] reduce-window(%fusion.12), window={size=2}
  %slice.1 = s32[1] slice(%fusion.12), slice={[1:2]}, @moe.route/dynamic_slice
  %fusion.13 = s32[2] fusion(%slice.1), kind=kLoop, calls=%fused_astray, @moe.route/route.sort/add
  %broadcast.31 = f32[2] broadcast(), dimensions={}, metadata={op_name="jit(f)"}
  %while.1 = (s32[], bf16[2]) while(%fusion.12), condition=%cond, body=%chunk_body, @moe.experts/while
  %while.2 = (s32[], bf16[2]) while(%while.1), condition=%cond, body=%chunk_body, @moe.experts/experts.combine/while
  %custom-call.4 = f32[2] custom-call(%while.1), @moe.experts/experts.combine/jit(moe_combine)/pallas_call
  %copy.5 = f32[2] copy(%custom-call.4), @moe.experts/while
  ROOT %fusion.14 = f32[2] fusion(%copy.5), kind=kLoop, calls=%fused_first, @moe.shared/add
}
"""
# ``@path`` at a line's end is that instruction's ``metadata={op_name="jit(f)/path"}``, ``~`` the chunk loop's body
HAND = re.sub(
    r"@(\S+)$", lambda m: 'metadata={op_name="jit(f)/%s"}' % m.group(1).replace("~", "moe.experts/while/body"),
    RAW, flags=re.M,
)

# nanoseconds of each operation in one run of the step program
NS = {
    "fusion.10": 500, "fusion.11": 4000, "sort.1": 3000, "fusion.12": 700, "reduce-window.1": 300,
    "slice.1": 10, "fusion.13": 40, "broadcast.31": 600,
    "fusion.1": 2400, "custom-call.1": 5000, "custom-call.2": 5200, "fusion.2": 900, "custom-call.3": 5400,
    "fusion.3": 1300, "add.7": 5, "custom-call.4": 3900, "copy.5": 3300, "fusion.14": 2000,
}
CHUNK_BODY = ("fusion.1", "custom-call.1", "custom-call.2", "fusion.2", "custom-call.3", "fusion.3", "add.7")
WANT = {  # ms a step
    "experts.gather": 2400, "experts.products": 5000 + 5200 + 900 + 5400, "experts.layout": 1300,
    "experts.combine": 3900, "moe.experts (no phase)": 5 + 3300,
    "route.score": 4000, "route.sort": 3000 + 700, "moe.route (no phase)": 10 + 40,
}


def _without_phases(text: str) -> str:
    return re.sub(r"/(?:experts|route)\.\w+/", "/", text)


def _trace(runs: int = 3):
    """``runs`` back-to-back runs of the step program, every operation once a
    run; both loops last as long as the chunk's body, which is listed too."""
    ops, modules, t = [], [], 1000
    for _run in range(runs):
        start = t
        for name, ns in NS.items():
            opcode = "custom-call" if name.startswith("custom-call") else name.split(".")[0]
            ops.append([f"{name} f32[2]", opcode, t, ns])
            t += ns
        body = sum(NS[n] for n in CHUNK_BODY)
        ops.append(["while.1 (s32[], bf16[2])", "while", t - body, body])
        ops.append(["while.2 (s32[], bf16[2])", "while", t - body, body])
        modules.append(["jit_fwd_bf16(7)", start, t - start])
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "host": []}


def _ctx(text=HAND, logs=None, **more):
    fields = dict(
        trace=trace_reduce.Reduced(_trace()), peaks=harness.peak_row("TPU v5 lite"), config=dict(CONFIG), shapes=SHAPES,
        devices=[None], counters={"offline.batch": 2}, samples={}, spans={},
        log=(logs.append if logs is not None else lambda msg: None), step_hlo_text=text,
    )
    fields.update(more)
    return types.SimpleNamespace(**fields)


def _read(name):
    return harness.load_plugin("layer_metrics", name).read


# ---- the split ------------------------------------------------------------

@pytest.mark.parametrize("layer", sorted(phase_times.PHASES_OF))
def test_the_phases_and_no_phase_partition_what_the_layer_reads_from_outside(layer):
    logs = []
    ctx = _ctx(logs=logs)
    pt = phase_times.of(ctx)
    assert phase_times.of(ctx) is pt and ctx.phase_times is pt  # made once
    parts = list(phase_times.PHASES_OF[layer]) + [layer + phase_times.NO_PHASE]
    for part in parts:
        assert pt.ms(part) * 1e6 == pytest.approx(WANT[part]), part
    whole = scope_roofline.body_ms(ctx, layer_times.of(ctx), layer)
    assert sum(pt.ms(part) for part in parts) == pytest.approx(whole, abs=1e-12)
    assert whole * 1e6 == pytest.approx(sum(WANT[part] for part in parts))
    assert any(f"{layer:>12s}" in line and "(no phase)" in line for line in logs)
    # what (no phase) holds is listed by operation, from a microsecond a step: the loop result's copy, not the scalars
    listed = [line.split()[-2] for line in logs if f"{layer:>12s}       (no phase)" in line and "%" not in line]
    assert [op for op in listed if op in NS] == (["copy.5"] if layer == "moe.experts" else [])
    # the instructions of no layer stay where they were: the renamed zero fill, the cumsum's window
    assert "broadcast.31" not in pt.scopes and "reduce-window.1" not in pt.scopes
    assert pt.scopes["fusion.10"] == "embed" and pt.scopes["fusion.14"] == "moe.shared"


@pytest.mark.parametrize(
    "fusion,found,counted",
    [
        ("fusion.2", ["experts.layout", "experts.products"], "experts.products"),  # most of its instructions
        ("fusion.12", ["route.score", "route.sort"], "route.sort"),
        # layer_times gave it to moe.route (4 of 7), its commonest name is a phase of moe.experts (3 against 2 and
        # 2): a phase counts only inside its own layer, so this is moe.route's (no phase), and it is said
        ("fusion.13", ["experts.gather", "route.score", "route.sort"], "moe.route (no phase)"),
        ("fusion.14", None, "moe.shared"),  # a phase in the body of another layer's fusion moves nothing
    ],
)
def test_a_fusion_that_spans_phases_counts_once_and_inside_its_own_layer(fusion, found, counted):
    logs = []
    ctx = _ctx(logs=logs)
    pt = phase_times.of(ctx)
    assert pt.scopes[fusion] == counted
    listed = [line for line in logs if f"phase times: {fusion} holds" in line]
    if found:
        assert listed == [f"phase times: {fusion} holds {' + '.join(found)}; counted under {counted}"]
    else:
        assert not listed  # under two phases of PHASES_READ: nothing to say


def test_a_while_that_carries_a_phase_is_left_out_beside_its_body():
    ctx = _ctx()
    pt = phase_times.of(ctx)
    assert pt.scopes["while.2"] == "experts.combine" and pt.scopes["while.1"] == "moe.experts (no phase)"
    assert pt.ms("experts.combine") * 1e6 == pytest.approx(NS["custom-call.4"])
    # the per-scope table, which counts a loop beside its body, reads the loop's 20.2 us more
    table = layer_times.LayerTimes(ctx.trace, pt.scopes)
    body = sum(NS[n] for n in CHUNK_BODY)
    assert table.step_ms(layer_times.exactly("experts.combine")) * 1e6 == pytest.approx(NS["custom-call.4"] + body)


@pytest.mark.parametrize("metric", SPANS)
def test_each_reader_reads_its_phases(metric):
    logs = []
    ctx = _ctx(logs=logs)
    got = _read(metric)(ctx)
    if metric == "kernels.moe_products_roofline":
        # 3 pairs: 3 GFLOP -> 15.2 us at 197 TFLOP/s; (1e6 + 3 x 8 x 2 x 2) B -> 1.2 us: compute-bound, of 16.5 us
        assert got == pytest.approx(100.0 * (3e9 / 197e12) / (WANT["experts.products"] * 1e-9), rel=1e-6)
        assert any("roofline of experts.products" in line and "compute-bound" in line for line in logs)
    else:
        want = {
            "kernels.moe_gather_ms": WANT["experts.gather"],
            "kernels.moe_combine_ms": WANT["experts.layout"] + WANT["experts.combine"],
            "kernels.moe_sort_ms": WANT["route.sort"],
        }[metric]
        assert got * 1e6 == pytest.approx(want)


# ---- a program that names no phase, a run with no device plane ------------------

@pytest.mark.parametrize("metric", SPANS)
def test_a_program_without_phases_reads_zero_and_the_layers_table_is_unchanged(metric):
    named, logs = _ctx(), []
    bare = _ctx(text=_without_phases(HAND), logs=logs, shapes=None)  # the shapes are never asked
    assert "experts." not in bare.step_hlo_text and "/moe.experts/" in bare.step_hlo_text
    assert _read(metric)(bare) == 0.0
    assert any("names no phases" in line for line in logs)
    assert layer_times.of(bare).table() == layer_times.of(named).table()
    pt = phase_times.of(bare)
    for layer in phase_times.PHASES_OF:  # everything is the layer's (no phase)
        whole = scope_roofline.body_ms(bare, layer_times.of(bare), layer)
        assert pt.ms(layer + phase_times.NO_PHASE) == pytest.approx(whole)
    # and reading the phases first changes nothing the layers' readers read afterwards
    first = _ctx()
    _read(metric)(first)
    assert layer_times.of(first).table() == layer_times.of(named).table()
    assert _read("kernels.moe_route_ms")(first) == _read("kernels.moe_route_ms")(named) > 0


@pytest.mark.parametrize("metric", NEW)
def test_without_a_device_plane_every_new_reader_reads_nothing(metric):
    read = _read(metric)
    empty = types.SimpleNamespace(trace=trace_reduce.Reduced({"devices": {}, "host": []}), counters={})
    assert read(empty) is None
    assert read(types.SimpleNamespace(trace=None, counters={})) is None


def test_the_text_is_lowered_once_for_all_five_readers():
    """Where the driver left no text on the context (the image driver), the
    first reader's lowering is kept there for the rest, ``layer_times``'
    included."""
    calls, logs = [], []

    class Adapter:
        make_params = staticmethod(lambda cfg, seed: {})
        input_shape = staticmethod(lambda cfg, batch: (batch, 2))

        @staticmethod
        def build_forward(cfg):
            calls.append(1)
            lowered = types.SimpleNamespace(compile=lambda: types.SimpleNamespace(as_text=lambda: HAND))
            return types.SimpleNamespace(lower=lambda params, x: lowered)

    ctx = _ctx(logs=logs, adapter=Adapter)
    del ctx.step_hlo_text
    assert phase_times.ms(ctx, "experts.gather") > 0 and ctx.step_hlo_text == HAND
    for metric in SPANS[1:]:
        assert _read(metric)(ctx) > 0
    # layer_times.of came first and does not keep its text: its lowering and one more, whatever is read after
    assert len(calls) == 2


# ---- the counter's reader -------------------------------------------------

@pytest.mark.parametrize(
    "gauges,want",
    [
        ({"moe.pairs_held": 15898.0, "moe.rows_padded": 24576.0, "moe.pairs_all": 262144.0}, 100.0 * 15898 / 24576),
        ({"moe.pairs_held": 512.0, "moe.rows_padded": 512.0}, 100.0),  # every tile full
        ({"moe.pairs_held": 15898.0, "moe.pairs_all": 262144.0}, 0.0),  # the parent: no such gauge
        ({"moe.pairs_held": 0.0, "moe.rows_padded": 0.0}, 0.0),  # nothing routed here
        ({}, 0.0),
    ],
)
def test_tile_fill_share_is_the_pairs_over_the_padded_rows(gauges, want):
    adapter = types.SimpleNamespace(registry_summary=lambda: types.SimpleNamespace(summary=lambda: dict(gauges)))
    assert _read("moe.tile_fill_share")(types.SimpleNamespace(adapter=adapter)) == pytest.approx(want)
    assert _read("moe.tile_fill_share")(types.SimpleNamespace(adapter=object())) is None
    assert _read("moe.tile_fill_share")(types.SimpleNamespace()) is None


# ---- the products' work ---------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_the_products_work_is_the_familys_own_for_the_reference_pairs(cell):
    entry = harness.find_cell(MANIFEST, cell)
    cfg = harness.load_config(MANIFEST, entry["config"])
    shapes = harness.load_plugin("shapes", cfg["family"])
    traffic = harness.load_json(REPO / "benchmark" / "traffic" / f"{entry['traffic']}.json")
    batch, tokens = int(traffic["batch"]), int(traffic["batch"]) * cfg["seq_len"]
    layers, width = shapes.n_moe_layers(cfg), 2
    held = cfg.get("n_routed_experts", cfg.get("num_experts"))
    # the reference routed 1,000 pairs to the held experts on one checked sequence
    counters = {"check.ref_pairs_held": 1000.0, "check.ref_tokens": float(cfg["seq_len"])}
    ctx = types.SimpleNamespace(config=cfg, shapes=shapes, counters=counters)
    pairs = 1000.0 * tokens / cfg["seq_len"]
    assert phase_times.pairs_held(ctx, batch) == pytest.approx(pairs)
    flops, bytes_ = phase_times.products_work(ctx, batch)
    one_expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    assert flops == shapes.experts_flops(cfg, pairs) == pytest.approx(2.0 * pairs * one_expert)
    assert bytes_ == pytest.approx(layers * held * one_expert * width + pairs * cfg["hidden_size"] * 2 * width)
    # the same pairs kernels.moe_experts_roofline takes, and less to move than the whole scope's yardstick
    registry = types.SimpleNamespace(summary=lambda: {})
    whole = _read("kernels.moe_experts_roofline").__globals__["_work"](
        types.SimpleNamespace(config=cfg, shapes=shapes, counters=counters, log=lambda msg: None,
                              adapter=types.SimpleNamespace(registry_summary=lambda: registry)), batch)
    assert whole[0] == flops and whole[1] > bytes_
    # where the check has not run: a uniform router's share
    uniform = types.SimpleNamespace(config=cfg, shapes=shapes, counters={})
    assert phase_times.pairs_held(uniform, batch) == shapes.expected_pairs_per_step(cfg, batch)


# ---- the manifest ---------------------------------------------------------

@pytest.mark.parametrize("name", NEW)
def test_the_new_entries_keep_the_manifests_rules(name):
    entries = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    metric = entries[0]
    rules.check_per_layer_metric(MANIFEST, REPO, metric)
    assert metric["workloads"] == CELLS and metric["layer"] == "kernels" and metric["moves"] == "images_per_s"
    assert metric["source"] == ("program_counter" if name == "moe.tile_fill_share" else "program_span")
    assert (metric["unit"], metric["better"]) == {
        "kernels.moe_products_roofline": ("%", "higher"), "moe.tile_fill_share": ("%", "higher"),
    }.get(name, ("ms", "lower"))
    # appended: the entries the benchmark had stand before them, in their order
    assert [m["name"] for m in MANIFEST["per_layer"]][-len(NEW):] == NEW
    # the phase names are the reader's own list and the program's, letter for letter
    from cuda_mpi_gpu_cluster_programming_tpu.ops import scopes

    assert phase_times.PHASES_READ == scopes.PHASES
    assert {p: l for l, ps in phase_times.PHASES_OF.items() for p in ps} == scopes.PHASE_LAYER
