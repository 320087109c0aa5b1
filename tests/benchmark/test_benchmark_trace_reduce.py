"""The reduction from a trace to numbers, on a trace small enough to work
out by hand and on one cut from a real v5e trace of PR 22 (fixtures/)."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import trace_reduce  # noqa: E402
from benchmark.trace_reduce import Reduced  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"

# Two chips over a window of 1000 ns. Chip 0: a convolution 0-400, a fusion
# 300-500 that overlaps it, a collective 800-1000. Chip 1: one convolution
# 0-250. The host fences 500-800 inside a chain span 0-1000.
HAND = {
    "devices": {
        "/device:TPU:0": {
            "ops": [
                ["convolution.1 f32[8]", "convolution", 0, 400],
                ["fusion.7 f32[8]", "fusion", 300, 200],
                ["collective-permute-start.2 f32[8]", "collective-permute-start", 800, 200],
            ],
            "modules": [["jit_fwd(1)", 0, 500], ["jit_fwd(1)", 800, 200], ["jit_other(2)", 500, 10]],
        },
        "/device:TPU:1": {
            "ops": [["fusion.9 f32[8]", "fusion", 0, 250]],
            "modules": [["jit_fwd(1)", 0, 125], ["jit_fwd(1)", 125, 125]],
        },
        "/device:TPU:2": {"ops": [], "modules": []},
    },
    "host": [
        ["main", "bench.window", 0, 1000],
        ["main", "bench.chain", 0, 1000],
        ["main", "bench.fence", 500, 300],
        ["worker", "unrelated", 5000, 100],
    ],
}


def test_merge_intervals_unions_overlaps_and_keeps_gaps():
    merged = trace_reduce.merge_intervals([(300, 500), (0, 400), (800, 1000), (850, 900)])
    assert merged == [(0, 500), (800, 1000)]


def test_busy_is_the_union_and_idle_is_the_mean_over_chips():
    r = Reduced(HAND)
    assert list(r.devices) == ["/device:TPU:0", "/device:TPU:1"]  # a chip with no work is no plane
    assert r.window_ns == (0, 1000) and r.window_s == pytest.approx(1e-6)
    assert r.busy_s_by_device() == pytest.approx(
        {"/device:TPU:0": 700e-9, "/device:TPU:1": 250e-9}
    )
    assert r.busy_s() == pytest.approx(475e-9)
    assert r.idle_share() == pytest.approx(1 - 0.475)


KINDS = {"fusion.7": "loop", "fusion.9": "convolution"}


def test_a_plane_that_lost_most_of_its_steps_is_named_and_left_out():
    lossy = json.loads(json.dumps(HAND))
    lossy["devices"]["/device:TPU:1"]["modules"] = [["jit_fwd(1)", i * 10, 5] for i in range(40)]
    lossy["devices"]["/device:TPU:1"]["ops"].append(["fusion.9 f32[8]", "fusion", 750, 250])
    r = Reduced(lossy)  # chip 0 shows 2 steps against chip 1's 40
    assert r.incomplete == ["/device:TPU:0"] and r.planes_with_work == 2
    assert list(r.devices) == ["/device:TPU:1"]
    assert r.busy_s() == pytest.approx(500e-9) and r.window_s == pytest.approx(1e-6)
    assert r.idle_share() == pytest.approx(0.5)  # of the whole chip alone, as busy_s is


def test_category_sums_and_shares():
    r = Reduced(HAND, kinds=KINDS)
    assert r.category_seconds() == pytest.approx({
        "convolution": 400e-9, "convolution fusion": 250e-9,
        "loop fusion": 200e-9, "collective-permute-start": 200e-9,
    })
    assert r.op_seconds() == pytest.approx(1050e-9)
    assert r.share_of(trace_reduce.CONV_CATEGORIES) == pytest.approx(650 / 1050)
    assert r.share_of(trace_reduce.COLLECTIVE_CATEGORIES) == pytest.approx(200 / 1050)


def test_a_fusion_that_is_not_named_stays_a_plain_fusion():
    r = Reduced(HAND)
    assert r.category_seconds()["fusion"] == pytest.approx(450e-9)
    assert r.share_of(trace_reduce.CONV_CATEGORIES) == pytest.approx(400 / 1050)


def test_top_ops_keep_name_shape_and_category():
    top = Reduced(HAND, kinds=KINDS).top_ops(2)
    assert top[0] == ["convolution.1 f32[8] [convolution]", pytest.approx(400e-9)]
    assert top[1] == ["fusion.9 f32[8] [convolution fusion]", pytest.approx(250e-9)]


def test_step_program_is_the_one_with_most_device_time():
    r = Reduced(HAND)
    assert r.step_program() == "jit_fwd"
    assert sorted(r.step_durations_ms()) == pytest.approx([125e-6, 125e-6, 200e-6, 500e-6])


def test_idle_gap_is_named_by_the_shortest_host_event_that_covers_it():
    gaps = Reduced(HAND).idle_gaps()
    # chip 0 idles 500-800: the fence covers it and is shorter than the chain
    assert gaps == [["bench.fence", pytest.approx(300e-9)]]
    gaps1 = Reduced(HAND).idle_gaps(device="/device:TPU:1")
    # chip 1 idles 250-1000: only the chain covers half of it
    assert gaps1 == [["bench.chain", pytest.approx(750e-9)]]


def test_a_gap_with_no_host_event_says_so():
    trace = {"devices": {"/device:TPU:0": {"ops": [["a", "", 0, 10], ["b", "", 90, 10]], "modules": []}}, "host": []}
    assert Reduced(trace).idle_gaps() == [["(no host event)", pytest.approx(80e-9)]]


def test_cut_and_json_round_trip(tmp_path):
    part = trace_reduce.cut(HAND, 0, 600)
    assert [e[0] for e in part["devices"]["/device:TPU:0"]["ops"]] == ["convolution.1 f32[8]", "fusion.7 f32[8]"]
    assert ["worker", "unrelated", 5000, 100] not in part["host"]
    path = tmp_path / "t.json.gz"
    trace_reduce.to_json(part, path)
    assert trace_reduce.from_json(path) == part


@pytest.mark.parametrize(
    "text,want",
    [
        (  # as the v5e trace of PR 22 names conv2
            "%fusion.12 = bf16[128,27,27,256]{3,0,2,1:T(8,128)(2,1)S(1)} fusion(bf16[128,27,27,96]"
            "{0,3,2,1:T(8,128)(2,1)S(1)} %fusion, f32[5,5,96,256]{3,2,1,0:T(8,128)S(1)} %custom-call),"
            " kind=kOutput, calls=%fused_computation.13",
            ("fusion.12 bf16[128,27,27,256]", "fusion"),
        ),
        (
            "%copy-start = (f32[11,11,3,96]{3,2,1,0:T(4,128)S(1)}, f32[11,11,3,96]{3,2,1,0:T(4,128)},"
            " u32[]{:S(2)}) copy-start(f32[11,11,3,96]{3,2,1,0:T(4,128)} %p__conv1____w__.1)",
            ("copy-start f32[11,11,3,96]", "copy-start"),
        ),
        (
            "%reduce_window_sum.0 = bf16[128,13,13,256]{3,0,2,1:T(8,128)(2,1)S(1)} reduce-window("
            "bf16[128,13,13,256]{3,0,2,1} %get-tuple-element, bf16[]{:T(256)} %constant.0), window={size=1x1x1x5}",
            ("reduce_window_sum.0 bf16[128,13,13,256]", "reduce-window"),
        ),
        ("not hlo text", ("not hlo text", "")),
    ],
)
def test_parse_op(text, want):
    assert trace_reduce.parse_op(text) == want


def test_fusion_kinds_from_compiled_hlo_text():
    import jax
    import jax.numpy as jnp

    def f(x, w):
        y = jax.lax.conv_general_dilated(x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.maximum(y, 0.0) * 2.0

    text = jax.jit(f).lower(jnp.ones((2, 8, 8, 4)), jnp.ones((3, 3, 4, 4))).compile().as_text()
    kinds = trace_reduce.fusion_kinds(text)
    assert kinds, text[:400]
    assert set(kinds.values()) <= {"convolution", "reduce-window", "loop", "output", "input", "custom"}
    hand = """
%fused_computation.3 (p0: f32[2], p1: f32[2]) -> f32[2] {
  %p0 = f32[2] parameter(0)
  ROOT %c = f32[2] convolution(%p0, %p1), window={size=1}
}

%fused_computation (p: f32[2]) -> f32[2] {
  ROOT %m = f32[2] multiply(%p, %p)
}

ENTRY %main (a: f32[2]) -> f32[2] {
  %fusion.3 = f32[2]{0} fusion(f32[2] %a, f32[2] %a), kind=kOutput, calls=%fused_computation.3
  ROOT %fusion = f32[2]{0} fusion(f32[2] %fusion.3), kind=kLoop, calls=%fused_computation
}
"""
    assert trace_reduce.fusion_kinds(hand) == {"fusion.3": "convolution", "fusion": "loop"}


@pytest.mark.parametrize("name", sorted(p.name for p in FIXTURES.glob("*.expected.json")))
def test_recorded_chip_trace_reduces_to_its_known_numbers(name):
    want = json.loads((FIXTURES / name).read_text())
    r = Reduced(trace_reduce.from_json(FIXTURES / want["trace"]), kinds=want["kinds"])
    assert len(r.devices) == want["device_planes"] and r.incomplete == want.get("incomplete", [])
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert r.busy_s() == pytest.approx(want["busy_s"], rel=1e-9)
    assert r.idle_share() == pytest.approx(want["idle_share"], rel=1e-9)
    assert r.idle_share() == pytest.approx(1 - r.busy_s() / r.window_s)
    assert r.step_program() == want["step_program"]
    assert len(r.step_durations_ms()) == want["steps"]
    for cat, secs in want["category_seconds"].items():
        assert r.category_seconds()[cat] == pytest.approx(secs, rel=1e-9)
    assert r.share_of(trace_reduce.CONV_CATEGORIES) == pytest.approx(want["conv_share"], rel=1e-9)
    if "collective_share" in want:
        assert r.share_of(trace_reduce.COLLECTIVE_CATEGORIES) == pytest.approx(
            want["collective_share"], rel=1e-9
        )
    assert [g[0] for g in r.idle_gaps(3)] == want["idle_gap_names"]


# Which cell each recorded trace was cut from.
RECORDED = {
    "blocks12_offline": "v5e_blocks12_offline.expected.json",
    "blocks12_rows4_offline": "v5e_blocks12_rows4_offline.expected.json",
}


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_every_per_layer_metric_of_the_cell_reads_a_value_from_its_recorded_trace(cell):
    """The check refuses a traced run whose last line lacks one of the cell's
    metrics, so on a real chip trace (one of them with a chip's plane
    incomplete) no reader of the cell may come back empty."""
    import math
    import types

    from benchmark import harness

    manifest = harness.load_json(REPO / "BENCHMARK.json")
    entry = harness.find_cell(manifest, cell)
    config = harness.load_config(manifest, entry["config"])
    want = json.loads((FIXTURES / RECORDED[cell]).read_text())
    ctx = types.SimpleNamespace(
        trace=Reduced(trace_reduce.from_json(FIXTURES / want["trace"]), kinds=want["kinds"]),
        peaks=harness.peak_row("TPU v5 lite"),
        config=config,
        shapes=harness.load_plugin("shapes", config["family"]),
        devices=[None] * entry["chips"],
        counters={"offline.batch": 128},
        samples={
            "offline.rate_img_s": [100.0, 102.0],
            "offline.window_rate_img_s": [101.0],
            "offline.baseline_rate_img_s": [400.0],
        },
        spans={"build.compile": [(0.0, 0.5)]},
        span_seconds=lambda name: 0.5,
        log=lambda msg: None,
    )
    for metric in harness.metrics_for(manifest, "per_layer", cell):
        value = harness.load_plugin("layer_metrics", metric["name"]).read(ctx)
        assert value is not None and math.isfinite(float(value)), metric["name"]
