"""Autotuner tier-1 tests (CPU interpret mode): candidate-space pruning,
plan cache round-trip, stale-key invalidation, resilience degradation
(Deadline abort / chaos compile faults -> default plan), plans written
before PR 42 loading as stale, and per-layer variant threading through the
Pallas forward.

The sweep itself is exercised with an injected deterministic timer (the
real amortized-timing path is covered by the run CLI --tune test and the
production timing suite) so these stay fast and order-stable.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import (
    BLOCKS12,
    Blocks12Config,
    flops_per_image,
    layer_dims,
    matmul_flops_per_image,
    output_shape,
)
from cuda_mpi_gpu_cluster_programming_tpu.ops import pallas_kernels as pk
from cuda_mpi_gpu_cluster_programming_tpu.resilience import chaos
from cuda_mpi_gpu_cluster_programming_tpu.resilience.policy import Deadline
from cuda_mpi_gpu_cluster_programming_tpu.tuning import plan as tp
from cuda_mpi_gpu_cluster_programming_tpu.tuning import space as ts
from cuda_mpi_gpu_cluster_programming_tpu.tuning.autotune import (
    autotune,
    autotune_model,
)

SMALL = Blocks12Config(in_height=43, in_width=43)


def geometries(cfg=BLOCKS12):
    return {g.name: g for g in ts.conv_geometries(cfg)}


# ---------------------------------------------------------------- space ---


def test_shared_traversal_matches_committed_dims():
    """layer_dims is the one shape walk: output_shape and the FLOP counters
    must keep their committed default-config values on top of it."""
    assert output_shape() == (13, 13, 256)
    assert flops_per_image() == 1108641024
    assert matmul_flops_per_image() == 1106625600
    names = [n for n, *_ in layer_dims(BLOCKS12)]
    assert names == ["conv1", "pool1", "conv2", "pool2", "lrn2"]


def test_conv_geometries_carry_trailing_pools():
    gs = geometries()
    assert set(gs) == {"conv1", "conv2"}
    g1, g2 = gs["conv1"], gs["conv2"]
    assert (g1.in_h, g1.in_w, g1.in_channels, g1.out_channels) == (227, 227, 3, 96)
    assert g1.out_h == 55 and g1.pool_window == 3 and g1.pool_stride == 2
    assert (g2.in_h, g2.in_w, g2.in_channels, g2.out_channels) == (27, 27, 96, 256)
    assert g2.out_h == 27


# conv1 (11x11 s4, 55 output rows, a pool after it): taps and vcol each run
# rb 8/16/32/64 unfused plus hpool and block at rb 64 (the whole image) = 6
# each; g8 takes no fusion and runs each output phase whole, so its four
# row blocks are one lowering = 1. conv2 (5x5 s1, 27 rows): g8 falls back
# to vcol at stride 1 = 0; taps and vcol each run rb 8/16/32 (64 clamps to
# 32) unfused plus hpool and block at rb 32 = 5 each.
KEPT_BY_HAND = {"conv1": 6 + 6 + 1, "conv2": 5 + 5 + 0}


@pytest.mark.parametrize("layer", ["conv1", "conv2"])
def test_candidate_space_is_the_kept_lowerings(layer):
    """3 conv lowerings x 4 row blocks x 3 fusions = 36 candidates before
    pruning, every one inside the kept domains; the pruned list is the
    count worked out by hand above."""
    g = geometries()[layer]
    dropped = []
    cands = ts.candidate_space(g, on_prune=lambda v, why: dropped.append(v))
    assert len(cands) + len(dropped) == 36
    for v in cands + dropped:
        assert v.conv in ("taps", "vcol", "g8")
        assert v.row_block in (8, 16, 32, 64)
        assert v.fuse in ("none", "hpool", "block")
    assert len(cands) == KEPT_BY_HAND[layer]
    assert len(set(cands)) == len(cands)


def test_space_prunes_variant_geometry_mismatches():
    gs = geometries()
    c2 = ts.candidate_space(gs["conv2"])
    assert all(v.conv != "g8" for v in c2)  # stride 1: g8 falls back to vcol
    c1 = ts.candidate_space(gs["conv1"])
    assert any(v.conv == "g8" for v in c1)  # stride 4: g8 is a real candidate
    # hpool candidates obey the production gate exactly.
    for cands, g in ((c1, gs["conv1"]), (c2, gs["conv2"])):
        for v in cands:
            if v.fuse == "hpool":
                assert v.conv in ("taps", "vcol") and v.row_block >= g.out_h


def test_space_dedupes_clamped_row_blocks_and_reports_prunes():
    """Row blocks past the output height all clamp to whole-image programs —
    only one such candidate may survive — and every drop is reported."""
    g2 = geometries()["conv2"]  # out_h = 27: rb 32 and 64 alias
    dropped = []
    cands = ts.candidate_space(g2, on_prune=lambda v, why: dropped.append(why))
    taps_plain = [
        v.row_block for v in cands if (v.conv, v.fuse) == ("taps", "none")
    ]
    assert sorted(taps_plain) in ([8, 16, 32], [8, 16, 64])
    assert dropped and any("duplicate effective lowering" in w for w in dropped)


# ----------------------------------------------------------------- plan ---


def fake_timer(table=None):
    """Deterministic injected timer; optionally scripted per (layer, label)."""
    calls = []

    def timer(g, v, dtype, batch, repeats, warmup):
        calls.append((g.name, v))
        if table is not None:
            return table(g, v), 0.01, 3
        # Stable, distinct: favor vcol/sep2/none deterministically.
        ms = 10.0
        ms -= 3.0 * (v.conv == "vcol")
        ms -= 0.5 * (v.fuse == "none")
        ms -= 0.1 * v.row_block / 64.0
        return ms, 0.01, 3

    timer.calls = calls
    return timer


def test_autotune_cache_round_trip(tmp_path):
    path = tmp_path / "plan.json"
    timer = fake_timer()
    plan, cached = autotune(
        path, SMALL, dtype="fp32", batch=2, timer=timer, log=lambda s: None,
        device_kind="cpu",
    )
    assert not cached and timer.calls and not plan.degraded
    assert [n for n, _ in plan.layers] == ["conv1", "conv2"]
    for _n, v in plan.layers:
        assert v.conv == "vcol" and v.fuse == "none"  # the scripted winner
    obj = json.loads(path.read_text())
    assert plan.key in obj["plans"]
    # Second call: loaded from disk, NO sweep (the acceptance criterion).
    timer2 = fake_timer()
    plan2, cached2 = autotune(
        path, SMALL, dtype="fp32", batch=2, timer=timer2, log=lambda s: None,
        device_kind="cpu",
    )
    assert cached2 and not timer2.calls
    assert plan2.plan_hash() == plan.plan_hash()
    assert plan2.layers == plan.layers


def test_plan_key_misses_do_not_cross_points(tmp_path):
    path = tmp_path / "plan.json"
    plan, _ = autotune(
        path, SMALL, dtype="fp32", batch=2, timer=fake_timer(),
        log=lambda s: None, device_kind="cpu",
    )
    # Different dtype / device / geometry are all misses.
    assert tp.load_plan(path, device_kind="cpu", model_cfg=SMALL,
                        dtype="bf16", batch=2) is None
    assert tp.load_plan(path, device_kind="TPU v5 lite", model_cfg=SMALL,
                        dtype="fp32", batch=2) is None
    assert tp.load_plan(path, device_kind="cpu", model_cfg=BLOCKS12,
                        dtype="fp32", batch=2) is None
    # A different batch at the same point is the nearest usable plan
    # (opt-out via match_any_batch=False, which autotune's cache check uses).
    near = tp.load_plan(path, device_kind="cpu", model_cfg=SMALL,
                        dtype="fp32", batch=64)
    assert near is not None and near.batch == 2
    assert tp.load_plan(path, device_kind="cpu", model_cfg=SMALL,
                        dtype="fp32", batch=64, match_any_batch=False) is None


def test_stale_code_rev_invalidates(tmp_path):
    """A plan tuned against different kernel sources is a MISS — stale
    winners must never apply to changed code."""
    path = tmp_path / "plan.json"
    plan, _ = autotune(
        path, SMALL, dtype="fp32", batch=2, timer=fake_timer(),
        log=lambda s: None, device_kind="cpu",
    )
    obj = json.loads(path.read_text())
    (key,) = obj["plans"]
    stale_key = key.replace(f"rev={plan.code_rev}", "rev=deadbeefdead")
    obj["plans"][stale_key] = {
        **obj["plans"].pop(key), "code_rev": "deadbeefdead",
    }
    path.write_text(json.dumps(obj))
    assert tp.load_plan(path, device_kind="cpu", model_cfg=SMALL,
                        dtype="fp32", batch=2) is None
    # And autotune re-sweeps over it rather than reusing.
    timer = fake_timer()
    _plan, cached = autotune(
        path, SMALL, dtype="fp32", batch=2, timer=timer, log=lambda s: None,
        device_kind="cpu",
    )
    assert not cached and timer.calls


def test_deadline_abort_falls_back_to_default_plan(tmp_path):
    """An already-expired Deadline must yield a usable DEFAULT plan, marked
    degraded — never a wedge, never a half-silent fallback."""
    timer = fake_timer()
    plan = autotune_model(
        SMALL, dtype="fp32", batch=2, deadline=Deadline.after(1e-9),
        timer=timer, log=lambda s: None, device_kind="cpu",
    )
    assert not timer.calls
    assert plan.degraded and "deadline" in plan.degraded
    default = pk.KernelVariants()
    for name, v in plan.layers:
        assert v == default, (name, v)
        assert "degraded" in plan.stats[name]


def test_chaos_compile_faults_degrade_not_wedge(tmp_path, monkeypatch):
    """kernel_compile chaos: a transiently-failing candidate is skipped; a
    layer whose candidates ALL fail degrades to the defaults."""
    monkeypatch.setenv("CHAOS_SPEC", "kernel_compile=2")
    chaos.reset()
    try:
        timer = fake_timer()
        plan = autotune_model(
            SMALL, dtype="fp32", batch=2, timer=timer, log=lambda s: None,
            device_kind="cpu",
        )
        # Two injected faults burned, sweep healed: winners still tuned.
        assert not plan.degraded
        assert plan.stats["conv1"]["failed"] == 2
        assert dict(plan.layers)["conv1"].conv == "vcol"

        monkeypatch.setenv("CHAOS_SPEC", "kernel_compile=100000")
        chaos.reset()
        plan2 = autotune_model(
            SMALL, dtype="fp32", batch=2, timer=fake_timer(),
            log=lambda s: None, device_kind="cpu",
        )
        assert "all" in plan2.degraded and "failed" in plan2.degraded
        for _n, v in plan2.layers:
            assert v == pk.KernelVariants()
    finally:
        chaos.reset()


def _write_plan_file(path, layers, rev=None):
    """A plan file holding one plan for (cpu, SMALL, b2, fp32) whose layers
    are stored exactly as given — the shape an older writer left them."""
    rev = rev or tp.code_rev()
    key = tp.plan_key("cpu", tp.shape_key(SMALL), 2, "fp32", rev)
    path.write_text(json.dumps({"version": 1, "plans": {key: {
        "device_kind": "cpu", "shape_key": tp.shape_key(SMALL), "batch": 2,
        "dtype": "fp32", "code_rev": rev, "layers": layers,
    }}}))


COMMITTED_PLAN = Path(__file__).resolve().parent.parent / "perf" / "tune_plan.json"


@pytest.mark.parametrize("case", ["committed", "conv_pairs", "k_block_128"])
def test_a_plan_written_before_loads_as_stale(tmp_path, case):
    """A plan file from before PR 42 — the committed one as it stands
    (every layer names k_block and pool, and its code_rev is old), a plan
    whose winner is conv="pairs", a plan with k_block 128 — loads as a
    miss that names why, with no exception, and the defaults run. The
    committed case also goes through run.py --plan."""
    if case == "committed":
        (stored,) = json.loads(COMMITTED_PLAN.read_text())["plans"].values()
        blocks, batch = BLOCKS12, stored["batch"]
        # at this code's rev: a plan tuned at another one
        with pytest.warns(RuntimeWarning, match="stale: tuned at code_rev"):
            assert tp.load_plan(COMMITTED_PLAN, device_kind="cpu",
                                model_cfg=blocks, dtype="fp32", batch=batch) is None
        # at its own rev: winners this code cannot build
        with pytest.warns(RuntimeWarning, match="is stale .*(pool|k_block)"):
            assert tp.load_plan(COMMITTED_PLAN, device_kind="cpu", model_cfg=blocks,
                                dtype="fp32", batch=batch,
                                rev=stored["code_rev"]) is None
        proc = subprocess.run(
            [sys.executable, "-m", "cuda_mpi_gpu_cluster_programming_tpu.run",
             "--config", "v3_pallas", "--batch", "1", "--repeats", "1",
             "--warmup", "0", "--plan", str(COMMITTED_PLAN)],
            capture_output=True, text=True, timeout=600, cwd=COMMITTED_PLAN.parents[1],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "stale: tuned at code_rev" in proc.stderr
        assert "Tune plan: none matching" in proc.stdout
        # the default lowering's golden first values (deterministic init)
        (line,) = [ln for ln in proc.stdout.splitlines() if ln.startswith("Final Output (")]
        first = [float(t) for t in line.split(":", 1)[1].split()[:3]]
        np.testing.assert_allclose(first, [29.2932, 25.9153, 23.3255], rtol=2e-5)
        return
    path = tmp_path / "plan.json"
    if case == "conv_pairs":
        layers, why = {"conv1": {"conv": "pairs", "row_block": 64, "fuse": "none"}}, "pairs"
    else:
        layers, why = {"conv2": {"conv": "vcol", "row_block": 32, "k_block": 128,
                                 "fuse": "none"}}, "k_block"
    _write_plan_file(path, layers)
    with pytest.warns(RuntimeWarning, match=f"is stale .*{why}"):
        assert tp.load_plan(path, device_kind="cpu", model_cfg=SMALL,
                            dtype="fp32", batch=2) is None
    # the builder, handed no plan, runs the defaults on every layer
    assert tp.effective_layer_variants(None).for_layer("conv1") == pk.KernelVariants()


# ------------------------------------------------------------ threading ---


def test_layer_variants_thread_through_forward():
    """A per-layer plan (different variants per conv) must produce the same
    numbers as the global-variant forward — allclose across lowering
    variants, same contract as the variant A/B tests."""
    from cuda_mpi_gpu_cluster_programming_tpu.models.init import (
        deterministic_input,
        init_params_deterministic,
    )
    from cuda_mpi_gpu_cluster_programming_tpu.ops.pallas_model import (
        forward_blocks12_pallas,
    )

    params = init_params_deterministic(SMALL)
    x = deterministic_input(2, SMALL)
    base = np.asarray(
        forward_blocks12_pallas(params, x, SMALL, variants=pk.KernelVariants())
    )
    lv = pk.LayerVariants(
        layers=(
            ("conv1", pk.KernelVariants(conv="taps", row_block=16)),
            ("conv2", pk.KernelVariants(conv="vcol", fuse="hpool")),
        ),
        default=pk.KernelVariants(),
    )
    got = np.asarray(forward_blocks12_pallas(params, x, SMALL, variants=lv))
    np.testing.assert_allclose(got, base, rtol=1e-5, atol=1e-5)


def test_build_forward_applies_plan(tmp_path):
    """configs.build_forward(plan=...) runs the tuned per-layer variants and
    matches the untuned forward numerically."""
    from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward
    from cuda_mpi_gpu_cluster_programming_tpu.models.init import (
        deterministic_input,
        init_params_deterministic,
    )

    plan = autotune_model(
        SMALL, dtype="fp32", batch=2,
        timer=fake_timer(lambda g, v: 1.0 if v.conv == "taps" else 5.0),
        log=lambda s: None, device_kind="cpu",
    )
    assert all(v.conv == "taps" for _n, v in plan.layers)
    params = init_params_deterministic(SMALL)
    x = deterministic_input(2, SMALL)
    untuned = build_forward(REGISTRY["v3_pallas"], SMALL)(params, x)
    tuned = build_forward(REGISTRY["v3_pallas"], SMALL, plan=plan)(params, x)
    np.testing.assert_allclose(
        np.asarray(tuned), np.asarray(untuned), rtol=1e-5, atol=1e-5
    )


def test_build_forward_donate_smoke():
    """donate=True builds and computes (donation is advisory on CPU; the
    wiring must not change results for a single call)."""
    import warnings

    from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward
    from cuda_mpi_gpu_cluster_programming_tpu.models.init import (
        deterministic_input,
        init_params_deterministic,
    )

    params = init_params_deterministic(SMALL)
    ref = build_forward(REGISTRY["v1_jit"], SMALL)(params, deterministic_input(1, SMALL))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # CPU: "donation is not implemented"
        out = build_forward(REGISTRY["v1_jit"], SMALL, donate=True)(
            params, deterministic_input(1, SMALL)
        )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ------------------------------------------------------------------ CLI ---


@pytest.mark.slow
def test_run_tune_cli_sweeps_then_caches(tmp_path):
    """The acceptance flow end to end: --tune sweeps and writes the plan,
    a second invocation loads it without re-sweeping (real timing path, so
    marked slow; tier-1 covers the same logic with the injected timer)."""
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    plan_path = tmp_path / "plan.json"
    cmd = [
        sys.executable, "-m", "cuda_mpi_gpu_cluster_programming_tpu.run",
        "--config", "v3_pallas", "--batch", "1", "--height", "43",
        "--width", "43", "--repeats", "2", "--warmup", "1", "--tune",
        "--tune-repeats", "2", "--tune-warmup", "1", "--plan", str(plan_path),
    ]
    first = subprocess.run(
        cmd, capture_output=True, text=True, timeout=560, cwd=root
    )
    assert first.returncode == 0, first.stderr[-2000:]
    assert "Tune plan: swept hash=" in first.stdout
    assert plan_path.exists()
    second = subprocess.run(
        cmd, capture_output=True, text=True, timeout=560, cwd=root
    )
    assert second.returncode == 0, second.stderr[-2000:]
    assert "Tune plan: cache hash=" in second.stdout
