"""Fused-block megakernel tier-1 tests (ISSUE 17, docs/TUNING.md "Fused
block variants"): megakernel-vs-staged parity across dtypes and both
blocks against DEFAULT_BUDGETS, the single block-fusibility gate, the
fused-candidate sweep with attributable gate-pruning, block-granularity
attribution + the roofline block join (including the staged-minus-fused
byte identity), the sharded-int8w rung drills, and the regression gate's
staged-vs-fused variant separation.

All on CPU via the Pallas interpreter (the same numerics as the Mosaic
lowering for the vcol/sep2 regime)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import Blocks12Config
from cuda_mpi_gpu_cluster_programming_tpu.models.init import (
    init_params_random,
    random_input,
)
from cuda_mpi_gpu_cluster_programming_tpu.ops import megakernel as mk
from cuda_mpi_gpu_cluster_programming_tpu.ops import pallas_kernels as pk
from cuda_mpi_gpu_cluster_programming_tpu.ops.pallas_model import (
    forward_blocks12_pallas,
)
from cuda_mpi_gpu_cluster_programming_tpu.precision.gate import (
    BLOCK_BOUNDARIES,
    DEFAULT_BUDGETS,
    ToleranceGate,
)
from cuda_mpi_gpu_cluster_programming_tpu.precision.quantize import (
    forward_blocks12_int8w,
)

SMALL = Blocks12Config(in_height=43, in_width=43)


@pytest.fixture(scope="module")
def seeded():
    kp, kx = jax.random.split(jax.random.PRNGKey(0))
    return init_params_random(kp, SMALL), random_input(kx, 2, SMALL)


# ------------------------------------------------------------ fusibility ---


def test_block_fusible_reason_is_the_single_gate():
    """Every illegal combo names its reason; the legal regime is ''."""
    ok = dict(variant="vcol", row_block=64, k_block=0, pool="sep2",
              out_h=9, pool_window=3)
    assert mk.block_fusible_reason(**ok) == ""
    for patch, needle in (
        (dict(variant="g8"), "taps/vcol"),
        (dict(pool="phases"), "sep2"),
        (dict(row_block=8), "whole image"),
        (dict(k_block=128), "k_block"),
        (dict(pool_window=0), "adjacent pool"),
    ):
        why = mk.block_fusible_reason(**{**ok, **patch})
        assert why and needle in why, (patch, why)


def test_conv_block_pallas_raises_not_falls_back(seeded):
    """An infusible call must raise attributably, never silently run some
    other lowering (the candidate space relies on the same gate)."""
    params, x = seeded
    with pytest.raises(ValueError, match="block fusion"):
        mk.conv_block_pallas(
            x, params["conv1"]["w"], params["conv1"]["b"],
            stride=SMALL.conv1.stride, padding=SMALL.conv1.padding,
            pool_window=SMALL.pool1.window, pool_stride=SMALL.pool1.stride,
            variant="vcol", row_block=4,  # < out_h: not whole-image
        )


# ---------------------------------------------------- megakernel parity ---


@pytest.mark.parametrize("dtype", ["fp32", "bf16"])
def test_megakernel_bitwise_equals_staged_chain(seeded, dtype):
    """fp32/bf16: the fused model forward is BITWISE the staged Pallas
    chain — same accumulation order, same cast points, whole image per
    program on both sides."""
    params, x = seeded
    if dtype == "bf16":
        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        x = x.astype(jnp.bfloat16)
    staged = forward_blocks12_pallas(
        params, x, SMALL, variants=pk.KernelVariants(fuse="none"))
    fused = forward_blocks12_pallas(
        params, x, SMALL, variants=pk.KernelVariants(fuse="block"))
    assert fused.dtype == staged.dtype
    assert np.array_equal(
        np.asarray(fused, np.float32), np.asarray(staged, np.float32)
    )


def test_int8w_megakernel_matches_staged_within_budget(seeded):
    """int8w is tolerance-level, not bitwise: the megakernel rescales the
    uncast fp32 accumulator while the staged path round-trips bf16 first.
    The budget that judges it is the int8w DEFAULT_BUDGET."""
    params, x = seeded
    staged = np.asarray(forward_blocks12_int8w(
        params, x, SMALL, variants=pk.KernelVariants(fuse="none"),
        tier="pallas"), np.float32)
    fused = np.asarray(forward_blocks12_int8w(
        params, x, SMALL, variants=pk.KernelVariants(fuse="block"),
        tier="pallas"), np.float32)
    rel = np.max(np.abs(fused - staged)) / max(np.max(np.abs(staged)), 1e-30)
    assert rel <= DEFAULT_BUDGETS["int8w"]["*"].max_rel


@pytest.mark.parametrize("dtype", ["fp32", "bf16", "int8w"])
def test_screen_blocks_passes_all_dtypes(seeded, dtype):
    """The fp32-oracle block screen (the autotuner's fused-candidate
    guard) passes with headroom at every policy, judged against the
    calibrated DEFAULT_BUDGETS block entries."""
    params, x = seeded
    res = ToleranceGate().screen_blocks(dtype, params, x, SMALL)
    assert res.passed, res.reason()
    assert res.margin > 0
    names = {c.stage for c in res.stages}
    assert names == {b for b, _ in BLOCK_BOUNDARIES}


# ------------------------------------------------------- candidate sweep ---


def test_candidate_space_offers_and_prunes_block_attributably():
    """Block candidates appear exactly where the fusibility gate allows
    them; infusible combos carry the gate's own reason in the prune log."""
    from cuda_mpi_gpu_cluster_programming_tpu.tuning import space as ts

    all_block_drops = []
    for g in ts.conv_geometries(SMALL):
        dropped = []
        cands = ts.candidate_space(
            g, interpret=True, on_prune=lambda v, why: dropped.append((v, why))
        )
        blocks = [v for v in cands if v.fuse == "block"]
        assert blocks, f"no block candidate at {g.name}"
        assert all(v.row_block >= g.out_h for v in blocks)
        assert all(
            not mk.block_fusible_reason(
                variant=v.conv, row_block=v.row_block, k_block=v.k_block,
                pool=v.pool, out_h=g.out_h, pool_window=g.pool_window,
            )
            for v in blocks
        )
        # LRN geometry threads through: conv2's block fuses pool2+lrn2.
        if g.name == "conv2":
            assert g.lrn and g.lrn[0] == SMALL.lrn2.size
        block_drops = [w for v, w in dropped if v.fuse == "block"]
        assert block_drops and all(block_drops), f"unattributed prune at {g.name}"
        all_block_drops.extend(block_drops)
    # The fusibility gate's own words reach the prune log: conv1's small
    # row_blocks fail the whole-image requirement, k_block never composes.
    assert any("whole image" in w for w in all_block_drops)
    assert any("k_block" in w for w in all_block_drops)


def test_tune_layer_block_screen_prunes_before_timing():
    """A gate-failed block screen prunes every fuse="block" candidate
    pre-timing, with the screen's reason counted in pruned_reasons; the
    winner comes from the surviving staged candidates."""
    from cuda_mpi_gpu_cluster_programming_tpu.resilience.policy import Deadline
    from cuda_mpi_gpu_cluster_programming_tpu.tuning import space as ts
    from cuda_mpi_gpu_cluster_programming_tpu.tuning.autotune import tune_layer

    g = ts.conv_geometries(SMALL)[0]
    timed = []

    def timer(gg, v, dtype, batch, repeats, warmup):
        timed.append(v)
        return 1.0, 0.01, 3

    reason = "fuse=block gate-pruned for int8w: block1 rel 0.2 > 0.06"
    winner, stats, degraded = tune_layer(
        g, dtype="fp32", batch=2, deadline=Deadline.after(60), repeats=1,
        warmup=0, timer=timer, log=lambda s: None, interpret=True,
        block_screen=reason,
    )
    assert not degraded
    assert winner.fuse != "block"
    assert all(v.fuse != "block" for v in timed)
    assert stats["pruned_reasons"].get(reason, 0) >= 1
    # Without the screen the same sweep DOES time block candidates.
    timed.clear()
    tune_layer(
        g, dtype="fp32", batch=2, deadline=Deadline.after(60), repeats=1,
        warmup=0, timer=timer, log=lambda s: None, interpret=True,
    )
    assert any(v.fuse == "block" for v in timed)


# --------------------------------------------------- block attribution ---


def test_attribute_blocks_granularity_and_sums(seeded):
    from cuda_mpi_gpu_cluster_programming_tpu.observability.stages import (
        attribute_blocks,
    )

    params, x = seeded
    att = attribute_blocks(params, x, SMALL, repeats=1, warmup=1)
    assert att.granularity == "block"
    assert [n for n, _ in att.stages] == ["block1", "block2"]
    assert att.stage_sum_ms == pytest.approx(att.total_ms, rel=1e-6)
    obj = att.to_obj()
    assert obj["granularity"] == "block"
    assert obj["method"] == "prefix-diff/megakernel-blocks"


def test_roofline_joins_block_names_against_fused_model():
    """Block-vocabulary breakdowns join against the BlockModels: bytes are
    the FUSED bytes, the floor is the fused floor, and the measured MFU is
    judged against fused_mfu_ceiling — while the staged-minus-fused byte
    delta still reproduces the 2x-interior-activations identity."""
    from cuda_mpi_gpu_cluster_programming_tpu.observability.roofline import (
        attribute_roofline,
        pass_ledger,
    )

    rep = attribute_roofline(
        {"block1": 0.8, "block2": 1.2}, dtype="bf16", batch=128,
        device_kind="TPU v5e",
    )
    assert rep.granularity == "block"
    by_block = {b.name: b for b in rep.blocks}
    for s in rep.stages:
        b = by_block[s.name]
        assert s.bytes == b.fused_bytes
        assert s.floor_ms == pytest.approx(b.fused_floor_ms)
        assert s.mfu_ceiling == pytest.approx(b.fused_mfu_ceiling)
        assert s.mfu is not None and s.mfu <= s.mfu_ceiling
    # The identity the fused rows exist to delete: staged - fused ==
    # 2 x every interior activation (written once, read once).
    entries = {e.name: e for e in pass_ledger(None, dtype="bf16", batch=128)}
    for bname, interior in (("block1", ["conv1"]), ("block2", ["conv2", "pool2"])):
        b = by_block[bname]
        assert b.staged_bytes - b.fused_bytes == 2 * sum(
            entries[n].act_out_bytes for n in interior
        )
    obj = rep.to_obj()
    assert obj["granularity"] == "block"
    assert all("mfu_ceiling" in s for s in obj["stages"])
    assert "granularity=block" in rep.render()
    # Stage-vocabulary joins are unchanged: stage granularity, no ceiling.
    rep2 = attribute_roofline(
        {"conv1": 0.5, "pool1": 0.1}, dtype="bf16", batch=128,
        device_kind="TPU v5e",
    )
    assert rep2.granularity == "stage"
    assert all(s.mfu_ceiling is None for s in rep2.stages)
    with pytest.raises(ValueError, match="no ledger stage or fused block"):
        attribute_roofline(
            {"bogus": 1.0}, dtype="fp32", batch=1, device_kind="TPU v5e"
        )


# ------------------------------------------------------- sharded int8w ---


@pytest.mark.parametrize("key,shards", [
    ("v2.2_sharded", 2), ("v4_hybrid", 2), ("v2.1_replicated", 2),
])
def test_sharded_int8w_rungs_build_and_screen(seeded, key, shards):
    """The lifted refusal: halo/staged/replicated rungs build int8w
    forwards that match the single-device quantized output, and the
    per-rung gate re-screen passes against the fp32 oracle."""
    from cuda_mpi_gpu_cluster_programming_tpu.configs import (
        REGISTRY,
        build_forward,
    )

    params, x = seeded
    fwd = build_forward(REGISTRY[key], SMALL, n_shards=shards, policy="int8w")
    got = np.asarray(fwd(params, x), np.float32)
    want = np.asarray(
        forward_blocks12_int8w(params, x, SMALL, tier="reference"), np.float32
    )
    # int8w-vs-int8w across tiers: bf16 staging differences between the
    # sharded pallas path and the reference chain are tolerance-level,
    # not bitwise (the oracle-relative budget is the screen below).
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 2e-2
    if key != "v2.1_replicated":
        res = ToleranceGate().screen_sharded(
            "int8w", params, x, SMALL, n_shards=shards,
            staged=(key == "v4_hybrid"),
        )
        assert res.passed, res.reason()


def test_staticcheck_scope_covers_megakernel():
    from pathlib import Path

    from cuda_mpi_gpu_cluster_programming_tpu.staticcheck import rules_jax

    assert "megakernel.py" in rules_jax._HOT_LOOP_FILES
    p = Path("cuda_mpi_gpu_cluster_programming_tpu/ops/megakernel.py")
    assert rules_jax._in_hot_loop_scope(p)
