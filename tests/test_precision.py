"""Precision subsystem tier-1 tests (docs/PRECISION.md): quantize/dequant
roundtrip bounds, per-channel scale correctness on seeded weights, the
fp32-oracle ToleranceGate (pass on bf16/int8w, fail on injected SDC
perturbations, oracle-preflight fault), the dtype-swept autotuner with an
attributably gate-pruned candidate, policy threading through
configs.build_forward and the sharded pallas builder, and the run CLI
--dtype line.

The dtype sweep uses the injected deterministic timer (same discipline as
tests/test_tuning.py) so the race outcome is scripted; the GATE always
runs the real forwards — its verdicts are the thing under test.
"""

import json
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward
from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import Blocks12Config
from cuda_mpi_gpu_cluster_programming_tpu.models.init import (
    init_params_random,
    random_input,
)
from cuda_mpi_gpu_cluster_programming_tpu.precision import (
    DEFAULT_BUDGETS,
    DtypePolicy,
    LayerPrecision,
    StageBudget,
    ToleranceGate,
    dequantize,
    forward_blocks12_int8w,
    quantize_channelwise,
    quantize_conv_params,
    resolve_policy,
)
from cuda_mpi_gpu_cluster_programming_tpu.precision.quantize import (
    QMAX,
    roundtrip_error_bound,
)
from cuda_mpi_gpu_cluster_programming_tpu.resilience.journal import Journal
from cuda_mpi_gpu_cluster_programming_tpu.resilience.sentinel import inject_bit_flip
from cuda_mpi_gpu_cluster_programming_tpu.tuning import plan as tp
from cuda_mpi_gpu_cluster_programming_tpu.tuning.autotune import (
    DTYPES,
    autotune_precision,
)

SMALL = Blocks12Config(in_height=43, in_width=43)


@pytest.fixture(scope="module")
def seeded():
    """Params + input from the seeded init stream — the same calibration
    source the production sweep gates on."""
    kp, kx = jax.random.split(jax.random.PRNGKey(0))
    return init_params_random(kp, SMALL), random_input(kx, 2, SMALL)


def scripted_timer(g, v, dtype, batch, repeats, warmup):
    """Deterministic dtype race: bf16 < int8w < fp32."""
    return {"fp32": 5.0, "bf16": 1.0, "int8w": 2.0}[dtype], 0.01, 3


# ------------------------------------------------------------- quantize ---


def test_quantize_roundtrip_error_bound(seeded):
    """Roundtrip error of every seeded conv weight is elementwise within
    scale/2 — the bound the scheme promises (docs/PRECISION.md)."""
    params, _x = seeded
    for name in ("conv1", "conv2"):
        w = params[name]["w"]
        q, scale = quantize_channelwise(w)
        assert q.dtype == np.int8
        assert int(np.max(np.abs(np.asarray(q, np.int32)))) <= QMAX
        err = np.abs(np.asarray(dequantize(q, scale)) - np.asarray(w))
        bound = np.asarray(roundtrip_error_bound(w))
        assert np.all(err <= bound + 1e-7), name


def test_per_channel_scale_correctness():
    """scale[k] == max|w[..., k]|/127 per output channel; an all-zero
    channel takes scale 1.0 (safe divide) and quantizes to exact zeros."""
    w = np.zeros((3, 3, 2, 4), np.float32)
    w[..., 0] = 0.5
    w[..., 1] = -2.0
    w[0, 0, 0, 2] = 127.0
    # channel 3 stays all-zero
    q, scale = quantize_channelwise(w)
    np.testing.assert_allclose(
        np.asarray(scale), [0.5 / QMAX, 2.0 / QMAX, 1.0, 1.0], rtol=1e-6
    )
    q = np.asarray(q, np.int32)
    assert np.all(q[..., 0] == QMAX) and np.all(q[..., 1] == -QMAX)
    assert q[0, 0, 0, 2] == QMAX and np.all(q[..., 3] == 0)


def test_quantize_conv_params_tree_shape(seeded):
    """Both conv layers quantized; biases stay fp32 (added after the
    rescale, in the accumulation dtype)."""
    params, _x = seeded
    qp = quantize_conv_params(params)
    assert set(qp) == {"conv1", "conv2"}
    for name, e in qp.items():
        assert e["q"].dtype == np.int8
        assert e["scale"].dtype == np.float32
        assert e["scale"].shape == (params[name]["w"].shape[-1],)
        assert e["b"].dtype == np.float32


def test_int8w_forward_tiers_agree(seeded):
    """The quantized forward's two op tiers (reference conv vs Pallas
    kernels) compute the same function."""
    params, x = seeded
    ref = np.asarray(forward_blocks12_int8w(params, x, SMALL, tier="reference"))
    pal = np.asarray(forward_blocks12_int8w(params, x, SMALL, tier="pallas"))
    np.testing.assert_allclose(pal, ref, rtol=2e-2, atol=2e-2)


# ----------------------------------------------------------------- gate ---


def test_gate_passes_bf16_and_int8w_on_blocks12(seeded, tmp_path):
    """bf16 and int8w both clear their default budgets against the fp32
    oracle on Blocks 1-2 seeded weights, with positive margin, and every
    screening lands one journaled verdict."""
    params, x = seeded
    journal = Journal(tmp_path / "gate.jsonl")
    gate = ToleranceGate(journal=journal)
    for pol in ("bf16", "int8w"):
        res = gate.screen(pol, params, x, SMALL)
        assert res.passed and res.margin > 0.0, (pol, res.reason())
        assert {s.stage for s in res.stages} == {
            "conv1", "pool1", "conv2", "pool2", "lrn2"
        }
    recs = Journal.load(tmp_path / "gate.jsonl")
    assert [r["kind"] for r in recs] == ["gate_pass", "gate_pass"]
    assert all(r["margin"] > 0 for r in recs)


def test_gate_fails_on_injected_perturbation(seeded, tmp_path):
    """A bit-flipped candidate param tree (the chaos ``sdc`` payload,
    resilience.sentinel.inject_bit_flip) gated against the CLEAN oracle
    must fail with an attributable per-stage reason."""
    params, x = seeded
    corrupted, where = inject_bit_flip(params, seed=1)
    assert where is not None
    journal = Journal(tmp_path / "gate.jsonl")
    gate = ToleranceGate(journal=journal)
    res = gate.screen("bf16", params, x, SMALL, candidate_params=corrupted)
    assert not res.passed and res.margin < 0.0
    assert res.worst_stage in {"conv1", "pool1", "conv2", "pool2", "lrn2"}
    assert "stage" in res.reason() and "budget" in res.reason()
    (rec,) = Journal.load(tmp_path / "gate.jsonl")
    assert rec["kind"] == "gate_fail" and rec["reason"] == res.reason()


def test_gate_oracle_preflight_fault(seeded, monkeypatch):
    """A device whose fp32 path itself deviates from the numpy loop oracle
    fails EVERY candidate rather than blessing a matching error."""
    from cuda_mpi_gpu_cluster_programming_tpu.resilience import sentinel

    params, x = seeded
    monkeypatch.setattr(sentinel, "oracle_spot_check", lambda *a, **k: 1.0)
    res = ToleranceGate().screen("bf16", params, x, SMALL)
    assert not res.passed and res.oracle_fault
    assert "oracle" in res.reason()


def test_gate_budget_tables_and_margins():
    """Budget lookup: exact stage beats "*"; margin is the binding
    fraction of budget left."""
    gate = ToleranceGate(
        budgets={"bf16": {"*": StageBudget(max_rel=1e-2),
                          "lrn2": StageBudget(max_rel=4e-2)}},
        preflight=False,
    )
    assert gate.budget_for("bf16", "conv1").max_rel == 1e-2
    assert gate.budget_for("bf16", "lrn2").max_rel == 4e-2
    assert DEFAULT_BUDGETS["bf16"]["*"].max_rel < DEFAULT_BUDGETS["int8w"]["*"].max_rel


# --------------------------------------------------------------- policy ---


def test_policy_presets_and_resolution():
    for name in ("fp32", "bf16", "int8w"):
        pol = resolve_policy(name)
        assert pol.name == name
    assert resolve_policy(None).name == "fp32"
    assert resolve_policy("int8w").quantized
    assert not resolve_policy("bf16").quantized
    with pytest.raises(ValueError, match="unknown precision policy"):
        resolve_policy("fp8")
    # Per-layer overrides: named layers diverge from the default triple.
    pol = DtypePolicy(
        "mixed",
        LayerPrecision("bfloat16", "float32", "bfloat16"),
        layers=(("conv1", LayerPrecision("float32", "float32", "float32")),),
    )
    assert pol.layer("conv1").compute == "float32"
    assert pol.layer("conv2").compute == "bfloat16"


def test_build_forward_policy_matches_oracle_within_budget(seeded):
    """The acceptance contract: build_forward(policy=...) reproduces the
    fp32 oracle within the same budget the gate screens that policy at."""
    params, x = seeded
    oracle = np.asarray(build_forward(REGISTRY["v1_jit"], SMALL)(params, x))
    denom = float(np.max(np.abs(oracle)))
    for pol in ("bf16", "int8w"):
        got = np.asarray(
            build_forward(REGISTRY["v1_jit"], SMALL, policy=pol)(params, x)
        )
        rel = float(np.max(np.abs(got - oracle))) / denom
        assert rel <= DEFAULT_BUDGETS[pol]["*"].max_rel, (pol, rel)


def test_build_forward_rejects_quantized_unsupported_configs():
    """ISSUE 17 lifted the halo/replicated int8w refusal, but the
    still-unsupported combos (tensor-parallel, full AlexNet) must keep
    refusing loudly and attributably, not silently run unquantized."""
    for key in ("v7_tp", "v6_full_sharded"):
        with pytest.raises(ValueError, match="open ROADMAP items"):
            build_forward(REGISTRY[key], SMALL, n_shards=2, policy="int8w")
    with pytest.raises(ValueError, match="unknown compute mode"):
        build_forward(REGISTRY["v1_jit"], SMALL, policy="int9")


# ---------------------------------------------------------- dtype sweep ---


def test_autotune_precision_prunes_gate_failed_attributably(seeded, tmp_path):
    """ONE sweep covers {fp32, bf16, int8w}: a zero-budget int8w gate
    prunes that dtype with an attributable journaled reason before any
    timing, the scripted-fastest bf16 wins, the fp32 floor is kept, and
    the winner's policy record persists with its gate_pass verdict."""
    path = tmp_path / "plan.json"
    journal_path = tmp_path / "gate.jsonl"
    gate = ToleranceGate(
        budgets={"int8w": {"*": StageBudget(max_rel=0.0)}},
        journal=Journal(journal_path),
    )
    res = autotune_precision(
        path, SMALL, batch=2, timer=scripted_timer, log=lambda s: None,
        device_kind="cpu", gate=gate, seed=0,
    )
    assert res.winner == "bf16" and not res.cached
    assert set(res.pruned) == {"int8w"}
    assert "stage" in res.pruned["int8w"] and "budget" in res.pruned["int8w"]
    # fp32 reference floor swept and kept alongside the winner.
    assert set(res.plans) == {"fp32", "bf16"}
    assert res.plan is res.plans["bf16"]
    assert "bf16" in res.summary() and "int8w=gate-pruned" in res.summary()
    # Journal: one verdict per screened dtype; the non-fp32 winner exists
    # only with a gate_pass record (the acceptance invariant).
    kinds = {r["policy"]: r["kind"] for r in Journal.load(journal_path)}
    assert kinds == {
        "fp32": "gate_pass", "bf16": "gate_pass", "int8w": "gate_fail"
    }
    # Persisted policy record round-trips with the pruned reasons + gates.
    rec = tp.load_policy(
        path, device_kind="cpu", model_cfg=SMALL, batch=2,
        match_any_batch=False,
    )
    assert rec is not None and rec["dtype"] == "bf16"
    assert sorted(rec["swept"]) == sorted(DTYPES)
    assert rec["pruned"]["int8w"] == res.pruned["int8w"]
    assert rec["gates"]["bf16"]["passed"] and not rec["gates"]["int8w"]["passed"]
    # Per-dtype plans landed under their own keys in the same file.
    obj = json.loads(path.read_text())
    plan_dtypes = {k.split("|")[3] for k in obj["plans"]}
    assert plan_dtypes == {"fp32", "bf16"}


def test_autotune_precision_cache_short_circuits(seeded, tmp_path):
    """A fresh policy record + per-dtype plans short-circuit gate and
    sweep alike; --tune-force re-runs both."""
    path = tmp_path / "plan.json"
    kw = dict(
        batch=2, timer=scripted_timer, log=lambda s: None, device_kind="cpu",
        gate=ToleranceGate(), seed=0,
    )
    first = autotune_precision(path, SMALL, **kw)
    assert not first.cached
    calls = []

    def counting_timer(*a):
        calls.append(a)
        return scripted_timer(*a)

    second = autotune_precision(path, SMALL, **{**kw, "timer": counting_timer})
    assert second.cached and not calls
    assert second.winner == first.winner
    assert second.plan.plan_hash() == first.plan.plan_hash()
    forced = autotune_precision(
        path, SMALL, force=True, **{**kw, "timer": counting_timer}
    )
    assert not forced.cached and calls


def test_autotune_precision_all_pruned_raises(seeded, tmp_path):
    """Every dtype gate-pruned (broken oracle chain) is a loud error
    carrying each dtype's reason — never a silent default plan."""
    gate = ToleranceGate(
        budgets={
            name: {"*": StageBudget(max_abs=-1.0)} for name in ("fp32", "bf16")
        },
    )
    with pytest.raises(RuntimeError, match="gate-pruned"):
        autotune_precision(
            tmp_path / "plan.json", SMALL, batch=2, dtypes=("fp32", "bf16"),
            timer=scripted_timer, log=lambda s: None, device_kind="cpu",
            gate=gate, seed=0,
        )


def test_int8w_candidate_space_excludes_epilogue_fusion():
    """hpool fusion needs the in-kernel bias/ReLU epilogue; int8w's rescale
    lands between accumulation and bias, so the sweep must not offer it.
    Block fusion (the ISSUE 17 megakernel) IS legal under int8w — its
    epilogue rescales the fp32 accumulator before bias by construction —
    so "block" stays in the quantized space."""
    from cuda_mpi_gpu_cluster_programming_tpu.tuning import space as ts

    for g in ts.conv_geometries(SMALL):
        fp32_fuses = {v.fuse for v in ts.candidate_space(g)}
        int8_fuses = {
            v.fuse
            for v in ts.candidate_space(g, dtype="int8w")
        }
        assert "hpool" in fp32_fuses
        assert "block" in fp32_fuses
        assert "hpool" not in int8_fuses
        assert int8_fuses == {"none", "block"}


# ------------------------------------------------------------- threading ---


def test_sharded_pallas_builder_applies_plan(seeded):
    """PR 5 leftover closed: a TunePlan rides into the SHARDED pallas
    builder and reproduces the untuned output (allclose across lowering
    variants, same contract as the single-device threading test)."""
    from cuda_mpi_gpu_cluster_programming_tpu.parallel.sharded import (
        build_sharded_forward,
    )
    from cuda_mpi_gpu_cluster_programming_tpu.tuning.autotune import autotune_model

    params, x = seeded
    plan = autotune_model(
        SMALL, dtype="fp32", batch=2,
        timer=lambda g, v, *a: (1.0 if v.conv == "taps" else 5.0, 0.01, 3),
        log=lambda s: None, device_kind="cpu",
    )
    assert all(v.conv == "taps" for _n, v in plan.layers)
    base = np.asarray(build_sharded_forward(SMALL, 2, tier="pallas")(params, x))
    tuned = np.asarray(
        build_sharded_forward(SMALL, 2, tier="pallas", plan=plan)(params, x)
    )
    np.testing.assert_allclose(tuned, base, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------- CLI ---


def test_run_dtype_cli_line():
    """run.py --dtype pins the policy and prints the machine-parsed
    Precision line (harness._RE_PRECISION)."""
    root = Path(__file__).resolve().parent.parent
    r = subprocess.run(
        [
            sys.executable, "-m", "cuda_mpi_gpu_cluster_programming_tpu.run",
            "--config", "v1_jit", "--batch", "1", "--height", "35",
            "--width", "35", "--repeats", "1", "--warmup", "1",
            "--dtype", "int8w",
        ],
        capture_output=True, text=True, timeout=300, cwd=root,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "Precision: dtype=int8w source=dtype gate=none" in r.stdout
    from cuda_mpi_gpu_cluster_programming_tpu.harness import _RE_PRECISION

    m = _RE_PRECISION.search(r.stdout)
    assert m and m.group(1) == "int8w"


def test_run_dtype_policy_mutually_exclusive():
    root = Path(__file__).resolve().parent.parent
    r = subprocess.run(
        [
            sys.executable, "-m", "cuda_mpi_gpu_cluster_programming_tpu.run",
            "--dtype", "bf16", "--policy", "int8w",
        ],
        capture_output=True, text=True, timeout=120, cwd=root,
    )
    assert r.returncode == 2
    assert "mutually exclusive" in r.stderr
