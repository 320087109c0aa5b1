"""KV-cache incremental decode: parity with the training forward + generation.

The contract: ``decode_logits`` (one token at a time through per-layer
K/V caches) must reproduce ``forward_lm``'s logits — the same model, two
execution schedules. Generation is then argmax/sampling over that
verified path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cuda_mpi_gpu_cluster_programming_tpu.models.transformer import (
    TransformerConfig,
    decode_logits,
    forward_lm,
    generate,
    init_transformer,
    make_lm_train_step,
)

CFG = TransformerConfig(d_model=64, n_heads=2, n_layers=2, d_ff=128, max_len=96)


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    return init_transformer(key, CFG), jax.random.randint(key, (2, 40), 0, CFG.vocab)


def test_teacher_forced_parity(setup):
    params, tokens = setup
    lg_dec = decode_logits(params, tokens, CFG)
    lg_ref = forward_lm(params, tokens, CFG)
    np.testing.assert_allclose(
        np.asarray(lg_dec), np.asarray(lg_ref), rtol=1e-4, atol=2e-4
    )


def test_parity_bf16(setup):
    """bf16 params: the two schedules round differently (full-sequence
    matmuls vs per-token cache matmuls), so parity is loose — bf16 has
    ~2-3 significant decimal digits and the residual stream compounds it."""
    params, tokens = setup
    pb = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    lg_dec = decode_logits(pb, tokens, CFG)
    lg_ref = forward_lm(pb, tokens, CFG)
    np.testing.assert_allclose(
        np.asarray(lg_dec, np.float32), np.asarray(lg_ref, np.float32),
        rtol=0.1, atol=0.3,
    )


def test_greedy_generation_continues_learned_pattern(setup):
    params, _ = setup
    pattern = jnp.tile(jnp.arange(8, dtype=jnp.int32), 12)[None, :65].repeat(4, 0)
    oi, step = make_lm_train_step(CFG, lr=3e-3)
    opt = oi(params)
    for _ in range(60):
        params, opt, _ = step(params, opt, pattern)
    prompt = pattern[:1, :16]
    seq = jax.jit(lambda p, pr: generate(p, pr, CFG, steps=24))(params, prompt)
    assert seq.shape == (1, 40)
    np.testing.assert_array_equal(np.asarray(seq[0, :16]), np.asarray(prompt[0]))
    want = (jnp.arange(16, 40) % 8).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(seq[0, 16:]), np.asarray(want))


def test_sampling_and_guards(setup):
    params, tokens = setup
    # temperature sampling runs and stays in-vocab
    seq = generate(
        params, tokens[:, :8], CFG, steps=4, temperature=0.8,
        key=jax.random.PRNGKey(1),
    )
    assert seq.shape == (2, 12)
    assert int(seq.min()) >= 0 and int(seq.max()) < CFG.vocab
    with pytest.raises(ValueError, match="needs an explicit key"):
        generate(params, tokens[:, :8], CFG, steps=2, temperature=0.5)
    with pytest.raises(ValueError, match="steps"):
        generate(params, tokens[:, :8], CFG, steps=0)
    with pytest.raises(ValueError, match="max_len"):
        generate(params, tokens, CFG, steps=CFG.max_len)
def test_moe_teacher_forced_parity():
    """MoE serving (round-4 verdict weak item 6): the capacity-∞ decode
    FFN must reproduce forward_lm exactly whenever training routing drops
    nothing — pinned with an undroppable capacity factor (cap >= T for
    every expert), where the two schedules are the same math."""
    moe = TransformerConfig(
        d_model=64, n_heads=2, n_layers=2, d_ff=128, max_len=64,
        n_experts=4, capacity_factor=16.0,
    )
    key = jax.random.PRNGKey(3)
    params = init_transformer(key, moe)
    tokens = jax.random.randint(key, (2, 24), 0, moe.vocab)
    lg_dec = decode_logits(params, tokens, moe)
    lg_ref = forward_lm(params, tokens, moe)
    np.testing.assert_allclose(
        np.asarray(lg_dec), np.asarray(lg_ref), rtol=1e-4, atol=2e-4
    )


def test_moe_generation_runs():
    """generate() on an MoE config (default capacity factor): in-vocab
    tokens of the right shape through the capacity-∞ serving path."""
    moe = TransformerConfig(
        d_model=64, n_heads=2, n_layers=1, d_ff=128, max_len=64, n_experts=2
    )
    key = jax.random.PRNGKey(2)
    params = init_transformer(key, moe)
    prompt = jax.random.randint(key, (2, 8), 0, moe.vocab)
    seq = generate(params, prompt, moe, steps=4)
    assert seq.shape == (2, 12)
    assert int(seq.min()) >= 0 and int(seq.max()) < moe.vocab
    np.testing.assert_array_equal(np.asarray(seq[:, :8]), np.asarray(prompt))


def test_generate_with_tp_sharded_params():
    """Serving under tensor parallelism: generate() with Megatron-TP-sharded
    params (8-way) produces exactly the replicated sequence — GSPMD
    partitions the decode einsums with no decode-specific code."""
    from cuda_mpi_gpu_cluster_programming_tpu.parallel.mesh import make_mesh
    from cuda_mpi_gpu_cluster_programming_tpu.parallel.tensor_parallel import (
        shard_lm_params_tp,
    )

    key = jax.random.PRNGKey(5)
    params = init_transformer(key, CFG)
    prompt = jax.random.randint(key, (2, 8), 0, CFG.vocab)
    ref = np.asarray(generate(params, prompt, CFG, steps=12))
    tp_params = shard_lm_params_tp(params, make_mesh(8, axis_name="tp"))
    got = np.asarray(
        jax.jit(lambda p, pr: generate(p, pr, CFG, steps=12))(tp_params, prompt)
    )
    np.testing.assert_array_equal(got, ref)


def test_decode_bench_script_smoke():
    """scripts/decode_bench.py emits well-formed JSON rows on the CPU
    backend (the chip queue runs the same script for the serving tok/s
    evidence; this guards the script's import path and schema)."""
    import json
    import subprocess
    import sys
    from pathlib import Path

    from cuda_mpi_gpu_cluster_programming_tpu.utils.env_info import (
        cpu_subprocess_env)

    root = Path(__file__).resolve().parent.parent
    out = subprocess.run(
        [sys.executable, str(root / "scripts" / "decode_bench.py"),
         "--batches", "1", "--steps", "4", "--repeats", "1"],
        capture_output=True, text=True, timeout=300,
        cwd=root,
        env=cpu_subprocess_env(1),  # CPU-forced child
    )
    assert out.returncode == 0, out.stderr[-800:]
    rows = [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]
    assert len(rows) == 1
    r = rows[0]
    assert r["metric"] == "lm_decode_tok_per_sec"
    assert r["batch"] == 1 and r["steps"] == 4
    assert r["tok_s"] > 0 and r["ms_per_step"] > 0
