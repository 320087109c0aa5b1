"""SDC sentinel tests — CPU-only, deterministic, on the virtual 8-device mesh.

Covers every trip kind (nan_loss, nonfinite, norm_spike,
replica_divergence, oracle_mismatch), the structured SDC fault class, the
seeded bit-flip injector the chaos ``sdc`` site uses, and the cross-replica
digest helpers for the shard_map paths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cuda_mpi_gpu_cluster_programming_tpu.resilience.sentinel import (
    SDC,
    Sentinel,
    SentinelConfig,
    cross_replica_digests,
    inject_bit_flip,
    oracle_spot_check,
    replica_spread,
    replicated_shard_spread,
    tree_digest,
)

# ---------------------------------------------------------------- scalars ---


def test_nan_loss_trips_with_structured_fields():
    s = Sentinel()
    with pytest.raises(SDC) as ei:
        s.check_scalar(7, float("nan"), "loss")
    assert ei.value.kind == "nan_loss"
    assert ei.value.step == 7
    assert s.trips == [ei.value]


def test_inf_nonloss_scalar_trips_nonfinite():
    s = Sentinel()
    with pytest.raises(SDC) as ei:
        s.check_scalar(0, float("inf"), "grad_norm")
    assert ei.value.kind == "nonfinite"


def test_norm_spike_trips_after_warmup_only():
    s = Sentinel(SentinelConfig(window=4, warmup=2, spike_factor=100.0))
    # Below warmup: even a wild value is observed, not tripped.
    s.check_scalar(0, 1.0)
    s.check_scalar(1, 1.1)
    with pytest.raises(SDC) as ei:
        s.check_scalar(2, 1e6)  # 100x the median of {1.0, 1.1}
    assert ei.value.kind == "norm_spike"
    # The corrupted value was NOT added to history: a sane value still passes.
    assert s.check_scalar(3, 1.2) == 1.2


def test_smooth_descent_never_trips():
    s = Sentinel(SentinelConfig(window=8, warmup=2, spike_factor=1e3))
    for i, v in enumerate(np.linspace(350.0, 300.0, 50)):
        s.check_scalar(i, float(v))
    assert s.trips == []


# ------------------------------------------------------------------ trees ---


def test_check_tree_nonfinite_leaf_trips():
    s = Sentinel()
    tree = {"w": jnp.ones((3, 3)), "b": jnp.array([0.0, jnp.nan])}
    with pytest.raises(SDC) as ei:
        s.check_tree(0, tree)
    assert ei.value.kind == "nonfinite"
    assert "non-finite" in ei.value.detail


def test_check_tree_norm_spike_trips():
    s = Sentinel(SentinelConfig(warmup=2, spike_factor=100.0))
    tree = {"w": jnp.ones((4,))}
    s.check_tree(0, tree)
    s.check_tree(1, tree)
    with pytest.raises(SDC) as ei:
        s.check_tree(2, {"w": jnp.full((4,), 1e8)})
    assert ei.value.kind == "norm_spike"
    assert "params_norm" in ei.value.detail


def test_bit_flip_injection_is_detected_by_tree_check():
    """The chaos `sdc` payload: a seeded high-exponent bit flip must trip
    the sentinel within the same check."""
    from cuda_mpi_gpu_cluster_programming_tpu.models.init import init_params_random

    params = init_params_random(jax.random.PRNGKey(0))
    s = Sentinel(SentinelConfig(warmup=2, spike_factor=1e3))
    s.check_tree(0, params)
    s.check_tree(1, params)
    corrupted, loc = inject_bit_flip(params, seed=3)
    assert loc is not None
    with pytest.raises(SDC) as ei:
        s.check_tree(2, corrupted)
    assert ei.value.kind in ("nonfinite", "norm_spike")


def test_bit_flip_is_deterministic_and_single_element():
    from cuda_mpi_gpu_cluster_programming_tpu.models.init import init_params_random

    params = init_params_random(jax.random.PRNGKey(0))
    c1, loc1 = inject_bit_flip(params, seed=5)
    c2, loc2 = inject_bit_flip(params, seed=5)
    assert loc1 == loc2  # same seed -> same flip site
    diff = sum(
        int(jnp.sum(a != b))
        for a, b in zip(jax.tree_util.tree_leaves(c1), jax.tree_util.tree_leaves(params))
    )
    assert diff == 1  # exactly one element changed
    assert inject_bit_flip(params, seed=6)[1] != loc1  # seed moves the site


# ------------------------------------------------------------- divergence ---


def test_tree_digest_moves_on_any_change():
    t = {"a": jnp.arange(4.0), "b": jnp.ones((2, 2))}
    d0 = float(tree_digest(t))
    t2 = {"a": jnp.arange(4.0).at[1].set(9.0), "b": jnp.ones((2, 2))}
    assert float(tree_digest(t2)) != d0


def test_cross_replica_digests_clean_vs_corrupt():
    """The shard_map-path checksum: identical per-shard rows digest
    identically; corrupting one shard's row shows up as spread > 0."""
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
    clean = jnp.tile(jnp.arange(16.0)[None], (8, 1))  # every shard identical
    d = cross_replica_digests(clean, mesh, "dp")
    assert d.shape == (8,)
    assert float(d.max() - d.min()) == 0.0
    corrupt = clean.at[3, 5].add(7.0)  # one replica drifts
    d2 = cross_replica_digests(corrupt, mesh, "dp")
    assert float(d2.max() - d2.min()) > 0.0


def test_replica_spread_inside_shard_map():
    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
    f = shard_map(
        lambda t: replica_spread(t, "dp")[None],
        mesh=mesh,
        in_specs=(P("dp"),),
        out_specs=P("dp"),
    )
    clean = jnp.ones((8, 4))
    assert float(np.asarray(f(clean)).max()) == 0.0
    corrupt = clean.at[2, 0].set(5.0)
    assert float(np.asarray(f(corrupt)).max()) > 0.0


def test_replicated_shard_spread_zero_for_replicated_params():
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
    x = jax.device_put(jnp.ones((4, 4)), NamedSharding(mesh, P()))  # replicated
    assert replicated_shard_spread({"w": x}) == 0.0


def test_check_divergence_trips_on_spread(monkeypatch):
    import cuda_mpi_gpu_cluster_programming_tpu.resilience.sentinel as mod

    s = Sentinel(SentinelConfig(divergence_tol=0.0))
    monkeypatch.setattr(mod, "replicated_shard_spread", lambda tree: 1.5)
    with pytest.raises(SDC) as ei:
        s.check_divergence(4, {"w": jnp.ones(2)})
    assert ei.value.kind == "replica_divergence"
    assert "1.5" in ei.value.detail


# ----------------------------------------------------------------- oracle ---


def test_oracle_spot_check_framework_matches_numpy_oracle():
    err = oracle_spot_check()
    assert err is not None, "tests/oracle.py must be loadable from the repo"
    assert err < 1e-3


def test_oracle_mismatch_trips(monkeypatch):
    import cuda_mpi_gpu_cluster_programming_tpu.resilience.sentinel as mod

    s = Sentinel(SentinelConfig(oracle_every=1))
    monkeypatch.setattr(mod, "oracle_spot_check", lambda tol=1e-3: 0.5)
    with pytest.raises(SDC) as ei:
        s.check_tree(0, {"w": jnp.ones(2)})
    assert ei.value.kind == "oracle_mismatch"


def test_oracle_every_period(monkeypatch):
    import cuda_mpi_gpu_cluster_programming_tpu.resilience.sentinel as mod

    calls = []
    monkeypatch.setattr(
        mod, "oracle_spot_check", lambda tol=1e-3: calls.append(1) or 0.0
    )
    s = Sentinel(SentinelConfig(oracle_every=3))
    for i in range(6):
        s.check_tree(i, {"w": jnp.ones(2)})
    assert len(calls) == 2  # checks 3 and 6
