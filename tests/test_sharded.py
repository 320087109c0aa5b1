"""Shard-vs-single equivalence: the test the reference never passed.

The reference's np>1 runs are numerically incomplete (V2.2 np=4 gathers
33,280 of 43,264 values; V4 np=2/4 gather 8/4 of 13 rows). Here the
row-sharded pipeline must reproduce the single-device output exactly, for
every shard count, on the non-divisible H=227 (227 = 8*29 - 5), both halo
transports, and batch > 1.
"""

import dataclasses
import re

import jax
import numpy as np
import pytest

from cuda_mpi_gpu_cluster_programming_tpu.models import (
    BLOCKS12,
    deterministic_input,
    forward_blocks12,
    init_params_deterministic,
    init_params_random,
    random_input,
)
from cuda_mpi_gpu_cluster_programming_tpu.parallel.plan import make_shard_plan, owned_range
from cuda_mpi_gpu_cluster_programming_tpu.parallel import sharded
from cuda_mpi_gpu_cluster_programming_tpu.parallel.sharded import build_sharded_forward


@pytest.fixture(scope="module")
def single_out():
    params = init_params_deterministic()
    x = deterministic_input(batch=1)
    return np.asarray(jax.jit(forward_blocks12)(params, x))


def test_plan_covers_all_rows():
    for n in (1, 2, 3, 4, 5, 8):
        plan = make_shard_plan(BLOCKS12, n)
        for lp in plan.layers:
            covered = []
            for i in range(n):
                s, e = owned_range(lp.b_out, lp.l_out, i)
                covered.extend(range(s, min(e, lp.l_out)))
            assert covered == list(range(lp.l_out)), (n, lp.name)


def _geometry(lp):
    return (
        lp.b_in, lp.b_out, lp.h_top, lp.h_bot, lp.s0_coef, lp.s0_const, lp.win_rows, lp.pad_bot
    )


def test_plan_at_four_shards_is_drawn_from_the_consumer():
    """Each block is what the next layer reads: 64/16/8/8/4 rows, every
    window start static and every halo the layer's natural P / F-S-P rows —
    pool2's 1 bottom row, not 4, and conv1's one exchange of 7, not 3 + 6."""
    plan = make_shard_plan(BLOCKS12, 4)
    got = {lp.name: (lp.b_in, lp.b_out, lp.h_top, lp.h_bot) for lp in plan.layers}
    assert got == {
        "conv1": (64, 16, 0, 7),
        "pool1": (16, 8, 0, 1),
        "conv2": (8, 8, 2, 2),
        "pool2": (8, 4, 0, 1),
        "lrn2": (4, 4, 0, 0),
    }
    assert all(lp.s0_coef == 0 for lp in plan.layers)


@pytest.mark.parametrize(
    "n,want",
    [
        # ceil(L/n) already aligned: the plan of PR 40 and before, field for field
        (2, [(114, 28, 2, 5, -2, 2, 119, 0), (28, 14, 0, 1, 0, 0, 29, 0),
             (14, 14, 2, 2, 0, 0, 18, 0), (14, 7, 0, 1, 0, 0, 15, 0), (7, 7, 0, 0, 0, 0, 7, 0)]),
        # 4*8 = 32 rows cannot cover 227 over 7: conv1's ceil wins and drifts
        (7, [(33, 8, 6, 6, -1, 6, 39, 0), (8, 4, 0, 1, 0, 0, 9, 0),
             (4, 4, 2, 2, 0, 0, 8, 0), (4, 2, 0, 1, 0, 0, 5, 0), (2, 2, 0, 0, 0, 0, 2, 0)]),
    ],
)
def test_plan_unchanged_where_ceil_already_aligned(n, want):
    assert [_geometry(lp) for lp in make_shard_plan(BLOCKS12, n).layers] == want


@pytest.mark.parametrize("n", range(1, 17))
def test_plan_windows_cover_what_each_shard_owns(n):
    """Heights 63..227: the blocks chain (a layer's output block is the
    next one's input block) and cover the rows, and every owning shard's
    window lies in its padded buffer and spans exactly the input rows its
    owned output rows read."""
    for h in range(63, 228):
        cfg = dataclasses.replace(BLOCKS12, in_height=h, in_width=h)
        plan = make_shard_plan(cfg, n)
        for lp, nxt in zip(plan.layers, plan.layers[1:]):
            assert lp.b_out == nxt.b_in and lp.l_out == nxt.l_in, (h, lp.name)
        for lp in plan.layers:
            assert n * lp.b_in >= lp.l_in and n * lp.b_out >= lp.l_out, (h, lp.name)
            for i in range(n):
                s, e = owned_range(lp.b_out, lp.l_out, i)
                if s >= e:
                    continue
                s0 = i * lp.s0_coef + lp.s0_const
                assert 0 <= s0 and s0 + lp.win_rows <= lp.padded_rows, (h, n, lp.name, i)
                first = i * lp.b_in - lp.h_top + s0  # the window's first row, global
                assert first == s * lp.stride - lp.padding, (h, n, lp.name, i)
                last = (e - 1) * lp.stride - lp.padding + lp.filter_size
                assert last <= first + lp.win_rows, (h, n, lp.name, i)


@pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 7, 8])
def test_sharded_matches_single_deterministic(n, single_out):
    """Float32, bit for bit: plans drawn from the consumer (3, 4, 5, 8) and
    plans that drift (2, 7) alike."""
    params = init_params_deterministic()
    x = deterministic_input(batch=1)
    fwd = build_sharded_forward(BLOCKS12, n_shards=n)
    out = np.asarray(fwd(params, x))
    assert out.shape == single_out.shape
    np.testing.assert_array_equal(out, single_out)


@pytest.mark.parametrize("n", [2, 8])
def test_sharded_matches_single_random(n):
    key = jax.random.PRNGKey(123)
    kp, kx = jax.random.split(key)
    params = init_params_random(kp)
    x = random_input(kx, batch=2)
    want = np.asarray(jax.jit(forward_blocks12)(params, x))
    got = np.asarray(build_sharded_forward(BLOCKS12, n_shards=n)(params, x))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n", [2, 4])
def test_staged_halo_matches_single(n, single_out):
    """V4-analogue transport (all_gather staging) must be numerically identical."""
    params = init_params_deterministic()
    x = deterministic_input(batch=1)
    got = np.asarray(build_sharded_forward(BLOCKS12, n_shards=n, staged=True)(params, x))
    np.testing.assert_allclose(got, single_out, rtol=1e-6, atol=1e-6)


def test_odd_shard_counts():
    """227 rows over 3 and 5 shards (uneven remainders, 2.2:main.cpp:103-109)."""
    params = init_params_deterministic()
    x = deterministic_input(batch=1)
    want = np.asarray(jax.jit(forward_blocks12)(params, x))
    for n in (3, 5):
        got = np.asarray(build_sharded_forward(BLOCKS12, n_shards=n)(params, x))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_small_image_sharded():
    """Non-default geometry through the planner (H=W=63)."""
    cfg = dataclasses.replace(BLOCKS12, in_height=63, in_width=63)
    params = init_params_deterministic(cfg)
    key = jax.random.PRNGKey(5)
    x = jax.random.uniform(key, (2, 63, 63, 3))
    want = np.asarray(jax.jit(lambda p, v: forward_blocks12(p, v, cfg))(params, x))
    got = np.asarray(build_sharded_forward(cfg, n_shards=4)(params, x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("staged", [False, True])
def test_pallas_tier_inside_sharded(staged, single_out):
    """v4_hybrid / v5_collective: Pallas kernels per shard (interpret mode on
    CPU). Regression: pallas_call inside shard_map requires check_vma=False."""
    params = init_params_deterministic()
    x = deterministic_input(batch=1)
    fwd = build_sharded_forward(BLOCKS12, n_shards=4, tier="pallas", staged=staged)
    got = np.asarray(fwd(params, x))
    np.testing.assert_allclose(got, single_out, rtol=1e-5, atol=1e-5)


def test_multihop_halo_tiny_layers():
    """8 shards on a 63x63 image: conv2 sees only 6 rows (<1 per shard), so
    halos must hop multiple neighbors. The reference architecture cannot
    express this at all (immediate-neighbor Isend/Irecv only)."""
    cfg = dataclasses.replace(BLOCKS12, in_height=63, in_width=63)
    params = init_params_deterministic(cfg)
    key = jax.random.PRNGKey(11)
    x = jax.random.uniform(key, (1, 63, 63, 3))
    want = np.asarray(jax.jit(lambda p, v: forward_blocks12(p, v, cfg))(params, x))
    got = np.asarray(build_sharded_forward(cfg, n_shards=8)(params, x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_sharded_forward_is_differentiable():
    """ppermute/dynamic_slice path must support reverse-mode autodiff —
    this is the spatial-parallel training path (GSPMD's is broken)."""
    import jax.numpy as jnp

    params = init_params_deterministic()
    x = deterministic_input(batch=1)
    fwd = build_sharded_forward(BLOCKS12, n_shards=4)

    def loss(p):
        return jnp.sum(fwd(p, x) ** 2)

    g = jax.grad(loss)(params)
    leaves = jax.tree_util.tree_leaves(g)
    assert all(np.all(np.isfinite(np.asarray(l))) for l in leaves)
    assert any(np.abs(np.asarray(l)).max() > 0 for l in leaves)


# ---- the scatter (PR 26): x goes to its owners by where it lives ----------

SMALL = dataclasses.replace(BLOCKS12, in_height=63, in_width=63)


def _small_case(seed: int = 7, batch: int = 2):
    kp, kx = jax.random.split(jax.random.PRNGKey(seed))
    return init_params_random(kp, SMALL), jax.random.uniform(kx, (batch, 63, 63, 3))


def _arrive(fwd, x, where: str):
    """``x`` as one kind of caller hands it over."""
    if where == "uncommitted":
        return x
    if where == "committed_in_mesh":
        return jax.device_put(x, fwd.devices[-1])
    if where == "committed_outside_mesh":
        return jax.device_put(x, jax.devices()[-1])
    if where == "host":
        return np.asarray(x)
    assert where == "row_sharded"
    import jax.numpy as jnp

    padded = jnp.pad(x, ((0, 0), (0, fwd.h_pad - x.shape[1]), (0, 0), (0, 0)))
    return jax.device_put(padded, fwd.rows)


@pytest.mark.parametrize(
    "where",
    ["uncommitted", "committed_in_mesh", "committed_outside_mesh", "row_sharded", "host", "tracer"],
)
@pytest.mark.parametrize("compute", ["fp32", "bf16"])
@pytest.mark.parametrize("n", [2, 3, 4, 8])
def test_scatter_equals_in_graph_bitwise(n, compute, where):
    """Wherever x lives, the output is the in-graph program's, bit for bit:
    63 rows pad to 64 at 2, 4 and 8 shards and not at all at 3."""
    import jax.numpy as jnp

    params, x = _small_case()
    fwd = build_sharded_forward(
        SMALL, n_shards=n, compute_dtype=jnp.bfloat16 if compute == "bf16" else None
    )
    want = np.asarray(fwd.whole(params, x))
    if where == "tracer":
        got = jax.jit(lambda p, v: fwd(p, v))(params, x)
    else:
        got = fwd(params, _arrive(fwd, x, where))
    assert got.dtype == want.dtype == np.float32
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize(
    "tier,staged,with_digests,quantized",
    [
        ("reference", True, False, False),
        ("reference", False, True, False),
        ("reference", False, False, True),
        ("pallas", False, False, False),
        ("pallas", True, False, False),
        ("pallas", False, True, False),
    ],
)
def test_scatter_equals_in_graph_bitwise_every_build(tier, staged, with_digests, quantized):
    """The tiers, the staged transport, the digest taps and the int8w
    forward (whose activations travel in bf16) all take the scattered x."""
    params, x = _small_case(seed=9)
    fwd = build_sharded_forward(
        SMALL, n_shards=4, tier=tier, staged=staged,
        with_digests=with_digests, quantized=quantized,
    )
    want, got = fwd.whole(params, x), fwd(params, x)
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got), strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if with_digests:
        assert all(d.shape == (4,) for d in got[1].values())


@pytest.mark.parametrize("abstract", [False, True])
def test_step_program_moves_only_row_blocks(abstract):
    """The point of PR 26, held in the compiled step program at four shards
    and the real geometry: what crosses between devices on the input side is
    one row block in the compute type to each other device, each by a
    collective-permute with the holder as its one source, in mesh order (the
    CPU's devices have no coordinates, so no block is relayed); the whole
    input is never gathered, replicated or cast (each device's parameter is
    the float32 batch, real on the holder alone, and each block is cut from
    it before it is cast). And ``fwd.lower`` of the public
    signature (concrete arguments, or bare ``ShapeDtypeStruct``s as the
    benchmark's readers pass) gives that program, with the halos, the
    convolutions and the scopes."""
    import re

    import jax.numpy as jnp

    params = init_params_deterministic()
    x = deterministic_input(batch=2)
    if abstract:
        params = jax.eval_shape(lambda: params)
        x = jax.ShapeDtypeStruct(x.shape, jnp.float32)
    fwd = build_sharded_forward(BLOCKS12, n_shards=4, compute_dtype=jnp.bfloat16)
    lowered = fwd.lower(params, x)
    program = lowered.as_text()
    # what is sent, as the program is written (the CPU's compiler widens a
    # bf16 collective to float32; the TPU's sends it as it is)
    sent = re.findall(
        r"stablehlo\.(collective_permute|all_gather|all_to_all|all_reduce)\S*\(.*?"
        r"(?:source_target_pairs = dense<(\[\[.*?\]\])>.*?)?: \(tensor<(\w+)>\)",
        program,
    )
    of_the_input = [(op, pairs, t) for op, pairs, t in sent if re.fullmatch(r"\[\[0, \d\]\]", pairs)]
    assert [(op, pairs) for op, pairs, _t in of_the_input] == [
        ("collective_permute", f"[[0, {j}]]") for j in (1, 2, 3)
    ], sent
    # 64-row blocks of the 227 real rows, the last of 35 and no zero row, in
    # the compute type as they travel: the batch last, W padded to 232
    assert [t for _op, _pairs, t in of_the_input] == [
        f"{rows * 3 * 232}x2xbf16" for rows in (64, 64, 35)
    ], sent
    # the rest of the input that moves is conv1's halo rows
    halos = [t for _op, _pairs, t in sent if t.endswith("x227x3xbf16")]
    assert halos and all(int(t.split("x")[1]) < 10 for t in halos), sent
    assert not [t for _op, _pairs, t in sent if t.endswith("x227x3xf32")], sent
    # each block is cut from the float32 batch, then cast: no convert of the whole
    assert "x227x227x3xbf16" not in program
    assert not re.search(r"stablehlo\.convert .*tensor<2x227x227x3xf32>", program)
    text = lowered.compile().as_text()
    entry = text[text.index("ENTRY") :]
    inputs = re.findall(r"= (\w+\[[\d,]*\])\S* parameter\(\d+\), sharding=\{devices=", entry)
    assert inputs == ["f32[2,227,227,3]"], inputs  # a quarter of the global (4*2)-image array
    assert "collective-permute" in text and "convolution" in text
    for layer in ("conv1", "pool1", "conv2", "pool2"):
        assert f"halo.{layer}" in text
    assert "/scatter/cast_in/" in text


V5E_2X2 = {0: (0, 0, 0), 1: (1, 0, 0), 2: (0, 1, 0), 3: (1, 1, 0)}


def test_scatter_routes():
    """On a v5e 2x2 (mesh devices 0-3 at (0,0) (1,0) (0,1) (1,1)) the
    holder's diagonal is two hops off and both its neighbours reach it in
    one: its block goes first, in parts through both; the others go
    straight, in mesh order. Without coordinates every block goes straight."""

    class Chip:
        def __init__(self, i):
            self.coords = V5E_2X2[i]

    v5e = [Chip(i) for i in range(4)]
    assert sharded.scatter_routes(v5e, 0) == [(3, (1, 2)), (1, ()), (2, ())]
    assert sharded.scatter_routes(v5e, 1) == [(2, (0, 3)), (0, ()), (3, ())]
    assert sharded.scatter_routes(v5e, 3) == [(0, (1, 2)), (1, ()), (2, ())]
    assert sharded.scatter_routes(jax.devices()[:4], 0) == [(1, ()), (2, ()), (3, ())]
    assert sharded.scatter_routes(jax.devices()[:1], 0) == []


@pytest.mark.parametrize("compute", ["fp32", "bf16"])
def test_relayed_scatter_equals_in_graph_bitwise(monkeypatch, compute):
    """With the CPU's devices placed as a v5e 2x2's, the diagonal's block
    reaches it in two parts through the holder's neighbours, and the output
    is the in-graph program's, bit for bit. ``sharding.scatter.relayed_bytes``
    reads the real rows relayed, set as the program is traced; 0 where the
    devices have no coordinates."""
    import jax.numpy as jnp

    from cuda_mpi_gpu_cluster_programming_tpu.observability.metrics import registry

    params, x = _small_case()
    dtype = jnp.bfloat16 if compute == "bf16" else None
    fwd = build_sharded_forward(SMALL, n_shards=4, compute_dtype=dtype)
    want = np.asarray(fwd.whole(params, x))
    got = fwd(params, x)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert registry().summary()[sharded.RELAYED_BYTES] == 0

    monkeypatch.setattr(sharded, "_coords", lambda d: V5E_2X2.get(d.id))
    fwd = build_sharded_forward(SMALL, n_shards=4, compute_dtype=dtype)
    text = fwd.lower(params, x).as_text()
    pairs = re.findall(r"source_target_pairs = dense<(\[\[\d, \d\]\])>", text)
    assert pairs[:4] == ["[[0, 1]]", "[[1, 3]]", "[[0, 2]]", "[[2, 3]]"], pairs
    got = fwd(params, x)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(np.asarray(got), want)
    # 63 rows in blocks of 16: the diagonal's 15 real rows, 2 images of 63 x 3
    itemsize = 2 if compute == "bf16" else 4
    assert registry().summary()[sharded.RELAYED_BYTES] == 15 * 2 * 63 * 3 * itemsize


def test_scatter_counters():
    """Scattered, already-placed and in-graph calls, and the bytes that
    leave the device that held x, read what each kind of argument gives."""
    import jax.numpy as jnp

    from cuda_mpi_gpu_cluster_programming_tpu.observability.metrics import registry

    def read():
        got = registry().summary()
        return [
            got.get(name, 0)
            for name in (
                sharded.SCATTERED_CALLS, sharded.PLACED_CALLS,
                sharded.IN_GRAPH_CALLS, sharded.SCATTERED_BYTES,
            )
        ]

    params, x = _small_case()
    fwd = build_sharded_forward(SMALL, n_shards=4, compute_dtype=jnp.bfloat16)
    row = 2 * 63 * 3  # (N, 1, W, C) elements; blocks of 16, 16, 16 and 15 real rows
    block = 16 * row
    before = read()
    fwd(params, x)  # held by device 0, which owns block 0: three blocks leave, in bf16
    fwd(params, x)
    assert [a - b for a, b in zip(read(), before)] == [2, 0, 0, 2 * (16 + 16 + 15) * row * 2]
    before = read()
    fwd(params, jax.device_put(x, jax.devices()[2]))  # device 2 keeps block 2
    assert [a - b for a, b in zip(read(), before)] == [1, 0, 0, (16 + 16 + 15) * row * 2]
    before = read()
    fwd(params, jax.device_put(x, jax.devices()[3]))  # device 3 keeps the short block
    assert [a - b for a, b in zip(read(), before)] == [1, 0, 0, 3 * block * 2]
    before = read()
    fwd(params, jax.device_put(x, jax.devices()[-1]))  # held outside the mesh: x leaves whole
    assert [a - b for a, b in zip(read(), before)] == [1, 0, 0, x.nbytes]
    before = read()
    fwd(params, np.asarray(x))  # from the host: four blocks leave, in float32
    assert [a - b for a, b in zip(read(), before)] == [1, 0, 0, 4 * block * 4]
    before = read()
    fwd(params, _arrive(fwd, x, "row_sharded"))
    assert [a - b for a, b in zip(read(), before)] == [0, 1, 0, 0]
    before = read()
    outer = jax.jit(lambda p, v: fwd(p, v))
    outer(params, x)
    outer(params, x)  # a tracer is seen once per trace, not once per run
    fwd(params, jax.device_put(x, jax.sharding.NamedSharding(fwd.rows.mesh, jax.sharding.PartitionSpec())))
    assert [a - b for a, b in zip(read(), before)] == [0, 0, 2, 0]


def test_parameters_are_placed_once_for_a_tree_that_comes_again():
    """Parameters left on one device would be sent to every device before
    every step; the forward places a tree once and knows it again by its
    leaves. A new tree is placed anew, host leaves are left to the runtime."""
    params, x = _small_case()
    fwd = build_sharded_forward(SMALL, n_shards=4)
    first = np.asarray(fwd(params, x))
    placed = fwd._on_every_device(params)
    assert all(
        leaf.sharding.is_equivalent_to(fwd.replicated, leaf.ndim)
        for leaf in jax.tree.leaves(placed)
    )
    assert fwd._on_every_device(params) is placed
    assert fwd._on_every_device(placed) is not placed  # other leaves: another tree
    np.testing.assert_array_equal(np.asarray(fwd(placed, x)), first)
    other = jax.tree.map(lambda a: a * 2, params)
    assert fwd._on_every_device(other) is not placed
    assert not np.array_equal(np.asarray(fwd(other, x)), first)
    np.testing.assert_array_equal(np.asarray(fwd(params, x)), first)
    on_host = jax.tree.map(np.asarray, params)
    assert fwd._on_every_device(on_host) is on_host
    np.testing.assert_array_equal(np.asarray(fwd(on_host, x)), first)


def test_halo_bytes_gauge_at_four_shards():
    """``sharding.halo_bytes``: what one interior chip receives by halo in a
    step, set as the step program is traced — conv1's 7 input rows, pool1's
    1, conv2's 4 and pool2's 1, at batch 128 in bf16: 7.0 MB (12.6 MB with
    the plan of PR 40)."""
    import jax.numpy as jnp

    from cuda_mpi_gpu_cluster_programming_tpu.observability.metrics import registry

    params = jax.eval_shape(init_params_deterministic)
    x = jax.ShapeDtypeStruct((128, 227, 227, 3), jnp.float32)
    build_sharded_forward(BLOCKS12, n_shards=4, compute_dtype=jnp.bfloat16).lower(params, x)
    rows = 7 * 227 * 3 + 1 * 55 * 96 + 4 * 27 * 96 + 1 * 27 * 256
    assert registry().summary()[sharded.HALO_BYTES] == 128 * rows * 2 == 6_995_712


def test_scatter_sends_only_real_rows_at_four_shards():
    """227 rows in blocks of 64: chip 0 sends 64, 64 and 35 real rows (163,
    not 3 x 57 = 171) and the short block is padded where it lands; the
    output is the in-graph program's, bit for bit."""
    import jax.numpy as jnp

    from cuda_mpi_gpu_cluster_programming_tpu.observability.metrics import registry

    kp, kx = jax.random.split(jax.random.PRNGKey(3))
    params, x = init_params_random(kp), random_input(kx, batch=1)
    fwd = build_sharded_forward(BLOCKS12, n_shards=4, compute_dtype=jnp.bfloat16)
    before = registry().summary().get(sharded.SCATTERED_BYTES, 0)
    got = fwd(params, x)
    assert registry().summary()[sharded.SCATTERED_BYTES] - before == 1 * 163 * 227 * 3 * 2
    np.testing.assert_array_equal(np.asarray(got), np.asarray(fwd.whole(params, x)))
