"""The four-chip cell's scatter, read from the text the TPU's compiler gives
for the row-sharded step at batch 128 on a described v5e:2x2: the holder
casts no whole input (no ``bf16[128,227,227,3]`` anywhere), each block that
leaves it is made from the float32 parameter with no ``copy`` on the way to
its ``collective-permute``, and the block of the chip that the devices'
``coords`` put two hops away is cut first and leaves first, in two parts
that the holder's two neighbours forward, before their own blocks take the
holder's links; ``sharding.scatter.relayed_bytes`` reads those 35 rows.
What keeps a later change from putting the whole-input cast, the
send-layout copies or the one-sided link back; no chip, so nothing here is
a time.

The topology is described inside a module-scoped fixture, never at import,
and the fixture skips where it cannot be described (the rule of
``tests/test_mla_step_dataflow_v5e.py``).
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward
from cuda_mpi_gpu_cluster_programming_tpu.models import init
from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import BLOCKS12
from cuda_mpi_gpu_cluster_programming_tpu.observability.metrics import registry
from cuda_mpi_gpu_cluster_programming_tpu.parallel import sharded

BATCH = 128
X = f"f32[{BATCH},227,227,3]"
_INSTR = re.compile(r"^\s+(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w\-]+)\((.*?)\)(.*)$")


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def step(topo):
    """``(entry, devices, relayed)``: the compiled step's ENTRY instructions
    in the order they run, ``[(name, type, opcode, operands, attributes)]``,
    the mesh's devices and ``sharding.scatter.relayed_bytes`` as the build
    set it. The mesh of the described chips is handed to the build
    here (the program would take ``jax.devices()``, the CPU). A compile for a
    described chip is written to the persistent cache but cannot be read back
    without one: the cache is kept out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    mesh = Mesh(topo.devices[:4], ("sp",))
    params = jax.eval_shape(init.init_params_random, jax.random.key(0))
    x = jax.ShapeDtypeStruct((BATCH, 227, 227, 3), jnp.float32)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        fwd = build_forward(REGISTRY["v2.2_sharded"], BLOCKS12, n_shards=4, mesh=mesh, compute="bf16")
        text = fwd.lower(params, x).compile().as_text()
        relayed = registry().summary()[sharded.RELAYED_BYTES]
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    entry = []
    for line in text[text.index("\nENTRY") :].splitlines()[1:]:
        m = _INSTR.match(line)
        if m:
            name, typ, opcode, operands, attrs = m.groups()
            entry.append((name, typ, opcode, re.findall(r"%([\w.\-]+)", operands), attrs))
    return entry, list(mesh.devices.flat), relayed


def _sends(entry, source=0):
    """``[(target, start instruction, rows)]`` of one device's sends, in the
    order they run: the collective-permutes with ``source`` as their one
    source, and the image rows each carries (its wire is (rows*3*232, 128))."""
    out = []
    for name, typ, opcode, _operands, attrs in entry:
        pairs = re.search(r"source_target_pairs=\{\{(\d+),(\d+)\}\}", attrs)
        if opcode == "collective-permute-start" and pairs and pairs.group(1) == str(source):
            rows = int(re.search(r"bf16\[(\d+),128\]", typ).group(1)) // (3 * 232)
            out.append((int(pairs.group(2)), name, rows))
    return out


def _made_from(entry, name):
    """The opcodes from instruction ``name`` back along first operands (a
    pad's second is its constant) to the parameter it is made from, bitcasts
    skipped: ``["pad", "fusion", "parameter f32[...]"]``."""
    by_name = {e[0]: e for e in entry}
    chain = []
    while name in by_name:
        _n, typ, opcode, operands, _attrs = by_name[name]
        if opcode == "parameter":
            chain.append(f"parameter {typ.split('{')[0]}")
            break
        if opcode != "bitcast":
            chain.append(opcode)
        if not operands:
            break
        name = operands[0]
    return chain


def test_no_whole_input_is_cast(step):
    entry, _devices, _relayed = step
    whole = [e[0] for e in entry if e[1].startswith(f"bf16[{BATCH},227,227,3]")]
    assert not whole, whole


def test_each_block_leaves_from_the_float32_parameter_without_a_copy(step):
    entry, _devices, _relayed = step
    sends = _sends(entry)
    assert sorted(rows for _t, _name, rows in sends) == [17, 18, 64, 64], sends
    by_name = {e[0]: e for e in entry}
    for target, name, _rows in sends:
        chain = _made_from(entry, by_name[name][3][0])
        # one fusion reads the block's rows of the batch and casts them; the
        # pad fills W to whole tiles, so the send takes it as it stands
        assert chain == ["pad", "fusion", f"parameter {X}"], (target, chain)


def test_the_diagonal_block_leaves_first_in_parts_through_both_neighbours(step):
    entry, devices, _relayed = step
    hops = [sum(abs(a - b) for a, b in zip(devices[0].coords, d.coords)) for d in devices]
    assert hops == [0, 1, 1, 2], hops  # mesh device 3 is the 2x2's diagonal
    sends = _sends(entry)
    # the diagonal's 35 rows leave first, 17 to one neighbour, 18 to the other
    assert [(t, rows) for t, _name, rows in sends[:2]] in ([(1, 17), (2, 18)], [(2, 18), (1, 17)]), sends
    # each neighbour forwards its part to the diagonal
    assert [(t, rows) for t, _name, rows in _sends(entry, 1)] == [(3, 17)], _sends(entry, 1)
    assert [(t, rows) for t, _name, rows in _sends(entry, 2)] == [(3, 18)], _sends(entry, 2)
    # the neighbours' own blocks take the links once the parts are through
    order = [e[0] for e in entry]
    parts_done = [
        order.index(e[0]) for e in entry
        if e[2] == "collective-permute-done" and e[3][0] in {name for _t, name, _r in sends[:2]}
    ]
    assert len(parts_done) == 2
    assert all(order.index(name) > max(parts_done) for _t, name, _r in sends[2:]), sends


def test_the_diagonal_block_is_cut_first(step):
    entry, _devices, _relayed = step
    param = next(e[0] for e in entry if e[2] == "parameter" and e[1].startswith(X))
    by_name = {e[0]: e for e in entry}
    made = {}
    for target, name, rows in _sends(entry):
        producer = by_name[name][3][0]
        while by_name[producer][3] and param not in by_name[producer][3]:
            producer = by_name[producer][3][0]
        made[producer] = rows
    assert len(made) == 4, made  # each block and part cut from the parameter on its own
    first_cut = next(e[0] for e in entry if param in e[3])
    assert made.get(first_cut) in (17, 18), (first_cut, made)


def test_the_relayed_bytes_are_the_diagonal_block(step):
    """``sharding.scatter.relayed_bytes``: the diagonal's 35 real rows of 128
    images in bf16, what shows a run that the relay is on."""
    _entry, _devices, relayed = step
    assert relayed == 35 * BATCH * 227 * 3 * 2 == 6_101_760
