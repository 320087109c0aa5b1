"""Compile cells' step programs for a described v5e:2x2 and keep their HLO
text, for the fixtures of ``tests/benchmark/test_benchmark_layer_times.py``
and for comparing two checkouts' programs without a chip.

    JAX_PLATFORMS=cpu python3 benchmark/tools/record_step_hlo.py [--out DIR] [cell ...]

writes ``<out>/v5e_<cell>.step_hlo.txt.gz`` for each named cell (every cell of
``BENCHMARK.json`` if none is named; ``--out`` defaults to
``tests/benchmark/fixtures``). The text is what ``compiled.as_text()`` gives
for the program the cell's adapter builds, lowered on the cell's shapes: the
same instruction names a v5e trace of that cell shows (``fusion.12``,
``all-gather.5``), each with the scope the program gave it in
``metadata={op_name=...}``. ``--strip-metadata`` drops every ``metadata={...}``
and the module's tables of source locations that they index, so that two
checkouts' texts can be compared with ``cmp``: scopes are metadata and must
change nothing else.

Run by hand, in a process of its own: it loads the TPU's compiler (see the
``on-chip-measurement`` guide, section 2), which one process at a time may do.
Nothing runs on a device, so nothing here is a measurement.
"""

from __future__ import annotations

import argparse
import gzip
import os
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
sys.path[:] = [str(ROOT)] + [p for p in sys.path if Path(p or ".").resolve() != HERE]

METADATA = re.compile(r",? ?metadata=\{[^{}]*\}")
# FileNames, FunctionNames, FileLocations, StackFrames: numbered lines each
SOURCE_TABLES = re.compile(r"(?m)^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:\d+ .*\n)*")


def strip_metadata(text: str) -> str:
    return METADATA.sub("", SOURCE_TABLES.sub("", text))


def compile_step(manifest, cell, topo) -> str:
    """The compiled HLO text of ``cell``'s step program on the described
    chips: one chip, or the first ``n_shards`` as the program's mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, SingleDeviceSharding

    from benchmark import harness

    cfg = harness.load_config(manifest, cell["config"])
    traffic = harness.load_json(ROOT / "benchmark" / "traffic" / f"{cell['traffic']}.json")
    adapter = harness.load_plugin("adapters", cfg["family"])
    shapes = harness.load_plugin("shapes", cfg["family"])
    if cfg["n_shards"] > 1:
        # The program builds its mesh from jax.devices(), the CPU here: hand
        # it the described chips (steered here, not by an option of the
        # program), and leave the arguments where the program puts them.
        from cuda_mpi_gpu_cluster_programming_tpu.parallel import sharded

        mesh = Mesh(topo.devices[: cfg["n_shards"]], ("sp",))
        sharded.make_mesh = lambda n, axis_name="sp": mesh
        where = None
    else:
        where = SingleDeviceSharding(topo.devices[0])
    params = {
        name: {
            "w": jax.ShapeDtypeStruct(ws, jnp.float32, sharding=where),
            "b": jax.ShapeDtypeStruct(bs, jnp.float32, sharding=where),
        }
        for name, (ws, bs) in shapes.param_shapes(cfg).items()
    }
    x = jax.ShapeDtypeStruct(
        adapter.input_shape(cfg, int(traffic["batch"])), jnp.float32, sharding=where
    )
    return adapter.build_forward(cfg).lower(params, x).compile().as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--out", default=str(ROOT / "tests" / "benchmark" / "fixtures"))
    ap.add_argument("--strip-metadata", action="store_true")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        # the program counts jax.devices() before it builds a sharded forward
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
        ).strip()
    import jax
    from jax.experimental import topologies

    from benchmark import harness

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    for name in args.cells or [c["name"] for c in manifest["workloads"]]:
        text = compile_step(manifest, harness.find_cell(manifest, name), topo)
        if args.strip_metadata:
            text = strip_metadata(text)
        path = out / f"v5e_{name}.step_hlo.txt.gz"
        with gzip.GzipFile(path, "wb", mtime=0) as f:  # no timestamp: same text, same bytes
            f.write(text.encode())
        print(f"{name}: {len(text)} characters -> {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
