"""Every production forward names its layers where they run: the compiled
program of each execution config carries the scopes of ``ops/scopes.py`` in
``metadata={op_name=...}`` (the CPU compiler keeps them as the TPU's does), so
a device trace of any of them splits by layer. Tiny sizes; sharded entries on
the virtual CPU devices."""

from __future__ import annotations

import re

import jax
import pytest

from cuda_mpi_gpu_cluster_programming_tpu.configs import (
    LANGUAGE_MODELS,
    REGISTRY,
    build_forward,
    language_model,
)
from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import Blocks12Config
from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet_full import (
    AlexNetConfig,
    init_full_deterministic,
)
from cuda_mpi_gpu_cluster_programming_tpu.models.init import init_params_deterministic
from cuda_mpi_gpu_cluster_programming_tpu.ops import scopes

SMALL = Blocks12Config(in_height=63, in_width=63)
SMALL_FULL = AlexNetConfig(  # 99x99 leaves pool5 a 2x2 map to flatten
    blocks12=Blocks12Config(in_height=99, in_width=99), fc6=32, fc7=32, num_classes=10
)
HALO_LAYERS = ("conv1", "pool1", "conv2", "pool2")  # the layers whose windows cross rows


def _paths(fwd, params, x):
    """Every ``op_name`` of the compiled program, in the text's order."""
    text = fwd.lower(params, x).compile().as_text()
    return re.findall(r'op_name="((?:[^"\\]|\\.)*)"', text)


def _scoped(paths, scope):
    return [p for p in paths if scope in p.split("/")[:-1]]


def _build(key, compute="fp32"):
    exec_cfg = REGISTRY[key]
    if exec_cfg.model in LANGUAGE_MODELS:
        import jax.numpy as jnp

        model = language_model(exec_cfg)
        small, batch, seq = model.PRESETS["small"]
        fwd = build_forward(exec_cfg, small, compute=compute)
        dtype = jnp.bfloat16 if compute == "bf16" else jnp.float32
        params = jax.eval_shape(lambda: model.init(jax.random.key(0), small, dtype))
        return exec_cfg, fwd, params, jax.ShapeDtypeStruct((batch, seq), "int32")
    full = exec_cfg.model == "alexnet_full"
    model_cfg = SMALL_FULL if full else SMALL
    # four shards: the 2 output rows then leave padding for the gather to slice off
    n_shards = 1 if exec_cfg.strategy == "single" else 4
    fwd = build_forward(exec_cfg, model_cfg, n_shards=n_shards, compute=compute)
    params = init_full_deterministic(model_cfg) if full else init_params_deterministic(model_cfg)
    x = jax.ShapeDtypeStruct(
        (2, model_cfg.in_height, model_cfg.in_width, model_cfg.in_channels), "float32"
    )
    return exec_cfg, fwd, params, x


@pytest.mark.parametrize("key", sorted(REGISTRY))
def test_every_layer_of_the_chain_is_named_in_the_compiled_program(key):
    exec_cfg, fwd, params, x = _build(key)
    paths = _paths(fwd, params, x)
    chain = scopes.BLOCKS12_LAYERS
    if exec_cfg.model == "alexnet_full":
        chain += scopes.ALEXNET_TAIL_LAYERS + scopes.FC_LAYERS
    if exec_cfg.model == "mla_moe":  # a dense layer first, then the MoE layers
        chain = scopes.MLA_MOE_LAYERS
    if exec_cfg.model == "kda_moe":  # a softmax layer with its MoE first, then the linear layers
        names = scopes.KDA_MOE_LAYERS
        chain = names[:3] + names[6:9] + names[3:6] + names[9:]
        assert sorted(chain) == sorted(names)
    if exec_cfg.model == "cca_moe":  # the rotary tables (cca.mix) are made once, before the loop over the layers
        names = scopes.CCA_MOE_LAYERS
        chain = names[:1] + names[3:4] + names[1:3] + names[4:]
        assert sorted(chain) == sorted(names)
    if exec_cfg.model == "scmoe_mla":  # the MoE branches off before the first dense FFN; the branch's sum ends it
        names = scopes.SCMOE_MLA_LAYERS
        chain = names[:4] + names[5:8] + names[4:5] + names[8:]
        assert sorted(chain) == sorted(names)
    if exec_cfg.model == "sambay":  # an MLP after the first scan; the memory units only after both hand-off layers
        names = scopes.SAMBAY_LAYERS
        chain = names[:5] + names[9:10] + names[6:9] + names[5:6] + names[10:]
        assert sorted(chain) == sorted(names)
    for layer in chain:
        assert _scoped(paths, layer), f"{key}: no operation under the scope {layer!r}"
    # in order: the jaxpr is the program as written, before any scheduling
    stacks = [stack for _eqn, stack in _full_stacks(jax.make_jaxpr(fwd)(params, x).jaxpr)]
    seen = []
    for stack in stacks:
        for part in stack.split("/"):
            if part in chain and part not in seen:
                seen.append(part)
    assert tuple(seen) == chain, f"{key}: layers run as {seen}"
    if exec_cfg.strategy in ("halo", "staged_halo"):
        for layer in HALO_LAYERS:
            inside = [p for p in paths if f"/{layer}/{scopes.HALO_PREFIX}{layer}/" in p]
            assert inside, f"{key}: no halo.{layer} nested in {layer}"
        # the pad before the shard_map keeps its name through the compiler;
        # the slice after it is rewritten with the all-gather the partitioner
        # puts in and comes out with none (the benchmark counts a collective
        # that has no scope of its own under gather, by its opcode)
        assert _scoped(paths, scopes.SCATTER), f"{key}: nothing under scatter"
        assert any(scopes.GATHER in s.split("/") for s in stacks), f"{key}: nothing under gather"
    if exec_cfg.strategy == "tp":
        assert [p for p in paths if "/conv2/halo.conv2/" in p]  # the channel all-gather
        assert [p for p in paths if "/lrn2/halo.lrn2/" in p]  # the LRN's channel halo


def _full_stacks(jaxpr, prefix=""):
    """``(equation, its whole name stack)`` through nested jaxprs, in order: a
    loop's or a call's body counts its names from the equation that holds it."""
    for eqn in jaxpr.eqns:
        stack = "/".join(part for part in (prefix, str(eqn.source_info.name_stack)) if part)
        yield eqn, stack
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _full_stacks(sub, stack)


@pytest.mark.parametrize("key,compute", [("v1_jit", "bf16"), ("v1_jit", "int8w"), ("v2.2_sharded", "int8w")])
def test_the_casts_to_the_compute_type_are_named(key, compute):
    _cfg, fwd, params, x = _build(key, compute)
    paths = _paths(fwd, params, x)
    assert [p for p in _scoped(paths, scopes.CAST_IN) if "convert_element_type" in p]
    for layer in scopes.BLOCKS12_LAYERS:
        assert _scoped(paths, layer), layer


def test_a_kernel_that_covers_conv_and_pool_says_so():
    """``fuse="block"`` runs a block as one kernel: its scope names every
    layer it covers, and no operation claims to be the convolution alone."""
    from cuda_mpi_gpu_cluster_programming_tpu.ops import pallas_kernels as pk
    from cuda_mpi_gpu_cluster_programming_tpu.ops.pallas_model import forward_blocks12_pallas

    fwd = jax.jit(
        lambda p, x: forward_blocks12_pallas(p, x, SMALL, variants=pk.KernelVariants(fuse="block"))
    )
    x = jax.ShapeDtypeStruct((1, 63, 63, 3), "float32")
    paths = _paths(fwd, init_params_deterministic(SMALL), x)
    assert _scoped(paths, "conv1+pool1") and _scoped(paths, "conv2+pool2+lrn2")
    assert not _scoped(paths, "conv1") and not _scoped(paths, "lrn2")


@pytest.mark.parametrize(
    "key,layers",
    [
        ("v8_mla_moe", scopes.MLA_MOE_LAYERS),
        ("v9_kda_moe", scopes.KDA_MOE_LAYERS),
        ("v10_cca_moe", scopes.CCA_MOE_LAYERS),
        ("v11_scmoe_mla", scopes.SCMOE_MLA_LAYERS),
        ("v12_sambay", scopes.SAMBAY_LAYERS),
    ],
)
def test_token_ids_and_parameters_stored_in_bf16_pass_the_bf16_wrapper_uncast(key, layers):
    """``compute="bf16"`` casts floating inputs only: a language model's
    integer ids and its parameters, already bf16, reach the forward as they
    are, and every one of its scopes is in the compiled program."""
    _cfg, fwd, params, ids = _build(key, "bf16")
    paths = _paths(fwd, params, ids)
    assert not _scoped(paths, scopes.CAST_IN)
    for layer in layers:
        assert _scoped(paths, layer), layer
    jaxpr = jax.make_jaxpr(fwd)(params, ids)
    assert str(jaxpr.jaxpr.invars[-1].aval.dtype) == "int32"


LANGUAGE_KEYS = ("v8_mla_moe", "v9_kda_moe", "v10_cca_moe", "v11_scmoe_mla")


@pytest.mark.parametrize("combine", ["gathers", "kernel"])
@pytest.mark.parametrize("key", LANGUAGE_KEYS)
def test_the_routed_experts_and_the_route_name_their_phases_inside_their_layers(key, combine, monkeypatch):
    """Every phase of ``scopes.PHASES`` is in the step program, nested in its
    layer and nowhere else, and holds what its name says: the three grouped
    products under ``experts.products``, the combine (the kernel where the
    shapes say so, steered here: the small presets' do not) under
    ``experts.combine``, the rows' place in the span and both zero fills under
    ``experts.layout``, the tokens' gather under ``experts.gather``, the sort
    under ``route.sort`` and the router's top-k or argmax under
    ``route.score``."""
    from cuda_mpi_gpu_cluster_programming_tpu.models import moe_share

    if combine == "kernel":
        monkeypatch.setattr(moe_share, "worth_a_kernel", lambda *shape: True)
    _cfg, fwd, params, ids = _build(key, "bf16")
    paths = _paths(fwd, params, ids)
    for phase, layer in scopes.PHASE_LAYER.items():
        under = [p.split("/")[:-1] for p in paths if phase in p.split("/")[:-1]]
        assert under, f"{key}: nothing under {phase}"
        # inside its layer (a loop's ``while/body`` may stand between them), and inside no other layer since
        for parts in under:
            outer = [part for part in parts[: parts.index(phase)] if part in scopes.LAYERS and part != "layer_loop"]
            assert outer and outer[-1] == layer, f"{key}: {phase} under {'/'.join(parts)}"
    held: dict = {}
    for eqn, stack in _full_stacks(jax.make_jaxpr(fwd)(params, ids).jaxpr):
        name = eqn.primitive.name
        if name == "pallas_call":
            name = str(eqn.params.get("name_and_src_info") or eqn.params["name"]).split(" ")[0]
        held.setdefault(name, set()).add(tuple(part for part in stack.split("/") if part in scopes.PHASES))
    assert held["grouped_matmul"] == {("experts.products",)}
    assert held.get("moe_combine") == ({("experts.combine",)} if combine == "kernel" else None)
    assert held["sort"] == {("route.sort",)}
    assert ("experts.layout",) in held["dynamic_update_slice"] and ("experts.layout",) in held["broadcast_in_dim"]
    assert ("experts.gather",) in held["gather"] and ("experts.products",) not in held["gather"]
    assert ("route.score",) in held["argmax" if key == "v10_cca_moe" else "top_k"]
    # a phase never stands inside another
    assert all(len(phases) <= 1 for stacks in held.values() for phases in stacks)


def _strip_metadata(text: str) -> str:
    """``benchmark/tools/record_step_hlo.py``'s: every ``metadata={...}`` and
    the module's tables of source locations."""
    import importlib.util
    import sys
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "benchmark" / "tools" / "record_step_hlo.py"
    spec = importlib.util.spec_from_file_location("record_step_hlo", path)
    module = importlib.util.module_from_spec(spec)
    sys_path = list(sys.path)
    try:
        spec.loader.exec_module(module)
    finally:
        sys.path[:] = sys_path
    return module.strip_metadata(text)


@pytest.mark.parametrize("key", LANGUAGE_KEYS)
def test_the_phases_are_metadata_and_change_nothing_the_compiler_builds(key, monkeypatch):
    """The compiled step with its phases and the same step built with
    ``scopes.phase`` naming nothing are one program once the metadata is
    stripped; and the phases are in the first and not in the second."""
    import contextlib

    def text():
        _cfg, fwd, params, ids = _build(key, "bf16")
        return fwd.lower(params, ids).compile().as_text()

    named = text()
    monkeypatch.setattr(scopes, "phase", lambda name: contextlib.nullcontext())
    plain = text()
    assert not [phase for phase in scopes.PHASES if f"/{phase}/" not in named]
    assert not [phase for phase in scopes.PHASES if f"/{phase}/" in plain]
    assert "/moe.experts/" in plain and _strip_metadata(named) == _strip_metadata(plain)


@pytest.mark.parametrize("name", ["experts.scatter", "moe.experts", "conv1", "halo.experts.gather"])
def test_a_phase_outside_the_vocabulary_is_refused(name):
    with pytest.raises(ValueError, match="not a phase name"):
        scopes.phase(name)
    assert name not in scopes.PHASES and set(scopes.PHASE_LAYER.values()) <= set(scopes.LAYERS)
    assert not set(scopes.PHASES) & set(scopes.LAYERS)


def test_a_name_outside_the_vocabulary_is_refused():
    with pytest.raises(ValueError, match="not a layer name"):
        scopes.layer("conv9")
    with pytest.raises(ValueError, match="not a layer name"):
        scopes.halo("cast_in")


def test_the_chains_and_the_sentinel_stages_take_their_names_from_the_scopes():
    from cuda_mpi_gpu_cluster_programming_tpu.observability.stages import SENTINEL_STAGES

    assert SENTINEL_STAGES is scopes.BLOCKS12_LAYERS
    assert tuple(n for n, _s in SMALL.layer_chain()) == scopes.BLOCKS12_LAYERS
    assert tuple(n for n, _s in SMALL_FULL.layer_chain()) == (
        scopes.BLOCKS12_LAYERS + scopes.ALEXNET_TAIL_LAYERS
    )
