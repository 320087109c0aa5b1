"""Execution-config registry: the reference's V1-V5 stages as configs.

The reference implements its parallelization stages as five divergent source
trees (final_project/v1_serial ... v5_cuda_aware_mpi). Here each stage is an
``ExecConfig`` selecting (a) the op tier — XLA reference ops or Pallas
kernels — and (b) the distribution strategy — none, replicate-all, or
row-sharded with halo exchange — over the *same* model definition.

Stage mapping (version_name strings stay compatible with the reference's
canonical analysis mapping, analysis.md:69-92, extended with a V6 family):

- ``v1_jit``        ↔ V1 Serial (v1_serial/): single device, XLA ops.
- ``v2.1_replicated``↔ V2.1 BroadcastAll (2.1_broadcast_all/src/main.cpp:49-87):
  fully-replicated input+params, every device computes the full pass — kept
  as the pedagogical anti-baseline.
- ``v2.2_sharded``  ↔ V2.2 ScatterHalo (2.2_scatter_halo/src/main.cpp:100-249):
  1-D row decomposition, neighbor halo exchange, XLA ops.
- ``v3_pallas``     ↔ V3 CUDA (v3_cuda_only/): single device, hand-written
  Pallas kernels (the TPU counterpart of the .cu kernels).
- ``v4_hybrid``     ↔ V4 MPI+CUDA (v4_mpi_cuda/): row-sharded with
  *host-staged-style* halo (all_gather + reslice — the analogue of V4's
  D2H→MPI→H2D staging) + Pallas kernels per shard.
- ``v5_collective`` ↔ V5 CUDA-aware MPI (planned-only in the reference,
  README.md:158-166): row-sharded with direct device-to-device ``ppermute``
  halos over ICI — the natural state of the TPU backend, exposed as an
  explicit measured config to reproduce the V4-vs-V5 comparison story.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import jax

from .models.alexnet import BLOCKS12, forward_blocks12


@dataclasses.dataclass(frozen=True)
class ExecConfig:
    key: str
    version_name: str  # canonical name for CSV/analysis compatibility
    tier: str  # "reference" (XLA ops) | "pallas"
    strategy: str  # "single" | "replicated" | "halo" | "staged_halo"
    description: str
    model: str = "blocks12"  # "blocks12" | "alexnet_full" | "mla_moe" | "kda_moe" | "cca_moe" | "scmoe_mla" | "sambay"


REGISTRY: Dict[str, ExecConfig] = {
    c.key: c
    for c in [
        ExecConfig(
            "v1_jit",
            "V1 Serial",
            "reference",
            "single",
            "single-device jit-compiled XLA ops (serial-CPU analogue)",
        ),
        ExecConfig(
            "v2.1_replicated",
            "V2.1 BroadcastAll",
            "reference",
            "replicated",
            "fully-replicated compute on every device (anti-baseline)",
        ),
        ExecConfig(
            "v2.2_sharded",
            "V2.2 ScatterHalo",
            "reference",
            "halo",
            "1-D row decomposition + ppermute halo exchange, XLA ops",
        ),
        ExecConfig(
            "v3_pallas",
            "V3 CUDA",
            "pallas",
            "single",
            "single-device hand-written Pallas kernels (CUDA-kernel analogue)",
        ),
        ExecConfig(
            "v4_hybrid",
            "V4 MPI+CUDA",
            "pallas",
            "staged_halo",
            "row-sharded, Pallas per shard, all_gather-staged halos (V4 host-staging analogue)",
        ),
        ExecConfig(
            "v5_collective",
            "V5 MPI+CUDA-Aware",
            "pallas",
            "halo",
            "row-sharded, Pallas per shard, device-to-device ppermute halos over ICI",
        ),
        ExecConfig(
            "v7_tp",
            "V7 TensorParallel",
            "reference",
            "tp",
            "conv filter-bank (K-axis) decomposition — the reference's named-"
            "but-unbuilt alternative to row decomposition (README.md:638); "
            "weights sharded, channel-halo LRN, boundary all_gather",
        ),
        # V6 family: the reference's explicit extension task (README.md:19) —
        # full AlexNet through conv5 + FC6-8 (dims summary.md:29-45).
        ExecConfig(
            "v6_full_jit",
            "V6 AlexNet Full",
            "reference",
            "single",
            "full AlexNet (conv1-5 + FC6-8) single device, XLA ops",
            model="alexnet_full",
        ),
        ExecConfig(
            "v6_full_pallas",
            "V6 AlexNet Full Pallas",
            "pallas",
            "single",
            "full AlexNet, Pallas kernels for the spatial part, MXU matmul FC",
            model="alexnet_full",
        ),
        ExecConfig(
            "v6_full_sharded",
            "V6 AlexNet Full Sharded",
            "reference",
            "halo",
            "full AlexNet, row-sharded spatial part + replicated FC head",
            model="alexnet_full",
        ),
        # V8: the language-model family. Token ids in, logits out; parameters
        # stored in the compute type (models.mla_moe).
        ExecConfig(
            "v8_mla_moe",
            "V8 MLA-MoE Share",
            "reference",
            "single",
            "latent-attention MoE decoder as one expert-parallel chip holds it: "
            "its experts and vocabulary slice, single device, XLA ops with the "
            "flash-attention and grouped-matmul kernels",
            model="mla_moe",
        ),
        ExecConfig(
            "v9_kda_moe",
            "V9 KDA-MoE Share",
            "reference",
            "single",
            "hybrid linear-attention (gated delta rule) / gated softmax GQA MoE decoder "
            "as one expert-parallel chip holds it, single device, XLA ops with the "
            "chunked-scan, flash-attention and grouped-matmul kernels",
            model="kda_moe",
        ),
        ExecConfig(
            "v10_cca_moe",
            "V10 CCA-MoE Share",
            "reference",
            "single",
            "compressed-convolutional-attention MoE decoder (latent attention mixed by causal "
            "convolutions; an MLP router over a state carried down the depth, top-1 with a skip "
            "output) as one expert-parallel chip holds it: one scan over its layers, single "
            "device, XLA ops with the flash-attention and grouped-matmul kernels",
            model="cca_moe",
        ),
        ExecConfig(
            "v11_scmoe_mla",
            "V11 ScMoE-MLA Share",
            "reference",
            "single",
            "shortcut-connected MoE decoder over latent attention (a layer is two MLA + dense-FFN "
            "sublayers with the MoE as a branch from the first to the layer's end; a softmax router "
            "over the experts and zero-computation identity experts) as one expert-parallel chip "
            "holds it: one scan over its layers, single device, XLA ops with the flash-attention, "
            "grouped-matmul and combine kernels",
            model="scmoe_mla",
        ),
        ExecConfig(
            "v12_sambay",
            "V12 SambaY Dense",
            "reference",
            "single",
            "decoder-hybrid-decoder, a dense model whole on one device: (Mamba scan, windowed "
            "differential attention) pairs, a Mamba layer and a causal differential attention layer "
            "that hand their scan output and their keys and values down, then (gated memory unit, "
            "differential cross-attention) pairs that read them; two scans over stacked pairs, XLA "
            "ops with the selective-scan and flash-attention kernels",
            model="sambay",
        ),
    ]
}

# The language-model families: token ids in, logits out, parameters stored in
# the compute type. ``ExecConfig.model`` names the module under ``models``.
LANGUAGE_MODELS = ("mla_moe", "kda_moe", "cca_moe", "scmoe_mla", "sambay")


def language_model(exec_cfg: ExecConfig):
    """The model module of a language-model config (``SMALL``, ``PRESETS``,
    ``init``, ``param_count``, ``forward``)."""
    import importlib

    return importlib.import_module(f".models.{exec_cfg.model}", __package__)


def _resolve_variants(plan):
    """Env-resolved variants, overlaid with a TunePlan's per-layer winners
    when one is supplied. Precedence per knob: explicit env var beats the
    tuned plan beats the code default (tuning.plan.effective_layer_variants
    is the one implementation)."""
    from .ops.pallas_kernels import KernelVariants

    kv = KernelVariants.resolve()
    if plan is None:
        return kv
    from .tuning.plan import effective_layer_variants

    return effective_layer_variants(plan, base=kv)


def build_forward(
    exec_cfg: ExecConfig,
    model_cfg=None,
    n_shards: int = 1,
    mesh: Optional[jax.sharding.Mesh] = None,
    compute: str = "fp32",
    plan=None,
    donate: bool = False,
    policy=None,
) -> Callable:
    """Return a jitted ``(params, x) -> out`` for the given execution config.

    ``model_cfg`` defaults per model family (BLOCKS12 / ALEXNET).
    ``n_shards`` is the TPU analogue of ``mpirun -np N``
    (scripts/common_test_utils.sh:274-276).
    ``policy`` (or the legacy ``compute`` string, which accepts the same
    names) selects numerics via the precision subsystem
    (docs/PRECISION.md): ``fp32`` (exact reference parity — fp32 MACs even
    on the MXU), ``bf16`` (params+input cast to bfloat16, fp32 accumulation
    on the MXU, fp32 output — the TPU-native perf mode; halves HBM traffic
    and engages the MXU's fast path), or ``int8w`` (symmetric per-channel
    int8 weights, dequant-free bf16-accumulate compute — single-device
    Blocks 1-2 tiers only). A ``precision.policy.DtypePolicy`` object is
    accepted wherever a name is. Non-fp32 policies are expected to have
    cleared the fp32-oracle ``ToleranceGate`` (the autotuner enforces this
    before persisting a winner).
    ``plan``: a ``tuning.plan.TunePlan`` whose per-layer kernel variants the
    Pallas tiers run with (single-device AND sharded builders; reference
    tiers ignore it); explicit env knobs still win — see docs/TUNING.md.
    ``donate``: donate the input-activation buffer to the computation
    (single-device tiers; halves peak HBM for the activation at the cost of
    consuming ``x`` — callers that re-invoke with the same array, e.g. the
    amortized timing chains, must leave this off).
    """
    from .precision.policy import POLICY_NAMES, resolve_policy

    try:
        pol = resolve_policy(policy if policy is not None else compute)
    except ValueError:
        raise ValueError(
            f"unknown compute mode / precision policy "
            f"{(policy if policy is not None else compute)!r} "
            f"({'|'.join(POLICY_NAMES)})"
        ) from None
    # Persistent XLA compile cache (the prebuilt-binaries analogue), wired
    # at build time so EVERY builder caller — tuner candidates included —
    # gets it, not just the run/bench entry mains.
    from .utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    if pol.quantized:
        if exec_cfg.model != "blocks12" or exec_cfg.strategy not in (
            "single", "halo", "staged_halo", "replicated"
        ):
            raise ValueError(
                f"policy {pol.name!r} supports the Blocks 1-2 single-device, "
                f"halo-sharded, and replicated tiers only (config "
                f"{exec_cfg.key!r} is {exec_cfg.model}/{exec_cfg.strategy}); "
                "quantized tensor-parallel and full-AlexNet forwards are "
                "open ROADMAP items"
            )
        from .models.alexnet import BLOCKS12 as _B12

        mcfg = model_cfg or _B12
        if exec_cfg.strategy in ("halo", "staged_halo", "replicated"):
            # Sharded int8w rungs: int8 values + per-channel scales ride the
            # replicated param tree; each rung is expected to re-screen via
            # precision.gate.ToleranceGate.screen_sharded before its rows
            # publish.
            need = n_shards
            if mesh is None and jax.device_count() < need:
                raise ValueError(
                    f"config {exec_cfg.key!r} with {n_shards} shards needs "
                    f"{need} devices, have {jax.device_count()} (use "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count=N on "
                    f"CPU to fake a mesh)"
                )
            if exec_cfg.strategy == "replicated":
                from .parallel.replicated import build_replicated_forward

                fwd = build_replicated_forward(
                    mcfg, n_shards, mesh=mesh, quantized=True
                )
            else:
                from .parallel.sharded import build_sharded_forward

                fwd = build_sharded_forward(
                    mcfg,
                    n_shards,
                    mesh=mesh,
                    tier=exec_cfg.tier,
                    staged=(exec_cfg.strategy == "staged_halo"),
                    plan=plan,
                    quantized=True,
                )
            return _observed(fwd, exec_cfg, pol.name, n_shards)
        from .precision.quantize import forward_blocks12_int8w

        kv = _resolve_variants(plan) if exec_cfg.tier == "pallas" else None
        tier = exec_cfg.tier
        return _observed(
            _jit(
                lambda p, x: forward_blocks12_int8w(
                    p, x, mcfg, variants=kv, tier=tier
                ),
                donate,
            ),
            exec_cfg,
            pol.name,
            1,
        )
    import jax.numpy as jnp

    bf16 = pol.name != "fp32"
    # The row-sharded Blocks 1-2 forward casts for itself: the input where
    # it is held, before it is scattered in the compute type, the parameters
    # inside its step program (parallel.sharded). A jit round it would hand
    # it a tracer, and with that the whole input to every device.
    casts_itself = exec_cfg.model == "blocks12" and exec_cfg.strategy in (
        "halo", "staged_halo"
    )
    fwd = _build_forward_fp32(
        exec_cfg, model_cfg, n_shards, mesh, plan, donate,
        compute_dtype=jnp.bfloat16 if bf16 and casts_itself else None,
    )
    if casts_itself or not bf16:
        return _observed(fwd, exec_cfg, pol.name, n_shards)

    from .ops import scopes

    def to_bf16(a):
        # Integer inputs (token ids) pass as they are; what is already stored
        # in bf16 is left alone, so a model held in bf16 is never copied.
        floating = jnp.issubdtype(a.dtype, jnp.floating)
        return a.astype(jnp.bfloat16) if floating and a.dtype != jnp.bfloat16 else a

    def fwd_bf16(p, x):
        with scopes.cast_in():
            pb = jax.tree.map(to_bf16, p)
            xb = to_bf16(x)
        return fwd(pb, xb).astype(jnp.float32)

    return _observed(_jit(fwd_bf16, donate), exec_cfg, pol.name, n_shards)


def _observed(
    fn: Callable, exec_cfg: ExecConfig, dtype: str, n_shards: int
) -> Callable:
    """Compile-observer gate (observability.health): when an observer is
    installed (run/bench journal wiring), first calls per input shape are
    timed and reported as ``compile_event`` records. With no observer —
    every existing caller — the jitted callable is returned UNCHANGED:
    same identity, same ``.lower()``, zero overhead."""
    from .observability.health import get_compile_observer, observed_first_calls

    if get_compile_observer() is None:
        return fn
    return observed_first_calls(
        fn,
        site="build",
        entry=exec_cfg.key,
        dtype=dtype,
        n_shards=n_shards if exec_cfg.strategy != "single" else 1,
    )


def _jit(fn: Callable, donate: bool) -> Callable:
    # Donation argnums: 1 is the activation input x of (params, x). Params
    # are never donated — every caller reuses them across passes.
    return jax.jit(fn, donate_argnums=(1,) if donate else ())


def _build_forward_fp32(
    exec_cfg: ExecConfig,
    model_cfg=None,
    n_shards: int = 1,
    mesh: Optional[jax.sharding.Mesh] = None,
    plan=None,
    donate: bool = False,
    compute_dtype=None,
) -> Callable:
    need = n_shards if exec_cfg.strategy != "single" else 1
    if mesh is None and jax.device_count() < need:
        raise ValueError(
            f"config {exec_cfg.key!r} with {n_shards} shards needs {need} devices, "
            f"have {jax.device_count()} (use XLA_FLAGS=--xla_force_host_platform_"
            f"device_count=N on CPU to fake a mesh)"
        )

    if exec_cfg.model in LANGUAGE_MODELS:
        model = language_model(exec_cfg)
        if exec_cfg.strategy != "single":
            raise ValueError(f"strategy {exec_cfg.strategy!r} not supported for {exec_cfg.model}")
        model_cfg = model_cfg or model.SMALL
        return _jit(lambda p, ids: model.forward(p, ids, model_cfg), donate)

    if exec_cfg.model == "alexnet_full":
        from .models.alexnet_full import ALEXNET, forward_alexnet

        model_cfg = model_cfg or ALEXNET
        if exec_cfg.strategy == "single":
            if exec_cfg.tier == "pallas":
                from .ops.pallas_model import forward_alexnet_pallas

                # Resolve lowering variants NOW: each build_forward call
                # re-reads the env, so the A/B workflow is build-per-variant
                # instead of the round-3 process-per-variant footgun. A
                # TunePlan overlays per-layer winners (env still wins).
                kv = _resolve_variants(plan)
                return _jit(
                    lambda p, x: forward_alexnet_pallas(p, x, model_cfg, variants=kv),
                    donate,
                )
            return _jit(lambda p, x: forward_alexnet(p, x, model_cfg), donate)
        if exec_cfg.strategy in ("halo", "staged_halo"):
            from .models.alexnet_full import fc_head
            from .parallel.sharded import build_sharded_forward

            spatial = build_sharded_forward(
                model_cfg,
                n_shards,
                mesh=mesh,
                tier=exec_cfg.tier,
                staged=(exec_cfg.strategy == "staged_halo"),
                plan=plan,
            )
            # Row-sharded feature extractor; FC head on the gathered features
            # (replicated — the 6x6x256 activations are tiny next to conv1's).
            return jax.jit(lambda p, x: fc_head(p, spatial(p, x), model_cfg))
        raise ValueError(f"strategy {exec_cfg.strategy!r} not supported for alexnet_full")

    model_cfg = model_cfg or BLOCKS12
    if exec_cfg.strategy == "single":
        if exec_cfg.tier == "pallas":
            from .ops.pallas_model import _chain_variant, forward_blocks12_pallas

            kv = _resolve_variants(plan)  # eager: see alexnet_full branch
            ch = _chain_variant()
            return _jit(
                lambda p, x: forward_blocks12_pallas(
                    p, x, model_cfg, variants=kv, chain=ch
                ),
                donate,
            )
        return _jit(lambda p, x: forward_blocks12(p, x, model_cfg), donate)

    if exec_cfg.strategy == "replicated":
        from .parallel.replicated import build_replicated_forward

        return build_replicated_forward(model_cfg, n_shards, mesh=mesh)

    if exec_cfg.strategy in ("halo", "staged_halo"):
        from .parallel.sharded import build_sharded_forward

        return build_sharded_forward(
            model_cfg,
            n_shards,
            mesh=mesh,
            tier=exec_cfg.tier,
            staged=(exec_cfg.strategy == "staged_halo"),
            plan=plan,
            compute_dtype=compute_dtype,
        )

    if exec_cfg.strategy == "tp":
        from .parallel.tensor_parallel import build_tp_forward

        return build_tp_forward(model_cfg, n_shards, mesh=mesh)

    raise ValueError(f"unknown strategy {exec_cfg.strategy!r}")
