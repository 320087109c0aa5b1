"""The names the program gives its layers where they run.

Every production forward wraps each layer in ``jax.named_scope`` under the
names below, so the compiled program's instructions carry them in
``metadata={op_name=...}`` and a device trace can be split by layer after any
refactor of the fusions (the instruction names ``fusion.<n>`` are the
compiler's and change with it). Scopes are metadata only: they change nothing
the compiler builds.

One vocabulary, defined here and nowhere else: ``Blocks12Config.layer_chain``
and ``AlexNetConfig.layer_chain`` take their names from it, and so do the
sentinel taps (``observability.stages.SENTINEL_STAGES``). ReLU belongs to its
convolution's scope, as the taps bound the stages.
"""

from __future__ import annotations

import jax

BLOCKS12_LAYERS = ("conv1", "pool1", "conv2", "pool2", "lrn2")
ALEXNET_TAIL_LAYERS = ("conv3", "conv4", "conv5", "pool5")
FC_LAYERS = ("fc6", "fc7", "fc8")
# The latent-attention mixture-of-experts decoder (``models.mla_moe``), in the
# order a MoE layer runs them: ``mla.proj`` holds the five projections with
# their norms and the rotary embedding, ``mla.attn`` scores, softmax and
# values; ``moe.route`` the router matmul, the group-limited top-k, the sort
# and the index arithmetic; ``moe.experts`` the gather, the grouped products,
# their results laid out for the way back and the combine (``ops.moe_combine``'s
# kernel of row DMAs, or one gather and multiply-add per place where a row is
# too narrow to pay for a DMA); ``head`` the final norm and the output head.
# What stands inside the two ``moe.*`` scopes is named by ``PHASES`` below.
MLA_MOE_LAYERS = (
    "embed", "mla.proj", "mla.attn", "dense_mlp",
    "moe.route", "moe.experts", "moe.shared", "head",
)
# The hybrid linear-attention mixture-of-experts decoder (``models.kda_moe``);
# the MoE's and the ends' names are the ones above. ``gqa.proj``: the norm, the
# q/k/v/gate projections, the gate's sigmoid and product, the output
# projection; ``gqa.attn`` the flash kernel. ``kda.proj``: the norm, the q/k/v
# projections, both low-rank pairs, the write strength's projection, the output
# norm, gate and projection; ``kda.mix`` the short convolution, silu and l2norm
# of q, k and v (``ops.kda_mix``'s kernel, one call an array, where the shapes
# fit it; elementwise passes otherwise), the decay's softplus and the write
# strength's sigmoid (elementwise); ``kda.scan`` the chunked scan's kernel.
KDA_MOE_LAYERS = (
    "embed", "gqa.proj", "gqa.attn", "kda.proj", "kda.mix", "kda.scan",
    "moe.route", "moe.experts", "moe.shared", "head",
)
# The compressed-convolutional-attention mixture-of-experts decoder
# (``models.cca_moe``). ``cca.proj``: the norm, the q/k/v1/v2 projections into
# the latent, the output projection and the merge; ``cca.mix``: both causal
# convolutions (with the per-head products inside it), the q-k mean, the value
# shift, the q/k normalisation with ``tau``, the rotary embedding and its
# tables; ``cca.attn`` the flash kernel. ``moe.route`` here holds the norm, the
# carried state, the router's MLP, softmax and top-1 before the sort and the
# index arithmetic; ``moe.experts`` the merge after the weighted combine.
# ``layer_loop`` is the scan over the layers itself (the ``while``, its counter
# and the slicing of a layer's parameters out of the stack): every operation of
# a layer keeps its own scope inside it, and the innermost counts.
CCA_MOE_LAYERS = (
    "embed", "layer_loop", "cca.proj", "cca.mix", "cca.attn", "moe.route", "moe.experts", "head",
)
# The shortcut-connected mixture-of-experts decoder over latent attention
# (``models.scmoe_mla``): a layer is two sublayers of ``mla.proj``, ``mla.attn``
# and ``dense_mlp`` with the MoE as a branch beside them. ``moe.route`` holds
# the norm after the first attention, the softmax router over the experts and
# the identity experts, the top-k, then the sort; ``moe.experts`` the routed
# sum over the held pairs; ``moe.zero`` the identity experts' term (the chosen
# identities' weights summed, times the token's normed input) and its sum with
# the routed part: the branch as the layer's end takes it. ``dense_mlp`` also
# holds that last addition. ``layer_loop`` as above.
SCMOE_MLA_LAYERS = (
    "embed", "layer_loop", "mla.proj", "mla.attn", "dense_mlp", "moe.route", "moe.experts", "moe.zero", "head",
)
# The decoder-hybrid-decoder of state-space scans, differential attention and
# gated memory units (``models.sambay``), a dense model. ``mamba.proj``: the
# norm, the input projection, the low-rank projections of the step and of the
# input and output maps, the gate and the output projection; ``mamba.mix`` the
# short causal convolution with its bias, silu, the step's softplus and ``A``
# (elementwise); ``mamba.scan`` the selective scan's kernel. ``gmu``: a gated
# memory unit whole (norm, both products, the gate by the memory).
# ``diff.proj``: the norm, the q/k/v (or q) projection with its bias, the
# heads laid out for the kernel, ``lambda``, the difference of the two
# softmaxes' outputs, the per-head norm and the output projection;
# ``diff.attn_window`` the flash kernel of the windowed layers,
# ``diff.attn_full`` of the causal layer that keeps its keys and values and of
# the cross layers that read them. ``dense_mlp`` holds its norm and residual
# addition too. ``layer_loop`` as above, two loops.
SAMBAY_LAYERS = (
    "embed", "layer_loop", "mamba.proj", "mamba.mix", "mamba.scan", "gmu",
    "diff.proj", "diff.attn_window", "diff.attn_full", "dense_mlp", "head",
)
LAYERS = (
    BLOCKS12_LAYERS + ALEXNET_TAIL_LAYERS + FC_LAYERS + MLA_MOE_LAYERS
    + tuple(name for name in KDA_MOE_LAYERS if name not in MLA_MOE_LAYERS)
    + tuple(name for name in CCA_MOE_LAYERS if name not in MLA_MOE_LAYERS)
    + ("moe.zero",)
    + tuple(name for name in SAMBAY_LAYERS if name not in MLA_MOE_LAYERS + CCA_MOE_LAYERS)
)

# A second, nested level: the phases of a layer, which stand only inside that
# layer's scope (``moe.experts/experts.products/...``), as a halo exchange
# stands inside its layer's. ``moe_share._routed_experts`` and ``_dispatch``
# say what runs under each.
PHASE_LAYER = {
    "experts.gather": "moe.experts",  # a chunk's index arithmetic and the gather of its rows' tokens
    "experts.products": "moe.experts",  # the three grouped products, silu and the multiply
    "experts.layout": "moe.experts",  # the rows laid out for the way back, and the zero fills
    "experts.combine": "moe.experts",  # every token collects its own rows, weighted (and a model's merge after it)
    "route.score": "moe.route",  # the norm, the router, the scores, the choice, the weights
    "route.sort": "moe.route",  # the pairs sorted by expert, the counts, the padded rows
}
PHASES = tuple(PHASE_LAYER)

# Parameters and input to the compute type: the bf16 wrapper's casts and the
# int8w quantisation.
CAST_IN = "cast_in"
# The sharded paths: the pad (or replication) before the shard_map, the slice
# (or constraint) after it, and a layer's neighbour exchange, nested in that
# layer's scope as ``<layer>/halo.<layer>``.
SCATTER = "scatter"
GATHER = "gather"
HALO_PREFIX = "halo."


def _check(name: str) -> None:
    if name not in LAYERS:
        raise ValueError(f"{name!r} is not a layer name ({', '.join(LAYERS)})")


def layer(*names: str):
    """The scope of one layer, or of one kernel that covers several
    (``layer("conv1", "pool1")`` is ``conv1+pool1``: a fused block is never
    silently its convolution)."""
    for name in names:
        _check(name)
    return jax.named_scope("+".join(names))


def phase(name: str):
    """The scope of one phase of a layer, to be entered inside that layer's
    own scope (``PHASE_LAYER`` says which)."""
    if name not in PHASES:
        raise ValueError(f"{name!r} is not a phase name ({', '.join(PHASES)})")
    return jax.named_scope(name)


def halo(layer_name: str):
    """The exchange that fetches ``layer_name``'s neighbour rows or channels."""
    _check(layer_name)
    return jax.named_scope(HALO_PREFIX + layer_name)


def cast_in():
    return jax.named_scope(CAST_IN)


def scatter():
    return jax.named_scope(SCATTER)


def gather():
    return jax.named_scope(GATHER)
