"""The share of their roofline of the routed experts held here (scope
``moe.experts``: the gather, the grouped products, the weighted scatter-add),
every MoE layer of the step together, in percent. The operations follow the
pairs the plain REFERENCE routed to the held experts on the sampled sequences,
scaled to the step's tokens (the driver's ``check.ref_pairs_held`` and
``check.ref_tokens``; where the check has not run, the expected share of a
uniform router); the share the program's own routing counters give
(``moe.pairs_held / moe.pairs_all``) is logged beside it. Bytes: every held
expert read once, a row gathered and a float32 row added per pair
(``shapes/mla_moe.py``). See ``scope_roofline.pct``."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    tokens = batch * cfg["seq_len"]
    ref_pairs, ref_tokens = ctx.counters.get("check.ref_pairs_held"), ctx.counters.get("check.ref_tokens")
    if ref_pairs and ref_tokens:
        pairs = ref_pairs * tokens / ref_tokens
    else:
        pairs = shapes.expected_pairs_per_step(cfg, batch)
    program = ctx.adapter.registry_summary().summary()
    if program.get("moe.pairs_all"):
        ctx.log(
            f"pairs to the held experts: {pairs:.0f} a step by the reference's routing "
            f"({pairs / (shapes.n_moe_layers(cfg) * tokens * cfg['num_experts_per_tok']):.4f} of all); "
            f"the program's counters: {program['moe.pairs_held'] / program['moe.pairs_all']:.4f} of all"
        )
    return shapes.experts_flops(cfg, pairs), shapes.experts_bytes(cfg, pairs)


def read(ctx):
    return scope_roofline.pct(ctx, "moe.experts", _work)
