"""Expert layer, in a model only SOME of whose layers hold experts: rows a
held expert gets in a decode step, mean over the window's steps: the step's
`moe_pairs` (on its `serving.decode` span) over held experts
(`n_routed_experts`) times expert layers, counted from the pattern's letters
(`hybrid_override_pattern`). `moe_rows_per_expert` reads the same where the
expert layers follow `first_k_dense_replace` dense ones."""


def read(ctx):
    pairs = [s["attrs"]["moe_pairs"] for s in ctx.named("serving.decode",
                                                        batch_level=True)
             if "moe_pairs" in s["attrs"]]
    cfg = ctx.cell.config
    if not pairs or "hybrid_override_pattern" not in cfg:
        return None
    slots = cfg["n_routed_experts"] * cfg["hybrid_override_pattern"].count("E")
    return sum(pairs) / len(pairs) / slots
