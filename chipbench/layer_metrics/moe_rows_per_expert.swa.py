"""Expert layer, under this family's published key names: rows a held expert
gets in a decode step, mean over the window's steps: the step's `moe_pairs`
(on its `serving.decode` span) over held experts (`num_experts`) times expert
layers (`num_hidden_layers` - `num_dense_layers`). `moe_rows_per_expert` reads
the same under the latent family's key names."""


def read(ctx):
    pairs = [s["attrs"]["moe_pairs"] for s in ctx.named("serving.decode",
                                                        batch_level=True)
             if "moe_pairs" in s["attrs"]]
    cfg = ctx.cell.config
    if not pairs or "num_dense_layers" not in cfg:
        return None
    slots = cfg["num_experts"] * (cfg["num_hidden_layers"]
                                  - cfg["num_dense_layers"])
    return sum(pairs) / len(pairs) / slots
