"""Expert layer: rows a held expert gets in a decode step, mean over the
window's steps: the step's `moe_pairs` (on its `serving.decode` span: real
rows sent to held experts, summed over expert layers) over held experts
times expert layers. 1.0 where a full batch routes evenly at the
deployment's load (batch x experts per token / experts of the deployment)."""


def read(ctx):
    pairs = [s["attrs"]["moe_pairs"] for s in ctx.named("serving.decode",
                                                        batch_level=True)
             if "moe_pairs" in s["attrs"]]
    cfg = ctx.cell.config
    if not pairs or "n_routed_experts" not in cfg:
        return None
    slots = cfg["n_routed_experts"] * (cfg["num_hidden_layers"]
                                       - cfg["first_k_dense_replace"])
    return sum(pairs) / len(pairs) / slots
