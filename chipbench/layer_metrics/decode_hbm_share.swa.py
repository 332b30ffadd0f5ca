"""Kernels, serving a model of window and full layers: the least bytes a
decode step must read (the family's `decode_step_min_bytes(config, live_full,
live_window, experts_touched)`: every step's weights and head once, each
held expert that got a row once, the keys and values its rows hold by kind of
layer once) over the chip's HBM bandwidth, over that step's device time in
the traced slice; median over the traced steps, %. Bandwidth-bound. The
counts are the step's own (`live_full`, `live_window`,
`moe_experts_touched` on its `serving.decode` span; the last no more than the
held experts of all expert layers), so the share is a lower bound over
measured time: it cannot pass 100."""
from chipbench.harness import context


def read(ctx):
    least_bytes = getattr(ctx.family, "decode_step_min_bytes", None)
    steps = [(s["attrs"], d) for s, d in ctx.steps_in_trace(
        "serving.decode", batch_level=True)
        if "live_full" in s["attrs"] and "moe_experts_touched" in s["attrs"]]
    if least_bytes is None or not steps:
        return None
    cfg = ctx.cell.config
    held = cfg["num_experts"] * (cfg["num_hidden_layers"]
                                 - cfg["num_dense_layers"])
    return context.median([
        100.0 * least_bytes(cfg, a["live_full"], a["live_window"],
                            min(a["moe_experts_touched"], held))
        / ctx.peaks["hbm_bytes_per_s"] / device_s for a, device_s in steps])
