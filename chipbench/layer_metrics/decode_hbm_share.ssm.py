"""Kernels, serving a model with a recurrent state beside its keys and
values: the least bytes a decode step must move (the family's
`decode_step_min_bytes(config, state_rows, live_full)`: the weights and the
head once; each row's state and convolution inputs read once and written
once a layer; the keys and values its rows hold once a layer) over the
chip's HBM bandwidth, over that step's device time in the traced slice;
median over the traced steps, %. Bandwidth-bound. The counts are the step's
own (`state_rows`, `live_full` on its `serving.decode` span), so the share is
a lower bound over measured time: it cannot pass 100."""
from chipbench.harness import context


def read(ctx):
    least_bytes = getattr(ctx.family, "decode_step_min_bytes", None)
    steps = [(s["attrs"], d) for s, d in ctx.steps_in_trace(
        "serving.decode", batch_level=True)
        if "state_rows" in s["attrs"] and "live_full" in s["attrs"]]
    if least_bytes is None or not steps:
        return None
    return context.median([
        100.0 * least_bytes(ctx.cell.config, a["state_rows"], a["live_full"])
        / ctx.peaks["hbm_bytes_per_s"] / device_s for a, device_s in steps])
