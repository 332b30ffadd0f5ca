"""Kernels, serving a model whose layers are each a state-space mixer, an
expert layer or an attention: the least bytes a decode step must move (the
family's `decode_step_min_bytes(config, state_rows, live_full,
experts_touched)`: every step's matrices and the head once; each held expert
that got a row once; each row's state and convolution inputs read once and
written once a state layer; the keys and values its rows hold once an
attention layer) over the chip's HBM bandwidth, over that step's device time
in the traced slice; median over the traced steps, %: the share of the WHOLE
step. Bandwidth-bound. The counts are the step's own (`state_rows`,
`live_full`, `moe_experts_touched` on its `serving.decode` span; the last no
more than the held experts of all expert layers, counted from the pattern),
so the share is a lower bound over measured time: it cannot pass 100."""
from chipbench.harness import context


def read(ctx):
    least_bytes = getattr(ctx.family, "decode_step_min_bytes", None)
    cfg = ctx.cell.config
    steps = [(s["attrs"], d) for s, d in ctx.steps_in_trace(
        "serving.decode", batch_level=True)
        if all(k in s["attrs"] for k in ("state_rows", "live_full",
                                         "moe_experts_touched"))]
    if least_bytes is None or not steps \
            or "hybrid_override_pattern" not in cfg:
        return None
    held = cfg["n_routed_experts"] * cfg["hybrid_override_pattern"].count("E")
    return context.median([
        100.0 * least_bytes(cfg, a["state_rows"], a["live_full"],
                            min(a["moe_experts_touched"], held))
        / ctx.peaks["hbm_bytes_per_s"] / device_s for a, device_s in steps])
