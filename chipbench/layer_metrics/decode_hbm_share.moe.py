"""Kernels, serving a sparse-expert model: the least bytes a decode step must
read (the family's `decode_step_min_bytes(config, live_tokens,
experts_touched)`: what every step reads, each held expert that got a row
once, the cached rows of the live tokens once) over the chip's HBM
bandwidth, over that step's device time in the traced slice; median over the
traced steps, %. `experts_touched` is the step's own (`moe_experts_touched`
on its `serving.decode` span), so an expert no row chose is not counted and
the share is a lower bound over measured time: it cannot pass 100."""
from chipbench.harness import context

MATCH_US = 5000      # a step's per-request spans start within this of it


def live_tokens(spans, step):
    """Tokens held by the sequences a decode step advanced: the engine copies
    the step's span once per request, each with the position it wrote."""
    return sum(s["attrs"]["position"] for s in spans
               if s["name"] == "serving.decode"
               and "position" in s.get("attrs", {})
               and abs(s["ts"] - step["ts"]) <= MATCH_US)


def read(ctx):
    least_bytes = getattr(ctx.family, "decode_step_min_bytes", None)
    steps = [(s, d) for s, d in ctx.steps_in_trace("serving.decode",
                                                   batch_level=True)
             if "moe_experts_touched" in s["attrs"]]
    if least_bytes is None or not steps:
        return None
    shares = []
    for span, device_s in steps:
        live = live_tokens(ctx.spans, span)
        if not live:
            continue
        least_s = least_bytes(ctx.cell.config, live,
                              span["attrs"]["moe_experts_touched"]) \
            / ctx.peaks["hbm_bytes_per_s"]
        shares.append(100.0 * least_s / device_s)
    return context.median(shares)
