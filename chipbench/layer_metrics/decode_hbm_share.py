"""Kernels, serving: the least bytes a decode step must read (weights and head
once, K and V of the live tokens once: the family's `decode_step_min_bytes`,
from shapes) over the chip's HBM bandwidth, over that step's device time in
the traced slice; median over the traced steps, %. Bandwidth-bound lower
bound over measured time."""
from chipbench.harness import context


MATCH_US = 5000      # a step's per-request spans start within this of it


def read(ctx):
    least_bytes = getattr(ctx.family, "decode_step_min_bytes", None)
    steps = ctx.steps_in_trace("serving.decode", batch_level=True)
    if least_bytes is None or not steps:
        return None
    # the engine copies a decode step's span once per request it advanced,
    # each with the position it wrote; a few microseconds before its own
    rows = sorted((s["ts"], s["attrs"]["position"]) for s in ctx.spans
                  if s["name"] == "serving.decode"
                  and "position" in s.get("attrs", {}))
    shares = []
    for span, device_s in steps:
        live = sum(pos for ts, pos in rows if abs(ts - span["ts"]) <= MATCH_US)
        if not live:
            continue
        least_s = least_bytes(ctx.cell.config, live) / ctx.peaks["hbm_bytes_per_s"]
        shares.append(100.0 * least_s / device_s)
    return context.median(shares)
