"""Engine step: keys the decode steps' attention loops visit over the keys
their rows really hold, summed over the window's steps. Every row of a
step's batch bucket walks as far as the longest live sequence (round the
ring at most on a window layer), so short rows pay for the longest in one
queue: the family's `decode_keys_walked(config, batch, live_max)` over its
`decode_keys_live(config, live_full, live_window)`, from the step's own
`serving.decode` span. 1.0 where every row is as long as the longest."""


def read(ctx):
    walked = getattr(ctx.family, "decode_keys_walked", None)
    steps = [s["attrs"] for s in ctx.named("serving.decode", batch_level=True)
             if "live_full" in s["attrs"]]
    if walked is None or not steps:
        return None
    cfg = ctx.cell.config
    live = sum(ctx.family.decode_keys_live(cfg, a["live_full"],
                                           a["live_window"]) for a in steps)
    return sum(walked(cfg, a["batch"], a["live_max"]) for a in steps) / live
