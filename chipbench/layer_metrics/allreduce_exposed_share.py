"""Collectives: device time in collective operations during which no compute
ran on that device (`trace/reduce.py`, `collective_s`) over the device time of
the programs run in the traced slice, %."""


def read(ctx):
    if ctx.trace is None or not ctx.trace["modules"]:
        return None
    return 100.0 * ctx.trace["collective_s"] \
        / sum(d for _, d, _ in ctx.trace["modules"])
