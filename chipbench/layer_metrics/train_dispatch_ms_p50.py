"""Trainer: host time of one `step(x, y)` call until it returns, before any
wait for the device, median, ms. The benchmark's own span."""
from chipbench.harness import context


def read(ctx):
    return context.median([ms for _, ms in ctx.record.get("dispatch", [])])
