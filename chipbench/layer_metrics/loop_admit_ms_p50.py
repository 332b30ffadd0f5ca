"""Scheduler and admission: self time of `serving.admit` (its duration less
the prefills and prefix lookups that run inside it), over the iterations
that went on to decode; median, ms."""
from chipbench.harness import context, spans


def read(ctx):
    own = []
    for tree in spans.iterations(ctx.spans):
        admit = spans.one(tree, "serving.admit")
        if admit is not None and "serving.decode" in tree:
            under = [s for found in tree.values() for s in found]
            own.append(spans.self_us(admit, under) / 1e3)
    return context.median(own)
