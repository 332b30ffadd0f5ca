"""Serving loop: what follows a decode step before the next pass, per
iteration: appending the tokens and copying the step's span to each request
(`serving.decode.append`) plus the metrics, per-token records and eviction
(`serving.account`); median, ms."""
from chipbench.harness import context, spans


def read(ctx):
    both = []
    for tree in spans.iterations(ctx.spans):
        parts = [spans.one(tree, "serving.decode.append"),
                 spans.one(tree, "serving.account")]
        if None not in parts:
            both.append(sum(p["dur"] for p in parts) / 1e3)
    return context.median(both)
