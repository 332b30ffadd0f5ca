"""Serving loop: the host's turn between two decode steps, from the moment
the first step's token is on the host (end of `serving.decode.readback`) to
the moment the next step is handed to the device (end of
`serving.decode.dispatch`), median, ms. Over consecutive loop iterations
(`it`) that both decoded, the second without a prefill, whose device time
would count as the host's."""
from chipbench.harness import context, spans


def read(ctx):
    turns, last = [], None
    for tree in spans.iterations(ctx.spans):
        back = spans.one(tree, "serving.decode.readback")
        sent = spans.one(tree, "serving.decode.dispatch")
        it = tree[spans.LOOP][0].get("attrs", {}).get("it")
        if last is not None and sent is not None and it == last[0] + 1 \
                and "serving.prefill" not in tree:
            turns.append((spans.end_us(sent) - last[1]) / 1e3)
        last = (it, spans.end_us(back)) if back is not None else None
    return context.median(turns)
