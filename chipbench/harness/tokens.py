"""The window's served tokens, one `serving.token` span each on its request's
trace (ISSUE 40): the span ends when the host held the token, when a client
could read it, and starts at the request's token before it, so its `dur` is
the gap that client saw; `prefills` says how many prefill programs (of any
request) the engine ran in between. A request's first token spans its
prefill, carries `first` and is no gap. The whole window, not the traced
slice. A program that records no such span (before ISSUE 40) yields nothing,
and the readers built on this return None."""
from chipbench.harness import spans, util

NAME = "serving.token"


def gaps(ctx):
    """[(gap in ms, prefills)] of every token the window served after its
    request's first. `ctx.spans` holds what started in the window; a gap
    that the window's end cuts is left out like one that its start cuts."""
    end = (ctx.record["t0"] + ctx.record["window_s"]) * 1e6
    return [(s["dur"] / 1e3, s["attrs"].get("prefills", 0))
            for s in ctx.spans
            if s["name"] == NAME and "first" not in s["attrs"]
            and spans.end_us(s) <= end]


def percentile_ms(ctx, q):
    found = [g for g, _ in gaps(ctx)]
    return util.percentile(found, q) if found else None
