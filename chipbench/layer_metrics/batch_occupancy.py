"""Scheduler: sequences per decode step over `max_batch`, mean over the
window's steps, %. From the `batch` attribute of `serving.decode` spans."""


def read(ctx):
    sizes = [s["attrs"]["batch"] for s in ctx.spans
             if s["name"] == "serving.decode" and "batch" in s.get("attrs", {})]
    if not sizes:
        return None
    return 100.0 * sum(sizes) / len(sizes) / ctx.counters["max_batch"]
