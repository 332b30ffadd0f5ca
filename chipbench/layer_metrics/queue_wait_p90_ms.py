"""Scheduler: time from submit to admission (t_admit - t_submit), p90, ms."""
from chipbench.harness import util


def read(ctx):
    waits = [1e3 * (r["t_admit"] - r["t_submit"])
             for r in ctx.record.get("requests", [])
             if r["t_admit"] is not None]
    return util.percentile(waits, 90) if waits else None
