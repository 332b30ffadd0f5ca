"""How late the open-loop generator sent: actual send - due time, p90, ms."""
from chipbench.harness import util


def read(ctx):
    late = [1e3 * (r["sent"] - r["due"]) for r in ctx.record.get("requests", [])]
    return util.percentile(late, 90) if late else None
