"""Engine: host duration of a prefill call (`serving.prefill` spans), median,
ms."""
from chipbench.harness import context


def read(ctx):
    return context.median(ctx.span_ms("serving.prefill"))
