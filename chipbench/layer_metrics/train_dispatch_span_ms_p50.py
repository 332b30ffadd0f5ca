"""Trainer: `TrainStep.__call__` from its first line to its return, the
program's own `train.dispatch` span (inside the stopwatch of
`train_dispatch_ms_p50`, so no larger), median over the window, ms."""
from chipbench.harness import context


def read(ctx):
    return context.median(ctx.span_ms("train.dispatch"))
