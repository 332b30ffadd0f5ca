"""Engine: building a decode step's inputs, the three numpy arrays and their
uploads (`serving.decode.build`), median, ms."""
from chipbench.harness import context


def read(ctx):
    return context.median(ctx.span_ms("serving.decode.build"))
