"""Engine: host duration of a decode step (`serving.decode`, which closes
after the token is read back), median, ms."""
from chipbench.harness import context


def read(ctx):
    return context.median(ctx.span_ms("serving.decode", batch_level=True))
