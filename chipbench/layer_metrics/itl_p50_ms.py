"""Serving loop: the median gap between two tokens of one request, over every
token the window served (`harness/tokens.py`): the step interval as a client
sees it, ms. A decode-step change should move it; a prefill change should
not, while fewer than half the gaps hold a prefill."""
from chipbench.harness import tokens


def read(ctx):
    return tokens.percentile_ms(ctx, 50)
