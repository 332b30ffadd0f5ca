"""Serving loop: the 99th percentile of the gaps between two tokens of one
request, over every token the window served (`harness/tokens.py`), ms. Where
whole prompts are prefilled between decode steps, more than one gap in a
hundred holds a prefill, so this is the size of a stall: a step plus the
prefills that ran before its tokens were read."""
from chipbench.harness import tokens


def read(ctx):
    return tokens.percentile_ms(ctx, 99)
