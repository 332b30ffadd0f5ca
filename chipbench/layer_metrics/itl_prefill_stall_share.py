"""Serving loop: of the time the window's rows spent between tokens, the share
that other requests' prefills took, %: over the gaps in which the engine ran
a prefill (`prefills` >= 1 on the token's span, `harness/tokens.py`), what
each exceeds the median gap by, over the sum of all gaps. The whole-window,
row-weighted twin of `prefill_device_share`: a prefill stalls every row that
is decoding, for as long as it runs."""
import statistics

from chipbench.harness import tokens


def read(ctx):
    found = tokens.gaps(ctx)
    if not found:
        return None
    median = statistics.median(g for g, _ in found)
    stalled = sum(g - median for g, prefills in found if prefills >= 1)
    return 100.0 * stalled / sum(g for g, _ in found)
