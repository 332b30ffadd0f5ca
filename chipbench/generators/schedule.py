"""Sizes and arrivals for a traffic mix. Every seed gets the same set of sizes
and the same set of gaps between arrivals, in another order: the values are
the evenly spaced quantiles of the mix's stated distributions (so the work of
a window does not swing with the draw), shuffled once by the mix's own
`schedule_seed`, then rotated by the run's seed (so neighbours stay
neighbours and the queue a window builds does not swing with the order)."""
import math
import random
import statistics

_NORMAL = statistics.NormalDist()


def quantiles(dist, n):
    """n evenly spaced quantiles ((i + 0.5) / n) of a distribution given as
    data: {"kind": "lognormal", "median", "sigma", "min", "max"},
    {"kind": "uniform", "min", "max"} or {"kind": "exponential", "mean"}."""
    ps = [(i + 0.5) / n for i in range(n)]
    kind = dist["kind"]
    if kind == "lognormal":
        xs = [dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(p))
              for p in ps]
    elif kind == "uniform":
        xs = [dist["min"] + p * (dist["max"] - dist["min"]) for p in ps]
    elif kind == "exponential":
        xs = [-dist["mean"] * math.log(1.0 - p) for p in ps]
    else:
        raise ValueError("unknown distribution kind %r" % kind)
    if "min" in dist and kind != "uniform":
        xs = [min(max(x, dist["min"]), dist["max"]) for x in xs]
    return xs


def ordered(values, schedule_seed, seed, salt):
    """`values` shuffled by the mix's `schedule_seed` (and `salt`, so that
    prompt and output lengths are not sorted alike), then rotated by the run's
    seed."""
    values = list(values)
    random.Random("%s/%s" % (schedule_seed, salt)).shuffle(values)
    k = seed % len(values)
    return values[k:] + values[:k]


def lengths(dist, n, schedule_seed, seed, salt):
    return [int(round(x)) for x in
            ordered(quantiles(dist, n), schedule_seed, seed, salt)]


def arrivals(rate, seconds, schedule_seed, seed):
    """Poisson-like due times in [0, seconds): round(rate * seconds) arrivals
    whose gaps are the quantiles of Exp(rate), scaled to fill the window."""
    n = max(1, int(round(rate * seconds)))
    gaps = ordered(quantiles({"kind": "exponential", "mean": 1.0}, n),
                   schedule_seed, seed, "gaps")
    scale = seconds / (sum(gaps) + 1.0)
    due, t = [], 0.0
    for g in gaps:
        t += g * scale
        due.append(t)
    return due


def prompt_tokens(n_tokens, vocab, rng):
    """A prompt of its own: token ids drawn from the run's seed."""
    return [int(t) for t in rng.integers(1, vocab, size=n_tokens)]
