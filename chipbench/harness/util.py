"""Small arithmetic the whole harness shares: percentiles, the spread rule of
the contract, seeds, and the record of one number compared with its limit."""
import importlib.util
import math
import os
import statistics


def percentile(values, q):
    """q in [0, 100], linear interpolation between order statistics (numpy's
    default), on a plain list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def percentiles(values, qs=(50, 90, 95)):
    """{q: percentile} for an earlier line; None where there is no sample."""
    return {q: percentile(values, q) for q in qs} if values else None


def tail(values, n_missing, q):
    """Percentile of a latency over all requests: one that failed, was shed or
    never got there counts as the largest value seen."""
    if not values:
        raise ValueError("no request produced a value")
    return percentile(list(values) + [max(values)] * n_missing, q)


def spread(values):
    """The contract's spread: distance between the first and third quartile
    (statistics.quantiles, n=4) as a share of the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def prng_key(seed):
    """A jax key from any whole seed up to a little over 2**31."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def compared(name, value, limit, ok=None):
    """One number `correct` compares, beside its limit (value <= limit)."""
    value = float(value)
    if ok is None:
        ok = math.isfinite(value) and value <= limit
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok)}


def note(name, value):
    """A number printed with the comparisons that decides nothing."""
    return {"name": name, "value": value, "limit": None, "ok": True}


def load_file_module(path, name):
    """Import one .py file by path (a per-layer metric's name may hold a dot,
    so it is not importable by name)."""
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
