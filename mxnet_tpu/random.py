"""Global random state.

Parity: reference seeds a per-device stateful RandomGenerator
(`src/common/random_generator.h`, python `mxnet/random.py`). JAX is
functional, so we keep one process-global PRNG key that ops split from.

Inside a jit trace (hybridized CachedOp / Module bind) a *traced* key is
threaded through the compiled function as an explicit argument so stochastic
ops (dropout, samplers) stay correct across calls without retracing — the
trace-local key + fold_in counter below implements that seam.
"""
from __future__ import annotations

import threading

import numpy as np
import jax


class _RandomState(threading.local):
    """Per-thread RNG state. ``key`` is created LAZILY on first use: building
    a PRNGKey forces JAX backend initialization, and importing the framework
    must do zero device work (round-1 lesson — an import-time key made bench
    die and the multichip dryrun hang under the TPU plugin)."""

    def __init__(self):
        super().__init__()
        self.key = None  # materialized by _current_key() on first use
        self.seed_value = None  # pending integer seed, if seed() ran first
        self.trace_key = None  # set while tracing a CachedOp
        self.trace_counter = 0


_STATE = _RandomState()


def _current_key():
    if _STATE.key is None:
        seed_val = _STATE.seed_value if _STATE.seed_value is not None \
            else np.random.randint(0, 2**31 - 1)
        key = jax.random.PRNGKey(seed_val)
        # under omnistaging EVERY op inside an active jit trace is staged,
        # so this key is a tracer when first use happens mid-trace (e.g. a
        # functionalized eval-mode net drawing its lazy key) — caching it
        # would poison the thread's eager stream. Keep the pending seed
        # instead; the eager key materializes on the next eager call.
        if isinstance(key, jax.core.Tracer):
            _STATE.seed_value = seed_val
            return key
        _STATE.key = key
    return _STATE.key


def seed(seed_state, ctx="all"):
    """Seed the global generator (parity: mx.random.seed). Device-lazy: only
    records the integer; the PRNGKey materializes on first sampling call."""
    _STATE.seed_value = int(seed_state) & 0x7FFFFFFF
    _STATE.key = None
    _STATE.trace_counter = 0
    np.random.seed(int(seed_state) & 0xFFFFFFFF)


def next_key():
    """Return a fresh PRNG key (concrete eagerly, traced inside a jit trace)."""
    if _STATE.trace_key is not None:
        _STATE.trace_counter += 1
        return jax.random.fold_in(_STATE.trace_key, _STATE.trace_counter)
    key = _current_key()
    new, sub = jax.random.split(key)
    if isinstance(new, jax.core.Tracer):
        # inside someone else's jit trace with no trace_key_scope
        # installed (e.g. a functionalized eval-mode net being traced):
        # every op is staged there, so the split came back traced and
        # storing it would poison the NEXT trace (UnexpectedTracerError).
        # Derive per-call keys off the eager key via the counter instead
        # — distinct per call, eager stream untouched.
        _STATE.trace_counter += 1
        return jax.random.fold_in(key, _STATE.trace_counter)
    _STATE.key = new
    return sub


class trace_key_scope:
    """Context manager installing a traced base key during jit tracing."""

    def __init__(self, key):
        self._key = key
        self._saved = None

    def __enter__(self):
        self._saved = (_STATE.trace_key, _STATE.trace_counter)
        _STATE.trace_key = self._key
        _STATE.trace_counter = 0
        return self

    def __exit__(self, *exc):
        _STATE.trace_key, _STATE.trace_counter = self._saved


# Imperative sampling API (mx.random.*) is populated by mxnet_tpu.ndarray at
# import time (uniform/normal/randint/...) — see ndarray/__init__.py.


def get_state():
    """Snapshot the global PRNG key as a host array (for checkpoint/resume —
    the reference's RandomGenerator state save). An owned copy — asarray
    on a jax CPU array may alias device memory."""
    import numpy as _np
    return _np.array(_current_key())


def set_state(key_data):
    """Restore a key snapshot taken by get_state()."""
    _STATE.key = jax.numpy.asarray(key_data)
    _STATE.seed_value = None
