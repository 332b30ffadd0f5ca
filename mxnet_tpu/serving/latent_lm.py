"""Paged-cache adapter for the latent-attention, dropless sparse-expert
family (models/latent_moe.py): what `TransformerLM` is for the
functional transformer. `serve((params, LatentMoEConfig), ...)` resolves
to it (server `_resolve_model`), and `Engine`, the scheduler, the block
pool and the serving loop drive it as they drive the old family: on the
default gather path, whole-prompt prefill then one decode step a token.

Its pool is the latent layout (`kv_cache.CacheSpec.latent_dim`): one
array, donated to and returned first by both step programs, which keep
the names the old family's have (`jit_serving_prefill`,
`jit_serving_decode`). Beside its results each step returns the rows of
real tokens it sent to each held expert in each expert layer; the engine
reads them back with the tokens and hands them to `note_step`.
"""
from __future__ import annotations

import jax
import numpy as np

from .. import telemetry
from ..models import latent_moe
from .engine import _step_jit
from .kv_cache import CacheSpec


class LatentMoELM:
    """params dict + `LatentMoEConfig` (models/latent_moe.py)."""

    uses_cache = True

    _PREFILL_ARGS = ("params", "kv_pool", "tokens", "length", "table_row")
    _DECODE_ARGS = ("params", "kv_pool", "tokens", "positions", "tables")

    def __init__(self, params, cfg):
        self.params = params
        self.cfg = cfg
        self.vocab = cfg.vocab
        self.max_len = cfg.max_len
        #: rows of real tokens sent so far to each (expert layer, held
        #: expert), prefill and decode together; the serving metrics
        #: publish it as `serving.moe.expert_tokens`
        self.expert_rows = np.zeros((cfg.n_moe_layers, cfg.n_held), np.int64)
        self._prefill_jit = self._decode_jit = None

    def place(self, device):
        """Commit the parameters to one device (a one-chip replica's
        window)."""
        self.params = jax.device_put(self.params, device)

    def cache_spec(self):
        return CacheSpec(self.cfg.n_layers, self.params["embed"].dtype,
                         latent_dim=self.cfg.latent_dim)

    def bind(self, block_size):
        cfg = self.cfg
        instrument = telemetry.introspect.instrument
        self._prefill_jit = instrument(_step_jit(
            "serving_prefill",
            lambda p, kv, t, ln, tb: latent_moe.prefill(p, kv, t, ln, tb,
                                                        cfg),
            self._PREFILL_ARGS),
            site="serving.prefill", phase="prefill",
            argnames=self._PREFILL_ARGS, variant="prefill_latent")
        self._decode_jit = instrument(_step_jit(
            "serving_decode",
            lambda p, kv, t, pos, tb: latent_moe.decode(p, kv, t, pos, tb,
                                                        cfg),
            self._DECODE_ARGS),
            site="serving.decode", phase="decode",
            argnames=self._DECODE_ARGS, variant="decode_latent")

    def prefill(self, kv, tokens, length, table_row):
        return self._prefill_jit(self.params, kv, tokens, length, table_row)

    def decode(self, kv, tokens, positions, tables):
        return self._decode_jit(self.params, kv, tokens, positions, tables)

    def note_step(self, counts):
        """One step's rows per (expert layer, held expert), on the host:
        add them up and say what the step's span should carry."""
        self.expert_rows += counts
        return {"moe_pairs": int(counts.sum()),
                "moe_experts_touched": int(np.count_nonzero(counts))}
