"""Paged-cache adapter for the window-and-full attention, grouped-query,
dropless sparse-expert family (models/afmoe.py): what `TransformerLM` and
`LatentMoELM` are for theirs. `serve((params, AfmoeConfig), ...)` resolves
to it (server `_resolve_model`), and `Engine`, the scheduler, the block
pools and the serving loop drive it as they drive the other two: on the
default gather path, whole-prompt prefill then one decode step a token,
one step in flight.

Its cache is of two KINDS (`kv_cache.CacheSpec.layer_kinds`): the full
layers' planes keep every token, the window layers' a ring of the last
`window`; four arrays, donated to and returned first by both step
programs, which keep the names the other families' have
(`jit_serving_prefill`, `jit_serving_decode`). The views are the K/V
layout's own (`kv_cache.PromptView`, `LiveGatherView`), told the spec.
Beside its results each step returns the rows of real tokens it sent to
each held expert in each expert layer, as the latent family's does.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..models import afmoe
from .engine import _program, carried_tokens, carry_of
from .kv_cache import CacheSpec, LiveGatherView, PromptView
from .latent_lm import LatentMoELM


def prefill(params, pools, tokens, length, table_row, cfg, spec):
    """One padded prompt (S,) of true `length`: writes every layer's
    keys and values into its kind's columns of `table_row` (a window
    layer keeps what its window still sees) and returns (*pools, logits
    at position length-1, pairs per (expert layer, held expert)). Padded
    positions lie after the real ones, so no real position attends to
    them."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    view = PromptView(pools, table_row, spec, length)
    x, counts = afmoe.trunk(params, tokens, positions, positions < length,
                            cfg, view)
    return (*view.pools, afmoe.logits_of(params, x[length - 1], cfg), counts)


def decode(params, pools, carry, tokens, positions, tables, cfg, spec):
    """One decode step of a padded batch: tokens (B,) (`carried_tokens`
    of the step before's `carry`) at positions (B,), block tables (B,
    full columns + ring). A padded row carries the all-null table: it
    writes to the null blocks, is routed to no expert and its logits are
    dropped by the caller. Returns (*pools, logits (B, vocab), greedy
    next token (`carry_of`: at max_batch), pairs per (expert layer, held
    expert))."""
    tokens = carried_tokens(carry, tokens)
    view = LiveGatherView(pools, tables, positions, spec=spec,
                          rows=carry.shape[0])
    x, counts = afmoe.trunk(params, tokens, positions, tables[:, 0] != 0,
                            cfg, view)
    logits = afmoe.logits_of(params, x, cfg)
    return (*view.pools, logits,
            carry_of(jnp.argmax(logits, -1).astype(jnp.int32), carry), counts)


class AfmoeLM(LatentMoELM):
    """params dict + `AfmoeConfig` (models/afmoe.py). The expert tally
    and `note_step` are the dropless family's."""

    def cache_spec(self):
        cfg = self.cfg
        return CacheSpec(cfg.n_layers, self.params["embed"].dtype,
                         n_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                         n_q_heads=cfg.n_heads, layer_kinds=cfg.layer_kinds,
                         window=cfg.window)

    def bind(self, block_size, paged=False, kv_quant=False, mesh=None):
        """The family's two step programs. It has the gather path only:
        an engine resolves the others to off before it binds
        (`CacheSpec.paged_unfit`)."""
        cfg, spec = self.cfg, self.cache_spec()
        names = ("k_pool", "v_pool", "k_ring", "v_ring")[
            :2 * len(spec.kinds)]
        self._prefill_jit = _program(
            "prefill", "serving_prefill", "prefill_kinds",
            lambda p, pools, t, ln, tb: prefill(p, pools, t, ln, tb, cfg,
                                                spec), names)
        self._decode_jit = _program(
            "decode", "serving_decode", "decode_kinds",
            lambda p, pools, c, t, pos, tb: decode(p, pools, c, t, pos, tb,
                                                   cfg, spec), names)

    def prefill(self, *pools_and_args):
        return self._prefill_jit(self.params, *pools_and_args)

    def decode(self, *pools_and_args):
        return self._decode_jit(self.params, *pools_and_args)
