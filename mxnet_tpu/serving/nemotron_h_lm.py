"""Paged-cache adapter for the family whose every layer is ONE mixer by a
pattern's letter (models/nemotron_h.py): what `TransformerLM`,
`LatentMoELM`, `AfmoeLM` and `FalconH1LM` are for theirs.
`serve((params, NemotronHConfig), ...)` resolves to it (server
`_resolve_model`), and `Engine`, the scheduler, the block pools and the
serving loop drive it as they drive the other four: on the default gather
path, whole-prompt prefill then one decode step a token, one step in
flight.

Its cache's kinds differ LAYER BY LAYER (`kv_cache.CacheSpec.layer_kinds`):
a state-space layer keeps a recurrent state alone ("state": a slot a
sequence, however long), an attention layer keys and values alone
("full"), an expert layer nothing ("none"). Each kind's planes, block
pool and table columns run over that kind's own layers; four arrays,
donated to and returned first by both step programs, which keep the names
the other families' have (`jit_serving_prefill`, `jit_serving_decode`).
The views are the K/V layout's own (`kv_cache.PromptView`,
`LiveGatherView`), told the spec. Beside its results each step returns
the rows of real tokens it sent to each held expert in each expert layer,
as the dropless families' steps do (`LatentMoELM.note_step`).
"""
from __future__ import annotations

import jax.numpy as jnp

from ..models import falcon_h1, nemotron_h
from .engine import _program, carried_tokens, carry_of
from .kv_cache import CacheSpec, LiveGatherView, PromptView
from .latent_lm import LatentMoELM

#: the pool arrays of each kind, as every step takes them (`POOL_ARGS`)
_PLANES_OF = {"full": ("k_pool", "v_pool"), "state": ("ssm_state",
                                                      "conv_state")}


def prefill(params, pools, tokens, length, table_row, cfg, spec):
    """One padded prompt (S,) of true `length`: writes every attention
    layer's keys and values into the blocks of `table_row` and every
    state-space layer's state after position length - 1 into the row's
    slot, and returns (*pools, logits at position length - 1, pairs per
    (expert layer, held expert)). Padded positions lie after the real
    ones: no real position attends to them, they leave the state as it is
    and are routed nowhere."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    view = PromptView(pools, table_row, spec, length)
    x, counts = nemotron_h.trunk(params, tokens, positions < length, cfg,
                                 view)
    return (*view.pools, nemotron_h.logits_of(params, x[length - 1], cfg),
            counts)


def decode(params, pools, carry, tokens, positions, tables, cfg, spec):
    """One decode step of a padded batch: tokens (B,) (`carried_tokens`
    of the step before's `carry`) at positions (B,), block tables (B,
    full columns + the slot). A padded row carries the all-null table: it
    writes to the null block and the null slot, is routed to no expert
    and its logits are dropped by the caller. Returns (*pools, logits (B,
    vocab), greedy next token (`carry_of`: at max_batch), pairs per
    (expert layer, held expert))."""
    tokens = carried_tokens(carry, tokens)
    view = LiveGatherView(pools, tables, positions, spec=spec,
                          rows=carry.shape[0])
    x, counts = nemotron_h.trunk(params, tokens, tables[:, 0] != 0, cfg, view)
    logits = nemotron_h.logits_of(params, x, cfg)
    return (*view.pools, logits,
            carry_of(jnp.argmax(logits, -1).astype(jnp.int32), carry), counts)


class NemotronHLM(LatentMoELM):
    """params dict + `NemotronHConfig` (models/nemotron_h.py). The expert
    tally and `note_step` are the dropless family's."""

    def cache_spec(self):
        cfg = self.cfg
        return CacheSpec(
            cfg.n_layers, self.params["embed"].dtype,
            n_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            n_q_heads=cfg.n_heads,
            layer_kinds=tuple(nemotron_h.CACHE_KIND[c] for c in cfg.pattern),
            state_shape=falcon_h1.state_layout(cfg),
            conv_shape=(cfg.conv_taps - 1, cfg.conv_channels),
            state_dtype=cfg.state_dtype)

    def bind(self, block_size, paged=False, kv_quant=False, mesh=None):
        """The family's two step programs. It has the gather path only:
        an engine resolves the others to off before it binds
        (`CacheSpec.paged_unfit`)."""
        cfg, spec = self.cfg, self.cache_spec()
        names = tuple(n for kind in spec.kinds for n in _PLANES_OF[kind])
        self._prefill_jit = _program(
            "prefill", "serving_prefill", "prefill_pattern",
            lambda p, pools, t, ln, tb: prefill(p, pools, t, ln, tb, cfg,
                                                spec), names)
        self._decode_jit = _program(
            "decode", "serving_decode", "decode_pattern",
            lambda p, pools, c, t, pos, tb: decode(p, pools, c, t, pos, tb,
                                                   cfg, spec), names)

    def prefill(self, *pools_and_args):
        return self._prefill_jit(self.params, *pools_and_args)

    def decode(self, *pools_and_args):
        return self._decode_jit(self.params, *pools_and_args)
