"""Paged-cache adapter for the family whose every layer has attention
heads and state-space heads side by side (models/falcon_h1.py): what
`TransformerLM`, `LatentMoELM` and `AfmoeLM` are for theirs.
`serve((params, FalconH1Config), ...)` resolves to it (server
`_resolve_model`), and `Engine`, the scheduler, the block pools and the
serving loop drive it as they drive the other three: on the default
gather path, whole-prompt prefill then one decode step a token, one step
in flight.

Its cache is of two KINDS that every layer keeps BOTH of
(`kv_cache.CacheSpec.layer_kinds`: "full+state"): keys and values, which
grow with a sequence, and a recurrent state (the recurrence's matrix a
head in `state_dtype`, the convolution's last inputs), one slot a
sequence however long; four arrays, donated to and returned first by
both step programs, which keep the names the other families' have
(`jit_serving_prefill`, `jit_serving_decode`). The views are the K/V
layout's own (`kv_cache.PromptView`, `LiveGatherView`), told the spec:
prefill runs the mixer as a chunked scan and leaves the state at the
prompt's true length in its padded bucket, decode is one recurrence step
a row whose state is found through the row's table.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models import falcon_h1
from .engine import _program, carried_tokens, carry_of
from .kv_cache import CacheSpec, LiveGatherView, PromptView


def prefill(params, pools, tokens, length, table_row, cfg, spec):
    """One padded prompt (S,) of true `length`: writes every layer's keys
    and values into the blocks of `table_row` and its state after
    position length - 1 into the row's slot, and returns (*pools, logits
    at position length - 1). Padded positions lie after the real ones: no
    real position attends to them and they leave the state as it is."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    view = PromptView(pools, table_row, spec, length)
    x = falcon_h1.trunk(params, tokens, positions, cfg, view)
    return (*view.pools, falcon_h1.logits_of(params, x[length - 1], cfg))


def decode(params, pools, carry, tokens, positions, tables, cfg, spec):
    """One decode step of a padded batch: tokens (B,) (`carried_tokens`
    of the step before's `carry`) at positions (B,), block tables (B,
    full columns + the slot). A padded row carries the all-null table: it
    writes to the null block and the null slot, and its logits are
    dropped by the caller. Returns (*pools, logits (B, vocab), greedy
    next token (`carry_of`: at max_batch))."""
    tokens = carried_tokens(carry, tokens)
    view = LiveGatherView(pools, tables, positions, spec=spec,
                          rows=carry.shape[0])
    logits = falcon_h1.logits_of(
        params, falcon_h1.trunk(params, tokens, positions, cfg, view), cfg)
    return (*view.pools, logits,
            carry_of(jnp.argmax(logits, -1).astype(jnp.int32), carry))


class FalconH1LM:
    """params dict + `FalconH1Config` (models/falcon_h1.py)."""

    uses_cache = True

    def __init__(self, params, cfg):
        self.params = params
        self.cfg = cfg
        self.vocab = cfg.vocab
        self.max_len = cfg.max_len
        self._prefill_jit = self._decode_jit = None

    def place(self, device):
        """Commit the parameters to one device (a one-chip replica's
        window)."""
        self.params = jax.device_put(self.params, device)

    def cache_spec(self):
        cfg = self.cfg
        return CacheSpec(
            cfg.n_layers, self.params["embed"].dtype,
            n_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            n_q_heads=cfg.n_heads,
            layer_kinds=("full+state",) * cfg.n_layers,
            state_shape=falcon_h1.state_layout(cfg),
            conv_shape=(cfg.conv_taps - 1, cfg.conv_channels),
            state_dtype=cfg.state_dtype)

    def bind(self, block_size, paged=False, kv_quant=False, mesh=None):
        """The family's two step programs. It has the gather path only:
        an engine resolves the others to off before it binds
        (`CacheSpec.paged_unfit`)."""
        cfg, spec = self.cfg, self.cache_spec()
        names = ("k_pool", "v_pool", "ssm_state", "conv_state")
        self._prefill_jit = _program(
            "prefill", "serving_prefill", "prefill_state",
            lambda p, pools, t, ln, tb: prefill(p, pools, t, ln, tb, cfg,
                                                spec), names)
        self._decode_jit = _program(
            "decode", "serving_decode", "decode_state",
            lambda p, pools, c, t, pos, tb: decode(p, pools, c, t, pos, tb,
                                                   cfg, spec), names)

    def prefill(self, *pools_and_args):
        return self._prefill_jit(self.params, *pools_and_args)

    def decode(self, *pools_and_args):
        return self._decode_jit(self.params, *pools_and_args)
