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
import jax.numpy as jnp
import numpy as np

from ..models import latent_moe
from .engine import _program, carried_tokens, carry_of
from .kv_cache import (CacheSpec, append_latent, write_latent_prompt,
                       gather_latent, flat_slots)


class PromptView(latent_moe.DenseView):
    """Prefill: the whole prompt's latents go into the blocks of
    `table_row`, then every position attends by the expanded form."""

    def __init__(self, pool, table_row):
        self.pool, self.table_row = pool, table_row

    def attend(self, layer, q_nope, q_rope, latent, wk_b, wv_b, cfg):
        self.pool = write_latent_prompt(self.pool, layer, self.table_row,
                                        latent)
        return super().attend(layer, q_nope, q_rope, latent, wk_b, wv_b, cfg)


class DecodeView:
    """Decode: row b is sequence b's token at `positions[b]`; append its
    latent, gather the sequence's blocks by table, attend absorbed."""

    def __init__(self, pool, tables, positions):
        self.pool, self.tables = pool, tables
        bs = pool.shape[2]
        self.slots = flat_slots(tables, positions, bs)
        self.live = jnp.arange(tables.shape[1] * bs)[None, :] \
            <= positions[:, None]

    def attend(self, layer, q_nope, q_rope, latent, wk_b, wv_b, cfg):
        self.pool = append_latent(self.pool, layer, self.slots, latent)
        cached = gather_latent(self.pool, layer, self.tables)
        return latent_moe.absorbed_attention(q_nope, q_rope, cached,
                                             self.live, wk_b, wv_b, cfg)


def prefill(params, pool, tokens, length, table_row, cfg):
    """One padded prompt (S,) of true `length`: writes every layer's
    latents into the blocks of `table_row` and returns (pool, logits at
    position length-1, pairs per (expert layer, held expert)). Padded
    positions lie after the real ones, so no real position attends to
    them; what they write is overwritten by decode before it is read."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    view = PromptView(pool, table_row)
    x, counts = latent_moe._trunk(params, tokens, positions,
                                  positions < length, cfg, view)
    return view.pool, latent_moe._logits(params, x[length - 1], cfg), counts


def decode(params, pool, carry, tokens, positions, tables, cfg):
    """One decode step of a padded batch: tokens (B,) (`carried_tokens`
    of the step before's `carry`) at positions (B,), block tables
    (B, nblk). A padded row carries the all-null table: it writes to the
    null block, is routed to no expert and its logits are dropped by the
    caller. Returns (pool, logits (B, vocab), greedy next token
    (`carry_of`: at max_batch), pairs per (expert layer, held expert))."""
    tokens = carried_tokens(carry, tokens)
    view = DecodeView(pool, tables, positions)
    x, counts = latent_moe._trunk(params, tokens, positions,
                                  tables[:, 0] != 0, cfg, view)
    logits = latent_moe._logits(params, x, cfg)
    return (view.pool, logits,
            carry_of(jnp.argmax(logits, -1).astype(jnp.int32), carry), counts)


class LatentMoELM:
    """params dict + `LatentMoEConfig` (models/latent_moe.py)."""

    uses_cache = True

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

    def bind(self, block_size, paged=False, kv_quant=False, mesh=None):
        """The family's two step programs. It has the gather path only:
        an engine resolves the other three to off before it binds
        (`Engine.paged_fallback`)."""
        cfg = self.cfg
        self._prefill_jit = _program(
            "prefill", "serving_prefill", "prefill_latent",
            lambda p, pools, t, ln, tb: prefill(p, *pools, t, ln, tb, cfg),
            ("kv_pool",))
        self._decode_jit = _program(
            "decode", "serving_decode", "decode_latent",
            lambda p, pools, c, t, pos, tb: decode(p, *pools, c, t, pos, tb,
                                                   cfg),
            ("kv_pool",))

    def prefill(self, kv, tokens, length, table_row):
        return self._prefill_jit(self.params, kv, tokens, length, table_row)

    def decode(self, kv, carry, tokens, positions, tables):
        return self._decode_jit(self.params, kv, carry, tokens, positions,
                                tables)

    def moe_unfit(self):
        """Why both step programs multiply the held experts' tiles with
        XLA's loop and not with the kernel
        (ops/pallas_grouped_experts.py), or None: `latent_moe.experts_unfit`
        asked of the first expert layer's matrices as `grouped_experts`
        asks it of every layer's while it traces (the layers are alike).
        None too with no expert layer."""
        up = next((n for n in sorted(self.params) if n.endswith("we_up")),
                  None)
        if up is None:
            return None
        return latent_moe.experts_unfit(
            self.params["embed"], self.params.get(up[:-2] + "gate"),
            self.params[up])

    def note_step(self, counts):
        """One step's rows per (expert layer, held expert), on the host:
        add them up and say what the step's span should carry."""
        self.expert_rows += counts
        return {"moe_pairs": int(counts.sum()),
                "moe_experts_touched": int(np.count_nonzero(counts))}
