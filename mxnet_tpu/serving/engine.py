"""Prefill + decode engine over the paged KV-cache.

Two model families plug in behind one `Engine`:

* `TransformerLM` — the functional transformer (models/transformer.py)
  with a real paged-cache decode path. Its layer is written once
  (`models.transformer.block`); the four step functions here (`prefill`,
  `decode`, `prefill_chunk`, `spec_score`) say what a row is and which
  rows' logits come back, and a cache view (kv_cache.py) says where a
  layer's keys and values are written and how they are read. An engine
  runs one of two configurations:

  - the GATHER path (PR 1, the default, the fallback and the parity
    oracle): prefill runs the dense causal forward once per request over
    a power-of-two length bucket (`PromptView`); decode gathers each
    sequence's K/V blocks by table as far as the batch's longest live
    position (`LiveGatherView`): the table handed in is always
    full-capacity, one program serves a batch bucket at every length, and
    the bytes a step moves follow the longest live sequence.
  - the PAGED path (`MXNET_PAGED_ATTENTION=1`, or `Engine(paged=True)`):
    attention runs as ONE Pallas kernel per layer that walks the block
    table in place with per-sequence true lengths (`PagedView`,
    ops/pallas_paged.py) — no dense gather is ever materialized, and
    the table WIDTH handed to the kernel is bucketed to the longest
    live sequence, so the bytes per decoded token track true lengths
    rather than the padded pool capacity. Prefill is CHUNKED: long
    prompts stream through a fixed-shape chunk that appends K/V
    into the pool chunk-by-chunk — one compiled chunk shape replaces
    the per-length-bucket dense prefill lattice, and the serving loop
    co-schedules pending chunks with decode steps under the
    scheduler's token budget (a long prompt cannot starve decode).

* `BlockLM` / `ExportedLM` — any Gluon causal LM (via
  parallel.functional.functionalize) or a `.mxtpu` artifact from
  `predict.export_model`. These have no cache hooks, so decode re-runs
  the full forward over the (bucketed) token history — slower per token
  but it makes the whole serving stack (scheduler, batching, HTTP)
  available to every model the framework can express or export.

Four further families bring their own step functions over the same views
and pools and plug in beside `TransformerLM`: `serving/latent_lm.py`
(a latent pool), `serving/afmoe_lm.py` (window and full layers over a
cache of two kinds, `kv_cache.CacheSpec.layer_kinds`),
`serving/falcon_h1_lm.py` (keys and values and a recurrent state in every
layer) and `serving/nemotron_h_lm.py` (a state alone, keys and values
alone, or nothing, layer by layer).

One decode step stays in flight (`Engine.decode_pass`): a pass launches
step n + 1 from step n's tokens on the device (`carried_tokens`) and only
then reads step n, so the device runs while the host appends, accounts,
admits and builds. The tokens are carried at one shape (max_batch), so a
step launched with none in flight and a step launched ahead are one
program per batch bucket. Engines whose next input exists on the host
alone (speculative, cache-free, `keep_logits`: `Engine.sync_reason`)
collect every step in the pass that launched it. A whole-prompt prefill's
first token takes the same road (`First`): it is chosen on the device
(`first_token`), the next step takes it from a free place of its carry
(`carry_first`), and the host reads it behind that step's launch
(`Engine.collect_firsts`).

jit stability: the engine never hands XLA a novel shape per request.
Prompt lengths pad to power-of-two buckets (gather path) or one fixed
chunk shape (paged path), the decode batch and the paged table width pad
to power-of-two buckets, and the cache pool is fixed-shape (kv_cache.py)
— so the number of distinct compilations is bounded by #buckets, not by
traffic. Since ISSUE 9 every step function registers through the compile
watchdog (telemetry/introspect.py): `prefill_compilations` /
`decode_compilations` count, at the real jit seam, the compiles THIS
engine's calls paid (per-thread dispatch attribution, so engines sharing
one adapter never absorb a sibling's warm-up), and each compile is
attributed to the argument whose shape/dtype/sharding changed; tests pin
the bounds for both paths.
"""
from __future__ import annotations

import contextlib
import functools
import math
import os
import time
import weakref

import numpy as np
import jax
import jax.numpy as jnp

from ..base import MXNetError
from .. import telemetry
from ..models.transformer import OneChip, block, _layer_norm
from ..ops.pallas_splice import preload as preload_pallas
from . import tp
from .kv_cache import (POOL_ARGS, CacheSpec, PagedKVCache, PromptView,
                       LiveGatherView, PagedView, walk_unfit,
                       prompt_attn_unfit, state_step_unfit, flat_slots,
                       copy_block, zero_block_scales)
from .prefix_cache import PrefixCache, prefix_cache_enabled


def quantized_kv_enabled():
    """MXNET_QUANTIZED_KV=1 requests the int8 KV block pool — read when
    an Engine is constructed (docs/ENV_VARS.md). Ineligible configs
    fall back to the verbatim f32 pool with the reason recorded on
    `Engine.kv_quant_fallback`."""
    return os.environ.get("MXNET_QUANTIZED_KV", "") == "1"


def quantized_weights_env():
    """MXNET_QUANTIZED_WEIGHTS=int8 requests weight quantization at
    load — read when an Engine is constructed (docs/ENV_VARS.md).
    Unset/empty = f32 weights."""
    v = os.environ.get("MXNET_QUANTIZED_WEIGHTS", "").strip()
    return v or None


#: operation -> (watchdog site, phase, the arguments after the pools, how
#: many results follow the pools)
_STEPS = {
    "prefill": ("serving.prefill", "prefill",
                ("tokens", "length", "table_row"), 1),
    "decode": ("serving.decode", "decode",
               ("carry", "tokens", "positions", "tables"), 2),
    "prefill_chunk": ("serving.prefill", "prefill",
                      ("tokens", "q_start", "length", "last_idx",
                       "table_row"), 1),
    "spec_score": ("serving.spec_score", "decode",
                   ("tokens", "q_starts", "counts", "tables"), 1),
}


def _program(op, name, variant, step, pool_names, shard_over=None):
    """`step(params, pools, *args) -> (*pools, *results)` as the step
    program `jit_<name>` of operation `op`, taking `(params, *pools,
    *args)` (a device trace names a program after its function; a
    lambda's is `jit__lambda`, whichever step it is), registered with
    the compile watchdog at the operation's site under the AOT tag
    `variant`. It CONSUMES its pools: the arguments named in `POOL_ARGS`
    are donated, the executable aliases them to the pools it returns
    and writes the new K/V into those same buffers, so a step holds the
    pool once and copies none of it. `shard_over` = (mesh, the
    parameters' specs) runs it under shard_map over the tp mesh, each
    chip on its shard (serving/tp.py)."""
    site, phase, args, n_results = _STEPS[op]
    n = len(pool_names)

    def fn(params, *rest):
        return step(params, rest[:n], *rest[n:])

    fn.__name__ = name
    if shard_over is not None:
        fn = tp.shard_step(fn, *shard_over, len(pool_names), len(args),
                           n_results)
    argnames = ("params",) + tuple(pool_names) + args
    return telemetry.introspect.instrument(
        jax.jit(fn, donate_argnums=tuple(
            i for i, a in enumerate(argnames) if a in POOL_ARGS)),
        site=site, phase=phase, argnames=argnames, variant=variant)


def pow2_bucket(n, lo=1, hi=None):
    """Smallest power of two >= n (clamped to [lo, hi])."""
    b = lo
    while b < n:
        b *= 2
    return min(b, hi) if hi is not None else b


class Sequence:
    """One in-flight generation: prompt + generated tokens, cache blocks,
    bookkeeping the engine and scheduler share. `prefilled` counts prompt
    tokens already written to the cache (chunked prefill advances it one
    chunk per `prefill_step`); `prefill_s` accumulates prefill wall time
    across chunks for the metrics roll-up. `t_last_token` is when the
    host held its newest token (`t_begin`, when the engine took it in,
    until there is one), and `prefills_seen` / `prefill_tokens_seen`
    are the engine's two prefill counters as they stood then: what a
    token's record (`Engine.record_tokens`) is made from. `attn`: what
    scored its prompt in a whole-prompt prefill, the `kernel`
    (ops/pallas_prompt_attention.py) or `xla`, block by block; None
    elsewhere. `first`: its whole-prompt prefill while that is launched and
    its token not read (`First`): until then it is one token longer on
    the device than its `tokens` say."""

    __slots__ = ("tokens", "prompt_len", "blocks", "table_row",
                 "max_total", "eos_id", "done", "last_logits", "request",
                 "prefilled", "prefill_s", "cache_hit_tokens",
                 "shared_blocks", "token_logits", "t_begin",
                 "t_last_token", "prefills_seen", "prefill_tokens_seen",
                 "attn", "first")

    def __init__(self, prompt, max_total, eos_id=None):
        self.tokens = list(prompt)
        self.prompt_len = len(prompt)
        self.blocks = ()              # its block ids, a list a kind of
                                      # layer (`PagedKVCache.try_alloc`)
        self.table_row = None         # `PagedKVCache.row` of them
        self.max_total = max_total
        self.eos_id = eos_id
        self.done = False
        self.last_logits = None
        self.request = None
        self.prefilled = 0
        self.prefill_s = 0.0
        self.cache_hit_tokens = 0     # prompt tokens served by prefix hits
        self.shared_blocks = 0        # table entries pointing at shared
                                      # (refcounted) cache blocks
        self.token_logits = None      # keep_logits engines: one f32 (V,)
                                      # row PER EMITTED token, both decode
                                      # paths — the spec parity oracle
        self.t_begin = self.t_last_token = time.perf_counter()
        self.prefills_seen = self.prefill_tokens_seen = 0
        self.attn = self.first = None

    @property
    def generated(self):
        return self.tokens[self.prompt_len:]

    @property
    def block_ids(self):
        """Its blocks of the first kind: all of them where layers are of
        one kind."""
        return self.blocks[0] if self.blocks else []


class Step:
    """One decode step from its launch to its collect
    (`Engine.decode_pass`). In between, `nxt` (the tokens it chose, at
    max_batch), `stats` (what the family returns beside them) and
    `logits` (`keep_logits` engines) are on the device, and the next
    step can be launched from `nxt` there. `ahead`: it was launched
    while the step before's tokens had not been read on the host.
    `drains`: why it is collected in the pass that launched it, or
    launched with nothing in flight (the serving metrics count them by
    reason). `walk`: what walks the cache in the step's program on the
    gather path, the `kernel` (ops/pallas_decode_walk.py) or `xla`'s
    loop; None elsewhere. A collect fills `t_read`, when the host held
    the step's tokens, and `advanced`, [(sequence that took tokens, its
    length before, its length after, the gap in seconds between each of
    those tokens and the one before it)]."""

    __slots__ = ("seqs", "ahead", "nxt", "stats", "logits", "drains",
                 "advanced", "t_launch", "t_read", "walk")

    def __init__(self, seqs, ahead=False, drains=()):
        self.seqs = seqs
        self.ahead = ahead
        self.walk = None
        self.nxt = self.logits = self.advanced = None
        self.stats = []
        self.drains = list(drains)
        self.t_launch = time.perf_counter()
        self.t_read = None

    @property
    def sealed(self):
        """Nothing can be launched from it: it is collected in the pass
        that launched it."""
        return any(d != "first_step" for d in self.drains)


class First:
    """One whole-prompt prefill of the gather path from its dispatch to
    the read of its first token (`Engine.collect_firsts`), and its
    `serving.prefill` span, which covers just that. In between, `token`
    (chosen on the device, `first_token`), `stats` (what the family
    returns beside the logits) and `logits` (`keep_logits` engines) are
    on the device. `ahead`: the token is read behind the launch of the
    decode step that takes it there (`carry_first`), and not before
    anything else is done. `attrs` are the span's (`bucket`, `length`,
    `attn`, `moe`, `ahead`, what `note_step` adds at the read): the span
    is recorded when it closes, by hand, since it outlives the pass's
    `serving.admit` and overlaps its `serving.decode`."""

    __slots__ = ("seq", "ahead", "token", "stats", "logits", "trace",
                 "t0_us", "attrs")

    name = "serving.prefill"

    def __init__(self, seq, ahead, **attrs):
        self.seq = seq
        self.ahead = ahead
        self.token = self.logits = None
        self.stats = []
        self.trace = seq.request.trace if seq.request is not None \
            else telemetry.current_trace()
        self.attrs = dict(attrs, ahead=int(ahead))
        self.t0_us = time.perf_counter_ns() // 1000

    def close(self, at_us=None, **attrs):
        if at_us is None:
            at_us = time.perf_counter_ns() // 1000
        telemetry.record_span(self.name, self.t0_us, at_us - self.t0_us,
                              trace=self.trace, category="serving",
                              **self.attrs, **attrs)


# ---------------------------------------------------------------------------
# paged-cache transformer adapter
# ---------------------------------------------------------------------------


def _layers(params, x, cfg, view, shard):
    for i in range(cfg.n_layers):
        x = block(params, i, x, cfg, view, shard)
    return x


def _logits(params, x):
    h = _layer_norm(x, params["lnf_g"], params["lnf_b"])
    return (h @ params["head"]).astype(jnp.float32)


def prefill(params, pools, tokens, length, table_row, cfg):
    """Dense causal forward over one padded prompt (S,), writing every
    layer's K/V into the pool and returning the logits at position
    length-1. Padded positions (>= length) sit AFTER the real tokens, so
    under the causal mask no real position ever attends to them; their
    K/V writes land in not-yet-used or null-block slots and are
    overwritten by decode before they can be read."""
    view = PromptView(pools, table_row, None, length)
    x = params["embed"][tokens] + params["pos_embed"][:tokens.shape[0]]
    x = _layers(params, x, cfg, view, OneChip)                     # (S, D)
    return (*view.pools, _logits(params, x[length - 1]))


def carried_tokens(carry, tokens):
    """A decode step's input tokens (B,). A row gives its own token (>= 0,
    from the host: a sequence that has just been prefilled), or -1 - r
    for row r of `carry`: the tokens the step before chose, which are
    still on the device and which the host may not have read yet. So a
    step can be launched from the last one's result with nothing read
    in between (`Engine.launch_step`)."""
    return jnp.where(tokens < 0, carry[jnp.maximum(-1 - tokens, 0)], tokens)


def carry_of(nxt, carry):
    """A step's chosen tokens (B,) at the one shape every batch bucket
    carries them in (`carry`'s: max_batch): whatever bucket the next
    step has, it takes them under one signature."""
    return jnp.zeros_like(carry).at[:nxt.shape[0]].set(nxt)


@jax.jit
def first_token(logits):
    """A prefill's greedy first token, chosen on the device from the
    float32 logits (V,) its program returns: among equals the lowest
    index, as `np.argmax` gives on the host."""
    return jnp.argmax(logits, -1).astype(jnp.int32)


@jax.jit
def carry_first(carry, token, place):
    """`carry` (the step before's tokens, `carry_of`) with a prefill's
    `first_token` at `place`, a row of it that no row of the next step
    takes: that step then takes the sequence's first token there as it
    takes every other row's (`carried_tokens`), under the one signature
    a batch bucket has, the place being data."""
    return carry.at[place].set(token)


# `first_token` and `carry_first` are the two small programs of a first
# token: a device trace names them `jit_first_token` and `jit_carry_first`,
# neither a `jit_serving_prefill*` nor a `jit_serving_decode*`, whose device
# time readers add up by name. Plain jits of one shape each, as the
# copy-on-write and the scale reset are: the first admission compiles both.


def decode(params, pools, carry, tokens, positions, tables, cfg, block_size,
           view_of, shard=OneChip):
    """One decode step for a (padded) batch: tokens (B,) (`carried_tokens`
    of the step before's `carry`) at positions
    (B,), block tables (B, nblk). Writes the new K/V, attends over each
    sequence's cache through `view_of` (`LiveGatherView`: the table at
    full capacity, walked as far as the longest live sequence;
    `PagedView`: the table width-bucketed by the caller to the longest
    live sequence, walked in place by one kernel a layer, so no dense
    gather is materialized), returns logits (B, V) and the greedy next
    token (`carry_of`: at max_batch). Padded rows carry the all-null
    table — their writes hit the null block and their logits are
    discarded by the caller."""
    tokens = carried_tokens(carry, tokens)
    x = params["embed"][tokens] + params["pos_embed"][positions]   # (B, D)
    # the gather view pads its kernel's operands to the rows the batch
    # can hold, so that every bucket's step calls one lowered kernel
    rows = {"rows": carry.shape[0]} if view_of is LiveGatherView else {}
    view = view_of(pools, tables, positions,
                   flat_slots(tables, positions, block_size), **rows)
    logits = _logits(params, _layers(params, x, cfg, view, shard))
    return (*view.pools, logits,
            carry_of(jnp.argmax(logits, -1).astype(jnp.int32), carry))


def prefill_chunk(params, pools, toks, qs, length, last_idx, table_row, cfg,
                  block_size, shard=OneChip):
    """One fixed-shape prefill chunk for ONE sequence: toks (C,) are the
    prompt tokens at positions qs..qs+C-1 (zero-padded past the true
    prompt `length`), table_row (w,) is the sequence's width-bucketed
    block table. Writes the chunk's K/V into the pool and attends via the
    ragged paged kernel (`PagedView`). Returns logits at chunk index
    `last_idx` (the prompt's final token when this is the last chunk;
    earlier chunks' logits are discarded by the caller).

    Padded positions (>= length) write their garbage K/V into the null
    block — NOT into their table slot, which belongs to a future decode
    position: the decode step that later owns that slot writes its own
    K/V before anything can read it, and real queries never attend past
    position length-1 anyway."""
    C = toks.shape[0]
    pos = qs + jnp.arange(C)                                       # (C,)
    slots = jnp.take(table_row, pos // block_size) * block_size \
        + pos % block_size
    slots = jnp.where(pos < length, slots, pos % block_size)       # null blk
    # a contiguous C-token chunk touches at most ceil-plus-straddle
    # blocks (it starts inside a block after a prefix-cache hit on a
    # partial one) plus the null block — a tight candidate set keeps
    # the writer's read and write of whole blocks small
    view = PagedView(pools, table_row[None],
                     jnp.reshape(qs, (1,)).astype(jnp.int32), slots,
                     ncand=(C - 1) // block_size + 3)
    x = params["embed"][toks] + params["pos_embed"][pos]           # (C, D)
    x = _layers(params, x, cfg, view, shard)
    return (*view.pools, _logits(params, x[last_idx]))


def spec_score(params, pools, toks, q_starts, counts, tables, cfg,
               block_size, shard=OneChip):
    """Speculative scoring pass: the batched generalization of
    `prefill_chunk`. For each row, toks (B, C) holds [last history
    token, draft_1..draft_k] (zero-padded past that row's true `counts`)
    at true positions q_starts[b]..q_starts[b]+C-1; tables (B, w) are the
    live width-bucketed block tables. ONE paged pass writes the C
    positions' K/V and returns logits (B, C, V) f32 — row j of a
    sequence is the target's next-token distribution given its history
    plus the first j draft tokens, exactly what greedy/rejection
    verification consumes.

    Position truth: each scored position attends precisely the tokens a
    one-at-a-time decode would (`PagedView`'s mask). Positions past
    `counts` (shorter-than-k proposals, padded batch rows) write to the
    null block and their logits are discarded by the caller; positions
    past an eventual rejection DO land in real table slots, but they are
    rewritten by the next pass over this sequence (spec passes re-score
    from the new history end; a non-spec step writes its own slot)
    before any mask lets a query read them."""
    B, C = toks.shape
    w = tables.shape[1]
    pos = q_starts[:, None] + jnp.arange(C)[None, :]               # (B, C)
    valid = jnp.arange(C)[None, :] < counts[:, None]               # (B, C)
    blk = jnp.minimum(pos // block_size, w - 1)
    slots = jnp.take_along_axis(tables, blk, axis=1) * block_size \
        + pos % block_size
    slots = jnp.where(valid, slots, pos % block_size)              # null blk
    # each row's C contiguous positions straddle at most
    # (C-1)//block_size + 2 blocks, and all padded ones share the null block
    view = PagedView(pools, tables, q_starts.astype(jnp.int32),
                     slots.reshape(B * C),
                     ncand=min(B * ((C - 1) // block_size + 2) + 1, B * C))
    x = params["embed"][toks] \
        + params["pos_embed"][jnp.minimum(pos, cfg.max_len - 1)]   # (B,C,D)
    x = _layers(params, x, cfg, view, shard)
    return (*view.pools, _logits(params, x))


class TransformerLM:
    """Paged-cache adapter for the functional transformer
    (models/transformer.py): params dict + TransformerConfig. It holds
    the step programs of ONE engine's configuration (`bind`), under the
    four names the engine calls: a gather engine `prefill` and `decode`,
    a paged engine `prefill_chunk`, `decode` and `spec_score`."""

    uses_cache = True

    def __init__(self, params, cfg):
        if cfg.n_experts and cfg.moe_top_k:
            raise MXNetError(
                "serving: top-k MoE routing is capacity-dependent across "
                "the token group, so padded decode batches would change "
                "real tokens' routing; serve dense-FFN or dense-dispatch "
                "MoE configs (moe_top_k=0). Sparse experts are served by "
                "the dropless family (models/latent_moe.py, LatentMoELM): "
                "its routing has no capacity, so a token's experts depend "
                "on that token alone")
        self.params = params
        self.cfg = cfg
        self.vocab = cfg.vocab
        self.max_len = cfg.max_len
        self.weight_quant = None
        self.params_f32 = None    # original weights once quantized —
                                  # the tp placement + self-draft source
        self.programs = {}        # operation -> its bound step program
        self._tp_params = None

    def place(self, device):
        """Commit the parameters to one device (a one-chip replica's
        window): the single-device steps then run where they live."""
        self.params = jax.device_put(self.params, device)

    def cache_spec(self):
        return CacheSpec(self.cfg.n_layers, self.params["embed"].dtype,
                         n_heads=self.cfg.n_heads,
                         head_dim=self.cfg.d_model // self.cfg.n_heads)

    def quantize_weights(self, mode="int8"):
        """Quantize the matmul weights ONCE at load (ISSUE 20):
        per-channel symmetric int8 for wqkv/wo/w1/w2 (each becomes a
        `{"q": int8, "s": f32-per-output-channel}` dict the layer's
        `_mm` dispatch consumes); embeddings, positional table,
        layer norms, and the LM head stay f32 — they are small, and the
        logits' final projection dominates the error budget. MoE expert
        stacks (3-D w1/w2) stay f32 too. Idempotent; must run BEFORE
        `bind` so the jits trace the quantized pytree."""
        if str(mode) != "int8":
            raise MXNetError("weight_quant %r is not supported (int8 "
                             "or None)" % (mode,))
        if self.weight_quant:
            return
        from ..predict import quantize_lm_params
        self.params_f32 = self.params
        self.params = quantize_lm_params(self.params, self.cfg.n_layers,
                                         mode=mode)
        self.weight_quant = "int8"

    def bind(self, block_size, paged=False, kv_quant=False, mesh=None):
        """Build the step programs of the configuration `Engine.__init__`
        resolved, and no other. Every program takes `(params, *pools,
        *args)` and returns `(*pools, *results)`, the pools in the order
        of `PagedKVCache.arrays()` (the int8 pool's scale sidecars after
        `v`), donated.

        Over `mesh` (axis 'tp'; paged only) the same step functions run
        under shard_map on head-major-resharded params (serving/tp.py);
        `self.params` stays the untouched replicated original. The tp
        programs register at the SAME watchdog sites as the
        single-device ones: a tp restart over unchanged shapes is then
        attributed to the params/pool sharding diff, not misread as new
        traffic shapes.

        `variant=` tags each program's entries in the persistent AOT
        cache (mxnet_tpu/aot): the gather and paged decode steps share
        the serving.decode SITE and can trace equal signatures, and the
        int8 steps trace extra scale operands — the tag (plus the
        lowered-text hash in the key) keeps their disk entries apart,
        so a warm load can never swap implementations."""
        cfg = self.cfg
        shard, where = OneChip, ""
        self._tp_params = None
        if mesh is not None:
            shard = tp.HeadShard
            # the tp variant embeds the mesh's DEVICE WINDOW: two
            # replicas' tp steps have equal shapes and identity-free
            # sharding descriptions but compile against different chips
            # — their AOT cache entries must never collide
            # (aot.placement_key covers committed args; the tag is the
            # belt under that brace)
            where = ":" + tp.tp_cache_variant(mesh)
            # weight quant composes with tp by quantizing AFTER shard
            # placement: the f32 originals are resharded, then each chip
            # quantizes its own shard so scales are chip-local (a
            # row-parallel shard's per-output-channel scales differ per
            # chip — each dequantizes its partial before the psum)
            self._tp_params = tp.place_tp_params(
                self.params_f32 if self.weight_quant else self.params,
                cfg, mesh)
            if self.weight_quant:
                self._tp_params = tp.quantize_tp_params(self._tp_params,
                                                        cfg, mesh)
        kw = dict(cfg=cfg, block_size=block_size, shard=shard)
        # operation -> (the program's name, its AOT variant, the step)
        if paged:
            on_mesh = mesh is not None
            steps = {
                "decode": ("decode_tp" if on_mesh else "decode_paged",
                           functools.partial(decode, view_of=PagedView,
                                             **kw)),
                "prefill_chunk": ("prefill_chunk_tp" if on_mesh
                                  else "prefill_chunk",
                                  functools.partial(prefill_chunk, **kw)),
                "spec_score": ("spec_score_tp" if on_mesh else "spec_score",
                               functools.partial(spec_score, **kw))}
            q8 = "_q8" if kv_quant else ""
            steps = {op: (name + q8, name + q8 + where, step)
                     for op, (name, step) in steps.items()}
        else:
            steps = {
                "prefill": ("prefill", "prefill_dense",
                            functools.partial(prefill, cfg=cfg)),
                "decode": ("decode", "decode_gather",
                           functools.partial(decode, view_of=LiveGatherView,
                                             **kw))}
        shard_over = None if mesh is None else (
            mesh, tp.tp_param_specs(cfg, bool(self.weight_quant)))
        self.programs = {
            op: _program(op, "serving_" + name, variant, step,
                         POOL_ARGS[:4 if kv_quant else 2], shard_over)
            for op, (name, variant, step) in steps.items()}

    @property
    def step_params(self):
        """What every bound program takes first: the parameters, as they
        are laid over the tp mesh when there is one."""
        return self.params if self._tp_params is None else self._tp_params

    def _run(self, op, pools_and_args):
        return self.programs[op](self.step_params, *pools_and_args)

    def prefill(self, *pools_and_args):
        return self._run("prefill", pools_and_args)

    def decode(self, *pools_and_args):
        return self._run("decode", pools_and_args)

    def prefill_chunk(self, *pools_and_args):
        return self._run("prefill_chunk", pools_and_args)

    def spec_score(self, *pools_and_args):
        return self._run("spec_score", pools_and_args)


# ---------------------------------------------------------------------------
# full-forward adapters (no cache hooks): Gluon Blocks and .mxtpu artifacts
# ---------------------------------------------------------------------------


class BlockLM:
    """Serve an initialized Gluon causal LM Block: tokens (B, S) ->
    logits (B, S, V) (or time-major (S, B) -> (S*B, V) like
    models.word_lm.RNNModel with time_major=True)."""

    uses_cache = False

    def __init__(self, block, vocab, max_len, time_major=False):
        from ..parallel.functional import functionalize
        apply_fn, _names, values = functionalize(block, train_mode=False)
        self.vocab = vocab
        self.max_len = max_len

        def logits_fn(vals, toks):                       # toks (B, S) int32
            B, S = toks.shape
            if time_major:
                out = apply_fn(vals, toks.T.astype(jnp.float32))
                out = out.reshape(S, B, -1).transpose(1, 0, 2)
            else:
                out = apply_fn(vals, toks)
            return out                                   # (B, S, V)

        def serving_step_full(vals, toks, lengths):
            out = logits_fn(vals, toks)
            rows = jnp.take_along_axis(
                out, (lengths - 1)[:, None, None], axis=1)[:, 0]
            return rows.astype(jnp.float32)              # (B, V)

        self._values = values
        self._step_jit = telemetry.introspect.instrument(
            jax.jit(serving_step_full), site="serving.step_full",
            argnames=("values", "tokens", "lengths"))

    def step_full(self, tokens, lengths, phase=None):
        # one jit serves both prefill and decode; the caller's `phase`
        # attributes each compile to the side that triggered it
        return self._step_jit(self._values, tokens, lengths, _phase=phase)


class ExportedLM:
    """Serve a `.mxtpu` artifact (predict.export_model) whose one input is
    int token ids (B_sig, S_sig) and whose first output is logits
    (B_sig, S_sig, V). The program shape is frozen at export, so serving
    pads/chunks each decode batch to the exported signature — the
    engine-side generalization of Predictor.predict's pad/bucket
    helper."""

    uses_cache = False

    def __init__(self, artifact):
        from ..predict import ExportedPredictor, load_exported
        pred = (artifact if isinstance(artifact, ExportedPredictor)
                else load_exported(artifact))
        desc = pred.input_descs
        if len(desc) != 1 or len(desc[0]["shape"]) != 2:
            raise MXNetError(
                "ExportedLM needs an artifact with ONE (batch, seq) token "
                "input; got %r" % (desc,))
        self._pred = pred
        self.sig_batch, self.sig_len = desc[0]["shape"]
        self.max_len = self.sig_len
        self._dtype = desc[0]["dtype"]
        self.vocab = None  # unknown until the first forward
        # the artifact compiles inside jax.export's call machinery — the
        # watchdog can observe (time first-signature calls) but not AOT
        # it, so no memory analysis on this site
        self._call = telemetry.introspect.instrument(
            lambda buf: pred._exported.call(buf),
            site="serving.exported_call", argnames=("tokens",),
            owned=False)

    def step_full(self, tokens, lengths, phase=None):
        """tokens (B, S<=sig_len) int -> f32 logits (B, V) at lengths-1,
        chunking over the exported batch size."""
        tokens = np.asarray(tokens)
        lengths = np.asarray(lengths)
        B, S = tokens.shape
        if S > self.sig_len:
            raise MXNetError("sequence length %d exceeds the exported "
                             "signature %d" % (S, self.sig_len))
        buf = np.zeros((self.sig_batch, self.sig_len), self._dtype)
        out_rows = []
        for lo in range(0, B, self.sig_batch):
            chunk = tokens[lo:lo + self.sig_batch]
            buf[:] = 0
            buf[:len(chunk), :S] = chunk
            logits = np.asarray(self._call(buf, _phase=phase)[0],
                                np.float32)              # (Bs, Ss, V)
            self.vocab = logits.shape[-1]
            take = lengths[lo:lo + self.sig_batch] - 1
            out_rows.append(logits[np.arange(len(chunk)), take])
        return np.concatenate(out_rows, axis=0)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class PoolsLost(MXNetError):
    """A step failed after it had consumed the KV pools. The engine has
    made empty pools and dropped its prefix cache; every sequence it
    holds has lost its history on the device and must be replayed
    (prompt plus the tokens generated so far) — the failed step alone
    is not enough."""


#: every live Engine, weakly held — the serving tests' shared quiescence
#: fixture audits the pools of engines a test created (leak check: after
#: a clean close, in-use blocks == prefix-cache residents, nothing else)
_LIVE = weakref.WeakSet()


class Engine:
    """Owns the compiled step functions, the cache pool, and the shape
    buckets. Thread-compatible, not thread-safe: all compute entry points
    (`start`, `decode_step`, `decode_pass`) must be called from one
    serving thread (the server loop): every step consumes the pool arrays
    and the cache is rebound from its results (`_step`), which only one
    thread may do.

    Placement flags (`paged`, `tp`, `prefill_chunk`) are read at
    CONSTRUCTION only and frozen afterwards: the compiled step functions,
    the cache layout, and the mesh placement are all derived from them at
    bind time, so a post-start mutation could leave a replica straddling
    two configs (half the pool sharded one way, jits traced another).
    Assigning any of them after `__init__` raises; build a new Engine (or
    replica) instead."""

    #: flags the engine derives compiled state from — construction-only
    _FROZEN_FLAGS = frozenset(
        ("paged", "paged_requested", "prefill_chunk", "tp",
         "tp_requested", "mesh", "device", "prefix_cache", "aot_cache",
         "spec", "spec_requested", "spec_k", "draft",
         "kv_quant", "kv_quant_requested", "weight_quant"))

    def __init__(self, model, max_batch=8, max_len=None, block_size=16,
                 num_blocks=None, keep_logits=False, paged=None,
                 prefill_chunk=None, tp=None, devices=None,
                 prefix_cache=None, aot_cache=None, draft=None,
                 spec=None, spec_k=None, kv_quant=None,
                 weight_quant=None):
        from ..ops.pallas_paged import (paged_enabled,
                                        paged_fallback_reason)
        from ..ops.pallas_attention import default_interpret
        from .tp import (serving_tp, tp_fallback_reason, build_tp_mesh,
                         kv_pool_spec, kv_scale_spec)
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .. import aot
        # persistent AOT executable cache (ISSUE 16): `aot_cache=` names
        # a directory (configuring it process-wide — the watchdog seam
        # the jits compile through is process-global); None defers to
        # MXNET_AOT_CACHE_DIR. Resolved BEFORE bind() so this engine's
        # own compiles warm-load/publish. Like every placement flag it
        # switches where executables come from, never logits.
        if aot_cache is not None:
            aot.configure(str(aot_cache))
        self.aot_cache = aot.cache_dir()
        self.model = model
        self.max_batch = max_batch
        self.max_len = int(max_len or model.max_len)
        self.keep_logits = keep_logits
        self._sigs = set()
        self.cache = None
        # tensor parallel: env default (MXNET_SERVING_TP), explicit
        # `tp=` overrides. tp>1 implies the paged path (the gather
        # oracle is deliberately single-device); configs the tp step
        # can't shard fall back to tp=1 with the reason recorded on
        # `tp_fallback` — the flag switches placement, never logits.
        tp_req = serving_tp() if tp is None else int(tp)
        if tp_req < 1:
            raise MXNetError("tp must be >= 1, got %d" % tp_req)
        self.tp_requested = tp_req
        self.tp_fallback = None
        self.tp = 1
        self.mesh = None
        self.device = None      # the one chip of a placed tp=1 engine
        if tp_req > 1 and paged is False:
            self.tp_fallback = ("paged=False pins the single-device "
                                "gather oracle")
            tp_req = 1
        # paged path: env default (MXNET_PAGED_ATTENTION), explicit
        # `paged=` overrides; shapes the Mosaic kernel can't tile fall
        # back to the gather path (interpret mode takes anything) with
        # the reason recorded on `paged_fallback`
        self.paged_requested = (tp_req > 1) or (
            paged_enabled() if paged is None else bool(paged))
        self.paged = False
        self.paged_fallback = None
        # why the gather path's decode step walks the cache with XLA's
        # loop and not with the kernel (ops/pallas_decode_walk.py):
        # `kv_cache.walk_unfit` asked of the pool itself, as the view
        # asks it of the same pool while the step is traced. None where
        # the kernel walks it, and where no gather step does (paged, no
        # cache)
        self.walk_fallback = None
        # why a whole prompt's attention is XLA's, block by block, and
        # not the kernel (ops/pallas_prompt_attention.py), as far as an
        # engine knows once (backend, head_dim, dtype):
        # `kv_cache.prompt_attn_unfit` asked of the pool, as the view
        # asks it while a prefill program is traced. The bucket is a
        # program's: `prefill_step` asks again with it and says which of
        # the two the program holds on its span (`attn`). None where the
        # kernel may, and where no whole prompt is prefilled (paged, no
        # cache)
        self.prompt_attn_fallback = None
        # why a decode step updates the recurrent states of a "state"
        # kind with XLA's gather and scatter and not with the kernel
        # (ops/pallas_ssm_step.py): `kv_cache.state_step_unfit`, asked
        # the same way. None where the kernel does, and with no such kind
        self.state_step_fallback = None
        # why the held experts of an expert layer are XLA's loop over
        # tiles and not the grouped-product kernel
        # (ops/pallas_grouped_experts.py): the family's `moe_unfit`,
        # asked of the matrices it will hand every trace. None where the
        # kernel runs, and with no expert layer. `moe`: which of the two
        # both step programs hold (`kernel` or `xla`: on their spans and
        # in the serving metrics' counts), None with no expert layer
        unfit = getattr(model, "moe_unfit", None)
        self.moe_fallback = unfit() if unfit else None
        self.moe = None if unfit is None \
            else "xla" if self.moe_fallback else "kernel"
        self.prefill_chunk = 0
        # quantized serving (ISSUE 20): env defaults
        # (MXNET_QUANTIZED_KV / MXNET_QUANTIZED_WEIGHTS), explicit
        # `kv_quant=` / `weight_quant=` override. The standing contract
        # generalizes from "flag switches placement, never logits" to
        # "flag switches PRECISION, with a pinned tolerance + logit-
        # error budget vs the f32 oracle" — the gather path and the f32
        # pool stay verbatim as the oracle; ineligible configs record
        # their reason on `kv_quant_fallback` / `weight_quant_fallback`
        # and fall back.
        kvq_req = (quantized_kv_enabled() if kv_quant is None
                   else bool(kv_quant))
        wq_req = (quantized_weights_env() if weight_quant is None
                  else (weight_quant or None))
        self.kv_quant_requested = kvq_req
        self.kv_quant = False
        self.kv_quant_fallback = None
        self.weight_quant = None
        self.weight_quant_fallback = None
        self.quant_logit_error = None   # parity seam: bench/tests record
                                        # the measured max |logit - f32|
        if wq_req is not None:
            if not hasattr(model, "quantize_weights"):
                self.weight_quant_fallback = (
                    "model family has no weight hooks (BlockLM/"
                    "ExportedLM serve their own parameters f32)")
            else:
                model.quantize_weights(str(wq_req))
                self.weight_quant = str(wq_req)
        if kvq_req and not model.uses_cache:
            self.kv_quant_fallback = ("model family has no cache hooks "
                                      "(int8 KV needs the paged pool)")
        if self.paged_requested and not model.uses_cache:
            self.paged_fallback = ("model family has no cache hooks "
                                   "(there is no block pool to walk)")
        if model.uses_cache:
            cspec = model.cache_spec()
            dh, dt = cspec.head_dim, cspec.dtype
            self._nblk = max(1, math.ceil(self.max_len / block_size))
            # a pool a kind of layer, each as large as max_batch
            # sequences of max_len can fill: every block, or a ring's
            # worth where a window is; `num_blocks` sizes the first
            rings = [cspec.ring(k, block_size) for k in cspec.kinds]
            sized = [max_batch * (min(r, self._nblk) if r else self._nblk)
                     + 1 for r in rings]
            if num_blocks is not None:
                sized[0] = num_blocks
            num_blocks = tuple(sized)
            if self.paged_requested:
                self.paged_fallback = cspec.paged_unfit()
            if self.paged_requested and self.paged_fallback is None:
                self.prefill_chunk = min(self.max_len,
                                         int(prefill_chunk
                                             or 2 * block_size))
                self.paged_fallback = paged_fallback_reason(
                    dh, block_size, default_interpret(), dt)
                self.paged = self.paged_fallback is None
            if kvq_req:
                if not self.paged:
                    self.kv_quant_fallback = (
                        "int8 KV needs the paged path "
                        "(MXNET_PAGED_ATTENTION=1 / Engine(paged=True) "
                        "and a tileable config); the gather oracle "
                        "reads the f32 pool")
                else:
                    self.kv_quant_fallback = paged_fallback_reason(
                        dh, block_size, default_interpret(), jnp.int8)
                    self.kv_quant = self.kv_quant_fallback is None
            self.cache = PagedKVCache.of(
                cspec, block_size=block_size, num_blocks=num_blocks,
                kv_dtype="int8" if self.kv_quant else None)
            if tp_req > 1:
                reason = tp_fallback_reason(model.cfg, self.paged,
                                            tp_req, devices)
                if reason is not None:
                    self.tp_fallback = reason
                else:
                    self.mesh = build_tp_mesh(tp_req, devices)
                    self.tp = tp_req
                    self.cache.place(
                        NamedSharding(self.mesh, kv_pool_spec()),
                        NamedSharding(self.mesh, kv_scale_spec())
                        if self.kv_quant else None)
            if not self.paged:
                self.walk_fallback = walk_unfit(self.cache.k, cspec.layout)
                self.prompt_attn_fallback = prompt_attn_unfit(
                    self.cache.k, cspec.q_group, layout=cspec.layout)
                if None in (self.walk_fallback, self.prompt_attn_fallback) \
                        or self.moe == "kernel":
                    preload_pallas()
                if "state" in cspec.kinds:
                    self.state_step_fallback = state_step_unfit(
                        self.cache.ssm_state)
            model.bind(block_size, paged=self.paged, kv_quant=self.kv_quant,
                       mesh=self.mesh)
            if self.tp == 1 and devices:
                # a one-chip engine that was given a window (the router
                # gives every replica one) commits its parameters and
                # its pool to that chip: every step then runs there, and
                # N replicas hold N chips instead of all sharing chip 0
                self.device = devices[0]
                model.place(self.device)
                self.cache.place(self.device, self.device)
            # what a decode step launched with none in flight takes for
            # the last step's tokens (`carried_tokens`: no row refers to
            # it), placed as a step returns them, so that it and a step
            # launched ahead are one signature: one program a bucket.
            # Put there as it is: no program is compiled to make it
            self._no_carry = jax.device_put(
                np.zeros((max_batch,), np.int32),
                NamedSharding(self.mesh, P()) if self.mesh is not None
                else self.device)
        elif tp_req > 1:
            self.tp_fallback = ("model family has no cache hooks "
                                "(BlockLM/ExportedLM run single-device)")
        # prefix cache: env default (MXNET_PREFIX_CACHE), explicit
        # `prefix_cache=` overrides. Needs the chunked-prefill paged
        # path (a prefill that can START mid-prompt); ineligible configs
        # fall back with the reason recorded — the flag switches which
        # blocks a table points at, never logits.
        self.prefix_cache = None
        self.prefix_cache_fallback = None
        self._cow_jit = None
        self._zero_jit = None     # scale-reset jit for freshly allocated
                                  # blocks on the int8 pool
        want_prefix = (prefix_cache_enabled() if prefix_cache is None
                       else bool(prefix_cache))
        if want_prefix:
            if not model.uses_cache:
                self.prefix_cache_fallback = (
                    "model family has no cache hooks (prefix reuse "
                    "needs the paged KV pool)")
            elif not self.paged:
                self.prefix_cache_fallback = (
                    "prefix reuse needs the chunked-prefill paged path "
                    "(MXNET_PAGED_ATTENTION=1 / Engine(paged=True)); "
                    "the gather oracle prefills whole prompts")
            else:
                self.prefix_cache = PrefixCache(self.cache.pool,
                                                block_size)
        # speculative decoding (ISSUE 19): a draft LM proposes spec_k
        # tokens per decode iteration and the target scores all k+1
        # positions in ONE ragged paged pass; greedy verification
        # accepts a prefix, so the flag switches SPEED, never logits.
        # Env default (MXNET_SPEC_DECODE + MXNET_SPEC_DRAFT_LAYERS for
        # an env-only self-draft), explicit `draft=`/`spec=` overrides;
        # ineligible configs keep the verbatim per-token decode as the
        # fallback + parity oracle with the reason on `spec_fallback`.
        from . import spec as _spec
        self.spec_requested = (bool(spec) if spec is not None
                               else (_spec.spec_decode_enabled()
                                     or draft is not None))
        self.spec_k = (int(spec_k) if spec_k is not None
                       else _spec.spec_k())
        if self.spec_k < 1:
            raise MXNetError("spec_k must be >= 1, got %d" % self.spec_k)
        self.spec = False
        self.spec_fallback = None
        self.draft = None
        self.chaos_spec_poison = False   # armed per-iteration by the
                                         # serving loop's chaos seam
        self.last_spec = None            # most recent pass's accounting
        self.spec_passes = 0
        self.spec_proposed_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_fallbacks = 0
        if self.spec_requested:
            d = _spec.build_draft(draft, model)
            reason = _spec.spec_fallback_reason(model, d, self.paged)
            if reason is not None:
                self.spec_fallback = reason
            else:
                # the draft stays replicated (its jit never touches the
                # mesh) while the target's scoring pass shards with tp —
                # same placement split the tentpole demands
                self.draft = d
                self.spec = True
        # per-engine compile counters, fed by the watchdog's per-thread
        # dispatch attribution (telemetry/introspect.py): each model call
        # below is bracketed by `_count`, which adds exactly the compiles
        # THIS engine's call paid — so engines sharing one model adapter
        # (replicas over a BlockLM, a rebound TransformerLM) never absorb
        # a sibling's warm-up compiles, matching the pre-migration
        # engine-local ints while the watchdog stays the source of truth
        self._compile_counts = {"prefill": 0, "decode": 0}
        # ... and the warm-load tally (ISSUE 16): executables this
        # engine's calls LOADED from the persistent AOT cache instead of
        # compiling — kept apart from _compile_counts so the
        # recompile-bound tests stay meaningful with the cache on
        self._warm_counts = {"prefill": 0, "decode": 0}
        # prefill programs run (whole prompts or chunks) and the rows of
        # their buckets: a token's record says how many ran between it
        # and the one before it (`record_tokens`)
        self.prefills_run = 0
        self.prefill_tokens_run = 0
        # whole-prompt prefills launched whose first token the host has
        # not read, oldest first (`collect_firsts`)
        self._firsts = []
        self.pools_lost = 0     # times the pools had to be made anew
        self._constructed = True
        _LIVE.add(self)

    def __setattr__(self, name, value):
        if name in self._FROZEN_FLAGS and \
                getattr(self, "_constructed", False):
            raise MXNetError(
                "Engine.%s is fixed at construction (the compiled steps, "
                "cache layout, and mesh placement derive from it); build "
                "a new Engine instead of mutating a live one" % name)
        object.__setattr__(self, name, value)

    # -- admission accounting ------------------------------------------------

    def blocks_needed(self, prompt_len, max_new):
        """Blocks a request reserves, a count a kind of layer."""
        if self.cache is None:
            return ()
        total = min(self.max_len, prompt_len + max_new)
        return self.cache.blocks_by_kind(total)

    def can_admit(self, prompt_len, max_new):
        """Would this request's block reservation fit right now? With
        the prefix cache on, refcount-zero cached blocks count as
        available — `try_alloc` reclaims them LRU on demand, so a cache
        that has absorbed the free list is capacity, not exhaustion
        (without this the scheduler would gate admission forever and
        the reclaimer, which only runs inside allocation, would never
        fire). The count is a cheap upper bound (an interior entry
        pinned through a child may not be reclaimable THIS instant);
        over-admission is safe — `begin` returns None on the transient
        shortfall and the serving loop requeues in order."""
        if prompt_len > self.max_len:
            raise MXNetError("prompt length %d exceeds max_len %d"
                             % (prompt_len, self.max_len))
        if self.cache is None:
            return True
        avail = [pool.available for pool in self.cache.pools]
        if self.prefix_cache is not None:
            avail[0] += self.prefix_cache.reclaimable_blocks()
        return all(n <= a for n, a in
                   zip(self.blocks_needed(prompt_len, max_new), avail))

    def cache_utilization(self):
        return self.cache.utilization() if self.cache else None

    def kv_bytes_per_token(self):
        """Bytes of KV-cache one token occupies on this engine (both the
        K and the V plane, or the one latent row, every layer): the unit
        the migration ledger
        prices a prefix-cache hit in — a migration hop whose target
        already holds a block skips re-prefilling block_size tokens,
        i.e. this many bytes per token of KV it did not have to
        rebuild. 0 when the model family keeps no cache. A recurrent
        state is no token's: a sequence holds `CacheSpec.state_bytes()`
        of it whatever its length, and a hop rebuilds all of it."""
        if self.cache is None:
            return 0
        spec = self.cache.spec
        if self.cache.quantized:
            # int8 payload plus the f32 per-block-per-head scale
            # sidecars amortized over the block's tokens — the ledger
            # must price the QUANTIZED layout or disagg bytes-saved
            # overstates a migration hop's savings ~4x
            scale_bytes = math.ceil(2 * spec.n_layers * spec.n_heads * 4
                                    / float(self.cache.block_size))
            return spec.values_per_token() + scale_bytes
        return spec.values_per_token() * np.dtype(spec.dtype).itemsize

    @property
    def prefill_compilations(self):
        """Prefill-path compilations THIS engine's calls paid, counted
        by the compile watchdog at the real jit seam
        (telemetry/introspect.py) — no longer a hand-maintained proxy.
        The signature-bound tests pin the same <=2 chunked / per-bucket
        dense contract as before the migration."""
        return self._compile_counts["prefill"]

    @property
    def decode_compilations(self):
        """Watchdog-counted decode-path compilations (see
        `prefill_compilations`)."""
        return self._compile_counts["decode"]

    @property
    def prefill_warm_loads(self):
        """Prefill executables this engine's calls warm-loaded from the
        persistent AOT cache (mxnet_tpu/aot) instead of compiling."""
        return self._warm_counts["prefill"]

    @property
    def decode_warm_loads(self):
        """Decode-path warm loads (see `prefill_warm_loads`)."""
        return self._warm_counts["decode"]

    @property
    def warm_loads(self):
        """Total executables this engine warm-loaded from the AOT cache
        — the router's warm-start gauge counts replicas where this is
        positive."""
        return sum(self._warm_counts.values())

    @contextlib.contextmanager
    def _count(self, kind, sig):
        """Bracket one model step call: record its shape-bucket signature
        (test failure messages show it) and add the compiles the call
        paid — per-thread attribution, so a sibling engine sharing this
        adapter never inflates these counters — to this engine's tally.
        Warm AOT-cache loads are tallied separately on the same seam."""
        self._sigs.add((kind, sig))
        mark = telemetry.introspect.dispatch_mark()
        wmark = telemetry.introspect.dispatch_warm_mark()
        try:
            yield
        finally:
            # a dispatch that compiled then FAILED to run still paid the
            # compile; count it even as the exception propagates
            self._compile_counts[kind] += \
                telemetry.introspect.dispatch_compiles_since(mark)
            self._warm_counts[kind] += \
                telemetry.introspect.dispatch_warm_loads_since(wmark)

    # -- the pools' one way in and out -----------------------------------------

    @contextlib.contextmanager
    def _donating(self):
        """Round every call that donates pool arrays. A fault raised
        before the launch (tracing, a shape error, a chaos seam) leaves
        the pools whole and passes through as it is. A fault after the
        launch leaves them deleted, and every later step would raise for
        ever: make them anew under the same placement, drop every
        prefix-cache entry (the blocks' contents are gone), and raise
        `PoolsLost`, which tells the caller to replay every sequence
        this engine holds, not only the one at hand."""
        try:
            yield
        except Exception as e:
            if not self.cache.lost():
                raise
            raise self._pools_lost(e) from e

    def _pools_lost(self, e):
        """The pools made anew after `e`, a fault of a program that had
        consumed them, and the `PoolsLost` to raise for it."""
        self.cache.remake()
        if self.prefix_cache is not None:
            self.prefix_cache.clear()
        self.pools_lost += 1
        return PoolsLost(
            "a step failed after it consumed the KV pools (%s: %s); "
            "the pools were made anew, empty: replay every running "
            "and prefilling sequence" % (type(e).__name__, e))

    def _read_back(self, step_span, result, stats):
        """A step's result on the host. What a family's step returns
        beside its results (`stats`: the dropless family's rows per held
        expert) comes over in the same transfer and goes to the family's
        `note_step`, whose answer (counts) the step's span carries, added
        up where a pass collects two steps under one span."""
        if not stats:
            return np.asarray(result)
        result, *stats = jax.device_get([result, *stats])
        for name, n in self.model.note_step(*stats).items():
            step_span.attrs[name] = step_span.attrs.get(name, 0) + n
        return result

    def _step(self, fn, *args):
        """Call one step program (or the copy-on-write op): hand it the
        pools first, take the pools it returns as the cache's, return
        the rest of its results."""
        pools = self.cache.arrays()
        with self._donating():
            out = fn(*pools, *args)
        self.cache.rebind(out[:len(pools)])
        return out[len(pools):]

    # -- prefill -------------------------------------------------------------

    def begin(self, prompt, max_new, eos_id=None):
        """Admit one request: allocate its cache blocks, no compute.
        Prefill is advanced by `prefill_step` (one chunk per call on the
        paged path; the whole prompt in one call otherwise). Returns the
        Sequence, or None if blocks ran out (transient)."""
        L = len(prompt)
        if L < 1:
            raise MXNetError("empty prompt")
        seq = Sequence(prompt, min(self.max_len, L + max_new), eos_id)
        seq.prefills_seen = self.prefills_run
        seq.prefill_tokens_seen = self.prefill_tokens_run
        if self.keep_logits:
            seq.token_logits = []
        if self.cache is not None:
            n = self.blocks_needed(L, max_new)
            if self.prefix_cache is None:
                blocks = self.cache.try_alloc(n)
                if blocks is not None and self.kv_quant:
                    self._zero_scales(blocks[0], held=blocks[0])
            else:
                ids = self._begin_cached(seq, prompt, n[0])
                blocks = None if ids is None else (ids,)
            if blocks is None:
                return None
            seq.blocks = blocks
            seq.table_row = self.cache.row(blocks, self._nblk)
        return seq

    def _zero_scales(self, ids, held):
        """Reset the int8 pool's scale sidecars for freshly allocated
        (possibly reclaimed) blocks: `write_kv_quant`'s per-block scale
        is a monotonic max, so a previous occupant's scale would pin the
        new tokens' quantization step far too coarse. Padded to pow2
        id-array buckets so the jit lattice stays bounded; the pad
        entries hit block 0 (the null block, whose scale is always 0).
        A fault gives `held`, the admission's blocks, back to the pool:
        no sequence owns them yet."""
        if not ids:
            return
        n = pow2_bucket(len(ids), lo=1, hi=self.cache.num_blocks)
        arr = np.zeros((n,), np.int32)
        arr[:len(ids)] = ids
        if self._zero_jit is None:
            self._zero_jit = jax.jit(zero_block_scales,
                                     donate_argnums=(0, 1))
        try:
            with self._donating():
                self.cache.k_scale, self.cache.v_scale = self._zero_jit(
                    self.cache.k_scale, self.cache.v_scale,
                    jnp.asarray(arr))
        except Exception:
            self.cache.pool.free(held)
            raise

    def _begin_cached(self, seq, prompt, n):
        """Prefix-cache admission: point the leading table entries at
        resident shared blocks (refs taken by the lookup), allocate the
        rest fresh, and COW-copy a partially-matched tail block — this
        request WILL write into it (the rest of its prompt, then
        decode), and a reader must never mutate a shared block. Skipped
        prefix tokens start `seq.prefilled` past zero, so whole prefill
        chunks never run. Returns the table's id list, or None on
        transient exhaustion (all refs dropped)."""
        pool = self.cache.pool
        with telemetry.span("prefix.lookup", category="serving",
                            prompt_len=len(prompt)):
            full, tail = self.prefix_cache.lookup(prompt)
        fresh = pool.try_alloc(n - len(full))
        held = full + ([tail[0]] if tail else [])
        if fresh is None:
            if held:
                pool.free(held)
            return None
        held = held + fresh
        if self.kv_quant:
            # fresh (possibly reclaimed) blocks first — a COW copy below
            # then installs the shared block's scales over fresh[0]
            self._zero_scales(fresh, held)
        hit = len(full) * self.cache.block_size
        if tail is not None:
            src, m = tail
            if self._cow_jit is None:
                # like every step it consumes the pools, so XLA updates
                # the one block in place
                self._cow_jit = jax.jit(copy_block, donate_argnums=tuple(
                    range(len(self.cache.arrays()))))
            try:
                self._step(self._cow_jit, jnp.int32(src),
                           jnp.int32(fresh[0]))
            except Exception:
                pool.free(held)       # no sequence owns them yet
                raise
            pool.free([src])          # drop the transient tail ref: the
                                      # private copy replaces it in the
                                      # table
            self.prefix_cache.cow_copies += 1
            hit += m
        seq.prefilled = hit
        seq.cache_hit_tokens = hit
        seq.shared_blocks = len(full)
        return full + fresh

    def prefill_tokens_per_step(self, prompt_len):
        """Tokens one `prefill_step` call will process — the scheduler's
        token-budget admission cost. Fixed chunk on the paged path; the
        whole (bucketed) prompt in one shot on the others."""
        if self.model.uses_cache and self.paged:
            return self.prefill_chunk
        return pow2_bucket(prompt_len, lo=1, hi=self.max_len)

    @property
    def first_sync_reason(self):
        """Why a prefill's first token is read before anything else is
        done, or None where the next decode step can take it on the
        device (`First`): a whole-prompt prefill of the gather path,
        under an engine whose steps stay in flight. `sync_reason` says
        why they cannot; the paged path's prompt comes in chunks, and its
        steps take a token from the host or from the step before."""
        if self.sync_reason is None and self.paged:
            return "paged"
        return self.sync_reason

    def prefill_step(self, seq, hold=False):
        """Advance one sequence's prefill. Paged path: run ONE
        fixed-shape chunk (appending its K/V to the pool); other paths:
        run the whole prompt. Returns True when the prompt is fully
        prefilled: its first token has been chosen and appended or, with
        `hold` where the engine can carry it (`first_sync_reason`), is
        left in flight as `seq.first` for `decode_pass` to launch from
        and `collect_firsts` to read."""
        if self.model.uses_cache and not self.paged:
            first = self._launch_first(
                seq, hold and self.first_sync_reason is None)
            if first.ahead:
                seq.first = first
                self._firsts.append(first)
            else:
                self._collect_first(first)
            return True
        L = seq.prompt_len
        prompt = seq.tokens[:L]
        rid = seq.request.trace if seq.request is not None else None
        with telemetry.span("serving.prefill", trace=rid,
                            category="serving", prompt_len=L, length=L,
                            chunk_start=seq.prefilled, ahead=0):
            if self.model.uses_cache:
                C = self.prefill_chunk
                qs = seq.prefilled
                toks = np.zeros((C,), np.int32)
                toks[:min(C, L - qs)] = prompt[qs:qs + C]
                w = pow2_bucket(self.cache.blocks_for(qs + C),
                                lo=1, hi=self._nblk)
                with self._count("prefill", (C, w)):
                    logits, = self._step(
                        self.model.prefill_chunk, jnp.asarray(toks),
                        jnp.int32(qs),
                        jnp.int32(L), jnp.int32(min(L - 1 - qs, C - 1)),
                        jnp.asarray(seq.table_row[:w]))
                self._ran_prefill(C)
                seq.prefilled = min(L, qs + C)
                if seq.prefilled < L:
                    return False
                logits = np.asarray(logits)
                if self.prefix_cache is not None:
                    # the full prompt blocks are immutable from here on
                    # (decode writes start past the prompt): register
                    # them now so a same-prefix burst hits while this
                    # request is still decoding. The partial tail stays
                    # private until release — decode keeps writing it.
                    self.prefix_cache.insert(prompt, seq.block_ids, L)
            else:
                s_pad = pow2_bucket(L, lo=1, hi=self.max_len)
                toks = np.zeros((1, s_pad), np.int32)
                toks[0, :L] = prompt
                with self._count("prefill", s_pad):
                    logits = np.asarray(self.model.step_full(
                        jnp.asarray(toks), jnp.asarray([L], np.int32),
                        phase="prefill"))[0]
                self._ran_prefill(s_pad)
                seq.prefilled = L
            # the host holds the prefill's result: a client could read
            # the first token from here on
            seq.t_last_token = time.perf_counter()
        self._keep_logits(seq, logits)
        self._append(seq, int(np.argmax(logits)))
        return True

    def _keep_logits(self, seq, logits):
        if self.keep_logits:
            seq.last_logits = logits
            if seq.token_logits is not None:
                seq.token_logits.append(logits)

    def _launch_first(self, seq, ahead):
        """Dispatch the whole-prompt prefill of the gather path and the
        choice of its first token; nothing of either is read. What
        raises here (tracing, shapes, a chaos seam; `PoolsLost` from a
        program that had the pools) is this request's fault alone."""
        L = seq.prompt_len
        s_pad = pow2_bucket(L, lo=min(8, self.max_len), hi=self.max_len)
        toks = np.zeros((s_pad,), np.int32)
        toks[:L] = seq.tokens[:L]
        spec = self.cache.spec
        seq.attn = "xla" if prompt_attn_unfit(
            self.cache.k, spec.q_group, s_pad, spec.layout) else "kernel"
        first = First(seq, ahead, prompt_len=L, length=L,
                      chunk_start=seq.prefilled, bucket=s_pad, attn=seq.attn,
                      **({"moe": self.moe} if self.moe else {}))
        try:
            with self._count("prefill", s_pad):
                logits, *first.stats = self._step(
                    self.model.prefill, jnp.asarray(toks), jnp.int32(L),
                    jnp.asarray(seq.table_row))
            first.token = first_token(logits)
        except Exception as e:
            first.close(error=type(e).__name__)
            raise
        seq.prefilled = L
        if self.keep_logits:
            first.logits = logits
        # the copy back starts as soon as the prefill has run
        for result in (first.token, *first.stats):
            result.copy_to_host_async()
        return first

    def _collect_first(self, first):
        """The blocking half: the first token on the host (the read
        returns when the prefill has run, whatever was launched behind
        it), the family's `note_step`, the span closed, the token
        appended. The prefill counts as run from here on: the tokens of
        a step collected before this read did not wait for it, those of
        the steps behind it did (`record_tokens`). A fault here is one of
        a program that had consumed the pools, as has every program
        queued behind it since: `PoolsLost`."""
        seq = first.seq
        seq.first = None
        try:
            token = int(self._read_back(first, first.token, first.stats))
            if first.logits is not None:
                self._keep_logits(seq, np.asarray(first.logits))
        except Exception as e:
            first.close(error=type(e).__name__)
            raise self._pools_lost(e) from e
        self._ran_prefill(first.attrs["bucket"])
        # the host holds the prefill's result: a client could read the
        # first token from here on
        seq.t_last_token = time.perf_counter()
        first.close(int(seq.t_last_token * 1e6))
        self._append(seq, token)

    def collect_firsts(self):
        """Read the first tokens in flight, oldest first, which is the
        device's order: yields each sequence as its token is appended.
        The serving loop does so once the pass's step is launched and the
        step before it collected and accounted for."""
        while self._firsts:
            first = self._firsts.pop(0)
            self._collect_first(first)
            yield first.seq

    def drop_firsts(self):
        """Forget the first tokens in flight, unread (a fault, a replay,
        the loop's end): none was appended, so every sequence's tokens
        are still exactly those the host has read, and a replay from
        them chooses the dropped ones again."""
        for first in self._firsts:
            first.seq.first = None
        self._firsts = []

    def _ran_prefill(self, rows):
        self.prefills_run += 1
        self.prefill_tokens_run += rows

    def start(self, prompt, max_new, eos_id=None, hold=False):
        """Admit one request and run its whole prefill: allocate blocks,
        prefill (chunk-by-chunk on the paged path), sample the first
        token. Returns the live Sequence (caller keeps it in the running
        set), or None if blocks ran out (transient). The serving loop
        uses begin/prefill_step on the paged path, so chunks interleave
        with decode steps, and `hold` on the other (`prefill_step`);
        without it `start` is the synchronous convenience for direct
        Engine users (bench.py, tests)."""
        seq = self.begin(prompt, max_new, eos_id=eos_id)
        if seq is None:
            return None
        try:
            while not self.prefill_step(seq, hold):
                pass
        except Exception:
            self.release(seq, reusable=False)   # nobody else holds it
            raise
        return seq

    # -- decode --------------------------------------------------------------

    def decode_tokens_per_step(self):
        """Tokens one decode iteration SCORES per running sequence — the
        scheduler's per-iteration/per-tenant budget cost and the fair
        price next to prefill chunks: a speculating sequence occupies
        k+1 scored positions per step, a plain one exactly 1."""
        return self.spec_k + 1 if self.spec else 1

    @property
    def sync_reason(self):
        """Why a step of this engine cannot stay in flight while the next
        is launched, or None where it can: on the single-token cache path
        the next step's input is the last one's result, on the device.
        A speculative engine reads acceptance counts to place the next
        tokens, a cache-free model is fed the token history from the
        host, and a `keep_logits` engine hands every step's logits over:
        their next input exists on the host alone."""
        if self.spec:
            return "spec"
        if not self.model.uses_cache:
            return "no_cache"
        return "keep_logits" if self.keep_logits else None

    def decode_step(self, seqs):
        """Advance every sequence in `seqs` (one fused jit call over the
        power-of-two padded batch): launch the step, then collect it,
        the synchronous contract of `decode_pass` for direct Engine
        users (bench.py, tools, tests, the parity oracles).
        Non-speculative engines emit exactly one token per sequence;
        speculative engines emit 1..spec_k+1 accepted tokens per
        sequence per call, token-identical to the plain path. A draft
        fault (non-finite logits — the `serve_spec_poison` chaos seam or
        a real draft bug) degrades THIS batch to the verbatim
        non-speculative path."""
        collected, _ = self.decode_pass(seqs, hold=False)
        return [row[0] for row in collected[0].advanced] if collected else []

    def decode_pass(self, seqs, after=None, hold=True):
        """One pass of the decode pipeline, under one `serving.decode`
        span: LAUNCH the next step over `seqs` (build, dispatch; its
        tokens stay on the device), then COLLECT `after`, the step the
        pass before left in flight (the blocking read, `note_step`, the
        appends). So the device runs the new step while the host reads,
        appends and accounts for the old one, admits and builds again.
        Returns (collected, in_flight): the steps whose tokens this pass
        appended, oldest first, each with `advanced` (its sequences that
        took a token, with their lengths before and after and the token's
        gap), and the step left in flight for the next pass, or None.

        The launched step is collected in this pass too where nothing
        can be launched from it: `hold` is false, the engine's next input
        exists on the host alone (`sync_reason`), or every one of its
        rows ends with it by length (`Step.drains` says which). A fault
        leaves both steps uncollected: the caller drops them, and the
        tokens appended so far are exactly those of collected steps,
        which is all a replay needs.

        A sequence whose first token is in flight (`First`) is launched
        like a row of `after`: its token is set in a free place of the
        step's carry, on the device. The caller reads it once this pass
        has returned (`collect_firsts`): behind the launch, and after
        `after`'s tokens, which did not wait for that prefill. Without
        `hold` nothing is left in flight, so it is read first."""
        if not hold:
            for _ in self.collect_firsts():
                pass
        rows = self._rows(seqs, after)
        if len(rows) > self.max_batch:
            raise MXNetError("decode batch %d exceeds max_batch %d"
                             % (len(rows), self.max_batch))
        if self.spec and rows:
            step = self._spec_decode_step([row[0] for row in rows])
            if step is not None:
                return [step], None
            # fall through: the un-touched single-token path IS the
            # degradation target (and the parity oracle)
        if not rows and after is None:
            return [], None
        collected = []
        with telemetry.span("serving.decode",
                            category="serving") as step_span:
            step = self._launch(rows, after, step_span) if rows else None
            if after is not None:
                collected.append(self._read(after, step_span))
            if step is not None and (step.sealed or not hold):
                collected.append(self._read(step, step_span))
                step = None
        for done in collected:
            self._append_step(done, step_span.id)
        return collected, step

    def _rows(self, seqs, after):
        """The rows of the next step, [(sequence, its length when the
        step runs, its row in `after` or None)], given `after`, the step
        launched and not yet collected: a row of it will be one token
        longer by then, as will a sequence whose first token is in
        flight, and one that this brings to its `max_total` ends there,
        which is known before either has run, so it is left out. A row
        that may end with `after`, or with its first token, by its
        `eos_id` is launched all the same (`_append_step`)."""
        row_of = {} if after is None else {
            id(s): r for r, s in enumerate(after.seqs)}
        rows = [(s, len(s.tokens) + (id(s) in row_of or s.first is not None),
                 row_of.get(id(s))) for s in seqs if not s.done]
        return [row for row in rows if row[1] < row[0].max_total]

    def _launch(self, rows, after, step_span):
        """Build and dispatch one step; returns it in flight, with its
        tokens, and what the family returns beside them, on the device.
        A row that continues from `after` takes its token from
        `after`'s result there (`carried_tokens`), at the position and
        table width the host knows it will have; a row that joined since
        (a sequence just prefilled) takes its first token from a place of
        the carry that no such row refers to, where `carry_first` sets it
        on the device (there is one for every row: the step has at most
        max_batch), or brings it from the host where it was read there.
        So a step costs three uploads, as a step that reads first did,
        and one program per batch bucket serves all three."""
        bb = pow2_bucket(len(rows), lo=1, hi=self.max_batch)
        step = Step([row[0] for row in rows], ahead=after is not None)
        step_span.attrs["batch"] = len(rows)
        if self.model.uses_cache and not self.paged:
            step.walk = "xla" if self.walk_fallback else "kernel"
            step_span.attrs["walk"] = step.walk
        if self.moe:
            step_span.attrs["moe"] = self.moe
        # the cache path's host work in three child spans (to label the
        # device's idle gaps, PERF.md); ring and profiler only
        part = functools.partial(telemetry.span, category="serving",
                                 to_flight=False, batch=len(rows))
        if not self.model.uses_cache:
            s_pad = pow2_bucket(max(n for _, n, _ in rows),
                                lo=1, hi=self.max_len)
            toks = np.zeros((bb, s_pad), np.int32)
            lens = np.ones((bb,), np.int32)
            for i, (s, n, _) in enumerate(rows):
                toks[i, :n] = s.tokens
                lens[i] = n
            with self._count("decode", (bb, s_pad)):
                logits = np.asarray(self.model.step_full(
                    toks, lens, phase="decode"))
            step.nxt = np.argmax(logits, axis=-1)
            step.logits = logits if self.keep_logits else None
        else:
            # paged path: the table width handed to the kernel is
            # bucketed to the longest LIVE sequence, so a decode
            # step's bytes track true lengths, not max_len; the
            # gather path gets the full-capacity table and its one
            # program walks it as far as the longest live sequence
            # (`_attend_live`: the trip count is read from `pos`)
            w = self.cache.table_width(self._nblk)
            if self.paged:
                w = pow2_bucket(max(self.cache.blocks_for(n)
                                    for _, n, _ in rows),
                                lo=1, hi=self._nblk)
            carry = self._no_carry if after is None else after.nxt
            taken = {r for _, _, r in rows if r is not None}
            free = (r for r in range(self.max_batch) if r not in taken)
            with part("serving.decode.build"):
                toks = np.zeros((bb,), np.int32)
                pos = np.zeros((bb,), np.int32)
                tabs = np.zeros((bb, w), np.int32)
                for i, (s, n, r) in enumerate(rows):
                    if s.first is not None:
                        r = next(free)
                        carry = carry_first(carry, s.first.token,
                                            jnp.int32(r))
                    toks[i] = s.tokens[-1] if r is None else -1 - r
                    pos[i] = n - 1
                    tabs[i] = s.table_row[:w]
                step_span.attrs["live_max"] = int(pos.max()) + 1
                spec = self.cache.spec
                if spec.window or spec.state_shape:
                    # tokens the rows hold on a layer that keeps every
                    # one, and on a layer that keeps a window of them
                    held = pos[:len(rows)] + 1
                    step_span.attrs["live_full"] = int(held.sum())
                if spec.window:
                    step_span.attrs["live_window"] = int(np.minimum(
                        held, spec.window).sum())
                if spec.state_shape:
                    # rows that read and write a recurrent state
                    step_span.attrs["state_rows"] = len(rows)
                toks, pos, tabs = (jnp.asarray(toks), jnp.asarray(pos),
                                   jnp.asarray(tabs))
            # same (batch, width) signature lattice whether the paged
            # step runs on one chip or sharded over the tp mesh
            sig = (bb, w) if self.paged else bb
            with part("serving.decode.dispatch", ahead=int(step.ahead)), \
                    self._count("decode", sig):
                step.logits, step.nxt, *step.stats = self._step(
                    self.model.decode, carry, toks, pos, tabs)
                if not self.keep_logits:
                    step.logits = None
            # the copy back starts as soon as the step has run, not when
            # the host comes to ask for it behind the next step's launch
            for result in (step.nxt, *step.stats):
                result.copy_to_host_async()
        if self.sync_reason is not None:
            step.drains.append(self.sync_reason)
            return step
        if after is None:
            step.drains.append("first_step")
        # a step that carries a first token is collected behind the read
        # of that token, so not in this pass, whatever ends with it
        if all(n + 1 >= s.max_total and s.first is None for s, n, _ in rows):
            step.drains.append("last_step")
        return step

    def _read(self, step, step_span):
        """The blocking half of a collect: the step's tokens on the host
        (the read returns when the step has run, whatever was launched
        behind it), the family's `note_step`."""
        if self.model.uses_cache:
            with telemetry.span("serving.decode.readback",
                                category="serving", to_flight=False,
                                batch=len(step.seqs)):
                step.nxt = self._read_back(step_span, step.nxt, step.stats)
                if step.logits is not None:
                    step.logits = np.asarray(step.logits)
        step.t_read = time.perf_counter()
        return step

    def _append_step(self, step, parent):
        """The other half: every row's token appended to its sequence. A
        row whose sequence has ended since the step was launched is
        dropped: it met its `eos_id` in the step before, or with the first
        token it was launched from (`First`), which the host learned only
        after this one was launched (or a failover detached it
        meanwhile). Its token is not the sequence's: never appended,
        counted or recorded. The one cache write the row made lies past
        the sequence's end in blocks that were still its own when the
        step was queued (`blocks_needed` reserves up to `max_total`, and
        a row at `max_total` is never launched); blocks freed at a
        collect can be handed out only to programs queued after every
        step launched before it, so device order keeps the write from
        anyone else's data. A prefix-cache entry made of such a sequence
        is sound for the same reason: `release` registers `tokens[:-1]`,
        the stray write is at the position after them, and a reader of a
        partial tail block copies it and writes its own tokens from the
        registered count on before it reads any. The tokens appended are
        filed on their requests' timelines (`record_tokens`) under
        `parent`, the pass's span."""
        took = []
        with telemetry.span("serving.decode.append", category="serving",
                            to_flight=False, batch=len(step.seqs)):
            for i, s in enumerate(step.seqs):
                if s.done:
                    continue
                took.append((s, len(s.tokens)))
                if step.logits is not None:
                    s.last_logits = step.logits[i]
                    if s.token_logits is not None:
                        s.token_logits.append(step.logits[i])
                self._append(s, int(step.nxt[i]))
            gaps = self.record_tokens(took, step.t_read, step, parent)
        step.advanced = [(s, n, n + 1, (gap,))
                         for (s, n), gap in zip(took, gaps)]

    def record_tokens(self, tokens, at, step=None, parent=None, since=None,
                      **attrs):
        """The tokens [(sequence, position)] that a client could read from
        `at` on, when the host held them: those of `step`, or the one out
        of a prefill. Returns each one's gap in seconds to its sequence's
        token before it (`since`: to something earlier) and, where the
        sequence serves a request, makes ONE `serving.token` span on the
        request's trace from the one stamp to the other, so a request's
        row is a gapless chain of its tokens and a span's `dur` is the
        gap a client saw. It says what the token waited for, by what the
        engine ran in between and not by what overlapped it: `prefills`
        programs (whole prompts or chunks, of any request) over
        `prefill_tokens` rows; the step's `ahead`, and its `drains` where
        it has them, so that a gap a drain lengthened names it. Ring
        only: B a step would evict the flight recorder's history, and
        the chrome trace has the pass."""
        ahead = 0
        if step is not None:
            ahead = int(step.ahead)
            if step.drains:
                attrs["drains"] = ",".join(step.drains)
        at_us = int(at * 1e6)
        ran, rows = self.prefills_run, self.prefill_tokens_run
        gaps = []
        for seq, position in tokens:
            start = seq.t_last_token if since is None else since
            req = seq.request
            if req is not None:
                ts = int(start * 1e6)
                telemetry.record_span(
                    "serving.token", ts, at_us - ts, trace=req.trace,
                    category="serving", to_profiler=False, to_flight=False,
                    parent=parent, position=position,
                    prefills=ran - seq.prefills_seen,
                    prefill_tokens=rows - seq.prefill_tokens_seen,
                    ahead=ahead, **attrs)
                # what a failover's replay starts its first gap from
                # (`make_resume`): the chain goes on across the hop
                req.t_last_token = at
            seq.t_last_token = at
            seq.prefills_seen = ran
            seq.prefill_tokens_seen = rows
            gaps.append(at - start)
        return gaps

    def _draft_propose(self, seqs, bb, k, poison):
        """Draft proposal loop: k greedy autoregressive steps of the
        cache-free draft over the (bucketed) batch of token histories.
        Returns (draft (B, k) int32, per-sequence proposal counts), or
        None when the draft emitted non-finite logits — the poisoned
        batch degrades to the non-speculative path, proposing nothing.
        Sequences within 1 token of max_total get a shorter (possibly
        empty) proposal: the bonus token takes the last slot, and tokens
        drafted past max_total would be priced but undeliverable."""
        d = self.draft
        B = len(seqs)
        hist = [list(s.tokens) for s in seqs]
        nbs = [max(0, min(k, s.max_total - len(s.tokens) - 1))
               for s in seqs]
        out = np.zeros((B, k), np.int32)
        for j in range(max(nbs)):
            s_pad = pow2_bucket(max(len(h) for h in hist),
                                lo=min(8, d.max_len), hi=d.max_len)
            toks = np.zeros((bb, s_pad), np.int32)
            lens = np.ones((bb,), np.int32)
            for i, h in enumerate(hist):
                toks[i, :len(h)] = h
                lens[i] = len(h)
            with self._count("decode", ("draft", bb, s_pad)):
                logits = np.asarray(d.logits_at(jnp.asarray(toks),
                                                jnp.asarray(lens)))
            if poison:
                logits = np.full_like(logits, np.nan)
            if not np.isfinite(logits[:B]).all():
                return None
            nxt = np.argmax(logits, axis=-1).astype(np.int32)
            for i in range(B):
                if j < nbs[i]:
                    out[i, j] = nxt[i]
                    hist[i].append(int(nxt[i]))
        return out, nbs

    def _spec_decode_step(self, seqs):
        """One speculative iteration: draft proposes, the target scores
        all k+1 positions in ONE ragged paged pass against the live
        block tables, greedy verification accepts a prefix (plus the
        target's own token at the first disagreement, plus a bonus on a
        full sweep) — emitted tokens are EXACTLY the plain greedy
        path's. Returns the step, collected (its tokens are appended as
        they are verified), or None to degrade this batch to the verbatim
        non-speculative step (draft fault).

        KV discipline: the pass writes positions len-1..len-1+k per
        sequence. Accepted positions become ordinary history; rejected
        positions hold garbage that is REWRITTEN by the next step over
        this sequence before any attention mask reaches it, and the
        prefix cache only ever indexes tokens[:-1] (accepted history).
        """
        from .spec import greedy_verify
        k = self.spec_k
        C = k + 1
        bb = pow2_bucket(len(seqs), lo=1, hi=self.max_batch)
        step = Step(seqs, drains=["spec"])
        before = [len(s.tokens) for s in seqs]
        poison, self.chaos_spec_poison = self.chaos_spec_poison, False
        with telemetry.span("serving.spec", category="serving",
                            batch=len(seqs), k=k):
            drafted = self._draft_propose(seqs, bb, k, poison)
            if drafted is None:
                self.spec_fallbacks += 1
                self.last_spec = {"fallback": True, "batch": len(seqs)}
                return None
            draft, nbs = drafted
            B = len(seqs)
            w = pow2_bucket(
                max(self.cache.blocks_for(len(s.tokens) + k)
                    for s in seqs), lo=1, hi=self._nblk)
            toks = np.zeros((bb, C), np.int32)
            qs = np.zeros((bb,), np.int32)
            counts = np.zeros((bb,), np.int32)
            tabs = np.zeros((bb, w), np.int32)
            for i, s in enumerate(seqs):
                toks[i, 0] = s.tokens[-1]
                toks[i, 1:1 + nbs[i]] = draft[i, :nbs[i]]
                qs[i] = len(s.tokens) - 1
                counts[i] = 1 + nbs[i]
                tabs[i] = s.table_row[:w]
            with self._count("decode", ("spec", bb, w)):
                logits, = self._step(
                    self.model.spec_score, jnp.asarray(toks),
                    jnp.asarray(qs), jnp.asarray(counts),
                    jnp.asarray(tabs))
            logits = np.asarray(logits)                    # (bb, C, V)
            step.t_read = time.perf_counter()
            accepted = proposed = emitted_n = 0
            step.advanced = []
            for i, s in enumerate(seqs):
                am = np.argmax(logits[i], axis=-1)
                emitted, acc = greedy_verify(am, draft[i], nbs[i])
                accepted += acc
                proposed += nbs[i]
                for j, tok in enumerate(emitted):
                    if s.done:
                        break
                    if self.keep_logits:
                        s.last_logits = logits[i, j]
                        if s.token_logits is not None:
                            s.token_logits.append(logits[i, j])
                    self._append(s, int(tok))
                    emitted_n += 1
                # a burst reaches the client at once: the gaps inside it
                # are 0
                n = len(s.tokens)
                step.advanced.append((s, before[i], n, self.record_tokens(
                    [(s, p) for p in range(before[i], n)], step.t_read,
                    step)))
        self.spec_passes += 1
        self.spec_proposed_tokens += proposed
        self.spec_accepted_tokens += accepted
        self.last_spec = {"fallback": False, "batch": B,
                          "proposed": proposed, "accepted": accepted,
                          "emitted": emitted_n}
        return step

    def _append(self, seq, token):
        seq.tokens.append(token)
        if (seq.eos_id is not None and token == seq.eos_id) \
                or len(seq.tokens) >= seq.max_total:
            seq.done = True

    def audit_quiescent(self):
        """Leak audit (ISSUE 11): with no sequence in flight, every
        allocated pool block must be a prefix-cache resident pinned by
        exactly the cache's own ref — anything else is a block some
        sequence leaked. Raises MXNetError listing the leaked ids."""
        if self.cache is None:
            return
        resident = []
        if self.prefix_cache is not None:
            resident = [e.block_id
                        for e in self.prefix_cache._by_hash.values()]
        self.cache.assert_quiescent(resident)

    def close(self, audit=True):
        """End-of-life seam: with `audit=True` (the default) run the
        block-pool leak audit — an engine being retired with blocks that
        belong to no cache entry has leaked them, and at fleet scale a
        silent leak is a slow-motion outage. Callers tearing down a
        CRASHED engine pass audit=False (its pool dies with it; the
        in-flight blocks were already released by the death path). The
        engine leaves the live set either way — a failed audit already
        surfaced the leak once; close() stays idempotent."""
        try:
            if audit:
                self.audit_quiescent()
        finally:
            _LIVE.discard(self)

    def release(self, seq, reusable=True):
        """Recycle a finished sequence's cache blocks. With the prefix
        cache on, everything whose KV is now immutable — full blocks
        over prompt AND generated tokens, plus the final partial tail —
        is registered for reuse first (the cache pins what it keeps via
        refcounts; this sequence's own refs are dropped either way).
        `reusable=False` skips registration — fault paths release
        sequences whose KV cannot be trusted (a poisoned batch must not
        seed the cache), and a mid-prefill release registers nothing
        either way (its blocks may hold partial garbage)."""
        if seq.blocks:
            if reusable and self.prefix_cache is not None and \
                    seq.prefilled >= seq.prompt_len:
                # the final token was appended but its KV never written:
                # only tokens[:-1] are content-addressable
                self.prefix_cache.insert(seq.tokens, seq.block_ids,
                                         len(seq.tokens) - 1,
                                         partial_ok=True)
            self.cache.note_recycled(len(seq.tokens) - 1, seq.blocks)
            self.cache.free(seq.blocks)
            seq.blocks = ()
