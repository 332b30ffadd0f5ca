"""Paged KV-cache: fixed-shape block pools for jit-stable decode.

The serving problem with a naive per-sequence KV cache is shape churn:
every admitted/evicted request changes the cache tensor shapes and XLA
recompiles the decode step. Following the paged-attention design (Ragged
Paged Attention, arxiv 2604.15464) the cache here is ONE fixed-shape pool
of `num_blocks` blocks of `block_size` token slots per layer; a sequence
owns an ordered list of block ids (its *block table*) and the attention
read path gathers keys/values by table — so the compiled decode program
only ever sees (pool, int32 tables, int32 lengths) of constant shape, no
matter which sequences come and go (the compiler-visible O(1) cache
argument of arxiv 2603.09555).

Block 0 is the *null block*: never allocated, it absorbs every write from
padded batch rows and padded table entries, so the jitted step needs no
branches for inactive slots. Reads from it are masked by sequence length.

Host side (`BlockPool`) is a plain free-list — allocation policy is a
scheduling decision and lives outside the compiled program. Device side,
the pool arrays are CONTIGUOUS PER LAYER with an explicit block axis and
the HEADS AHEAD OF THE BLOCK — (n_layers, num_blocks, n_heads,
block_size, head_dim) — so a (block id, head) pair indexes one
(block_size, head_dim) slab: that is the unit the ragged paged-attention
kernel (ops/pallas_paged.py) DMAs per grid step, and its two minor
dimensions are the whole minor extent of the array, which is what Mosaic
asks of a block (a head axis between them made the block's second-minor
extent 1 of H, which it refuses). Only this module and the kernel know
the order of the axes.

Every write UPDATES THE POOL IN PLACE, a whole (n_heads, block_size,
head_dim) block at a time: read the blocks the new tokens fall in, set
their rows, write the blocks back (`append_kv`, `write_kv`,
`write_kv_prompt`). A scatter of single tokens, whose window (n_heads,
head_dim) is split by the block_size axis, makes XLA's layout assignment
move the whole pool to a layout with that axis ahead of the heads and
back: four copies of a 1 GB pool in every step, a quarter of a decode
step on a v5e (PERF.md, PR 26). Whole blocks are the pool's trailing
axes as they lie, and with the pools donated (`PagedKVCache`) no step
program holds an operation of the pool's size. The by-table gather hands
the blocks out as they lie too, and the attention contracts over them
(`gather_kv`).

A model whose layers keep different things (`CacheSpec.layer_kinds`: every
token, or a window of the last ones) has a pair of planes, a `BlockPool`
and a group of table columns a KIND of layer; a window kind's columns are
a ring. The views find a layer's planes and columns by its kind
(`_place`); with one kind they are the whole of both.

A third kind does not grow with a sequence's length: the "state" of a
state-space layer (models/falcon_h1.py), the recurrence's matrix a head
and the convolution's last inputs. Its pool's block is a SLOT, one a
sequence however long (a ring of one), its planes `ssm_state` and
`conv_state` are indexed (layer, slot), a sequence's slot is the last
column of its table row and slot 0 is the null slot of padded rows.
Prefill overwrites a slot wholly, so a slot given to a new sequence
carries nothing of the last one. A layer may keep a state BESIDE its
keys and values (`CacheSpec.layer_kinds`: "full+state"), or a state
ALONE ("state"), or nothing ("none": a layer that mixes no positions, as
an expert layer of models/nemotron_h.py); every kind's planes, pool and
columns run over that kind's OWN layers (`CacheSpec.layers_of`), and a
view is asked nothing by a layer that keeps nothing.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from ..base import MXNetError
from ..ops import pallas_decode_walk as _walk
from ..ops import pallas_prompt_attention as _prompt
from ..ops import pallas_ssm_step as _ssm
from ..ops.pallas_attention import default_interpret
from ..ops.pallas_paged import paged_attention
from ..models.afmoe import banded_attention
from ..models.falcon_h1 import mix_prompt, mix_step, state_update


#: the names under which every step function takes the pool arrays: the
#: arguments a step donates (engine `_program`); `kv_pool` is the one
#: array of the latent layout, `k_ring`/`v_ring` the planes of a second
#: kind of layer (`CacheSpec.layer_kinds`), `ssm_state`/`conv_state` the
#: planes of the state kind
POOL_ARGS = ("k_pool", "v_pool", "k_scale", "v_scale", "kv_pool",
             "k_ring", "v_ring", "ssm_state", "conv_state")
#: the attributes of `PagedKVCache` that may hold a device array
_PLANES = ("k", "v", "k_scale", "v_scale", "kv", "k_ring", "v_ring",
           "ssm_state", "conv_state")
#: lanes of a TPU tile: the latent pool's rows are whole tiles wide
LANES = 128


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """What a model family keeps per token, as its adapter's
    `cache_spec()` answers it. Two layouts: `kv`, full keys and values,
    two arrays (n_layers, num_blocks, n_heads, block_size, head_dim);
    and `latent` (`latent_dim` > 0), ONE array (n_layers, num_blocks,
    block_size, row_width) holding a compressed row a token from which
    attention rebuilds, or never builds, keys and values (multi-head
    latent attention: no head axis and no second plane). `row_width` is
    `latent_dim` rounded up to whole 128-lane tiles: a TPU lays a 576-wide
    bf16 row out as 640 in any case, and where the logical width is not
    whole tiles its default layout for the array avoids the padding by
    moving the BLOCK axis innermost, which every step then pays for with
    two copies of the whole pool (PERF.md, PR 27).

    `n_heads` is the heads the CACHE holds; a model whose query heads
    outnumber them (grouped-query attention: query head h reads cached
    head h // (n_q_heads / n_heads)) says so in `n_q_heads`.

    Layers of two KINDS: `layer_kinds` names each layer "full" (every
    token kept) or "window" (a query at position t sees key j iff t - j <
    `window`, so only the last `window` tokens are kept). Each kind has
    its own pair of K/V arrays over its own layers, its own `BlockPool`
    and its own columns of a sequence's table; a window kind's columns
    are a RING (`ring`). Empty: one kind, every layer full.

    A third kind, "state", is what a state-space layer carries from
    token to token, of one size however long the sequence: per layer
    `state_shape` values in `state_dtype` (the recurrence's matrix a
    head) and `conv_shape` = (taps - 1, channels) values in `dtype` (the
    causal convolution's last inputs). A layer that keeps it BESIDE its
    keys and values names both kinds, joined by "+" ("full+state"); one
    that keeps it ALONE is "state"; one that keeps nothing at all (it
    mixes no positions) is "none". Its block is a SLOT: a ring of one."""
    n_layers: int
    dtype: object
    n_heads: int = 0
    head_dim: int = 0
    latent_dim: int = 0
    n_q_heads: int = 0
    layer_kinds: tuple = ()
    window: int = 0
    state_shape: tuple = ()
    conv_shape: tuple = ()
    state_dtype: object = None

    @property
    def layout(self):
        return "latent" if self.latent_dim else "kv"

    @property
    def row_width(self):
        return -(-self.latent_dim // LANES) * LANES

    @property
    def q_group(self):
        """Query heads one cached head serves."""
        return (self.n_q_heads or self.n_heads) // max(self.n_heads, 1)

    def values_per_token(self):
        """Cached values one token occupies over all the layers that
        keep keys and values (a token inside the window, where kinds
        differ); a state is no token's (`state_bytes`)."""
        if self.latent_dim:
            return self.n_layers * self.row_width
        return sum(len(self.layers_of(k)) for k in ("full", "window")) \
            * 2 * self.n_heads * self.head_dim

    def _kinds_of(self, layer):
        return (self.layer_kinds[layer] if self.layer_kinds
                else "full").split("+")

    @functools.cached_property
    def kinds(self):
        """The kinds of layer present, "full" first and "state" last: the
        order of the pool's arrays, its block pools and a table's groups
        of columns."""
        present = {k for i in range(self.n_layers) for k in self._kinds_of(i)}
        return tuple(k for k in ("full", "window", "state") if k in present)

    def layers_of(self, kind):
        """The model's layers that keep one kind, in order: layer `i` of
        the model is layer `layers_of(kind).index(i)` of its kind's
        arrays."""
        return tuple(i for i in range(self.n_layers)
                     if kind in self._kinds_of(i))

    def attn_kind(self, layer):
        """The kind layer `layer`'s keys and values are kept as, or None
        where it keeps none (a state alone, or nothing)."""
        return next((k for k in self._kinds_of(layer)
                     if k in ("full", "window")), None)

    def ring(self, kind, block_size):
        """Blocks a sequence holds at most for a layer of `kind`: 0 (no
        bound) where every token is kept; `window / block_size + 1` for
        a window, which is every block the last `window` positions can
        touch. Position p lies in column `(p // block_size) % ring`: the
        block it overwrites is wholly behind the window by then. A state
        is one slot, however long the sequence."""
        if kind == "state":
            return 1
        return self.window // block_size + 1 if kind == "window" else 0

    def state_bytes(self):
        """Bytes one sequence's slots hold over all state layers,
        whatever its length (0 with no state kind)."""
        if not self.state_shape:
            return 0
        return len(self.layers_of("state")) * (
            math.prod(self.state_shape) * np.dtype(self.state_dtype).itemsize
            + math.prod(self.conv_shape) * np.dtype(self.dtype).itemsize)

    def paged_unfit(self):
        """Why the paged kernel (and what is built on it: chunked
        prefill, the prefix cache, the int8 pool, speculation, tensor
        parallelism) cannot read this cache, or None."""
        if "state" in self.kinds:
            beside = any(self.attn_kind(i) for i in self.layers_of("state"))
            return ("layers keep a recurrent state %s: the paged step and "
                    "the chunked prefill do not carry it from chunk to "
                    "chunk, a shared prefix block has no snapshot of it, "
                    "the int8 pool does not hold it and a speculative pass "
                    "cannot roll it back"
                    % ("beside their keys and values" if beside else
                       "alone, beside layers that keep keys and values or "
                       "nothing"))
        if self.layout != "kv":
            return ("the pool holds %s rows, not keys and values: the "
                    "paged kernel and the chunked prefill read the K and "
                    "V planes" % self.layout)
        if self.n_q_heads and self.n_q_heads != self.n_heads:
            return ("%d query heads read %d cached heads: the paged "
                    "kernel walks one block a query head"
                    % (self.n_q_heads, self.n_heads))
        if self.kinds != ("full",):
            return ("layers of kinds %s keep different blocks: the paged "
                    "kernel walks one table, and a window layer's blocks "
                    "are recycled, so they cannot be shared by prefix"
                    % "/".join(self.kinds))
        return None


class CacheOverflow(MXNetError):
    """Raised when a reservation asks for more blocks than exist at all;
    transient exhaustion (blocks held by running sequences) is reported by
    ``try_alloc`` returning None so the scheduler can queue instead."""


class BlockPool:
    """Free-list over block ids 1..num_blocks-1 (0 is the null block).

    Invariants (tested): a block is never handed out twice while live,
    freeing a block not currently live raises, and freed blocks are reused
    (LIFO — the hottest block stays cache-warm on the host bookkeeping
    side; device placement is unaffected). `high_water` tracks the peak
    in-use count for the serving metrics snapshot.

    Blocks are REFCOUNTED (the prefix cache shares one block between
    many sequences): `try_alloc` hands a block out at refcount 1,
    `add_ref` pins it for an additional reader, and `free` drops one
    ref per id — a block only returns to the free list at refcount
    zero, so freeing a shared block can never yank it out from under
    its other readers. A `free` call is validated ATOMICALLY before any
    mutation: duplicate ids within one call and ids that are not live
    both raise with the pool untouched (a partial free on error was a
    silent corruption vector once blocks became shared). When the free
    list runs short, `try_alloc` first asks the `reclaimer` hook (the
    prefix cache) to evict refcount-zero cached blocks, so resident
    prefixes are reusable capacity, never a leak.
    """

    def __init__(self, num_blocks):
        if num_blocks < 2:
            raise MXNetError("BlockPool needs >= 2 blocks (block 0 is the "
                             "reserved null block)")
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, 0, -1))  # pop() -> 1 first
        self._live = set()
        self._refs = {}               # live block id -> refcount >= 1
        self.high_water = 0
        self.reclaimer = None         # callable(shortfall) -> blocks freed

    @property
    def available(self):
        return len(self._free)

    @property
    def in_use(self):
        return len(self._live)

    def refcount(self, b):
        """Current refcount of a block (0 when not live)."""
        return self._refs.get(b, 0)

    def try_alloc(self, n):
        """Reserve n blocks (each at refcount 1); None when the pool
        can't satisfy it right now (backpressure), CacheOverflow when it
        never could. A shortfall first asks the reclaimer (the prefix
        cache's LRU eviction) to release refcount-zero cached blocks."""
        if n > self.num_blocks - 1:
            raise CacheOverflow(
                "requested %d blocks but the pool only has %d total"
                % (n, self.num_blocks - 1))
        if n > len(self._free) and self.reclaimer is not None:
            self.reclaimer(n - len(self._free))
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        self._live.update(ids)
        for b in ids:
            self._refs[b] = 1
        self.high_water = max(self.high_water, len(self._live))
        return ids

    def assert_quiescent(self, cache_resident=()):
        """Leak audit (ISSUE 11): with no sequence in flight, every live
        block must be a prefix-cache resident pinned by exactly the
        cache's own ref. Anything else — a block some released sequence
        never freed, or a cache entry with a phantom extra ref — is a
        leak, and at serving scale a slow leak is an outage with a delay
        timer. Raises MXNetError LISTING the leaked block ids (the hard
        part of chasing a leak is knowing which allocation it was);
        called from `Engine.close()` and the serving tests' shared
        quiescence fixture."""
        resident = set(cache_resident)
        leaked = sorted(b for b in self._live
                        if b not in resident or self._refs[b] != 1)
        phantom = sorted(b for b in resident if b not in self._live)
        if leaked or phantom:
            raise MXNetError(
                "BlockPool not quiescent: %d leaked block id(s) %r "
                "(in_use=%d, cache-resident=%d%s) — a sequence was "
                "released without freeing them, or a shared block "
                "holds a ref no reader owns"
                % (len(leaked), leaked[:32], len(self._live),
                   len(resident),
                   (", cache entries pointing at dead blocks %r"
                    % phantom[:8]) if phantom else ""))

    def add_ref(self, ids):
        """Pin each live block for one more reader; raises on a block
        that is not currently live (nothing to pin)."""
        for b in ids:
            if b not in self._live:
                raise MXNetError(
                    "add_ref on block %r which is not live" % b)
        for b in ids:
            self._refs[b] += 1

    def free(self, ids):
        """Drop one ref per id; blocks reaching refcount zero return to
        the free list. Validated atomically BEFORE any mutation: a
        duplicate id in one call or a non-live id raises MXNetError and
        leaves the pool unchanged."""
        ids = list(ids)
        seen = set()
        for b in ids:
            if b in seen:
                raise MXNetError(
                    "duplicate block id %r in one free() call (would "
                    "drop two refs for one reader); pool left unchanged"
                    % b)
            seen.add(b)
            if b not in self._live:
                raise MXNetError("double-free or foreign block id %r; "
                                 "pool left unchanged" % b)
        for b in ids:
            self._refs[b] -= 1
            if self._refs[b] == 0:
                del self._refs[b]
                self._live.remove(b)
                self._free.append(b)


class PagedKVCache:
    """Device-side K/V pools plus the host free-lists.

    Arrays: ``k``/``v`` of shape (n_layers, num_blocks, n_heads,
    block_size, head_dim) — contiguous-per-layer block layout (see module
    docstring). They are plain jax arrays threaded through the jitted
    engine functions, and every such function CONSUMES the arrays it is
    given (they are donated: the step writes the new K/V into the same
    buffers and returns them, and the arrays handed in are deleted). So
    the pool has one owner, this object, which is rebound from every
    call's result (`Engine._donating`); no caller may keep ``k``/``v``
    or the scale sidecars across a step. A step that fails after its
    launch leaves them deleted: `lost()` says so and `remake()` makes
    them anew, empty, under the same placement.

    With ``kv_dtype="int8"`` the pools store symmetric-per-block int8
    and grow f32 scale SIDECARS ``k_scale``/``v_scale`` of shape
    (n_layers, num_blocks, n_heads) living beside the pool in the same
    contiguous block layout: one scale per (layer, block, head), so the
    paged kernel scalar-prefetches exactly one f32 per DMA'd block per
    head and dequantizes in VMEM. `blocks_for`, tables, and the host
    free-list are precision-agnostic — a block id means the same thing
    in both layouts.

    The LATENT layout (`PagedKVCache.of(spec, ...)` with a latent
    `CacheSpec`) has one array, ``kv``, of shape (n_layers, num_blocks,
    block_size, row_width) in the served dtype: `arrays()` has length
    one, and ``k``/``v`` do not exist. Blocks, tables and the free-list
    are the same.

    A spec with layers of two KINDS (`CacheSpec.layer_kinds`) has a pair
    of planes a kind, each over that kind's layers and that kind's
    blocks: ``k``/``v`` the first kind's, ``k_ring``/``v_ring`` the
    window kind's, and a `BlockPool` a kind (`pools`; `pool` is the
    first's). A sequence holds a list of blocks a kind (`try_alloc`,
    `free`) and its table row is the kinds' columns side by side
    (`row`): the first kind's at the engine's width, a window kind's at
    its ring. One kind is the same code with one entry in each. A
    "state" kind's planes are ``ssm_state`` (layers, slots, *state_shape)
    in the spec's `state_dtype` and ``conv_state`` (layers, slots, taps -
    1 times channels) in the pool's dtype; its `BlockPool` hands out
    slots, one a sequence, and its column is the row's last.
    """

    def __init__(self, n_layers, n_heads, head_dim, block_size=16,
                 num_blocks=64, dtype=jnp.float32, kv_dtype=None,
                 latent_dim=0, spec=None):
        self.spec = spec or CacheSpec(n_layers, dtype, n_heads, head_dim,
                                      latent_dim)
        self.n_layers = n_layers
        self.n_heads = n_heads
        self.head_dim = head_dim
        self.latent_dim = latent_dim
        self.block_size = block_size
        kinds = self.spec.kinds
        #: blocks of each kind's planes; an int sizes the first kind and
        #: leaves the others as large
        self.blocks_of = tuple(num_blocks) if np.ndim(num_blocks) \
            else (num_blocks,) * len(kinds)
        self.num_blocks = self.blocks_of[0]
        self.pools = tuple(BlockPool(n) for n in self.blocks_of)
        self.pool = self.pools[0]
        #: the ring of each kind (`CacheSpec.ring`), 0 where none
        self.rings = tuple(self.spec.ring(k, block_size) for k in kinds)
        #: a window kind's blocks written over again by the same
        #: sequence (each was wholly behind the window), counted as
        #: sequences end
        self.recycled = 0
        #: blocks in use, a kind, when the first kind's count was last at
        #: its highest: what the kinds hold of the same sequences
        self.held_at_high_water = (0,) * len(kinds)
        if kv_dtype is not None and str(kv_dtype) != "int8":
            raise MXNetError("kv_dtype %r is not supported (int8 or "
                             "None)" % (kv_dtype,))
        self.kv_dtype = "int8" if kv_dtype is not None else None
        self._dtype = jnp.int8 if self.kv_dtype else dtype
        if (latent_dim or len(kinds) > 1) and self.kv_dtype:
            raise MXNetError("only the one-kind K/V layout has an int8 "
                             "pool")
        self._sharding = self._scale_sharding = None
        self.remake()

    @classmethod
    def of(cls, spec, block_size=16, num_blocks=64, kv_dtype=None):
        """The pool a `CacheSpec` describes."""
        return cls(spec.n_layers, spec.n_heads, spec.head_dim,
                   block_size=block_size, num_blocks=num_blocks,
                   dtype=spec.dtype, kv_dtype=kv_dtype,
                   latent_dim=spec.latent_dim, spec=spec)

    @property
    def quantized(self):
        return self.kv_dtype is not None

    @property
    def layout(self):
        return self.spec.layout

    def _planes(self):
        """[(attribute, shape, dtype, is it a scale sidecar)] of the
        device arrays, in the order every step takes and returns them:
        (k, v), and the scale sidecars of an int8 pool; (kv,) in the
        latent layout; (k, v, k_ring, v_ring) with a window kind; a
        state kind's (ssm_state, conv_state) after the keys and values,
        indexed (layer, slot), the convolution's inputs flat."""
        if self.latent_dim:
            return [("kv", (self.n_layers, self.num_blocks, self.block_size,
                            self.spec.row_width), self._dtype, False)]
        planes, spec = [], self.spec
        names = iter((("k", "v"), ("k_ring", "v_ring")))
        for kind, blocks in zip(spec.kinds, self.blocks_of):
            layers = len(spec.layers_of(kind))
            if kind == "state":
                planes += [
                    ("ssm_state", (layers, blocks) + tuple(spec.state_shape),
                     spec.state_dtype, False),
                    ("conv_state", (layers, blocks,
                                    math.prod(spec.conv_shape)),
                     self._dtype, False)]
                continue
            shape = (layers, blocks, self.n_heads, self.block_size,
                     self.head_dim)
            planes += [(n, shape, self._dtype, False) for n in next(names)]
        if self.quantized:
            planes += [(n, planes[0][1][:3], jnp.float32, True)
                       for n in ("k_scale", "v_scale")]
        return planes

    def arrays(self):
        """The device arrays in the order every step takes and returns
        them (`_planes`)."""
        return tuple(getattr(self, n) for n, _, _, _ in self._planes())

    def rebind(self, arrays):
        """Take a step's results as the pool (see `arrays`)."""
        for (n, _, _, _), a in zip(self._planes(), arrays, strict=True):
            setattr(self, n, a)

    def drop(self):
        """Let the device arrays go (a server being torn down hands its
        pool's memory back before its successor's is made)."""
        for n in _PLANES:
            setattr(self, n, None)

    def lost(self):
        """Did a step consume the arrays and give nothing back (it
        failed after its launch)? Block contents are gone then."""
        return any(a.is_deleted() for a in self.arrays())

    def remake(self):
        """Empty pools (and sidecars) under the placement `place` gave:
        at construction, when placed, and after `lost()`. The host
        free-lists are not touched — whoever holds blocks still frees
        them."""
        # let go first: the old and the new pool never lie side by side
        self.drop()
        for n, shape, dtype, scale in self._planes():
            setattr(self, n, jnp.zeros(
                shape, dtype,
                device=self._scale_sharding if scale else self._sharding))

    def place(self, sharding, scale_sharding=None):
        """Lay the device pools out under `sharding` (a NamedSharding, or
        the one device of a placed single-chip engine).
        The tensor-parallel engine shards the HEAD axis — each chip owns
        n_heads/k heads of every block — so block ids, tables, and the
        host free-list are placement-agnostic and unchanged. A quantized
        pool's scale sidecars shard on the same head axis via
        `scale_sharding` (their (L, NB, H) layout drops the trailing
        token/dim axes), so each chip's scales are chip-local."""
        self._sharding, self._scale_sharding = sharding, scale_sharding
        self.remake()

    def blocks_for(self, n_tokens):
        """Blocks needed to hold n_tokens KV entries — by construction
        the kernel-side table width for a sequence of that length:
        position n_tokens-1 lives in block (n_tokens-1)//block_size, the
        table's last occupied slot."""
        return max(1, math.ceil(n_tokens / self.block_size))

    def blocks_by_kind(self, n_tokens):
        """Blocks of each kind a sequence of n_tokens holds: all of them
        where every token is kept, no more than the ring where a window
        is."""
        n = self.blocks_for(n_tokens)
        return tuple(min(n, ring) if ring else n for ring in self.rings)

    def try_alloc(self, counts):
        """Reserve `counts[i]` blocks of kind i, all or none: a list of
        ids a kind, or None when any kind is short right now (nothing is
        taken then); `CacheOverflow` when a kind never could."""
        for pool, n in zip(self.pools, counts, strict=True):
            if n > pool.num_blocks - 1:
                pool.try_alloc(n)             # raises, having taken nothing
        got = []
        for pool, n in zip(self.pools, counts):
            ids = pool.try_alloc(n)
            if ids is None:
                self.free(got)
                return None
            got.append(ids)
        if self.pool.in_use >= self.held_at_high_water[0]:
            self.held_at_high_water = tuple(p.in_use for p in self.pools)
        return tuple(got)

    def free(self, blocks):
        """Give back a sequence's blocks, a list a kind (`try_alloc`)."""
        for pool, ids in zip(self.pools, blocks):
            if ids:
                pool.free(ids)

    def note_recycled(self, n_tokens, blocks):
        """A sequence that held `blocks` ends after n_tokens: count the
        ring columns it wrote over again."""
        for kind, ring, ids in zip(self.spec.kinds, self.rings, blocks):
            if ring and kind != "state":
                self.recycled += max(0, self.blocks_for(n_tokens) - len(ids))

    def table_row(self, block_ids, n_entries):
        """Fixed-width int32 table row: allocated ids, null-padded."""
        row = np.zeros((n_entries,), np.int32)
        row[:len(block_ids)] = block_ids
        return row

    def table_width(self, n_entries):
        """Columns of a whole table row: the first kind's `n_entries`,
        then each further kind's ring."""
        return n_entries + sum(self.rings[1:])

    def row(self, blocks, n_entries):
        """A sequence's table row over all kinds, side by side
        (`table_width`)."""
        return np.concatenate([
            self.table_row(ids, ring if i else n_entries)
            for i, (ids, ring) in enumerate(zip(blocks, self.rings))])

    def utilization(self):
        return self.pool.in_use / float(self.num_blocks - 1)

    def further_kinds(self):
        """{kind: its pool's counts} of every kind after the first, whose
        counts are the pool's own (`pool`, `num_blocks`): what the
        metrics publish by kind."""
        return {kind: {"blocks_in_use": pool.in_use,
                       "blocks_high_water": pool.high_water,
                       "blocks_total": pool.num_blocks - 1,
                       "blocks_recycled": self.recycled}
                for kind, pool in list(zip(self.spec.kinds, self.pools))[1:]}

    def assert_quiescent(self, cache_resident=()):
        """`BlockPool.assert_quiescent` over every kind; only the first
        kind's blocks can be prefix-cache residents."""
        for i, pool in enumerate(self.pools):
            pool.assert_quiescent(() if i else cache_resident)


# ---------------------------------------------------------------------------
# pure ops used inside the jitted engine functions
# ---------------------------------------------------------------------------


def flat_slots(block_table, positions, block_size, ring=0):
    """Flat pool slot for each (row, position): the position'th token of a
    sequence lives in its table's position//bs block at offset
    position%bs, or, where the columns are a ring, in column
    (position//bs) % ring. block_table (B, nblk), positions (B,) -> (B,)."""
    col = positions[:, None] // block_size
    blk = jnp.take_along_axis(block_table, col % ring if ring else col,
                              axis=1)[:, 0]
    return blk * block_size + positions % block_size


def _touched_blocks(slots, block_size, ncand):
    """Split flat slots (N,) into (block, offset) and name the DISTINCT
    blocks they fall in: `cand` (ncand,) block ids, sorted, padded with
    the null block, and `ci` (N,) each token's row in `cand`. `ncand`
    is the static bound on distinct blocks (default N): N contiguous
    positions span at most (N-1)//block_size + 2 blocks with the null
    block, and a caller that knows the span passes it to shrink the
    read. A padding row of `cand` duplicates the null block, whose
    contents nothing reads."""
    n = slots.shape[0]
    ncand = n if ncand is None else min(ncand, n)
    tb, off = slots // block_size, slots % block_size
    cand = jnp.unique(tb, size=ncand, fill_value=0)
    # every tb[i] is present in cand by the ncand bound
    ci = jnp.argmax(cand[None, :] == tb[:, None], axis=1)
    return tb, off, cand, ci


def write_kv(k_pool, v_pool, layer, slots, k_new, v_new, ncand=None):
    """Write N new K/V entries into one layer's flat slots (block id *
    block_size + offset), any number of them to a block. slots (N,)
    int32; k_new/v_new (N, n_heads, head_dim). In place, by whole blocks
    (module docstring): the `ncand` distinct blocks are read, the rows
    set, the blocks written back. The rows are set by a scatter over the
    small gathered array, about 1.6 us a token on a v5e: right for a
    prefill chunk or a speculative pass, not for a whole prompt
    (`write_kv_prompt`) nor for the decode step (`append_kv`)."""
    _, off, cand, ci = _touched_blocks(slots, k_pool.shape[3], ncand)

    def put(pool, new):
        blocks = pool[layer, cand]                    # (ncand, H, bs, Dh)
        blocks = blocks.at[ci, :, off].set(new.astype(pool.dtype))
        return pool.at[layer, cand].set(blocks)

    return put(k_pool, k_new), put(v_pool, v_new)


def append_kv(k_pool, v_pool, layer, slots, k_new, v_new):
    """`write_kv` for the decode step: ONE new token a sequence, so no
    two real tokens share a block (a shared prefix block is never
    written; padded rows all hit the null block, whose contents nothing
    reads) and the distinct blocks need not be looked for. slots (B,);
    k_new/v_new (B, n_heads, head_dim)."""
    bs = k_pool.shape[3]
    blk, off = slots // bs, slots % bs
    here = (jnp.arange(bs)[None, :] == off[:, None])[:, None, :, None]

    def put(pool, new):
        blocks = pool[layer, blk]                     # (B, H, bs, Dh)
        blocks = jnp.where(here, new.astype(pool.dtype)[:, :, None, :],
                           blocks)
        return pool.at[layer, blk].set(blocks)

    return put(k_pool, k_new), put(v_pool, v_new)


def write_kv_prompt(k_pool, v_pool, layer, table_row, k_new, v_new):
    """`write_kv` for positions 0..S-1 of ONE sequence: whole blocks,
    with no read. table_row (nblk,) int32; k_new/v_new (S, n_heads,
    head_dim). S is padded up to whole blocks with zeros: like the
    positions past the prompt's true length they fall in slots of this
    sequence that decode writes before anything reads them, or, past
    the allocated blocks, in the null block."""
    bs = k_pool.shape[3]
    S, H, Dh = k_new.shape
    nb = -(-S // bs)
    ids = table_row[:nb]

    def put(pool, new):
        new = jnp.pad(new.astype(pool.dtype),
                      ((0, nb * bs - S), (0, 0), (0, 0)))
        blocks = new.reshape(nb, bs, H, Dh).transpose(0, 2, 1, 3)
        return pool.at[layer, ids].set(blocks)

    return put(k_pool, k_new), put(v_pool, v_new)


def write_kv_prompt_ring(k_pool, v_pool, layer, table_row, k_new, v_new,
                         length):
    """`write_kv_prompt` into the columns of a RING (`CacheSpec.ring`;
    table_row (ring,)): of a prompt's blocks, column r takes the newest
    one that falls on it and is not past the prompt's last real token
    (position length - 1), so a prompt longer than the window leaves
    only what the window still sees, and the padding behind a prompt
    never lands on a block that is still seen. A column no such block
    falls on (the prompt is shorter than the ring) is not written: its
    write goes to the null block."""
    bs, ring = k_pool.shape[3], table_row.shape[0]
    S, H, Dh = k_new.shape
    nb = -(-S // bs)
    last = (length - 1) // bs
    src = last - (last - jnp.arange(ring)) % ring            # (ring,)
    ids = jnp.where(src >= 0, table_row, 0)

    def put(pool, new):
        new = jnp.pad(new.astype(pool.dtype),
                      ((0, nb * bs - S), (0, 0), (0, 0)))
        blocks = new.reshape(nb, bs, H, Dh).transpose(0, 2, 1, 3)
        return pool.at[layer, ids].set(blocks[jnp.maximum(src, 0)])

    return put(k_pool, k_new), put(v_pool, v_new)


def copy_block(*pools_src_dst):
    """Copy one block across every layer of every array of the pool
    (`PagedKVCache.arrays()`, then src and dst) — the prefix cache's
    copy-on-write op: a request that will write into a shared block
    (its tokens diverge mid-block, or its prompt/decode continues
    inside a cached tail) gets a private copy first, so a shared block
    is never mutated by a reader. An int8 pool's scale sidecars move
    WITH the data: a private copy under the source's scale is
    bit-identical to the shared original, so prefix-cache divergence
    stays logit-invariant under quantization. One dynamic-index update
    per array; under tensor-parallel placement the block axis is
    replicated and the head axis sharded, so the copy stays chip-local."""
    *pools, src, dst = pools_src_dst
    return tuple(p.at[:, dst].set(p[:, src]) for p in pools)


def write_kv_quant(k_pool, v_pool, k_scale, v_scale, layer, slots,
                   k_new, v_new, ncand=None):
    """Quantizing scatter for an int8 pool: write N new K/V rows into one
    layer's flat slots, requantizing each touched block symmetric-per-
    block-per-head. slots (N,) int32; k_new/v_new (N, n_heads, head_dim)
    float; scales (n_layers, num_blocks, n_heads) f32.

    Per touched block: scale goes MONOTONIC — s_new = max(s_old,
    amax(new rows)/127) — so rows written earlier under a smaller scale
    are rescaled in place (dequant with s_old, requant with s_new; when
    the scale is unchanged requantization is the exact identity, so a
    block is only re-rounded when a larger row actually arrives). The
    write unit is the whole block, as in `write_kv`; the ~2x saving is
    on the read side the kernel DMAs every step.

    `ncand` is the static upper bound on DISTINCT blocks the N slots can
    touch (default N): the N contiguous positions of a prefill chunk
    span at most (N-1)//block_size + 2 blocks incl. the null block, so
    callers that know the span pass it to shrink the gather. Writes
    aimed at the null block (padded rows) land there like the f32 path —
    its contents and scale are garbage that length masking never reads.
    """
    tb, off, cand, ci = _touched_blocks(slots, k_pool.shape[3], ncand)

    def upd(pool, scale, new):
        new = new.astype(jnp.float32)
        a = jnp.max(jnp.abs(new), axis=-1)                  # (N, H)
        plane = scale[layer].at[tb].max(a / 127.0)          # (NB, H)
        s_old = scale[layer][cand]                          # (ncand, H)
        s_new = plane[cand]
        s_safe = jnp.where(s_new > 0, s_new, 1.0)
        blk = pool[layer][cand].astype(jnp.float32) \
            * s_old[:, :, None, None]                       # (ncand,H,bs,Dh)
        blk = blk.at[ci, :, off].set(new)
        q = jnp.clip(jnp.rint(blk / s_safe[:, :, None, None]),
                     -127, 127).astype(jnp.int8)
        return (pool.at[layer, cand].set(q),
                scale.at[layer].set(plane))

    k_pool, k_scale = upd(k_pool, k_scale, k_new)
    v_pool, v_scale = upd(v_pool, v_scale, v_new)
    return k_pool, v_pool, k_scale, v_scale


def zero_block_scales(k_scale, v_scale, ids):
    """Reset the scale sidecars of freshly ALLOCATED blocks (ids (m,)
    int32, null-padded — zeroing the null block's garbage scale is
    harmless). A reused block id otherwise inherits its previous
    occupant's scale, and the monotonic max in `write_kv_quant` would
    quantize the new tokens at the stale (possibly much larger) scale —
    a silent precision leak. Prefix-cache SHARED blocks keep their
    scales: their data is reused, so their scale still describes it."""
    k_scale = k_scale.at[:, ids].set(0.0)
    v_scale = v_scale.at[:, ids].set(0.0)
    return k_scale, v_scale


def gather_kv(k_pool, v_pool, layer, block_table):
    """Read one layer's K/V for a batch of sequences by block table.
    block_table (B, nblk) -> k/v (B, nblk, n_heads, block_size,
    head_dim): the blocks AS THEY LIE in the pool, in table order, so
    position t of a sequence is [t // block_size, :, t % block_size].
    The caller contracts over them as they are (`_attend_live`, which
    hands in a chunk of the table's columns at a time): a transpose to
    (B, T, n_heads, head_dim) would copy everything gathered once more.
    Entries past each sequence's length are garbage and must be masked
    by the caller (`_attend_live`: the chunk's first position +
    arange(chunk tokens) <= position)."""
    # one gather over (layer, block): `pool[layer][table]` has the chip's
    # compiler write the layer's whole slice of the pool out first
    return k_pool[layer, block_table], v_pool[layer, block_table]


# ---------------------------------------------------------------------------
# cache views: how one layer's attention writes its new keys and values and
# reads the cache. The layer (models/transformer.py `block`) knows a view by
# `attend(layer, q, k, v)`, heads (N, H, Dh) in and out, and a step program
# (engine.py) reads the updated arrays off `pools` afterwards, in the order
# of `PagedKVCache.arrays()`. On a tensor-parallel mesh the same views run
# on a chip's shard of the heads.
# ---------------------------------------------------------------------------


def _place(spec, layer, pools, tables):
    """Where layer `layer` keeps its keys and values: (where its kind's
    K plane is in `pools`, V after it; the layer's index in them; its
    kind's columns of `tables` (B, W); the window; the ring). `spec`
    None is one kind over every layer: the two planes, the whole table,
    no window."""
    if spec is None or not spec.layer_kinds:
        return 0, layer, tables, 0, 0
    i = spec.kinds.index(spec.attn_kind(layer))
    rings = [spec.ring(k, pools[0].shape[3]) for k in spec.kinds]
    first = tables.shape[1] - sum(rings[1:])
    lo = first + sum(rings[1:i]) if i else 0
    return (2 * i, spec.layers_of(spec.kinds[i]).index(layer),
            tables[:, lo:lo + (rings[i] if i else first)],
            spec.window if rings[i] else 0, rings[i])


def _put(pools, i, planes):
    """`pools` with the pair at `i` replaced."""
    return pools[:i] + tuple(planes) + pools[i + 2:]


def _place_state(spec, layer):
    """Where layer `layer` keeps its recurrent state: (where the state
    plane is in the pools, the convolution's after it; the layer's index
    in them). A sequence's slot is the last column of its table row."""
    return (2 * spec.kinds.index("state"),
            spec.layers_of("state").index(layer))


#: query rows one pass of a prompt's attention scores at once, against
#: the keys of its band (`banded_attention`)
PROMPT_Q_BLOCK = 256


def prompt_attn_unfit(plane, group, bucket=None, layout="kv"):
    """Why a whole prompt's attention is XLA's (`banded_attention`) and
    not the kernel (ops/pallas_prompt_attention.py), or None: asked of a
    K plane as it lies and the query heads a cached head serves, by
    `PromptView.attend` while it traces (`bucket`: the program's rows)
    and by the engine of the plane it will hand that trace (`bucket`
    None: what it knows once; a prefill's own: which of the two that
    program holds), so the two cannot disagree. A `layout` other than
    keys and values never meets the view."""
    if layout != "kv":
        return ("the pool holds %s rows, not keys and values: "
                "`expanded_attention` scores their prompts" % layout)
    return _prompt.prompt_attention_unfit(bucket, plane.shape[-1], group,
                                          plane.dtype)


class PromptView:
    """Prefill of a whole prompt: the rows are positions 0..S-1 of ONE
    sequence of true `length`. Every layer's K/V go into the blocks of
    its kind's columns of `table_row` (`write_kv_prompt`; a ring's by
    `write_kv_prompt_ring`), and attention is causal over the prompt's
    own K/V inside the layer's band: the cache is written, not read. ONE
    kernel a layer where the gate lets it (`prompt_attn_unfit`), which
    scores the first `length` rows alone, every layer of one window a
    call site of one lowered function
    (ops/pallas_prompt_attention.py); `banded_attention` elsewhere."""

    def __init__(self, pools, table_row, spec, length):
        self.pools, self.table_row = tuple(pools), table_row
        self.spec, self.length = spec, length

    def attend(self, layer, q, k, v):
        i, j, row, window, ring = _place(self.spec, layer, self.pools,
                                         self.table_row[None])
        planes = self.pools[i:i + 2]
        if ring:
            planes = write_kv_prompt_ring(*planes, j, row[0], k, v,
                                          self.length)
        else:
            planes = write_kv_prompt(*planes, j, row[0], k, v)
        self.pools = _put(self.pools, i, planes)
        if prompt_attn_unfit(planes[0], q.shape[1] // k.shape[1],
                             q.shape[0]) is None:
            return _prompt.prompt_attention(
                q, k, v, self.length, window=window,
                interpret=default_interpret())
        return banded_attention(q, k, v, window, PROMPT_Q_BLOCK)

    def mix(self, layer, xbc, dt, w, cfg):
        """A state-space layer's mixer over the prompt as a chunked scan
        from an empty state (`mix_prompt`); the sequence's slot is
        overwritten with the state at the prompt's true `length` and the
        convolution's last real inputs, whatever the bucket's padding."""
        i, j = _place_state(self.spec, layer)
        y, state, tail = mix_prompt(xbc, dt, w, cfg, self.length)
        # the slot's new state in the plane's own order of axes: left to
        # choose, the chip's compiler keeps the scan's (N innermost) and
        # turns the WHOLE plane round to match it, twice a prefill
        # (PERF.md, PR 42)
        state = with_layout_constraint(
            state, Layout(major_to_minor=tuple(range(state.ndim))))
        slot = self.table_row[-1]
        planes = self.pools[i:i + 2]
        self.pools = _put(self.pools, i, (
            planes[0].at[j, slot].set(state.astype(planes[0].dtype)),
            planes[1].at[j, slot].set(tail.reshape(-1)
                                      .astype(planes[1].dtype))))
        return y


#: keys one pass of the live-gather view's attention loop folds in: a
#: whole number of blocks (PERF.md, PR 28: 128, 256 and 512 on the chip)
_DECODE_CHUNK_TOKENS = 128
#: what a key outside a row's window scores where a whole chunk may be
#: outside it: finite, so that the running maximum is, and what such a
#: chunk adds is wiped by the first chunk that holds a key the row sees
#: (the kernel's constant: both walks mask alike)
_UNSEEN = _walk.UNSEEN


def _attend_live(qh, k_pool, v_pool, layer, tables, positions, window=0):
    """Attention of one query a sequence (qh (B, H, Dh), the newest
    position) over layer `layer` of the pools, walking the block table
    only as far as the batch's longest live sequence: ONE loop whose
    body (a chunk of the table's columns gathered as the blocks lie,
    contracted, masked by position, folded into a running maximum,
    denominator and weighted sum in float32: the online softmax of
    ops/pallas_paged.py) is compiled once and whose trip count is read
    from `positions` on the device. So the bytes a step moves follow
    the live lengths with one program per batch bucket and no branch.
    The pools hold Hkv heads, H a multiple of it: query head h reads
    head h // (H / Hkv), contracted as the blocks lie.

    With a `window` the columns are a RING (`CacheSpec.ring`): column r
    of row b holds the newest block that falls on it, block
    `n - (n - r) % ring` for n the block of the row's position, or
    nothing yet (a negative block). The walk is then bounded by the
    ring as well, and a key is seen iff its position is real, not past
    the query's and less than `window` behind it.
    The pools are only read. Returns (B, H, Dh) float32."""
    B, H, Dh = qh.shape
    Hkv, block_size = k_pool.shape[2:4]
    G = H // Hkv
    nblk = tables.shape[1]
    cb = max(1, min(nblk, _DECODE_CHUNK_TOKENS // block_size))
    ct = cb * block_size
    scale = 1.0 / math.sqrt(Dh)
    # whole chunks: the columns added hold the null block, past every
    # position
    tables = jnp.pad(tables, ((0, 0), (0, -nblk % cb)))
    offs = jnp.arange(ct)
    qg = qh.reshape(B, Hkv, G, Dh)
    unseen = _UNSEEN if window else -jnp.inf

    def fold(c, carry):
        m, l, acc = carry
        tab = jax.lax.dynamic_slice_in_dim(tables, c * cb, cb, axis=1)
        ks, vs = gather_kv(k_pool, v_pool, layer, tab)   # (B,cb,Hkv,bs,Dh)
        # same masking/upcast semantics as attention_reference, with the
        # length mask standing in for the causal mask (the query IS the
        # newest position); position t is (block n, offset s) = divmod(t,
        # block_size), contracted over as the blocks lie in the pool
        s = jnp.einsum("bkgd,bnksd->bkgns", qg, ks).astype(jnp.float32) \
            * scale
        if window:
            n = positions[:, None] // block_size                   # (B, 1)
            col = (c * cb + jnp.arange(cb))[None, :]
            held = jnp.where(col < nblk, n - (n - col) % nblk, -1)
            at = (held[:, :, None] * block_size
                  + jnp.arange(block_size)).reshape(B, ct)
            live = (at >= 0) & (at <= positions[:, None]) \
                & (positions[:, None] - at < window)
        else:
            live = (c * ct + offs)[None, :] <= positions[:, None]  # (B, ct)
        s = jnp.where(live[:, None, :], s.reshape(B, H, ct), unseen)
        # position 0 is live in every row, so `m` is finite from the
        # first chunk on and a chunk wholly past a row adds exact zeros
        # (with a window: see `_UNSEEN`)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "bkgns,bnksd->bkgd", p.reshape(B, Hkv, G, cb, block_size),
            vs.astype(p.dtype)).reshape(B, H, Dh)
        return m_new, l, acc

    init = (jnp.full((B, H), unseen, jnp.float32),
            jnp.zeros((B, H), jnp.float32),
            jnp.zeros((B, H, Dh), jnp.float32))
    trips = jnp.max(positions) // ct + 1
    if window:
        trips = jnp.minimum(trips, tables.shape[1] // cb)
    _, l, acc = jax.lax.fori_loop(0, trips, fold, init)
    return acc / l[..., None]


def walk_unfit(plane, layout="kv"):
    """Why the gather path's decode step walks a cache with XLA's loop
    (`_attend_live`) and not with the kernel (ops/pallas_decode_walk.py),
    or None: asked of a plane as it lies (layers, blocks, heads,
    block_size, head_dim), by `LiveGatherView.attend` while it traces
    and by the engine of the plane it will hand that trace, so the two
    cannot disagree. A `layout` other than keys and values never meets
    the view."""
    if layout != "kv":
        return ("the pool holds %s rows, not keys and values: "
                "`gather_latent` reads them" % layout)
    return _walk.walk_fallback_reason(plane.shape[-1], plane.shape[3],
                                      plane.dtype)


class LiveGatherView:
    """Decode on the gather path: row b is sequence b's newest token at
    `positions[b]`. Its K/V are appended at its slot in the layer's
    kind's columns (`append_kv`), then the row attends over its
    sequence's blocks by table as far as the batch's longest live
    sequence reaches, or round the ring (`_attend_live`), or, where the
    gate lets it (`walk_unfit`), by ONE kernel a layer that
    reads each row's own live blocks (ops/pallas_decode_walk.py), every
    layer of a kind a call site of one lowered function: the layer's
    index goes in as data. `tables` is
    the full-capacity table, every kind's columns side by side
    (`PagedKVCache.row`); a padded row carries the all-null one.
    `slots` are the one kind's (`flat_slots`), or None where the view
    works them out a kind. `rows` is how many rows the engine's batch
    can hold (the step's `carry` has as many): the kernel is handed its
    operands at that many, so every batch bucket's step calls one
    traced and lowered kernel (`decode_walk`)."""

    def __init__(self, pools, tables, positions, slots=None, spec=None,
                 rows=None):
        self.pools = tuple(pools)
        self.tables, self.positions, self.spec = tables, positions, spec
        self.slots, self.rows = slots, rows

    def attend(self, layer, q, k, v):
        i, j, tab, window, ring = _place(self.spec, layer, self.pools,
                                         self.tables)
        slots = self.slots
        if slots is None:
            slots = flat_slots(tab, self.positions, self.pools[i].shape[3],
                               ring)
        planes = append_kv(*self.pools[i:i + 2], j, slots, k, v)
        self.pools = _put(self.pools, i, planes)
        if walk_unfit(planes[0]) is None:
            return _walk.decode_walk(
                q, *planes, tab, self.positions, jnp.int32(j),
                scale=1.0 / math.sqrt(q.shape[-1]), window=window,
                ring=ring, rows=self.rows, interpret=default_interpret())
        return _attend_live(q, *planes, j, tab, self.positions, window)

    def mix(self, layer, xbc, dt, w, cfg):
        """A state-space layer's mixer, one recurrence step a row
        (`mix_step`): each row's state and last inputs are found through
        its table's last column (a row's place in the batch changes from
        step to step). The states are updated where they lie by ONE
        kernel a layer (ops/pallas_ssm_step.py; the layer's index and
        the slots as data) where the gate lets it (`state_step_unfit`),
        else read by one gather over (layer, slot) and written back to
        the same slots. A padded row's slot is the null slot."""
        i, j = _place_state(self.spec, layer)
        slots = self.tables[:, -1]
        state_p, conv_p = self.pools[i:i + 2]
        tail = conv_p[j, slots].reshape(xbc.shape[0], -1, xbc.shape[1])

        def update(decay, dtx, Bm, Cm):
            nonlocal state_p
            if state_step_unfit(state_p) is None:
                state_p, y = _ssm.ssm_step(
                    state_p, jnp.int32(j), slots, decay, dtx, Bm, Cm,
                    interpret=default_interpret())
                return y
            h, y = state_update(state_p[j, slots].astype(jnp.float32),
                                decay, dtx, Bm, Cm)
            state_p = state_p.at[j, slots].set(h.astype(state_p.dtype))
            return y

        y, tail = mix_step(xbc, dt, tail, w, cfg, update)
        self.pools = _put(self.pools, i, (
            state_p, conv_p.at[j, slots].set(tail.reshape(xbc.shape[0], -1))))
        return y


def state_step_unfit(state_plane):
    """Why the decode step updates a state plane with XLA's gather and
    scatter and not with the kernel (ops/pallas_ssm_step.py), or None:
    asked of the plane as it lies, by `LiveGatherView.mix` while it
    traces and by the engine of the plane it will hand that trace."""
    return _ssm.step_fallback_reason(state_plane)


class PagedView:
    """The paged path: the rows are B sequences x C consecutive positions
    from `q_starts` (B,), row-major (C = 1 a decode step, a chunk's or
    a speculative pass's length otherwise). Their K/V are written at
    `slots` (B * C,), then ONE ragged paged-attention kernel a layer
    (ops/pallas_paged.py) walks `tables` (B, w) in place over the
    layer's planes: its mask `key_pos <= q_starts[b] + i` is the causal
    mask within the C positions and the full-history mask across the
    cache. `ncand` is the static bound on distinct blocks the slots
    touch (`write_kv`); None says one token a sequence (`append_kv`).

    With four arrays in `pools` the pool is int8 and the last two are
    its scale sidecars: the writes quantize (`write_kv_quant`) and the
    kernel dequantizes in VMEM. The choice is made while tracing, so the
    f32 program holds nothing of the other."""

    def __init__(self, pools, tables, q_starts, slots, ncand=None):
        self.pools = tuple(pools)
        self.tables, self.q_starts = tables, q_starts
        self.slots, self.ncand = slots, ncand

    def attend(self, layer, q, k, v):
        scales = {}
        if len(self.pools) == 4:
            self.pools = write_kv_quant(*self.pools, layer, self.slots,
                                        k, v, ncand=self.ncand)
            scales = dict(k_scale=self.pools[2][layer],
                          v_scale=self.pools[3][layer])
        elif self.ncand is None:
            self.pools = append_kv(*self.pools, layer, self.slots, k, v)
        else:
            self.pools = write_kv(*self.pools, layer, self.slots, k, v,
                                  ncand=self.ncand)
        k_pool, v_pool = self.pools[:2]
        B = self.tables.shape[0]
        att = paged_attention(q.reshape(B, -1, *q.shape[1:]), k_pool[layer],
                              v_pool[layer], self.tables, self.q_starts,
                              k_pool.shape[3], **scales)   # (B, C, H, Dh)
        return att.reshape(q.shape)


# ---------------------------------------------------------------------------
# the latent layout: one array (n_layers, num_blocks, block_size, width)
# ---------------------------------------------------------------------------


def _rows(pool, new):
    """`new` (..., latent_dim) as rows of the pool: its dtype, zeros up to
    its width."""
    pad = [(0, 0)] * (new.ndim - 1) + [(0, pool.shape[-1] - new.shape[-1])]
    return jnp.pad(new.astype(pool.dtype), pad)


def append_latent(pool, layer, slots, new):
    """`append_kv` for the latent pool: ONE new row a sequence. slots
    (B,); new (B, latent_dim). The block each row falls in is read, the row
    set, the block written back: whole trailing axes as they lie, so
    the donated pool is updated in place."""
    bs = pool.shape[2]
    blk, off = slots // bs, slots % bs
    here = (jnp.arange(bs)[None, :] == off[:, None])[:, :, None]
    blocks = jnp.where(here, _rows(pool, new)[:, None, :],
                       pool[layer, blk])                     # (B, bs, W)
    return pool.at[layer, blk].set(blocks)


def write_latent_prompt(pool, layer, table_row, new):
    """`write_kv_prompt` for the latent pool: positions 0..S-1 of ONE
    sequence, whole blocks, no read. table_row (nblk,); new (S,
    latent_dim), padded up to whole blocks with zeros."""
    bs, W = pool.shape[2:]
    S = new.shape[0]
    nb = -(-S // bs)
    new = jnp.pad(_rows(pool, new), ((0, nb * bs - S), (0, 0)))
    return pool.at[layer, table_row[:nb]].set(new.reshape(nb, bs, W))


def gather_latent(pool, layer, block_table):
    """One layer's cached rows for a batch of sequences by block table:
    (B, nblk) -> (B, nblk, block_size, row_width), the blocks as they
    lie; entries past a sequence's length are garbage the caller masks,
    lanes past `latent_dim` are zero."""
    return pool[layer][block_table]
