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
extent 1 of H, which it refuses). The per-token scatter and the by-table
gather both remain single advanced-indexing ops XLA lowers without
data-dependent shapes (`write_kv` splits a flat slot into (block,
offset) with one divmod). Only this module and the kernel know the
order of the axes.
"""
from __future__ import annotations

import math

import numpy as np
import jax.numpy as jnp

from ..base import MXNetError


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
    """Device-side K/V pools plus the host free-list.

    Arrays: ``k``/``v`` of shape (n_layers, num_blocks, n_heads,
    block_size, head_dim) — contiguous-per-layer block layout (see module
    docstring). They are plain jax arrays threaded through the jitted
    engine functions (functional update: each step returns the new
    pools).

    With ``kv_dtype="int8"`` the pools store symmetric-per-block int8
    and grow f32 scale SIDECARS ``k_scale``/``v_scale`` of shape
    (n_layers, num_blocks, n_heads) living beside the pool in the same
    contiguous block layout: one scale per (layer, block, head), so the
    paged kernel scalar-prefetches exactly one f32 per DMA'd block per
    head and dequantizes in VMEM. `blocks_for`, tables, and the host
    free-list are precision-agnostic — a block id means the same thing
    in both layouts.
    """

    def __init__(self, n_layers, n_heads, head_dim, block_size=16,
                 num_blocks=64, dtype=jnp.float32, kv_dtype=None):
        self.n_layers = n_layers
        self.n_heads = n_heads
        self.head_dim = head_dim
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.pool = BlockPool(num_blocks)
        if kv_dtype is not None and str(kv_dtype) != "int8":
            raise MXNetError("kv_dtype %r is not supported (int8 or "
                             "None)" % (kv_dtype,))
        self.kv_dtype = "int8" if kv_dtype is not None else None
        shape = (n_layers, num_blocks, n_heads, block_size, head_dim)
        pool_dtype = jnp.int8 if self.kv_dtype else dtype
        self.k = jnp.zeros(shape, pool_dtype)
        self.v = jnp.zeros(shape, pool_dtype)
        if self.kv_dtype:
            sshape = (n_layers, num_blocks, n_heads)
            self.k_scale = jnp.zeros(sshape, jnp.float32)
            self.v_scale = jnp.zeros(sshape, jnp.float32)
        else:
            self.k_scale = self.v_scale = None

    @property
    def quantized(self):
        return self.kv_dtype is not None

    def place(self, sharding, scale_sharding=None):
        """Lay the device pools out under `sharding` (a NamedSharding, or
        the one device of a placed single-chip engine).
        The tensor-parallel engine shards the HEAD axis — each chip owns
        n_heads/k heads of every block — so block ids, tables, and the
        host free-list are placement-agnostic and unchanged. A quantized
        pool's scale sidecars shard on the same head axis via
        `scale_sharding` (their (L, NB, H) layout drops the trailing
        token/dim axes), so each chip's scales are chip-local."""
        import jax
        self.k = jax.device_put(self.k, sharding)
        self.v = jax.device_put(self.v, sharding)
        if self.quantized and scale_sharding is not None:
            self.k_scale = jax.device_put(self.k_scale, scale_sharding)
            self.v_scale = jax.device_put(self.v_scale, scale_sharding)

    def blocks_for(self, n_tokens):
        """Blocks needed to hold n_tokens KV entries — by construction
        the kernel-side table width for a sequence of that length:
        position n_tokens-1 lives in block (n_tokens-1)//block_size, the
        table's last occupied slot."""
        return max(1, math.ceil(n_tokens / self.block_size))

    def table_row(self, block_ids, n_entries):
        """Fixed-width int32 table row: allocated ids, null-padded."""
        row = np.zeros((n_entries,), np.int32)
        row[:len(block_ids)] = block_ids
        return row

    def utilization(self):
        return self.pool.in_use / float(self.num_blocks - 1)


# ---------------------------------------------------------------------------
# pure ops used inside the jitted engine functions
# ---------------------------------------------------------------------------


def flat_slots(block_table, positions, block_size):
    """Flat pool slot for each (row, position): the position'th token of a
    sequence lives in its table's position//bs block at offset
    position%bs. block_table (B, nblk), positions (B,) -> (B,)."""
    blk = jnp.take_along_axis(block_table,
                              positions[:, None] // block_size,
                              axis=1)[:, 0]
    return blk * block_size + positions % block_size


def prompt_slots(table_row, length_cap, block_size):
    """Flat slots for prompt positions 0..length_cap-1 of ONE sequence.
    table_row (nblk,) -> (length_cap,). Positions past the allocated
    blocks hit null-padded table entries -> the null block."""
    pos = jnp.arange(length_cap)
    return table_row[pos // block_size] * block_size + pos % block_size


def write_kv(k_pool, v_pool, layer, slots, k_new, v_new):
    """Scatter new K/V entries into one layer's flat slots (block id *
    block_size + offset). slots (...,) int32; k_new/v_new (..., n_heads,
    head_dim)."""
    bs = k_pool.shape[3]
    blk, off = slots // bs, slots % bs
    # advanced indices split by the head slice: the indexed dims lead, so
    # the update is (..., n_heads, head_dim) like k_new
    k_pool = k_pool.at[layer, blk, :, off].set(k_new.astype(k_pool.dtype))
    v_pool = v_pool.at[layer, blk, :, off].set(v_new.astype(v_pool.dtype))
    return k_pool, v_pool


def copy_block(k_pool, v_pool, src, dst):
    """Copy one block's K/V across every layer — the prefix cache's
    copy-on-write op: a request that will write into a shared block
    (its tokens diverge mid-block, or its prompt/decode continues
    inside a cached tail) gets a private copy first, so a shared block
    is never mutated by a reader. One dynamic-index update per pool;
    under tensor-parallel placement the block axis is replicated and
    the head axis sharded, so the copy stays chip-local."""
    k_pool = k_pool.at[:, dst].set(k_pool[:, src])
    v_pool = v_pool.at[:, dst].set(v_pool[:, src])
    return k_pool, v_pool


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
    write unit is the whole block, not the token: an append rewrites
    block_size slots where the f32 path rewrites one. That amplification
    is on the (small) write side; the ~2x saving is on the read side the
    kernel DMAs every step.

    `ncand` is the static upper bound on DISTINCT blocks the N slots can
    touch (default N): the N contiguous positions of a prefill chunk
    span at most (N-1)//block_size + 2 blocks incl. the null block, so
    callers that know the span pass it to shrink the gather. Writes
    aimed at the null block (padded rows) land there like the f32 path —
    its contents and scale are garbage that length masking never reads.
    """
    bs = k_pool.shape[3]
    n = slots.shape[0]
    if ncand is None:
        ncand = n
    ncand = min(ncand, n)
    tb, off = slots // bs, slots % bs                       # (N,)
    cand = jnp.unique(tb, size=ncand, fill_value=0)         # (ncand,)
    # token i updates candidate row ci: every tb[i] is present in cand
    # by the ncand bound, and duplicate fill rows compute identical
    # updates from identical inputs, so the scatter below is consistent
    ci = jnp.argmax(cand[None, :] == tb[:, None], axis=1)   # (N,)

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


def copy_block_quant(k_pool, v_pool, k_scale, v_scale, src, dst):
    """`copy_block` for an int8 pool: the COW copy moves the scale
    sidecars WITH the data — a private copy under the source's scale is
    bit-identical to the shared original, so prefix-cache divergence
    stays logit-invariant under quantization."""
    k_pool = k_pool.at[:, dst].set(k_pool[:, src])
    v_pool = v_pool.at[:, dst].set(v_pool[:, src])
    k_scale = k_scale.at[:, dst].set(k_scale[:, src])
    v_scale = v_scale.at[:, dst].set(v_scale[:, src])
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


def gather_kv(k_pool, v_pool, layer, block_table, block_size):
    """Read one layer's K/V for a batch of sequences by block table.
    block_table (B, nblk) -> k/v (B, nblk*block_size, n_heads, head_dim),
    position-ordered; entries past each sequence's length are garbage and
    must be masked by the caller (mask = arange(T) <= position)."""
    B, nblk = block_table.shape

    def read(pool):
        blocks = pool[layer][block_table]       # (B, nblk, H, bs, Dh)
        H, Dh = blocks.shape[2], blocks.shape[4]
        return blocks.transpose(0, 1, 3, 2, 4).reshape(
            B, nblk * block_size, H, Dh)

    return read(k_pool), read(v_pool)
