"""Pallas decode walk: the gather path's attention of one query a
sequence, one kernel a layer, over the block pool as it lies.

What it replaces (serving/kv_cache.py `_attend_live`, which stays as the
fallback and as the tests' reference) is an XLA loop that gathers a chunk
of 128 keys of EVERY row of the batch, writes the chunk out, reads it back
for the contraction, and goes as far as the batch's LONGEST sequence, with
nothing fetched while the last chunk is contracted: about 45 % of a v5e's
bandwidth (PERF.md, PRs 28 and 31). Here one grid step is one row of the
batch. The row's own count of live blocks (`positions[b] // block_size +
1`, at most the ring on a window layer) bounds its own loop; each block
`pool[layer, tables[b, c]]` is one contiguous (Hkv, block_size, Dh) slab,
brought to VMEM by the kernel's own asynchronous copies, two buffers, the
next chunk (the next ROW's first chunk after a row's last) in flight while
this one is contracted; a chunk is sized by bytes (`CHUNK_BYTES`), so 8
cached heads take four times the tokens of 32; all G query heads of a
group are contracted against the one cached head; scores, running
maximum, denominator and sum are float32, masked by position exactly as
`_attend_live` masks. The planes are handed in WHOLE and stay in HBM: the
layer's index in them is data (a scalar-prefetch operand beside the table
and the positions), never a slice and never a Python constant.

That the index is data is also what keeps a warm process's set-up where
it was (PERF.md §6, PR 33). A warm process traces and lowers every step
program anew (the persistent cache's key is made from the module), so
what is traced or lowered per layer, or per program, is paid in every
`setup_s` (PRs 25 and 32 were refused for it), and on the chip's host
one trace of this kernel and its lowering to Mosaic take 0.45 s. So: the
calls a step program makes, one a layer, are call sites of ONE jitted
function a cache kind (`_walk_rows`), and the step's module holds one
`tpu_custom_call` a kind, not one a layer; its operands are padded to
the rows the engine's batch can hold and the batch itself is the grid's
extent, as data, so the programs of EVERY batch bucket call the same
function; and that function's body is the kernel traced and lowered once
a process and kept as text (`_lowered_once`): a program merges the
module in and calls it (ops/pallas_splice.py, which also imports Pallas
on a thread of its own as the engine is built, `preload`: about a second
of Python that the first decode program's trace would otherwise wait
for).

The interpreter runs the same kernel on the CPU for the parity tests
(tests/test_pallas_decode_walk.py); `chip_smoke.py` compiles it with
Mosaic at both cells' shapes and compares it with a dense reference on the
chip.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from .pallas_fused import _cost
from .pallas_paged import _SUBLANES
from .pallas_splice import Spliced

#: what a key the row does not see scores, here and in `_attend_live`:
#: finite, so that the running maximum is, and what a chunk of unseen keys
#: adds is wiped by the first chunk that holds a key the row sees
UNSEEN = -1e30
#: bytes of ONE plane that one chunk brings to VMEM (two planes, two
#: buffers: four times this is held): whole blocks, at least one
CHUNK_BYTES = 512 * 1024


def walk_fallback_reason(head_dim, block_size, kv_dtype, backend=None):
    """Gate of the kernel, decided while tracing from what the code can
    observe: the lane dimension (head_dim) a multiple of 128 and
    block_size whole tiles of the pool's dtype (a block's (block_size,
    head_dim) is what one copy lands at a tile-aligned offset of the
    chunk), on a compiled TPU backend (the interpreter is the tests'
    tool: on the CPU the XLA loop is the faster answer). Returns None
    where the kernel runs, else why `_attend_live` does; the engine
    records it on `walk_fallback`."""
    backend = backend or jax.default_backend()
    if backend != "tpu":
        return ("the backend is %s: the kernel is compiled for the TPU, "
                "elsewhere the XLA loop walks the table" % backend)
    if head_dim % 128 != 0:
        return ("head_dim %d is not a multiple of the 128-lane tile"
                % head_dim)
    rows = _SUBLANES.get(jnp.dtype(kv_dtype).itemsize)
    if rows is None or block_size % rows != 0:
        return ("block_size %d is not a multiple of the %s-row tile of a "
                "%s pool" % (block_size, rows, jnp.dtype(kv_dtype).name))
    return None


def chunk_blocks(n_kv_heads, block_size, head_dim, kv_dtype, width,
                 chunk_bytes=CHUNK_BYTES):
    """Blocks a chunk holds: `chunk_bytes` of one plane in whole blocks,
    at least one, no more than the table has columns."""
    block_bytes = n_kv_heads * block_size * head_dim \
        * jnp.dtype(kv_dtype).itemsize
    return int(max(1, min(width, chunk_bytes // block_bytes)))


def _kernel(tab_ref, pos_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, slot_ref, m_scr, l_scr, acc_scr, *,
            scale, block_size, width, window, ring, cb):
    """Grid step b: row b of the batch over its own live blocks. The
    chunk buffers, their semaphores and `slot_ref` (the buffer that holds
    this row's first chunk) outlive a grid step: a row's last chunk
    starts the next row's first."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # nothing here is negative, so lax's truncating `div` and `rem` are
    # the floor's, at a fifth of the equations `//` and `%` trace to:
    # the kernel is traced and lowered inside a warm process's set-up
    def div(a, n):
        return jax.lax.div(a, np.int32(n))

    def rem(a, n):
        return jax.lax.rem(a, np.int32(n))

    def clamp(a, hi):
        return jax.lax.clamp(np.int32(0), a, np.int32(hi))

    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    # an index past a plane's end would send a copy outside the array,
    # which halts the chip where XLA's gather would clamp: clamp here too
    layer = clamp(layer_ref[0], k_hbm.shape[0] - 1)
    ct = cb * block_size

    def live_blocks(row):
        return jax.lax.min(div(pos_ref[row], block_size) + 1,
                           np.int32(ring or width))

    def chunk_copies(row, c, slot, i):
        """The two copies of block i of chunk c of `row` into `slot`."""
        blk = clamp(tab_ref[row * width + c * cb + i], k_hbm.shape[1] - 1)
        at = pl.ds(pl.multiple_of(i * block_size, block_size), block_size)
        return [pltpu.make_async_copy(hbm.at[layer, blk],
                                      buf.at[slot, :, at, :],
                                      sems.at[p, slot])
                for p, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf)))]

    def each_block(row, c, slot, what):
        """`what` over the copies of the live blocks of chunk c: a block
        past the row's last is neither fetched nor waited for."""
        def one(i, _):
            for copy in chunk_copies(row, c, slot, i):
                what(copy)
            return 0
        jax.lax.fori_loop(
            0, jax.lax.min(np.int32(cb), live_blocks(row) - c * cb), one, 0)

    start = functools.partial(each_block, what=lambda copy: copy.start())
    wait = functools.partial(each_block, what=lambda copy: copy.wait())

    @pl.when(b == 0)
    def _first_row():
        # what a buffer holds past a row's live blocks is what an earlier
        # chunk left there, masked below; before the first chunk it is
        # whatever the memory held, and 0 x NaN is NaN
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        start(0, 0, 0)

    slot0 = slot_ref[0]
    pos = pos_ref[b]
    n_chunks = div(live_blocks(b) + (cb - 1), cb)
    m_scr[...] = jnp.full_like(m_scr, UNSEEN)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    offs = jax.lax.broadcasted_iota(jnp.int32, (1, ct), 1)
    # the group of query heads padded to whole sublanes of the planes'
    # dtype HERE, not in HBM (16 times a row's queries and outputs where
    # G is 1): rows are independent, the padded ones are never written
    q = q_ref[0]                                          # (Hkv, G, Dh)
    Hkv, G, Dh = q.shape
    Gp = acc_scr.shape[1]
    if G == 1:
        q = jnp.broadcast_to(q, (Hkv, Gp, Dh))
    elif G < Gp:
        q = jnp.concatenate(
            [q, jnp.zeros((Hkv, Gp - G, Dh), q.dtype)], axis=1)

    def fold(c, _):
        slot = rem(slot0 + c, 2)
        more = c + 1 < n_chunks

        @pl.when(more | (b + 1 < n_rows))
        def _prefetch():
            # this row's next chunk or, after its last, the next row's
            # first, which that row finds in `slot_ref`
            slot_ref[0] = jax.lax.select(more, slot0, 1 - slot)
            start(jax.lax.select(more, b, b + 1),
                  jax.lax.select(more, c + 1, jnp.zeros_like(c)), 1 - slot)

        wait(b, c, slot)
        k = k_buf[slot]                                   # (Hkv, ct, Dh)
        v = v_buf[slot]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # (Hkv, Gp, ct)
        if ring:
            # column r holds the newest block that falls on it: block
            # n - (n - r) % ring for n the block of the row's position
            n = div(pos, block_size)
            r = rem(n, ring)
            col = c * cb + div(offs, block_size)
            back = jnp.where(col <= r, r - col, r - col + ring)
            at = (n - back) * block_size + rem(offs, block_size)
            live = (at >= 0) & (at <= pos) & (pos - at < window) \
                & (col < ring)
        else:
            live = c * ct + offs <= pos                   # (1, ct)
        s = jnp.where(live[None], s, UNSEEN)
        m_prev = m_scr[...]                               # (Hkv, Gp, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # (Hkv, Gp, Dh)
        m_scr[...] = m_new
        return 0

    jax.lax.fori_loop(0, n_chunks, fold, 0)
    o_ref[0] = (acc_scr[...] / l_scr[...])[:, :G]


def _kernel_call(q, k_pool, v_pool, tables, positions, layer, n_rows, *,
                 scale, window, ring, chunk_bytes, interpret):
    """The kernel over the first `n_rows` (int32 (1,), DATA: the grid's
    extent) of R rows: q (R, Hkv, G, Dh) in the planes' dtype, tables
    (R * W,) flat, positions (R,), layer (1,). Returns (R, Hkv, G, Dh)
    float32; rows past `n_rows` are not written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, Hkv, G, Dh = q.shape
    block_size = k_pool.shape[3]
    W = tables.shape[0] // R
    dtype = k_pool.dtype
    Gp = -(-G // _SUBLANES[dtype.itemsize]) * _SUBLANES[dtype.itemsize]
    cb = chunk_blocks(Hkv, block_size, Dh, dtype, ring or W, chunk_bytes)
    ct = cb * block_size

    def row(b, *_prefetched):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_rows[0],),
        in_specs=[pl.BlockSpec((1, Hkv, G, Dh), row),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, Hkv, G, Dh), row),
        scratch_shapes=[pltpu.VMEM((2, Hkv, ct, Dh), dtype),
                        pltpu.VMEM((2, Hkv, ct, Dh), dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((Hkv, Gp, 1), jnp.float32),
                        pltpu.VMEM((Hkv, Gp, 1), jnp.float32),
                        pltpu.VMEM((Hkv, Gp, Dh), jnp.float32)])
    # declared for XLA's scheduler as the table's whole width: what a
    # call moves follows the rows' live blocks, known on the device alone
    keys = R * (ring or W) * block_size
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_size=block_size,
                          width=W, window=window, ring=ring, cb=cb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, Hkv, G, Dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="decode_walk",
        **_cost(4 * keys * Hkv * G * Dh,
                2 * keys * Hkv * Dh * dtype.itemsize + 6 * R * Hkv * G * Dh,
                keys * Hkv * G),
    )(tables, positions, layer, q, k_pool, v_pool)


#: the kernel as a step program sees it: one call of the module traced
#: and lowered once a process (ops/pallas_splice.py), 0.45 s a program on
#: the chip's host otherwise (PERF.md §6, PR 33)
_spliced = Spliced(
    "decode_walk", _kernel_call,
    lambda q, *operands, **static: jax.core.ShapedArray(q.shape, jnp.float32))
_lowered_once = _spliced.lowered_once


@functools.partial(jax.jit, static_argnames=("scale", "window", "ring",
                                             "chunk_bytes", "interpret"))
def _walk_rows(*operands, interpret, **static):
    """One function a cache kind and a process: every layer of every
    step program is a call site of it (its operands' shapes do not
    follow the batch's bucket: `decode_walk` pads to the engine's
    rows)."""
    if interpret:
        return _kernel_call(*operands, interpret=True, **static)
    return _spliced(*operands, **static)


def decode_walk(q, k_pool, v_pool, tables, positions, layer, *, scale,
                window=0, ring=0, rows=None, chunk_bytes=CHUNK_BYTES,
                interpret=False):
    """Attention of one query a sequence over one layer of the pools.

    q:         (B, H, Dh), the newest position of each row.
    k_pool, v_pool: (layers of the kind, blocks, Hkv, block_size, Dh),
               whole; H a multiple of Hkv (query head h reads cached head
               h // (H / Hkv)).
    tables:    (B, W) int32, the kind's columns; with `ring` (= W) a ring:
               column r of row b holds block `n - (n - r) % ring` of its
               sequence, n the block of its position, or nothing yet.
    positions: (B,) int32; a padded row carries position 0 and the
               all-null table and reads one block.
    layer:     int32 scalar, the layer's index in the planes, as DATA:
               every layer of a kind is a call site of one function.
    rows:      the rows the engine's batch can hold (None: B). The
               kernel's operands are padded to it and its grid is B, as
               data, so the steps of EVERY batch bucket call one traced
               and lowered kernel; the padding is never visited.
    A key is seen iff its position is real, not past the query's and,
    with `window`, less than `window` behind it. Returns (B, H, Dh)
    float32."""
    B, H, Dh = q.shape
    Hkv = k_pool.shape[2]
    pad = max(rows or B, B) - B
    qg = jnp.pad(q.reshape(B, Hkv, H // Hkv, Dh).astype(k_pool.dtype),
                 ((0, pad), (0, 0), (0, 0), (0, 0)))
    out = _walk_rows(
        qg, k_pool, v_pool, jnp.pad(tables, ((0, pad), (0, 0))).reshape(-1),
        jnp.pad(positions, (0, pad)),
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        jnp.full((1,), B, jnp.int32), scale=scale, window=window, ring=ring,
        chunk_bytes=chunk_bytes, interpret=interpret)
    return out[:B].reshape(B, H, Dh)


def reference(q, k_pool, v_pool, tables, positions, layer, window=0):
    """The same attention, plainly: the table's every column gathered,
    every key given its position (on a ring, column r of a row at block n
    holds block `n - (n - r) % ring`), one masked softmax in float32.
    What the tests and `chip_smoke.py` hold the kernel to."""
    B, H, Dh = q.shape
    Hkv, block_size = k_pool.shape[2:4]
    W = tables.shape[1]
    f32 = jnp.float32
    ks = k_pool[layer, tables].astype(f32)            # (B, W, Hkv, bs, Dh)
    vs = v_pool[layer, tables].astype(f32)
    held = jnp.broadcast_to(jnp.arange(W), (B, W))
    if window:
        n = positions[:, None] // block_size
        held = n - (n - held) % W
    at = (held[:, :, None] * block_size
          + jnp.arange(block_size)).reshape(B, W * block_size)
    pos = positions[:, None]
    live = (at >= 0) & (at <= pos)
    if window:
        live &= pos - at < window
    s = jnp.einsum("bkgd,bnksd->bkgns", q.reshape(B, Hkv, -1, Dh).astype(f32),
                   ks, precision="highest") / jnp.sqrt(f32(Dh))
    s = jnp.where(live[:, None, None], s.reshape(B, Hkv, -1, at.shape[1]),
                  -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgns,bnksd->bkgd",
                      p.reshape(B, Hkv, -1, W, block_size), vs,
                      precision="highest").reshape(B, H, Dh)
