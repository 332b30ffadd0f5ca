"""Pallas ragged paged-attention: decode/prefill reads straight off the
block pool.

Why a hand kernel: the PR 1 serving engine decodes by GATHERING each
sequence's K/V blocks per layer (serving/kv_cache.gather_kv), a chunk of
the block table at a time up to the BATCH's longest live sequence
(serving/kv_cache.py `_attend_live`), and running a masked online softmax
over them — every decoded token pays HBM reads of the longest history in
its batch plus a materialized copy of each chunk. Following "Ragged Paged
Attention" (arxiv 2604.15464, PAPERS.md) the decode read should instead
be ONE kernel that walks the block table in place: the grid iterates
(batch row, head, table slot), a scalar-prefetched block table drives the
BlockSpec index map so each grid step DMAs exactly one (block_size, Dh)
slab of the (num_blocks, H, block_size, Dh) pool into VMEM, and an
online-softmax accumulator (running max + denominator in VMEM scratch,
the flash-attention formulation of ops/pallas_attention.py) folds the
block in — no dense gather is ever materialized and scores never leave
the chip.

Raggedness: every sequence carries its TRUE last position (`q_start`).
Table slots past a row's live blocks are dead — the kernel skips their
compute entirely (`pl.when`) and the index map clamps them to the row's
last live block, so Pallas's revisit-elision skips their DMA too. The
caller additionally buckets the table WIDTH to the longest live sequence
in the batch (serving/engine.py), so the bytes a decode step moves track
true lengths, never the padded pool capacity — the compiler-visible O(1)
per-token cache read of arxiv 2603.09555.

One kernel serves both phases: decode is Tq=1 (one query row per
sequence), chunked prefill is Tq=chunk (a fixed-shape query block whose
K/V were appended to the pool just before the call; the ragged mask
`key_pos <= q_start + i` doubles as the causal mask within the chunk).

Tensor-parallel serving (serving/tp.py) runs this SAME kernel inside
shard_map over a head-sharded pool: each chip sees H/k heads of every
block and walks the same replicated table. Nothing here is tp-aware —
the head grid dimension and the declared CostEstimate are computed from
the (local) shapes the kernel receives, so per-chip bytes scale ~1/k by
construction (`paged_call_cost`). Online softmax is per-head, so the
sharded call needs no cross-chip traffic.

Every pallas_call declares a CostEstimate: on TPU the kernel is an opaque
custom call, and without declared flops/bytes the XLA cost model — the
A/B instrument of benchmarks/serving_bytes_report.py — would count it as
moving zero bytes.

On CPU the kernel runs in Pallas interpreter mode; the parity tests
(tests/test_pallas_paged.py) prove it equal to the dense gather path
there. `chip_smoke.py` compiles it with Mosaic (f32, bf16 and int8 pools)
and compares it with the same reference on the chip.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from .pallas_attention import default_interpret
from .pallas_fused import _cost


def paged_enabled():
    """MXNET_PAGED_ATTENTION=1 — read when an Engine is constructed
    (docs/ENV_VARS.md)."""
    return os.environ.get("MXNET_PAGED_ATTENTION", "0") == "1"


def paged_call_cost(B, Tq, H, Dh, w, block_size, kv_itemsize=4,
                    q_itemsize=4, scale_blocks=0):
    """Declared (flops, bytes) of ONE paged_attention call — the
    CostEstimate `_make_paged` hands XLA, factored out so instruments
    (benchmarks/serving_bytes_report.py) can cite the same numbers.
    `H` is the head count THE KERNEL SEES: under tensor-parallel serving
    (serving/tp.py) each chip runs the kernel over its H/k local heads
    of the pool shard, so the declared per-chip bytes scale ~1/k by this
    very formula — tables/q_start (replicated int32) are the only terms
    that don't. A quantized pool passes `kv_itemsize=1` plus
    `scale_blocks=num_blocks` (the f32 scale sidecars are scalar-
    prefetched whole, once per call): the dominant K/V block term shrinks
    4x by construction, which the committed cost-model A/B proves."""
    nk = B * H * w * block_size           # pool tokens touched
    flops = 4 * nk * Tq * Dh              # 2 MACs/pair for QK and PV
    bytes_ = (2 * nk * Dh * kv_itemsize           # K + V blocks walked
              + 2 * B * Tq * H * Dh * q_itemsize  # q in, out back
              + 2 * scale_blocks * H * 4          # k/v scale sidecars
              + B * w * 4 + B * 4)                # tables + q_start
    return flops, bytes_


#: rows of one VMEM tile by pool itemsize (f32 8, bf16 16, int8 32); the
#: lane extent is always 128
_SUBLANES = {4: 8, 2: 16, 1: 32}


def paged_fallback_reason(head_dim, block_size, interpret, kv_dtype):
    """Gate for the compiled (Mosaic) kernel; interpreter mode takes any
    shape. A grid step moves one (block_size, head_dim) slab of the pool,
    so on the chip the slab must be whole tiles of the pool's dtype: the
    lane dim (head_dim) a multiple of 128 and block_size a multiple of
    the tile's rows (8 for f32, 16 for bf16, 32 for int8). The query
    block needs no gate: the wrapper pads it to whole f32 tiles. Callers
    fall back to the XLA gather path otherwise and record why
    (`Engine.paged_fallback`); an ineligible int8 config falls back to
    the unquantized pool (the precision contract's oracle), not to a
    different kernel. Returns None when eligible, else the reason."""
    if interpret:
        return None
    if head_dim % 128 != 0:
        return ("head_dim %d is not a multiple of the 128-lane tile"
                % head_dim)
    rows = _SUBLANES.get(jnp.dtype(kv_dtype).itemsize)
    if rows is None or block_size % rows != 0:
        return ("block_size %d is not a multiple of the %s-row tile of a "
                "%s pool" % (block_size, rows, jnp.dtype(kv_dtype).name))
    return None


def _kernel(tab_ref, qs_ref, *rest, scale, block_size, nw, tq, n_heads,
            quant=False):
    """One (batch row b, head h, table slot j) grid step: fold pool slab
    `(tab[b, j], h)` into row b's online softmax. Scratch carries the
    accumulator across the innermost (j) dimension. `tq` is the TRUE
    query count; the q/out blocks hold it padded to whole tiles and the
    padded rows are dropped by the caller. With `quant` the pool refs
    hold int8 and two extra scalar-prefetched flat (num_blocks * H,) f32
    refs carry the per-block-per-head scales: the block is dequantized
    HERE, in VMEM, after the 1-byte-per-element DMA — the HBM read stays
    int8-sized."""
    from jax.experimental import pallas as pl

    if quant:
        (ksc_ref, vsc_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = rest
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = rest
        ksc_ref = vsc_ref = None

    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)
    rows = q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # table slots whose first key position lies beyond the row's last
    # query position hold nothing any query may attend to: skip the MXU
    # work (their DMA is already elided by the clamped index map)
    live = j * block_size <= qs_ref[b] + tq - 1

    @pl.when(live)
    def _accumulate():
        q = q_ref[0, 0]                                   # [rows, Dh] f32
        k = k_ref[0, 0].astype(jnp.float32)               # [bs, Dh]
        if quant:
            # live implies j <= last, so tab[b, j] is this very block
            k = k * ksc_ref[tab_ref[b, j] * n_heads + h]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # ragged mask: key at table position j*bs+t is live for query i
        # iff it is at or before that query's true position qs+i (for
        # prefill chunks this IS the causal mask within the chunk)
        kp = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 1)
        qp = qs_ref[b] + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 0)
        s = jnp.where(kp <= qp, s, -jnp.inf)

        m_prev = m_scr[...]                               # [rows, 1]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe),
                          0.0)
        v = v_ref[0, 0].astype(jnp.float32)               # [bs, Dh]
        if quant:
            v = v * vsc_ref[tab_ref[b, j] * n_heads + h]
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

    @pl.when(j == nw - 1)
    def _emit():
        o_ref[0, 0] = acc_scr[...] / jnp.maximum(l_scr[...], 1e-20)


@functools.lru_cache(maxsize=None)
def _make_paged(scale, block_size, interpret, quant=False):
    """Build the traced kernel entry for one (scale, block_size, quant)
    static configuration — cached so every layer of every decode/prefill
    signature shares one traced op (the _make_flash pattern)."""

    def call(q, k_pool, v_pool, tables, q_start, k_scale=None,
             v_scale=None):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        B, Tq, H, Dh = q.shape
        w = tables.shape[1]
        itemsize = jnp.dtype(k_pool.dtype).itemsize
        # the query block rides head-major like the pool, in f32 and
        # padded to whole (8, 128) tiles: decode's single row would
        # otherwise be a block Mosaic cannot tile. Rows are independent
        # in attention, so the padded ones cost MXU lanes that were idle
        # anyway and are dropped below.
        rows = -(-Tq // 8) * 8
        qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
        if rows != Tq:
            qt = jnp.pad(qt, ((0, 0), (0, 0), (0, rows - Tq), (0, 0)))

        def kv_idx(b, h, j, tab_ref, qs_ref, *_scales):
            # dead slots re-read the row's last live block: Pallas skips
            # the DMA when consecutive grid steps map to the same block
            last = jnp.maximum(qs_ref[b] + Tq - 1, 0) // block_size
            return (tab_ref[b, jnp.minimum(j, last)], h, 0, 0)

        def q_idx(b, h, j, *_pref):
            return (b, h, 0, 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            # index maps see every scalar-prefetch operand as a trailing
            # ref
            num_scalar_prefetch=4 if quant else 2,
            grid=(B, H, w),
            in_specs=[
                pl.BlockSpec((1, 1, rows, Dh), q_idx),
                pl.BlockSpec((1, 1, block_size, Dh), kv_idx),
                pl.BlockSpec((1, 1, block_size, Dh), kv_idx),
            ],
            out_specs=pl.BlockSpec((1, 1, rows, Dh), q_idx),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, Dh), jnp.float32)],
        )
        kern = functools.partial(_kernel, scale=scale,
                                 block_size=block_size, nw=w, tq=Tq,
                                 n_heads=H, quant=quant)
        # 2 MACs/flop-pair per element for each of the QK and PV
        # matmuls; bytes = K+V blocks walked + q/out + the tables
        # (paged_call_cost — shared with the bytes-report instrument)
        flops, bytes_ = paged_call_cost(
            B, Tq, H, Dh, w, block_size, kv_itemsize=itemsize,
            q_itemsize=jnp.dtype(q.dtype).itemsize,
            scale_blocks=k_pool.shape[0] if quant else 0)
        # the scale sidecars ride SMEM flat: a 2-D (num_blocks, H) ref
        # would pad its minor dimension out to a full lane row per block
        operands = ((tables, q_start, k_scale.reshape(-1),
                     v_scale.reshape(-1)) if quant
                    else (tables, q_start))
        out = pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(qt.shape, jnp.float32),
            interpret=interpret,
            **_cost(flops, bytes_),
        )(*operands, qt, k_pool, v_pool)
        return jnp.swapaxes(out[:, :, :Tq], 1, 2).astype(q.dtype)

    return call


def paged_attention(q, k_pool, v_pool, tables, q_start, block_size,
                    scale=None, interpret=None, k_scale=None,
                    v_scale=None):
    """Ragged paged attention against a contiguous-per-layer block pool.

    q:       (B, Tq, H, Dh) query block — Tq=1 for decode, Tq=chunk for
             chunked prefill (whose K/V are already written to the pool).
    k_pool:  (num_blocks, H, block_size, Dh) one layer's key pool
             (serving/kv_cache.py keeps the heads ahead of the block).
    v_pool:  same shape, values.
    tables:  (B, w) int32 block table, width w bucketed by the caller to
             the longest live sequence (null-padded past each row's
             blocks).
    q_start: (B,) int32 true position of each row's FIRST query token
             (for decode: the sequence's current last position).
    k_scale, v_scale: (num_blocks, H) f32 per-block-per-head scales for
             an INT8 pool (serving/kv_cache.py `kv_dtype="int8"`). When
             given, blocks DMA as int8 and are dequantized in VMEM
             inside the grid step — the per-step HBM read is
             1 byte/element instead of 4, declared as such in the
             CostEstimate.

    Returns (B, Tq, H, Dh) attention outputs; per-sequence keys past
    position q_start+i are masked, so padded table entries and pool
    garbage never leak into real rows. Softmax statistics accumulate in
    f32 regardless of pool dtype.
    """
    if interpret is None:
        interpret = default_interpret()
    B, Tq, H, Dh = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    quant = k_scale is not None
    call = _make_paged(float(scale), int(block_size), bool(interpret),
                       quant)
    if quant:
        return call(q, k_pool, v_pool, tables, q_start,
                    k_scale.astype(jnp.float32),
                    v_scale.astype(jnp.float32))
    return call(q, k_pool, v_pool, tables, q_start)
