"""Pallas prompt attention: a whole prompt's causal attention inside its
band, one kernel a layer, over a padded bucket of which `length`
positions are real.

What it replaces in a whole-prompt prefill (`models/afmoe.py`
`banded_attention`, which stays as the fallback, as the dense forwards'
attention and as the tests' reference) is XLA over one block of 256
queries at a time: the (heads, 256, band) float32 scores and
probabilities of every block are written to HBM and read back, which in
the `trinity_mixed_closed` cell was 56 of the 159 ms of an 8,192-bucket
prefill program (PERF.md §6, PR 41), and the padded rows of a
power-of-two bucket, a quarter of it at the mean, are scored like real
ones. Here:

- q (S, H, Dh), k and v (S, Hkv, Dh) are handed in as they lie, viewed as
  (S, H * Dh) and (S, Hkv * Dh): a grid step's blocks are column ranges
  of those rows, so nothing is transposed in HBM. The grid is (cached
  head, query block, key block of the band), key blocks innermost. The
  H / Hkv query heads of a group are stacked into the left operand's rows
  once a query block (`bq` x group rows against one (`bk`, Dh) block of
  K), so each K/V block of a band is read once a GROUP;
- running maximum, denominator and sum live in VMEM in float32, scores
  and softmax are float32, the probabilities meet V in V's dtype with a
  float32 sum (what XLA's default precision gives float32 probabilities
  against bf16 values on the chip: one bf16 pass); the (heads, block,
  band) array never exists;
- `length` is DATA (a scalar-prefetch operand). The key-block axis counts
  from the first block of the query block's band (`_span`): past the
  block's last query, before `first query - window + 1` on a window
  layer and past `length` nothing is fetched: the index maps clamp to
  the band's last block, so a skipped step re-names the block already
  resident and no copy is issued, and `pl.when` guards the arithmetic.
  A query block that starts at or past `length` fetches nothing and
  writes zeros: its rows are padding (a prefill reads row `length - 1`
  alone; what padded positions put in the cache is overwritten by decode
  before it is read);
- only a band's edge blocks are masked (the diagonal, the window's
  trailing edge, the block that holds `length`); there V's rows that no
  query of the block sees are zeroed too, so whatever lies past `length`
  or outside the band is never multiplied.

It enters a step program as the decode walk does (ops/pallas_splice.py):
every layer of one window is a call site of one jitted function, whose
body is the kernel traced and lowered once a process and a bucket.

The interpreter runs the same kernel on the CPU for the parity tests
(tests/test_prompt_attention.py).
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

from .pallas_decode_walk import UNSEEN
from .pallas_fused import _cost
from .pallas_paged import _SUBLANES
from .pallas_splice import Spliced

#: queries of one head a grid step scores (x the group's heads = the left
#: operand's rows) and keys it scores them against. On the chip at
#: Trinity's 48 heads on 8 (PERF.md §6, PR 41, has the sweep): the matrix
#: unit paces what grows with the pairs, and each row of a step costs
#: 1.7 ns beside it (the running maximum, denominator and sum), so the
#: key block is as long as the band's edges let it be: a window layer of
#: the 8,192 bucket at length 6,144 takes 6.19 / 5.03 / 4.85 ms at (512,
#: 512) / (256, 1024) / (512, 1024), 5.87 at (256, 2048) (more of an edge
#: block lies outside the band), 11.6 and 13.8 at (1024, 1024) and (512,
#: 2048) (the scores no longer fit)
Q_BLOCK = 512
K_BLOCK = 1024
#: the shortest bucket the kernel takes: XLA's own is no slower below it
#: (a layer of Trinity's at three quarters of the bucket, XLA / kernel:
#: 0.67 / 0.63 ms at 2,048 and 0.81 when the bucket is full, the prefill
#: program 28.9 / 29.3 ms; 2.36 / 1.67 at 4,096, 64.3 / 58.4; PERF.md §6,
#: PR 41)
MIN_BUCKET = 4096
#: what a grid step may hold in VMEM: the float32 scores and
#: probabilities of `Q_BLOCK` x group rows by `K_BLOCK` keys beside the
#: blocks and the running sums (38 MB at 6 heads a group; the compiler's
#: own limit is 16 MiB of the chip's 128)
VMEM_LIMIT = 100 * 1024 * 1024


def block_sizes(S):
    """(query block, key block) for a bucket of S rows."""
    return min(Q_BLOCK, S // 2), min(K_BLOCK, S)


def prompt_attention_unfit(S, head_dim, group, dtype, backend=None):
    """Gate of the kernel, from what the code can observe: a compiled TPU
    backend (the interpreter is the tests' tool), the lane dimension
    (head_dim) a multiple of 128 (a head is a column range of a row),
    a dtype whose tiles the blocks are whole numbers of, a group of
    query heads whose scores a step can hold (float32 scores and
    probabilities and what the compiler keeps beside them: 16 bytes a
    pair) and a bucket long enough. `S` None asks what an engine knows
    once; the bucket is a program's. Returns None where the kernel runs,
    else why `banded_attention` does."""
    backend = backend or jax.default_backend()
    if backend != "tpu":
        return ("the backend is %s: the kernel is compiled for the TPU, "
                "elsewhere XLA scores a prompt block by block" % backend)
    if head_dim % 128 != 0:
        return ("head_dim %d is not a multiple of the 128-lane tile"
                % head_dim)
    if jnp.dtype(dtype).itemsize not in _SUBLANES:
        return "no tile is known for %s" % jnp.dtype(dtype).name
    if 16 * group * Q_BLOCK * K_BLOCK > VMEM_LIMIT:
        return ("%d query heads a cached head: the scores of %d rows a "
                "step do not fit the kernel's VMEM" % (group, group * Q_BLOCK))
    if S is not None and (S < MIN_BUCKET or S & (S - 1)):
        return ("a bucket of %d rows is not a power of two of at least %d: "
                "XLA's own is no slower there" % (S, MIN_BUCKET))
    return None


def _span(qi, length, bq, bk, window, xp=jnp):
    """(first, last) key block of query block `qi`'s band among the
    first `length` rows; a query block at or past `length` is given the
    last real one's. Integers of `xp`: traced int32 scalars in the kernel
    and its index maps, numpy's where the extent is worked out."""
    q_lo = xp.minimum(qi, (length - 1) // bq) * bq
    first = xp.maximum(q_lo - (window - 1), 0) // bk if window else 0 * q_lo
    return first, xp.minimum(q_lo + bq - 1, length - 1) // bk


def band_blocks(S, bq, bk, window):
    """Extent of the key-block axis: the most key blocks any query
    block's band holds."""
    first, last = _span(np.arange(S // bq), S, bq, bk, window, np)
    return int(np.max(last - first)) + 1


def _length(len_ref):
    """The prompt's length as the kernel and its index maps read it: at
    least one row is real."""
    return jnp.maximum(len_ref[0], 1)


def block_maps(bq, bk, window):
    """The index maps of (q, k and v, out): (cached head, query block,
    key step, `length`) -> block indices. A step with nothing to fetch
    names the block its predecessor left resident."""
    def q_map(h, qi, kj, len_ref):
        return jnp.minimum(qi, (_length(len_ref) - 1) // bq), h

    def kv_map(h, qi, kj, len_ref):
        length = _length(len_ref)
        first, last = _span(qi, length, bq, bk, window)
        return jnp.where(qi * bq < length,
                         jnp.minimum(first + kj, last), last), h

    def o_map(h, qi, kj, len_ref):
        return qi, h

    return q_map, kv_map, o_map


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, qs_scr, m_scr, l_scr,
            acc_scr, *, scale, window, bq, bk, group):
    """Grid step (h, qi, kj): the group of cached head h, its queries
    `qi * bq` on, against key block `first + kj` of their band."""
    from jax.experimental import pallas as pl

    qi, kj = pl.program_id(1), pl.program_id(2)
    length = _length(len_ref)
    Dh = k_ref.shape[1]
    q_lo = qi * bq
    real = q_lo < length
    first, last = _span(qi, length, bq, bk, window)
    k_lo = (first + kj) * bk
    f32 = jnp.float32

    @pl.when(kj == 0)
    def _start():
        m_scr[...] = jnp.full_like(m_scr, UNSEEN)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        for g in range(group):
            qs_scr[g * bq:(g + 1) * bq, :] = q_ref[:, g * Dh:(g + 1) * Dh]

    def fold(edge):
        v = v_ref[...]
        s = jax.lax.dot_general(
            qs_scr[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=f32) * scale           # (group*bq, bk)
        if edge:
            t = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            j = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            at = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
            seen = (j <= t) & (j < length)
            used = at <= jnp.minimum(q_lo + bq - 1, length - 1)
            if window:
                seen &= t - j < window
                used &= q_lo - at < window
            s = jnp.where(seen[None], s.reshape(group, bq, bk),
                          UNSEEN).reshape(group * bq, bk)
            # 0 x whatever a row nobody sees holds is not 0 where it is
            # not finite
            v = jnp.where(used, v.astype(f32), 0.0).astype(v.dtype)
        m_prev = m_scr[...]                               # (group*bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)                   # (group*bq, Dh)
        m_scr[...] = m_new

    visited = real & (first + kj <= last)
    # an inner block: every key of it is seen by every query of the block
    inner = k_lo + bk - 1 <= q_lo
    if window:
        inner &= q_lo + bq - 1 - k_lo < window
    pl.when(visited & inner)(lambda: fold(False))
    pl.when(visited & ~inner)(lambda: fold(True))

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        @pl.when(real)
        def _rows():
            out = acc_scr[...] / l_scr[...]
            for g in range(group):
                o_ref[:, g * Dh:(g + 1) * Dh] = \
                    out[g * bq:(g + 1) * bq].astype(o_ref.dtype)

        @pl.when(~real)
        def _padding():
            o_ref[...] = jnp.zeros_like(o_ref)


def _kernel_call(q, k, v, length, *, window, n_kv_heads, blocks, interpret):
    """The kernel over q (S, H * Dh), k and v (S, Hkv * Dh) and `length`
    (1,) int32. Returns (S, H * Dh) in q's dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, Hkv = q.shape[0], n_kv_heads
    Dh, group = k.shape[1] // Hkv, q.shape[1] // k.shape[1]
    bq, bk = blocks
    rows = group * bq
    q_map, kv_map, o_map = block_maps(bq, bk, window)
    steps = band_blocks(S, bq, bk, window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Hkv, S // bq, steps),
        in_specs=[pl.BlockSpec((bq, group * Dh), q_map),
                  pl.BlockSpec((bk, Dh), kv_map),
                  pl.BlockSpec((bk, Dh), kv_map)],
        out_specs=pl.BlockSpec((bq, group * Dh), o_map),
        scratch_shapes=[pltpu.VMEM((rows, Dh), q.dtype),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, Dh), jnp.float32)])
    # declared for XLA's scheduler as the whole bucket's band: what a call
    # does follows `length`, known on the device alone
    pairs = Hkv * (S // bq) * steps * rows * bk
    return pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(Dh), window=window,
                          bq=bq, bk=bk, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="prompt_attention",
        **_cost(4 * pairs * Dh,
                (2 * q.size + 2 * Hkv * (S // bq) * steps * bk * Dh)
                * q.dtype.itemsize, pairs),
    )(length, q, k, v)


_spliced = Spliced(
    "prompt_attention", _kernel_call,
    lambda q, *operands, **static: jax.core.ShapedArray(q.shape, q.dtype))
_lowered_once = _spliced.lowered_once


@functools.partial(jax.jit, static_argnames=("window", "n_kv_heads", "blocks",
                                             "interpret"))
def _attend(*operands, interpret, **static):
    """One function a (bucket, window) and a process: every layer of a
    prefill program with that window is a call site of it."""
    if interpret:
        return _kernel_call(*operands, interpret=True, **static)
    return _spliced(*operands, **static)


def prompt_attention(q, k, v, length, *, window=0, blocks=None,
                     interpret=False):
    """Causal attention of ONE sequence over its own keys and values,
    `banded_attention`'s over the first `length` rows.

    q:      (S, H, Dh); k, v: (S, Hkv, Dh), H a multiple of Hkv (query
            head h reads head h // (H / Hkv)); S a power of two
            (`prompt_attention_unfit`).
    length: int32 scalar, DATA: rows at and past it are padding.
    blocks: (query block, key block), `block_sizes(S)` by default (the
            tests scale them down with their shapes).
    Key j is seen by query t iff j <= t and, with `window`, t - j <
    window. Returns (S, H, Dh) in q's dtype; rows below `length` are the
    attention's, the others are unspecified finite values (zero in every
    query block past `length`)."""
    S, H, Dh = q.shape
    Hkv = k.shape[1]
    out = _attend(q.reshape(S, H * Dh), k.reshape(S, Hkv * Dh),
                  v.reshape(S, Hkv * Dh),
                  jnp.reshape(length, (1,)).astype(jnp.int32),
                  window=window, n_kv_heads=Hkv,
                  blocks=blocks or block_sizes(S), interpret=interpret)
    return out.reshape(S, H, Dh)
