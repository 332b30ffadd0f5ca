"""Pallas flash attention — the hot-op kernel for transformer training.

Why a hand kernel: attention is the one op where XLA's automatic fusion
leaves MXU/HBM performance on the table — materializing the [T, T] score
matrix costs O(T^2) HBM traffic. This kernel streams K/V blocks through
VMEM with an online-softmax accumulator (running max + denominator in
VMEM scratch), so scores never leave the chip: the flash-attention
formulation mapped onto the TPU memory hierarchy per
/opt/skills/guides/pallas_guide.md (grid iterates the K dimension
innermost; scratch carries the accumulator across grid steps).

Backward: recompute-based custom_vjp (the reference-attention vjp), the
standard memory/compute trade for flash kernels — no O(T^2) residuals.

On CPU (tests, virtual meshes) the kernel runs in interpreter mode.

STATUS (measured 2026-07-31, v5e, BENCH_FLASH_SWEEP.jsonl): 0.96-1.06x
vs XLA attention at seq 1024/2048/4096 — XLA's own attention fusion has
closed the gap on this hardware/JAX version, so the transformer uses the
kernel only when MXNET_FLASH_ATTENTION=1 (opt-in) and falls back to the
pure-XLA reference otherwise; the bench keeps measuring both so a future
JAX/Pallas upgrade that re-opens the gap is caught.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, m_scr, l_scr,
            acc_scr, *, scale, causal, block_q, block_k, nk):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: K/V blocks strictly above the block diagonal contribute
    # nothing — skip their MXU work entirely (~2x for long sequences)
    live = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)              # [bq, D]
        k = k_ref[0].astype(jnp.float32)              # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            tq = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            tk = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(tk <= tq, s, -jnp.inf)

        m_prev = m_scr[...]                            # [bq, 1]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe)
        p = jnp.where(jnp.isfinite(s), p, 0.0)         # fully-masked guard
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe),
                          0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)               # [bk, D]
        acc = alpha * acc_scr[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _emit():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-20)) \
            .astype(o_ref.dtype)
        m_ref[0] = m_scr[...]
        l_ref[0] = l_scr[...]


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    """q/k/v: [BH, T, D] -> (out [BH, T, D], m [BH, T, 1], l [BH, T, 1]).
    The softmax stats feed the blockwise backward."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .pallas_fused import _cost

    BH, T, D = q.shape
    Tk = k.shape[1]
    nq = T // block_q
    nk = Tk // block_k
    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k, nk=nk)
    itemsize = q.dtype.itemsize
    # declared cost (house invariant: every pallas_call under ops/ says
    # what the TPU cost model should count for the opaque custom call):
    # 2 MACs/element for each of the QK^T and PV matmuls; bytes = q/out
    # streamed once per (b, i) row, K/V blocks re-walked once per query
    # row (the j grid), plus the f32 softmax stats
    return pl.pallas_call(
        kern,
        out_shape=[jax.ShapeDtypeStruct((BH, T, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, T, 1), jnp.float32),
                   jax.ShapeDtypeStruct((BH, T, 1), jnp.float32)],
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        **_cost(4 * BH * T * Tk * D,
                2 * BH * T * D * itemsize
                + 2 * BH * nq * Tk * D * itemsize
                + 2 * BH * T * 4,
                transcendentals=BH * T * Tk),
    )(q, k, v)


def _flash_bwd_blockwise(q, k, v, o, m, l, g, scale, causal, bq, bk):
    """Flash backward in pure lax, blockwise: recompute each [bq, bk] score
    tile from the saved softmax stats, so no O(T^2) matrix is ever live —
    the long-context memory property holds through the backward too.

    Standard flash-attention backward: with delta_i = sum(dO_i * O_i),
    ds = p * (dO V^T - delta) * scale; dq += ds K; dk += ds^T Q;
    dv += p^T dO.
    """
    from jax import lax

    BH, T, D = q.shape
    Tk = k.shape[1]
    nq, nk = T // bq, Tk // bk
    f32 = jnp.float32
    delta = jnp.sum(g.astype(f32) * o.astype(f32), axis=-1)      # [BH, T]
    qb = q.reshape(BH, nq, bq, D)
    gb = g.reshape(BH, nq, bq, D)
    mb = m.reshape(BH, nq, bq)
    lb = l.reshape(BH, nq, bq)
    db = delta.reshape(BH, nq, bq)
    kb = k.reshape(BH, nk, bk, D)
    vb = v.reshape(BH, nk, bk, D)

    def outer(carry, qi):
        dk_acc, dv_acc = carry
        qq = qb[:, qi].astype(f32)
        gg = gb[:, qi].astype(f32)
        mm = mb[:, qi]
        m_safe = jnp.where(jnp.isfinite(mm), mm, 0.0)[..., None]
        ll = jnp.maximum(lb[:, qi], 1e-20)[..., None]
        dd = db[:, qi][..., None]

        def inner(carry, ki):
            def live_block(carry):
                dq_blk, dk_acc, dv_acc = carry
                kk = kb[:, ki].astype(f32)
                vv = vb[:, ki].astype(f32)
                s = jnp.einsum("bqd,bkd->bqk", qq, kk,
                               preferred_element_type=f32) * scale
                if causal:
                    tq = qi * bq + jnp.arange(bq)[:, None]
                    tk_ = ki * bk + jnp.arange(bk)[None, :]
                    s = jnp.where((tk_ <= tq)[None], s, -jnp.inf)
                p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe) / ll,
                              0.0)
                dv_acc = dv_acc.at[:, ki].add(
                    jnp.einsum("bqk,bqd->bkd", p, gg,
                               preferred_element_type=f32))
                dp = jnp.einsum("bqd,bkd->bqk", gg, vv,
                                preferred_element_type=f32)
                ds = p * (dp - dd) * scale
                dq_blk = dq_blk + jnp.einsum("bqk,bkd->bqd", ds, kk,
                                             preferred_element_type=f32)
                dk_acc = dk_acc.at[:, ki].add(
                    jnp.einsum("bqk,bqd->bkd", ds, qq,
                               preferred_element_type=f32))
                return dq_blk, dk_acc, dv_acc

            if causal:
                # skip fully-masked above-diagonal tiles, mirroring the
                # forward's `live` predicate (~2x fewer backward FLOPs)
                live = ki * bk <= qi * bq + bq - 1
                carry = lax.cond(live, live_block, lambda c: c, carry)
            else:
                carry = live_block(carry)
            return carry, None

        (dq_blk, dk_acc, dv_acc), _ = lax.scan(
            inner, (jnp.zeros((BH, bq, D), f32), dk_acc, dv_acc),
            jnp.arange(nk))
        return (dk_acc, dv_acc), dq_blk

    (dk_acc, dv_acc), dq_blocks = lax.scan(
        outer, (jnp.zeros((BH, nk, bk, D), f32),
                jnp.zeros((BH, nk, bk, D), f32)), jnp.arange(nq))
    dq = dq_blocks.transpose(1, 0, 2, 3).reshape(BH, T, D).astype(q.dtype)
    dk = dk_acc.reshape(BH, Tk, D).astype(k.dtype)
    dv = dv_acc.reshape(BH, Tk, D).astype(v.dtype)
    return dq, dk, dv


def _reference(q, k, v, scale, causal):
    """3-D wrapper over the one dense attention reference
    (parallel.ring_attention.attention_reference) — a single source of
    truth for masking/upcast/scale semantics."""
    from ..parallel.ring_attention import attention_reference
    return attention_reference(q[:, None], k[:, None], v[:, None],
                               causal=causal, scale=scale)[:, 0]


@functools.lru_cache(maxsize=None)
def _make_flash(scale, causal, block_q, block_k, interpret):
    @jax.custom_vjp
    def fa(q, k, v):
        out, _m, _l = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                                 interpret)
        return out

    def fwd(q, k, v):
        out, m, l = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                               interpret)
        return out, (q, k, v, out, m, l)

    def bwd(res, g):
        q, k, v, o, m, l = res
        return _flash_bwd_blockwise(q, k, v, o, m, l, g, scale, causal,
                                    block_q, block_k)

    fa.defvjp(fwd, bwd)
    return fa


def default_interpret():
    """Interpreter mode off only on real TPU backends. A backend that
    fails to initialise raises here: it must not turn every kernel into
    the interpreter."""
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=None):
    """Flash attention over [B, H, T, D] (or [BH, T, D]) q/k/v.

    Falls back to the pure-XLA reference when T doesn't tile into the
    block sizes (shape-polymorphic callers keep working). Block sizes
    default to 128x128 (the MXU/VMEM sweet spot on v5e) and are
    overridable per-run with MXNET_FLASH_BLOCK_Q/MXNET_FLASH_BLOCK_K for
    on-hardware A/B without code edits.
    """
    import os
    if block_q is None:
        block_q = int(os.environ.get("MXNET_FLASH_BLOCK_Q", "128"))
    if block_k is None:
        block_k = int(os.environ.get("MXNET_FLASH_BLOCK_K", "128"))
    squeeze = q.ndim == 4
    if squeeze:
        B, H, T, D = q.shape
        q3 = q.reshape(B * H, T, D)
        k3 = k.reshape(B * H, k.shape[2], D)
        v3 = v.reshape(B * H, v.shape[2], D)
    else:
        q3, k3, v3 = q, k, v
    scale = (1.0 / (q.shape[-1] ** 0.5)) if scale is None else float(scale)
    T, Tk = q3.shape[1], k3.shape[1]
    D = q3.shape[2]
    bq = min(block_q, T)
    bk = min(block_k, Tk)
    if interpret is None:
        interpret = default_interpret()
    use_kernel = not (T % bq or Tk % bk or (causal and bq != bk))
    if use_kernel and not interpret and \
            (D % 128 != 0 or bq % 8 != 0 or bk % 8 != 0):
        # conservative on real hardware: blocks off the (8,128) VMEM tiling
        # grid (head dim or sublane-unaligned block sizes from short
        # sequences) go through XLA (which pads) instead of the kernel
        use_kernel = False
    if not use_kernel:
        out3 = _reference(q3, k3, v3, scale, causal)
    else:
        out3 = _make_flash(scale, causal, bq, bk, bool(interpret))(q3, k3,
                                                                   v3)
    if squeeze:
        return out3.reshape(q.shape)
    return out3
