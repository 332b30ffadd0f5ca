"""Pallas fused BatchNorm/ReLU/residual epilogue kernels.

Why a hand kernel: the 2026-07-31 roofline analysis pinned the ResNet-50
train step at 95% of the v5e HBM-bandwidth floor — 81.49 GB accessed per
step at ~70 flops/byte — and the per-HLO profile named the remaining
elementwise headroom: 9 ms-class loop fusions on
[256,256,56,56] BatchNorm/residual chains. XLA's automatic fusion has
already done what it can there; the next step is the TVM-style cross-op
fusion (Chen et al., arXiv:1802.04799) written by hand: one kernel per
chain so every activation tensor is read once and written once, instead
of once per op.

Kernels (all on an [N, C, S] channel-axis-1 view, S = flattened spatial):

- `_stats_kernel`    — one-pass E[x]/E[x^2] batch statistics with f32
  accumulation in VMEM scratch (a single HBM read of the activation).
- `_apply_kernel`    — the epilogue: y = [relu](x * scale + offset
  [+ residual]), one read of x (+ residual), one write of y.
- `_bwd_reduce_kernel` — backward pass 1: dz = relu-mask(dy), plus the
  two per-channel reductions the dBN needs (sum dz, sum dz*xhat) in the
  same read; dz is written once and doubles as the residual gradient.
- `_bwd_dx_kernel`   — backward pass 2: dx = c1*dz + c2*x + c3 with all
  per-channel coefficients folded outside the kernel, so the big pass is
  a pure 2-read/1-write elementwise sweep.

`fused_bn_act` wires them into a jax.custom_vjp whose residuals are the
BN input (= the conv output, already `checkpoint_name`-tagged "conv_out"
in ops/nn.py) and the f32 batch stats — exactly the save set of the
`remat="io"` policy (parallel/trainer.py), so under io-remat the relu
outputs are never stored: backward replays the epilogue kernel from the
saved conv output instead of re-reading a stored activation from HBM.

Selection: `MXNET_FUSED_BN_EPILOGUE=1` (read at trace time) routes the
`BatchNorm` / `_contrib_BatchNormAddRelu` ops (ops/nn.py) through these
kernels for training-mode batch-stats BN; everything else (eval BN,
channels-last layouts, exotic dtypes) keeps the XLA path. On CPU the
kernels run in Pallas interpreter mode — the equality tests in
tests/test_pallas.py prove forward + VJP against the XLA path there;
`chip_smoke.py` compiles them with Mosaic and compares with the same
XLA path on the chip.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_attention import default_interpret


def fuse_enabled():
    """MXNET_FUSED_BN_EPILOGUE=1 — read at trace time (docs/ENV_VARS.md)."""
    return os.environ.get("MXNET_FUSED_BN_EPILOGUE", "0") == "1"


#: per-grid-step VMEM budget for one input block. The backward reduce
#: holds four such blocks (dy, y, x in; dz out), each double-buffered by
#: the pipeline, plus their f32 working copies: 0.5 MiB keeps all of it
#: well inside v5e's 16 MiB default scoped VMEM.
_BLOCK_BYTES = 1 << 19
#: grid-size cap for the interpreter, whose grid is a python loop (CPU
#: tests): beyond this the XLA path is the better one. The chip has no
#: such limit.
_MAX_GRID = 4096


@functools.lru_cache(maxsize=None)
def _largest_divisor(n, cap, multiple_of=1):
    """Largest divisor of n that is <= cap and a multiple of
    `multiple_of`, or None (blocks must tile exactly — Pallas pads
    out-of-bounds reads with undefined values, which would corrupt the
    statistics reductions)."""
    for d in range(max(1, min(n, cap)), 0, -1):
        if n % d == 0 and d % multiple_of == 0:
            return d
    return None


def _blocks_for(shape3, dtype):
    """(bn, bc, bs) batch/channel/spatial block sizes for an [N, C, S]
    view, or None when no legal decomposition exists. Mosaic wants the
    two minor block dims whole tiles or the array's whole extent, so bc
    is the sublane tile (16 for bf16, 8 for f32) or all of C, and bs is
    all of S when a [1, bc, S] slab fits the block budget (ResNet's
    56x56, 28x28, 14x14 and 7x7 planes are no multiple of 128) and the
    largest 128-multiple divisor of S otherwise. The batch dim is free:
    bn fills what is left of the budget."""
    N, C, S = shape3
    itemsize = jnp.dtype(dtype).itemsize
    sub = 16 if jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16) else 8
    bc = sub if C % sub == 0 else C
    cap = _BLOCK_BYTES // (bc * itemsize)
    bs = S if S <= cap else _largest_divisor(S, cap, 128)
    if not bs:
        return None
    # VMEM rounds the lane extent up to whole 128-lane tiles
    lanes = -(-bs // 128) * 128
    return _largest_divisor(N, max(1, cap // lanes)), bc, bs


def _grid_for(shape3, blocks):
    """(channel, batch, spatial) grid: the two reduction axes innermost,
    so a channel block's statistics accumulate in scratch across them."""
    (N, C, S), (bn, bc, bs) = shape3, blocks
    return C // bc, N // bn, S // bs


def _big_spec(blocks):
    from jax.experimental import pallas as pl
    return pl.BlockSpec(blocks, lambda i, j, k: (j, i, k))


def _per_channel_spec(bc):
    from jax.experimental import pallas as pl
    return pl.BlockSpec((bc, 1), lambda i, j, k: (i, 0))


def _flat_spatial(shape):
    s = 1
    for d in shape[2:]:
        s *= d
    return s


def fuse_eligible(x, axis=1, interpret=None):
    """Gate for the fused kernels; callers fall back to the XLA path when
    False. Requires channel axis 1, f32/bf16 data and a legal block
    decomposition (`_blocks_for`); under the interpreter also a grid
    small enough for its python loop."""
    if x.ndim < 2 or axis % x.ndim != 1:
        return False
    if jnp.dtype(x.dtype) not in (jnp.dtype(jnp.float32),
                                  jnp.dtype(jnp.bfloat16)):
        return False
    shape3 = (x.shape[0], x.shape[1], _flat_spatial(x.shape))
    if 0 in shape3:
        return False
    blocks = _blocks_for(shape3, x.dtype)
    if blocks is None:
        return False
    if interpret is None:
        interpret = default_interpret()
    if not interpret:
        return True
    nc, nn, ns = _grid_for(shape3, blocks)
    return nc * nn * ns <= _MAX_GRID


def _cost(flops, bytes_accessed, transcendentals=0):
    """cost_estimate kwarg for pallas_call — on TPU the kernel is an
    opaque custom call, and without a declared cost the XLA cost model
    (bytes_report.py's A/B instrument) would count it as zero bytes.
    Shared with pallas_rnn.py and pallas_attention.py."""
    from jax.experimental import pallas as pl
    return {"cost_estimate": pl.CostEstimate(
        flops=int(flops), bytes_accessed=int(bytes_accessed),
        transcendentals=int(transcendentals))}


# ---------------------------------------------------------------------------
# forward kernels
# ---------------------------------------------------------------------------


def _first_last_step():
    """(is first, is last) step of a channel block's reduction sweep —
    grid axes 1 (batch) and 2 (spatial) of `_grid_for`."""
    from jax.experimental import pallas as pl
    j, k = pl.program_id(1), pl.program_id(2)
    return ((j == 0) & (k == 0),
            (j == pl.num_programs(1) - 1) & (k == pl.num_programs(2) - 1))


def _channel_sum(xb):
    """[bn, bc, bs] -> [bc, 1]: the batch dim folds with plain vector
    adds, then one lane reduction that keeps its dim (a rank-1 [bc]
    intermediate is a shape Mosaic lays out poorly)."""
    return jnp.sum(jnp.sum(xb, axis=0), axis=1, keepdims=True)


def _stats_kernel(x_ref, mean_ref, var_ref, s_scr, q_scr, *, inv_m):
    """One-pass E[x]/E[x^2] per channel, f32 accumulation. Grid
    (nc, nn, ns), channel outermost; scratch carries the partial sums
    across a channel block's batch and spatial steps (same accumulator
    pattern as the flash-attention kernel)."""
    from jax.experimental import pallas as pl

    first, last = _first_last_step()

    @pl.when(first)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)
        q_scr[...] = jnp.zeros_like(q_scr)

    xb = x_ref[...].astype(jnp.float32)            # [bn, bc, bs]
    s_scr[...] = s_scr[...] + _channel_sum(xb)
    q_scr[...] = q_scr[...] + _channel_sum(xb * xb)

    @pl.when(last)
    def _emit():
        m = s_scr[...] * inv_m
        mean_ref[...] = m
        var_ref[...] = jnp.maximum(q_scr[...] * inv_m - m * m, 0.0)


def _bn_stats(x3, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, C, S = x3.shape
    blocks = _blocks_for(x3.shape, x3.dtype)
    bc = blocks[1]
    per_c = _per_channel_spec(bc)
    kern = functools.partial(_stats_kernel, inv_m=1.0 / (N * S))
    mean, var = pl.pallas_call(
        kern,
        out_shape=[jax.ShapeDtypeStruct((C, 1), jnp.float32),
                   jax.ShapeDtypeStruct((C, 1), jnp.float32)],
        grid=_grid_for(x3.shape, blocks),
        in_specs=[_big_spec(blocks)],
        out_specs=[per_c, per_c],
        scratch_shapes=[pltpu.VMEM((bc, 1), jnp.float32),
                        pltpu.VMEM((bc, 1), jnp.float32)],
        interpret=interpret,
        **_cost(3 * N * C * S,
                N * C * S * jnp.dtype(x3.dtype).itemsize + 8 * C),
    )(x3)
    return mean[:, 0], var[:, 0]


def _apply_kernel(x_ref, scale_ref, offset_ref, *rest, relu, has_res):
    """y = [relu](x * scale + offset [+ residual]) — the whole epilogue in
    one read of x (+ residual) and one write of y."""
    if has_res:
        res_ref, o_ref = rest
    else:
        (o_ref,) = rest
    z = x_ref[...].astype(jnp.float32) * scale_ref[...][None] \
        + offset_ref[...][None]
    if has_res:
        z = z + res_ref[...].astype(jnp.float32)
    if relu:
        z = jnp.maximum(z, 0.0)
    o_ref[...] = z.astype(o_ref.dtype)


def _bn_apply(x3, scale, offset, res3, relu, interpret):
    from jax.experimental import pallas as pl

    N, C, S = x3.shape
    blocks = _blocks_for(x3.shape, x3.dtype)
    itemsize = jnp.dtype(x3.dtype).itemsize
    big = _big_spec(blocks)
    per_c = _per_channel_spec(blocks[1])
    kern = functools.partial(_apply_kernel, relu=relu,
                             has_res=res3 is not None)
    in_specs = [big, per_c, per_c]
    args = [x3, scale, offset]
    npasses = 2
    if res3 is not None:
        in_specs.append(big)
        args.append(res3)
        npasses = 3
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((N, C, S), x3.dtype),
        grid=_grid_for(x3.shape, blocks),
        in_specs=in_specs,
        out_specs=big,
        interpret=interpret,
        **_cost((2 + (res3 is not None) + relu) * N * C * S,
                npasses * N * C * S * itemsize + 8 * C),
    )(*args)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_reduce_kernel(*refs, relu):
    """Backward pass 1: apply the relu mask to dy (one read of dy + y) and
    reduce sum(dz), sum(dz * xhat) per channel in the same sweep — the
    one-pass statistic-gradient read. dz is stored once; it IS the
    residual gradient, so d-residual costs no extra traffic."""
    from jax.experimental import pallas as pl

    if relu:
        (dy_ref, y_ref, x_ref, mean_ref, inv_ref,
         dz_ref, sdz_ref, sdx_ref, a_scr, b_scr) = refs
    else:
        (dy_ref, x_ref, mean_ref, inv_ref,
         sdz_ref, sdx_ref, a_scr, b_scr) = refs
    first, last = _first_last_step()

    @pl.when(first)
    def _init():
        a_scr[...] = jnp.zeros_like(a_scr)
        b_scr[...] = jnp.zeros_like(b_scr)

    dy = dy_ref[...].astype(jnp.float32)
    if relu:
        # mask from the saved/recomputed output sign; store dz rounded to
        # the activation dtype and reduce the SAME rounded values so the
        # sums seen by pass 2 are consistent with the dz it re-reads
        # (compared in f32: the v5e vector unit has no bf16 compare)
        dz_store = jnp.where(y_ref[...].astype(jnp.float32) > 0, dy,
                             0.0).astype(dz_ref.dtype)
        dz_ref[...] = dz_store
        dzf = dz_store.astype(jnp.float32)
    else:
        dzf = dy
    xh = (x_ref[...].astype(jnp.float32) - mean_ref[...][None]) \
        * inv_ref[...][None]
    a_scr[...] = a_scr[...] + _channel_sum(dzf)
    b_scr[...] = b_scr[...] + _channel_sum(dzf * xh)

    @pl.when(last)
    def _emit():
        sdz_ref[...] = a_scr[...]
        sdx_ref[...] = b_scr[...]


def _bwd_reduce(dy3, y3, x3, mean, inv, relu, interpret):
    """Returns (dz, sum_dz [C], sum_dz_xhat [C]); dz is dy3 itself when
    there is no relu mask to apply (no extra write)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, C, S = x3.shape
    blocks = _blocks_for(x3.shape, x3.dtype)
    bc = blocks[1]
    itemsize = jnp.dtype(x3.dtype).itemsize
    big = _big_spec(blocks)
    per_c = _per_channel_spec(bc)
    kern = functools.partial(_bwd_reduce_kernel, relu=relu)
    sums_shape = jax.ShapeDtypeStruct((C, 1), jnp.float32)
    if relu:
        out_shape = [jax.ShapeDtypeStruct((N, C, S), dy3.dtype),
                     sums_shape, sums_shape]
        out_specs = [big, per_c, per_c]
        args = (dy3, y3, x3, mean[:, None], inv[:, None])
        in_specs = [big, big, big, per_c, per_c]
        npasses = 4
    else:
        out_shape = [sums_shape, sums_shape]
        out_specs = [per_c, per_c]
        args = (dy3, x3, mean[:, None], inv[:, None])
        in_specs = [big, big, per_c, per_c]
        npasses = 2
    outs = pl.pallas_call(
        kern,
        out_shape=out_shape,
        grid=_grid_for(x3.shape, blocks),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((bc, 1), jnp.float32),
                        pltpu.VMEM((bc, 1), jnp.float32)],
        interpret=interpret,
        **_cost(6 * N * C * S, npasses * N * C * S * itemsize + 16 * C),
    )(*args)
    if relu:
        dz, sdz, sdx = outs
    else:
        sdz, sdx = outs
        dz = dy3
    return dz, sdz[:, 0], sdx[:, 0]


def _bwd_dx_kernel(dz_ref, x_ref, c1_ref, c2_ref, c3_ref, dx_ref):
    """Backward pass 2: dx = c1*dz + c2*x + c3 — every dBN term (including
    the mean/var-output cotangents) folded into three per-channel
    coefficients outside the kernel."""
    dx = (dz_ref[...].astype(jnp.float32) * c1_ref[...][None]
          + x_ref[...].astype(jnp.float32) * c2_ref[...][None]
          + c3_ref[...][None])
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _bwd_dx(dz3, x3, c1, c2, c3, interpret):
    from jax.experimental import pallas as pl

    N, C, S = x3.shape
    blocks = _blocks_for(x3.shape, x3.dtype)
    itemsize = jnp.dtype(x3.dtype).itemsize
    big = _big_spec(blocks)
    per_c = _per_channel_spec(blocks[1])
    return pl.pallas_call(
        _bwd_dx_kernel,
        out_shape=jax.ShapeDtypeStruct((N, C, S), x3.dtype),
        grid=_grid_for(x3.shape, blocks),
        in_specs=[big, big, per_c, per_c, per_c],
        out_specs=big,
        interpret=interpret,
        **_cost(4 * N * C * S, 3 * N * C * S * itemsize + 12 * C),
    )(dz3, x3, c1, c2, c3)


# ---------------------------------------------------------------------------
# custom-VJP assembly
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _make_fused(eps, relu, has_res, interpret):
    """Build the custom-VJP fused op for one (eps, act, residual?) static
    configuration — cached so repeated BN layers share one traced op, the
    same pattern as pallas_attention._make_flash."""

    def fwd_impl(x3, gamma, beta, res3):
        mean, var = _bn_stats(x3, interpret)
        inv = lax.rsqrt(var + eps)
        scale = gamma.astype(jnp.float32) * inv
        offset = beta.astype(jnp.float32) - mean * scale
        y = _bn_apply(x3, scale[:, None], offset[:, None], res3, relu,
                      interpret)
        return y, mean, var

    def bwd_impl(resids, cts):
        x3, gamma, beta, mean, var, y = resids
        gy, gm, gv = cts
        N, C, S = x3.shape
        m_count = N * S
        inv = lax.rsqrt(var + eps)
        dz, sdz, sdx = _bwd_reduce(gy, y, x3, mean, inv, relu, interpret)
        g32 = gamma.astype(jnp.float32)
        gm32 = gm.astype(jnp.float32)
        gv32 = gv.astype(jnp.float32)
        inv2 = inv * inv
        # dx = g*inv*(dz - sum(dz)/M - xhat*sum(dz*xhat)/M)
        #      + gm/M + gv*2*(x - mean)/M, regrouped as c1*dz + c2*x + c3
        c1 = g32 * inv
        c2 = (-g32 * inv2 * sdx + 2.0 * gv32) / m_count
        c3 = (-g32 * inv * sdz + g32 * inv2 * mean * sdx + gm32
              - 2.0 * gv32 * mean) / m_count
        dx = _bwd_dx(dz, x3, c1[:, None], c2[:, None], c3[:, None],
                     interpret)
        dgamma = sdx.astype(gamma.dtype)
        dbeta = sdz.astype(beta.dtype)
        if has_res:
            return dx, dgamma, dbeta, dz
        return dx, dgamma, dbeta

    if has_res:
        @jax.custom_vjp
        def f(x3, gamma, beta, res3):
            return fwd_impl(x3, gamma, beta, res3)

        def fwd(x3, gamma, beta, res3):
            y, mean, var = fwd_impl(x3, gamma, beta, res3)
            # residuals: x3 is the conv output ("conv_out" tag upstream),
            # mean/var are the tiny stats ("bn_stats" tag at the wiring) —
            # the remat="io" save set; y (the relu output, needed only for
            # the mask) is recomputed under that policy instead of stored
            return (y, mean, var), (x3, gamma, beta, mean, var,
                                    y if relu else None)
    else:
        @jax.custom_vjp
        def f(x3, gamma, beta):
            return fwd_impl(x3, gamma, beta, None)

        def fwd(x3, gamma, beta):
            y, mean, var = fwd_impl(x3, gamma, beta, None)
            return (y, mean, var), (x3, gamma, beta, mean, var,
                                    y if relu else None)

    f.defvjp(fwd, bwd_impl)
    return f


def fused_bn_act(x, gamma, beta, eps=1e-5, act=None, residual=None,
                 interpret=None):
    """Fused training-mode BatchNorm [+ residual add] [+ ReLU].

    x: [N, C, ...] with channels on axis 1; gamma/beta: [C]. Returns
    (y, batch_mean, batch_var) with f32 one-pass E[x]/E[x^2] statistics —
    the same contract as the XLA path in ops/nn.py's BatchNorm. The custom
    VJP fuses the dReLU/d-residual/dBN chain with the one-pass statistic
    gradients (see module docstring). Callers gate on fuse_eligible().
    """
    if act not in (None, "relu"):
        raise ValueError("fused epilogue supports act in (None, 'relu'), "
                         "got %r" % (act,))
    if interpret is None:
        interpret = default_interpret()
    orig_shape = x.shape
    N, C = x.shape[0], x.shape[1]
    S = _flat_spatial(x.shape)
    x3 = x.reshape(N, C, S)
    relu = act == "relu"
    if residual is not None:
        # cast/reshape OUTSIDE the custom_vjp so the residual cotangent
        # flows back through them automatically
        res3 = residual.reshape(N, C, S).astype(x.dtype)
        f = _make_fused(float(eps), relu, True, bool(interpret))
        y, mean, var = f(x3, gamma, beta, res3)
    else:
        f = _make_fused(float(eps), relu, False, bool(interpret))
        y, mean, var = f(x3, gamma, beta)
    return y.reshape(orig_shape), mean, var
