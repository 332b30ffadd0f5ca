"""Neural-network ops.

Parity: reference `src/operator/nn/` (Convolution, FullyConnected, Pooling,
BatchNorm, LayerNorm, LRN, Activation, Softmax, Dropout, UpSampling) and the
legacy top-level ops (RNN fused kernel `rnn-inl.h`, SoftmaxOutput,
regression outputs, InstanceNorm, LeakyReLU family).

TPU-native redesign: convs/matmuls are lax.conv_general_dilated / jnp.matmul
(MXU-tiled by XLA, bf16-friendly); pooling is lax.reduce_window; the fused
RNN is a lax.scan over time (the XLA analog of the cuDNN fused kernel);
training-vs-inference heads (SoftmaxOutput & friends) use jax.custom_vjp to
reproduce the reference's hand-written backward semantics.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import ad_checkpoint, lax

from .registry import register
from ..random import next_key

# ---------------------------------------------------------------------------
# activations (parity: src/operator/nn/activation-inl.h, leaky_relu-inl.h)
# ---------------------------------------------------------------------------


@register("relu")
def relu(data):
    return jax.nn.relu(data)


@register("sigmoid")
def sigmoid(data):
    return jax.nn.sigmoid(data)


@register("softsign")
def softsign(data):
    return jax.nn.soft_sign(data)


@register("softrelu")
def softrelu(data):
    return jax.nn.softplus(data)


@register("hard_sigmoid")
def hard_sigmoid(data, alpha=0.2, beta=0.5):
    return jnp.clip(alpha * data + beta, 0.0, 1.0)


@register("Activation")
def Activation(data, act_type="relu"):
    if act_type == "relu":
        return jax.nn.relu(data)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    raise ValueError("unknown act_type %s" % act_type)


@register("LeakyReLU", stochastic=True)
def LeakyReLU(data, gamma=None, act_type="leaky", slope=0.25,
              lower_bound=0.125, upper_bound=0.334):
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if gamma.ndim == 1 else gamma
        return jnp.where(data > 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        a, s = 1.6732632423543772, 1.0507009873554805
        return s * jnp.where(data > 0, data, a * jnp.expm1(data))
    if act_type == "rrelu":
        from .. import autograd
        if autograd.is_training():
            slopes = jax.random.uniform(next_key(), data.shape,
                                        minval=lower_bound, maxval=upper_bound,
                                        dtype=data.dtype)
        else:
            slopes = (lower_bound + upper_bound) / 2.0
        return jnp.where(data > 0, data, slopes * data)
    raise ValueError("unknown act_type %s" % act_type)


# ---------------------------------------------------------------------------
# softmax family (parity: src/operator/nn/softmax-inl.h)
# ---------------------------------------------------------------------------


@register("softmax")
def softmax(data, axis=-1, temperature=None, length=None):
    x = data / temperature if temperature else data
    if length is not None:
        steps = jnp.arange(data.shape[axis])
        mask = steps[None, :] < length[:, None].astype(jnp.int32)
        shape = [1] * data.ndim
        shape[0] = data.shape[0]
        shape[axis] = data.shape[axis]
        mask = mask.reshape(shape)
        x = jnp.where(mask, x, -jnp.inf)
        out = jax.nn.softmax(x, axis=axis)
        return jnp.where(mask, out, 0.0)
    return jax.nn.softmax(x, axis=axis)


@register("log_softmax")
def log_softmax(data, axis=-1, temperature=None):
    x = data / temperature if temperature else data
    return jax.nn.log_softmax(x, axis=axis)


@register("softmin")
def softmin(data, axis=-1, temperature=None):
    x = data / temperature if temperature else data
    return jax.nn.softmax(-x, axis=axis)


@register("SoftmaxActivation")
def SoftmaxActivation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


# ---------------------------------------------------------------------------
# training heads with custom backward semantics
# (parity: src/operator/softmax_output-inl.h, regression_output-inl.h --
# forward is inference; backward injects (pred - label) style gradients)
# ---------------------------------------------------------------------------


def _softmax_output_impl(data, label, grad_scale, ignore_label, use_ignore,
                         normalization, multi_output, preserve_shape,
                         smooth_alpha):
    @jax.custom_vjp
    def fwd(d, l):
        if multi_output and d.ndim > 2:
            return jax.nn.softmax(d, axis=1)
        return jax.nn.softmax(d, axis=-1)

    def fwd_fwd(d, l):
        return fwd(d, l), (d, l)

    def fwd_bwd(res, g):
        d, l = res
        axis = 1 if (multi_output and d.ndim > 2) else -1
        prob = jax.nn.softmax(d, axis=axis)
        k = d.shape[axis]
        onehot = jax.nn.one_hot(l.astype(jnp.int32), k, axis=axis, dtype=d.dtype)
        if smooth_alpha:
            onehot = onehot * (1.0 - smooth_alpha) + smooth_alpha / (k - 1)
        grad = prob - onehot
        if use_ignore:
            keep = (l.astype(jnp.int32) != int(ignore_label))
            keep = jnp.expand_dims(keep, axis).astype(d.dtype)
            grad = grad * keep
        scale = grad_scale
        if normalization == "batch":
            scale = scale / d.shape[0]
        elif normalization == "valid":
            if use_ignore:
                valid = jnp.maximum(jnp.sum(
                    (l.astype(jnp.int32) != int(ignore_label)).astype(d.dtype)), 1.0)
            else:
                valid = float(np.prod(l.shape))
            scale = scale / valid
        return (grad * scale, jnp.zeros_like(l))

    fwd.defvjp(fwd_fwd, fwd_bwd)
    return fwd(data, label)


@register("SoftmaxOutput", aliases=("Softmax",))
def SoftmaxOutput(data, label, grad_scale=1.0, ignore_label=-1.0,
                  use_ignore=False, normalization="null", multi_output=False,
                  preserve_shape=False, out_grad=False, smooth_alpha=0.0):
    return _softmax_output_impl(data, label, grad_scale, ignore_label,
                                use_ignore, normalization, multi_output,
                                preserve_shape, smooth_alpha)


def _regression_head(transform, grad_fn):
    def impl(data, label, grad_scale=1.0):
        @jax.custom_vjp
        def fwd(d, l):
            return transform(d)

        def fwd_fwd(d, l):
            return fwd(d, l), (d, l)

        def fwd_bwd(res, g):
            d, l = res
            num_out = float(np.prod(d.shape[1:])) if d.ndim > 1 else 1.0
            grad = grad_fn(transform(d), l) * (grad_scale / num_out)
            return (grad, jnp.zeros_like(l))

        fwd.defvjp(fwd_fwd, fwd_bwd)
        return fwd(data, label.reshape(data.shape))
    return impl


register("LinearRegressionOutput")(
    _regression_head(lambda d: d, lambda p, l: p - l))
register("MAERegressionOutput")(
    _regression_head(lambda d: d, lambda p, l: jnp.sign(p - l)))
register("LogisticRegressionOutput")(
    _regression_head(jax.nn.sigmoid, lambda p, l: p - l))


@register("SVMOutput")
def SVMOutput(data, label, margin=1.0, regularization_coefficient=1.0,
              use_linear=False):
    @jax.custom_vjp
    def fwd(d, l):
        return d

    def fwd_fwd(d, l):
        return d, (d, l)

    def fwd_bwd(res, g):
        d, l = res
        k = d.shape[-1]
        onehot = jax.nn.one_hot(l.astype(jnp.int32), k, dtype=d.dtype)
        score_correct = jnp.sum(d * onehot, axis=-1, keepdims=True)
        viol = (margin - (score_correct - d)) > 0
        if use_linear:
            gwrong = jnp.where(viol & (onehot == 0), 1.0, 0.0)
        else:
            gwrong = jnp.where(viol & (onehot == 0),
                               2.0 * (margin - (score_correct - d)), 0.0)
        gright = -jnp.sum(gwrong, axis=-1, keepdims=True) * onehot
        return ((gwrong + gright) * regularization_coefficient, jnp.zeros_like(l))

    fwd.defvjp(fwd_fwd, fwd_bwd)
    return fwd(data, label)


@register("make_loss", aliases=("MakeLoss",))
def make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    @jax.custom_vjp
    def fwd(d):
        return d

    def fwd_fwd(d):
        return d, d

    def fwd_bwd(d, g):
        scale = grad_scale
        if normalization == "batch":
            scale = scale / d.shape[0]
        return (jnp.full_like(d, scale),)

    fwd.defvjp(fwd_fwd, fwd_bwd)
    return fwd(data)


# ---------------------------------------------------------------------------
# FullyConnected (parity: src/operator/nn/fully_connected.cc:228)
# ---------------------------------------------------------------------------


@register("FullyConnected")
def FullyConnected(data, weight, bias=None, num_hidden=0, no_bias=False,
                   flatten=True):
    x = data.reshape(data.shape[0], -1) if flatten else data
    out = jnp.matmul(x, weight.T)  # weight: (num_hidden, in_units) as in ref
    out = ad_checkpoint.checkpoint_name(out, "fc_out")
    if bias is not None and not no_bias:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution / Deconvolution (parity: src/operator/nn/convolution-inl.h,
# deconvolution-inl.h; NCHW/NCW/NCDHW layouts like the reference default)
# ---------------------------------------------------------------------------

def _conv_dims(kernel):
    return len(kernel)


def _dimnums(nd):
    if nd == 1:
        return ("NCH", "OIH", "NCH")
    if nd == 2:
        return ("NCHW", "OIHW", "NCHW")
    return ("NCDHW", "OIDHW", "NCDHW")


_CHANNELS_LAST = ("NWC", "NHWC", "NDHWC")


def _layout_specs(layout, nd):
    """(lhs_spec, rhs_spec, channel_axis) for a conv/pool layout string.

    Channels-last layouts store the weight as (O, *kernel, I) — the
    reference's NHWC convention (conv layers docs, convolution-inl.h).
    """
    if layout in _CHANNELS_LAST:
        lhs = {1: "NWC", 2: "NHWC", 3: "NDHWC"}[nd]
        rhs = {1: "OWI", 2: "OHWI", 3: "ODHWI"}[nd]
        return lhs, rhs, nd + 1
    lhs, rhs, _ = _dimnums(nd)
    return lhs, rhs, 1


def _tup(v, nd, default):
    if not v:
        return (default,) * nd
    if np.isscalar(v):
        return (int(v),) * nd
    return tuple(int(x) for x in v)


@register("Convolution", aliases=("Convolution_v1",))
def Convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                pad=(), num_filter=0, num_group=1, workspace=1024,
                no_bias=False, cudnn_tune=None, cudnn_off=False, layout=None):
    nd = _conv_dims(kernel)
    stride = _tup(stride, nd, 1)
    dilate = _tup(dilate, nd, 1)
    pad = _tup(pad, nd, 0)
    lhs_spec, rhs_spec, ch_axis = _layout_specs(layout, nd)
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    (lhs_spec, rhs_spec, lhs_spec))
    # bf16 convs accumulate in f32 on the MXU by default; forcing
    # preferred_element_type here breaks the conv transpose rule under AD
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=num_group)
    # remat-policy tag: MXU outputs are the values worth SAVING for the
    # backward pass; everything cheaper (BN normalize, relu, residual adds)
    # is recomputed from them under the "io" policy (parallel/trainer.py)
    out = ad_checkpoint.checkpoint_name(out, "conv_out")
    if bias is not None and not no_bias:
        bshape = [1] * out.ndim
        bshape[ch_axis] = -1
        out = out + bias.reshape(bshape)
    return out


@register("Deconvolution")
def Deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                  pad=(), adj=(), target_shape=(), num_filter=0, num_group=1,
                  workspace=512, no_bias=True, cudnn_tune=None,
                  cudnn_off=False, layout=None):
    """Transposed conv as an input-dilated conv (XLA-native formulation)."""
    if layout in _CHANNELS_LAST:
        raise NotImplementedError(
            "Deconvolution supports channel-first layouts only; transpose "
            "the data or use the default NCHW layout")
    nd = _conv_dims(kernel)
    stride = _tup(stride, nd, 1)
    dilate = _tup(dilate, nd, 1)
    pad = _tup(pad, nd, 0)
    adj = _tup(adj, nd, 0)
    kernel = _tup(kernel, nd, 1)
    # reference weight layout: (C_in, num_filter//num_group, *kernel)
    g = num_group
    cin, cog = weight.shape[0], weight.shape[1]
    w = weight.reshape((g, cin // g, cog) + weight.shape[2:])
    w = jnp.swapaxes(w, 1, 2)  # (g, cog, cin//g, *k)
    w = w.reshape((g * cog, cin // g) + weight.shape[2:])
    w = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
    dk = [d * (k - 1) for d, k in zip(dilate, kernel)]
    padding = [(dk_i - p, dk_i - p + a)
               for dk_i, p, a in zip(dk, pad, adj)]
    dn = lax.conv_dimension_numbers(data.shape, w.shape, _dimnums(nd))
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd, padding=padding,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=g)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling (parity: src/operator/nn/pooling-inl.h, pool.h)
# ---------------------------------------------------------------------------


@register("Pooling", aliases=("Pooling_v1",))
def Pooling(data, kernel=(), pool_type="max", global_pool=False,
            cudnn_off=False, pooling_convention="valid", stride=(), pad=(),
            p_value=2, count_include_pad=True, layout=None):
    nd = data.ndim - 2
    channels_last = layout in _CHANNELS_LAST
    sp0 = 1 if channels_last else 2  # first spatial axis
    if global_pool:
        ax = tuple(range(sp0, sp0 + nd))
        if pool_type == "max":
            return jnp.max(data, axis=ax, keepdims=True)
        if pool_type in ("avg", "sum"):
            red = jnp.sum(data, axis=ax, keepdims=True)
            n = float(np.prod([data.shape[a] for a in ax]))
            return red / n if pool_type == "avg" else red
        if pool_type == "lp":
            return jnp.power(jnp.sum(jnp.power(jnp.abs(data), p_value),
                                     axis=ax, keepdims=True), 1.0 / p_value)
    kernel = _tup(kernel, nd, 1)
    stride = _tup(stride, nd, 1)
    pad = _tup(pad, nd, 0)
    if channels_last:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        spatial_pad = [(p, p) for p in pad]
        base_pad = [(0, 0)] + spatial_pad + [(0, 0)]
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        base_pad = [(0, 0), (0, 0)] + [(p, p) for p in pad]
    if pooling_convention == "full":
        # ceil-mode: add extra right/bottom padding so the last window fits
        extra = []
        for i in range(nd):
            size = data.shape[sp0 + i] + 2 * pad[i]
            out = int(np.ceil((size - kernel[i]) / stride[i])) + 1
            need = (out - 1) * stride[i] + kernel[i] - size
            extra.append(max(0, need))
        sp = [(p, p + e) for p, e in zip(pad, extra)]
        base_pad = ([(0, 0)] + sp + [(0, 0)]) if channels_last else \
            ([(0, 0), (0, 0)] + sp)
    # NB: init values must be Python scalars so JAX recognizes the max/add
    # monoid and dispatches to the differentiable reduce_window variants
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) \
            else int(jnp.iinfo(data.dtype).min)
        return lax.reduce_window(data, init, lax.max,
                                 window, strides, base_pad)
    if pool_type in ("avg", "sum"):
        summed = lax.reduce_window(data, 0.0, lax.add,
                                   window, strides, base_pad)
        if pool_type == "sum":
            return summed
        if count_include_pad:
            return summed / float(np.prod(kernel))
        ones = jnp.ones(data.shape, dtype=data.dtype)
        counts = lax.reduce_window(ones, 0.0, lax.add,
                                   window, strides, base_pad)
        return summed / jnp.maximum(counts, 1)
    if pool_type == "lp":
        summed = lax.reduce_window(jnp.power(jnp.abs(data), p_value),
                                   0.0, lax.add,
                                   window, strides, base_pad)
        return jnp.power(summed, 1.0 / p_value)
    raise ValueError("unknown pool_type %s" % pool_type)


# ---------------------------------------------------------------------------
# normalization (parity: batch_norm-inl.h, layer_norm-inl.h,
# instance_norm-inl.h, lrn-inl.h)
# ---------------------------------------------------------------------------


@register("BatchNorm", num_outputs=3, aliases=("BatchNorm_v1",))
def BatchNorm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
              momentum=0.9, fix_gamma=True, use_global_stats=False,
              output_mean_var=False, axis=1, cudnn_off=False):
    """Returns (out, batch_mean, batch_var); the framework threads moving-stat
    updates functionally (see gluon.nn.BatchNorm) instead of the reference's
    in-kernel aux mutation (src/operator/nn/batch_norm-inl.h)."""
    from .. import autograd
    red_ax = tuple(a for a in range(data.ndim) if a != axis % data.ndim)
    shape = [1] * data.ndim
    shape[axis % data.ndim] = data.shape[axis % data.ndim]
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    training = autograd.is_training() and not use_global_stats
    if training:
        # MXNET_FUSED_BN_EPILOGUE=1: hand-fused Pallas kernels (one-pass
        # stats + normalize in two HBM sweeps, custom VJP) — the bytes/step
        # lever for the bandwidth-bound train step. Ineligible
        # shapes/layouts keep the XLA path below.
        from . import pallas_fused as _pf
        if _pf.fuse_enabled() and _pf.fuse_eligible(data, axis):
            out, mean, var = _pf.fused_bn_act(data, g, beta, eps=eps)
            mean = ad_checkpoint.checkpoint_name(mean, "bn_stats")
            var = ad_checkpoint.checkpoint_name(var, "bn_stats")
            return out, mean.astype(gamma.dtype), var.astype(gamma.dtype)
        # one-pass statistics, >=f32 accumulation: E[x] and E[x^2] reduce in
        # a single fused read of the activation (jnp.var would re-read it
        # after the mean lands — an extra full HBM pass per BN under bf16
        # training); f64 inputs keep f64 stats
        xf = data.astype(jnp.promote_types(data.dtype, jnp.float32))
        mean = jnp.mean(xf, axis=red_ax)
        var = jnp.maximum(
            jnp.mean(jnp.square(xf), axis=red_ax) - jnp.square(mean), 0.0)
        # remat-policy tag: stats are tiny (C,) but cost a full activation
        # read to recompute — always worth saving under the "io" policy
        mean = ad_checkpoint.checkpoint_name(mean, "bn_stats")
        var = ad_checkpoint.checkpoint_name(var, "bn_stats")
    else:
        mean, var = moving_mean, moving_var
    mean_b = lax.stop_gradient(mean) if not training else mean
    var_b = lax.stop_gradient(var) if not training else var
    # fold into one per-channel affine in >=f32, apply in the data's dtype
    sdt = jnp.promote_types(data.dtype, jnp.float32)
    inv = lax.rsqrt(var_b.astype(sdt) + eps)
    scale = g.astype(sdt) * inv
    offset = beta.astype(sdt) - mean_b.astype(sdt) * scale
    out = (data * scale.reshape(shape).astype(data.dtype)
           + offset.reshape(shape).astype(data.dtype))
    return out, mean.astype(gamma.dtype), var.astype(gamma.dtype)


@register("_contrib_BatchNormAddRelu", num_outputs=3,
          aliases=("BatchNormAddRelu",))
def BatchNormAddRelu(data, gamma, beta, moving_mean, moving_var, addend=None,
                     eps=1e-3, momentum=0.9, fix_gamma=True,
                     use_global_stats=False, axis=1, act_type="relu"):
    """act(BN(data) + addend): the BN epilogue of a residual block as ONE
    op (parity: the reference's contrib BatchNormAddRelu fused kernel).

    Returns (out, batch_mean, batch_var) like BatchNorm. With
    MXNET_FUSED_BN_EPILOGUE=1 the training-mode chain runs as the Pallas
    fused kernels (ops/pallas_fused.py) — each activation read once,
    written once, forward and backward; otherwise (or for ineligible
    shapes / eval mode) it composes the same math from the XLA ops, so the
    op is always available and the env flag only switches implementation.
    `addend` is optional (keyword tensor): without it the op is a fused
    BN+activation. act_type: "relu" or None.
    """
    from .. import autograd
    if act_type not in (None, "None", "relu"):
        raise ValueError("BatchNormAddRelu supports act_type 'relu' or "
                         "None, got %r" % (act_type,))
    relu = act_type == "relu"
    training = autograd.is_training() and not use_global_stats
    if training:
        from . import pallas_fused as _pf
        if _pf.fuse_enabled() and _pf.fuse_eligible(data, axis) and \
                (addend is None or addend.shape == data.shape):
            g = jnp.ones_like(gamma) if fix_gamma else gamma
            out, mean, var = _pf.fused_bn_act(
                data, g, beta, eps=eps, act="relu" if relu else None,
                residual=addend)
            mean = ad_checkpoint.checkpoint_name(mean, "bn_stats")
            var = ad_checkpoint.checkpoint_name(var, "bn_stats")
            return (out, mean.astype(gamma.dtype),
                    var.astype(gamma.dtype))
    out, mean, var = BatchNorm(data, gamma, beta, moving_mean, moving_var,
                               eps=eps, momentum=momentum,
                               fix_gamma=fix_gamma,
                               use_global_stats=use_global_stats, axis=axis)
    if addend is not None:
        out = out + addend.astype(out.dtype)
    if relu:
        out = jax.nn.relu(out)
    return out, mean, var


@register("LayerNorm")
def LayerNorm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    out = (data - mean) * lax.rsqrt(var + eps)
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register("InstanceNorm")
def InstanceNorm(data, gamma, beta, eps=1e-3):
    ax = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.var(data, axis=ax, keepdims=True)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    out = (data - mean) * lax.rsqrt(var + eps)
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register("LRN")
def LRN(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, [(0, 0), (half, half), (0, 0), (0, 0)])
    windows = sum(padded[:, i:i + data.shape[1]] for i in range(nsize))
    return data / jnp.power(knorm + (alpha / nsize) * windows, beta)


# ---------------------------------------------------------------------------
# Dropout (parity: src/operator/nn/dropout-inl.h)
# ---------------------------------------------------------------------------


@register("Dropout", stochastic=True)
def Dropout(data, p=0.5, mode="training", axes=()):
    from .. import autograd
    if mode != "always" and not autograd.is_training():
        return data
    if p <= 0.0:
        return data
    keep = 1.0 - p
    shape = list(data.shape)
    for a in axes:
        shape[a] = 1
    mask = jax.random.bernoulli(next_key(), keep, tuple(shape))
    return jnp.where(mask, data / keep, jnp.zeros((), dtype=data.dtype))


# ---------------------------------------------------------------------------
# UpSampling / resize (parity: upsampling-inl.h, bilinear_resize,
# adaptive_avg_pool from contrib)
# ---------------------------------------------------------------------------


@register("UpSampling")
def UpSampling(*data, scale=1, sample_type="nearest", num_filter=0,
               multi_input_mode="concat", num_args=1, workspace=512):
    outs = []
    for d in data:
        n, c, h, w = d.shape
        if sample_type == "nearest":
            o = jnp.repeat(jnp.repeat(d, scale, axis=2), scale, axis=3)
        else:
            o = jax.image.resize(d, (n, c, h * scale, w * scale), method="bilinear")
        outs.append(o)
    if len(outs) == 1:
        return outs[0]
    maxh = max(o.shape[2] for o in outs)
    maxw = max(o.shape[3] for o in outs)
    outs = [jax.image.resize(o, o.shape[:2] + (maxh, maxw), method="nearest")
            if o.shape[2:] != (maxh, maxw) else o for o in outs]
    if multi_input_mode == "sum":
        return sum(outs)
    return jnp.concatenate(outs, axis=1)


@register("_contrib_BilinearResize2D")
def BilinearResize2D(data, height=1, width=1, scale_height=None, scale_width=None):
    n, c, h, w = data.shape
    if scale_height is not None:
        height, width = int(h * scale_height), int(w * scale_width)
    return jax.image.resize(data, (n, c, int(height), int(width)), method="bilinear")


@register("_contrib_AdaptiveAvgPooling2D")
def AdaptiveAvgPooling2D(data, output_size=()):
    if not output_size:
        oh = ow = 1
    elif np.isscalar(output_size):
        oh = ow = int(output_size)
    else:
        oh, ow = (int(x) for x in output_size)
    n, c, h, w = data.shape
    x = data.reshape(n, c, oh, h // oh, ow, w // ow)
    return jnp.mean(x, axis=(3, 5))


# ---------------------------------------------------------------------------
# fused RNN (parity: src/operator/rnn-inl.h:49 + cudnn_rnn-inl.h — the
# multi-layer/bidirectional fused kernel, here a lax.scan the XLA way)
# ---------------------------------------------------------------------------

def _gates(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


def rnn_param_size(num_layers, input_size, state_size, bidirectional, mode):
    ngates = _gates(mode)
    dirs = 2 if bidirectional else 1
    size = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * dirs
        size += dirs * ngates * state_size * (in_sz + state_size + 2)
    return size


def _unpack_rnn_params(params, num_layers, input_size, state_size,
                       bidirectional, mode):
    """Slice the flat parameter vector into per-layer/direction weights.

    Layout (ours, documented for checkpoints): for each layer, for each
    direction: W_i2h (G*H, in), W_h2h (G*H, H), b_i2h (G*H), b_h2h (G*H).
    """
    ngates = _gates(mode)
    dirs = 2 if bidirectional else 1
    out = []
    off = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * dirs
        layer_params = []
        for _ in range(dirs):
            gh = ngates * state_size
            wi = params[off:off + gh * in_sz].reshape(gh, in_sz); off += gh * in_sz
            wh = params[off:off + gh * state_size].reshape(gh, state_size); off += gh * state_size
            bi = params[off:off + gh]; off += gh
            bh = params[off:off + gh]; off += gh
            layer_params.append((wi, wh, bi, bh))
        out.append(layer_params)
    return out


def _cell_step(mode, px, h, c, wh, bh):
    """One recurrence step from a PRE-PROJECTED input px (= x @ wi.T plus
    the input-side bias, computed for the whole sequence outside the scan
    — see _scan_layer). Only the small h @ wh.T matmul runs inside the
    sequential scan."""
    if mode in ("rnn_relu", "rnn_tanh"):
        pre = px + h @ wh.T
        h2 = jax.nn.relu(pre) if mode == "rnn_relu" else jnp.tanh(pre)
        return h2, c
    if mode == "lstm":
        pre = px + h @ wh.T
        i, f, g, o = jnp.split(pre, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c2 = f * c + i * g
        return o * jnp.tanh(c2), c2
    if mode == "gru":
        gh = h @ wh.T + bh
        ir, iz, inn = jnp.split(px, 3, axis=-1)
        hr, hz, hn = jnp.split(gh, 3, axis=-1)
        r = jax.nn.sigmoid(ir + hr)
        z = jax.nn.sigmoid(iz + hz)
        n = jnp.tanh(inn + r * hn)
        return (1 - z) * n + z * h, c
    raise ValueError(mode)


def _scan_layer(mode, xs, h0, c0, wi, wh, bi, bh, reverse=False,
                fused=None):
    """One (direction of one) RNN layer over [T, N, C].

    The input projection for ALL timesteps is hoisted out of the scan as
    one (T*N, C) @ (C, G*H) matmul — the cuDNN fused-RNN trick
    (reference src/operator/cudnn_rnn-inl.h): at word-LM shapes the
    per-step x @ wi.T is a tiny latency-bound matmul repeated T times;
    batched it runs at MXU efficiency, and the sequential scan carries
    only the irreducible h @ wh.T recurrence.

    With `MXNET_FUSED_RNN=1` (or `RNN(..., fused=True)`) and a
    Mosaic-tileable shape, that remaining recurrence runs as ONE
    persistent Pallas kernel per sequence (ops/pallas_rnn.py) — weights
    VMEM-resident, h/c carried in VMEM scratch — instead of T XLA
    while-loop iterations; ineligible shapes and gru keep this scan,
    which stays the parity oracle either way (the flag switches the
    kernel, never the semantics)."""
    T, N = xs.shape[0], xs.shape[1]
    # input-side bias folds into the hoisted projection; for gru the
    # hidden-side bias stays inside (it feeds the reset gate product)
    bias = bi if mode == "gru" else bi + bh
    pxs = (xs.reshape(T * N, -1) @ wi.T + bias).reshape(T, N, -1)

    from . import pallas_rnn
    if pallas_rnn.use_fused(fused) and pallas_rnn.fused_eligible(
            mode, T, N, h0.shape[-1], pxs.dtype, wh.dtype, h0.dtype):
        return pallas_rnn.fused_scan_layer(mode, pxs, h0, c0, wh,
                                           reverse=reverse)

    def step(carry, px):
        h, c = carry
        h2, c2 = _cell_step(mode, px, h, c, wh, bh)
        return (h2, c2), h2
    (hT, cT), ys = lax.scan(step, (h0, c0), pxs, reverse=reverse)
    return ys, hT, cT


@register("RNN", num_outputs=-1, stochastic=True)
def RNN(data, parameters, state, state_cell=None, state_size=0, num_layers=1,
        mode="lstm", bidirectional=False, p=0.0, state_outputs=False,
        projection_size=None, lstm_state_clip_min=None,
        lstm_state_clip_max=None, lstm_state_clip_nan=False, fused=None):
    """Fused multi-layer (bi)RNN over time-major [T, N, C] input.

    `fused`: None (default) = honor MXNET_FUSED_RNN; True/False force the
    persistent Pallas scan kernel on/off per call (ops/pallas_rnn.py).
    Either way ineligible shapes fall back to the lax.scan path — the
    knob selects a kernel, never different semantics."""
    from .. import autograd
    T, N, C = data.shape
    dirs = 2 if bidirectional else 1
    layers = _unpack_rnn_params(parameters, num_layers, C, state_size,
                               bidirectional, mode)
    h0 = state  # [L*dirs, N, H]
    c0 = state_cell if state_cell is not None else jnp.zeros_like(state)
    xs = data
    hTs, cTs = [], []
    for li, layer_params in enumerate(layers):
        outs = []
        for di in range(dirs):
            wi, wh, bi, bh = layer_params[di]
            idx = li * dirs + di
            ys, hT, cT = _scan_layer(mode, xs, h0[idx], c0[idx], wi, wh, bi, bh,
                                     reverse=(di == 1), fused=fused)
            outs.append(ys)
            hTs.append(hT)
            cTs.append(cT)
        xs = outs[0] if dirs == 1 else jnp.concatenate(outs, axis=-1)
        if p > 0.0 and li < num_layers - 1 and autograd.is_training():
            keep = 1.0 - p
            mask = jax.random.bernoulli(next_key(), keep, xs.shape)
            xs = jnp.where(mask, xs / keep, 0.0)
    out = xs
    hT = jnp.stack(hTs)
    if state_outputs:
        if mode == "lstm":
            return out, hT, jnp.stack(cTs)
        return out, hT
    return out


# ---------------------------------------------------------------------------
# spatial transform ops (parity: grid_generator-inl.h,
# bilinear_sampler-inl.h, spatial_transformer-inl.h, roi_pooling-inl.h)
# ---------------------------------------------------------------------------


@register("GridGenerator")
def GridGenerator(data, transform_type="affine", target_shape=(0, 0)):
    H, W = int(target_shape[0]), int(target_shape[1])
    if transform_type == "affine":
        n = data.shape[0]
        theta = data.reshape(n, 2, 3)
        ys = jnp.linspace(-1, 1, H)
        xs = jnp.linspace(-1, 1, W)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        ones = jnp.ones_like(gx)
        grid = jnp.stack([gx.ravel(), gy.ravel(), ones.ravel()])  # [3, H*W]
        out = jnp.einsum("nij,jk->nik", theta, grid)  # [n, 2, H*W]
        return out.reshape(n, 2, H, W)
    return data  # "warp": data is already a flow field


def _bilinear_sample_nchw(data, grid):
    """grid: [N,2,H,W] in [-1,1]; returns [N,C,H,W]."""
    N, C, Hi, Wi = data.shape
    gx = (grid[:, 0] + 1.0) * (Wi - 1) / 2.0
    gy = (grid[:, 1] + 1.0) * (Hi - 1) / 2.0
    x0 = jnp.floor(gx); y0 = jnp.floor(gy)
    x1 = x0 + 1; y1 = y0 + 1
    wx1 = gx - x0; wy1 = gy - y0
    wx0 = 1.0 - wx1; wy0 = 1.0 - wy1

    def gather(yy, xx):
        yi = jnp.clip(yy.astype(jnp.int32), 0, Hi - 1)
        xi = jnp.clip(xx.astype(jnp.int32), 0, Wi - 1)
        batch = jnp.arange(N).reshape(N, 1, 1)
        return data[batch, :, yi, xi].transpose(0, 3, 1, 2)

    def inb(yy, xx):
        return ((yy >= 0) & (yy <= Hi - 1) & (xx >= 0) & (xx <= Wi - 1))

    out = (gather(y0, x0) * (wy0 * wx0 * inb(y0, x0))[:, None] +
           gather(y0, x1) * (wy0 * wx1 * inb(y0, x1))[:, None] +
           gather(y1, x0) * (wy1 * wx0 * inb(y1, x0))[:, None] +
           gather(y1, x1) * (wy1 * wx1 * inb(y1, x1))[:, None])
    return out


@register("BilinearSampler")
def BilinearSampler(data, grid, cudnn_off=False):
    return _bilinear_sample_nchw(data, grid)


@register("SpatialTransformer")
def SpatialTransformer(data, loc, target_shape=(0, 0),
                       transform_type="affine", sampler_type="bilinear",
                       cudnn_off=False):
    grid = GridGenerator(loc, transform_type="affine", target_shape=target_shape)
    return _bilinear_sample_nchw(data, grid)


@register("ROIPooling")
def ROIPooling(data, rois, pooled_size=(1, 1), spatial_scale=1.0):
    """rois: [R, 5] (batch_idx, x1, y1, x2, y2). Static-shape friendly impl."""
    PH, PW = int(pooled_size[0]), int(pooled_size[1])
    N, C, H, W = data.shape

    def pool_one(roi):
        b = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1] * spatial_scale).astype(jnp.int32)
        y1 = jnp.round(roi[2] * spatial_scale).astype(jnp.int32)
        x2 = jnp.round(roi[3] * spatial_scale).astype(jnp.int32)
        y2 = jnp.round(roi[4] * spatial_scale).astype(jnp.int32)
        rh = jnp.maximum(y2 - y1 + 1, 1).astype(jnp.float32)
        rw = jnp.maximum(x2 - x1 + 1, 1).astype(jnp.float32)
        img = data[b]  # [C, H, W]
        ys = jnp.arange(H)
        xs = jnp.arange(W)
        py = jnp.clip(((ys - y1).astype(jnp.float32) * PH / rh), 0, PH - 1).astype(jnp.int32)
        px = jnp.clip(((xs - x1).astype(jnp.float32) * PW / rw), 0, PW - 1).astype(jnp.int32)
        valid_y = (ys >= y1) & (ys <= y2)
        valid_x = (xs >= x1) & (xs <= x2)
        mask = (valid_y[:, None] & valid_x[None, :])
        neg = jnp.full((C, H, W), -jnp.inf, dtype=data.dtype)
        src = jnp.where(mask[None], img, neg)
        cell = py[:, None] * PW + px[None, :]  # [H, W]
        flat = src.reshape(C, H * W)
        seg = cell.reshape(H * W)
        out = jnp.full((C, PH * PW), -jnp.inf, dtype=data.dtype)
        out = out.at[:, seg].max(flat)
        out = jnp.where(jnp.isfinite(out), out, 0.0)
        return out.reshape(C, PH, PW)

    return jax.vmap(pool_one)(rois)


# ---------------------------------------------------------------------------
# correlation (parity: src/operator/correlation-inl.h) — simplified dense impl
# ---------------------------------------------------------------------------


@register("Correlation")
def Correlation(data1, data2, kernel_size=1, max_displacement=1, stride1=1,
                stride2=1, pad_size=0, is_multiply=True):
    d = max_displacement
    N, C, H, W = data1.shape
    p1 = jnp.pad(data1, [(0, 0), (0, 0), (pad_size, pad_size), (pad_size, pad_size)])
    p2 = jnp.pad(data2, [(0, 0), (0, 0), (pad_size, pad_size), (pad_size, pad_size)])
    outs = []
    for dy in range(-d, d + 1, stride2):
        for dx in range(-d, d + 1, stride2):
            shifted = jnp.roll(p2, (-dy, -dx), axis=(2, 3))
            if is_multiply:
                corr = jnp.mean(p1 * shifted, axis=1)
            else:
                corr = jnp.mean(jnp.abs(p1 - shifted), axis=1)
            outs.append(corr)
    out = jnp.stack(outs, axis=1)
    if pad_size:
        out = out[:, :, pad_size:-pad_size, pad_size:-pad_size]
    return out[:, :, ::stride1, ::stride1]


# ---------------------------------------------------------------------------
# legacy Crop + sparse-regularization identity + image_random ops
# ---------------------------------------------------------------------------


@register("Crop")
def Crop(*inputs, offset=(0, 0), h_w=(0, 0), center_crop=False,
         num_args=1):
    """Legacy spatial crop (parity: src/operator/crop.cc). With two inputs
    the second (crop_like) donates the target H,W; otherwise h_w does.
    offset is (y, x); center_crop centers the window instead."""
    data = inputs[0]
    if len(inputs) > 1:
        th, tw = inputs[1].shape[2], inputs[1].shape[3]
    else:
        th, tw = int(h_w[0]), int(h_w[1])
    H, W = data.shape[2], data.shape[3]
    if center_crop:
        y0, x0 = (H - th) // 2, (W - tw) // 2
    else:
        y0, x0 = int(offset[0]), int(offset[1])
    if y0 < 0 or x0 < 0 or y0 + th > H or x0 + tw > W:
        raise ValueError(
            "Crop window [%d:%d, %d:%d] exceeds input %dx%d"
            % (y0, y0 + th, x0, x0 + tw, H, W))
    return data[:, :, y0:y0 + th, x0:x0 + tw]


@jax.custom_vjp
def _kl_sparse_identity(data, sparseness_target, penalty):
    return data


def _kl_sparse_fwd(data, sparseness_target, penalty):
    return data, (data, sparseness_target, penalty)


def _kl_sparse_bwd(res, g):
    data, target, penalty = res
    # rho_hat: mean activation per hidden unit over the batch (the
    # reference keeps a momentum moving average in an aux state; the
    # batch estimate is its momentum=0 case)
    rho = jnp.clip(jnp.mean(data, axis=0, keepdims=True), 1e-6, 1 - 1e-6)
    kl_grad = penalty * (-target / rho + (1.0 - target) / (1.0 - rho))
    return (g + jnp.broadcast_to(kl_grad, g.shape), None, None)


_kl_sparse_identity.defvjp(_kl_sparse_fwd, _kl_sparse_bwd)


@register("IdentityAttachKLSparseReg")
def IdentityAttachKLSparseReg(data, sparseness_target=0.1, penalty=0.001,
                              momentum=0.9):
    """Identity forward; backward adds the KL sparsity-penalty gradient
    (parity: src/operator/identity_attach_KL_sparse_reg.cc — sparse
    autoencoder regularization on sigmoid activations)."""
    return _kl_sparse_identity(data, float(sparseness_target), float(penalty))


@register("_image_to_tensor")
def _image_to_tensor(data):
    """HWC (or NHWC) uint8 [0,255] -> CHW (NCHW) float32 [0,1]
    (parity: src/operator/image/image_random.cc ToTensor)."""
    out = data.astype(jnp.float32) / 255.0
    if data.ndim == 3:
        return jnp.transpose(out, (2, 0, 1))
    return jnp.transpose(out, (0, 3, 1, 2))


@register("_image_normalize")
def _image_normalize(data, mean=(0.0,), std=(1.0,)):
    """Channel-wise (x - mean) / std on CHW/NCHW float images
    (parity: src/operator/image/image_random.cc Normalize)."""
    mean = jnp.asarray(mean, dtype=data.dtype)
    std = jnp.asarray(std, dtype=data.dtype)
    shape = (-1, 1, 1) if data.ndim == 3 else (1, -1, 1, 1)
    return (data - mean.reshape(shape)) / std.reshape(shape)
