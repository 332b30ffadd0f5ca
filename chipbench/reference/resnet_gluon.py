"""Plain reference for the `resnet_gluon` family: ResNet v1 with bottleneck
blocks (He et al., arXiv:1512.03385, table 1) as MXNet Gluon's model zoo
builds it, its softmax cross-entropy loss, its gradients, and SGD with
momentum, in float32 `jax.numpy` / `lax` with precision "highest". Nothing
is imported from the program.

What "as Gluon builds it" fixes (`gluon/model_zoo/vision/resnet.py` of the
reference MXNet, `BottleneckV1`): the stride of a stage's first block sits
on its first 1x1 convolution; that convolution and the last 1x1 carry a
bias (a bias in front of a batch norm has a gradient of zero up to
rounding, which is why norms are compared against the median leaf's);
the 3x3 and the projection shortcut do not. Batch norm runs in training
mode: statistics of the batch, biased variance, eps 1e-5. Max pooling 3x3,
stride 2, padding 1. The loss is the mean over the batch of
-log softmax(logits)[label]. SGD as MXNet's `sgd` with momentum:
    g' = g + wd * w;  m = momentum * m - lr * g';  w = w + m
with weight decay on every trained leaf (Gluon's default multipliers).

Leaves come as an ordered list in the order `leaf_spec` gives, which is the
order Gluon collects them in; running statistics are not leaves here, the
training-mode forward never reads them.

`mantissa_bits=3` is the control of "How correct is decided", the step
below bfloat16 compute: every tensor that the program keeps in bfloat16 (8
significant bits: the weights' compute copy, the images, the output of every
convolution, batch norm, ReLU and residual add, and the gradient of each on
the way back) is rounded here to fp8-e4m3's 4 significant bits instead,
range not clipped (an ideal per-tensor scale). Master weights, the loss and
the optimizer stay in float32, as they do in the program.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
BN_EPS = 1e-5


def leaf_spec(config):
    """[(name, shape)] of the trained leaves in Gluon's order."""
    layers, channels = config["layers"], config["channels"]
    spec = [("stem.conv.w", (channels[0], 3, 7, 7)),
            ("stem.bn.g", (channels[0],)), ("stem.bn.b", (channels[0],))]
    cin = channels[0]
    for s, (n, cout) in enumerate(zip(layers, channels[1:])):
        mid = cout // 4
        for b in range(n):
            p = "s%d.b%d." % (s + 1, b)
            spec += [(p + "conv1.w", (mid, cin, 1, 1)), (p + "conv1.bias", (mid,)),
                     (p + "bn1.g", (mid,)), (p + "bn1.b", (mid,)),
                     (p + "conv2.w", (mid, mid, 3, 3)),
                     (p + "bn2.g", (mid,)), (p + "bn2.b", (mid,)),
                     (p + "conv3.w", (cout, mid, 1, 1)), (p + "conv3.bias", (cout,)),
                     (p + "bn3.g", (cout,)), (p + "bn3.b", (cout,))]
            if b == 0 and cin != cout:
                spec += [(p + "down.w", (cout, cin, 1, 1)),
                         (p + "down.bn.g", (cout,)), (p + "down.bn.b", (cout,))]
            cin = cout
    spec += [("fc.w", (config["classes"], cin)), ("fc.bias", (config["classes"],))]
    return spec


def _round_float(x, bits):
    """x rounded to a float of `bits` explicit mantissa bits."""
    m, e = jnp.frexp(x)
    scale = float(2 ** (bits + 1))
    return jnp.ldexp(jnp.round(m * scale) / scale, e)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rounded(x, bits):
    return _round_float(x, bits)


def _rounded_fwd(x, bits):
    return _round_float(x, bits), None


def _rounded_bwd(bits, _, g):
    return (_round_float(g, bits),)


_rounded.defvjp(_rounded_fwd, _rounded_bwd)


def _keep(x, bits):
    """A tensor as the compute dtype keeps it: float32 here (`bits` None), or
    rounded to `bits` mantissa bits, its gradient too."""
    return x if bits is None else _rounded(x, bits)


def _conv(x, w, stride, pad, bits):
    return _keep(lax.conv_general_dilated(
        _keep(x, bits), _keep(w, bits), (stride, stride),
        [(pad, pad), (pad, pad)], dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=HIGHEST), bits)


def _bn(x, g, b, bits=None):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    return _keep((x - mean) * lax.rsqrt(var + BN_EPS)
                 * _keep(g, bits)[None, :, None, None]
                 + _keep(b, bits)[None, :, None, None], bits)


def _bias(x, b, bits=None):
    return _keep(x + _keep(b, bits)[None, :, None, None], bits)


def _relu(x, bits):
    return _keep(jax.nn.relu(x), bits)


def _block(x, leaves, stride, bits):
    w1, b1, g1, be1, w2, g2, be2, w3, b3, g3, be3 = leaves[:11]
    y = _relu(_bn(_bias(_conv(x, w1, stride, 0, bits), b1, bits), g1, be1, bits),
              bits)
    y = _relu(_bn(_conv(y, w2, 1, 1, bits), g2, be2, bits), bits)
    y = _bn(_bias(_conv(y, w3, 1, 0, bits), b3, bits), g3, be3, bits)
    if len(leaves) > 11:
        wd, gd, bd = leaves[11:]
        x = _bn(_conv(x, wd, stride, 0, bits), gd, bd, bits)
    return _relu(y + x, bits)


def loss_fn(leaves, x, y, config, mantissa_bits=None):
    """Mean softmax cross-entropy of the batch (x NCHW float32, y int)."""
    bits = mantissa_bits
    it = iter(leaves)
    take = lambda n: [next(it) for _ in range(n)]
    w, g, b = take(3)
    h = _relu(_bn(_conv(x, w, 2, 3, bits), g, b, bits), bits)
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    cin = config["channels"][0]
    for s, (n, cout) in enumerate(zip(config["layers"], config["channels"][1:])):
        for blk in range(n):
            down = blk == 0 and cin != cout
            stride = 2 if (blk == 0 and s > 0) else 1
            # one block's activations at a time in the backward pass, so the
            # float32 reference holds the timed batch beside the program
            h = jax.checkpoint(
                functools.partial(_block, stride=stride, bits=bits))(
                    h, take(14 if down else 11))
            cin = cout
    fw, fb = take(2)
    pooled = _keep(jnp.mean(h, axis=(2, 3)), bits)
    logits = jnp.matmul(pooled, _keep(fw, bits).T, precision=HIGHEST) \
        + _keep(fb, bits)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def _norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(t))) for t in tree])


@functools.partial(jax.jit, static_argnames=("config_key", "mantissa_bits"))
def _step(leaves, moms, x, y, lr, momentum, wd, config_key, mantissa_bits):
    config = dict(layers=list(config_key[0]), channels=list(config_key[1]),
                  classes=config_key[2])
    loss, grads = jax.value_and_grad(loss_fn)(leaves, x, y, config,
                                              mantissa_bits)
    moms = [momentum * m - lr * (g + wd * w)
            for m, g, w in zip(moms, grads, leaves)]
    return loss, _norms(grads), [w + m for w, m in zip(leaves, moms)], moms


@jax.jit
def _change(leaves, start):
    return _norms([w - a for w, a in zip(leaves, start)])


def follow(leaves, batches, config, optimizer, mantissa_bits=None):
    """Follow one SGD step per (x, y) of `batches` from `leaves`. Returns
    (losses (n,), norm of each leaf's first gradient (L,), norm of each
    leaf's change after the n steps (L,)), float32. One step is one
    compiled program, run n times."""
    key = (tuple(config["layers"]), tuple(config["channels"]),
           int(config["classes"]))
    start = [jnp.asarray(w, jnp.float32) for w in leaves]
    leaves, moms = start, [jnp.zeros_like(w) for w in start]
    losses, first = [], None
    for x, y in batches:
        loss, gnorms, leaves, moms = _step(
            leaves, moms, jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.int32),
            jnp.float32(optimizer["learning_rate"]),
            jnp.float32(optimizer["momentum"]), jnp.float32(optimizer["wd"]),
            config_key=key, mantissa_bits=mantissa_bits)
        losses.append(loss)
        first = gnorms if first is None else first
    return jnp.stack(losses), first, _change(leaves, start)


def leaf_gaps(program_norms, reference_norms):
    """The contract's comparison of per-leaf norms: for every leaf the gap
    between the program's norm and the reference's, against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    ref = jnp.asarray(reference_norms, jnp.float32)
    return jnp.abs(jnp.asarray(program_norms, jnp.float32) - ref) \
        / jnp.maximum(ref, jnp.median(ref))


def whole_gap(program_norms, reference_norms):
    """The same gap for the norm over all leaves together."""
    whole = lambda n: float(jnp.sqrt(jnp.sum(jnp.square(
        jnp.asarray(n, jnp.float32)))))
    return abs(whole(program_norms) - whole(reference_norms)) \
        / whole(reference_norms)
